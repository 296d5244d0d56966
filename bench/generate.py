"""The one traffic generator: inputs and arrival times from a mix's
parameters (``bench/traffic/<mix>.json``) and ``--seed``.

Every random stream is drawn from ``SeedSequence([seed, stream])``, so a
seed of any size gives the same inputs on every run. A Poisson mix sends
``round(rate_per_s * seconds)`` requests in every run: their arrival times
are a Poisson process conditioned on that count (the sorted uniform times
of the order statistics, drawn as normalised exponential gaps), so seeds
change the order and spacing of the work, not its amount. The gaps are
the exponential gaps of ``repro.core.traffic.PoissonArrivals``.
"""

from __future__ import annotations

import numpy as np

# stream ids: one independent random stream per use
ARRIVALS, INPUTS, WEIGHTS, SAMPLE = 1, 2, 3, 4


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def key_words(seed: int, stream: int) -> np.ndarray:
    """Two uint32 words for a raw JAX PRNG key (``jnp.asarray`` them)."""
    return np.random.SeedSequence([int(seed), stream]).generate_state(2, np.uint32)


def arrivals(traffic: dict, seconds: float, seed: int) -> np.ndarray:
    """Offsets in seconds from the window's start, sorted, for an open
    loop; an empty array for closed and offline mixes."""
    if traffic["kind"] != "poisson":
        return np.zeros(0)
    n = max(1, round(traffic["rate_per_s"] * seconds))
    t = np.cumsum(rng(seed, ARRIVALS).exponential(1.0, n + 1))
    return seconds * t[:n] / t[n]


def images(traffic: dict, config: dict, seed: int) -> np.ndarray:
    """(pool, batch, size, size, channels) float32 host images."""
    size, ch = config["image_size"], config["in_channels"]
    shape = (traffic["pool"], traffic["batch"], size, size, ch)
    return rng(seed, INPUTS).standard_normal(shape, dtype=np.float32)


def prompts(traffic: dict, config: dict, seed: int) -> np.ndarray:
    """(pool, requests_per_call, prompt_len) int32 token ids."""
    shape = (traffic["pool"], traffic["requests_per_call"], traffic["prompt_len"])
    return rng(seed, INPUTS).integers(0, config["vocab_size"], shape, dtype=np.int32)


def sample(n_units: int, k: int, seed: int) -> np.ndarray:
    """``k`` distinct unit indices out of ``n_units``, sorted."""
    k = min(k, n_units)
    return np.sort(rng(seed, SAMPLE).choice(n_units, size=k, replace=False))
