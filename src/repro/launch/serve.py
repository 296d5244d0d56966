"""Serving launcher: ``python -m repro.launch.serve --arch <id> [--full]``.

AMP4EC-scheduled batched serving with real greedy decode on the simulated
edge cluster: the reduced model by default, the published widths with
``--full`` (one accelerator chip; see chip_smoke.py). Prints the engine's
metrics in simulated edge time, then the time to first token, the gap
between tokens and the routing time measured from the program's spans, and
the share of prompt positions the engine prefilled rather than stepped.
The scripted adaptation demo is examples/serve_adaptive.py.
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import ModelConfig
from repro.core.cluster import make_paper_cluster
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import Model
from repro.serving import Request, ServingEngine
from repro.serving.engine import SIMULATED, measured_counts, measured_ms
from repro.utils import obs


def build_engine(cfg: ModelConfig, *, max_batch: int = 4,
                 seed: int = 0) -> ServingEngine:
    """Seeded weights for ``cfg`` behind a ServingEngine on the paper's
    three-node cluster."""
    params, _ = Model(cfg).init(jax.random.PRNGKey(seed))
    return ServingEngine(cfg, params, make_paper_cluster(), max_batch=max_batch)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen2.5-3b")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--full", action="store_true",
                    help="published widths (one accelerator chip); default reduced")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    engine = build_engine(cfg, max_batch=args.max_batch)
    reqs = [Request(i, np.arange(1, args.prompt_len + 1, dtype=np.int32),
                    args.new_tokens) for i in range(args.requests)]
    obs.enable()
    m = engine.serve(reqs)
    obs.disable()
    for k, v in m.items():
        if k != "scheduler":
            print(f"{k}{' (simulated edge time)' if k in SIMULATED else ''}: {v}")
    measured = measured_ms(obs.snapshot())
    share = measured.pop("prefill_share")
    for k, v in measured.items():
        print(f"{k} (measured on the host clock, compiles included): {v}")
    print(f"prefill_share (prompt positions taken by one prefill): {share}")
    counts = measured_counts(obs.snapshot())
    if counts["routed_here"] is not None:
        print("MoE counters (assignments computed by the held experts, most on one "
              "expert in one layer, dropped): "
              + ", ".join(f"{k} {v}" for k, v in counts.items()))


if __name__ == "__main__":
    main()
