"""Readings that the limits and the open-loop rate are set from; not run by
the benchmark's own runs.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 5
    python3 bench/calibrate.py --workload <cell> --seeds 1 --seconds 20 --rates 6,8,10

Without ``--rates``: for each seed, in this one process, the cell's
set-up, a short window at the cell's own load, and its check with the
control, printing one JSON line per seed with each compared number of the
program and of the control (the reference in the precision below the
configuration's), and whether the control fails the cell's limit. With ``--rates``: set-up once, then one window per
offered rate of an open-loop mix, printing the latency percentiles, the
rate completed and how the backlog grew over the window.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:] = [str(ROOT / "src"), str(ROOT)] + [p for p in sys.path
                                                if Path(p or ".").resolve() != ROOT / "bench"]


def quiet():
    """A recorder with no profiler."""
    from bench.harness import Recorder
    return Recorder(False, 0.0)


def readings(cell: dict, config: dict, traffic: dict, seeds, seconds: float) -> list:
    from bench import harness
    path = harness.path_module(config["path"])
    limits = {k: v["limit"] for k, v in harness.data("limits", cell["name"]).items()}
    out = []
    for seed in seeds:
        t0 = time.perf_counter()
        state = path.setup(config, traffic, seed)
        rec = quiet()
        units = path.serve(state, traffic, seconds, rec)
        t1 = time.perf_counter()
        row = dict(cell=cell["name"], seed=seed, units=len(units),
                   setup_s=rec.t_open - t0, window_s=t1 - rec.t_open,
                   **path.check(state, units, seed, control=True))
        row["check_s"] = time.perf_counter() - t1
        row["control_fails_limit"] = any(row[f"control.{n}"] > lim for n, lim in limits.items())
        print(json.dumps(row), flush=True)
        out.append(row)
    return out


def sweep(cell: dict, config: dict, traffic: dict, seed: int, seconds: float, rates) -> list:
    import numpy as np
    from bench import harness
    path = harness.path_module(config["path"])
    state = path.setup(config, traffic, seed)
    out = []
    for rate in rates:
        tr = dict(traffic, rate_per_s=rate)
        state.traffic = tr
        units = path.serve(state, tr, seconds, quiet())
        wait = np.array([u["end"] - u["due"] for u in units])
        half = len(wait) // 2
        row = dict(cell=cell["name"], rate_per_s=rate, requests=len(units),
                   completed_per_s=len(units) / (units[-1]["end"] - units[0]["due"]),
                   p50_ms=float(np.percentile(wait, 50) * 1e3),
                   p95_ms=float(np.percentile(wait, 95) * 1e3),
                   first_half_mean_ms=float(wait[:half].mean() * 1e3),
                   second_half_mean_ms=float(wait[half:].mean() * 1e3),
                   service_mean_ms=float(np.mean([u["end"] - u["start"] for u in units]) * 1e3))
        print(json.dumps(row), flush=True)
        out.append(row)
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", default="", help="comma-separated offered rates per second")
    args = ap.parse_args(argv)
    from bench import harness
    from bench.run import chips_or_exit
    spec = harness.load_spec()
    cell = harness.cell_of(spec, args.workload)
    chips_or_exit(cell)
    harness.enable_cache()
    config = harness.data("configs", cell["config"])
    traffic = harness.data("traffic", cell["traffic"])
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.rates:
        sweep(cell, config, traffic, seeds[0], args.seconds,
              [float(r) for r in args.rates.split(",")])
    else:
        readings(cell, config, traffic, seeds, args.seconds)


if __name__ == "__main__":
    main()
