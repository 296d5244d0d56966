"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Sets the cell up (weights and inputs from the seed, every shape warmed),
measures for ``--seconds``, checks what the window produced against the
plain reference, and prints one JSON line last on stdout: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device`` and, last, ``checks``:
each compared number beside its limit, which are also the last lines on
stderr. Exits non-zero, with no result, where JAX finds no TPU or fewer
chips than the cell asks for.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:] = [str(ROOT / "src"), str(ROOT)] + [p for p in sys.path
                                                if Path(p or ".").resolve() != ROOT / "bench"]


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def chips_or_exit(cell: dict) -> None:
    """Exit non-zero unless JAX sees a TPU with the chips the cell needs."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        sys.exit(f"bench: cell {cell['name']} needs {cell['chips']} TPU chip(s); JAX found "
                 f"{len(devices)} {devices[0].platform} device(s) ({devices[0].device_kind})")


def main(argv=None) -> None:
    args = parse(argv)
    from bench import harness
    spec = harness.load_spec()
    chips_or_exit(harness.cell_of(spec, args.workload))
    result, checks = harness.run_cell(args.workload, args.seed, args.seconds,
                                      bool(args.trace), t_start=T_START, spec=spec)
    harness.print_checks(checks)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
