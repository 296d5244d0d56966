#!/usr/bin/env python
"""Perf-regression gate for the pipeline engine.

Re-runs ``benchmarks/pipeline_bench.py`` in a reduced configuration (the
scale section shrunk to 20k requests; the Table-I, transfer-mode, and
open-loop sections are cheap and run at full size) and compares against the
committed ``BENCH_pipeline.json`` baseline:

* **Simulated metrics** (``table1`` + ``modes`` + ``openloop`` sections, the
  stage count of the scale plans, and the full ``multitenant`` section —
  per-tenant goodput, migrations, and the arbitration-beats-independent
  margin) must match the baseline exactly — the discrete-event simulation is
  bit-reproducible, so any difference is a timing-model or engine drift, not
  noise. A metric key present on one side only is also a failure: silently
  added (or dropped) columns would otherwise escape the gate until the next
  baseline refresh.
* **Wall-clock rate** (``sim_req_per_wall_s`` of the scale section) must
  stay at or above ``WALL_RATE_TOLERANCE`` × baseline — a wide band, because
  absolute wall time varies by machine; the gate catches order-of-magnitude
  hot-path regressions (e.g. reintroducing per-request O(layers) work),
  not scheduler jitter.

The comparison itself is the pure :func:`diff_results` — unit-tested in
``tests/test_check_perf.py`` (missing baseline, new metric keys, tolerance
boundary) without paying for a benchmark run.

Registered as the non-tier-1 ``perf`` pytest marker via
``tests/test_perf.py`` (the default suite deselects it; run with
``pytest -m perf``).

Run standalone:  PYTHONPATH=src python scripts/check_perf.py
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import sys
from typing import List

REPO = pathlib.Path(__file__).resolve().parents[1]
BASELINE_PATH = REPO / "BENCH_pipeline.json"
BENCH_PATH = REPO / "benchmarks" / "pipeline_bench.py"

#: reduced scale-section size for the gate (full bench uses 100k)
REDUCED_SCALE_REQUESTS = 20_000
#: current wall rate must be >= this fraction of the committed baseline
WALL_RATE_TOLERANCE = 0.25
#: sections whose rows are bit-reproducible and compared key-exactly
EXACT_SECTIONS = ("table1", "modes", "openloop", "batchcurve", "faultstorm",
                  "dagsweep")
#: scale-section fields that depend on stream length or wall clock — not
#: compared exactly (the wall rate has its own tolerance band above)
SCALE_VOLATILE_FIELDS = {"num_requests", "wall_s", "sim_req_per_wall_s",
                         "tail_throughput_rps", "sim_makespan_s"}
#: multitenant rows run at full size, so only the wall clock is volatile;
#: every simulated metric (per-tenant goodput, migrations, the
#: arbitration-beats-independent margin) is compared exactly
MT_VOLATILE_FIELDS = {"wall_s", "sim_req_per_wall_s"}
#: sections with wall-clock-volatile rows: {section: its volatile fields};
#: rows carrying ``sim_req_per_wall_s`` also get the wall-rate band
WALL_SECTIONS = {"scale": frozenset(SCALE_VOLATILE_FIELDS),
                 "multitenant": frozenset(MT_VOLATILE_FIELDS)}


def _load_bench():
    spec = importlib.util.spec_from_file_location("pipeline_bench",
                                                  BENCH_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _diff_row(section: str, brow: dict, crow: dict,
              volatile: frozenset, problems: List[str]) -> None:
    cfg = brow.get("config", "?")
    for k, v in brow.items():
        if k in volatile:
            continue
        if k not in crow:
            problems.append(f"{section}/{cfg}: metric {k} missing from "
                            f"current run (baseline {v!r})")
        elif crow[k] != v:
            problems.append(f"{section}/{cfg}: {k} = {crow[k]!r}, "
                            f"baseline {v!r} (simulated metric drifted)")
    for k in crow:
        if k not in brow and k not in volatile:
            problems.append(f"{section}/{cfg}: new metric key {k} = "
                            f"{crow[k]!r} not in baseline — refresh "
                            f"BENCH_pipeline.json")


def diff_results(baseline: dict, current: dict,
                 wall_rate_tolerance: float = WALL_RATE_TOLERANCE
                 ) -> List[str]:
    """Diff a current benchmark result against the committed baseline;
    returns one line per problem (empty list == clean). Pure — both inputs
    are the ``pipeline_bench.run()`` result shape, so edge cases (new
    keys, tolerance boundaries) are unit-testable without a bench run."""
    problems: List[str] = []

    for section in EXACT_SECTIONS + tuple(WALL_SECTIONS):
        if len(current.get(section, [])) != len(baseline.get(section, [])):
            problems.append(
                f"{section}: {len(current.get(section, []))} row(s), "
                f"baseline has {len(baseline.get(section, []))} — "
                f"configuration coverage changed")

    for section in EXACT_SECTIONS:
        for brow, crow in zip(baseline.get(section, []),
                              current.get(section, [])):
            _diff_row(section, brow, crow, frozenset(), problems)

    for section, volatile in WALL_SECTIONS.items():
        for brow, crow in zip(baseline.get(section, []),
                              current.get(section, [])):
            cfg = brow.get("config", "?")
            _diff_row(section, brow, crow, volatile, problems)
            if "sim_req_per_wall_s" not in brow:
                continue
            floor = brow["sim_req_per_wall_s"] * wall_rate_tolerance
            if crow["sim_req_per_wall_s"] < floor:
                problems.append(
                    f"{section}/{cfg}: {crow['sim_req_per_wall_s']:.0f} "
                    f"sim-req/wall-s < {floor:.0f} "
                    f"({wall_rate_tolerance:.0%} of baseline "
                    f"{brow['sim_req_per_wall_s']:.0f}) — "
                    f"hot-path regression")
    return problems


def check(baseline_path: pathlib.Path = BASELINE_PATH,
          scale_requests: int = REDUCED_SCALE_REQUESTS) -> List[str]:
    """Run the reduced benchmark and diff it against the committed
    baseline; returns one line per problem (empty list == clean)."""
    if not baseline_path.exists():
        return [f"missing baseline {baseline_path} — run "
                f"benchmarks/pipeline_bench.py to create it"]
    baseline = json.loads(baseline_path.read_text())
    # budget_s=None: wall-time enforcement here is the tolerance band
    # below, which *reports* on slow machines instead of crashing mid-bench
    current = _load_bench().run(scale_requests=scale_requests, write=False,
                                budget_s=None)
    return diff_results(baseline, current)


def main() -> int:
    problems = check()
    for line in problems:
        print(line)
    if problems:
        print(f"\n{len(problems)} perf-gate problem(s)", file=sys.stderr)
        return 1
    print("perf gate clean: simulated metrics match baseline, "
          "wall rate within band")
    return 0


if __name__ == "__main__":
    sys.exit(main())
