"""One run of one cell: set-up, a measured window, the check against the
plain reference, and the metrics, all found by name.

A cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix. The configuration file ``bench/configs/<config>.json`` names
the path that runs it, ``bench/paths/<path>.py``, which provides

- ``setup(config, traffic, seed) -> state``: builds the system under test
  with weights and inputs from the seed and warms up every shape the
  window uses;
- ``serve(state, traffic, seconds, rec) -> units``: drives the window and
  returns one dict per request or call (``start``, ``end`` and ``due`` on
  the host clock, ``items`` served, model ``flops``);
- ``check(state, units, seed, control=False) -> {number: value}``: frees
  the program's state and compares what the window produced with the
  plain reference in ``bench/ref/``.

Each metric is read by ``bench/metrics/<name>.py``, or by the file of the
name's part before its first dot, from a :class:`Run`. Each compared
number has its limit in ``bench/limits/<cell>.json``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import os
import re
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"

now = time.perf_counter


# ---------------------------------------------------------------------------
# files found by name
# ---------------------------------------------------------------------------

def load_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def data(kind: str, name: str) -> dict:
    """``bench/<kind>/<name>.json``."""
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def cell_of(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload named {name!r} in BENCHMARK.json")


def path_module(name: str):
    return importlib.import_module(f"bench.paths.{name}")


def reader(metric: str) -> Callable:
    """``read(run)`` of ``bench/metrics/<metric>.py``, else of the file
    named by the metric's part before its first dot."""
    for stem in (metric, metric.split(".")[0]):
        file = BENCH / "metrics" / f"{stem}.py"
        if file.exists():
            spec = importlib.util.spec_from_file_location(f"bench_metric_{stem}", file)
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            return module.read
    raise FileNotFoundError(f"no reader for metric {metric!r} under bench/metrics/")


def metrics_for(spec: dict, cell: str, trace: bool) -> List[dict]:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer ones."""
    e2e = [m for m in spec["end_to_end"] if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m else m["moves"] in names)]


# ---------------------------------------------------------------------------
# spans, the traced part of the window, compiles
# ---------------------------------------------------------------------------

class Recorder:
    """Host spans of the harness, on the host clock and, while the profiler
    runs, in its trace too (``jax.profiler.TraceAnnotation``).

    With ``trace`` on, the profiler covers the window from its start until
    the first unit that ends ``trace_s`` seconds or more after it.
    """

    def __init__(self, trace: bool, trace_s: float):
        import jax
        self._jax = jax
        self.trace, self.trace_s = trace, trace_s
        self.spans: List[Tuple[str, float, float, int]] = []
        self.unit = -1
        self.t_open = self.t_close = None
        self.traced: Optional[Tuple[float, float]] = None
        self._dir = self._window = None

    @contextlib.contextmanager
    def span(self, name: str):
        with self._jax.profiler.TraceAnnotation(name):
            t0 = now()
            yield
            self.spans.append((name, t0, now(), self.unit))

    def open_window(self) -> float:
        if self.trace:
            self._dir = tempfile.mkdtemp(prefix="bench_trace_")
            options = self._jax.profiler.ProfileOptions()
            options.python_tracer_level = 0     # Python call tracing slows the host threefold
            options.host_tracer_level = 1       # the harness's own spans
            options.enable_hlo_proto = False
            self._jax.profiler.start_trace(self._dir, profiler_options=options)
            self._window = self._jax.profiler.TraceAnnotation("bench.window")
            self._window.__enter__()
        self.t_open = now()
        return self.t_open

    def unit_done(self, t_end: float) -> None:
        if self._window is not None and t_end - self.t_open >= self.trace_s:
            self._stop()

    def close_window(self, t_end: float) -> None:
        self.t_close = t_end
        if self._window is not None:
            self._stop()

    def _stop(self) -> None:
        self._window.__exit__(None, None, None)
        self.traced = (self.t_open, now())
        self._jax.profiler.stop_trace()
        self._window = None

    def load_trace(self):
        if self._dir is None:
            return None
        from bench import trace
        try:
            return trace.load(self._dir)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)


# process-wide, as the jax.monitoring listeners are: they cannot be removed
_COMPILES = {"built": 0, "hits": 0}


def compiles() -> Tuple[int, int]:
    """Executables built so far in this process (compiled, or loaded from
    the persistent cache) and persistent-cache hits, from
    ``jax.monitoring``; the listeners are registered on the first call."""
    import jax
    if "listening" not in _COMPILES:
        _COMPILES["listening"] = True

        def on_duration(event, *args, **kwargs):
            if event == "/jax/core/compile/backend_compile_duration":
                _COMPILES["built"] += 1

        def on_event(event, *args, **kwargs):
            if event == "/jax/compilation_cache/cache_hits":
                _COMPILES["hits"] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
    return _COMPILES["built"], _COMPILES["hits"]


# ---------------------------------------------------------------------------
# what a metric reader sees
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    cell: dict
    config: dict
    traffic: dict
    units: List[dict]
    spans: List[Tuple[str, float, float, int]]
    window: Tuple[float, float]
    traced: Optional[Tuple[float, float]]
    setup_s: float
    trace: object
    peak: Optional[dict]

    def host_units(self) -> List[dict]:
        """Units the profiler did not slow: those that started after the
        traced part, or every unit where none did."""
        if self.traced is None:
            return self.units
        after = [u for u in self.units if u["start"] >= self.traced[1]]
        return after or self.units


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def enable_cache() -> str:
    """The program's persistent compile cache (``JAX_COMPILATION_CACHE_DIR``,
    else ``<checkout>/.jax_cache``), holding every program however fast it
    compiled, so that the eager path's per-op compiles are cached too."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return where


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, spec: Optional[dict] = None,
             config: Optional[dict] = None, traffic: Optional[dict] = None,
             cache: bool = True, control: bool = False,
             log=sys.stderr) -> Tuple[dict, List[Tuple[str, float, float]]]:
    """Set up, measure, check and read one cell. Returns the result line's
    object and the compared numbers as ``(name, value, limit)``.

    ``config`` and ``traffic`` replace the cell's files and ``cache``
    False leaves the persistent compile cache off (the CPU tests); the chip
    check lives in ``run.py``. With ``control`` the control's readings
    stand in the program's place against the cell's limits, which it has
    to fail (the tests; the benchmark's own runs never set it).
    """
    import jax
    from bench import peaks

    spec = spec or load_spec()
    cell = cell_of(spec, cell_name)
    config = config or data("configs", cell["config"])
    traffic = traffic or data("traffic", cell["traffic"])
    limits = {k: v["limit"] for k, v in data("limits", cell_name).items()}
    path = path_module(config["path"])

    cache_dir = enable_cache() if cache else "off"
    compiles()
    t_setup = now()
    state = path.setup(config, traffic, seed)
    parts = dict(state.parts, before=t_setup - t_start)
    rec = Recorder(trace, traffic["trace_s"])
    before = compiles()
    units = path.serve(state, traffic, seconds, rec)
    built, hits = (a - b for a, b in zip(compiles(), before))
    setup_s = rec.t_open - t_start

    dev = jax.devices()[0]
    mem = dev.memory_stats() or {}
    readings = path.check(state, units, seed, control=control)
    del state
    if control:
        readings = {name: readings[f"control.{name}"] for name in limits}
    checks = [(name, readings[name], limits[name]) for name in limits]
    failed = sum(1 for u in units if u.get("failed"))
    correct = failed == 0 and all(v <= lim for _, v, lim in checks)

    tr = rec.load_trace()
    try:
        peak = peaks.lookup(dev.device_kind)
    except KeyError:
        if dev.platform == "tpu":
            raise
        peak = None      # a CPU rehearsal: no share of a peak is read
    run = Run(cell, config, traffic, units, rec.spans, (rec.t_open, rec.t_close),
              rec.traced, setup_s, tr, peak)
    metrics = {}
    for m in metrics_for(spec, cell_name, trace):
        value = reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    result = {"correct": correct, "attempted": sum(u["requests"] for u in units),
              "failed": failed, "metrics": metrics, "device": device}
    if tr is not None:
        device["busy_s"] = tr.busy_ns() / 1e9
        device["window_s"] = tr.window_ns / 1e9
        result["breakdown"] = breakdown(tr)
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}

    late = [u["start"] - u["due"] for u in units if u.get("idle_before")]
    print(f"bench: {cell_name} seed {seed}: setup {setup_s:.3f} s, window "
          f"{rec.t_close - rec.t_open:.3f} s, {len(units)} units; in the window "
          f"{built} executables built ({hits} from the persistent cache at {cache_dir})",
          file=log)
    print("bench: set-up parts (s): " + ", ".join(f"{k} {v:.3f}" for k, v in parts.items()),
          file=log)
    if late:
        print(f"bench: generator lateness on an idle server: median "
              f"{sorted(late)[len(late) // 2] * 1e3:.3f} ms, max {max(late) * 1e3:.3f} ms",
              file=log)
    return result, checks


def breakdown(tr) -> Dict[str, list]:
    """The ten device operations that took most time, and the ten longest
    idle gaps named by the innermost harness span around each."""
    from bench.trace import idle_gaps
    lo, hi = tr.window
    per_op: Dict[str, float] = {}
    for dev in tr.ops:
        for name, s, e in dev:
            if s >= lo and e <= hi:
                name = op_name(name)
                per_op[name] = per_op.get(name, 0.0) + (e - s) / 1e9
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(idle_gaps([ev for dev in tr.ops for ev in dev], lo, hi),
                  key=lambda g: g[0] - g[1])[:10]
    named = []
    for s, e in gaps:
        mid = (s + e) / 2
        around = [sp for sp in tr.spans if sp[1] <= mid <= sp[2]]
        inner = min(around, key=lambda sp: sp[2] - sp[1])[0] if around else "outside bench spans"
        named.append([f"idle in {inner}", (e - s) / 1e9])
    return {"device_ops": [[n, v] for n, v in ops], "idle_gaps": named}


def op_name(hlo: str) -> str:
    """``%fusion.3 = bf16[8,128]{1,0:T(8,128)} fusion(...)`` -> ``fusion.3
    bf16[8,128]``: the instruction and the shape of its result."""
    head, _, rest = hlo.partition(" = ")
    shape = re.match(r"\(?(\w+\[[\d,]*\])", rest)
    return f"{head.lstrip('%')} {shape.group(1)}" if shape else head.lstrip("%")


def print_checks(checks, log=sys.stderr) -> None:
    """Each compared number beside its limit: the last lines on stderr."""
    for name, value, limit in checks:
        verdict = "ok" if value <= limit else "FAILED"
        print(f"check {name} {value!r} limit {limit!r} {verdict}", file=log)
    log.flush()
