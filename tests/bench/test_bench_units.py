"""The benchmark's yardstick on the CPU: trace reduction, generators, FLOP
counts, the peak table, the shape of BENCHMARK.json, and the refusal to
run without a TPU. Nothing here loads the TPU library."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench import flops, generate, harness, peaks, trace  # noqa: E402
from bench.trace import Trace  # noqa: E402

MS = 1_000_000  # ns


def _run(tr=None, units=(), spans=(), traced=None, peak=None, window=(0.0, 1.0)):
    return harness.Run(cell={}, config={}, traffic={}, units=list(units), spans=list(spans),
                       window=window, traced=traced, setup_s=12.5, trace=tr, peak=peak)


def _trace():
    # window 0-100 ms; ops overlap at 10-30 and 20-40 ms, then 60-70 ms
    ops = [[("conv", 10 * MS, 30 * MS), ("fusion", 20 * MS, 40 * MS),
            ("fusion", 60 * MS, 70 * MS), ("late", 95 * MS, 120 * MS)]]
    modules = [[("jit_decode_step(7)", 10 * MS, 40 * MS), ("jit_decode_step(7)", 60 * MS, 70 * MS),
                ("jit_other(3)", 95 * MS, 99 * MS)]]
    spans = [("bench.window", 0, 100 * MS), ("bench.request", 5 * MS, 45 * MS),
             ("bench.executor", 8 * MS, 42 * MS), ("bench.request", 55 * MS, 75 * MS)]
    return Trace(window=(0, 100 * MS), ops=ops, modules=modules, spans=spans)


def test_merge_and_union_of_device_intervals():
    assert trace.merge([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    tr = _trace()
    assert tr.busy_ns() == pytest.approx(30 * MS + 10 * MS + 5 * MS)
    assert trace.union_ns(tr.ops[0], [(15 * MS, 25 * MS), (22 * MS, 65 * MS)]) == \
        pytest.approx(25 * MS + 5 * MS)
    assert trace.idle_gaps(tr.ops[0], 0, 100 * MS) == [(0, 10 * MS), (40 * MS, 60 * MS),
                                                       (70 * MS, 95 * MS)]


@pytest.mark.parametrize("name, expect", [
    ("idle_share.closed", 55.0),            # 45 of 100 ms busy
    ("idle_share.serve", 55.0),
    ("decode_step_ms.serve", 20.0),        # (30 + 10) / 2 ms
])
def test_trace_readers(name, expect):
    assert harness.reader(name)(_run(_trace())) == pytest.approx(expect)


@pytest.mark.parametrize("name", ["idle_share.closed", "decode_step_ms.serve"])
def test_trace_readers_without_a_trace_return_nothing(name):
    assert harness.reader(name)(_run(None)) is None
    empty = Trace(window=(0, 1), ops=[], modules=[], spans=[])
    assert harness.reader(name)(_run(empty)) is None


def test_breakdown_names_ops_and_gaps():
    out = harness.breakdown(_trace())
    assert out["device_ops"][0] == ["fusion", pytest.approx(0.03)]
    assert out["idle_gaps"][0] == ["idle in bench.window", pytest.approx(0.025)]
    assert out["idle_gaps"][2] == ["idle in bench.request", pytest.approx(0.01)]


def test_host_clock_readers():
    units = [dict(due=0.0, start=0.1, end=0.3, items=32, requests=1, flops=2e12),
             dict(due=0.2, start=0.3, end=0.6, items=32, requests=1, flops=2e12),
             dict(due=0.5, start=0.6, end=1.0, items=32, requests=1, flops=2e12)]
    spans = [("bench.executor", 0.1, 0.2, 0), ("bench.executor", 0.2, 0.25, 0),
             ("bench.executor", 0.3, 0.5, 1), ("bench.executor", 0.6, 0.9, 2)]
    peak = peaks.lookup("TPU v5 lite")
    run = _run(units=units, spans=spans, peak=peak, window=(0.0, 1.0))
    assert harness.reader("images_per_s")(run) == pytest.approx(96.0)
    assert harness.reader("tokens_per_s")(run) == pytest.approx(96.0)
    assert harness.reader("setup_s")(run) == 12.5
    assert harness.reader("executor_ms.closed")(run) == pytest.approx((150 + 200 + 300) / 3)
    assert harness.reader("mfu.closed")(run) == pytest.approx(
        100 * 6e12 / (0.9 * peak["bf16_flops_per_s"]))
    # units that started inside the traced part are left out of host-clock readings
    run.traced = (0.0, 0.55)
    assert harness.reader("executor_ms.closed")(run) == pytest.approx(300.0)
    assert harness.reader("mfu.serve")(run) is not None
    assert harness.reader("mfu.serve")(_run(units=units)) is None     # no peak on the CPU


def test_load_reads_harness_spans_from_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    rec = harness.Recorder(True, 0.0)
    rec._dir = str(tmp_path)
    jax.profiler.start_trace(rec._dir)
    with jax.profiler.TraceAnnotation("bench.window"):
        with rec.span("bench.request"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = trace.load(str(tmp_path))
    names = [s[0] for s in tr.spans]
    assert "bench.request" in names and tr.window_ns > 0
    assert tr.ops == []                    # no TPU plane on the CPU


def test_generators_are_reproducible_per_seed():
    big = 2**31 + 12345
    traffic = dict(kind="poisson", rate_per_s=7.0, pool=2, batch=2,
                   requests_per_call=3, prompt_len=5)
    config = dict(image_size=8, in_channels=3, vocab_size=1000)
    a = generate.arrivals(traffic, 45.0, big)
    assert np.array_equal(a, generate.arrivals(traffic, 45.0, big))
    assert not np.array_equal(a, generate.arrivals(traffic, 45.0, big + 1))
    assert len(a) == 315 and np.all(np.diff(a) > 0) and 0 < a[0] and a[-1] < 45.0
    assert len(generate.arrivals(dict(traffic, kind="closed"), 45.0, 1)) == 0
    for fn in (generate.images, generate.prompts):
        x = fn(traffic, config, big)
        assert np.array_equal(x, fn(traffic, config, big))
        assert not np.array_equal(x, fn(traffic, config, 2**40))
    assert generate.images(traffic, config, 3).shape == (2, 2, 8, 8, 3)
    p = generate.prompts(traffic, config, 3)
    assert p.shape == (2, 3, 5) and p.dtype == np.int32 and p.max() < 1000
    s = generate.sample(50, 8, big)
    assert np.array_equal(s, generate.sample(50, 8, big)) and len(set(s)) == 8
    assert list(generate.key_words(big, 3)) == list(generate.key_words(big, 3))


def test_mobilenetv2_flops_against_the_published_count():
    config = harness.data("configs", "mobilenetv2-224")
    # the paper: 300 M multiply-adds at 224x224, width 1.0
    assert flops.mobilenetv2_macs(config) == pytest.approx(300e6, rel=0.02)
    assert flops.mobilenetv2_flops_per_image(config) == 2 * flops.mobilenetv2_macs(config)


def test_qwen_flops_against_twice_the_parameters():
    config = harness.data("configs", "qwen2.5-3b")
    # 3.09 B parameters; the tied embedding is multiplied once, as the head
    n = flops.qwen2_matmul_params(config)
    assert n == pytest.approx(3.086e9, rel=0.005)
    per = flops.qwen2_flops_per_position(config, 1)
    assert per == pytest.approx(2 * n, rel=1e-3)
    attn = flops.qwen2_flops_per_position(config, 1001) - per
    assert attn == pytest.approx(4 * 36 * 16 * 128 * 1000)
    call = flops.qwen2_serve_call_flops(config, 2, 3, 2)
    assert call == pytest.approx(2 * sum(flops.qwen2_flops_per_position(config, c)
                                         for c in (1, 2, 3, 4)))


def test_peak_table_refuses_an_unknown_device():
    assert peaks.lookup("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.lookup("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.lookup("cpu")


def test_nothing_reads_the_program_peak_constants():
    for file in (ROOT / "bench").rglob("*.py"):
        text = file.read_text()
        assert "repro.core.cost_model" not in text and "repro.launch.roofline" not in text, file


NAME = r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$"


def test_benchmark_json_is_complete():
    """The structure every entry needs, whatever cells and metrics it names:
    a later cell or metric is added with its files and entries alone."""
    import re
    spec = harness.load_spec()
    assert set(spec) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert spec["command"] == ["python3", "bench/run.py"]
    configs = {c["name"]: c for c in spec["configs"]}
    for c in spec["configs"]:
        assert re.match(NAME, c["name"]) and (ROOT / c["file"]).exists()
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]
    cells = {w["name"] for w in spec["workloads"]}
    for w in spec["workloads"]:
        assert re.match(NAME, w["name"]) and w["config"] in configs
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").exists()
        assert (ROOT / "bench" / "limits" / f"{w['name']}.json").exists()
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert re.match(NAME, m["name"]) and callable(harness.reader(m["name"]))
        assert set(m.get("workloads", [])) <= cells
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(e2e[m["moves"]].get("workloads", cells))
    for cell in cells:
        assert "setup_s" in [m["name"] for m in harness.metrics_for(spec, cell, False)]
        assert len(harness.metrics_for(spec, cell, False)) >= 2
        assert harness.metrics_for(spec, cell, True)


@pytest.mark.parametrize("cell", [w["name"] for w in harness.load_spec()["workloads"]])
def test_each_limit_lies_between_its_readings(cell):
    """Each limit sits above the program's largest chip reading and below
    the control's smallest, which is three times it or more."""
    for name, limit in harness.data("limits", cell).items():
        assert limit["lower"] < limit["limit"] < limit["upper"], name
        assert limit["upper"] >= 3 * limit["lower"], name


def test_run_refuses_a_cpu_backend(capsys):
    from bench import run
    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", "mnv2-b32-closed", "--seed", "1", "--seconds", "1"])
    assert exc.value.code not in (0, None)
    assert '"correct"' not in capsys.readouterr().out

