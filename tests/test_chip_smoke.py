"""CPU rehearsal of chip_smoke.py: its phase functions at reduced sizes
(f32_reduced configs, kernels in interpret mode), and its refusal to run
any phase when JAX finds no TPU."""

import importlib.util
import pathlib

import pytest

from conftest import f32_reduced

_SCRIPT = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
_spec = importlib.util.spec_from_file_location("chip_smoke", _SCRIPT)
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

# the real-width tuples of chip_smoke.kernel_shapes(), cut to interpret size;
# rglru's width spans two lane tiles
TINY_KERNEL_SHAPES = dict(flash=(1, 4, 2, 256, 64),
                          flash_mla=(1, 2, 2, 256, 192, 128),
                          ssd=(1, 128, 2, 32, 1, 16, 64),
                          ssd_granite=(1, 192, 4, 16, 1, 32, 64),
                          rglru=(1, 128, 1024, 64))


def test_kernel_shapes_are_model_widths():
    shapes = chip_smoke.kernel_shapes()
    assert shapes["flash"] == (1, 16, 2, 2048, 128)         # qwen2.5-3b
    assert shapes["flash_mla"] == (1, 128, 128, 2048, 192, 128)  # deepseek-v2
    assert shapes["ssd"] == (1, 2048, 24, 64, 1, 128, 256)  # mamba2-130m
    assert shapes["ssd_granite"] == (1, 8192, 128, 64, 1, 128, 256)  # granite-4.0-h-small
    assert shapes["rglru"] == (1, 2048, 4096, 256)          # recurrentgemma-9b


def test_kernel_phase_interpret():
    errs = chip_smoke.phase_kernels(TINY_KERNEL_SHAPES, impl="pallas_interpret")
    assert set(errs) == set(chip_smoke.KERNEL_TOLS)


def test_partitioned_phase():
    out = chip_smoke.phase_partitioned(batch=2, image=32, stream=8)
    # host and "device" are the same CPU here: the reference agrees exactly
    assert out["host_err"] == 0.0
    # read from the program's spans: the plan, and one warm call's stages
    assert out["plan_ms"] > 0.0
    assert len(out["stage_ms"]) >= 2 and min(out["stage_ms"]) > 0.0


def test_serving_and_prefill_phases(capsys):
    cfg = f32_reduced("qwen2.5-3b")
    engine = chip_smoke.phase_serving(cfg, requests=2, prompt_len=4, new_tokens=3)
    measured = [ln for ln in capsys.readouterr().out.splitlines()
                if ln.startswith("serving, measured")]
    assert len(measured) == 1 and "ttft_ms" in measured[0] and "None" not in measured[0]
    err = chip_smoke.phase_prefill(cfg, engine.params, seq=128,
                                   impl="pallas_interpret")
    assert err < 1e-4


def test_main_refuses_without_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main()
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_serving_phase_prints_the_moe_counters(capsys):
    engine = chip_smoke.phase_serving(f32_reduced("deepseek-v2-236b"), requests=2,
                                      prompt_len=4, new_tokens=3)
    assert engine.model.can_prefill
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("serving, MoE counters")]
    assert len(lines) == 1 and "dropped 0" in lines[0] and "routed_here" in lines[0]
