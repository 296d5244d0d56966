"""mfu.<cell kind>: model FLOPs (``bench/flops.py``) of the requests or
calls the profiler did not slow, over their summed host time times the
chip's bf16 peak (``bench/peaks.json``)."""


def read(run):
    if run.peak is None:
        return None
    units = run.host_units()
    busy = sum(u["end"] - u["start"] for u in units)
    return 100.0 * sum(u["flops"] for u in units) / (busy * run.peak["bf16_flops_per_s"])
