"""prefill_ms: device time per execution of the jitted prefill, from the
executions of the ``jit_prefill`` program in the traced window."""


def read(run):
    tr = run.trace
    if tr is None:
        return None
    lo, hi = tr.window
    runs = [e - s for dev in tr.modules for name, s, e in dev
            if name.startswith("jit_prefill") and s >= lo and e <= hi]
    return sum(runs) / len(runs) / 1e6 if runs else None
