"""idle_share: share of the traced window in which no operation ran on
the device, averaged over the chips (1 - union of device op intervals /
traced window)."""


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    return 100.0 * (1.0 - run.trace.busy_ns() / run.trace.window_ns)
