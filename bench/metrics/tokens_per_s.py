"""tokens_per_s: output tokens of every serve call completed in the
window, over the time from the window's start to the last completion."""


def read(run):
    start, end = run.window
    return sum(u["items"] for u in run.units) / (end - start)
