"""images_per_s: images whose logits reached the host in the window, over
the time from the window's start to the last of them."""


def read(run):
    start, end = run.window
    return sum(u["items"] for u in run.units) / (end - start)
