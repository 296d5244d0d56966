"""setup_s: process start until the window opens (weights, inputs, the
warm-up of every shape the window uses, compiles or their cache loads)."""


def read(run):
    return run.setup_s
