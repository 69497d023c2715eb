"""Seconds from the process's start to the start of the window: imports,
weights, the token table, compilation and epoch 0."""


def read(run):
    return run["setup_s"]
