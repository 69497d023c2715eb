"""Tokens trained in the window over the window's seconds, all chips."""


def read(run):
    return run["tokens"] / run["window_s"]
