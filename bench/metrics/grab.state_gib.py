"""Bytes on one chip of the GraB state (running sum, stale and fresh
means), from the leaves' shards, in GiB."""


def read(run):
    b = run["grab_state_bytes"]
    return b / 2 ** 30 if b else None
