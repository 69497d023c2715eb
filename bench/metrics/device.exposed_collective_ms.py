"""Device time per step in which a collective runs on a chip and no other
operation does, averaged over the chips, in the traced window, in ms
(``trace_reduce.exposed_collective_ns``)."""


def read(run):
    tr = run["trace"]
    if not tr or tr.get("exposed_collective_share") is None:
        return None
    return 1e3 * tr["exposed_collective_share"] * tr["window_s"] / run["steps"]
