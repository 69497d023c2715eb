"""Share of the traced window in which no operation runs on the device
(1 - busy union / window), averaged over the chips, in %."""


def read(run):
    tr = run["trace"]
    if not tr or tr["idle_share"] is None:
        return None
    return 100.0 * tr["idle_share"]
