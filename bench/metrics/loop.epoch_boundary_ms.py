"""Device idle time inside the loop's ``epoch_reorder`` span, per epoch of
the traced window, in ms. The span holds the sign fetch (which first waits
for the epoch's queued steps), the Algorithm-3 reorder and the dispatch of
the epoch-end rollover; the device is idle in it only once the queue has
drained, so this is what the boundary costs the device."""


def read(run):
    idle = (run["trace"] or {}).get("span_idle", {}).get("epoch_reorder")
    return 1e3 * sum(idle) / len(idle) if idle else None
