"""Device time per step of the step's ``optimizer`` scope in the traced
window, averaged over the chips, in ms: the mean over microbatches, the
learning rate and the AdamW update (``trace_scopes.reduce_events``)."""


def read(run):
    t = ((run["trace"] or {}).get("scope_s") or {}).get("optimizer")
    return 1e3 * t / run["steps"] if t else None
