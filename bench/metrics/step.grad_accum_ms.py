"""Device time per step of the step's ``grad_accum`` scope in the traced
window, averaged over the chips, in ms: the float32 gradient accumulator's
zeros, adds and per-worker mean (``trace_scopes.reduce_events``)."""


def read(run):
    t = ((run["trace"] or {}).get("scope_s") or {}).get("grad_accum")
    return 1e3 * t / run["steps"] if t else None
