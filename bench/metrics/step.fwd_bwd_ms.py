"""Device time per step of the step's ``fwd_bwd`` scope in the traced window,
averaged over the chips, in ms: the gradient call (forward, backward and
the remat recompute) (``trace_scopes.reduce_events``)."""


def read(run):
    t = ((run["trace"] or {}).get("scope_s") or {}).get("fwd_bwd")
    return 1e3 * t / run["steps"] if t else None
