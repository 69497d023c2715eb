"""Device time per step of the step's ``grab_balance`` scope in the traced
window, averaged over the chips, in ms: GraB's balance, the fresh-mean
fold, the sign exchange and the sign-buffer write
(``trace_scopes.reduce_events``)."""


def read(run):
    t = ((run["trace"] or {}).get("scope_s") or {}).get("grab_balance")
    return 1e3 * t / run["steps"] if t else None
