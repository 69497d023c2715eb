"""Mean of the loop's ``loader_wait`` span per step in the traced window,
in ms: how long the host waited for the prefetcher's next batch."""


def read(run):
    spans = (run["trace"] or {}).get("spans", {}).get("loader_wait")
    return 1e3 * sum(spans) / len(spans) if spans else None
