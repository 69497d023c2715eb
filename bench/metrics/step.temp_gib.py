"""Temporary bytes of the compiled step, from the loop's ``step.temp_bytes``
gauge (the compiler's memory analysis), in GiB."""


def read(run):
    g = run["gauges"].get("step.temp_bytes")
    return g["last"] / 2 ** 30 if g else None
