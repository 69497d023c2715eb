"""Peak device memory of the run on its fullest chip, in GiB: the largest
sum of the bytes held in buffers and the bytes the runtime reserves for a
running program's temporaries, sampled from the device's allocator through
set-up and the window (``harness.MemoryWatch``, ``harness.footprint``)."""


def read(run):
    return run["peak_bytes"] / 2 ** 30 if run["peak_bytes"] else None
