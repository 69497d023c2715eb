"""The peak memory reading: buffers in use and reserved temporaries read at
the same moment, never less than the allocator's own peak in use."""
import time

import harness


class FakeDevice:
    """Two phases: many buffers and a small reservation, then fewer buffers
    and a large one. Their two peaks never fall together."""

    def __init__(self):
        self.calls = 0

    def memory_stats(self):
        self.calls += 1
        if self.calls < 5:
            return {"bytes_in_use": 10, "bytes_reserved": 1,
                    "peak_bytes_in_use": 10, "peak_bytes_reserved": 1}
        return {"bytes_in_use": 6, "bytes_reserved": 3,
                "peak_bytes_in_use": 10, "peak_bytes_reserved": 3}


def test_watch_reads_in_use_and_reserved_together():
    dev = FakeDevice()
    watch = harness.MemoryWatch([dev], period=0.001)
    while dev.calls < 10:
        time.sleep(0.001)
    watch.stop()
    assert watch.peak == [11]
    # the sum of the two peaks, 13, would count more than was ever held
    assert harness.footprint([dev.memory_stats()], watch.peak) == 11


def test_footprint_takes_the_fullest_chip_and_the_allocator_peak():
    stats = [{"peak_bytes_in_use": 10}, {"peak_bytes_in_use": 20}]
    assert harness.footprint(stats, [12, 15]) == 20
    assert harness.footprint(stats, [12, 25]) == 25
    assert harness.footprint([{}], [0]) == 0
