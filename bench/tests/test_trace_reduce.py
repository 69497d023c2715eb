"""The reduction from a profiler trace to the benchmark's numbers."""
import os

import pytest

import trace_reduce as tr
import trace_scopes as ts

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "spans.xplane.pb")
MS = 1e6   # ns


def test_recorded_cpu_trace_spans():
    ev = ts.read_events(FIXTURE, ["bench_window", "loader_wait", "dispatch",
                                  "epoch_reorder"])
    assert ev["devices"] == {}             # a CPU trace has no device plane
    out = tr.reduce_events(ev, "bench_window")
    waits, disp = out["spans"]["loader_wait"], out["spans"]["dispatch"]
    assert len(waits) == 3 and len(disp) == 3
    assert all(0.019 <= w <= 0.040 for w in waits)
    assert all(0.009 <= d <= 0.040 for d in disp)
    assert "epoch_reorder" not in out["spans"]     # outside the window
    assert 0.09 <= out["window_s"] <= 0.3


def _events():
    """One device, window [0, 100] ms: compute 0-30, a collective 20-50
    (exposed 30-50), compute 60-70; host spans over the gaps."""
    ops = [(0, 30 * MS, "fusion.1"), (20 * MS, 50 * MS, "all-gather.2"),
           (60 * MS, 70 * MS, "fusion.1")]
    spans = [(0, 100 * MS, "window"), (48 * MS, 62 * MS, "loader_wait"),
             (70 * MS, 100 * MS, "epoch_reorder"),
             (72 * MS, 74 * MS, "dispatch")]
    return {"devices": {"/device:TPU:0": ops}, "spans": spans}


def test_busy_union_and_idle_share():
    out = tr.reduce_events(_events(), "window")
    assert out["busy_s"] == pytest.approx(0.060)        # 0-50 and 60-70
    assert out["window_s"] == pytest.approx(0.100)
    assert out["idle_share"] == pytest.approx(0.40)


def test_exposed_collective_share():
    out = tr.reduce_events(_events(), "window")
    assert out["exposed_collective_share"] == pytest.approx(0.20)


def test_a_collective_inside_a_loop_is_exposed():
    """The loop's own event spans its body; only the body's other ops hide
    the collective."""
    ev = _events()
    ev["devices"]["/device:TPU:0"].append((0, 100 * MS, "while.3"))
    out = tr.reduce_events(ev, "window")
    assert out["exposed_collective_share"] == pytest.approx(0.20)
    assert out["busy_s"] == pytest.approx(0.100)


def test_an_op_that_reads_a_collective_is_no_collective():
    """The trace names each op by its HLO text, operands included: the
    fusion that reads the gathered weight is compute."""
    ev = _events()
    ops = ev["devices"]["/device:TPU:0"]
    ops[1] = (20 * MS, 50 * MS, "%all-gather-done.2 = bf16[8]{0} "
              "all-gather-done(bf16[8]{0} %all-gather-start.2)")
    ops[2] = (60 * MS, 70 * MS, "%fusion.1 = bf16[8]{0} "
              "fusion(bf16[8]{0} %all-gather-done.2), kind=kOutput")
    out = tr.reduce_events(ev, "window")
    assert out["exposed_collective_share"] == pytest.approx(0.20)


def test_a_fused_collective_is_exposed():
    """A fusion that the program's HLO marks a collective (the fifth field
    of an event read with the trace's HLO) counts as one, whatever its
    name; one the HLO marks compute hides a collective under it."""
    ev = _events()
    ops = ev["devices"]["/device:TPU:0"]
    coll = [e for e in ops if tr.COLLECTIVE.match(tr.op_name(e[2]))]
    ops[:] = [e + (None, None) if e not in coll else
              (e[0], e[1], "%fusion.658 = bf16[8]{0} fusion(bf16[32]{0} "
               "%p), kind=kOutput", None, True) for e in ops]
    out = tr.reduce_events(ev, "window")
    assert out["exposed_collective_share"] == pytest.approx(0.20)
    ops[:] = [e[:4] + (False,) for e in ops]
    out = tr.reduce_events(ev, "window")
    assert out["exposed_collective_share"] == 0.0


def test_breakdown_ops_and_gap_attribution():
    out = tr.reduce_events(_events(), "window")
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(0.040)]
    assert out["device_ops"][1] == ["all-gather.2", pytest.approx(0.030)]
    # the gaps are 70-100 (under epoch_reorder) and 50-60 (loader_wait)
    assert out["idle_gaps"] == [["epoch_reorder", pytest.approx(0.030)],
                                ["loader_wait", pytest.approx(0.010)]]


def test_merge_and_subtract():
    merged = tr.merge([(5, 10), (0, 3), (2, 6), (20, 30)], 1, 25)
    assert merged == [(1, 10), (20, 25)]
    assert tr.gaps(merged, 0, 30) == [(0, 1), (10, 20), (25, 30)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]


def test_device_idle_inside_spans():
    out = tr.reduce_events(_events(), "window")
    # epoch_reorder 70-100 holds no device op; loader_wait 48-62 overlaps
    # the collective until 50 and compute from 60
    assert out["span_idle"]["epoch_reorder"] == [pytest.approx(0.030)]
    assert out["span_idle"]["loader_wait"] == [pytest.approx(0.010)]
    assert out["span_idle"]["dispatch"] == [pytest.approx(0.002)]
