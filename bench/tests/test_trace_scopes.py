"""Device time per scope and nested gap labels, on synthetic events."""
import os

import pytest

import trace_scopes as ts

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "spans.xplane.pb")
MS = 1e6   # ns


@pytest.mark.parametrize("op_name, scope", [
    ("jit(train_step)/while/body/closed_call/grab_balance/mul",
     "grab_balance"),
    ("jit(train_step)/while/body/fwd_bwd/jit(fwd_bwd)/transpose(jvp())/dot",
     "fwd_bwd"),
    ("jit(train_step)/transpose(jvp(fwd_bwd))/dot_general", "fwd_bwd"),
    ("jit(train_step)/optimizer/sqrt", "optimizer"),
    ("jit(grab_rollover)/grab_rollover/broadcast_in_dim", "grab_rollover"),
    ("jit(train_step)/while/body/add", None),
    ("jit(train_step)/my_optimizer_step/add", None),   # whole words only
    (None, None),
])
def test_scope_of(op_name, scope):
    assert ts.scope_of(op_name) == scope


def test_program_scope():
    assert ts.program_scope("jit_grab_rollover(1332)") == "grab_rollover"
    assert ts.program_scope("jit_train_step(5491)") is None
    assert ts.program_scope(None) is None


def test_op_names_from_the_hlo_a_trace_carries():
    """The recorded CPU trace carries the HLO of the jitted function it
    ran; each instruction's ``op_name`` comes back under the program's
    name as the trace names it."""
    with open(FIXTURE, "rb") as f:
        names = ts.hlo_op_names(f.read())
    prog = [p for p in names if p.startswith("jit__lambda(")]
    assert len(prog) == 1
    assert names[prog[0]]["tanh.0"] == "jit(<lambda>)/tanh"


def test_ops_take_the_op_name_of_the_program_they_run_in():
    """Two programs hold an instruction of the same name; an op takes the
    ``op_name`` of the program whose ``XLA Modules`` event it runs in, and
    the rollover program's unnamed ops take the program's scope."""
    programs = [(0, 100, "jit_train_step(1)"),
                (200, 250, "jit_grab_rollover(2)")]
    op_names = {"jit_train_step(1)": {
                    "fusion.1": "jit(train_step)/while/body/grab_balance/add",
                    "copy.2": None},
                "jit_grab_rollover(2)": {"copy.2": None}}
    ops = [(10, 20, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)"),
           (30, 40, "%copy.2 = f32[8]{0} copy(f32[8]{0} %p)"),
           (210, 220, "%copy.2 = f32[8]{0} copy(f32[8]{0} %p)"),
           (150, 160, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)")]
    got = [e[3] for e in ts.scope_events(ops, programs, op_names)]
    assert got == ["grab_balance", None, "grab_rollover", None]


def _events():
    """One device, window [0, 100] ms. Ops: fwd_bwd 0-30, grab_balance
    30-50, an unscoped copy 50-55, optimizer 60-70 and a loop container
    0-50 that holds the first two. Host spans: epoch_reorder 70-100 holding
    sign_fetch 70-72 and rollover 74-100; loader_wait 54-62."""
    ops = [(0, 50 * MS, "while.3", "fwd_bwd"),
           (0, 30 * MS, "fusion.1", "fwd_bwd"),
           (30 * MS, 50 * MS, "fusion.2", "grab_balance"),
           (50 * MS, 55 * MS, "copy.4", None),
           (60 * MS, 70 * MS, "fusion.5", "optimizer")]
    spans = [(0, 100 * MS, "window"), (10 * MS, 12 * MS, "dispatch"),
             (54 * MS, 62 * MS, "loader_wait"),
             (70 * MS, 100 * MS, "epoch_reorder"),
             (70 * MS, 72 * MS, "sign_fetch"),
             (74 * MS, 100 * MS, "rollover")]
    return {"devices": {"/device:TPU:0": ops}, "spans": spans}


def test_scope_seconds_and_unscoped():
    out = ts.reduce_events(_events(), "window")
    assert out["window_s"] == pytest.approx(0.100)
    assert out["busy_s"] == pytest.approx(0.065)
    assert out["steps"] == 1
    # the loop container counts towards busy time, not towards a scope
    assert out["scope_s"] == {"fwd_bwd": pytest.approx(0.030),
                              "grab_balance": pytest.approx(0.020),
                              "optimizer": pytest.approx(0.010)}
    assert out["unscoped_s"] == pytest.approx(0.005)


def test_ops_named_by_scope():
    out = ts.reduce_events(_events(), "window")
    assert [n for n, _ in out["device_ops"]] == [
        "fwd_bwd/fusion.1", "grab_balance/fusion.2", "optimizer/fusion.5",
        "copy.4"]


def test_gap_labels_nested_and_plain():
    out = ts.reduce_events(_events(), "window")
    # 70-100 lies in epoch_reorder, and rollover (74-100) covers 26 of its
    # 30 ms; 55-60 lies in loader_wait, which holds no span
    assert out["idle_gaps"] == [
        ["epoch_reorder/rollover", pytest.approx(0.030)],
        ["loader_wait", pytest.approx(0.005)]]


def test_gap_label_stays_when_no_child_covers_half():
    ev = _events()
    # the rollover now covers 10 of the gap's 30 ms
    ev["spans"] = [s if s[2] != "rollover" else (90 * MS, 100 * MS, s[2])
                   for s in ev["spans"]]
    out = ts.reduce_events(ev, "window")
    assert out["idle_gaps"][0] == ["epoch_reorder", pytest.approx(0.030)]


def test_gap_of_a_span_alone_and_of_no_span():
    ev = {"devices": {"/device:TPU:0": [(0, 10 * MS, "fusion.1", None),
                                        (40 * MS, 50 * MS, "fusion.1", None)]},
          "spans": [(0, 100 * MS, "window"), (10 * MS, 40 * MS, "dispatch")]}
    out = ts.reduce_events(ev, "window")
    assert out["idle_gaps"] == [["none", pytest.approx(0.050)],
                                ["dispatch", pytest.approx(0.030)]]


def test_whole_trace_as_window():
    ev = _events()
    ev["spans"] = [s for s in ev["spans"] if s[2] != "window"]
    out = ts.reduce_events(ev, None)
    assert out["window_s"] == pytest.approx(0.100)     # the spans' extent
    assert out["scope_s"]["fwd_bwd"] == pytest.approx(0.030)


def test_recorded_cpu_trace():
    """A CPU trace has no device plane: no scope time, and the window and
    the steps come from the host spans."""
    out = ts.reduce_file(FIXTURE, "bench_window")
    assert out["scope_s"] == {} and out["unscoped_s"] == 0.0
    assert out["steps"] == 3
    assert 0.09 <= out["window_s"] <= 0.3


def test_cli_prints_one_json_object(capsys):
    import json

    assert ts.main([FIXTURE, "--window", "bench_window"]) == 0
    assert json.loads(capsys.readouterr().out)["steps"] == 3
