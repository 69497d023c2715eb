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
        names = ts.hlo_ops(f.read())
    prog = [p for p in names if p.startswith("jit__lambda(")]
    assert len(prog) == 1
    assert names[prog[0]]["tanh.0"] == ("jit(<lambda>)/tanh", False)


def test_ops_take_the_op_name_of_the_program_they_run_in():
    """Two programs hold an instruction of the same name; an op takes the
    ``op_name`` of the program whose ``XLA Modules`` event it runs in, and
    the rollover program's unnamed ops take the program's scope."""
    programs = [(0, 100, "jit_train_step(1)"),
                (200, 250, "jit_grab_rollover(2)")]
    op_names = {"jit_train_step(1)": {
                    "fusion.1": ts.HloOp(
                        "jit(train_step)/while/body/grab_balance/add", False),
                    "copy.2": ts.HloOp(None, False)},
                "jit_grab_rollover(2)": {"copy.2": ts.HloOp(None, False)}}
    ops = [(10, 20, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)"),
           (30, 40, "%copy.2 = f32[8]{0} copy(f32[8]{0} %p)"),
           (210, 220, "%copy.2 = f32[8]{0} copy(f32[8]{0} %p)"),
           (150, 160, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)")]
    got = [e[3:] for e in ts.scope_events(ops, programs, op_names)]
    assert got == [("grab_balance", False, ()), (None, False, ()),
                   ("grab_rollover", False, ()), (None, None, ())]


def _varint(x: int) -> bytes:
    out = b""
    while True:
        low, x = x & 0x7F, x >> 7
        out += bytes([low | 0x80 if x else low])
        if not x:
            return out


def _msg(n: int, payload) -> bytes:
    """Field ``n`` of a protobuf message: an int, or bytes/str."""
    if isinstance(payload, int):
        return _varint(n << 3) + _varint(payload)
    payload = payload.encode() if isinstance(payload, str) else payload
    return _varint(n << 3 | 2) + _varint(len(payload)) + payload


def _instruction(name, opcode, called=()):
    return _msg(2, _msg(1, name) + _msg(2, opcode)
                + (_msg(38, b"".join(_varint(c) for c in called))
                   if called else b""))


def _xspace(program: str, computations) -> bytes:
    """A serialized XSpace whose metadata plane carries one program's HLO:
    ``computations`` is ``[(id, [instruction, ...])]``."""
    module = b"".join(_msg(3, _msg(1, f"c{cid}") + b"".join(ins)
                           + _msg(5, cid)) for cid, ins in computations)
    stat = _msg(1, 7) + _msg(6, _msg(1, module))
    event_md = _msg(1, 3) + _msg(2, program) + _msg(5, stat)
    stat_md = _msg(1, 7) + _msg(2, "Hlo Proto")
    plane = (_msg(2, ts.METADATA_PLANE)
             + _msg(4, _msg(1, 3) + _msg(2, event_md))
             + _msg(5, _msg(1, 7) + _msg(2, stat_md)))
    return _msg(1, plane)


def test_a_fusion_around_a_collective_is_a_collective():
    """XLA fuses a reduce-scatter into a fusion of another name (the
    four-chip cell's ``fusion.658`` calls ``all-reduce-scatter.7``, which
    holds ``all-reduce.150``): the fusion is a collective by the HLO it
    calls, at any depth; a loop whose body holds one is not, nor is a
    fusion of compute."""
    raw = _xspace("jit_train_step(1)", [
        (1, [_instruction("fusion.658", "fusion", [2]),
             _instruction("fusion.1", "fusion", [3]),
             _instruction("while.3", "while", [4, 3]),
             _instruction("async-collective-start", "fusion", [5]),
             _instruction("all-gather-start.2", "all-gather-start")]),
        (2, [_instruction("all-reduce.150", "all-reduce", [3])]),
        (3, [_instruction("add.1", "add")]),
        (4, [_instruction("all-gather.9", "all-gather")]),
        (5, [_instruction("fusion.9", "fusion", [2])]),
    ])
    ops = ts.hlo_ops(raw)["jit_train_step(1)"]
    got = {name: op.collective for name, op in ops.items()}
    assert got == {"fusion.658": True, "fusion.1": False, "while.3": False,
                   "async-collective-start": True,
                   "all-gather-start.2": True, "all-reduce.150": True,
                   "add.1": False, "all-gather.9": True, "fusion.9": True}


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


@pytest.mark.parametrize("op_name, scope, nested", [
    ("jit(train_step)/while/body/fwd_bwd/jvp()/while/body/closed_call/"
     "moe_experts/dot_general", "fwd_bwd",
     ("jvp", "while", "body", "closed_call", "moe_experts")),
    ("jit(train_step)/transpose(jvp(fwd_bwd))/moe_experts/router/dot_general",
     "fwd_bwd", ("moe_experts", "router")),
    ("jit(train_step)/while/body/fwd_bwd/jit(fwd_bwd)/moe_experts/"
     "jit(moe_experts)/dot", "fwd_bwd", ("jit", "moe_experts")),
    ("jit(train_step)/grab_balance/fold/add", "grab_balance", ("fold",)),
    ("jit(train_step)/optimizer/sqrt", "optimizer", ()),
    ("jit(grab_rollover)/grab_rollover/broadcast_in_dim", "grab_rollover",
     ()),
    ("jit(train_step)/while/body/add", None, ()),
])
def test_nested_words(op_name, scope, nested):
    assert ts.scope_of(op_name) == scope
    assert ts.nested_words(op_name, scope) == nested


def _nested_events():
    """One device, one program, window [0, 100] ms: under ``fwd_bwd`` an
    expert op forward (0-20) and one transposed (20-30), both nested in
    ``moe_experts``, the second also in ``router``, an attention op (30-40)
    and a loop container (0-30); under ``grab_balance`` an op nested in
    ``fold`` and ``moe_experts`` (40-50); an optimizer op (50-60); an
    unnamed copy (60-65)."""
    program = "jit_train_step(1)"
    names = {
        "while.1": "jit(train_step)/while/body/fwd_bwd/jvp()/while",
        "fusion.2": "jit(train_step)/while/body/fwd_bwd/jvp()/while/body/"
                    "closed_call/moe_experts/dot_general",
        "fusion.3": "jit(train_step)/transpose(jvp(fwd_bwd))/moe_experts/"
                    "router/jit(moe_experts)/dot_general",
        "fusion.4": "jit(train_step)/while/body/fwd_bwd/attn/dot_general",
        "fusion.5": "jit(train_step)/grab_balance/fold/moe_experts/add",
        "fusion.6": "jit(train_step)/optimizer/mul",
    }
    hlo = {program: {n: ts.HloOp(o, False) for n, o in names.items()}}
    hlo[program]["copy.7"] = ts.HloOp(None, False)
    ops = [(0, 30 * MS, "while.1"), (0, 20 * MS, "fusion.2"),
           (20 * MS, 30 * MS, "fusion.3"), (30 * MS, 40 * MS, "fusion.4"),
           (40 * MS, 50 * MS, "fusion.5"), (50 * MS, 60 * MS, "fusion.6"),
           (60 * MS, 65 * MS, "copy.7")]
    events = ts.scope_events(ops, [(0, 100 * MS, program)], hlo)
    return {"devices": {"/device:TPU:0": sorted(events)},
            "spans": [(0, 100 * MS, "window")]}


def test_nested_seconds_beside_the_scopes():
    out = ts.reduce_events(_nested_events(), "window")
    assert out["scope_s"] == {"fwd_bwd": pytest.approx(0.040),
                              "grab_balance": pytest.approx(0.010),
                              "optimizer": pytest.approx(0.010)}
    assert out["unscoped_s"] == pytest.approx(0.005)
    nested = out["nested_s"]
    # each word once an op, the container left out
    assert nested["moe_experts"] == pytest.approx(0.040)
    assert nested["router"] == pytest.approx(0.010)
    assert nested["attn"] == pytest.approx(0.010)
    assert nested["fold"] == pytest.approx(0.010)
    assert nested["jvp"] == pytest.approx(0.020)
    assert not {"fwd_bwd", "grab_balance", "optimizer", "dot_general",
                "add", "mul"} & set(nested)


def test_nested_seconds_leave_the_other_numbers_as_they_were():
    """The same ops with their nested words dropped give the same scope
    seconds, unscoped seconds, ops and gaps, and no nested seconds."""
    ev = _nested_events()
    plain = {"devices": {p: [e[:5] for e in evs]
                         for p, evs in ev["devices"].items()},
             "spans": ev["spans"]}
    new, old = ts.reduce_events(ev, "window"), ts.reduce_events(plain,
                                                                "window")
    assert old.pop("nested_s") == {}
    new.pop("nested_s")
    assert new == old


def test_recorded_cpu_trace_nested_and_scopes_as_before():
    out = ts.reduce_file(FIXTURE, "bench_window")
    assert out["nested_s"] == {}
    assert out["scope_s"] == {} and out["unscoped_s"] == 0.0


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


def test_harness_reads_the_trace_once_and_as_before(tmp_path):
    """The harness's reading of a trace, which adds the loop's nested spans
    and the scope times, gives the numbers that the readers of
    ``loop.epoch_boundary_ms``, ``device.idle_share``, ``step.mfu`` and
    ``data.loader_wait_ms`` take as the reduction with the old span list
    gave them."""
    import shutil

    import harness
    import trace_reduce as tr
    from layout import Layout

    shutil.copy(FIXTURE, tmp_path / "t.xplane.pb")
    old = tr.reduce_events(ts.read_events(FIXTURE, (
        "loader_wait", "dispatch", "epoch_reorder", "ckpt_save",
        harness.WINDOW_SPAN)), harness.WINDOW_SPAN)
    new = harness.read_trace(str(tmp_path))
    assert new["scope_s"] == {} and new["unscoped_s"] == 0.0
    for k in old:
        if k in ("device_ops", "idle_gaps"):
            continue             # the breakdown: ops and gaps by scope
        if k in ("spans", "span_idle"):
            assert {n: v for n, v in new[k].items() if n in old[k]} == old[k]
        else:
            assert new[k] == old[k], k
    lay = Layout(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    for name in ("loop.epoch_boundary_ms", "device.idle_share", "step.mfu",
                 "data.loader_wait_ms"):
        read = lay.reader(name)
        run = {"trace": None, "tokens": 1e5, "chips": 1,
               "flops_per_token": 1e9, "peak_flops": 1e12}
        assert read(dict(run, trace=new)) == read(dict(run, trace=old))


def test_harness_trace_reading_on_device_events():
    """On events with a device plane and nested boundary spans, the
    boundary's idle and the window's idle share do not move with the spans
    added; the scope times come beside them."""
    import harness
    import trace_reduce as tr

    ev = _events()
    old = tr.reduce_events(dict(ev, spans=[
        s for s in ev["spans"] if s[2] not in ("sign_fetch", "rollover")]),
        "window")
    new = tr.reduce_events(ev, "window")
    assert new["span_idle"]["epoch_reorder"] == \
        old["span_idle"]["epoch_reorder"]
    assert new["idle_share"] == old["idle_share"]
    assert {"sign_fetch", "rollover"} <= set(harness.SPANS)
