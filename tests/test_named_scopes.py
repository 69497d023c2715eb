"""The train step's layers are named in the compiled program.

``train/step.py`` puts ``fwd_bwd``, ``grab_balance``, ``grad_accum`` and
``optimizer`` on the step's layers and ``train/loop.py`` puts
``grab_rollover`` on the epoch-end rollover. The names reach each HLO
instruction's ``op_name`` metadata, which is what a device trace carries
and what the benchmark's trace reduction reads. These tests compile the
grab and rr steps of a tiny LM on the CPU and read ``op_name`` from
``compiled.as_text()``.

Instructions that the compiler inserts itself (copies, layout changes, the
CPU's rewrites of reductions) carry no ``op_name`` at all; the program
cannot name them, and a chip trace reads their time as unscoped.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.grab import GrabConfig, init_grab_state
from repro.models import lm
from repro.optim import adamw, constant
from repro.train.loop import grab_rollover
from repro.train.step import build_train_step, init_train_state

SCOPES = ("fwd_bwd", "grab_balance", "grad_accum", "optimizer",
          "grab_rollover")
# instructions that only route buffers or hold a loop's body
PLUMBING = ("parameter", "get-tuple-element", "tuple", "constant", "bitcast",
            "while", "call", "conditional")
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
                "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
                "u64": 8}
_INSTR = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\(")
_COMP = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")


def _scope(op_name):
    for part in (op_name or "").split("/"):
        for word in re.findall(r"[A-Za-z0-9_]+", part):
            if word in SCOPES:
                return word
    return None


def _bytes(shape: str) -> int:
    total = 0
    for dt, dims in re.findall(r"\b([a-z]+\d*)\[([\d,]*)\]", shape):
        if dt in _DTYPE_BYTES:
            total += int(np.prod([int(d) for d in dims.split(",") if d])) \
                * _DTYPE_BYTES[dt]
    return total


def _instructions(hlo: str) -> list:
    """Every instruction as a dict: its computation, whether that is a
    fusion's body, name, shape, opcode and ``op_name``."""
    out, comp = [], None
    for line in hlo.splitlines():
        m = _COMP.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if m and comp is not None:
            op = re.search(r'op_name="([^"]*)"', line)
            out.append({"comp": comp, "name": m.group(1),
                        "shape": m.group(2), "opcode": m.group(3),
                        "op_name": op.group(1) if op else None,
                        "calls": re.findall(r"calls=%?([\w.\-]+)", line)})
    fused = {c for i in out if i["opcode"] == "fusion" for c in i["calls"]}
    for i in out:
        i["fused"] = i["comp"] in fused
        i["scope"] = _scope(i["op_name"])
    return out


def _tiny_lm():
    model = get_config("phi3-mini-3.8b")[1].with_(param_dtype="bfloat16")
    params = lm.init_lm(jax.random.PRNGKey(0), model)
    return model, params


@pytest.fixture(scope="module", params=["grab", "rr"])
def step_hlo(request):
    model, params = _tiny_lm()
    grab_cfg = GrabConfig() if request.param == "grab" else None
    opt = adamw()
    step = jax.jit(build_train_step(
        lambda p, mb: lm.loss_fn(p, model, mb, remat=True), opt,
        constant(1e-3), grab_cfg, n_micro_per_epoch=8), donate_argnums=(0,))
    state = init_train_state(params, opt, grab_cfg, n_micro_per_epoch=8)
    batch = {k: jnp.zeros((4, 2, 16), jnp.int32) for k in ("tokens", "labels")}
    hlo = step.lower(state, batch).compile().as_text()
    leaf_shapes = {tuple(x.shape) for x in jax.tree.leaves(params)}
    smallest = min(x.size * 4 for x in jax.tree.leaves(params))
    return request.param, _instructions(hlo), leaf_shapes, smallest


def _shape_dims(shape: str):
    m = re.match(r"([a-z]+\d*)\[([\d,]*)\]", shape)
    return m.group(1), tuple(int(d) for d in m.group(2).split(",") if d)


def test_model_dots_fall_under_fwd_bwd(step_hlo):
    """Every matrix product of the model, forward and backward (the remat
    recompute included), is under ``fwd_bwd``."""
    _, ins, _, _ = step_hlo
    dots = [i for i in ins if i["opcode"] == "dot"
            and len(_shape_dims(i["shape"])[1]) >= 2]
    assert dots
    assert {i["scope"] for i in dots} == {"fwd_bwd"}, \
        [(i["name"], i["op_name"]) for i in dots if i["scope"] != "fwd_bwd"]
    names = [i["op_name"] for i in dots]
    assert any("transpose(" in n for n in names)            # backward
    assert any("transpose(" not in n for n in names)        # forward


def test_balance_falls_under_grab_balance(step_hlo):
    """GraB's inner product and its running-sum and fresh-mean updates are
    under ``grab_balance``; the rr step has no such op."""
    arm, ins, leaf_shapes, _ = step_hlo
    bal = [i for i in ins if i["scope"] == "grab_balance"]
    if arm == "rr":
        assert bal == []
        return
    assert any(i["opcode"] == "reduce" for i in bal)
    # per parameter leaf, the f32 updates of s (s + eps * z) and of m_acc
    # (m_acc + g / n): two adds of the leaf's shape
    adds = [_shape_dims(i["shape"]) for i in bal if i["opcode"] == "add"]
    for shape in leaf_shapes:
        assert adds.count(("f32", shape)) >= 2, (shape, adds)


def test_adamw_moments_fall_under_optimizer(step_hlo):
    """AdamW's two moments and the update are under ``optimizer``."""
    _, ins, leaf_shapes, _ = step_hlo
    opt = [i for i in ins if i["scope"] == "optimizer"]
    assert any(i["opcode"] == "sqrt" for i in opt)
    muls = [_shape_dims(i["shape"]) for i in opt if i["opcode"] == "multiply"]
    for shape in leaf_shapes:            # b1 * m, b2 * v and more per leaf
        assert muls.count(("f32", shape)) >= 2, (shape, muls)
    # nothing of the optimizer's squares runs under another scope
    sq = [i for i in ins if i["op_name"] and i["op_name"].endswith("/square")]
    assert sq and {i["scope"] for i in sq} == {"optimizer"}


def test_accumulation_falls_under_grad_accum(step_hlo):
    """The f32 accumulator's adds are under ``grad_accum``."""
    _, ins, leaf_shapes, _ = step_hlo
    adds = [_shape_dims(i["shape"]) for i in ins
            if i["scope"] == "grad_accum" and i["opcode"] == "add"]
    for shape in leaf_shapes:
        assert ("f32", shape) in adds, (shape, adds)


def test_every_large_instruction_is_scoped(step_hlo):
    """Every instruction that the step runs (not a fusion's inside, not
    plumbing) and that carries an ``op_name``, with an output at least as
    large as the smallest parameter leaf in f32, is under one of the five
    scopes. Only scalar bookkeeping (the loop counters, the loss mean,
    ``step + 1``) stays unscoped."""
    _, ins, _, smallest = step_hlo
    run = [i for i in ins if not i["fused"] and i["opcode"] not in PLUMBING
           and i["op_name"] is not None]
    assert run
    big_unscoped = [(i["name"], i["shape"][:40], i["op_name"]) for i in run
                    if i["scope"] is None and _bytes(i["shape"]) >= smallest]
    assert big_unscoped == []


def test_rollover_falls_under_grab_rollover():
    """The loop's epoch-end rollover is a program of its own named
    ``grab_rollover``, as the loop jits it, and whatever of it carries an
    ``op_name`` is under that scope. (Its zero fills are constants the
    compiler broadcasts, and ``m_prev``'s copy the compiler makes: neither
    carries an ``op_name``.)"""
    _, params = _tiny_lm()
    cfg = GrabConfig()
    grab = init_grab_state(params, cfg)
    hlo = jax.jit(grab_rollover, static_argnames="grab_cfg").lower(
        grab, grab_cfg=cfg).compile().as_text()
    assert hlo.startswith("HloModule jit_grab_rollover")
    named = [i for i in _instructions(hlo) if i["op_name"] is not None
             and i["opcode"] not in PLUMBING]
    assert {i["scope"] for i in named} <= {"grab_rollover"}
