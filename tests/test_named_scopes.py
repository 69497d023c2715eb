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
import collections

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
from _hlo import hlo_bytes, instructions, microbatch_loop, shape_dims

# instructions that only route buffers or hold a loop's body
PLUMBING = ("parameter", "get-tuple-element", "tuple", "constant", "bitcast",
            "while", "call", "conditional")


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
    leaf_shapes = collections.Counter(
        tuple(x.shape) for x in jax.tree.leaves(params))
    smallest = min(x.size * 4 for x in jax.tree.leaves(params))
    return request.param, instructions(hlo), leaf_shapes, smallest


def test_model_dots_fall_under_fwd_bwd(step_hlo):
    """Every matrix product of the model, forward and backward (the remat
    recompute included), is under ``fwd_bwd``."""
    _, ins, _, _ = step_hlo
    dots = [i for i in ins if i["opcode"] == "dot"
            and len(shape_dims(i["shape"])[1]) >= 2]
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
    # per parameter leaf, the f32 updates of s (s + eps * g) and of m_acc
    # (m_acc + g / n): two adds of the leaf's shape
    adds = [shape_dims(i["shape"]) for i in bal if i["opcode"] == "add"]
    for shape in leaf_shapes:
        assert adds.count(("f32", shape)) >= 2, (shape, adds)


def _closure(ins, roots, loops: bool) -> set:
    """The computations ``roots`` reach through calls and, if ``loops``,
    through loop conditions and bodies."""
    edges = collections.defaultdict(list)
    for i in ins:
        edges[i["comp"]] += i["calls"] + (i["loops"] if loops else [])
    seen, todo = set(), list(roots)
    while todo:
        comp = todo.pop()
        if comp not in seen:
            seen.add(comp)
            todo += edges[comp]
    return seen


def test_fresh_mean_folds_once_after_the_microbatch_loop(step_hlo):
    """GraB's fresh mean ``m_acc`` is folded from the step's f32 gradient
    sum once, after the microbatch loop: inside the loop each parameter
    leaf has one f32 add under ``grab_balance`` (the running sum ``s``) and
    none of ``m_acc``; outside it, exactly one more (the fold)."""
    arm, ins, leaf_shapes, _ = step_hlo
    if arm == "rr":
        return
    entry = {i["comp"] for i in ins if i["entry"]}
    bodies = [c for i in ins if i["entry"] for c in i["loops"]]
    assert bodies                            # the microbatch loop
    inside = _closure(ins, bodies, loops=True)
    outside = _closure(ins, entry, loops=False)

    def balance_adds(comps):
        return collections.Counter(
            shape_dims(i["shape"])[1] for i in ins
            if i["comp"] in comps and i["scope"] == "grab_balance"
            and i["opcode"] == "add" and shape_dims(i["shape"])[0] == "f32"
            and shape_dims(i["shape"])[1] in leaf_shapes)

    assert balance_adds(inside) == leaf_shapes
    assert balance_adds(outside) == leaf_shapes


def test_running_sum_update_reads_no_stale_mean(step_hlo):
    """GraB keeps its running sum uncentered (``s = sum eps g`` beside
    ``e = sum eps``), so the stale mean ``m_prev`` is read once a
    microbatch, by the inner product, and not again by the running sum's
    update: in the microbatch loop's body each ``m_prev`` leaf has one
    reader, under ``grab_balance``, and nothing computed elementwise from it
    leaves the body. (Centered, ``s + eps (g - m_prev)`` carries a value
    computed from every leaf of ``m_prev`` out of the body.)"""
    arm, ins, leaf_shapes, _ = step_hlo
    if arm == "rr":
        return
    loop = microbatch_loop(ins)
    assert len(loop.stale_mean) == sum(leaf_shapes.values())
    for leaf in loop.stale_mean:
        direct = loop.reads(leaf["name"])
        assert [r["scope"] for r in direct] == ["grab_balance"], \
            (leaf["name"], [(r["name"], r["op_name"]) for r in direct])
        # what is computed elementwise from the leaf (as large as it, or a
        # tuple holding such): none of it is a value the loop carries
        size = int(np.prod(shape_dims(leaf["shape"])[1]))
        todo, seen = list(direct), set()
        while todo:
            i = todo.pop()
            if i["name"] in seen:
                continue
            seen.add(i["name"])
            assert i["name"] not in loop.root["operands"], \
                (leaf["name"], i["name"], i["op_name"])
            todo += [r for r in loop.reads(i["name"])
                     if r["shape"].startswith("(")
                     or np.prod(shape_dims(r["shape"])[1]) >= size]


def test_adamw_moments_fall_under_optimizer(step_hlo):
    """AdamW's two moments and the update are under ``optimizer``."""
    _, ins, leaf_shapes, _ = step_hlo
    opt = [i for i in ins if i["scope"] == "optimizer"]
    assert any(i["opcode"] == "sqrt" for i in opt)
    muls = [shape_dims(i["shape"]) for i in opt if i["opcode"] == "multiply"]
    for shape in leaf_shapes:            # b1 * m, b2 * v and more per leaf
        assert muls.count(("f32", shape)) >= 2, (shape, muls)
    # nothing of the optimizer's squares runs under another scope
    sq = [i for i in ins if i["op_name"] and i["op_name"].endswith("/square")]
    assert sq and {i["scope"] for i in sq} == {"optimizer"}


def test_accumulation_falls_under_grad_accum(step_hlo):
    """The f32 accumulator's adds are under ``grad_accum``."""
    _, ins, leaf_shapes, _ = step_hlo
    adds = [shape_dims(i["shape"]) for i in ins
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
                    if i["scope"] is None
                    and hlo_bytes(i["shape"]) >= smallest]
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
    named = [i for i in instructions(hlo) if i["op_name"] is not None
             and i["opcode"] not in PLUMBING]
    assert {i["scope"] for i in named} <= {"grab_rollover"}
