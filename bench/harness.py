"""One run of one cell: set-up, the measured window, the comparison with the
reference, and the result.

The window drives ``repro.train.run_training`` — the loop a user's training
job calls — for whole epochs. Epoch 0 is set-up: it compiles the step and the
epoch-end rollover and runs one sign fetch and one reorder. The window runs
from the end of epoch 0 to the first epoch boundary at or after ``seconds``;
a hook at each boundary waits for the state and reads the host clock, so the
loop keeps its one sync per epoch and no more.

What the window ran is checked against ``reference.py``, with the model
module the configuration names (``Layout.model``): the first
``reference_steps`` steps of epoch 0 (the same compiled step and state the
window then drives) and the order of epochs 0 and 1. The probes read the
loop's state after the first and the last of those steps, in set-up; the
reference runs after the window, once the program's state is freed.

A configuration with a ``mesh`` runs on a mesh of the cell's chips, which
``run_training`` is given as ``LoopConfig.mesh``: the traffic's CD-GraB
workers, and the sign wire its ``grab`` block sets, then take the
program's sharded path, and the reference's CD-GraB mode puts one worker
to a chip.
"""
from __future__ import annotations

import gc
import json
import math
import os
import shutil
import sys
import threading
import time

import numpy as np

import data as bench_data
import flops
import reference
import trace_reduce
import trace_scopes
from layout import BENCH_DIR, Layout

WINDOW_SPAN = "bench_window"
SPANS = ("loader_wait", "dispatch", "epoch_reorder", "ckpt_save",
         "sign_fetch", "reorder", "rollover", "epoch_hook")
ORDERINGS = ("grab", "cd-grab", "rr")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class WindowClosed(Exception):
    """Raised by the epoch hook to end ``run_training`` after the window."""


def _loop_locals() -> dict:
    """The locals of the running ``run_training`` frame."""
    f = sys._getframe(1)
    while f is not None and f.f_code.co_name != "run_training":
        f = f.f_back
    if f is None:
        raise RuntimeError("the probe was not called from run_training")
    return f.f_locals


def _norms_fn():
    import jax
    import jax.numpy as jnp

    norm = lambda x: jnp.linalg.norm(x.astype(jnp.float32).ravel())
    leaf = jax.jit(lambda t: jnp.stack([norm(x) for x in jax.tree.leaves(t)]))
    diff = jax.jit(lambda a, b: jnp.stack(
        [norm(x.astype(jnp.float32) - y.astype(jnp.float32))
         for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]))
    return leaf, diff


def worst_gap(prog, ref, mask=None):
    """The largest, over leaves, of |prog - ref| / max(ref, median(ref)),
    and the index of that leaf."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    rel = np.abs(prog - ref) / np.maximum(ref, np.median(ref))
    if mask is not None:
        rel = np.where(mask, rel, -np.inf)
    i = int(np.argmax(rel))
    return float(rel[i]), i


def compare(prog: dict, ref: dict, grab: bool):
    """The numbers compared, from the program's readings and the
    reference's, and for each per-leaf number the index of its worst leaf.
    Leaves whose first reference gradient is under a thousandth of the
    median leaf's move by round-off alone and are left out of the
    parameters' change. Where the reference gives its own CD-GraB signs,
    ``sign_mismatch`` counts the program's that differ from them."""
    live = ref["grad_raw"] >= 1e-3 * np.median(ref["grad_raw"])
    out, worst = {}, {}
    out["loss_gap"] = float(np.max(np.abs(prog["losses"] - ref["losses"])
                                   / np.abs(ref["losses"])))
    out["grad_gap"], worst["grad_gap"] = worst_gap(prog["grad"], ref["grad"])
    out["update_gap"], worst["update_gap"] = worst_gap(
        prog["update"], ref["update"], live)
    if grab:
        out["sum_gap"], i = worst_gap(prog["sum"], ref["sum"])
        if len(ref["sum"]) > 1:          # a CD-GraB sketch sum is one vector
            worst["sum_gap"] = i
    if "signs" in ref:
        out["sign_mismatch"] = float(np.sum(
            np.asarray(prog["signs"]) != np.asarray(ref["signs"])))
    return out, worst


def decide(numbers: dict, limits: dict, nonfinite: int = 0):
    """The checks (each number of ``limits`` beside its limit) and whether
    the run is correct: every loss finite and every number within its limit.
    A number without a limit is read and printed but not compared (PERF.md
    says why for each)."""
    checks = {k: {"value": v, "limit": limits[k]}
              for k, v in numbers.items() if k in limits}
    correct = nonfinite == 0 and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
    return checks, correct


class MemoryWatch:
    """The largest sum, on each chip, of the bytes held in buffers and the
    bytes the runtime reserves for a running program's temporaries, sampled
    every ``period`` seconds on a thread of its own.

    ``peak_bytes_in_use`` leaves the reservation out, and the two peaks that
    ``memory_stats`` keeps fall at different times (the GraB rollover holds
    the most buffers, the step reserves the most temporaries): their sum
    can exceed what the chip holds. So the two are read together."""

    def __init__(self, devices, period: float = 0.002):
        self.devices = devices
        self.peak = [0] * len(devices)
        self._period = period
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        while not self._stop.wait(self._period):
            self._sample()

    def _sample(self):
        for i, d in enumerate(self.devices):
            s = d.memory_stats() or {}
            self.peak[i] = max(self.peak[i], s.get("bytes_in_use", 0)
                               + s.get("bytes_reserved", 0))

    def stop(self) -> None:
        self._stop.set()
        self._thread.join()
        self._sample()


def footprint(stats: list, sampled: list) -> int:
    """Peak bytes of the run on its fullest chip: the largest sampled sum of
    bytes in use and reserved, and never less than the allocator's own peak
    of bytes in use, which no sampling can miss."""
    return int(max(max(s.get("peak_bytes_in_use", 0), p)
                   for s, p in zip(stats, sampled)))


def _make_probe(on_dispatch, last: int):
    """The loop's metrics registry, with a hook on its first ``last``
    dispatches.

    ``phase("dispatch")`` records its timer right after the step has been
    dispatched: at that moment the loop's ``state`` holds that step's output
    and ``batch`` its input. The snapshot of the loop's locals is emptied
    after use: left on the frame, it would keep that state's buffers alive
    into the next epoch."""
    from repro.obs import MetricsRegistry

    class Probe(MetricsRegistry):
        dispatches = 0

        def timer(self, name):
            if name == "phase.dispatch":
                self.dispatches += 1
                if self.dispatches <= last:
                    loc = _loop_locals()
                    try:
                        on_dispatch(self.dispatches, loc)
                    finally:
                        if type(loc) is dict:
                            loc.clear()
            return super().timer(name)

    return Probe(print_events=False)


def read_trace(trace_dir: str) -> dict:
    """``trace_reduce.reduce_events`` of the trace in ``trace_dir`` inside
    the window, with the device seconds of each named scope of the step
    (``scope_s``), of each scope nested in one (``nested_s``) and of the
    rest (``unscoped_s``) by ``trace_scopes.reduce_events``, whose top ops,
    named ``<scope>/<op>``, and idle gaps, labelled ``parent/child``, make
    the breakdown. The trace is read once."""
    ev = trace_scopes.read_events(trace_reduce.find_trace(trace_dir),
                                  SPANS + (WINDOW_SPAN,))
    out = trace_reduce.reduce_events(ev, WINDOW_SPAN)
    scopes = trace_scopes.reduce_events(ev, WINDOW_SPAN)
    for k in ("scope_s", "nested_s", "unscoped_s", "device_ops",
              "idle_gaps"):
        out[k] = scopes[k]
    return out


def make_mesh(devices, shape: dict):
    """The configuration's mesh (axis name -> size) over ``devices``, with
    ``Auto`` axes, as the program's launcher builds its meshes."""
    from jax.sharding import AxisType, Mesh

    return Mesh(np.array(devices).reshape(tuple(shape.values())),
                tuple(shape), axis_types=(AxisType.Auto,) * len(shape))


def _trace_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def run(root: str, workload: str, seed: int, seconds: float, trace: bool,
        t0: float, *, need_chip: bool = True, log=sys.stderr) -> dict:
    """One run; returns ``{"result": <the result line>, "checks": ...}``.
    Raises :class:`NoChip` before any work when the chips are missing."""
    lay = Layout(root)
    cell = lay.cell(workload)
    cfg = lay.config(cell["config"])
    traffic = lay.traffic(cell["traffic"])
    limits = lay.limits(workload)
    ref_model = lay.model(cfg)

    import jax

    devices = jax.devices()
    if need_chip and (devices[0].platform != "tpu"
                      or len(devices) < cell["chips"]):
        raise NoChip(f"JAX found {len(devices)} {devices[0].platform} "
                     f"device(s); the cell {workload} needs {cell['chips']} "
                     f"TPU chip(s)")
    devices = devices[:cell["chips"]]
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, "bench", ".cache", "jax")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    watch = MemoryWatch(devices)

    from repro.configs import get_config
    from repro.core.grab import GrabConfig
    from repro.models import lm
    from repro.optim import adamw, constant
    from repro.train import LoopConfig, run_training

    seed_np = seed % (2 ** 63)
    model = get_config(cfg["program"]["arch"])[0].with_(
        **cfg["program"]["overrides"])
    _check_model(model, ref_model.program_fields(cfg))
    ordering = traffic["ordering"]
    if ordering not in ORDERINGS:
        raise ValueError(f"unknown ordering {ordering!r}")
    grab = ordering != "rr"
    workers = traffic.get("workers", 1)
    mesh = make_mesh(devices, cfg["mesh"]) if cfg.get("mesh") else None
    seq, micro = traffic["seq_len"], traffic["micro"]
    n_micro = traffic["n_micro"]
    spe = traffic["steps_per_epoch"]
    n_units = spe * n_micro
    hp = traffic["optimizer"]
    n_ref = traffic["reference_steps"]

    key = reference.make_key(seed)
    init = jax.jit(lambda k: ref_model.init_params(k, cfg))
    # made on the chip in one call, handed to the loop from the host: the
    # loop copies its arguments in, and a second copy of the weights left on
    # the chip would not fit beside the deepest step that does
    params = jax.device_get(init(key))
    want = jax.eval_shape(lambda: lm.init_lm(jax.random.PRNGKey(0), model))
    got = jax.eval_shape(lambda: params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError("the benchmark's parameters do not have the "
                           "layout of the program's model")
    ds = bench_data.TokenRows(n_units * micro, seq, cfg["vocab_size"], seed_np)

    leaf_norms, diff_norms = _norms_fn()
    prog = {}
    fed = []

    def on_dispatch(n, loc):
        fed.append(loc["batch"])
        if n == 1:
            m = loc["state"].opt.m
            prog["grad"] = np.asarray(jax.device_get(leaf_norms(m)),
                                      np.float64) / (1.0 - hp["b1"])
        if n == n_ref:
            st = loc["state"]
            if mesh is None:
                start = init(key)
            else:
                # the step's temporaries go first; the weights come back
                # in the step's own shardings, never whole on one chip
                jax.block_until_ready(st)
                start = jax.device_put(params, jax.tree.map(
                    lambda x: x.sharding, st.params))
            prog["update"] = np.asarray(jax.device_get(
                diff_norms(st.params, start)), np.float64)
            del start
            if grab:
                prog["sum"] = np.asarray(jax.device_get(
                    leaf_norms(st.grab.s)), np.float64)

    reg = _make_probe(on_dispatch, 2 * spe)
    win = {"epochs": 0, "nonfinite": 0, "compiles": 0}
    counting = [False]

    def on_compile(event, duration, **kw):
        if counting[0] and event == COMPILE_EVENT:
            win["compiles"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    trace_dir = os.path.join(BENCH_DIR, ".runs", "trace")
    ann = []

    def hook(epoch, state, history):
        jax.block_until_ready(state)
        losses = [h["loss"] for h in history if h["epoch"] == epoch]
        if epoch == 0:
            prog["losses"] = np.asarray(losses[:n_ref], np.float64)
            if grab:
                prog["signs0"] = np.asarray(jax.device_get(state.signs))
                prog["grab_bytes"] = sum(
                    x.addressable_shards[0].data.nbytes
                    for x in jax.tree.leaves(state.grab))
            if trace:
                shutil.rmtree(trace_dir, ignore_errors=True)
                # a first trace in a process pays the profiler's own
                # start-up inside it; take that one here, in set-up
                jax.profiler.start_trace(trace_dir,
                                         profiler_options=_trace_options())
                jax.device_get(state.step)
                jax.profiler.stop_trace()
                shutil.rmtree(trace_dir, ignore_errors=True)
                jax.profiler.start_trace(trace_dir,
                                         profiler_options=_trace_options())
                ann.append(jax.profiler.TraceAnnotation(WINDOW_SPAN))
                ann[0].__enter__()
            counting[0] = True
            win["start"] = time.perf_counter()
            return
        win["epochs"] += 1
        win["nonfinite"] += sum(not math.isfinite(x) for x in losses)
        now = time.perf_counter()
        if now - win["start"] >= seconds:
            win["end"] = now
            counting[0] = False
            if trace:
                ann[0].__exit__(None, None, None)
                jax.profiler.stop_trace()
            raise WindowClosed

    opt = adamw(b1=hp["b1"], b2=hp["b2"], eps=hp["eps"],
                weight_decay=hp["weight_decay"], clip_norm=hp["clip_norm"])
    loop_cfg = LoopConfig(
        epochs=2 ** 31 - 1, n_micro=n_micro, ordering=ordering,
        workers=workers, mesh=mesh, log_every=0, seed=seed_np, metrics=reg,
        loader_workers=traffic["loader"]["workers"],
        loader_window=traffic["loader"]["window"],
        loader_buffer=traffic["loader"]["buffer"])
    grab_cfg = GrabConfig(**traffic["grab"]) if grab else None
    remat = traffic["remat"]
    try:
        run_training(lambda p, mb: lm.loss_fn(p, model, mb, remat=remat),
                     params, opt, constant(hp["lr"]), ds, micro, loop_cfg,
                     grab_cfg=grab_cfg, hooks=hook)
        raise RuntimeError("run_training returned before the window closed")
    except WindowClosed:
        pass
    jax.monitoring.unregister_event_duration_listener(on_compile)
    watch.stop()
    setup_s = win["start"] - t0
    window_s = win["end"] - win["start"]
    stats = [d.memory_stats() or {} for d in devices]
    gauges = reg.summary()["gauges"]
    peak = footprint(stats, watch.peak)
    gc.collect()
    _join_threads()

    # --- what the window ran, against the reference -----------------------
    rows = [ds.row_ids(b["tokens"], b["labels"]) for b in fed]
    units = [r.reshape(-1, micro)[:, 0] // micro for r in rows]
    missing = sum(int(np.sum(r < 0)) for r in rows)
    ep0 = np.concatenate(units[:spe])
    ep1 = np.concatenate(units[spe:2 * spe])
    if ordering == "cd-grab":
        order0 = reference.cd_first_order(n_units, workers, seed_np)
        order1 = reference.cd_reorder(order0, prog["signs0"])
    elif grab:
        order0 = reference.first_grab_order(n_units, seed_np)
        order1 = reference.reorder(order0, prog["signs0"])
    if grab:
        order_mismatch = (missing + int(np.sum(ep0 != order0))
                          + int(np.sum(ep1 != order1)))
    else:
        order0 = ep0
        order_mismatch = missing + sum(n_units - len(np.unique(e))
                                       for e in (ep0, ep1))
    steps = _steps(ds, order0, n_ref, n_micro, micro)
    t_ref = time.perf_counter()
    if ordering == "cd-grab":
        prog["signs"] = prog["signs0"][:n_ref * n_micro // workers]
        ref = reference.train_steps_cd(
            lambda: init(key), steps, cfg, hp, workers=workers,
            sketch_dim=traffic["grab"]["sketch_dim"], devices=devices,
            prog_signs=prog["signs"], model=ref_model)
    else:
        ref = reference.train_steps(lambda: init(key), steps, cfg, hp,
                                    grab=grab, model=ref_model)
    numbers, worst = compare(prog, ref, grab)
    numbers["order_mismatch"] = float(order_mismatch)
    names = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_flatten_with_path(jax.eval_shape(
                 lambda: params))[0]]
    ref_s = time.perf_counter() - t_ref

    checks, correct = decide(numbers, limits, win["nonfinite"])

    # --- metrics -----------------------------------------------------------
    tokens = win["epochs"] * spe * n_micro * micro * seq
    peaks = _peaks(lay.bench_dir, devices[0].device_kind)
    per_token = getattr(ref_model, "flops_per_token", flops.per_token)
    record = {
        "setup_s": setup_s, "window_s": window_s, "tokens": tokens,
        "steps": win["epochs"] * spe, "epochs": win["epochs"],
        "chips": len(devices), "peak_bytes": peak, "gauges": gauges,
        "grab_state_bytes": prog.get("grab_bytes"),
        "flops_per_token": per_token(cfg, seq),
        "peak_flops": peaks["bf16_flops_per_s"], "trace": None,
        "config": cfg, "traffic": traffic, "peaks": peaks,
    }
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": jax.device_count(), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": win["epochs"] * spe,
              "failed": win["nonfinite"]}
    kind = "end_to_end"
    if trace:
        kind = "per_layer"
        record["trace"] = read_trace(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = record["trace"]["busy_s"]
        device["window_s"] = record["trace"]["window_s"]
    metrics = {}
    for m in lay.metrics_for(workload, kind):
        v = lay.reader(m["name"])(record)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    if trace:
        result["breakdown"] = {"device_ops": record["trace"]["device_ops"],
                               "idle_gaps": record["trace"]["idle_gaps"]}
    result["checks"] = checks
    print("memory: " + json.dumps(stats) + f"; sampled peak {watch.peak}",
          file=log)
    print(f"window: {win['epochs']} epochs, {window_s:.3f} s, "
          f"{win['compiles']} compiles inside; set-up {setup_s:.3f} s; "
          f"reference {ref_s:.3f} s", file=log)
    if trace:
        tr = record["trace"]
        print("trace: " + json.dumps({
            k: tr[k] for k in ("window_s", "busy_s", "idle_share",
                               "exposed_collective_share", "scope_s",
                               "nested_s", "unscoped_s")}
            | {"steps": record["steps"]}), file=log)
    for k, v in numbers.items():
        if k not in checks:
            print(f"reading {k} {v!r} (not compared)", file=log)
    for k, c in checks.items():
        leaf = f" (worst leaf {names[worst[k]]})" if k in worst else ""
        print(f"check {k} {c['value']!r} limit {c['limit']!r}{leaf}",
              file=log)
    return {"result": result, "numbers": numbers, "window": win,
            "program": prog, "reference": ref, "leaves": names,
            "memory": stats}


def _steps(ds, order, n_steps, n_micro, micro):
    """(tokens, labels) of the first ``n_steps`` steps of ``order``, each
    ``[n_micro, micro, T]``."""
    out = []
    for i in range(n_steps):
        units = order[i * n_micro:(i + 1) * n_micro]
        rows = np.concatenate([np.arange(u * micro, (u + 1) * micro)
                               for u in units])
        b = ds.batch(rows)
        out.append((b["tokens"].reshape(n_micro, micro, -1),
                    b["labels"].reshape(n_micro, micro, -1)))
    return out


def _peaks(bench_dir: str, kind: str) -> dict:
    """The chip's published peaks; a kind not in the table is an error."""
    with open(os.path.join(bench_dir, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def _check_model(model, pairs: dict) -> None:
    """The program's model has each attribute of ``pairs`` at its value:
    the configuration's model module's ``program_fields``."""
    bad = {k: (getattr(model, k), v) for k, v in pairs.items()
           if getattr(model, k) != v}
    if bad:
        raise RuntimeError(f"the program's model differs from the "
                           f"configuration file: {bad}")


def _join_threads(timeout: float = 30.0) -> None:
    """Wait for the loader's threads, which end once their epoch is
    abandoned."""
    deadline = time.monotonic() + timeout
    for t in threading.enumerate():
        if t is not threading.main_thread():
            t.join(max(0.0, deadline - time.monotonic()))
