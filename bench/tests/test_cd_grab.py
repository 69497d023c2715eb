"""The CD-GraB cell at a size a CPU can hold, on four host devices: the
reference's CD-GraB mode follows the program's semantics piece by piece,
a sound run on the mesh (and on the host-simulated path) is correct, and
runs with the timed path broken underneath, the float8 control and the
planted faults are not.

The cell is a small copy of ``phi3-dp4.cdgrab.s1k`` (the same model code,
mesh, traffic and generator; widths cut to 64, rows to 64 tokens, the
sketch to 256 coordinates). Its limits are set from this size's readings
on the CPU, beside the cell's own limits file.
"""
import json
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import calibrate
import harness
import reference
from layout import Layout

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "phi3-dp4.cdgrab.s1k"
DENSE = {"hidden_size": 64, "intermediate_size": 128,
         "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
         "num_hidden_layers": 2, "vocab_size": 500}
PROGRAM_KEYS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
                "num_attention_heads": "n_heads",
                "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
                "num_hidden_layers": "n_layers", "vocab_size": "vocab"}
# CPU readings at this size (seeds 3, 5, 7, mesh and host-simulated):
# sound runs read at most 1.75e-4 on the losses, 1.15e-3 on the gradients,
# 3.46e-3 on the parameters' change and 5.23e-3 on the running sum, and no
# sign or position differs; the float8 control reads 8.7e-3 to 1.3e-2 on
# the gradients and 3.5e-2 to 5.9e-2 on the running sum (seeds 3, 5), and
# worker 0's row left out of the scan 4.5e-2 to 0.13 on the running sum.
LIMITS = {"loss_gap": 1e-3, "grad_gap": 5e-3, "update_gap": 8e-3,
          "sum_gap": 2e-2, "sign_mismatch": 0, "order_mismatch": 0}
CELLS = {"d.cd": ("tiny-dp4", 4), "d.cd.host": ("tiny-host", 1)}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    assert jax.device_count() >= 4, "conftest.py asks for 4 host devices"
    r = tmp_path_factory.mktemp("bench_root")
    shutil.copytree(os.path.join(ROOT, "bench"), r / "bench",
                    ignore=shutil.ignore_patterns(".cache", ".runs",
                                                  "__pycache__"))
    b = r / "bench"
    base = json.loads((b / "configs" / "phi3-mini-3.8b.dp4.json")
                      .read_text())
    configs = []
    for name, mesh in (("tiny-dp4", base["mesh"]), ("tiny-host", None)):
        cfg = dict(base, name=name, mesh=mesh, **DENSE)
        cfg["program"] = {"arch": "phi3-mini-3.8b", "overrides": {
            PROGRAM_KEYS[k]: v for k, v in DENSE.items()}}
        (b / "configs" / f"{name}.json").write_text(json.dumps(cfg))
        configs.append({"name": name, "source": "test",
                        "file": f"bench/configs/{name}.json", "reduced": [],
                        "why": "test"})
    t = json.loads((b / "traffic" / "cdgrab.s1k.json").read_text())
    t["seq_len"] = 64
    t["grab"]["sketch_dim"] = 256
    (b / "traffic" / "tiny.cd.json").write_text(json.dumps(t))
    spec = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    spec["configs"] = configs
    spec["workloads"] = [{"name": n, "config": c, "traffic": "tiny.cd",
                          "chips": chips, "why": "test"}
                         for n, (c, chips) in CELLS.items()]
    for m in spec["per_layer"]:
        m.pop("workloads", None)
    (r / "BENCHMARK.json").write_text(json.dumps(spec))
    for n in CELLS:
        (b / "limits" / f"{n}.json").write_text(json.dumps(LIMITS))
    peaks = json.loads((b / "peaks.json").read_text())
    peaks["devices"]["cpu"] = {"bf16_flops_per_s": 1e12,
                               "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
    (b / "peaks.json").write_text(json.dumps(peaks))
    jax.config.update("jax_enable_compilation_cache", False)
    return str(r)


def _run(root, cell, seed=3):
    return harness.run(root, cell, seed, 0.0, False, time.perf_counter(),
                       need_chip=False)


@pytest.fixture(scope="module")
def sound(root):
    return {cell: _run(root, cell) for cell in CELLS}


# --- the reference's pieces against the program's -------------------------

def _params(root):
    cfg = Layout(root).config("tiny-dp4")
    return reference.init_params(jax.random.PRNGKey(0), cfg)


def test_sketch_indices_are_the_programs(root):
    from repro.core.grab import make_sketch

    params = _params(root)
    leaves = jax.tree.leaves(params)
    for k in (256, 1024, 7):
        sk = make_sketch(params, k)
        ours = reference.sketch_indices([x.shape for x in leaves], k)
        for leaf, idx, flat in zip(leaves, sk.leaf_idx, ours):
            if idx is None:
                assert flat is None
                continue
            want = np.ravel_multi_index([np.asarray(i) for i in idx],
                                        leaf.shape)
            np.testing.assert_array_equal(flat, want)


def test_int8_rows_and_sign_scan_are_the_programs():
    from repro.core.distributed import coordinated_pair_signs, quantize_wire

    rng = np.random.default_rng(0)
    z = rng.standard_normal((4, 256)).astype(np.float32)
    z[2] = 0.0
    rows = reference.int8_rows(z)
    np.testing.assert_array_equal(rows, np.asarray(quantize_wire(z)))
    s = rng.standard_normal(256).astype(np.float32)
    new_s, signs = coordinated_pair_signs(jnp.asarray(s), jnp.asarray(z),
                                          impl="xla", wire="int8")
    ref_s, ref_signs = reference.sign_scan(s, rows)
    np.testing.assert_array_equal(ref_signs, np.asarray(signs))
    np.testing.assert_allclose(ref_s, np.asarray(new_s), rtol=1e-5)
    # a forced sign moves the sum and leaves the sign read as it was
    forced = -ref_signs
    f_s, f_signs = reference.sign_scan(s, rows[:1], forced[:1])
    assert f_signs[0] == ref_signs[0]
    np.testing.assert_allclose(f_s, s + forced[0] * rows[0], rtol=1e-6)


def test_orders_are_parallel_grab_orders():
    from repro.core.orderings import ParallelGrabOrder

    n, w, seed = 64, 4, 2 ** 33 + 5
    pol = ParallelGrabOrder(n, workers=w, seed=seed)
    order0 = reference.cd_first_order(n, w, seed)
    np.testing.assert_array_equal(order0, pol.epoch_order(0))
    signs = np.zeros((n // w, w), np.int8)
    signs[1::2] = np.random.default_rng(1).choice([-1, 1], (n // w // 2, w))
    pol.record_step_signs(signs)
    pol.end_epoch(0)
    np.testing.assert_array_equal(reference.cd_reorder(order0, signs),
                                  pol.epoch_order(1))


# --- whole runs -------------------------------------------------------------

@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sound_run_is_correct(sound, cell):
    out = sound[cell]
    res = out["result"]
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] == 8
    assert res["checks"]["sign_mismatch"]["value"] == 0
    assert res["checks"]["order_mismatch"]["value"] == 0
    assert list(res)[-1] == "checks"
    assert {"tokens_per_s", "setup_s"} <= set(res["metrics"])
    assert res["device"]["count"] == jax.device_count()


def test_mesh_and_host_paths_take_the_same_signs(sound):
    mesh, host = (sound[c]["program"] for c in ("d.cd", "d.cd.host"))
    assert mesh["signs0"].shape == (16, 4)
    np.testing.assert_array_equal(mesh["signs0"], host["signs0"])
    assert np.all(mesh["signs0"][0::2] == 0)
    assert set(np.unique(mesh["signs0"][1::2])) <= {-1, 1}
    np.testing.assert_array_equal(sound["d.cd"]["reference"]["signs"],
                                  mesh["signs"])


def test_state_is_sharded_over_the_chips(sound):
    # one worker row of the pair stash per chip: a quarter of the host
    # path's stash bytes on a chip, the [k] sum and clock beside it
    mesh, host = (sound[c]["program"]["grab_bytes"]
                  for c in ("d.cd", "d.cd.host"))
    assert mesh < 0.3 * host


def _patch_step(monkeypatch, fault):
    """Break the program's compiled step: ``frozen`` returns the training
    state unchanged (the sign buffer and the GraB clock still advance);
    ``half`` trains on the first half of each step's microbatches, each
    counted twice; ``worker`` gives worker 3's microbatches worker 0's."""
    from repro.launch import live

    real = live.build_train_step

    def build(*a, **kw):
        step = real(*a, **kw)

        def broken(state, batch):
            if fault == "half":
                n = jax.tree.leaves(batch)[0].shape[0] // 2
                batch = jax.tree.map(
                    lambda x: jnp.concatenate([x[:n], x[:n]]), batch)
            elif fault == "worker":
                batch = jax.tree.map(
                    lambda x: x.at[3::4].set(x[0::4]), batch)
            new, metrics = step(state, batch)
            if fault != "frozen":
                return new, metrics
            grab = state.grab._replace(t=new.grab.t)
            return state._replace(signs=new.signs, grab=grab), metrics
        return broken

    monkeypatch.setattr(live, "build_train_step", build)


def _patch_exchange(monkeypatch):
    """Leave the exchange between chips out: each chip's sign scan sees its
    own row in every worker's place."""
    from repro.core import distributed

    monkeypatch.setattr(
        distributed, "hier_all_gather",
        lambda x, axis_name, *, axis, total, hier_group=0:
            jnp.concatenate([x] * total, axis=axis))


def _patch_flip(monkeypatch):
    """Invert the program's sign rule: -1 where <s, z> <= 0."""
    from repro.core import distributed

    monkeypatch.setattr(
        distributed, "deterministic_sign",
        lambda dot: jnp.where(dot <= 0, jnp.int32(-1), jnp.int32(1)))


def _patch_token(monkeypatch):
    """Alter one token of every batch where the loader produces it."""
    from repro.data import prefetch

    real = prefetch.WindowPrefetcher._assemble

    def assemble(self, micros):
        out = real(self, micros)
        out["tokens"] = out["tokens"].copy()
        out["tokens"][..., 5] = (out["tokens"][..., 5] + 1) % 500
        return out

    monkeypatch.setattr(prefetch.WindowPrefetcher, "_assemble", assemble)


@pytest.mark.parametrize("fault", ["frozen", "half", "worker", "exchange",
                                   "token", "flip"])
def test_broken_step_is_not_correct(root, fault, monkeypatch):
    if fault == "exchange":
        _patch_exchange(monkeypatch)
    elif fault == "flip":
        _patch_flip(monkeypatch)
    elif fault == "token":
        _patch_token(monkeypatch)
    else:
        _patch_step(monkeypatch, fault)
    res = _run(root, "d.cd")["result"]
    assert not res["correct"], res["checks"]
    cell_limits = Layout(ROOT).limits(CELL)
    out = {k: c["value"] for k, c in res["checks"].items()}
    assert not harness.decide(out, cell_limits)[1], out


def test_a_flipped_sign_rule_is_caught_by_the_signs_alone(root, monkeypatch):
    """The order of epoch 1 follows the program's own signs and the
    reference's sum takes them too, so only ``sign_mismatch`` sees a sign
    rule turned round: every balance sign of the compared steps differs."""
    _patch_flip(monkeypatch)
    out = _run(root, "d.cd")
    nums = out["numbers"]
    assert nums["order_mismatch"] == 0 and nums["sum_gap"] < LIMITS["sum_gap"]
    assert nums["sign_mismatch"] == np.count_nonzero(out["program"]["signs"])
    assert nums["sign_mismatch"] > Layout(ROOT).limits(CELL)["sign_mismatch"]


@pytest.fixture(scope="module")
def stand_ins(root):
    return calibrate.reference_in_place(root, "d.cd", 3, list(
        calibrate.STAND_INS))


@pytest.mark.parametrize("mode", ["control", "half", "sign", "flip"])
def test_stand_ins_are_not_correct(stand_ins, mode):
    nums = stand_ins[mode]
    assert "sign_mismatch" in nums and "sum_gap" in nums
    for limits in (LIMITS, Layout(ROOT).limits(CELL)):
        checks, correct = harness.decide(nums, limits)
        assert not correct, checks


# --- the layout ------------------------------------------------------------

ONE_CHIP = {
    "phi3.grab.s512": ["data.loader_wait_ms", "loop.epoch_boundary_ms",
                       "step.mfu", "device.idle_share", "step.temp_gib",
                       "grab.state_gib"],
    "phi3.rr.s512": ["data.loader_wait_ms", "step.mfu", "device.idle_share",
                     "step.temp_gib"],
}
ADDED = {"phi3.grab.s512": ["step.fwd_bwd_ms", "step.optimizer_ms",
                            "grab.balance_ms"],
         "phi3.rr.s512": ["step.fwd_bwd_ms", "step.grad_accum_ms",
                          "step.optimizer_ms"]}


@pytest.mark.parametrize("cell", sorted(ONE_CHIP))
def test_one_chip_cells_gain_only_the_added_metrics(cell):
    lay = Layout(ROOT)
    names = [m["name"] for m in lay.metrics_for(cell, "per_layer")]
    assert [n for n in names if n in ONE_CHIP[cell]] == ONE_CHIP[cell]
    assert sorted(set(names) - set(ONE_CHIP[cell])) == sorted(ADDED[cell])
    assert [m["name"] for m in lay.metrics_for(cell, "end_to_end")] == [
        "tokens_per_s", "peak_hbm_gib", "setup_s"]


def test_four_chip_cell_reports_its_layers():
    lay = Layout(ROOT)
    names = {m["name"] for m in lay.metrics_for(CELL, "per_layer")}
    assert names == {
        "data.loader_wait_ms", "loop.epoch_boundary_ms", "step.mfu",
        "device.idle_share", "step.temp_gib", "grab.state_gib",
        "device.exposed_collective_ms", "step.fwd_bwd_ms",
        "step.grad_accum_ms", "step.optimizer_ms", "grab.balance_ms"}
    assert lay.cell(CELL)["chips"] == 4
    assert lay.config(lay.cell(CELL)["config"])["mesh"] == {"data": 4,
                                                             "model": 1}
