"""``correct`` at a size a CPU can hold: a sound run passes; a run with the
timed path broken underneath, and the float8 control, do not.

The cells here are small copies of the benchmark's (the same model code and
traffic generator, widths cut to 64) with limits set from these sizes'
readings on the CPU; the harness runs them with its look for a chip
skipped.
"""
import json
import os
import shutil
import time

import jax
import pytest

import calibrate
import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DENSE = {"hidden_size": 64, "intermediate_size": 128,
         "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
         "num_hidden_layers": 2, "vocab_size": 500}
MOE = {"hidden_size": 64, "intermediate_size": 32, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
       "vocab_size": 500, "num_local_experts": 4, "num_experts_per_tok": 2,
       "expert_group_tokens": 16, "expert_capacity_factor": 1.25,
       "router_aux_loss_coef": 0.01}
PROGRAM_KEYS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
                "num_attention_heads": "n_heads",
                "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
                "num_hidden_layers": "n_layers", "vocab_size": "vocab",
                "num_local_experts": "moe_experts",
                "num_experts_per_tok": "moe_topk",
                "expert_group_tokens": "moe_group"}
# CPU readings at these sizes (seeds 1-3, three reference steps): sound runs
# read at most 7.2e-4 on the losses, 5.3e-3 on the gradients, 3.7e-3 on the
# parameters' change and 1.3e-2 on the running sum; the float8 control reads
# 1.4e-2 to 6.4e-2 on the gradients and 2.3e-2 to 0.13 on the running sum.
LIMITS = {"loss_gap": 5e-3, "grad_gap": 8e-3, "update_gap": 8e-3,
          "sum_gap": 4e-2, "order_mismatch": 0.0}
CELLS = {"d.grab": ("tiny-dense", "tiny.grab"),
         "d.rr": ("tiny-dense", "tiny.rr"),
         "m.grab": ("tiny-moe", "tiny.grab")}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = tmp_path_factory.mktemp("bench_root")
    shutil.copytree(os.path.join(ROOT, "bench"), r / "bench",
                    ignore=shutil.ignore_patterns(".cache", ".runs",
                                                  "__pycache__"))
    b = r / "bench"
    configs = []
    # both take the dense file's dtype, norm and rotary settings, which the
    # program's granite-moe architecture shares
    for name, sizes, arch in (("tiny-dense", DENSE, "phi3-mini-3.8b"),
                              ("tiny-moe", MOE, "granite-moe-3b-a800m")):
        cfg = json.loads((b / "configs" / "phi3-mini-3.8b.1chip.json")
                         .read_text())
        cfg.update(sizes, name=name)
        cfg["program"] = {"arch": arch, "overrides": {
            PROGRAM_KEYS[k]: v for k, v in sizes.items()
            if k in PROGRAM_KEYS}}
        (b / "configs" / f"{name}.json").write_text(json.dumps(cfg))
        configs.append({"name": name, "source": "test",
                        "file": f"bench/configs/{name}.json", "reduced": [],
                        "why": "test"})
    for name, base in (("tiny.grab", "grab.s512"), ("tiny.rr", "rr.s512")):
        t = json.loads((b / "traffic" / f"{base}.json").read_text())
        t.update(seq_len=64, n_micro=4, steps_per_epoch=4)
        (b / "traffic" / f"{name}.json").write_text(json.dumps(t))
    spec = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    spec["configs"] = configs
    spec["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": 1,
                          "why": "test"} for n, (c, t) in CELLS.items()]
    for m in spec["per_layer"]:
        m.pop("workloads", None)
    (r / "BENCHMARK.json").write_text(json.dumps(spec))
    for n, (_, t) in CELLS.items():
        lim = dict(LIMITS)
        if t == "tiny.rr":
            del lim["sum_gap"]
        (b / "limits" / f"{n}.json").write_text(json.dumps(lim))
    peaks = json.loads((b / "peaks.json").read_text())
    peaks["devices"]["cpu"] = {"bf16_flops_per_s": 1e12,
                               "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
    (b / "peaks.json").write_text(json.dumps(peaks))
    jax.config.update("jax_enable_compilation_cache", False)
    return str(r)


def _run(root, cell, seed=3):
    return harness.run(root, cell, seed, 0.0, False, time.perf_counter(),
                       need_chip=False)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sound_run_is_correct(root, cell):
    out = _run(root, cell)
    res = out["result"]
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 4
    assert list(res)[-1] == "checks"
    assert {"tokens_per_s", "setup_s"} <= set(res["metrics"])


def _broken_step(monkeypatch, fault):
    """Break the loop's compiled step: ``frozen`` returns the training
    state unchanged (the sign buffer and the GraB clock still advance, so
    the loop goes on); ``half`` trains on the first half of each step's
    microbatches, each counted twice, so the mean is over that half."""
    from repro.train import loop

    real = loop.build_train_step

    def build(*a, **kw):
        step = real(*a, **kw)

        def broken(state, batch):
            if fault == "half":
                n = jax.tree.leaves(batch)[0].shape[0] // 2
                batch = jax.tree.map(
                    lambda x: jax.numpy.concatenate([x[:n], x[:n]]), batch)
                return step(state, batch)
            new, metrics = step(state, batch)
            grab = (state.grab._replace(t=new.grab.t)
                    if state.grab is not None else None)
            return state._replace(signs=new.signs, grab=grab), metrics
        return broken

    monkeypatch.setattr(loop, "build_train_step", build)


@pytest.mark.parametrize("cell", ["d.grab", "d.rr"])
@pytest.mark.parametrize("fault", ["frozen", "half"])
def test_broken_step_is_not_correct(root, cell, fault, monkeypatch):
    _broken_step(monkeypatch, fault)
    res = _run(root, cell)["result"]
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_float8_control_is_not_correct(root, cell):
    nums = calibrate.reference_in_place(root, cell, 3, ["control"])["control"]
    lim = json.loads(open(os.path.join(root, "bench", "limits",
                                       f"{cell}.json")).read())
    checks, correct = harness.decide(nums, lim)
    assert not correct, checks
