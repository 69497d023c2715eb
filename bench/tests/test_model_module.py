"""A configuration brings its own model module as a new file: the harness's
model check, layout check, reference and FLOP count follow it, readers see
the configuration, traffic and peaks, and with no module the default model
of ``reference.py`` keeps the pairs the harness always checked.

The module here is ``fixtures/tied_model.py``: ``reference.py``'s model
with its output head tied to the embedding, which the program runs through
its ``tie_embeddings`` path. Sizes, traffic and limits are
``test_correct.py``'s (limits set there from CPU readings at these sizes).
"""
import json
import os
import shutil
import time

import jax
import pytest

import calibrate
import flops
import harness
import reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "tied_model.py")
SIZES = {"hidden_size": 64, "intermediate_size": 128,
         "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
         "num_hidden_layers": 2, "vocab_size": 500,
         "tie_word_embeddings": True}
OVERRIDES = {"d_model": 64, "d_ff": 128, "n_heads": 4, "n_kv_heads": 4,
             "head_dim": 16, "n_layers": 2, "vocab": 500,
             "tie_embeddings": True}
LIMITS = {"loss_gap": 5e-3, "grad_gap": 8e-3, "update_gap": 8e-3,
          "sum_gap": 4e-2, "order_mismatch": 0.0}
FLOPS = 1.25e9
# module file -> what it adds to the fixture; None: no module (the default)
MODULES = {
    "tiny-tied": "",
    "tiny-tied-default": None,
    "tiny-tied-wrong": ("\n\ndef program_fields(cfg):\n"
                        "    return dict(reference.program_fields(cfg),"
                        " qkv_bias=True)\n"),
    "tiny-tied-flops": ("\n\ndef flops_per_token(cfg, seq_len):\n"
                        f"    return {FLOPS!r}\n"),
}
CELLS = {"tt.grab": ("tiny-tied", "tiny.grab"),
         "tt.rr": ("tiny-tied", "tiny.rr"),
         "tt.default": ("tiny-tied-default", "tiny.rr"),
         "tt.wrong": ("tiny-tied-wrong", "tiny.rr"),
         "tt.flops": ("tiny-tied-flops", "tiny.rr")}
# readers added as new files: the record's FLOP count, and a roofline-like
# quotient of the peaks' HBM bandwidth by sizes from the configuration and
# the traffic
READERS = {
    "test.flops_per_token": "def read(run):\n"
                            "    return run['flops_per_token']\n",
    "test.record": "def read(run):\n"
                   "    return (run['peaks']['hbm_bytes_per_s']\n"
                   "            / run['config']['hidden_size']\n"
                   "            / run['traffic']['seq_len'])\n",
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = tmp_path_factory.mktemp("bench_root")
    shutil.copytree(os.path.join(ROOT, "bench"), r / "bench",
                    ignore=shutil.ignore_patterns(".cache", ".runs",
                                                  "__pycache__"))
    b = r / "bench"
    (b / "models").mkdir()
    base = json.loads((b / "configs" / "phi3-mini-3.8b.1chip.json")
                      .read_text())
    configs = []
    for name, extra in MODULES.items():
        cfg = dict(base, **SIZES, name=name)
        cfg["program"] = {"arch": "phi3-mini-3.8b", "overrides": OVERRIDES}
        if extra is not None:
            path = f"bench/models/{name}.py"
            with open(FIXTURE) as f:
                (r / path).write_text(f.read() + extra)
            cfg["reference"] = path
        (b / "configs" / f"{name}.json").write_text(json.dumps(cfg))
        configs.append({"name": name, "source": "test",
                        "file": f"bench/configs/{name}.json", "reduced": [],
                        "why": "test"})
    for name, src in (("tiny.grab", "grab.s512"), ("tiny.rr", "rr.s512")):
        t = json.loads((b / "traffic" / f"{src}.json").read_text())
        t.update(seq_len=64, n_micro=4, steps_per_epoch=4)
        (b / "traffic" / f"{name}.json").write_text(json.dumps(t))
    for name, text in READERS.items():
        (b / "metrics" / f"{name}.py").write_text(text)
    spec = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    spec["configs"] = configs
    spec["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": 1,
                          "why": "test"} for n, (c, t) in CELLS.items()]
    spec["end_to_end"] += [{"name": n, "unit": "x", "better": "higher",
                            "bound": 0.01, "source": "host_clock",
                            "workloads": ["tt.grab", "tt.rr", "tt.flops"]}
                           for n in READERS]
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


@pytest.mark.parametrize("cell", ["tt.grab", "tt.rr"])
def test_tied_module_runs_correct(root, cell):
    out = _run(root, cell)
    res = out["result"]
    assert res["correct"], res["checks"]
    # the module's parameters passed the layout check: no output head
    assert not any("lm_head" in n for n in out["leaves"])
    # a module with no FLOP count of its own is counted by flops.py
    cfg = json.loads(open(os.path.join(root, "bench", "configs",
                                       "tiny-tied.json")).read())
    assert res["metrics"]["test.flops_per_token"]["value"] == \
        flops.per_token(cfg, 64)
    assert res["metrics"]["test.record"]["value"] == 1e11 / 64 / 64


def test_tied_config_without_its_module_is_refused_at_the_layout_check(
        root):
    with pytest.raises(RuntimeError, match="layout of the program's model"):
        _run(root, "tt.default")


def test_module_whose_fields_disagree_is_refused_by_the_model_check(root):
    with pytest.raises(RuntimeError, match="qkv_bias"):
        _run(root, "tt.wrong")


def test_module_flops_reach_the_record_and_readers_see_config_and_peaks(
        root):
    res = _run(root, "tt.flops")["result"]
    assert res["correct"], res["checks"]
    assert res["metrics"]["test.flops_per_token"]["value"] == FLOPS
    assert res["metrics"]["test.record"]["value"] == 1e11 / 64 / 64


def test_calibrate_runs_the_control_through_the_module(root, monkeypatch):
    """The float8 control and the reference that judges it both run the
    configuration's model module, and the control is not correct."""
    seen = []
    real = reference.train_steps

    def spy(*a, **kw):
        seen.append(kw["model"].__name__)
        return real(*a, **kw)

    monkeypatch.setattr(reference, "train_steps", spy)
    nums = calibrate.reference_in_place(root, "tt.grab", 3,
                                        ["control"])["control"]
    assert seen == ["bench_model_tiny_tied"] * 2
    checks, correct = harness.decide(nums, LIMITS)
    assert not correct, checks


# the pairs that the harness checked before a configuration could name a
# module, at each configuration file's sizes
PHI3 = {"d_model": 3072, "n_heads": 32, "n_kv_heads": 32, "hd": 96,
        "d_ff": 8192, "vocab": 32064, "padded_vocab": 32256,
        "norm_eps": 1e-05, "rope_theta": 10000.0, "tie_embeddings": False,
        "param_dtype": "bfloat16", "moe_experts": 0, "moe_topk": 0,
        "sliding_window": None, "q_head_pad": 0}
MOE = {"hidden_size": 64, "intermediate_size": 32, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
       "vocab_size": 500, "num_local_experts": 4, "num_experts_per_tok": 2,
       "expert_group_tokens": 16, "expert_capacity_factor": 1.25,
       "router_aux_loss_coef": 0.01}


@pytest.mark.parametrize("name, sizes, pairs", [
    ("phi3-mini-3.8b.1chip", {}, dict(PHI3, n_layers=2)),
    ("phi3-mini-3.8b.dp4", {}, dict(PHI3, n_layers=3)),
    ("phi3-mini-3.8b.1chip", MOE, {
        "d_model": 64, "n_heads": 4, "n_kv_heads": 2, "hd": 16, "d_ff": 32,
        "n_layers": 2, "vocab": 500, "padded_vocab": 512, "norm_eps": 1e-05,
        "rope_theta": 10000.0, "tie_embeddings": False,
        "param_dtype": "bfloat16", "moe_experts": 4, "moe_topk": 2,
        "sliding_window": None, "q_head_pad": 0, "moe_capacity": 1.25,
        "moe_group": 16}),
])
def test_default_module_checks_the_same_pairs(name, sizes, pairs):
    from layout import Layout

    lay = Layout(ROOT)
    cfg = dict(json.loads(open(os.path.join(
        ROOT, "bench", "configs", f"{name}.json")).read()), **sizes)
    assert "reference" not in cfg
    assert lay.model(cfg) is reference
    assert reference.program_fields(cfg) == pairs
