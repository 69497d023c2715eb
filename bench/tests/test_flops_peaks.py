"""FLOPs per token against hand counts, the peaks table, and the refusal to
run without a chip."""
import json
import os
import subprocess
import sys

import pytest

import flops
import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _cfg(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


def test_phi3_flops_per_token():
    cfg = _cfg("phi3-mini-3.8b.1chip")
    # per layer: q, k, v, o 4 x 3072^2; SwiGLU 3 x 3072 x 8192
    layer = 4 * 3072 * 3072 + 3 * 3072 * 8192
    n = cfg["num_hidden_layers"] * layer + 3072 * 32064       # + output head
    attn = 12 * cfg["num_hidden_layers"] * 3072 * 512
    assert flops.per_token(cfg, 512) == 6 * n + attn
    assert cfg["num_hidden_layers"] == 2
    assert flops.per_token(cfg, 512) == pytest.approx(1.9877e9, rel=1e-4)


# granite-3.0-3b-a800m's published widths, at 3 of its 32 layers
GRANITE = {"hidden_size": 1536, "intermediate_size": 512,
           "num_attention_heads": 24, "num_key_value_heads": 8,
           "num_hidden_layers": 3, "num_local_experts": 40,
           "num_experts_per_tok": 8, "vocab_size": 49155}


def test_granite_flops_count_active_experts_only():
    cfg = GRANITE
    layers = cfg["num_hidden_layers"]
    # q, o 1536 x (24 x 64); k, v 1536 x (8 x 64); 8 of 40 SwiGLU experts of
    # width 512; the router 1536 x 40
    layer = (2 * 1536 * 1536 + 2 * 1536 * 512 + 8 * 3 * 1536 * 512
             + 1536 * 40)
    n = layers * layer + 1536 * 49155
    attn = 12 * layers * 1536 * 4096
    assert flops.per_token(cfg, 4096) == 6 * n + attn


def test_peaks_table_has_v5e_and_refuses_unknown_kinds():
    bench = os.path.join(ROOT, "bench")
    v5e = harness._peaks(bench, "TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with open(os.path.join(bench, "peaks.json")) as f:
        assert "TPU v5e" in json.load(f)["source"]
    with pytest.raises(KeyError):
        harness._peaks(bench, "TPU v9 imaginary")


def test_run_exits_nonzero_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "phi3.grab.s512", "--seed", "5", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
