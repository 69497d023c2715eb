"""Sharding rules + a miniature multi-device compile (subprocess with 8 fake
CPU devices)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.launch.sharding import ShardPolicy, param_spec, tree_specs
from repro.models import lm

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _specs_for(arch):
    _, smoke = get_config(arch)
    params = jax.eval_shape(lambda: lm.init_lm(jax.random.PRNGKey(0), smoke))
    return params, tree_specs(params, ShardPolicy())


def test_attention_and_embed_rules():
    params, specs = _specs_for("qwen2-7b")
    assert specs["embed"] == P("model", None)
    assert specs["lm_head"] == P(None, "model")
    # stacked block params get the leading layer axis
    assert specs["blocks"]["attn"]["wq"] == P(None, "data", "model")
    assert specs["blocks"]["attn"]["wo"] == P(None, "model", "data")
    assert specs["blocks"]["norm1"]["scale"] == P(None, None)


def test_moe_and_rwkv_rules():
    _, specs = _specs_for("mixtral-8x7b")
    assert specs["blocks"]["moe"]["wg"] == P(None, None, "data", "model")
    assert specs["blocks"]["moe"]["wo"] == P(None, None, "model", "data")
    assert specs["blocks"]["moe"]["router"] == P(None, None, None)
    _, specs = _specs_for("rwkv6-7b")
    assert specs["blocks"]["tmix"]["wr"] == P(None, "data", "model")
    assert specs["blocks"]["cmix"]["wv"] == P(None, "model", "data")


def test_no_fsdp_policy_drops_data_axis():
    _, smoke = get_config("qwen2-7b")
    params = jax.eval_shape(lambda: lm.init_lm(jax.random.PRNGKey(0), smoke))
    specs = tree_specs(params, ShardPolicy(fsdp=False))
    assert specs["blocks"]["attn"]["wq"] == P(None, None, "model")


_MINI_DRYRUN = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["REPRO_GLA_IMPL"] = "xla"
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.launch.sharding import ShardPolicy, tree_specs
    from repro.models import lm
    from repro.models.act_sharding import set_activation_specs
    from repro.optim import adamw, constant
    from repro.train.step import build_train_step, init_train_state
    from repro.core.grab import GrabConfig

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    set_activation_specs(("data",))
    _, cfg = get_config("{arch}")
    policy = ShardPolicy()
    params_abs = jax.eval_shape(lambda: lm.init_lm(jax.random.PRNGKey(0), cfg))
    g_specs = tree_specs(params_abs, policy)
    pin = lambda t: jax.tree.map(
        lambda x, s: jax.lax.with_sharding_constraint(x, s), t, g_specs)
    opt = adamw()
    grab = GrabConfig()
    step = build_train_step(lambda p, mb: lm.loss_fn(p, cfg, mb), opt,
                            constant(1e-3), grab, 64, constrain_grads=pin)
    state_abs = jax.eval_shape(lambda: init_train_state(params_abs, opt, grab))
    from repro.launch.sharding import state_specs
    s_specs = state_specs(state_abs, policy)
    batch = {{"tokens": jax.ShapeDtypeStruct((2, 8, 64), jnp.int32),
             "labels": jax.ShapeDtypeStruct((2, 8, 64), jnp.int32)}}
    b_specs = {{"tokens": P(None, "data", None), "labels": P(None, "data", None)}}
    ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                is_leaf=lambda x: isinstance(x, P))
    with mesh:
        compiled = jax.jit(step, in_shardings=(ns(s_specs), ns(b_specs)),
                           donate_argnums=0).lower(state_abs, batch).compile()
    print("COMPILED_OK", compiled.memory_analysis().temp_size_in_bytes)
""")


@pytest.mark.parametrize("arch", ["qwen2-7b", "mixtral-8x7b", "rwkv6-7b",
                                  "hymba-1.5b"])
def test_mini_multidevice_dryrun(arch):
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _MINI_DRYRUN.format(arch=arch)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert "COMPILED_OK" in r.stdout, r.stderr[-3000:]
