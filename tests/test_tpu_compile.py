"""Every Pallas kernel compiles for a TPU v5e chip at real widths, and the
GraB train step compiles to the fusions its HBM bytes assume.

Nothing runs: each case lowers the public ``kernels.ops`` wrapper with
``interpret=False`` for one chip of a described ``v5e:2x2`` topology and
compiles it with the TPU compiler, which refuses block shapes, stores and
VMEM use that the interpreter accepts. The widths are those of
``chip_smoke.py``'s kernel phase. The topology is described inside a fixture
(never at import): only one process may load the TPU library, and every test
worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from _hlo import instructions, microbatch_loop


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip can be written to the persistent cache
    # but not read back without one: keep the cache off around these
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


# name -> (wrapper, argument shapes)
CASES = {
    "balance_scan": (ops.balance_scan, [(1024,), (8, 1024)]),
    "coord_balance": (ops.coord_balance, [(1024,), (8, 1024), (8, 1024)]),
    "coord_balance_chunked": (ops.coord_balance,
                              [(131072,), (8, 131072), (8, 131072)]),
    "gla_scan": (ops.gla_scan, [(2, 16, 512, 64)] * 4 + [(16, 64)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{name}: no Pallas kernel in the compiled program"


def test_chunked_case_takes_the_chunked_kernel():
    """The chunked case above compiles the streamed kernel, not the plain
    one: at k=131072 the plain kernel's full-k tiles exceed the budget."""
    impl, chunk_k = ops.select_coord_impl(8, 131072)
    assert impl == "chunked" and chunk_k % 1024 == 0, (impl, chunk_k)


def test_grab_step_reads_the_stale_mean_once_a_microbatch(one_chip):
    """In the full-pytree GraB step compiled for the chip, each leaf of the
    stale mean ``m_prev`` is read by one fusion a microbatch, the inner
    product's; the running sum's update, fused with the gradient
    accumulator's, reads no ``m_prev`` (it adds ``eps * g`` to the
    uncentered sum). A centered update would make that fusion a second
    reader of every leaf: 4 more bytes per parameter a microbatch."""
    from repro.configs import get_config
    from repro.core.grab import GrabConfig
    from repro.models import lm
    from repro.optim import adamw, constant
    from repro.train.step import build_train_step, init_train_state

    model = get_config("phi3-mini-3.8b")[1].with_(param_dtype="bfloat16")
    cfg, opt = GrabConfig(), adamw()
    step = jax.jit(build_train_step(
        lambda p, mb: lm.loss_fn(p, model, mb, remat=True), opt,
        constant(1e-3), cfg, n_micro_per_epoch=8), donate_argnums=(0,))
    state = jax.eval_shape(lambda: init_train_state(
        lm.init_lm(jax.random.PRNGKey(0), model), opt, cfg,
        n_micro_per_epoch=8))
    place = lambda t: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        t)
    batch = {k: jax.ShapeDtypeStruct((4, 2, 16), jnp.int32,
                                     sharding=one_chip)
             for k in ("tokens", "labels")}
    loop = microbatch_loop(instructions(
        step.lower(place(state), batch).compile().as_text()))
    assert len(loop.stale_mean) == len(jax.tree.leaves(state.grab.m_prev))
    for leaf in loop.stale_mean:
        reads = loop.reads(leaf["name"])
        assert [r["opcode"] for r in reads] == ["fusion"], \
            (leaf["name"], [(r["name"], r["op_name"]) for r in reads])
