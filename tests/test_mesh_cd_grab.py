"""Device-count-parameterized equivalence tests for mesh-native CD-GraB.

JAX locks the device count at first init, so each device count gets a real
multi-device CPU mesh in its own subprocess
(``XLA_FLAGS=--xla_force_host_platform_device_count=N``, see
``tests/_mesh_worker.py``). The worker runs every check on seeded inputs and
reports JSON; the assertions here pin down that

* ``mesh_pair_signs`` (all-gather + replicated scan) is bit-identical to the
  ``coordinated_pair_signs`` host scan at every device count,
* the result is invariant to the DP shard layout — 1, 2, 4 and 8-way row
  sharding all produce the same bits,
* the Pallas ``coord_balance`` kernel bit-matches the same host scan,
* the Alweiss balancer under CD-GraB consumes one replicated PRNG stream
  (identical signs on every shard — the replicated-key invariant documented
  in ``core/distributed.py``),
* the full device step ``grab_step_workers(mesh=...)`` equals the
  host-simulated path,
* the int8 compressed sign wire (quantize-before-gather) is bit-identical
  to its host reference at every device count, the hierarchical two-stage
  gather equals the flat gather, and the deferred one-gather exchange
  equals the per-step exchange,
* the live mesh step (``launch.live.build_live_step``) gathers the int8
  sign rows once a step, outside the microbatch loop, in >= 3.5x fewer
  bytes than the f32 wire, and its slab constraint costs no more
  all-gather bytes than XLA's own propagation.
"""
import functools
import json
import os
import subprocess
import sys

import numpy as np
import jax.numpy as jnp
import pytest

import _mesh_worker as mw

DEVICE_COUNTS = (2, 4, 8)
_REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@functools.lru_cache(maxsize=None)
def worker(n_dev: int) -> dict:
    env = os.environ.copy()
    env.pop("XLA_FLAGS", None)            # the worker sets its own
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.abspath(_REPO_SRC)] + env.get("PYTHONPATH", "").split(os.pathsep))
    proc = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__),
                                      "_mesh_worker.py"), str(n_dev)],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, f"worker[{n_dev}] failed:\n{proc.stderr[-3000:]}"
    return json.loads(proc.stdout.splitlines()[-1])


@functools.lru_cache(maxsize=None)
def host_reference():
    """The single-device host scan on the worker's exact inputs."""
    from repro.core.distributed import coordinated_pair_signs
    zs, s0, _ = mw._inputs()
    s, signs = coordinated_pair_signs(jnp.asarray(s0), jnp.asarray(zs),
                                      impl="xla")
    return np.asarray(signs), np.asarray(s)


@pytest.mark.parametrize("n_dev", DEVICE_COUNTS)
def test_mesh_signs_bit_match_host_scan(n_dev):
    out = worker(n_dev)
    assert out["det_bitmatch"], out
    assert out["det_replicated"], "outputs differ across device replicas"


@pytest.mark.parametrize("n_dev", DEVICE_COUNTS)
def test_pallas_kernel_bit_matches_host_scan(n_dev):
    out = worker(n_dev)
    assert out["pallas_sign_bitmatch"], out
    assert out["pallas_s_close"], out


def test_mesh_signs_invariant_to_shard_layout():
    """1-way (this process), 2-, 4- and 8-way row sharding: same bits."""
    signs_ref, s_ref = host_reference()
    for n_dev in DEVICE_COUNTS:
        out = worker(n_dev)
        assert np.array_equal(np.asarray(out["det_signs"]), signs_ref), n_dev
        # f32 -> JSON double round-trip is exact, so this is a bit compare
        assert np.array_equal(
            np.asarray(out["det_s"], np.float32), s_ref), n_dev


@pytest.mark.parametrize("n_dev", DEVICE_COUNTS)
def test_alweiss_replicated_key_invariant(n_dev):
    """Every shard consumes the same PRNG stream: signs are identical on all
    shards and equal to the host scan with the same key."""
    out = worker(n_dev)
    assert out["alweiss_replicated"], "shard-dependent randomness detected"
    assert out["alweiss_bitmatch"], out


def test_alweiss_signs_agree_across_device_counts():
    base = worker(DEVICE_COUNTS[0])["alweiss_signs"]
    for n_dev in DEVICE_COUNTS[1:]:
        assert worker(n_dev)["alweiss_signs"] == base, n_dev


@pytest.mark.parametrize("n_dev", DEVICE_COUNTS)
def test_int8_wire_bit_identical(n_dev):
    """Quantize-before-gather: every shard sees the same int8 bytes, so the
    compressed path is bit-identical to the host scan on the quantized wire,
    replicated across shards, invariant to hierarchical staging, and the
    deferred one-gather exchange reproduces the per-step exchange."""
    out = worker(n_dev)
    assert out["int8_bitmatch"], out
    assert out["int8_replicated"], "int8 outputs differ across replicas"
    assert out["hier_bitmatch"], "two-stage gather changed the bits"
    assert out["deferred_bitmatch"], out
    assert out["deferred_replicated"]


def test_int8_signs_agree_across_device_counts():
    """2-, 4- and 8-way sharding and the single-device host quantized scan
    all produce identical signs and running sums."""
    from repro.core.distributed import coordinated_pair_signs
    zs, s0, _ = mw._inputs()
    s_ref, signs_ref = coordinated_pair_signs(
        jnp.asarray(s0), jnp.asarray(zs), impl="xla", wire="int8")
    s_ref, signs_ref = np.asarray(s_ref), np.asarray(signs_ref)
    for n_dev in DEVICE_COUNTS:
        out = worker(n_dev)
        assert np.array_equal(np.asarray(out["int8_signs"]),
                              signs_ref), n_dev
        assert np.array_equal(np.asarray(out["int8_s"], np.float32),
                              s_ref), n_dev


@pytest.mark.parametrize("n_dev", DEVICE_COUNTS)
def test_grab_step_workers_mesh_matches_host(n_dev):
    out = worker(n_dev)
    assert out["step_bitmatch"], out


def test_grab_step_workers_signs_agree_across_device_counts():
    base = worker(DEVICE_COUNTS[0])["step_signs"]
    for n_dev in DEVICE_COUNTS[1:]:
        assert worker(n_dev)["step_signs"] == base, n_dev
    # stash steps emit zeros, balance steps emit full +-1 rows
    arr = np.asarray(base)
    assert np.array_equal(arr[0::2], np.zeros_like(arr[0::2]))
    assert set(np.unique(arr[1::2])) <= {-1, 1}


# ---------------------------------------------------------------------------
# the live mesh step (launch.live.build_live_step, the program the CD-GraB
# cell runs), read from its compiled HLO: the sign rows' all-gathers are
# the ones shaped [T, W, K+4] (int8 wire) or [W, K] (f32 wire).
# ---------------------------------------------------------------------------

def _sign_gathers(collectives, wire):
    shape = ([mw.LIVE_T, mw.W, mw.K + 4] if wire == "int8" else [mw.W, mw.K])
    dtype = "s8" if wire == "int8" else "f32"
    return [c for c in collectives if c["opcode"] == "all-gather"
            and c["dtype"] == dtype and c["dims"] == shape]


def _bytes_per_step(collectives):
    return sum(c["bytes"] * c["runs"] for c in collectives)


@pytest.mark.parametrize("n_dev", DEVICE_COUNTS)
def test_live_cd_step_gathers_int8_rows_once_per_step(n_dev):
    """The deferred exchange: one s8 all-gather of the whole step's packed
    rows, outside the microbatch loop, T·W·(K+4) bytes."""
    live = worker(n_dev)["live"]["int8"]
    s8 = [c for c in live if c["opcode"] == "all-gather"
          and c["dtype"] == "s8"]
    assert len(s8) == 1, live
    assert s8 == _sign_gathers(live, "int8"), s8
    assert s8[0]["runs"] == 1, s8
    assert s8[0]["bytes"] == mw.LIVE_T * mw.W * (mw.K + 4)


@pytest.mark.parametrize("n_dev", DEVICE_COUNTS)
def test_live_cd_step_int8_wire_shrinks_sign_gather(n_dev):
    """Sign-gather bytes a timestep: the f32 wire's over the int8 wire's is
    at least 3.5 (4K/(K+4) = 3.84 at K=96)."""
    live = worker(n_dev)["live"]
    f32 = _sign_gathers(live["f32"], "f32")
    assert f32 and all(c["runs"] == mw.LIVE_T for c in f32), live["f32"]
    int8 = _sign_gathers(live["int8"], "int8")
    assert int8, live["int8"]
    per_t_f32 = _bytes_per_step(f32) / mw.LIVE_T
    per_t_int8 = _bytes_per_step(int8) / mw.LIVE_T
    assert per_t_f32 / per_t_int8 >= 3.5, (per_t_f32, per_t_int8)


@pytest.mark.parametrize("n_dev", DEVICE_COUNTS)
def test_live_cd_step_slab_set_is_no_worse(n_dev):
    """``make_cd_constraints``'s slab set against XLA's own propagation:
    the bytes of every collective a step, the sign gather left out, are no
    more. (XLA's propagation gathers fewer bytes on 4 and 8 devices, by
    all-reducing, permuting and exchanging more.)"""
    live = worker(n_dev)["live"]

    def other(collectives):
        sign = _sign_gathers(collectives, "int8")
        return _bytes_per_step(c for c in collectives if c not in sign)
    slab, unpinned = other(live["int8"]), other(live["int8_unpinned"])
    assert 0 < slab <= unpinned, (slab, unpinned)
