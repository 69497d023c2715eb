"""GraB — SGD with Online Gradient Balancing (Algorithm 4), as a composable
JAX module.

Device side
-----------
:class:`GrabState` carries O(d) state (three gradient-shaped pytrees) and
:func:`grab_step` implements lines 6-12 of Algorithm 4 for one stochastic
gradient: center with the *stale mean* ``m_prev``, pick a sign with the
balancer, update the running signed sum and the fresh-mean accumulator
``m_acc``. It is jit-safe and sharding-transparent: all three pytrees share
the gradient's PartitionSpecs, so the balancing inner product lowers to
per-shard partial dots + one scalar all-reduce.

In full-pytree mode the running sum is kept uncentered: ``s`` holds
``S = sum_i eps_i g_i`` and the scalar ``e`` holds ``E = sum_i eps_i``, so
the algorithm's centered sum ``sum_i eps_i (g_i - m_prev)`` is
``S - E m_prev`` (:func:`grab_running_sum`). The sign's inner product
``<S - E m_prev, g - m_prev>`` still reads ``s``, ``g`` and ``m_prev``, but
the update ``S += eps g`` no longer reads ``m_prev``: one f32 read of the
gradient's size less per balanced gradient. The values are the same up to
f32 rounding; the cancellation in ``S - E m_prev`` costs at most
``log2 |E|`` bits, and ``|E|`` stays small because the balancer balances
the signs (the loop's ``grab.sign_imbalance`` gauge). Sketch mode and pair
balancing keep the centered ``s``, and their ``e`` stays 0.

``grab_step`` is two halves that callers may also run apart:
:func:`grab_balance_step` (center, sign, update ``s`` and ``e``, advance
``t``) and :func:`grab_fold_mean` (``m_acc + grad_sum / n_per_epoch``). The
fresh mean is a plain sum, so it need not be folded per gradient: the train
step balances every microbatch but folds its f32 gradient sum into ``m_acc``
once per optimizer step, after the microbatch scan, which keeps ``m_acc``
out of the scan's per-microbatch reads and writes.

Sketch mode (beyond the paper) keeps ``s`` only for a fixed coordinate
subsample of the gradient (``k`` entries), cutting balance state and the
sequential-scan bandwidth from O(d) to O(k). The Pallas kernel in
``repro.kernels.balance`` accelerates exactly this path.

Host side
---------
The permutation itself lives on the host: the ordering policies in
``repro.core.orderings`` consume the epoch's signs and apply the Algorithm-3
two-pointer reorder at the boundary. Separating the two keeps the device step
purely functional (checkpointable, reshardable). The signs themselves stay
*device-resident* mid-epoch: :func:`init_sign_buffer` allocates the int8
``[T, W]`` per-epoch buffer carried in ``TrainState.signs``, the train step
appends to it at the GraB clock ``t``, and the host fetches it exactly once
per epoch (``orderings.OrderPolicy.apply_epoch_signs``) — no per-step
device→host sync on the dispatch path.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.balance import alweiss_sign, deterministic_sign, tree_balance_step
from repro.utils.tree import tree_dot, tree_zeros_like


@dataclasses.dataclass(frozen=True)
class GrabConfig:
    balancer: str = "deterministic"      # "deterministic" (Alg.5) | "alweiss" (Alg.6)
    alweiss_c: float = 30.0
    sketch_dim: int = 0                  # 0 = full pytree mode; >0 = sketch mode
    # Pair balancing (CD-GraB flavor, beyond paper): balance the differences
    # z_{2i} - z_{2i+1}, which are mean-free by construction — no stale-mean
    # estimate, and the m_prev/m_acc pytrees become a single prev-grad
    # buffer. The device emits the pair sign at odd steps; the host expands
    # it to (+e, -e) per pair (see orderings.expand_pair_signs).
    pair_balance: bool = False
    seed: int = 0
    # Sign-wire format for the CD-GraB coordination collective
    # (distributed.SIGN_WIRES): "f32" gathers the raw [W, k] sketched rows,
    # "int8" packs them to [W, k+4] int8 (per-row scale in-band) before the
    # gather — ~4x fewer wire bytes, signs still bit-identical on every
    # shard. sign_hier=L routes the gather through the two-stage
    # intra-host(L)/cross-host exchange; 0 is the flat gather.
    sign_wire: str = "f32"
    sign_hier: int = 0


class GrabState(NamedTuple):
    s: Any            # running signed sum: full-pytree mode the uncentered
                      # sum of eps * g (pytree; grab_running_sum centers it),
                      # sketch mode and pair balancing the centered sum
                      # (a [k] vector in sketch mode)
    e: jax.Array      # f32 sum of the epoch's signs (full-pytree mode; 0
                      # in sketch mode and pair balancing)
    m_prev: Any       # stale mean from previous epoch (pytree)
    m_acc: Any        # fresh mean accumulator (pytree): the epoch's gradients
                      # so far over n_per_epoch, folded in once per train step
    t: jax.Array      # step within epoch
    key: jax.Array    # PRNG (alweiss only)


# ---------------------------------------------------------------------------
# Sketch: fixed coordinate subsample of a pytree, precomputed per leaf.
# ---------------------------------------------------------------------------

class Sketch(NamedTuple):
    """Per-leaf coordinate subsample (static).

    Indices are stored *unraveled* (one int array per leaf dimension):
    ``leaf[idx0, idx1, ...]`` is a plain gather that XLA partitions without
    reshaping — a flat ``leaf.reshape(-1)[idx]`` forces full replication of
    2D-sharded weights (measured +20 GiB/dev and 2x collectives on the
    256-chip mesh)."""
    leaf_idx: tuple          # tuple of tuples-of-int-arrays, one per leaf

    @property
    def dim(self) -> int:
        """Realized sketch width: min(k, total params) coordinates."""
        total = 0
        for idx in self.leaf_idx:
            if idx is None:
                continue
            total += int(idx[0].size) if len(idx) else 1
        return total

    def apply(self, tree) -> jax.Array:
        leaves = jax.tree.leaves(tree)
        parts = []
        for leaf, idx in zip(leaves, self.leaf_idx):
            if idx is None:
                continue
            # 0-d leaves carry an empty index tuple: the coordinate is the
            # scalar itself (gather-indexing a 0-d array is not expressible).
            part = leaf[idx] if len(idx) else jnp.reshape(leaf, (1,))
            parts.append(jnp.reshape(part, (-1,)).astype(jnp.float32))
        return jnp.concatenate(parts)


def make_sketch(tree, k: int, seed: int = 0) -> Sketch:
    """Sample min(k, total) coordinates, allocated to leaves ~proportionally
    to size.

    The proportional floor allocation leaves a remainder; it is redistributed
    only to leaves with headroom (alloc < size) so no draw is ever clamped
    away — a largest-leaves round-robin can land on already-full leaves and
    silently return fewer than ``min(k, total)`` coordinates, which shows up
    later as a shape mismatch against the [k] running sum on tiny models.
    The invariant ``sum(alloc) == min(k, total)`` is asserted.
    """
    rng = np.random.default_rng(seed)
    leaves = jax.tree.leaves(tree)
    sizes = np.array([int(l.size) for l in leaves], dtype=np.int64)
    total = int(sizes.sum())
    target = min(int(k), total)
    alloc = np.minimum(np.maximum((sizes * k) // max(total, 1), 0), sizes)
    # redistribute the remainder to leaves with headroom, largest headroom
    # first (each pass allocates min(deficit, #leaves-with-headroom) slots,
    # so this terminates in a handful of passes)
    deficit = target - int(alloc.sum())
    while deficit > 0:
        headroom = sizes - alloc
        cand = np.flatnonzero(headroom > 0)
        take = cand[np.argsort(-headroom[cand], kind="stable")][:deficit]
        alloc[take] += 1
        # host numpy allocation bookkeeping, no device value
        # repro: allow[host-sync]
        deficit = target - int(alloc.sum())
    assert int(alloc.sum()) == target, (int(alloc.sum()), target)
    idxs = []
    for leaf, size, a in zip(leaves, sizes, alloc):
        a = int(a)  # host numpy scalar  repro: allow[host-sync]
        if not a:
            idxs.append(None)
            continue
        if leaf.ndim == 0:       # 0-d leaf: the one coordinate is the scalar
            idxs.append(())
            continue
        flat = np.sort(rng.choice(size, size=a, replace=False))
        nd = np.unravel_index(flat, leaf.shape)
        idxs.append(tuple(jnp.asarray(i) for i in nd))
    return Sketch(leaf_idx=tuple(idxs))


# ---------------------------------------------------------------------------
# State init / per-gradient step / epoch boundary
# ---------------------------------------------------------------------------

def init_grab_state(grad_template, cfg: GrabConfig) -> GrabState:
    # distinct zero trees per field: the live loop donates the whole
    # TrainState into the jitted step, and donating the *same* buffer twice
    # (an aliased s/m_prev/m_acc) is an XLA execute error
    if cfg.sketch_dim > 0:
        s = jnp.zeros((cfg.sketch_dim,), jnp.float32)
    else:
        s = tree_zeros_like(grad_template, jnp.float32)
    return GrabState(s=s, e=jnp.float32(0),
                     m_prev=tree_zeros_like(grad_template, jnp.float32),
                     m_acc=tree_zeros_like(grad_template, jnp.float32),
                     t=jnp.int32(0), key=jax.random.PRNGKey(cfg.seed))


def grab_running_sum(state: GrabState, cfg: GrabConfig):
    """The algorithm's centered running sum ``sum_i eps_i (g_i - m_prev)``:
    ``s - e * m_prev`` leaf by leaf in full-pytree mode, ``s`` itself in
    sketch mode and pair balancing."""
    if cfg.sketch_dim > 0 or cfg.pair_balance:
        return state.s
    return jax.tree.map(lambda s, m: s - state.e * m, state.s, state.m_prev)


def grab_step(state: GrabState, grad, n_per_epoch: int, cfg: GrabConfig,
              sketch: Optional[Sketch] = None):
    """One Algorithm-4 inner iteration. Returns (new_state, eps in {-1,+1};
    pair mode returns eps=0 on even steps — the pair's sign arrives on the
    odd step and the host expands it)."""
    if cfg.pair_balance:
        return _grab_step_pair(state, grad, cfg, sketch)
    state, eps = grab_balance_step(state, grad, cfg, sketch)
    return state._replace(m_acc=grab_fold_mean(state.m_acc, grad,
                                               n_per_epoch)), eps


def grab_balance_step(state: GrabState, grad, cfg: GrabConfig,
                      sketch: Optional[Sketch] = None):
    """The balance half of :func:`grab_step`: center ``grad`` with the stale
    mean, pick its sign from its inner product with the centered running
    sum, add the signed gradient to the running sum and advance ``t``.
    Full-pytree mode adds ``eps * grad`` to ``s`` and ``eps`` to ``e`` (see
    :func:`grab_running_sum`); sketch mode adds the signed sketched centered
    gradient to ``s``. ``m_acc`` is left as it is (it may be None); the
    caller folds the gradient in with :func:`grab_fold_mean`. Returns
    (new_state, eps in {-1,+1})."""
    assert not cfg.pair_balance, "pair balancing stashes in m_acc: grab_step"
    g32 = jax.tree.map(lambda x: x.astype(jnp.float32), grad)
    centered = jax.tree.map(jnp.subtract, g32, state.m_prev)

    if cfg.sketch_dim > 0:
        assert sketch is not None, "sketch mode needs a Sketch"
        z = sketch.apply(centered)
        eps, key = _pick_sign(jnp.vdot(state.s, z), cfg, state.key)
        return state._replace(s=state.s + eps.astype(jnp.float32) * z,
                              t=state.t + 1, key=key), eps
    # one reduction over s, g and m_prev per leaf: the centered sum is
    # formed inside it, never stored
    dot = tree_dot(grab_running_sum(state, cfg), centered)
    eps, key = _pick_sign(dot, cfg, state.key)
    epsf = eps.astype(jnp.float32)
    new_s = jax.tree.map(lambda s, g: s + epsf * g, state.s, g32)
    return state._replace(s=new_s, e=state.e + epsf, t=state.t + 1,
                          key=key), eps


def _pick_sign(dot, cfg: GrabConfig, key):
    """The balancer's sign for the inner product ``dot``; Alweiss splits
    ``key``. Returns (eps, key)."""
    if cfg.balancer == "deterministic":
        return deterministic_sign(dot), key
    key, sub = jax.random.split(key)
    return alweiss_sign(dot, jnp.float32(cfg.alweiss_c), sub), key


def grab_fold_mean(m_acc, grad_sum, n_per_epoch: int):
    """The fresh-mean half of :func:`grab_step`: ``m_acc + grad_sum /
    n_per_epoch`` in f32. ``grad_sum`` is one gradient, or the sum of
    several (a train step's f32 accumulator): the epoch's mean is the same
    sum either way, up to the order of the f32 additions."""
    return jax.tree.map(
        lambda a, g: a + g.astype(jnp.float32) / n_per_epoch, m_acc,
        grad_sum)


def _grab_step_pair(state: GrabState, grad, cfg: GrabConfig,
                    sketch: Optional[Sketch]):
    """CD-GraB pair balancing: stash even-step grads in the m_acc buffer;
    on odd steps balance the difference z = g_prev - g."""
    g32 = jax.tree.map(lambda x: x.astype(jnp.float32), grad)
    even = (state.t % 2) == 0

    def stash(_):
        return state._replace(m_acc=g32, t=state.t + 1), jnp.int32(0)

    def balance(_):
        diff = jax.tree.map(jnp.subtract, state.m_acc, g32)
        key = state.key
        if cfg.sketch_dim > 0:
            assert sketch is not None
            z = sketch.apply(diff)
            dot = jnp.vdot(state.s, z)
            if cfg.balancer == "deterministic":
                eps = deterministic_sign(dot)
            else:
                key, sub = jax.random.split(key)
                eps = alweiss_sign(dot, jnp.float32(cfg.alweiss_c), sub)
            new_s = state.s + eps.astype(jnp.float32) * z
        else:
            if cfg.balancer == "alweiss":
                key, sub = jax.random.split(state.key)
                new_s, eps = tree_balance_step(state.s, diff, kind="alweiss",
                                               c=cfg.alweiss_c, key=sub)
            else:
                new_s, eps = tree_balance_step(state.s, diff)
        return state._replace(s=new_s, key=key, t=state.t + 1), eps

    # both branches are cheap relative to the gradient computation; a
    # select keeps this jit-friendly without lax.cond's branch closure cost
    st_a, eps_a = stash(None)
    st_b, eps_b = balance(None)
    new_state = jax.tree.map(
        lambda a, b: jnp.where(even, a, b) if getattr(a, "ndim", None) is not None
        else a, st_a, st_b)
    eps = jnp.where(even, eps_a, eps_b)
    return new_state, eps


def init_parallel_grab_state(grad_template, cfg: GrabConfig,
                             n_workers: int) -> GrabState:
    """CD-GraB state for W logical workers: one *shared* running sum (the
    coordination), one pair stash per worker (a leading [W] axis on the
    m_prev/m_acc pytrees — sharded over the data axis on a real mesh, see
    ``launch.sharding.cd_grab_state_specs``)."""
    assert cfg.pair_balance, "parallel GraB is the CD-GraB pair-balance mode"
    assert n_workers >= 1

    def stash():   # distinct per field: donated states must not alias
        return jax.tree.map(
            lambda z: jnp.zeros((n_workers,) + z.shape, jnp.float32),
            grad_template)

    if cfg.sketch_dim > 0:
        s = jnp.zeros((cfg.sketch_dim,), jnp.float32)
    else:
        s = tree_zeros_like(grad_template, jnp.float32)
    return GrabState(s=s, e=jnp.float32(0), m_prev=stash(), m_acc=stash(),
                     t=jnp.int32(0), key=jax.random.PRNGKey(cfg.seed))


def init_sign_buffer(n_micro_per_epoch: int, n_workers: int = 1) -> jax.Array:
    """The device-resident per-epoch sign buffer: int8 ``[T, W]`` with
    ``T = n_micro_per_epoch / n_workers`` per-worker timesteps.

    Row ``t`` holds the W signs the balancer emitted at timestep ``t`` (zeros
    on pair-stash steps, exactly as the policies' expanders expect). The
    train step writes rows at offset ``grab.t`` via ``dynamic_update_slice``,
    so the buffer is epoch-positional: replaying or resuming an epoch
    overwrites the same rows it would have produced, and a mid-epoch
    checkpoint restores a prefix that the remaining steps complete."""
    assert n_micro_per_epoch % n_workers == 0, (n_micro_per_epoch, n_workers)
    return jnp.zeros((n_micro_per_epoch // n_workers, n_workers), jnp.int8)


def grab_step_workers(state: GrabState, grads, cfg: GrabConfig,
                      sketch: Optional[Sketch] = None, *,
                      mesh=None, data_axis: str = "data"):
    """One CD-GraB inner iteration over W workers' gradients.

    ``grads``: pytree whose leaves carry a leading [W] worker axis (worker
    w's microbatch gradient in row w). Even timesteps stash; odd timesteps
    balance the per-worker differences z_w = g_w^{t-1} - g_w^t sequentially
    in worker-index order against the shared running sum (the
    ``coordinated_pair_signs`` scan), which is what makes the signs globally
    coherent rather than W independent balancing walks.

    ``mesh``: when given (the launcher's mesh-native path), the sketch-mode
    sign dataflow runs through ``distributed.mesh_pair_signs`` — the [W, k]
    sketched differences stay sharded over ``data_axis`` (each DP shard
    sketches only its own workers' rows), one all-gather moves the W·k
    floats, and the scan replays replicated so every shard derives
    bit-identical signs. Without a mesh (host-simulated workers, CPU tests)
    the same scan runs on the gathered array directly — the two are
    bit-identical (``tests/test_mesh_cd_grab.py``). Full-pytree mode ignores
    ``mesh``: its tree dots already lower to per-shard partials + psum under
    pjit.

    Returns (new_state, eps [W] in {-1, 0, +1}): zeros on even (stash)
    steps, the pair signs on odd steps — the host expands them per worker
    (``orderings.ParallelGrabOrder``). Like ``_grab_step_pair``, both
    branches are computed and select'd; the balance scan is O(W·d) flops,
    noise next to the W gradient computations the step already did.
    """
    from repro.core.distributed import coordinated_pair_signs, mesh_pair_signs

    g32 = jax.tree.map(lambda x: x.astype(jnp.float32), grads)
    n_workers = jax.tree.leaves(g32)[0].shape[0]
    even = (state.t % 2) == 0

    # stash branch: remember this timestep's gradients, emit no signs
    st_stash = state._replace(m_acc=g32, t=state.t + 1)
    eps_stash = jnp.zeros((n_workers,), jnp.int32)

    # balance branch: per-worker differences, coordinated sequential signs
    diffs = jax.tree.map(jnp.subtract, state.m_acc, g32)
    key = state.key
    if cfg.sketch_dim > 0:
        assert sketch is not None, "sketch mode needs a Sketch"
        zs = jax.vmap(sketch.apply)(diffs)          # [W, k]
        if cfg.balancer == "alweiss":
            key, sub = jax.random.split(key)
        else:
            sub = key
        if mesh is not None:
            new_s, eps_bal = mesh_pair_signs(
                state.s, zs, mesh, data_axis, kind=cfg.balancer,
                c=cfg.alweiss_c, key=sub, wire=cfg.sign_wire,
                hier_group=cfg.sign_hier)
        else:
            new_s, eps_bal = coordinated_pair_signs(
                state.s, zs, kind=cfg.balancer, c=cfg.alweiss_c, key=sub,
                wire=cfg.sign_wire)
    else:
        def one_worker(carry, z_w):
            s_c, key_c = carry
            if cfg.balancer == "alweiss":
                key_c, sub = jax.random.split(key_c)
                s_c, eps = tree_balance_step(s_c, z_w, kind="alweiss",
                                             c=cfg.alweiss_c, key=sub)
            else:
                s_c, eps = tree_balance_step(s_c, z_w)
            return (s_c, key_c), eps

        (new_s, key), eps_bal = jax.lax.scan(
            one_worker, (state.s, state.key), diffs)
    st_bal = state._replace(s=new_s, key=key, t=state.t + 1)

    new_state = jax.tree.map(lambda a, b: jnp.where(even, a, b),
                             st_stash, st_bal)
    eps = jnp.where(even, eps_stash, eps_bal.astype(jnp.int32))
    return new_state, eps


def grab_step_workers_collect(state: GrabState, grads, cfg: GrabConfig,
                              sketch: Sketch):
    """Collect-only half of the deferred compressed exchange: like
    :func:`grab_step_workers` but instead of running the coordination
    collective per timestep, it *emits* this timestep's packed int8 wire row
    and leaves the running sum untouched.

    Even (stash) timesteps update the pair stash and emit an all-zero row;
    odd timesteps emit ``pack_rows_int8`` of the [W, k] sketched differences.
    The train step stacks the emitted rows over its microbatch scan and hands
    the [T, W, k+4] block to ``distributed.mesh_deferred_pair_signs`` — ONE
    gather + replicated scan per optimizer step, outside the scan where it
    overlaps the epilogue. The signs and final ``s`` that scan produces are
    bit-identical to the per-step ``wire="int8"`` path's (the rows carry the
    same bytes, consumed in the same time-major worker order).

    Deterministic balancer + sketch mode only — the per-step exchange covers
    Alweiss (its PRNG stream is per-timestep) and full-pytree mode (no
    fixed-width row to pack). Returns (new_state, packed [W, k+4] int8).
    """
    from repro.optim.compression import pack_rows_int8

    assert cfg.pair_balance and cfg.sketch_dim > 0 and sketch is not None, \
        "deferred sign collection is the sketch-mode CD-GraB path"
    assert cfg.balancer == "deterministic", \
        "deferred exchange needs the deterministic balancer (Alweiss takes " \
        "the per-step compressed exchange)"

    g32 = jax.tree.map(lambda x: x.astype(jnp.float32), grads)
    even = (state.t % 2) == 0

    diffs = jax.tree.map(jnp.subtract, state.m_acc, g32)
    zs = jax.vmap(sketch.apply)(diffs)                    # [W, k]
    packed = pack_rows_int8(zs)                           # [W, k+4] int8
    packed = jnp.where(even, jnp.zeros_like(packed), packed)

    m_acc = jax.tree.map(lambda g, a: jnp.where(even, g, a),
                         g32, state.m_acc)
    return state._replace(m_acc=m_acc, t=state.t + 1), packed


def expand_pair_signs(signs: np.ndarray) -> np.ndarray:
    """[..., 0, e1, 0, e2, ...] -> per-element signs [e1, -e1, e2, -e2, ...].

    2D input [T, W] (per-timestep, per-worker — the CD-GraB layout) expands
    each worker's column independently along time."""
    signs = np.asarray(signs)
    if signs.ndim == 2:
        return np.stack([expand_pair_signs(signs[:, w])
                         for w in range(signs.shape[1])], axis=1)
    signs = signs.reshape(-1)
    if signs.shape[0] % 2 != 0:
        raise ValueError(
            f"expand_pair_signs needs an even-length sign stream, got "
            f"{signs.shape[0]} steps: pair balancing emits one sign per "
            f"(stash, balance) step pair, so a partial epoch must either run "
            f"an even number of steps or drop the trailing stash step before "
            f"expanding")
    pair = signs[1::2]
    out = np.empty_like(signs)
    out[0::2] = pair
    out[1::2] = -pair
    return out


def grab_epoch_end(state: GrabState, cfg: GrabConfig) -> GrabState:
    """Promote the fresh mean to stale, reset the sums and accumulator."""
    if cfg.sketch_dim > 0:
        s = jnp.zeros_like(state.s)
    else:
        s = tree_zeros_like(state.s, jnp.float32)
    m_prev = (tree_zeros_like(state.m_acc, jnp.float32) if cfg.pair_balance
              else state.m_acc)
    return GrabState(s=s, e=jnp.float32(0), m_prev=m_prev,
                     m_acc=tree_zeros_like(state.m_acc, jnp.float32),
                     t=jnp.int32(0), key=state.key)
