"""Distributed GraB variants (beyond-paper, CD-GraB-flavored).

Two composable strategies for data-parallel meshes:

* :func:`local_rank_signs` — each data-parallel shard balances its *own*
  microbatch-gradient stream against a *local* running sum. Zero extra
  communication; each DP group maintains its own permutation over its data
  shard. Implemented with ``shard_map`` over the data axis so the per-rank
  partial gradients never leave the shard.

* global sketch balancing — the default in :mod:`repro.train.step`: the
  globally psum'd microbatch gradient (which pjit produces anyway) is
  balanced against one global running sum; in sketch mode the per-step state
  traffic is O(k). One sign per global microbatch; the host permutes global
  microbatch ids. This is the pod-scale default because it piggybacks
  entirely on collectives the training step already performs.

* CD-GraB coordination [Cooper et al. 2023] — :func:`coordinated_pair_signs`
  is the "order server" collapsed into a deterministic scan: the W workers'
  pair-difference vectors are balanced *sequentially in worker-index order*
  against one shared running sum, which is what preserves the global herding
  bound across data-parallel shards. On a real mesh,
  :func:`mesh_pair_signs` all-gathers the sketched differences (W·k floats —
  tiny next to the gradient all-reduce) and replays the same scan replicated
  on every shard, so every shard derives identical signs with a single
  collective and no server rank.

Alweiss-under-CD-GraB replicated-key invariant
----------------------------------------------
The Alweiss balancer is randomized, so coordination additionally requires
that every shard flips the *same* coins: the PRNG key is replicated
(``in_specs=P()`` in :func:`mesh_pair_signs`), and the key splits happen
*inside* the replicated scan, once per worker row in worker-index order.
Every shard therefore consumes an identical key stream and derives
bit-identical signs — there is nothing to broadcast and no shard-dependent
randomness anywhere in the ordering path. Violating this (e.g. folding a
shard id into the key) would silently degrade CD-GraB to W independent
balancing walks. Verified on real multi-device meshes in
``tests/test_mesh_cd_grab.py``.

Kernel dispatch
---------------
The deterministic W-row scan has a fused Pallas kernel
(``kernels/coord_balance.py``): :func:`coordinated_pair_signs` dispatches to
it when ``impl`` resolves to ``"pallas"`` (default on a real TPU backend;
override with ``REPRO_COORD_IMPL=pallas|xla``). The SPMD mesh path always
takes the XLA scan — a pallas_call inside pjit is opaque to the partitioner —
and the Alweiss balancer stays on XLA too (it needs a per-row PRNG split).

Compressed sign wire (``wire="int8"``)
--------------------------------------
The sketched pair differences exist only to produce ±1 sign decisions, so
their wire precision is negotiable in a way gradients are not: each shard
quantizes its own rows to int8 with an in-band per-row scale
(``optim.compression.pack_rows_int8``, [W, k] f32 -> [W, k+4] int8) *before*
the all-gather, cutting the collective to ~1/4 of the f32 bytes. Determinism
is preserved by construction — the compressed bytes are produced once on the
owning shard, the gather makes them byte-identical everywhere, and every
shard dequantizes the same bytes inside the replicated scan, so all shards
still derive identical signs. The quantization does perturb *which* signs
come out vs the exact wire (bounded ordering-quality drift, measured by
``benchmarks/cd_grab_scaling.py --sign-wire``).

Two more latency/topology levers stack on top:

* **hierarchical gather** (``hier_group=L``) — two-stage exchange: gather
  within contiguous groups of L shards (intra-host links), then exchange the
  per-group blocks across groups (one cross-host message per host rather
  than per worker), so cross-host wire cost scales with hosts, not workers.
* **deferred exchange** (:func:`mesh_deferred_pair_signs`) — the train step
  stashes each timestep's packed rows and performs ONE gather + replicated
  scan per optimizer step instead of one collective per pair timestep; the
  single gather sits outside the microbatch scan where the compiler can
  overlap it with the gradient-mean/optimizer epilogue (see
  ``train.step.build_train_step``).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.balance import alweiss_sign, deterministic_sign
from repro.optim.compression import pack_rows_int8, unpack_rows_int8


def local_rank_signs(local_sums: jax.Array, local_zs: jax.Array,
                     mesh, data_axis: str = "data"):
    """Per-rank deterministic balancing under shard_map.

    ``local_sums``: [dp, k] running sums (sharded over data axis).
    ``local_zs``:   [dp, k] this step's sketched local gradients.
    Returns (new_sums [dp, k], signs [dp]).
    """
    def one_rank(s, z):
        # s, z: [1, k] local shard
        dot = jnp.vdot(s, z)
        eps = jnp.where(dot <= 0, jnp.int32(1), jnp.int32(-1))
        return s + eps.astype(jnp.float32) * z, eps[None]

    fn = jax.shard_map(one_rank, mesh=mesh,
                       in_specs=(P(data_axis, None), P(data_axis, None)),
                       out_specs=(P(data_axis, None), P(data_axis)))
    return fn(local_sums, local_zs)


def pairwise_difference(zs: jax.Array) -> jax.Array:
    """Pair-balancing transform (CD-GraB's 'pair balance'): balance differences
    z_{2i} - z_{2i+1}, which are mean-free by construction — removes the stale-
    mean estimate entirely. ``zs``: [2m, k] -> [m, k] differences."""
    assert zs.shape[0] % 2 == 0, "pair balancing needs an even number of vectors"
    return zs[0::2] - zs[1::2]


def signs_from_pair_signs(pair_signs: jax.Array) -> jax.Array:
    """Expand per-pair signs to per-vector signs: pair sign e gives (+e, -e)."""
    return jnp.stack([pair_signs, -pair_signs], axis=1).reshape(-1)


_COORD_IMPLS = ("pallas", "xla")
SIGN_WIRES = ("f32", "int8")


def _validate_impl(impl: str, source: str) -> str:
    if impl not in _COORD_IMPLS:
        raise ValueError(
            f"{source}={impl!r} is not a known coordinated-scan "
            f"implementation; allowed values: {list(_COORD_IMPLS)}")
    return impl


def _validate_wire(wire: str, source: str = "wire") -> str:
    if wire not in SIGN_WIRES:
        raise ValueError(
            f"{source}={wire!r} is not a known sign-wire format; allowed "
            f"values: {list(SIGN_WIRES)}")
    return wire


def quantize_wire(zs: jax.Array) -> jax.Array:
    """The exact value perturbation the int8 wire applies: per-row quantize +
    dequantize (``[..., k]`` f32 -> f32). The host/reference scan consumes
    these so mesh-vs-host bit-identity holds for the compressed wire too —
    both paths run the identical elementwise pack/unpack on each row, the
    mesh path merely moving the packed bytes through the gather in between."""
    return unpack_rows_int8(pack_rows_int8(zs))


def hier_all_gather(x: jax.Array, axis_name: str, *, axis: int,
                    total: int, hier_group: int = 0) -> jax.Array:
    """All-gather ``x`` over ``axis_name``, optionally in two stages.

    ``hier_group=L`` (with ``1 < L < total`` dividing ``total``) models a
    host hierarchy over a flat mesh axis of ``total`` shards: stage 1
    gathers within each contiguous group of L shards (intra-host links),
    stage 2 exchanges the L-shard blocks across groups at fixed intra-group
    rank (one cross-host message per *group*, so cross-host cost scales with
    hosts rather than workers). Group order is ascending in both stages, so
    the result's row order — hence the coordinated scan's worker order — is
    identical to the flat gather's. ``hier_group`` of 0/1/``total`` is the
    flat single-stage gather."""
    if hier_group in (0, 1, total):
        return jax.lax.all_gather(x, axis_name, axis=axis, tiled=True)
    if total % hier_group:
        raise ValueError(
            f"hier_group={hier_group} must divide the {axis_name!r} axis "
            f"size {total}")
    hosts = total // hier_group
    intra = [[h * hier_group + l for l in range(hier_group)]
             for h in range(hosts)]
    cross = [[h * hier_group + l for h in range(hosts)]
             for l in range(hier_group)]
    x = jax.lax.all_gather(x, axis_name, axis=axis, tiled=True,
                           axis_index_groups=intra)
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=True,
                              axis_index_groups=cross)


def _coord_impl() -> str:
    """Resolve the coordinated-scan implementation: REPRO_COORD_IMPL wins,
    else the Pallas kernel on a real TPU backend and XLA everywhere else.
    Unknown values raise instead of silently falling through to the XLA
    scan (a typo like ``REPRO_COORD_IMPL=palas`` would otherwise quietly
    skip the kernel)."""
    impl = os.environ.get("REPRO_COORD_IMPL")
    if impl is None:
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return _validate_impl(impl, "REPRO_COORD_IMPL")


def coordinated_pair_signs(s: jax.Array, zs: jax.Array, *,
                           kind: str = "deterministic", c: float = 30.0,
                           key: jax.Array | None = None,
                           impl: str | None = None, wire: str = "f32"):
    """CD-GraB server step: balance the W workers' pair-difference vectors
    sequentially (worker-index order) against one *shared* running sum.

    ``s``: [k] running sum; ``zs``: [W, k] this timestep's differences.
    Returns (new_s [k], signs [W] in {-1, +1}). The scan is the whole
    coordination: worker i's sign sees workers < i's contributions from the
    same timestep, exactly as if a central server consumed the stream
    (z_1^t, ..., z_W^t, z_1^{t+1}, ...).

    ``impl``: "pallas" fuses the W dependent dot/sign/axpy steps into the
    ``kernels/coord_balance.py`` kernel (deterministic kind only — Alweiss
    needs per-row PRNG splits); "xla" is the plain ``lax.scan``; None picks
    via :func:`_coord_impl`. The SPMD path (:func:`mesh_pair_signs`) pins
    "xla": a pallas_call inside pjit is opaque to the partitioner.

    ``wire="int8"`` balances the quantize-dequantized rows
    (:func:`quantize_wire`) — this is the host-side reference for what the
    compressed mesh wire computes, bit-identical to the mesh path.
    """
    if impl is None:
        impl = _coord_impl()
    else:
        _validate_impl(impl, "impl")
    if _validate_wire(wire) == "int8":
        zs = quantize_wire(zs)
    if impl == "pallas" and kind == "deterministic":
        from repro.kernels.ops import coord_balance
        signs, new_s = coord_balance(s, zs)
        return new_s, signs
    if key is None:
        key = jax.random.PRNGKey(0)

    def body(carry, z):
        s_c, key_c = carry
        dot = jnp.vdot(s_c, z)
        if kind == "deterministic":
            eps = deterministic_sign(dot)
        elif kind == "alweiss":
            key_c, sub = jax.random.split(key_c)
            eps = alweiss_sign(dot, jnp.float32(c), sub)
        else:
            raise ValueError(f"unknown balancer kind: {kind!r}")
        return (s_c + eps.astype(jnp.float32) * z, key_c), eps

    (new_s, _), signs = jax.lax.scan(body, (s, key), zs)
    return new_s, signs


def mesh_pair_signs(s: jax.Array, z_local: jax.Array, mesh,
                    data_axis: str = "data", *, kind: str = "deterministic",
                    c: float = 30.0, key: jax.Array | None = None,
                    wire: str = "f32", hier_group: int = 0):
    """Coordinated pair signs on a mesh: the tiny sign dataflow of CD-GraB.

    ``z_local``: [W, k] sketched pair differences, sharded over ``data_axis``
    (each shard holds its own workers' rows); ``s``: [k] replicated running
    sum. Every shard all-gathers the W·k floats and replays the same scan,
    so the outputs are bit-identical everywhere — one collective, no server
    rank, nothing further to broadcast.

    Replicated-key invariant (``kind="alweiss"``): ``key`` enters with
    ``in_specs=P()`` — the *same* key on every shard — and all splits happen
    inside the replicated scan, once per worker row in worker-index order.
    Every shard consumes an identical PRNG stream, hence identical signs on
    all W shards; never fold a shard id into this key (that would degrade
    CD-GraB to W independent balancing walks).

    ``wire="int8"`` packs each shard's rows to ``[W_local, k+4]`` int8
    *before* the gather (values + in-band per-row scale, ~4x fewer wire
    bytes) and dequantizes the gathered bytes inside the replicated scan.
    The bytes are produced once on the owning shard, so every shard
    dequantizes identical data — the determinism invariant holds by
    construction, for the Alweiss kind too (the quantization happens before
    any coin flip). ``hier_group=L`` routes the gather through the two-stage
    intra-host/cross-host exchange (:func:`hier_all_gather`).

    Returns (new_s [k] replicated, signs [W] replicated). Always takes the
    XLA scan (``impl="xla"``): this runs under the SPMD partitioner, where a
    pallas_call is opaque.
    """
    _validate_wire(wire)
    total = mesh.shape[data_axis]
    if key is None:
        key = jax.random.PRNGKey(0)

    def fn(s_r, z_l, key_r):
        if wire == "int8":
            z_l = pack_rows_int8(z_l)
        zs = hier_all_gather(z_l, data_axis, axis=0, total=total,
                             hier_group=hier_group)
        if wire == "int8":
            zs = unpack_rows_int8(zs)
        return coordinated_pair_signs(s_r, zs, kind=kind, c=c, key=key_r,
                                      impl="xla")

    return jax.shard_map(fn, mesh=mesh,
                         in_specs=(P(), P(data_axis, None), P()),
                         out_specs=(P(), P()),
                         check_vma=False)(s, z_local, key)


def mesh_deferred_pair_signs(s: jax.Array, packed: jax.Array, t0: jax.Array,
                             mesh, data_axis: str = "data", *,
                             hier_group: int = 0):
    """Deferred (batched) compressed sign exchange: ONE gather + replicated
    scan for a whole optimizer step's worth of pair timesteps.

    ``packed``: [T, W, k+4] int8 — the per-timestep packed rows the microbatch
    scan stashed (``grab.grab_step_workers_collect``), sharded over
    ``data_axis`` on the worker axis; stash timesteps hold all-zero rows.
    ``t0``: replicated scalar — the GraB clock at the first of the T
    timesteps, which fixes the stash/balance parity of each row block.
    ``s``: [k] replicated running sum.

    The replicated scan walks all T·W rows in time-major worker-index order —
    exactly the stream the per-step exchange would have fed it — skipping
    stash rows bit-exactly (``s`` passes through untouched, sign 0, matching
    ``grab_step_workers``' even-step output). Deterministic balancer only:
    batching Alweiss would need the stashed rows to replay the per-timestep
    PRNG stream, which the per-step compressed exchange already handles.

    Because this sits *outside* the microbatch scan, the compiler is free to
    overlap the gather with the gradient-mean/optimizer epilogue — the
    compute-overlap half of the deferred design (see
    ``train.step.build_train_step``).

    Returns (new_s [k] replicated, signs [T, W] int32 replicated, zeros on
    stash timesteps).
    """
    total = mesh.shape[data_axis]

    def fn(s_r, p_l, t0_r):
        p = hier_all_gather(p_l, data_axis, axis=1, total=total,
                            hier_group=hier_group)
        rows = unpack_rows_int8(p)                        # [T, W, k]
        n_t, n_w, k = rows.shape
        balance = ((t0_r + jnp.arange(n_t)) % 2) == 1     # odd t balances
        row_live = jnp.repeat(balance, n_w)               # [T*W]

        def body(s_c, xs):
            z, live = xs
            eps = jnp.where(live, deterministic_sign(jnp.vdot(s_c, z)),
                            jnp.int32(0))
            # where() (not `+ eps*z` with z=0) keeps stash rows bit-exact:
            # adding ±0.0 can flip a -0.0 coordinate of s to +0.0
            s_n = jnp.where(live, s_c + eps.astype(jnp.float32) * z, s_c)
            return s_n, eps

        new_s, eps = jax.lax.scan(body, s_r,
                                  (rows.reshape(n_t * n_w, k), row_live))
        return new_s, eps.reshape(n_t, n_w)

    return jax.shard_map(fn, mesh=mesh,
                         in_specs=(P(), P(None, data_axis, None), P()),
                         out_specs=(P(), P()),
                         check_vma=False)(s, packed, t0)
