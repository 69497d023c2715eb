"""Rule-based sharding: tree path -> PartitionSpec.

Policy (MaxText-flavored 2D FSDP x TP, per-arch overridable):

* big 2D projections: input dim on the FSDP axis ('data'), output dim on
  'model' (up-projections) — transposed for down-projections so the matmul's
  contracting dim stays TP-sharded and the all-reduce happens once per block;
* embeddings: vocab on 'model' (152k-200k vocabs dominate small archs);
* MoE expert stacks [E, d, ff]: d on FSDP, ff on 'model' (expert dim stays
  local: dispatch einsums shard over tokens, expert matmuls over ff);
* everything 1D / small: replicated;
* stacked block params ([L, ...] from scan-over-layers) get a leading None.

``fsdp=False`` switches params to TP-only (replicated over 'data') — kills
the per-microbatch all-gathers at the cost of param memory; right for the
smaller archs (see EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ShardPolicy:
    fsdp: bool = True            # shard params over 'data' too (ZeRO-3-ish)
    zero1: bool = False          # FSDP only opt/GraB state; params TP-only.
    #                              Opt state is touched once per step (not
    #                              per microbatch), so its gathers don't get
    #                              amplified by gradient accumulation.
    shard_cache_seq: bool = True  # KV-cache sequence dim on 'model'

    @property
    def f(self):
        return "data" if self.fsdp else None


def _spec_for(path: str, ndim: int, policy: ShardPolicy) -> P:
    F = policy.f
    # order matters: first match wins
    RULES = [
        # embeddings / heads / positions
        (r"(^|/)embed$",                      P("model", None)),
        (r"(^|/)lm_head$",                    P(None, "model")),
        (r"(^|/)(dec_pos|enc_pos)$",          P()),
        # attention (incl. whisper self/cross)
        (r"attn/w[qkv]$",                     P(F, "model")),
        (r"attn/wo$",                         P("model", F)),
        (r"attn/b[qkv]$",                     P("model")),
        # dense mlp
        (r"mlp/(wg|wu|wi)$",                  P(F, "model")),
        (r"mlp/wo$",                          P("model", F)),
        # moe
        (r"moe/router$",                      P()),
        (r"moe/(wg|wu)$",                     P(None, F, "model")),
        (r"moe/wo$",                          P(None, "model", F)),
        # rwkv6 time mix
        (r"tmix/w[rkvg]$",                    P(F, "model")),
        (r"tmix/wo$",                         P("model", F)),
        (r"tmix/(wA|wB|w0|u)$",               P()),
        # rwkv6 channel mix
        (r"cmix/wk$",                         P(F, "model")),
        (r"cmix/wv$",                         P("model", F)),
        (r"cmix/wr$",                         P(F, "model")),
        # ssm (hymba)
        (r"ssm/(wx|wz|wB|wC)$",               P(F, "model")),
        (r"ssm/wo$",                          P("model", F)),
        (r"ssm/(wdt|dt_bias|a_log|D)$",       P()),
    ]
    for pat, spec in RULES:
        if re.search(pat, path):
            if len(spec) > ndim:      # e.g. rule for 2D hit a stacked scalar
                return P()
            return spec
    return P()


_STACKED = re.compile(r"(^|/)(blocks|enc_blocks|dec_blocks)/")


def path_str(key_path) -> str:
    parts = []
    for k in key_path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "name"):          # GetAttrKey (NamedTuple fields)
            parts.append(str(k.name))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k).strip("."))
    return "/".join(parts)


def param_spec(key_path, leaf, policy: ShardPolicy) -> P:
    path = path_str(key_path)
    # int8-quantized leaves: ".../w/q" shards like the original weight;
    # ".../w/s" (per-output-channel scale) inherits the output-dim sharding.
    suffix = None
    if path.endswith("/q") or path.endswith("/s"):
        suffix = path[-1]
        path = path[:-2]
    stacked = bool(_STACKED.search(path))
    if suffix == "s":
        # per-output-channel scale [..., out_dim]: inherit the parent
        # weight's output-dim sharding, replicate everything else.
        parent = _spec_for(path, 8, policy)
        last = parent[-1] if len(parent) else None
        return P(*([None] * (leaf.ndim - 1) + [last]))
    ndim = leaf.ndim - (1 if stacked else 0)
    spec = _spec_for(path, ndim, policy)
    parts = list(spec) + [None] * (ndim - len(spec))
    if stacked:
        parts = [None] + parts
    return P(*parts)


def tree_specs(tree, policy: ShardPolicy):
    """PartitionSpec pytree matching ``tree`` (params or grads)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: param_spec(path, leaf, policy), tree)


def state_specs(state, policy: ShardPolicy):
    """Specs for a TrainState: optimizer m/v and GraB pytrees mirror params;
    scalars replicate. Under ``zero1``, params stay TP-only while opt/GraB
    state additionally shards over 'data' (their per-step — not per-micro —
    access pattern makes the FSDP gathers cheap)."""
    p_policy = dataclasses.replace(policy, fsdp=policy.fsdp and not policy.zero1)
    s_policy = dataclasses.replace(policy, fsdp=policy.fsdp or policy.zero1)

    def spec(path, leaf):
        if leaf.ndim == 0:
            return P()
        head = path_str(path).split("/", 1)[0]
        pol = p_policy if head == "params" else s_policy
        return param_spec(path, leaf, pol)
    return jax.tree_util.tree_map_with_path(spec, state)


def _stack_worker_spec(spec: P, data_axis: str) -> P:
    """Prepend the [W] worker axis to a per-worker spec: the worker rows are
    the stash/gradient's data-parallel dimension, so any data-axis entry the
    FSDP rules put on inner dims yields to it (a mesh axis may appear only
    once per spec)."""
    return P(data_axis, *(None if ax == data_axis else ax for ax in spec))


def make_grad_pinner(params_tree, policy: ShardPolicy, mesh):
    """tree->tree callable applying the *gradient* PartitionSpecs (FSDP
    forced on, matching the grad/opt access pattern — see ``state_specs``)
    to gradient-shaped pytrees via with_sharding_constraint: the
    ``constrain_grads`` the live loop (``launch.live``) passes to
    ``build_train_step``. Uses NamedShardings so it works without an
    ambient ``with mesh:`` context."""
    g_policy = dataclasses.replace(policy, fsdp=policy.fsdp or policy.zero1)
    g_shardings = named(mesh, tree_specs(params_tree, g_policy))

    def constrain_grads(tree):
        return jax.tree.map(jax.lax.with_sharding_constraint, tree,
                            g_shardings)
    return constrain_grads


def make_cd_constraints(candidate: Optional[str], params_tree, batch_tree,
                        policy: ShardPolicy, mesh, *,
                        data_axis: str = "data"):
    """The explicit ``CdGrabConstraints`` applied inside ``micro_workers``:
    the per-timestep [W, micro, ...] batch slab is pinned with its worker
    rows over the data axis (:func:`cd_grab_slab_specs`). Left to XLA's
    propagation, the step moves more collective bytes
    (``tests/test_mesh_cd_grab.py`` compares the two compiled steps).

    ``candidate`` must be None: it is a positional argument that older
    callers still pass. ``batch_tree`` is the per-step batch pytree
    ([n_micro, micro, ...] leaves; only its *structure* matters).
    ``params_tree`` and ``policy`` are unused."""
    from repro.train.step import CdGrabConstraints

    assert candidate is None, candidate
    sh = named(mesh, cd_grab_slab_specs(batch_tree, data_axis=data_axis))
    return CdGrabConstraints(slab=lambda tree: jax.tree.map(
        jax.lax.with_sharding_constraint, tree, sh))


def cd_grab_slab_specs(batch_tree, *, data_axis: str = "data"):
    """Specs for the per-timestep [W, micro, ...] batch slab inside the
    ``micro_workers`` scan: worker rows over the data axis, everything else
    replicated (the per-worker microbatch stays local to its shard)."""
    return jax.tree.map(lambda _: P(data_axis), batch_tree)


def cd_grab_state_specs(state, policy: ShardPolicy, *,
                        data_axis: str = "data"):
    """Specs for a TrainState carrying CD-GraB's W-worker GraB state.

    The pair stash (``grab/m_prev``, ``grab/m_acc``) has a leading worker
    axis: row w is worker w's stashed gradient, so it shards over the data
    axis — each DP shard keeps only its own workers' stash, and the only
    cross-shard ordering traffic is the all-gather of the sketched sign
    rows (``core.distributed``).
    The shared running sum and everything else follow :func:`state_specs`.
    """
    def is_stash(path):
        p = path_str(path)
        return p.startswith("grab/m_prev") or p.startswith("grab/m_acc")

    # rule-match the stash against its per-worker (unstacked) shape, then
    # prepend the worker axis — dropping any data-axis entry the FSDP rules
    # put on the inner dims (a mesh axis may appear only once per spec, and
    # the worker axis is the stash's data-parallel dimension). Shape-level
    # unstacking (not leaf[0]) so abstract ShapeDtypeStruct states from
    # eval_shape work too.
    slim = jax.tree_util.tree_map_with_path(
        lambda path, leaf: (jax.ShapeDtypeStruct(leaf.shape[1:], leaf.dtype)
                            if is_stash(path) else leaf), state)
    base = state_specs(slim, policy)

    return jax.tree_util.tree_map_with_path(
        lambda path, spec: (_stack_worker_spec(spec, data_axis)
                            if is_stash(path) else spec),
        base, is_leaf=lambda x: isinstance(x, P))


def named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
