"""Plain reference of one training job, in two parts.

The training reference, the same for every model: the GraB balance in
full-pytree mode, CD-GraB's coordinated pair balance over W workers (a
section of its own, below), the Algorithm-3 reorder and AdamW. It takes the
model's loss from a model module.

A model module gives the model: ``sizes(cfg)``, ``init_params(key, cfg)``,
``loss(params, tokens, labels, z, prec)`` (z: what ``sizes`` returns),
``program_fields(cfg)`` (the program's ``ModelConfig`` attributes and the
values they must have) and optionally ``flops_per_token(cfg, seq_len)``. A
configuration file names its module under ``"reference"``
(``bench/models/<name>.py``, loaded by ``Layout.model``); without that key
the model part of this file is the module. A module may use this file's
helpers (``_mm``, ``_rms``, ``_rope``, ``hidden_states``, ``head_nll``) and,
as this file, imports nothing of the program.

Written from the published descriptions in ``jax.numpy``: float32 with every
matrix product at ``Precision.HIGHEST``, attention as an exact softmax over
blocks of queries, the mixture of experts as a dense sum over all experts
weighted by the routed and capacity-limited gates. It reads the sizes from
the benchmark's configuration file.

Parameters are stored as arrays of the configuration's dtype (bfloat16),
as the program stores them; each step takes the float32 gradient of their
float32 values, and each AdamW update is computed in float32 and stored
back as a bfloat16 array. (A float32 copy rounded inside one compiled
program, by a convert pair or ``reduce_precision``, does not round the same
way on the TPU: PERF.md gives the readings.) Gradients and the GraB state
are float32.

``precision="fp8"`` is the control: every matrix product takes its operands
rounded to float8 (e4m3 with a per-tensor scale) and its backward cotangent
to float8 e5m2, the next precision below the configuration's bfloat16.
"""
from __future__ import annotations

import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# The default model module: sizes from the configuration file
# ---------------------------------------------------------------------------

def sizes(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    v = cfg["vocab_size"]
    return {
        "d": d, "h": h, "kv": cfg["num_key_value_heads"],
        "hd": cfg.get("head_dim") or d // h,
        "ff": cfg["intermediate_size"], "layers": cfg["num_hidden_layers"],
        "vocab": v, "vocab_rows": (v + 255) // 256 * 256,   # padded table
        "eps": cfg["rms_norm_eps"], "theta": cfg["rope_theta"],
        "experts": cfg.get("num_local_experts", 0),
        "topk": cfg.get("num_experts_per_tok", 0),
        "capacity": cfg.get("expert_capacity_factor", 0.0),
        "group": cfg.get("expert_group_tokens", 0),
        "aux_coef": cfg.get("router_aux_loss_coef", 0.0),
        "dtype": jnp.dtype(cfg["torch_dtype"]),
    }


def init_params(key, cfg: dict):
    """Random parameters in the program's layout: per-layer weights stacked
    on a leading layer axis, matrices as ``[in, out]``. Linear weights are
    N(0, 1/fan_in), the embedding N(0, 0.02^2), norm scales one."""
    z = sizes(cfg)
    dt, L, d, ff = z["dtype"], z["layers"], z["d"], z["ff"]
    qd, kvd = z["h"] * z["hd"], z["kv"] * z["hd"]
    keys = iter(jax.random.split(key, 16))

    def normal(shape, scale, dtype=dt):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    def lin(shape):            # fan-in is the second-to-last axis
        return normal(shape, shape[-2] ** -0.5)

    blocks = {
        "norm1": {"scale": jnp.ones((L, d), dt)},
        "norm2": {"scale": jnp.ones((L, d), dt)},
        "attn": {"wq": lin((L, d, qd)), "wk": lin((L, d, kvd)),
                 "wv": lin((L, d, kvd)), "wo": lin((L, qd, d))},
    }
    if z["experts"]:
        e = z["experts"]
        blocks["moe"] = {"router": normal((L, d, e), d ** -0.5, jnp.float32),
                         "wg": lin((L, e, d, ff)), "wu": lin((L, e, d, ff)),
                         "wo": lin((L, e, ff, d))}
    else:
        blocks["mlp"] = {"wg": lin((L, d, ff)), "wu": lin((L, d, ff)),
                         "wo": lin((L, ff, d))}
    return {"embed": normal((z["vocab_rows"], d), 0.02),
            "blocks": blocks,
            "final_norm": {"scale": jnp.ones((d,), dt)},
            "lm_head": lin((d, z["vocab_rows"]))}


def make_key(seed: int):
    """A PRNG key that keeps all the bits of a seed wider than 32."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


# ---------------------------------------------------------------------------
# Matrix products: float32 at HIGHEST, or the float8 control
# ---------------------------------------------------------------------------

def _round8(x, dtype):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8(x):
    return _round8(x, jnp.float8_e4m3fn)


def _fp8_fwd(x):
    return _round8(x, jnp.float8_e4m3fn), None


def _fp8_bwd(_, g):
    return (_round8(g, jnp.float8_e5m2),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _mm(spec, a, b, prec):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if prec == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Forward pass and loss
# ---------------------------------------------------------------------------

def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        scale.astype(jnp.float32)


def _rope(x, theta):
    """Rotary embedding, rotate-half form: x [T, H, hd]."""
    t, _, hd = x.shape
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


QUERY_BLOCK = 512
LOSS_BLOCK = 1024


def _attention(p, x, z, prec):
    """Causal grouped-query attention over one sequence x [T, d], computed
    one block of queries at a time (exact softmax per query row)."""
    t = x.shape[0]
    h, kv, hd = z["h"], z["kv"], z["hd"]
    proj = lambda w, heads: _mm("td,de->te", x, w, prec).reshape(t, heads, hd)
    q = _rope(proj(p["wq"], h), z["theta"])
    k = _rope(proj(p["wk"], kv), z["theta"])
    v = proj(p["wv"], kv)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    qb = min(QUERY_BLOCK, t)

    @jax.checkpoint
    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, 0)
        s = _mm("qhe,khe->hqk", qi, k, prec) / math.sqrt(hd)
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(jnp.arange(t)[None, :] <= qpos[:, None], s, -jnp.inf)
        return _mm("hqk,khe->qhe", jax.nn.softmax(s, -1), v, prec)

    o = jax.lax.map(block, jnp.arange(t // qb)).reshape(t, h * hd)
    return _mm("te,ed->td", o, p["wo"], prec)


def _mlp(p, x, prec):
    g = _mm("td,df->tf", x, p["wg"], prec)
    u = _mm("td,df->tf", x, p["wu"], prec)
    return _mm("tf,fd->td", jax.nn.silu(g) * u, p["wo"], prec)


def _moe(p, x, z, prec):
    """Top-k routing over groups of ``group`` tokens. Within a group, each
    expert takes at most C = max(floor(group * topk * capacity / experts),
    topk) of the (token, choice) pairs routed to it, in token-major order;
    the rest are dropped. The output of a token is the sum over its kept
    choices of the renormalised gate times that expert's SwiGLU output.
    Also returns the Switch load-balancing term of the layer."""
    t, d = x.shape
    e, k = z["experts"], z["topk"]
    sg = min(z["group"], t)
    cap = max(int(sg * k * z["capacity"] / e), k)
    xg = x.reshape(t // sg, sg, d)
    probs = jax.nn.softmax(jnp.einsum("gsd,de->gse", xg, p["router"],
                                      precision=HIGHEST), -1)
    top, ids = jax.lax.top_k(probs, k)
    gate = top / jnp.clip(top.sum(-1, keepdims=True), 1e-9)
    choice = jax.nn.one_hot(ids, e, dtype=jnp.float32)          # [g, s, k, e]
    order = choice.reshape(-1, sg * k, e)
    seat = (jnp.cumsum(order, 1) - order).reshape(choice.shape)
    kept = choice * (seat < cap)
    weight = jnp.einsum("gske,gsk->gse", kept, gate).reshape(t, e)
    hg = _mm("td,edf->etf", x, p["wg"], prec)
    hu = _mm("td,edf->etf", x, p["wu"], prec)
    # the gate weight scales each expert's hidden activations, so the sum
    # over experts is one contraction and no [experts, T, d] tensor is made
    act = jax.nn.silu(hg) * hu * weight.T[:, :, None]
    out = _mm("etf,efd->td", act, p["wo"], prec)
    share = choice.sum(2).mean(1)                                # [g, e]
    aux = (share * probs.mean(1)).sum(-1).mean() * e
    return out, aux


def hidden_states(params, tokens, z, prec="f32"):
    """The final-normed hidden states [T, d] of one sequence and the
    routers' load-balancing terms [layers] (zero for a dense model)."""
    x = params["embed"][tokens].astype(jnp.float32)
    blocks = params["blocks"]

    @jax.checkpoint
    def layer(x, p):
        x = x + _attention(p["attn"], _rms(x, p["norm1"]["scale"], z["eps"]),
                           z, prec)
        h = _rms(x, p["norm2"]["scale"], z["eps"])
        if "moe" in p:
            y, aux = _moe(p["moe"], h, z, prec)
        else:
            y, aux = _mlp(p["mlp"], h, prec), jnp.float32(0.0)
        return x + y, aux

    x, auxs = jax.lax.scan(layer, x, blocks)
    return _rms(x, params["final_norm"]["scale"], z["eps"]), auxs


def head_nll(x, head, labels, prec="f32"):
    """Mean next-token cross-entropy of hidden states ``x`` [T, d] under the
    output head ``head`` [d, vocab], one block of tokens at a time."""
    tb = min(LOSS_BLOCK, x.shape[0])

    @jax.checkpoint
    def nll(xs):                       # one block of tokens
        xb, lb = xs
        logits = _mm("td,dv->tv", xb, head, prec)
        return jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, lb[:, None], -1)[:, 0]

    per_token = jax.lax.map(nll, (x.reshape(-1, tb, x.shape[1]),
                                  labels.reshape(-1, tb)))
    return per_token.mean()


def loss(params, tokens, labels, z, prec="f32"):
    """Mean next-token cross-entropy of one sequence under the untied
    ``lm_head`` (plus the routers' load-balancing term for a mixture of
    experts)."""
    x, auxs = hidden_states(params, tokens, z, prec)
    return (head_nll(x, params["lm_head"][:, :z["vocab"]], labels, prec)
            + z["aux_coef"] * auxs.sum())


def program_fields(cfg: dict) -> dict:
    """The program's ``ModelConfig`` attributes and the values that this
    model, at the configuration file's sizes, has: the program's windowed
    attention and padded query heads are not modelled here."""
    z = sizes(cfg)
    pairs = {
        "d_model": z["d"], "n_heads": z["h"], "n_kv_heads": z["kv"],
        "hd": z["hd"], "d_ff": z["ff"], "n_layers": z["layers"],
        "vocab": z["vocab"], "padded_vocab": z["vocab_rows"],
        "norm_eps": z["eps"], "rope_theta": z["theta"],
        "tie_embeddings": cfg["tie_word_embeddings"],
        "param_dtype": cfg["torch_dtype"], "moe_experts": z["experts"],
        "moe_topk": z["topk"], "sliding_window": None, "q_head_pad": 0,
    }
    if z["experts"]:
        pairs.update(moe_capacity=z["capacity"], moe_group=z["group"])
    return pairs


# ---------------------------------------------------------------------------
# Training: accumulate, balance, update
# ---------------------------------------------------------------------------

def _microbatch(z, prec, grab, loss_fn):
    """One microbatch: its gradient under the model's ``loss_fn``, the GraB
    balance against the running sum (Algorithm 5: +1 iff <s, g - m_prev> <=
    0, with m_prev zero in the first epoch) and the accumulation."""
    def f(params, acc, s, tokens, labels):
        def mean_loss(p):
            return jax.vmap(lambda a, b: loss_fn(p, a, b, z, prec))(
                tokens, labels).mean()

        p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
        val, g = jax.value_and_grad(mean_loss)(p32)
        acc = jax.tree.map(jnp.add, acc, g)
        if grab:
            dot = sum(jnp.vdot(a, b) for a, b in
                      zip(jax.tree.leaves(s), jax.tree.leaves(g)))
            eps = jnp.where(dot <= 0, 1, -1).astype(jnp.int32)
            s = jax.tree.map(lambda a, b: a + eps.astype(jnp.float32) * b,
                             s, g)
        return acc, s, val
    return jax.jit(f, donate_argnums=(1, 2))


def _adamw(hp):
    """AdamW with global-norm clipping, each update computed in float32 and
    stored in the parameter's own dtype."""
    b1, b2, eps, wd, clip = (hp["b1"], hp["b2"], hp["eps"],
                             hp["weight_decay"], hp["clip_norm"])

    def f(params, m, v, grads, t, lr):
        gn = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
        m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
        v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t

        def upd(p, a, b):
            p32 = p.astype(jnp.float32)
            p32 = p32 - lr * ((a / c1) / (jnp.sqrt(b / c2) + eps) + wd * p32)
            return p32.astype(p.dtype)

        return jax.tree.map(upd, params, m, v), m, v, grads
    return jax.jit(f, donate_argnums=(0, 1, 2, 3))


def leaf_norms(tree) -> np.ndarray:
    """Euclidean norm of each leaf, in ``jax.tree.leaves`` order."""
    return np.asarray(jax.device_get(jax.jit(lambda t: jnp.stack(
        [jnp.linalg.norm(x.astype(jnp.float32).ravel())
         for x in jax.tree.leaves(t)]))(tree)), np.float64)


def train_steps(init, steps, cfg: dict, hp: dict, *, grab: bool,
                prec: str = "f32", keep: float = 1.0, model=None) -> dict:
    """Run the first optimizer steps of the model module ``model`` (this
    file's own model where None) from the parameters ``init()`` makes.

    ``steps``: list of (tokens, labels) pairs, each ``[n_micro, micro, T]``.
    ``keep`` < 1 uses only the first share of each step's microbatches (a
    planted fault: part of the batch left out, the mean over the rest).
    Returns the per-step losses; the per-leaf norms of the first step's
    gradient (``grad_raw``) and of the same clipped, as AdamW takes it
    (``grad``); of the parameters' change over all steps (``update``); and,
    with ``grab``, of the running sum (``sum``)."""
    model = model or sys.modules[__name__]
    z = model.sizes(cfg)
    micro = _microbatch(z, prec, grab, model.loss)
    adamw = _adamw(hp)
    zeros = jax.jit(lambda: jax.tree.map(
        lambda x: jnp.zeros(x.shape, jnp.float32), jax.eval_shape(init)))
    p = init()
    m, v, s = zeros(), zeros(), (zeros() if grab else None)
    losses, out = [], {}
    for i, (tokens, labels) in enumerate(steps, start=1):
        n = max(1, int(round(len(tokens) * keep)))
        acc = zeros()
        vals = []
        for j in range(n):
            acc, s, val = micro(p, acc, s, jnp.asarray(tokens[j]),
                                jnp.asarray(labels[j]))
            vals.append(val)
        grads = jax.tree.map(lambda a: a / n, acc)
        del acc
        if i == 1:
            out["grad_raw"] = leaf_norms(grads)
        p, m, v, clipped = adamw(p, m, v, grads, jnp.float32(i),
                                 jnp.float32(hp["lr"]))
        if i == 1:
            out["grad"] = leaf_norms(clipped)
        del clipped
        losses.append(float(np.mean(jax.device_get(vals))))
    del m, v
    out["losses"] = np.asarray(losses)
    if grab:
        out["sum"] = leaf_norms(s)
    del s
    out["update"] = leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p, init()))
    return out


# ---------------------------------------------------------------------------
# Orders
# ---------------------------------------------------------------------------

def first_grab_order(n: int, seed: int) -> np.ndarray:
    """GraB's first epoch: a uniformly random permutation from the seed."""
    return np.random.default_rng((seed, 0)).permutation(n)


def reorder(order: np.ndarray, signs: np.ndarray) -> np.ndarray:
    """Algorithm 3: units with a + sign in their order, then the units with
    a - sign in reverse order."""
    order, signs = np.asarray(order), np.asarray(signs).reshape(-1)
    return np.concatenate([order[signs > 0], order[signs < 0][::-1]])


# ---------------------------------------------------------------------------
# CD-GraB: W workers, pair balance on a coordinate sketch, the int8 wire
# ---------------------------------------------------------------------------
#
# The semantics followed here (CD-GraB, Cooper et al. 2023, as the program
# documents its variant): W workers each own a contiguous shard of the n
# ordering units. The stream is time-major: at timestep t worker w takes
# slot t of its own permutation, global position t * W + w, and a step of
# n_micro units is T = n_micro / W timesteps. Per worker, even timesteps
# stash the gradient and odd ones form the pair difference z_w = g_prev -
# g. Only a fixed coordinate sketch of z takes part: k coordinates of the
# flattened parameters, allocated to leaves in proportion to their size
# (floor, then the remainder one by one to the leaves with the most
# headroom, ties to the earlier leaf) and drawn per leaf, sorted, without
# replacement, from ``numpy.random.default_rng(0)``. Each row z_w goes over
# the wire as int8 with a per-row scale max|z_w| / 127 (1 for a zero row):
# round half to even, clip to +-127, dequantize. The W rows of a timestep
# are balanced in worker order against one shared running sum s: the sign
# is +1 where <s, z> <= 0, else -1, and s += sign * z. At the epoch's end
# the pair signs (+e, -e) order the global time-major stream by Algorithm
# 3, and each worker's next permutation is that order restricted to its
# shard.
#
# The sketch is a subsample of coordinates, so it is linear: the sketch of
# a difference is the difference of the sketches. The reference therefore
# keeps each worker's stash as its [k] sketch, not as a full float32 tree.
# That keeps W gradients out of memory and lets the reference run at the
# timed sizes on one chip or, one worker row to a chip, on W.


def sketch_indices(shapes, k: int, seed: int = 0) -> list:
    """Flat indices of the coordinate sketch in each leaf of ``shapes`` (in
    ``jax.tree.leaves`` order), or None for a leaf that holds none."""
    rng = np.random.default_rng(seed)
    sizes = np.array([int(np.prod(s)) for s in shapes], np.int64)
    total = int(sizes.sum())
    target = min(int(k), total)
    alloc = np.minimum((sizes * k) // max(total, 1), sizes)
    while int(alloc.sum()) < target:
        headroom = sizes - alloc
        cand = np.flatnonzero(headroom > 0)
        take = cand[np.argsort(-headroom[cand], kind="stable")]
        alloc[take[:target - int(alloc.sum())]] += 1
    out = []
    for shape, size, a in zip(shapes, sizes, alloc):
        if not a:
            out.append(None)
        elif len(shape) == 0:
            out.append(np.zeros(1, np.int64))
        else:
            out.append(np.sort(rng.choice(int(size), size=int(a),
                                          replace=False)))
    return out


def _sketch(tree, idx):
    return jnp.concatenate([x.reshape(-1)[i] for x, i in
                            zip(jax.tree.leaves(tree), idx) if i is not None])


def int8_rows(z: np.ndarray) -> np.ndarray:
    """The rows of ``z`` [..., k] as the int8 wire carries them, in
    float32: per-row scale max|z| / 127 (1 for a zero row), values rounded
    half to even and clipped to +-127, then dequantized."""
    z = np.asarray(z, np.float32)
    amax = np.max(np.abs(z), axis=-1, keepdims=True)
    scale = np.where(amax > 0, amax / np.float32(127), np.float32(1))
    scale = scale.astype(np.float32)
    q = np.clip(np.round(z / scale), -127, 127).astype(np.float32)
    return q * scale


def sign_scan(s: np.ndarray, rows: np.ndarray, forced=None,
              flip: bool = False):
    """Balance ``rows`` [W, k] in order against the running sum ``s``
    (float64): the sign is +1 where <s, z> <= 0, else -1. Returns the new
    sum and the signs. With ``forced`` the sum takes those signs instead of
    its own, so that a sign that another computation took otherwise is
    counted once and does not move the rest. ``flip`` inverts the rule (a
    planted fault)."""
    s = np.array(s, np.float64)
    signs = np.empty(len(rows), np.int64)
    for w, z in enumerate(np.asarray(rows, np.float64)):
        signs[w] = 1 if (float(np.dot(s, z)) <= 0) != flip else -1
        s += (signs[w] if forced is None else float(forced[w])) * z
    return s, signs


def cd_scan(rows: np.ndarray, forced=None, flip: bool = False,
            live: int | None = None):
    """The sign scans of a run: ``rows`` [T, W, k], of which the odd
    timesteps balance (the even ones stash and are skipped), against one
    running sum from zero. ``forced`` [T, W] and ``flip`` as in
    :func:`sign_scan`; workers from ``live`` on read sign 0. Returns the
    sum and the signs [T, W]."""
    s = np.zeros(rows.shape[-1])
    signs = np.zeros(rows.shape[:2], np.int64)
    for tau in range(1, len(rows), 2):
        s, signs[tau] = sign_scan(s, rows[tau], None if forced is None
                                  else forced[tau], flip)
    if live is not None:
        signs[:, live:] = 0
    return s, signs


def worker_mesh(devices, workers: int):
    """A one-axis mesh ``w`` over as many of ``devices`` as divide the
    workers: one worker row to a device where there are W of them."""
    from jax.sharding import Mesh

    n = max(d for d in range(1, min(len(devices), workers) + 1)
            if workers % d == 0)
    return Mesh(np.array(devices[:n]), ("w",))


def _timestep(z, prec, idx, mesh, loss_fn):
    """One timestep of the W workers: each worker's ``loss_fn`` and float32
    gradient on its microbatch, the sketch of that gradient, and its
    gradient added, times the worker's weight (1, or 0 where a planted
    fault leaves it out), into its device's partial sum. Workers are split
    over the mesh's devices, and a device takes its own one at a time."""
    from jax.sharding import PartitionSpec as P

    def local(params, acc, tokens, labels, weight):
        p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)

        def row(a, xs):
            tok, lab, wt = xs

            def mean_loss(p):
                return jax.vmap(lambda t, l: loss_fn(p, t, l, z, prec))(
                    tok, lab).mean()

            val, g = jax.value_and_grad(mean_loss)(p32)
            a = jax.tree.map(lambda u, v: u + wt * v, a, g)
            return a, (val, _sketch(g, idx))

        a, (vals, sks) = jax.lax.scan(
            row, jax.tree.map(lambda x: x[0], acc), (tokens, labels, weight))
        return jax.tree.map(lambda x: x[None], a), vals, sks

    f = jax.shard_map(local, mesh=mesh,
                      in_specs=(P(), P("w"), P("w"), P("w"), P("w")),
                      out_specs=(P("w"), P("w"), P("w")), check_vma=False)
    return jax.jit(f, donate_argnums=(1,))


def train_steps_cd(init, steps, cfg: dict, hp: dict, *, workers: int,
                   sketch_dim: int, devices, prog_signs=None,
                   prec: str = "f32", keep: float = 1.0,
                   drop_row=None, flip: bool = False, model=None) -> dict:
    """The first optimizer steps of CD-GraB with W = ``workers``, of the
    model module ``model`` as in :func:`train_steps`, from the parameters
    ``init()`` makes.

    ``steps``: list of (tokens, labels), each ``[n_micro, micro, T]`` in
    the time-major order (unit j of a step is worker j % W's at timestep
    j // W). A step's gradient is the mean over its microbatches, whichever
    worker took each. ``prog_signs`` ([timesteps, W], the program's sign
    buffer): the running sum takes these signs, and the reference's own are
    returned beside them. ``keep`` < 1 leaves out the last workers' share
    of every timestep (their gradients, rows and signs; the mean is over
    the rest), ``drop_row`` = w zeroes worker w's row before the scan and
    ``flip`` inverts the sign rule: planted faults. Returns what
    :func:`train_steps` returns (``sum``: the norm of the running sum),
    ``signs`` [timesteps, W] and the rows the scans read (``rows``
    [timesteps, W, k], zero on stash timesteps), which the gradients alone
    fix: :func:`cd_scan` of them with other forced signs gives the sum and
    signs that a run forced to those would."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    model = model or sys.modules[__name__]
    z = model.sizes(cfg)
    mesh = worker_mesh(devices, workers)
    repl = NamedSharding(mesh, P())
    rows_sh = NamedSharding(mesh, P("w"))
    shapes = [x.shape for x in jax.tree.leaves(jax.eval_shape(init))]
    idx = [None if i is None else jnp.asarray(i)
           for i in sketch_indices(shapes, sketch_dim)]
    step_t = _timestep(z, prec, idx, mesh, model.loss)
    adamw = _adamw(hp)
    zeros = jax.jit(lambda: jax.tree.map(
        lambda x: jnp.zeros(x.shape, jnp.float32), jax.eval_shape(init)),
        out_shardings=repl)
    partial = jax.jit(lambda: jax.tree.map(
        lambda x: jnp.zeros((mesh.size,) + x.shape, jnp.float32),
        jax.eval_shape(init)), out_shardings=rows_sh)
    n_keep = max(1, int(round(workers * keep)))
    weight = np.asarray([1.0] * n_keep + [0.0] * (workers - n_keep),
                        np.float32)
    n = len(steps[0][0]) // workers * n_keep
    mean = jax.jit(lambda a: jax.tree.map(lambda x: x.sum(0) / n, a),
                   out_shardings=repl)

    p = jax.device_put(init(), repl)
    m, v = zeros(), zeros()
    losses, scanned, out = [], [], {}
    for i, (tokens, labels) in enumerate(steps, start=1):
        tw = tokens.reshape((-1, workers) + tokens.shape[1:])
        lw = labels.reshape((-1, workers) + labels.shape[1:])
        acc = partial()
        vals = []
        for t in range(tw.shape[0]):
            acc, val, sk = step_t(p, acc, jax.device_put(tw[t], rows_sh),
                                  jax.device_put(lw[t], rows_sh),
                                  jax.device_put(weight, rows_sh))
            vals.extend(np.asarray(jax.device_get(val))[:n_keep])
            sk = np.asarray(jax.device_get(sk), np.float32)
            rows = np.zeros_like(sk)
            if len(scanned) % 2 == 0:
                stash = sk
            else:
                rows = int8_rows(stash - sk)
                rows[n_keep:] = 0.0
                if drop_row is not None:
                    rows[drop_row] = 0.0
            scanned.append(rows)
        grads = mean(acc)
        del acc
        if i == 1:
            out["grad_raw"] = leaf_norms(grads)
        p, m, v, clipped = adamw(p, m, v, grads, jnp.float32(i),
                                 jnp.float32(hp["lr"]))
        if i == 1:
            out["grad"] = leaf_norms(clipped)
        del clipped
        losses.append(float(np.mean(vals)))
    del m, v
    out["losses"] = np.asarray(losses)
    out["rows"] = np.stack(scanned)
    s, out["signs"] = cd_scan(out["rows"], prog_signs, flip, n_keep)
    out["sum"] = np.asarray([np.linalg.norm(s)])
    p0 = jax.device_put(init(), repl)
    out["update"] = leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p, p0))
    return out


def cd_first_order(n: int, workers: int, seed: int) -> np.ndarray:
    """CD-GraB's first epoch: a random permutation from the seed, restricted
    to each worker's contiguous shard, interleaved time-major."""
    init = np.random.default_rng((seed, 0)).permutation(n)
    m = n // workers
    return np.stack([init[init // m == w] for w in range(workers)]
                    ).T.reshape(-1)


def cd_reorder(order: np.ndarray, signs: np.ndarray) -> np.ndarray:
    """The next epoch's time-major order: each worker's pair signs
    (``signs`` [T, W], zero on stash rows) expanded to (+e, -e), Algorithm
    3 on the global stream, and the result restricted to each shard."""
    signs = np.asarray(signs)
    workers = signs.shape[1]
    full = np.empty_like(signs)
    full[0::2] = signs[1::2]
    full[1::2] = -signs[1::2]
    out = reorder(order, full.reshape(-1))
    m = len(order) // workers
    return np.stack([out[out // m == w] for w in range(workers)]
                    ).T.reshape(-1)
