"""Plain reference of one training job: the model's loss and gradients, the
GraB balance in full-pytree mode, the Algorithm-3 reorder and AdamW.

Written from the published descriptions in ``jax.numpy``: float32 with every
matrix product at ``Precision.HIGHEST``, attention as an exact softmax over
blocks of queries, the mixture of experts as a dense sum over all experts
weighted by the routed and capacity-limited gates. It imports nothing of the
program. It reads the sizes from the benchmark's configuration file.

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

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# Sizes from the configuration file
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


def loss(params, tokens, labels, z, prec="f32"):
    """Mean next-token cross-entropy of one sequence (plus the routers'
    load-balancing term for a mixture of experts)."""
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
    x = _rms(x, params["final_norm"]["scale"], z["eps"])
    head = params["lm_head"][:, :z["vocab"]]
    tb = min(LOSS_BLOCK, x.shape[0])

    @jax.checkpoint
    def nll(xs):                       # one block of tokens
        xb, lb = xs
        logits = _mm("td,dv->tv", xb, head, prec)
        return jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, lb[:, None], -1)[:, 0]

    per_token = jax.lax.map(nll, (x.reshape(-1, tb, x.shape[1]),
                                  labels.reshape(-1, tb)))
    return per_token.mean() + z["aux_coef"] * auxs.sum()


# ---------------------------------------------------------------------------
# Training: accumulate, balance, update
# ---------------------------------------------------------------------------

def _microbatch(z, prec, grab):
    """One microbatch: its gradient, the GraB balance against the running
    sum (Algorithm 5: +1 iff <s, g - m_prev> <= 0, with m_prev zero in the
    first epoch) and the accumulation."""
    def f(params, acc, s, tokens, labels):
        def mean_loss(p):
            return jax.vmap(lambda a, b: loss(p, a, b, z, prec))(
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
                prec: str = "f32", keep: float = 1.0) -> dict:
    """Run the first optimizer steps from the parameters ``init()`` makes.

    ``steps``: list of (tokens, labels) pairs, each ``[n_micro, micro, T]``.
    ``keep`` < 1 uses only the first share of each step's microbatches (a
    planted fault: part of the batch left out, the mean over the rest).
    Returns the per-step losses; the per-leaf norms of the first step's
    gradient (``grad_raw``) and of the same clipped, as AdamW takes it
    (``grad``); of the parameters' change over all steps (``update``); and,
    with ``grab``, of the running sum (``sum``)."""
    z = sizes(cfg)
    micro = _microbatch(z, prec, grab)
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
