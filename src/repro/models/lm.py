"""Generic config-driven language model.

One parameter pytree, one forward, four block families (dense / moe / rwkv6 /
hymba). Layers are **stacked and scanned** (MaxText-style scan-over-layers):
per-layer params carry a leading ``[L, ...]`` axis and the stack runs under a
single ``lax.scan`` with optional per-layer remat — this keeps HLO size and
compile time flat in depth.

Entry points:
  init_lm(key, cfg)                        -> params
  forward(params, cfg, tokens, ...)        -> logits          (train / prefill)
  loss_fn(params, cfg, batch)              -> (loss, metrics)
  init_cache(cfg, batch, max_len)          -> cache pytree
  prefill(params, cfg, tokens)             -> (logits, cache)
  decode_step(params, cfg, token, cache)   -> (logits, cache)
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.act_sharding import constrain
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import rwkv6 as R6
from repro.models import ssm as SSM


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_block(key, cfg: ModelConfig):
    ks = jax.random.split(key, 8)
    p = {"norm1": L.init_norm(cfg), "norm2": L.init_norm(cfg)}
    if cfg.block == "dense":
        p["attn"] = L.init_attention(ks[0], cfg)
        p["mlp"] = L.init_mlp(ks[1], cfg)
    elif cfg.block == "moe":
        p["attn"] = L.init_attention(ks[0], cfg)
        p["moe"] = MOE.init_moe(ks[1], cfg)
    elif cfg.block == "rwkv6":
        p["tmix"] = R6.init_rwkv_time_mix(ks[0], cfg)
        p["cmix"] = R6.init_rwkv_channel_mix(ks[1], cfg)
    elif cfg.block == "hymba":
        p["attn"] = L.init_attention(ks[0], cfg)
        p["ssm"] = SSM.init_ssm(ks[1], cfg)
        p["mlp"] = L.init_mlp(ks[2], cfg)
        p["norm_attn"] = L.init_norm(cfg)
        p["norm_ssm"] = L.init_norm(cfg)
    else:
        raise ValueError(cfg.block)
    return p


def init_lm(key, cfg: ModelConfig):
    k_embed, k_blocks, k_head = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.param_dtype)
    block_keys = jax.random.split(k_blocks, cfg.n_layers)
    blocks = jax.vmap(lambda k: _init_block(k, cfg))(block_keys)  # stacked [L,...]
    params = {
        "embed": L.dense_init(k_embed, (cfg.padded_vocab, cfg.d_model), dt, scale=0.02),
        "blocks": blocks,
        "final_norm": L.init_norm(cfg),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(k_head, (cfg.d_model, cfg.padded_vocab), dt)
    return params


# ---------------------------------------------------------------------------
# Block application (full sequence)
# ---------------------------------------------------------------------------

def _apply_block(p, x, cfg: ModelConfig, positions, collect: bool = False):
    """One block. If ``collect``, also return the serving-cache payload
    (K/V for attention, final recurrent state for SSM/RWKV)."""
    aux = jnp.float32(0.0)
    payload = None
    if cfg.block == "dense":
        h = L.apply_norm(p["norm1"], x, cfg)
        if collect:
            y, kv = L.apply_attention(p["attn"], h, cfg, positions, return_kv=True)
            payload = {"kv": kv}
        else:
            y = L.apply_attention(p["attn"], h, cfg, positions)
        x = x + y
        x = x + L.apply_mlp(p["mlp"], L.apply_norm(p["norm2"], x, cfg), cfg)
    elif cfg.block == "moe":
        h = L.apply_norm(p["norm1"], x, cfg)
        if collect:
            y, kv = L.apply_attention(p["attn"], h, cfg, positions, return_kv=True)
            payload = {"kv": kv}
        else:
            y = L.apply_attention(p["attn"], h, cfg, positions)
        x = x + y
        y, aux = MOE.apply_moe(p["moe"], L.apply_norm(p["norm2"], x, cfg), cfg)
        x = x + y
    elif cfg.block == "rwkv6":
        h = L.apply_norm(p["norm1"], x, cfg)
        if collect:
            y, S = R6.apply_rwkv_time_mix(p["tmix"], h, cfg, return_state=True)
        else:
            y = R6.apply_rwkv_time_mix(p["tmix"], h, cfg)
        x = x + y
        h2 = L.apply_norm(p["norm2"], x, cfg)
        x = x + R6.apply_rwkv_channel_mix(p["cmix"], h2, cfg)
        if collect:
            payload = {"rwkv": {"S": S, "x_prev": h[:, -1], "x_prev_cm": h2[:, -1]}}
    elif cfg.block == "hymba":
        y = L.apply_norm(p["norm1"], x, cfg)
        if collect:
            a_raw, kv = L.apply_attention(p["attn"], y, cfg, positions, return_kv=True)
            s_raw, ssm_state = SSM.apply_ssm(p["ssm"], y, cfg, return_state=True)
            payload = {"kv": kv, "ssm": ssm_state}
        else:
            a_raw = L.apply_attention(p["attn"], y, cfg, positions)
            s_raw = SSM.apply_ssm(p["ssm"], y, cfg)
        a = L.apply_norm(p["norm_attn"], a_raw, cfg)
        s = L.apply_norm(p["norm_ssm"], s_raw, cfg)
        x = x + 0.5 * (a + s)          # parallel attention+SSM heads, fused mean
        x = x + L.apply_mlp(p["mlp"], L.apply_norm(p["norm2"], x, cfg), cfg)
    if collect:
        return x, (aux, payload)
    return x, aux


def _scan_blocks(params, x, cfg: ModelConfig, positions, remat: bool):
    body = functools.partial(_apply_block, cfg=cfg, positions=positions)
    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)

    def step(carry, layer_params):
        y, aux = body(layer_params, carry)
        return constrain(y, "btd"), aux

    x, auxs = jax.lax.scan(step, x, params["blocks"])
    return x, jnp.sum(auxs)


def _logits(params, cfg: ModelConfig, x):
    """[..., padded_vocab] logits with the padding columns masked to -inf."""
    if cfg.tie_embeddings:
        y = x @ params["embed"].T
    else:
        head = params["lm_head"]
        if isinstance(head, dict) and set(head.keys()) == {"q", "s"}:
            from repro.serve.quant import dequantize_leaf
            head = dequantize_leaf(head, x.dtype)
        y = x @ head
    if cfg.padded_vocab != cfg.vocab:
        pad_mask = jnp.arange(cfg.padded_vocab) < cfg.vocab
        y = jnp.where(pad_mask, y, jnp.asarray(L.NEG_INF, y.dtype))
    return y


def forward(params, cfg: ModelConfig, tokens: jax.Array,
            prefix_embeds: Optional[jax.Array] = None, remat: bool = False):
    """tokens: [B, T_txt] int32; prefix_embeds: optional [B, T_pre, d]
    (internvl patch embeddings / whisper-free audio stubs). Returns
    (logits [B, T, V], aux) where T = T_pre + T_txt."""
    x = params["embed"][tokens]
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    x = constrain(x, "btd")
    B, T, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    x, aux = _scan_blocks(params, x, cfg, positions, remat)
    x = L.apply_norm(params["final_norm"], x, cfg)
    return constrain(_logits(params, cfg, x), "logits"), aux


def loss_fn(params, cfg: ModelConfig, batch, remat: bool = True):
    """batch: {"tokens": [B,T], "labels": [B,T] (-1 = ignore),
    optional "prefix_embeds": [B,P,d]} — next-token CE in f32."""
    logits, aux = forward(params, cfg, batch["tokens"],
                          batch.get("prefix_embeds"), remat=remat)
    labels = batch["labels"]
    if "prefix_embeds" in batch:
        logits = logits[:, batch["prefix_embeds"].shape[1]:]
    logits = logits.astype(jnp.float32)
    valid = labels >= 0
    lab = jnp.maximum(labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * valid
    loss = nll.sum() / jnp.maximum(valid.sum(), 1)
    if cfg.block == "moe":
        loss = loss + 0.01 * aux
    return loss, {"loss": loss, "aux": aux, "tokens": valid.sum()}


# ---------------------------------------------------------------------------
# Serving: prefill + decode with per-family cache
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               quant_cache: bool = False):
    """``quant_cache``: int8 KV entries + per-(token, head) scales — halves
    decode residency (see layers.init_attention_cache)."""
    def one_layer(_):
        if cfg.block in ("dense", "moe"):
            return {"attn": L.init_attention_cache(cfg, batch, max_len,
                                                   quant=quant_cache)}
        if cfg.block == "rwkv6":
            return {"rwkv": R6.init_rwkv_state(cfg, batch)}
        if cfg.block == "hymba":
            return {"attn": L.init_attention_cache(cfg, batch, max_len,
                                                   quant=quant_cache),
                    "ssm": SSM.init_ssm_state(cfg, batch)}
        raise ValueError(cfg.block)

    # stacked along layer axis to match the scanned block params
    caches = [one_layer(i) for i in range(cfg.n_layers)]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *caches)


def _apply_block_decode(p, x, cfg: ModelConfig, cache):
    new_cache = dict(cache)
    if cfg.block in ("dense", "moe"):
        h = L.apply_norm(p["norm1"], x, cfg)
        y, new_cache["attn"] = L.apply_attention_decode(p["attn"], h, cfg, cache["attn"])
        x = x + y
        h = L.apply_norm(p["norm2"], x, cfg)
        if cfg.block == "dense":
            x = x + L.apply_mlp(p["mlp"], h, cfg)
        else:
            y, _ = MOE.apply_moe(p["moe"], h, cfg)
            x = x + y
    elif cfg.block == "rwkv6":
        h = L.apply_norm(p["norm1"], x, cfg)
        y, new_cache["rwkv"] = R6.apply_rwkv_time_mix_decode(p["tmix"], h, cfg, cache["rwkv"])
        x = x + y
        h = L.apply_norm(p["norm2"], x, cfg)
        x = x + R6.apply_rwkv_channel_mix(p["cmix"], h, cfg,
                                          x_prev=cache["rwkv"]["x_prev_cm"])
        new_cache["rwkv"] = dict(new_cache["rwkv"], x_prev_cm=h[:, 0])
    elif cfg.block == "hymba":
        h = L.apply_norm(p["norm1"], x, cfg)
        ya, new_cache["attn"] = L.apply_attention_decode(p["attn"], h, cfg, cache["attn"])
        ys, new_cache["ssm"] = SSM.apply_ssm_decode(p["ssm"], h, cfg, cache["ssm"])
        a = L.apply_norm(p["norm_attn"], ya, cfg)
        s = L.apply_norm(p["norm_ssm"], ys, cfg)
        x = x + 0.5 * (a + s)
        x = x + L.apply_mlp(p["mlp"], L.apply_norm(p["norm2"], x, cfg), cfg)
    return x, new_cache


def decode_step(params, cfg: ModelConfig, token: jax.Array, cache):
    """token: [B] int32; cache from init_cache/prefill. One new token.

    Transparently supports weight-only int8 params (repro.serve.quant):
    quantized leaves are dequantized per layer *inside* the scan, so only a
    one-layer bf16 transient ever materializes."""
    from repro.serve.quant import maybe_dequant

    x = params["embed"][token][:, None, :]          # [B, 1, d]

    def step(carry, scanned):
        layer_params, layer_cache = scanned
        layer_params = maybe_dequant(layer_params)
        y, new_cache = _apply_block_decode(layer_params, carry, cfg, layer_cache)
        return y, new_cache

    x, new_caches = jax.lax.scan(step, x, (params["blocks"], cache))
    x = L.apply_norm(params["final_norm"], x, cfg)
    return _logits(params, cfg, x)[:, 0], new_caches


def _kv_to_cache(cfg: ModelConfig, kv, max_len: int):
    """Place full-sequence K/V [B,T,KV,hd] into a (possibly ring) cache."""
    k, v = kv
    B, T = k.shape[:2]
    L = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    ck = jnp.zeros((B, L, cfg.n_kv_heads, cfg.hd), k.dtype)
    cv = jnp.zeros_like(ck)
    W = min(T, L)
    pos = jnp.arange(T - W, T)
    slots = pos % L if cfg.sliding_window else pos
    ck = ck.at[:, slots].set(k[:, T - W:])
    cv = cv.at[:, slots].set(v[:, T - W:])
    return {"k": ck, "v": cv, "idx": jnp.int32(T)}


def prefill(params, cfg: ModelConfig, tokens: jax.Array, max_len: int,
            prefix_embeds: Optional[jax.Array] = None):
    """Run the full prompt once, return (last-token logits, primed cache).

    One batched forward collects per-layer K/V (attention families) and/or
    the final recurrent state (SSM/RWKV families) — no sequential replay.
    ``prefix_embeds``: optional multimodal prefix (internvl patch embeddings).
    """
    x = params["embed"][tokens]
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    B, T = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))

    def step(carry, layer_params):
        y, (aux, payload) = _apply_block(layer_params, carry, cfg, positions,
                                         collect=True)
        c = {}
        if "kv" in payload:
            c["attn"] = _kv_to_cache(cfg, payload["kv"], max_len)
        if "rwkv" in payload:
            c["rwkv"] = payload["rwkv"]
        if "ssm" in payload:
            c["ssm"] = payload["ssm"]
        return constrain(y, "btd"), c

    # cache entries come out of the scan already stacked along the layer axis
    x, cache = jax.lax.scan(step, x, params["blocks"])
    x = L.apply_norm(params["final_norm"], x, cfg)
    logits = _logits(params, cfg, x)
    return logits[:, -1], cache
