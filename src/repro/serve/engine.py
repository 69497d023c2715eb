"""Serving: jit'd prefill + decode step builders and a small batched engine.

``ServeEngine`` jits these two functions: prefill, then one decode step
at a time against a max_len-deep cache.
"""
from __future__ import annotations

import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm, whisper
from repro.models.config import ModelConfig
from repro.obs import MetricsRegistry, phase


def build_prefill(cfg: ModelConfig, max_len: int) -> Callable:
    if cfg.enc_dec:
        def prefill_fn(params, batch):
            # whisper "prefill": encode + prime decoder cache from the prompt
            logits = whisper.forward(params, cfg, batch["frames"], batch["tokens"])
            cache = whisper.init_dec_cache(params, cfg, batch["frames"], max_len)
            return logits[:, -1], cache
        return prefill_fn

    def prefill_fn(params, batch):
        return lm.prefill(params, cfg, batch["tokens"], max_len)
    return prefill_fn


def build_decode_step(cfg: ModelConfig) -> Callable:
    if cfg.enc_dec:
        def decode_fn(params, token, cache):
            return whisper.decode_step(params, cfg, token, cache)
        return decode_fn

    def decode_fn(params, token, cache):
        return lm.decode_step(params, cfg, token, cache)
    return decode_fn


class ServeEngine:
    """Minimal batched greedy-decoding engine over the jit'd steps.

    The decode loop is **dispatch-asynchronous**, mirroring the training
    loop's contract: each step feeds the device-resident token straight
    back into the next jit'd decode, generated tokens accumulate on the
    device, and the whole sequence comes to the host in ONE batched
    ``jax.device_get`` after the last step (the serve token-sync
    chokepoint). The old per-token ``np.asarray`` blocked dispatch once
    per generated token — the step-path sync bug class the invariant
    linter (``repro.analysis``) flags.

    Latency telemetry (``repro.obs``) is always on and costs two
    ``perf_counter`` reads per phase: ``serve.prefill`` times prefill +
    the first-token sync (time-to-first-token stays a true latency),
    ``serve.decode`` times each token's dispatch, and ``serve.fetch``
    times the final batched fetch. Streaming p50/p95/p99 accumulate
    across ``generate`` calls — :meth:`latency_summary` is the serve-path
    record the load benchmarks and the run log share (schema kind
    ``serve``).
    """

    def __init__(self, params, cfg: ModelConfig, max_len: int,
                 metrics: Optional[MetricsRegistry] = None):
        self.params, self.cfg, self.max_len = params, cfg, max_len
        self._prefill = jax.jit(build_prefill(cfg, max_len))
        self._decode = jax.jit(build_decode_step(cfg))
        self.metrics = metrics if metrics is not None else MetricsRegistry()

    def generate(self, batch, n_tokens: int) -> np.ndarray:
        reg = self.metrics
        prefill_t = reg.timer("serve.prefill")
        decode_t = reg.timer("serve.decode")
        fetch_t = reg.timer("serve.fetch")
        t0 = time.perf_counter()
        with phase("serve_prefill"):
            logits, cache = self._prefill(self.params, batch)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            # TTFT sync: one fetch per request so serve.prefill stays a
            # true time-to-first-token latency
            first = jax.device_get(tok)  # repro: allow[host-sync]
        prefill_t.record(time.perf_counter() - t0)
        out = [first]
        for _ in range(n_tokens - 1):
            t0 = time.perf_counter()
            with phase("serve_decode"):
                logits, cache = self._decode(self.params, tok, cache)
                tok = jnp.argmax(logits, -1).astype(jnp.int32)
                out.append(tok)     # stays on device: fetched in one batch
            decode_t.record(time.perf_counter() - t0)
        t0 = time.perf_counter()
        with phase("serve_fetch"):
            # the serve token-sync chokepoint: ONE batched device→host
            # transfer for the whole generated sequence
            toks = jax.device_get(out)  # repro: allow[host-sync]
        fetch_t.record(time.perf_counter() - t0)
        reg.counter("serve.tokens").inc(n_tokens * toks[0].shape[0])
        reg.counter("serve.requests").inc()
        return np.stack(toks, axis=1)

    def latency_summary(self) -> dict:
        """Cumulative prefill/decode latency quantiles (p50/p95/p99 seconds)
        plus token/request counters, in run-log ``serve`` record shape."""
        s = self.metrics.summary()
        return {"timers": s["timers"], "counters": s["counters"]}
