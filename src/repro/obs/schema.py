"""The run-log record schema: one contract for live runs and benchmarks.

Every structured record this repo emits — the training loop's JSONL run log
(``train.loop`` via ``obs.registry``), the serve engine's latency summaries,
and the benchmark JSONs (``benchmarks/common.py::make_bench_record``) —
carries the same envelope::

    {"schema": "repro.obs/v1", "kind": <KINDS>, "time_unix": ..., "seq": ...}

plus the kind's required payload fields (:data:`REQUIRED`). That single
schema is what makes a live run's step-time quantiles directly comparable to
``BENCH_cd_grab.json``'s wall-clock rows: ``benchmarks/check_regression.py``
validates both sides against this module before trending them against each
other.

Records are validated at *write* time (``obs.registry.JsonlSink``) and again
at *read* time (the regression gate), so a drifting producer fails its own
CI run instead of silently corrupting the trend tables.
"""
from __future__ import annotations

import json
from typing import Any, Dict, Iterable, List

SCHEMA_VERSION = "repro.obs/v1"

# Envelope fields present on every record.
ENVELOPE = ("schema", "kind", "time_unix", "seq")

# kind -> required payload fields (beyond the envelope).
REQUIRED: Dict[str, tuple] = {
    # one per run: the run's static configuration
    "run_meta": ("run", "config"),
    # a human-readable event (the loop's former prints, resume notices, ...)
    "event": ("msg",),
    # one per epoch: wall time + cumulative phase-timer quantiles/counters
    "epoch": ("epoch", "duration_s", "timers", "counters", "gauges"),
    # one per epoch (GraB orderings): zero-sync ordering-quality metrics
    # computed from the device-resident sign buffer's once-per-epoch fetch
    "quality": ("epoch", "n_decisions", "signed_prefix_max",
                "herding_proxy_norm", "sign_flip_rate", "balance_prefix_max"),
    # offline benchmark record (BENCH_*.json)
    "bench": ("bench", "config", "rows"),
    # serve-path latency summary (prefill/decode quantiles)
    "serve": ("timers",),
}

KINDS = tuple(REQUIRED)


class SchemaError(ValueError):
    """A record violates the run-log schema (missing/typed-wrong fields)."""


def _jsonable(x: Any) -> Any:
    """Convert numpy scalars/arrays (and other array-likes) to plain JSON
    types so records serialize without a custom encoder."""
    if isinstance(x, dict):
        return {str(k): _jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_jsonable(v) for v in x]
    if hasattr(x, "item") and getattr(x, "ndim", None) == 0:
        return x.item()
    if hasattr(x, "tolist"):
        return x.tolist()
    if isinstance(x, (str, int, float, bool)) or x is None:
        return x
    return str(x)


def make_record(kind: str, time_unix: float, seq: int, **fields) -> dict:
    """Build + validate one schema record. ``fields`` is the kind's payload;
    numpy values are converted to plain JSON types."""
    rec = {"schema": SCHEMA_VERSION, "kind": kind,
           "time_unix": float(time_unix), "seq": int(seq)}
    rec.update(_jsonable(fields))
    validate_record(rec)
    return rec


def validate_record(rec: Any) -> dict:
    """Raise :class:`SchemaError` unless ``rec`` is a schema-valid record;
    returns the record for chaining."""
    if not isinstance(rec, dict):
        raise SchemaError(f"record must be a dict, got {type(rec).__name__}")
    for f in ENVELOPE:
        if f not in rec:
            raise SchemaError(f"record missing envelope field {f!r}: "
                              f"{_preview(rec)}")
    if rec["schema"] != SCHEMA_VERSION:
        raise SchemaError(
            f"record schema {rec['schema']!r} != {SCHEMA_VERSION!r} — "
            f"regenerate the file or teach the reader the new version")
    kind = rec["kind"]
    if kind not in REQUIRED:
        raise SchemaError(f"unknown record kind {kind!r} (known: {KINDS})")
    if not isinstance(rec["time_unix"], (int, float)):
        raise SchemaError(f"time_unix must be a number: {_preview(rec)}")
    if not isinstance(rec["seq"], int):
        raise SchemaError(f"seq must be an int: {_preview(rec)}")
    missing = [f for f in REQUIRED[kind] if f not in rec]
    if missing:
        raise SchemaError(f"{kind!r} record missing required fields "
                          f"{missing}: {_preview(rec)}")
    try:
        json.dumps(rec)
    except (TypeError, ValueError) as e:
        raise SchemaError(f"record not JSON-serializable ({e}): "
                          f"{_preview(rec)}") from None
    return rec


def _preview(rec: Any, n: int = 200) -> str:
    s = repr(rec)
    return s if len(s) <= n else s[:n] + "..."


def read_jsonl(path: str) -> List[dict]:
    """Read + validate a JSONL run log; raises :class:`SchemaError` with the
    offending line number on the first invalid record."""
    out = []
    with open(path) as f:
        for i, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                raise SchemaError(f"{path}:{i}: invalid JSON ({e})") from None
            try:
                validate_record(rec)
            except SchemaError as e:
                raise SchemaError(f"{path}:{i}: {e}") from None
            out.append(rec)
    return out


def records_of_kind(records: Iterable[dict], kind: str) -> List[dict]:
    return [r for r in records if r.get("kind") == kind]
