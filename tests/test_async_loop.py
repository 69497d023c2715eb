"""Async-loop smoke: the live training loop on a real (forced) 4-device CPU
mesh performs **zero implicit per-step device→host transfers** and fetches
the device-resident sign buffer **at most once per epoch** — with full
telemetry on.

The measurement runs in a subprocess (``tests/_loop_worker.py``) because the
device count locks at jax init: the worker forces 4 CPU devices, drives the
real ``examples/train_lm.py --preset cpu-smoke`` CLI with
``--ordering cd-grab --mesh --metrics-out``, runs the whole thing under
``jax.transfer_guard_device_to_host("disallow")`` (so any legacy per-step
``float(loss)`` / ``np.asarray(signs)`` sync would crash it), and tallies
explicit ``jax.device_get`` calls.

Because the run log is written *inside* the guard and the counting wrapper,
the unchanged device_get bounds are the proof that the telemetry subsystem
(per-step phase timers, per-epoch ordering-quality metrics) adds **zero**
extra device→host syncs: the quality metrics ride the one sign fetch per
epoch the loop already made.
"""
import json
import os
import subprocess
import sys

_REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_worker(tmp_path):
    metrics_path = str(tmp_path / "run_metrics.jsonl")
    env = os.environ.copy()
    env.pop("XLA_FLAGS", None)            # the worker sets its own
    env["JAX_PLATFORMS"] = "cpu"
    env["REPRO_TEST_METRICS"] = metrics_path
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.abspath(_REPO_SRC)] + env.get("PYTHONPATH", "").split(os.pathsep))
    proc = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__),
                                      "_loop_worker.py")],
        capture_output=True, text=True, env=env, timeout=900)
    assert proc.returncode == 0, \
        f"async-loop worker failed:\n{proc.stderr[-3000:]}"
    result_lines = [l for l in proc.stdout.splitlines()
                    if l.startswith("RESULT ")]
    assert result_lines, proc.stdout[-2000:]
    rec = json.loads(result_lines[-1][len("RESULT "):])
    return rec, metrics_path


def test_async_loop_fetches_signs_once_per_epoch(tmp_path):
    rec, metrics_path = _run_worker(tmp_path)
    # the contract from ISSUE 5: signs come back at most once per epoch
    assert rec["sign_fetch"] <= rec["epochs"], rec
    assert rec["sign_fetch"] >= 1, rec            # ...but they do come back
    # every explicit fetch is epoch-scale (sign buffer + batched loss
    # flushes), never step-scale: cpu-smoke runs 8 steps per epoch, so a
    # per-step fetch would blow far past this bound. The run log was written
    # inside the same guard/counter, so this bound holding with telemetry on
    # proves the metrics add zero extra per-step host syncs.
    assert rec["device_get"] <= rec["epochs"] * 4, rec

    # -- the structured run log the same guarded run emitted ---------------
    from repro.obs.schema import read_jsonl, records_of_kind

    records = read_jsonl(metrics_path)       # raises on any invalid line
    meta = records_of_kind(records, "run_meta")
    assert len(meta) == 1, [r["kind"] for r in records]
    cfg = meta[0]["config"]
    assert cfg["ordering"] == "cd-grab" and cfg["workers"] == 4, cfg

    epochs = records_of_kind(records, "epoch")
    assert len(epochs) == rec["epochs"], [r["kind"] for r in records]
    for i, ep in enumerate(epochs):
        timers = ep["timers"]
        # per-step timer quantiles + every instrumented phase showed up
        for t in ("phase.step", "phase.dispatch", "phase.loader_wait",
                  "phase.epoch_reorder"):
            assert t in timers, (t, sorted(timers))
        # the epoch boundary's sub-spans and the hook's span, once per
        # epoch (timers are cumulative over the run)
        for t in ("phase.sign_fetch", "phase.reorder", "phase.rollover",
                  "phase.epoch_hook"):
            assert timers[t]["count"] == i + 1, (t, timers.get(t))
        # the GraB state's bytes per chip, set once at set-up
        assert ep["gauges"]["grab.state_bytes"]["last"] > 0
        assert ep["gauges"]["grab.state_bytes"]["n"] == 1
        for q in ("p50_s", "p95_s", "p99_s"):
            assert timers["phase.step"][q] >= 0.0
        # loader health gauges ride the same record
        assert "loader.queue_depth" in ep["gauges"], sorted(ep["gauges"])
        assert "loader.producer_wait_s" in ep["counters"]
    # timer summaries are cumulative: the final epoch record carries every
    # step of the run (cpu-smoke: 8 steps/epoch)
    assert epochs[-1]["timers"]["phase.step"]["count"] == 8 * rec["epochs"]

    quality = records_of_kind(records, "quality")
    assert len(quality) == rec["epochs"]
    for qr in quality:
        # 8 steps/epoch on 4 workers -> 4 pair decisions/worker -> 16 total
        assert qr["n_decisions"] == 16, qr
        assert 0.0 <= qr["zero_fraction"] < 1.0, qr
        assert qr["signed_prefix_max"] >= 1.0, qr
        # expanded pairs cancel by construction: prefix stays O(W)
        assert qr["balance_prefix_max"] <= 2 * 4, qr
