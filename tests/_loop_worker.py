"""Subprocess worker for the async-loop smoke (``tests/test_async_loop.py``).

Runs ``examples/train_lm.py --preset cpu-smoke`` (the real driver, not a
mock) with ``--ordering cd-grab --mesh`` on a *forced 4-device CPU mesh*,
under two transfer guards:

* ``jax.transfer_guard_device_to_host("disallow")`` — any **implicit**
  device→host transfer in the step loop (the legacy ``float(loss)`` /
  ``np.asarray(signs)`` per-step syncs) raises immediately;
* a counting wrapper around ``jax.device_get`` — every **explicit** fetch is
  tallied, with single-leaf int8 matrices (the ``[T, W]`` sign buffer)
  classified separately.

Prints one ``RESULT {json}`` line with the counts; the parent test asserts
signs are fetched at most once per epoch and the total explicit-fetch count
stays at the once-per-epoch scale.
"""
import json
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(_REPO, "src"))

import numpy as np          # noqa: E402
import jax                  # noqa: E402

EPOCHS = 2

COUNTS = {"device_get": 0, "sign_fetch": 0}
_orig_device_get = jax.device_get


def _counting_device_get(x):
    COUNTS["device_get"] += 1
    leaves = jax.tree.leaves(x)
    if (len(leaves) == 1 and getattr(leaves[0], "dtype", None) == np.int8
            and getattr(leaves[0], "ndim", 0) == 2):
        COUNTS["sign_fetch"] += 1
    return _orig_device_get(x)


def main():
    assert jax.device_count() == 4, jax.devices()
    jax.device_get = _counting_device_get
    sys.argv = ["train_lm.py", "--preset", "cpu-smoke",
                "--ordering", "cd-grab", "--workers", "4", "--mesh",
                "--sketch-dim", "96", "--epochs", str(EPOCHS)]
    # the parent test can ask for the structured run log; telemetry runs
    # inside the same transfer guard + device_get counting, so the asserted
    # bounds double as "instrumentation adds zero per-step host syncs"
    metrics_out = os.environ.get("REPRO_TEST_METRICS")
    if metrics_out:
        sys.argv += ["--metrics-out", metrics_out]
    # a no-op epoch hook, so that the loop's epoch_hook span runs inside
    # the guard too
    import repro.train
    run_training = repro.train.run_training
    repro.train.run_training = lambda *a, **kw: run_training(
        *a, hooks=lambda epoch, state, history: None, **kw)
    import runpy
    with jax.transfer_guard_device_to_host("disallow"):
        runpy.run_path(os.path.join(_REPO, "examples", "train_lm.py"),
                       run_name="__main__")
    print("RESULT " + json.dumps({"epochs": EPOCHS, "devices": 4, **COUNTS}))


if __name__ == "__main__":
    main()
