"""Kill/resume equivalence (ISSUE 5 satellite): training N epochs straight
must be **bit-identical** to training that is killed mid-epoch and resumed
from the newest (mid-epoch) checkpoint — for both the single-stream ``grab``
ordering and distributed ``cd-grab``.

This locks the mid-epoch resume bugfix: the seed loop replayed a restored
epoch from step 0 against a checkpointed GraB state with ``t > 0`` and a
partially accumulated running sum ``s`` (double-counting the replayed
balance steps, and re-walking the epoch on mid-epoch params). The fixed loop
resumes *exactly*: the checkpointed TrainState carries the GraB state and
the partial device-resident sign buffer for the interrupted epoch, so the
continuation consumes the very next microbatches against the very sums the
straight run would have used.
"""
import json
import os
import shutil
import tempfile

import numpy as np
import jax
import pytest

from repro.data.synthetic import synthetic_classification
from repro.models.paper_models import logreg_init, logreg_loss
from repro.optim import constant, sgdm
from repro.train import LoopConfig, run_training
from repro.train.checkpoint import CheckpointManager, list_checkpoints


class ClsDataset:
    def __init__(self, x, y):
        self.x, self.y = x, y

    def __len__(self):
        return len(self.x)

    def batch(self, idx):
        return {"x": self.x[idx], "y": self.y[idx]}


N, D, MICRO, N_MICRO, EPOCHS = 64, 16, 4, 8, 3
STEPS_PER_EPOCH = N // (MICRO * N_MICRO)                      # = 2


def _run(ordering, workers, ckpt_dir=None, ckpt_every=0):
    x, y = synthetic_classification(N, D, seed=0)
    params = logreg_init(jax.random.PRNGKey(0), D, 10)
    loss = lambda p, mb: (logreg_loss(p, mb), {})
    cfg = LoopConfig(epochs=EPOCHS, n_micro=N_MICRO, ordering=ordering,
                     workers=workers, ckpt_dir=ckpt_dir,
                     ckpt_every_steps=ckpt_every, keep_ckpts=0, log_every=0)
    return run_training(loss, params, sgdm(0.9), constant(0.05),
                        ClsDataset(x, y), MICRO, cfg)


def _final_order(ckpt_dir):
    _, path = list_checkpoints(ckpt_dir)[-1]
    with open(os.path.join(path, "manifest.json")) as f:
        return json.load(f)["extra"]["order"]


@pytest.mark.parametrize("ordering,workers", [("grab", 1), ("cd-grab", 2)])
def test_kill_resume_is_bit_identical(ordering, workers):
    kill_step = STEPS_PER_EPOCH + 1          # mid-epoch: step 1 of epoch 1
    with tempfile.TemporaryDirectory() as da, \
            tempfile.TemporaryDirectory() as db:
        state_a, hist_a = _run(ordering, workers, ckpt_dir=da, ckpt_every=1)
        # "kill": run the same training, then drop every checkpoint newer
        # than the mid-epoch one, so restore lands mid-epoch-1
        _run(ordering, workers, ckpt_dir=db, ckpt_every=1)
        for s, path in list_checkpoints(db):
            if s > kill_step:
                shutil.rmtree(path)
        # the mid-epoch checkpoint holds the GraB state's sum of signs
        # ``e``: the epoch's signs so far in full-pytree mode, 0 in cd-grab
        kill = CheckpointManager(db).restore(state_a)[0]
        done = np.asarray(kill.signs, np.int64)[:int(kill.grab.t)]
        assert float(kill.grab.e) == (done.sum() if workers == 1 else 0)
        state_b, hist_b = _run(ordering, workers, ckpt_dir=db, ckpt_every=1)

        # resumed from the exact step: only the remaining steps re-ran
        assert {h["epoch"] for h in hist_b} == {1, 2}
        assert len(hist_b) == EPOCHS * STEPS_PER_EPOCH - kill_step

        # params, optimizer, GraB state, sign buffer: all bit-identical
        for a, b in zip(jax.tree.leaves(state_a), jax.tree.leaves(state_b)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        # sigma bit-identical (the balancer consumed each sign exactly once)
        ord_a, ord_b = _final_order(da), _final_order(db)
        key = "sigmas" if ordering == "cd-grab" else "sigma"
        np.testing.assert_array_equal(np.asarray(ord_a[key]),
                                      np.asarray(ord_b[key]))

        # and the replayed losses match the straight run's, step for step
        by_step_a = {h["step"]: h["loss"] for h in hist_a}
        for h in hist_b:
            assert h["loss"] == by_step_a[h["step"]], h


def test_boundary_resume_still_epoch_exact():
    """Resume from an epoch-boundary checkpoint (the pre-existing behavior)
    keeps working and never re-runs finished epochs."""
    with tempfile.TemporaryDirectory() as d:
        state_a, _ = _run("grab", 1, ckpt_dir=d)          # boundary saves only
        for s, path in list_checkpoints(d)[1:]:
            shutil.rmtree(path)                           # keep epoch-1 only
        state_b, hist_b = _run("grab", 1, ckpt_dir=d)
        assert {h["epoch"] for h in hist_b} == {1, 2}
        for a, b in zip(jax.tree.leaves(state_a), jax.tree.leaves(state_b)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
