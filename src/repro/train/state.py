"""TrainState: params + optimizer + GraB state, one pytree, one sharding rule.

``signs`` is the device-resident ordering side-channel: an int8 ``[T, W]``
buffer (T = per-worker timesteps per epoch, W = logical workers; W = 1 for
single-stream GraB) that ``build_train_step`` appends each step's balance
signs to via ``dynamic_update_slice`` at the GraB clock ``grab.t``. The loop
fetches it **once per epoch** right before the Algorithm-3 reorder instead of
pulling signs back every step — the device→host sync that used to serialize
dispatch. It lives inside the state (not the metrics) so it is donated across
steps (in-place update), checkpointed with everything else (a mid-epoch
snapshot carries its partial signs), and resharded on restore like any other
leaf. ``None`` for orderings that emit no signs (RR/SO/FlipFlop) and for
unit steps that never run an epoch.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax


class TrainState(NamedTuple):
    params: Any
    opt: Any                   # repro.optim.OptState
    grab: Optional[Any]        # repro.core.grab.GrabState | None (RR et al.)
    step: jax.Array
    signs: Optional[jax.Array] = None   # int8 [T, W] per-epoch sign buffer
