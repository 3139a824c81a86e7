"""Shared training utilities: metrics, losses, LR schedules.

Reference parity: examples/utils.py (Metric, LabelSmoothLoss, accuracy,
create_lr_schedule). Collective averaging of metrics happens inside the
jitted steps (pmean), so the host-side Metric is a plain weighted mean.
"""

from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp
import optax


class Metric:
    """Weighted running average of a scalar (loss, accuracy).

    The reference allreduce-averages each update (examples/utils.py:35-48);
    here values arriving from a jitted step are already globally averaged,
    so this just accumulates over batches.
    """

    def __init__(self, name: str):
        self.name = name
        self._sum = 0.0
        self._n = 0.0

    def update(self, value, n: float = 1.0):
        # No float() here: converting a just-computed device scalar
        # blocks the host on the step every update. Accumulating the device
        # array keeps the sync lazy until ``avg`` is read (epoch end).
        self._sum = self._sum + value * n
        self._n += n

    @property
    def avg(self) -> float:
        return float(self._sum) / max(self._n, 1e-12)


def accuracy(logits, labels) -> jnp.ndarray:
    """Top-1 accuracy of logits vs integer labels.

    Reference parity: examples/utils.py:6-8.
    """
    return (jnp.argmax(logits, axis=-1) == labels).mean()


def label_smooth_loss(logits, labels, smoothing: float = 0.0):
    """Cross entropy with label smoothing.

    Reference parity: examples/utils.py:21-33 (LabelSmoothLoss); with
    ``smoothing=0`` this is plain softmax cross entropy.
    """
    if smoothing <= 0.0:
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()
    n = logits.shape[-1]
    one_hot = jnp.eye(n, dtype=logits.dtype)[labels]
    smoothed = one_hot * (1.0 - smoothing) + smoothing / n
    return optax.softmax_cross_entropy(logits, smoothed).mean()


def create_lr_schedule(workers: int, warmup_epochs: float,
                       decay_schedule: Sequence[int],
                       alpha: float = 0.1):
    """LR *factor* schedule over epochs: linear warmup then step decay.

    Reference parity: examples/utils.py:50-61 — warms from 1/workers up to
    ``workers``-scaled over ``warmup_epochs``, then multiplies by ``alpha``
    at each epoch in ``decay_schedule``. Returns ``f(epoch) -> factor`` to
    multiply with the base (per-worker) learning rate.
    """
    decay_schedule = sorted(decay_schedule)

    def schedule(epoch: float) -> float:
        if warmup_epochs > 0 and epoch < warmup_epochs:
            # epoch 0 -> 1.0 (base lr), epoch warmup -> workers (scaled).
            return 1.0 + (workers - 1.0) * (epoch / warmup_epochs)
        factor = float(workers)
        for e in decay_schedule:
            if epoch >= e:
                factor *= alpha
        return factor

    return schedule
