"""Shared training utilities: metrics, losses, LR schedules.

Reference parity: examples/utils.py (Metric, LabelSmoothLoss, accuracy,
create_lr_schedule). Collective averaging of metrics happens inside the
jitted steps (pmean), so the host-side Metric is a plain weighted mean.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from distributed_kfac_pytorch_tpu.observability import tracing


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


@jax.jit
def _add_step(sums, metrics):
    return jax.tree.map(lambda s, v: s + v.astype(s.dtype),
                        sums, metrics)


def _zero_sum(value):
    """A host zero of ``value``'s shape in the dtype its sum is kept in
    (float32, or wider where the value is), placed as ``value`` is so
    that the sums a step hands back look to ``jit`` like these."""
    zero = np.zeros(np.shape(value),
                    jnp.promote_types(jnp.result_type(value), np.float32))
    sharding = getattr(value, 'sharding', None)
    return zero if sharding is None else jax.device_put(zero, sharding)


class RunningMeans:
    """Running means of the metrics dicts a train step returns.

    ``train_epoch``'s form of :class:`Metric`: one device execution a
    step for the whole dict, whatever its length, and no value read
    before :meth:`averages`. Step variants return different key sets
    (K-FAC telemetry rides only on steps that capture); each key set
    has its own sums and step count, so a key is averaged over the
    steps that carried it and ``_add_step`` is built once a key set,
    at its first step.
    """

    def __init__(self):
        # a step's keys, in its order -> [sums on the device, steps]
        self._by_keys: dict[tuple, list] = {}

    def update(self, metrics: dict) -> None:
        if not metrics:
            return
        keys = tuple(metrics)
        entry = self._by_keys.get(keys)
        if entry is None:
            entry = self._by_keys[keys] = [
                {k: _zero_sum(v) for k, v in metrics.items()}, 0]
        entry[0] = _add_step(entry[0], metrics)
        tracing.count('kfac/host/meter_dispatches')
        entry[1] += 1

    def averages(self) -> dict[str, float]:
        """Blocks on the last step; keys in the order first seen."""
        fetched = jax.device_get([e[0] for e in self._by_keys.values()])
        totals: dict[str, float] = {}
        steps: dict[str, int] = {}
        for sums, (keys, (_, n)) in zip(fetched, self._by_keys.items()):
            for k in keys:
                totals[k] = totals.get(k, 0.0) + float(sums[k])
                steps[k] = steps.get(k, 0) + n
        return {k: totals[k] / steps[k] for k in totals}


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
