"""K-FAC observability: on-device telemetry, profiler scopes, sinks,
and health monitoring (ISSUE r7).

Four parts, one discipline — *observing a run must not change it*:

  - :mod:`metrics` — an on-device metrics pytree accumulated inside
    the jitted step (``KFAC(collect_metrics=True)``) and drained
    asynchronously; metrics-off is bit-identical to the
    pre-observability step (test-pinned).
  - :mod:`profiling` — ``annotate(name)`` scopes threaded through every
    hot path so an XLA profile attributes step time to named K-FAC
    stages; ``start_trace``/``stop_trace`` back the CLIs'
    ``--profile-dir``.
  - :mod:`sink` — schema-versioned JSONL writer (rank-0 gated, atomic
    write-then-rename, rotation, ``metrics_interval``).
  - :mod:`health` — non-finite / staleness / damping-trajectory
    monitors with warn / skip / raise actions (the on-device non-finite
    factor guard lives in the preconditioner).
  - :mod:`tracing` — the program's one recorder of host spans and
    counters (``span`` / ``count`` / ``gauge``; the epoch loop, the
    sink and the step builder write it), with the reference's
    ``trace()`` table as a thin form over it (still re-exported from
    ``distributed_kfac_pytorch_tpu.utils``).
  - :mod:`report` — ``python -m ...observability.report run.jsonl``
    offline step-time + health summary (``--json`` for machines).
  - :mod:`memory` — device HBM watermarks + resident K-FAC state
    footprint breakdown (the ``kind='memory'`` records, r10).
  - :mod:`stragglers` — per-rank sink shards, the pre-collective
    barrier-wait probe, and the cross-host skew merger (r10).
  - :mod:`gate` — ``python -m ...observability.gate run.jsonl
    --baseline BASELINE_OBS.json`` CI regression gate over step-time
    percentiles / peak HBM / retraces, plus online anomaly checks
    (r10).

Only the leaf modules (tracing, profiling) import eagerly — the rest
load on first attribute access so ``ops``/``layers`` can take profiler
scopes without import cycles.
"""

from __future__ import annotations

import importlib

from distributed_kfac_pytorch_tpu.observability import profiling, tracing

_LAZY = ('metrics', 'sink', 'health', 'report', 'cli', 'memory',
         'stragglers', 'gate')

__all__ = ['tracing', 'profiling', *_LAZY]


def __getattr__(name):
    if name in _LAZY:
        mod = importlib.import_module(
            f'distributed_kfac_pytorch_tpu.observability.{name}')
        globals()[name] = mod
        return mod
    raise AttributeError(name)
