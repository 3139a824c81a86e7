"""Shared CLI wiring for the observability flags.

All three example entry points (CIFAR / ImageNet / LM) expose the same
observability surface; this module is its single implementation:

    add_observability_args(parser)       # --kfac-metrics / --metrics-
                                         # interval / --health-action /
                                         # --profile-dir / --memory-
                                         # interval / --straggler-shards
    sink = make_metrics_sink(args, info, meta={...})
    rank_sink = make_rank_shard_sink(args, info)     # r10 stragglers
    profile_epoch(args, info, epoch, start_epoch)   # context manager
"""

from __future__ import annotations

import contextlib
import os

from distributed_kfac_pytorch_tpu.observability import health as obs_health
from distributed_kfac_pytorch_tpu.observability import profiling
from distributed_kfac_pytorch_tpu.observability import sink as obs_sink


def add_observability_args(p) -> None:
    """Observability flags (r7; see README "Observability")."""
    p.add_argument('--kfac-metrics', nargs='?', const='auto',
                   default=None, metavar='PATH',
                   help='collect on-device K-FAC step metrics (damping, '
                        'KL-clip nu, grad/precond norms, firing counts, '
                        'non-finite events) into a schema-versioned '
                        'JSONL — default PATH <log-dir>/'
                        'kfac_metrics.jsonl, rank-0 only, no host '
                        'syncs added to the step. Summarize with: '
                        'python -m distributed_kfac_pytorch_tpu'
                        '.observability.report PATH')
    p.add_argument('--metrics-interval', type=int, default=10,
                   help='keep every Nth step record in the metrics '
                        'JSONL (epoch records always kept)')
    p.add_argument('--health-action', default=None,
                   choices=['warn', 'skip', 'raise'],
                   help='K-FAC health monitoring over the drained '
                        'metrics (non-finite events, factor staleness, '
                        'damping jumps). skip/raise also arm the '
                        'on-device non-finite factor-update guard — '
                        'which protects the FACTOR STATISTICS only; '
                        'for a whole-step skip of params/optimizer on '
                        'non-finite grads use --fp16 (dynamic loss '
                        'scaling, GradScaler parity). Requires '
                        '--kfac-metrics')
    p.add_argument('--profile-dir', default=None,
                   help='capture a jax.profiler trace of the first '
                        'trained epoch into this dir (kfac/* named '
                        'stage scopes attribute step time; rank 0 only)')
    p.add_argument('--memory-interval', type=int, default=100,
                   help='emit a memory-telemetry record (device HBM '
                        'watermarks + resident K-FAC state footprint '
                        'by group/dtype) every N steps into the '
                        'metrics JSONL; 0 disables. Host-side reads '
                        'only — the step program is untouched. '
                        'Requires --kfac-metrics')
    p.add_argument('--no-perf-anomalies', action='store_true',
                   help='disable the LIVE perf-anomaly monitors '
                        '(plain-step spike z-score, monotonic memory '
                        'growth) that --health-action otherwise arms '
                        'alongside the numerics checks. Use with '
                        '--health-action raise when a run must die on '
                        'NaNs but survive host jitter; the offline '
                        'gate still replays both checks from the '
                        'recorded stream')
    p.add_argument('--straggler-shards', action='store_true',
                   help='every host writes its own sink shard '
                        '(PATH.rank<r>) with per-step dispatch wall '
                        'time and pre-collective barrier-wait, for '
                        'mesh-wide straggler attribution '
                        '(observability.report merges the shards). '
                        'The barrier probe blocks the host on device '
                        'completion each step — costs async-dispatch '
                        'pipelining, so only enable when hunting '
                        'skew. Requires --kfac-metrics')
    p.add_argument('--straggler-sample-every', type=int, default=1,
                   metavar='N',
                   help='run the barrier-wait probe only every Nth '
                        'step (r14): amortizes the probe\'s host-sync '
                        'cost to 1/N so straggler attribution can '
                        'stay on in long runs. Every rank samples the '
                        'same steps (a pure function of the global '
                        'step), so the merged skew analysis still '
                        'lines up; non-sampled steps carry no wait '
                        'field. 1 = the r10 every-step probe. '
                        'Requires --straggler-shards')


def wants_guard(args) -> bool:
    """True when the on-device non-finite factor guard should be armed
    ('warn' observes only; 'skip'/'raise' protect the state)."""
    return getattr(args, 'health_action', None) in ('skip', 'raise')


def make_metrics_sink(args, info, meta: dict | None = None):
    """JSONL sink (+ optional health monitor) for a CLI, or None.

    Rank gating happens inside the sink (non-zero ranks get a no-op
    sink), so callers need no is_main branches. The monitor's
    factor-staleness threshold derives from the CLI's cov-update
    cadence (10x the expected interval — a schedule bug signature, not
    normal jitter); without that wiring the check would be dead from
    the CLIs (its constructor default is off).
    """
    if args.health_action and not args.kfac_metrics:
        raise SystemExit('--health-action requires --kfac-metrics '
                         '(the monitor consumes the drained metrics)')
    if getattr(args, 'straggler_shards', False) and not args.kfac_metrics:
        raise SystemExit('--straggler-shards requires --kfac-metrics '
                         '(shards live next to the metrics path)')
    if getattr(args, 'straggler_sample_every', 1) < 1:
        raise SystemExit('--straggler-sample-every must be >= 1')
    if (getattr(args, 'straggler_sample_every', 1) > 1
            and not getattr(args, 'straggler_shards', False)):
        raise SystemExit('--straggler-sample-every requires '
                         '--straggler-shards (it paces the barrier '
                         'probe those shards record)')
    if not args.kfac_metrics:
        return None
    path = metrics_path(args)
    monitor = None
    if args.health_action:
        cov_freq = max(1, int(getattr(args, 'kfac_cov_update_freq', 1)))
        # r10 online anomaly monitors: a plain step landing 8 sigmas
        # off the running mean, or the device watermark climbing
        # monotonically — the same signatures the gate checks offline,
        # surfaced live through the warn/skip/raise action. Opt out
        # with --no-perf-anomalies (e.g. raise-on-NaN CI on a noisy
        # shared host, where jitter must not abort the run).
        perf = not getattr(args, 'no_perf_anomalies', False)
        monitor = obs_health.HealthMonitor(
            action=args.health_action,
            stale_after_steps=10 * cov_freq,
            step_spike_zscore=8.0 if perf else None,
            memory_growth_windows=6 if perf else 0)
    return obs_sink.JsonlMetricsSink(
        path, interval=args.metrics_interval,
        process_index=info['process_index'], monitor=monitor,
        meta=meta)


def emit_layer_meta(sink, kfac) -> None:
    """Append the per-layer K-FAC registry provenance to the metrics
    stream (r13): the resolved weight-sharing approximation per layer
    (``KFAC.approx_summary`` — 'expand' / 'reduce' / '<approx>+tied')
    plus the global setting. Called by the CLIs AFTER registration
    (the sink is built before the model exists, so this rides as a
    second ``kind='meta'`` record). No-ops on None sinks, non-K-FAC
    runs, and duck-typed sinks without ``meta_record``.
    """
    if sink is None or kfac is None:
        return
    emit = getattr(sink, 'meta_record', None)
    if emit is None:
        return
    emit({'kfac_approx': kfac.approx_summary(),
          'kfac_approx_setting': (kfac.kfac_approx
                                  if isinstance(kfac.kfac_approx, str)
                                  else dict(kfac.kfac_approx)),
          'tied_embeddings': bool(kfac.tied_embeddings),
          # {layer: the earlier layer whose input, A statistic and A
          # inverse it shares} (KFAC.a_followers).
          'shared_a': kfac.a_followers()})


def metrics_path(args) -> str:
    """The resolved --kfac-metrics path (single point of truth for the
    main stream, the rank shards, and any post-run report/gate call)."""
    return (os.path.join(args.log_dir, 'kfac_metrics.jsonl')
            if args.kfac_metrics == 'auto' else args.kfac_metrics)


def make_rank_shard_sink(args, info, meta: dict | None = None):
    """Per-rank straggler shard sink for a CLI (or None when off).

    Every process gets a WRITING sink at ``<metrics-path>.rank<r>``
    (the inverse of the main stream's rank-0 gate). The shard's meta
    carries ``launch.host_metadata()`` so the merged report can name
    the slow machine, not just its rank.
    """
    if not getattr(args, 'straggler_shards', False):
        return None
    from distributed_kfac_pytorch_tpu import launch
    from distributed_kfac_pytorch_tpu.observability import stragglers

    shard_meta = {**launch.host_metadata(), **(meta or {})}
    return stragglers.make_rank_shard_sink(
        metrics_path(args), info['process_index'], meta=shard_meta)


@contextlib.contextmanager
def profile_epoch(args, info, epoch: int, start_epoch: int):
    """Profile exactly the first trained epoch when --profile-dir is set.

    Compile time of the step variants lands inside this window too —
    that is deliberate (the profile then shows compile vs steady-state);
    steady-state-only captures can re-run with checkpoints resumed.
    """
    active = (args.profile_dir is not None and epoch == start_epoch
              and profiling.start_trace(
                  args.profile_dir,
                  process_index=info['process_index']))
    try:
        yield
    finally:
        if active:
            profiling.stop_trace()
