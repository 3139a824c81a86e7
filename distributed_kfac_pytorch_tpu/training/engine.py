"""Train/eval epoch loops over the distributed K-FAC step.

Reference parity: examples/cnn_utils/engine.py (train/test loops with
allreduce-averaged metrics, progress display, TensorBoard scalars). The
per-step work (forward/backward, K-FAC, SGD, metric averaging) is entirely
inside the jitted step from ``DistributedKFAC.build_train_step``; the host
loop only feeds batches and accumulates metrics.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp

from distributed_kfac_pytorch_tpu.analysis import sanitize as _sanitize
from distributed_kfac_pytorch_tpu.observability import (
    memory as obs_memory,
    stragglers as obs_stragglers,
    tracing,
)
from distributed_kfac_pytorch_tpu.parallel.distributed import (
    KFAC_AXES,
    replicated_specs as _replicated_specs,
)
from distributed_kfac_pytorch_tpu.training.utils import (
    Metric,
    RunningMeans,
    accuracy,
)


def cadence_flags(step: int, factor_update_freq, inv_update_freq,
                  inv_pipeline_chunks: int = 1, *,
                  deferred_reduce: bool = False,
                  inv_staleness: int = 0) -> dict:
    """Static cadence flags for one host step (single point of truth).

    The classic schedule fires the whole inverse update at
    ``step % inv_update_freq == 0``. With ``inv_pipeline_chunks=k > 1``
    the firing is pipelined: chunk ``j`` fires on phase step
    ``j * inv_update_freq / k`` of each window (``inv_chunk=j`` in the
    returned flags), smearing the decomposition spike across the
    window — except at step 0, which fires monolithically
    (``inv_update=True``): every inverse slot is zero-seeded and must
    exist before its first preconditioning use, so the pipeline takes
    over from the first window's later phases onward. Each distinct
    flag combination is its own statically-compiled program variant
    (PERF.md pitfalls 2-3).

    r14 overlap knobs (read off the step builder's attributes by
    ``train_epoch``): ``deferred_reduce`` adds ``factor_reduce=True``
    on window-head steps — the one bucketed factor collective per
    window. ``inv_staleness=1`` re-times the firing schedule: window
    heads (past step 0) take a factor SNAPSHOT instead of firing, and
    chunk ``j`` fires at phase ``j * stride + 1`` from that snapshot —
    one step after the head, so the decomposition never shares a step
    with the window's factor reduction and carries no data dependency
    on its own step's factor work (with ``k == 1`` the whole firing
    runs as chunk 0 at phase 1). Step 0 stays a monolithic warmup
    either way.
    """
    f_freq, i_freq = int(factor_update_freq), int(inv_update_freq)
    k = int(inv_pipeline_chunks)
    phase = step % i_freq
    flags = {'factor_update': step % f_freq == 0}
    if int(inv_staleness) == 1 and i_freq % k == 0 and i_freq // k >= 2:
        stride = i_freq // k
        flags['inv_update'] = step == 0
        if step != 0:
            if phase == 0:
                flags['factor_snapshot'] = True
            elif (phase - 1) % stride == 0 and (phase - 1) // stride < k:
                flags['inv_chunk'] = (phase - 1) // stride
    elif k > 1 and i_freq % k == 0:
        stride = i_freq // k
        flags['inv_update'] = step == 0
        if step != 0 and phase % stride == 0:
            flags['inv_chunk'] = phase // stride
    else:
        flags['inv_update'] = step % i_freq == 0
    if deferred_reduce:
        flags['factor_reduce'] = phase == 0
    return flags


def _drain_selfheal(selfheal, metrics_sink) -> None:
    """Move the ladder's queued decision events into the metrics sink
    (duck-typed sinks without ``event_record`` keep their queue, like
    the compile-event drain)."""
    if not selfheal.pending_events or metrics_sink is None:
        return
    emit = getattr(metrics_sink, 'event_record', None)
    if emit is None:
        return
    for ev in selfheal.drain_events():
        emit(ev['event'], **{k: v for k, v in ev.items()
                             if k != 'event'})


def fired_stage(flags: dict) -> str | None:
    """Most expensive stage a step's static flags fire (for step-time
    attribution in the metrics stream): 'inverse' > 'chunk<j>' >
    'reduce' (the deferred window-boundary factor collective, r14) >
    'factor' > None. A firing step that ALSO pays the deferred reduce
    (the non-staleness combos put both on the window head) gets a
    compound label ('inverse+reduce' / 'chunk<j>+reduce') so the
    straggler merger's comm-wait split can still see the factor
    collective — classing those steps as collective-free 'firing'
    would hide the one real factor reduction per window from exactly
    the attribution the r14 decision rule reads. The report's outlier
    attribution and the merger's split consume this."""
    reduce_tag = '+reduce' if flags.get('factor_reduce') else ''
    if flags.get('inv_update'):
        return 'inverse' + reduce_tag
    if flags.get('inv_chunk') is not None:
        return f"chunk{flags['inv_chunk']}" + reduce_tag
    if flags.get('factor_reduce'):
        return 'reduce'
    if flags.get('factor_update'):
        return 'factor'
    return None


@dataclasses.dataclass
class TrainState:
    """Everything a training step threads through (one pytree-of-pytrees).

    The analogue of the reference's (model, optimizer, preconditioner,
    schedulers) object group (torch_cifar10_resnet.py:153-176).
    """
    params: Any
    opt_state: Any
    kfac_state: Any
    extra_vars: dict
    step: int = 0
    epoch: int = 0


def train_epoch(step_fn, state: TrainState, batches: Iterable,
                hyper: dict, *, log_writer=None, verbose: bool = False,
                epoch_len: int | None = None,
                static_cadence: tuple[int, int] | str | None = 'auto',
                metrics_sink=None, checkpointer=None,
                start_step_in_epoch: int = 0,
                rank_sink=None, barrier_probe=None,
                straggler_sample_every: int = 1,
                memory_interval: int = 0,
                cadence_policy=None, selfheal=None,
                heartbeat=None) -> dict[str, float]:
    """One training epoch; returns averaged metrics.

    ``hyper`` holds this epoch's dynamic hyperparameters ('lr', 'damping',
    optionally cadence overrides) — the reference adjusts these per epoch
    via LambdaLR/KFACParamScheduler (engine.py:84-93).

    ``static_cadence=(factor_update_freq, inv_update_freq)`` drives the
    K-FAC cadence from the host step counter (``state.step``) instead of
    on-device ``lax.cond``s: the step runs as one of a few
    statically-compiled program variants, which on TPU avoids the
    measured 10-18x cond-around-decompositions slowdown (see
    ``KFAC.step``). The freqs may change between epochs (the
    KFACParamScheduler path) — each distinct flag combination reuses its
    compiled variant. Requires a ``step_fn`` from
    ``DistributedKFAC.build_train_step``; pass None for on-device conds.
    The default ``'auto'`` uses the freqs in ``hyper`` when ``step_fn``
    accepts the flags (i.e. is a K-FAC step) and falls back to dynamic
    otherwise (e.g. the SGD baseline step).

    ``metrics_sink``: an ``observability.sink.JsonlMetricsSink`` (or
    None). Per-step metrics (including the on-device K-FAC telemetry
    when ``collect_metrics`` is on) are *enqueued* each step — device
    scalars, no sync — plus the host dispatch time; an epoch record with
    the averaged metrics and the recorder's span aggregates and counters
    (``observability.tracing``) is appended and the sink flushed at
    epoch end. The sink also flushes every ``drain_every`` records, and
    that drain waits for the step dispatched a moment before it: the
    ``kfac/host/sink_flush`` span's ``blocked_ms`` sizes the wait.

    ``checkpointer``: a ``resilience.policy.StepCheckpointer`` (or
    None). Its ``after_step(state, step_in_epoch)`` is called once per
    completed step — the single poll point for step-interval /
    wall-clock checkpoints, preemption drains, and fault injection. It
    may raise ``resilience.preemption.Preempted`` AFTER a blocking
    save; the exception propagates to the CLI, which exits with the
    relaunch code. ``start_step_in_epoch`` is the mid-epoch resume
    offset (how many batches of this epoch were already trained before
    ``batches``, which the caller built with a matching
    ``skip_batches=``) so checkpoint bundles record the true position.
    A resumed run whose offset already covers the whole epoch (the
    preemption landed on the final step) yields zero batches — that is
    treated as a completed epoch, not an error.

    ``rank_sink``: THIS process's straggler shard sink
    (``observability.stragglers.make_rank_shard_sink`` — every rank
    writes its own ``<path>.rank<r>``, unlike the rank-0-gated
    ``metrics_sink``). Each step's host dispatch time (and, with
    ``barrier_probe``, the pre-collective barrier wait) is recorded so
    ``observability.report`` can attribute mesh-wide skew to hosts.

    ``barrier_probe``: ``DistributedKFAC.build_barrier_probe()`` (or
    None). Called once per step BEFORE the step dispatch; the returned
    wait-ms lands in the rank shard. NOTE: the probe blocks the host
    on device completion each step (that is what it measures), so it
    costs async-dispatch pipelining — only wired when straggler
    attribution is requested.

    ``straggler_sample_every``: probe only on steps where
    ``step % N == 0`` (r14) — the probe's host-sync cost then
    amortizes to 1/N of the run, cheap enough to leave on in long
    runs. Every rank samples the SAME steps (the schedule is a pure
    function of the global step), so the merger's common-step skew
    analysis still lines up; non-sampled steps simply carry no wait
    field (report/merge handle the sparse shards). 1 (default) = the
    r10 every-step probe.

    ``memory_interval``: every Nth step, emit a ``kind='memory'``
    record into ``metrics_sink`` — device allocator watermarks plus the
    resident K-FAC state footprint (``observability.memory``). Pure
    host-side reads (0 = off). The footprint is computed once per
    epoch: the state's shapes/dtypes are static across steps.

    ``cadence_policy``: an ``autotune.StragglerCadencePolicy`` (or
    None, the default — that path is byte-for-byte the pre-policy
    engine). Per step, the policy sees the static cadence flags plus
    the barrier-probe wait and may suppress a scheduled factor update
    (straggler-aware cadence backoff, r12). The first suppression per
    flag combination may compile a new program variant once (a normal
    lazy-cache compile, recorded and labeled like any other — see
    ``autotune.policy``); the zero-RETRACE contract still holds with
    the policy active. Its decision events drain into
    ``metrics_sink`` like the compile telemetry. Requires
    ``barrier_probe`` to act on skew (without one the policy is
    inert).

    ``selfheal``: a ``resilience.selfheal.SelfHealController`` (or
    None, the default — that path is byte-for-byte the pre-r16
    engine). Per step the controller adjusts the traced
    hyperparameters (escalated damping, per-bucket quarantine gates —
    VALUE changes only, zero retraces) and observes the step's
    metrics; at window boundaries (its ``check_every``) it reads a
    handful of device scalars — the armed ladder's one deliberate
    host sync, amortized like the sampled straggler probe — and may
    reset quarantined layers' factor EWMAs in ``state.kfac_state`` or
    raise ``resilience.selfheal.Rollback`` (sinks are flushed first;
    the CLI catches it and restores in-process — README
    "Self-healing"). Ladder decision events drain into
    ``metrics_sink`` like the compile/backoff telemetry.

    ``heartbeat``: a ``resilience.heartbeat.HeartbeatEmitter`` (or
    None, the default — that path is byte-for-byte the pre-r17
    engine). Once per completed step the emitter publishes this
    rank's liveness lease (atomic write-then-rename; stride inside
    the emitter) BEFORE the checkpointer hook runs, so a step that
    wedges in that hook still left a fresh lease at its step — the
    exact stale-lease signature the failure supervisor's
    ``--hang-timeout`` detects (``resilience.supervisor``). Pure
    host-side file I/O: no device interaction, no program change —
    heartbeats off is bit-identical and on adds zero retraces
    (pinned by tests/test_supervisor.py).

    ``KFAC_SANITIZE=transfer,nan,retrace`` (env var, r15): run the
    epoch under the runtime sanitizer gates — device->host transfer
    guard around warm step dispatches, ``jax.debug_nans`` on every
    dispatch, and an after-step retrace check against the builder's
    ``trace_counts``. See :mod:`analysis.sanitize`; unset (default)
    is the unsanitized path.
    """
    if static_cadence == 'auto':
        import inspect
        try:
            accepts = 'factor_update' in inspect.signature(
                step_fn).parameters
        except (TypeError, ValueError):
            accepts = False
        if accepts and 'factor_update_freq' in hyper and \
                'inv_update_freq' in hyper:
            static_cadence = (hyper['factor_update_freq'],
                              hyper['inv_update_freq'])
        else:
            static_cadence = None
            if accepts:
                import warnings
                warnings.warn(
                    'train_epoch: step_fn accepts static cadence flags '
                    "but hyper lacks 'factor_update_freq'/"
                    "'inv_update_freq' — falling back to on-device "
                    'cadence conds, which are 10-18x slower on TPU '
                    '(PERF.md). Add the freqs to hyper (e.g. via '
                    'KFACParamScheduler.params()) to enable the static '
                    'fast path.')
    if (static_cadence is not None and isinstance(state.kfac_state, dict)
            and 'step' in state.kfac_state):
        # Static cadence is only correct while the host counter driving
        # the factor/inverse flags stays in phase with the on-device
        # K-FAC counter (a caller that rebuilds TrainState without
        # restoring ``step`` would silently shift the schedule). Checked
        # BEFORE the epoch so a desynced state cannot train a whole
        # epoch on the wrong schedule; one device sync per epoch.
        # kfaclint: waive[host-sync] documented blocking point: ONE device sync per epoch, before any step is dispatched
        kstep = int(jax.device_get(state.kfac_state['step']))
        if kstep != state.step:
            raise RuntimeError(
                f'static-cadence phase error: host step counter '
                f'{state.step} != on-device K-FAC step {kstep}. '
                'TrainState.step must be restored alongside kfac_state '
                '(checkpoint resume restores both; see '
                "MIGRATION.md 'Checkpoint format').")
    # Pipelined inverse firing: the step builder advertises its chunk
    # count (DistributedKFAC.build_train_step); a schedule the chunks
    # cannot divide evenly (e.g. a KFACParamScheduler freq decay)
    # falls back to monolithic firing for the epoch rather than
    # mis-phasing the pipeline.
    built_chunks = int(getattr(step_fn, 'inv_pipeline_chunks', 1) or 1)
    chunks = built_chunks
    if (chunks > 1 and static_cadence is not None
            and int(static_cadence[1]) % chunks != 0):
        import warnings
        warnings.warn(
            f'inv_pipeline_chunks={chunks} does not divide this '
            f'epoch\'s inv_update_freq={static_cadence[1]} — firing '
            'monolithically for the epoch')
        chunks = 1
    # r14 overlap knobs, advertised by the step builder like the chunk
    # count. A schedule the shifted staleness phases cannot fit
    # (stride < 2, or a non-dividing chunk count, after a
    # KFACParamScheduler freq decay) falls back to eager MONOLITHIC
    # window-head firing for the epoch: the inv_update=True program
    # snapshots-then-fires (eager semantics), whereas any partial
    # chunk schedule against the BUILT chunk count would either
    # mis-phase the pipeline or leave the carried snapshot stale
    # forever. The check uses ``built_chunks`` — the chunk plan baked
    # into the compiled programs — not the fallen-back count.
    deferred_reduce = bool(getattr(step_fn, 'deferred_factor_reduction',
                                   False))
    inv_staleness = int(getattr(step_fn, 'inv_staleness', 0) or 0)
    if (deferred_reduce or inv_staleness) and static_cadence is None:
        # Fail BEFORE the epoch with the real reason: the step itself
        # would raise the same contract mid-epoch at trace time, right
        # after the 'falling back to on-device cadence conds' warning
        # promised a fallback that cannot exist for these knobs (a
        # dynamic cond cannot host the window-boundary reduce or the
        # frozen-snapshot firing schedule — both are static program
        # structure).
        raise RuntimeError(
            'deferred_factor_reduction/inv_staleness require the '
            'static-cadence fast path: pass static_cadence=(f, i) or '
            "include 'factor_update_freq'/'inv_update_freq' in hyper "
            '(the window-boundary reduce and the frozen-snapshot '
            'firing schedule are static program structure)')
    if (inv_staleness and static_cadence is not None
            and (int(static_cadence[1]) % built_chunks != 0
                 or int(static_cadence[1]) // built_chunks < 2)):
        import warnings
        warnings.warn(
            f'inv_staleness=1 with inv_pipeline_chunks='
            f'{built_chunks} does not fit this epoch\'s '
            f'inv_update_freq={static_cadence[1]} (needs freq/chunks '
            '>= 2) — firing eagerly/monolithically at window heads '
            'for the epoch')
        inv_staleness = 0
        chunks = 1
    # r15 runtime sanitizer gates (KFAC_SANITIZE=transfer,nan,retrace
    # — see analysis.sanitize). Env read once per epoch; unset = an
    # inert sanitizer whose step guard is a null context.
    sanitizer = _sanitize.Sanitizer.from_env()
    meters = RunningMeans()
    t0 = time.perf_counter()
    n_batches = 0
    state_footprint = None  # computed lazily, once per epoch
    batch_iter = iter(batches)
    while True:
        # One root span a step (observability.tracing): its children
        # say where the loop's own time goes between two step calls.
        with tracing.span('kfac/host/step', step=state.step) as step_span:
            with tracing.span('kfac/host/next_batch') as fetch:
                try:
                    batch = next(batch_iter)
                except StopIteration:
                    # The end of the data is no step.
                    fetch.cancel()
                    step_span.cancel()
                    break
            if static_cadence is not None:
                f_freq, i_freq = static_cadence
                flags = cadence_flags(state.step, f_freq, i_freq, chunks,
                                      deferred_reduce=deferred_reduce,
                                      inv_staleness=inv_staleness)
            else:
                flags = {}
            wait_ms = None
            if barrier_probe is not None and (
                    straggler_sample_every <= 1
                    or state.step % straggler_sample_every == 0):
                # Straggler attribution: how long does THIS host wait
                # for the rest of the mesh before its next collective
                # could proceed? Measured before the dispatch so the
                # wait is not conflated with this step's own compute.
                with tracing.span('kfac/host/hook/barrier_probe'):
                    wait_ms = barrier_probe()
            if cadence_policy is not None:
                # Straggler-aware cadence backoff (r12): may flip a
                # scheduled factor_update off while skew is sustained.
                # Applied BEFORE dispatch and before the fired-stage
                # label is derived, so attribution reflects what
                # actually ran.
                with tracing.span('kfac/host/hook/cadence_policy'):
                    flags = cadence_policy.adjust(state.step, flags,
                                                  wait_ms)
            # Self-healing ladder (r16): escalated damping / quarantine
            # gates are traced-scalar VALUE changes on this step's
            # hyper — the dict structure is fixed at arming time, so
            # the variant cache never retraces. selfheal=None leaves
            # hyper untouched.
            if selfheal is None:
                hyper_step = hyper
            else:
                with tracing.span('kfac/host/hook/selfheal'):
                    hyper_step = selfheal.adjust_hyper(hyper)
            with tracing.span('kfac/host/step_call') as call:
                with sanitizer.step_guard(step_fn, flags):
                    (state.params, state.opt_state, state.kfac_state,
                     state.extra_vars, metrics) = step_fn(
                        state.params, state.opt_state, state.kfac_state,
                        state.extra_vars, batch, hyper_step, **flags)
                sanitizer.after_step(step_fn, state.step)
            # A queued compile event right after the call means THIS
            # step's wall time is dominated by trace+XLA compile, not
            # training work. Label plain steps 'compile' so (a) the
            # report's step-time attribution names the real culprit and
            # (b) the health monitor's spike z-score excludes it — one
            # absorbed 20 s compile sample would otherwise inflate the
            # running stddev by orders of magnitude and blind the
            # detector for the whole run. Steps that also fired a K-FAC
            # stage keep that label (fired steps are excluded from
            # spike stats anyway).
            fired = fired_stage(flags)
            if (fired and 'reduce' in fired
                    and getattr(step_fn, 'hierarchical_reduce', False)):
                # r20: the window-boundary collective of a hierarchical
                # run crosses slices over DCN — relabel so the
                # straggler merger's wait_by_stage attributes DCN wait
                # as its own bucket (stragglers.stage_class routes
                # 'dcn_reduce' to 'dcn' before the generic 'reduce'
                # match).
                fired = fired.replace('reduce', 'dcn_reduce')
            pending = getattr(step_fn, 'compile_events', None)
            if pending and fired is None:
                fired = 'compile'
            step_span.set(fired=fired)
            with tracing.span('kfac/host/sink'):
                if metrics_sink is not None:
                    # Enqueue only (device scalars + async host copy):
                    # the sink converts to floats at drain time.
                    metrics_sink.step_record(state.step, metrics,
                                             host_step_ms=call.duration_ms,
                                             fired=fired)
                    if (memory_interval > 0
                            and state.step % memory_interval == 0):
                        if state_footprint is None:
                            state_footprint = obs_memory.state_footprint(
                                state.kfac_state)
                        metrics_sink.memory_record(
                            state.step,
                            device=obs_memory.device_memory_stats(),
                            state=state_footprint)
                if rank_sink is not None:
                    # Per-rank straggler shard: dispatch wall + barrier
                    # wait only (the full metric set already rides the
                    # rank-0 stream; shards exist to compare HOSTS, not
                    # to duplicate it).
                    shard_metrics = {}
                    if wait_ms is not None:
                        shard_metrics[
                            obs_stragglers.BARRIER_WAIT_KEY] = wait_ms
                    rank_sink.step_record(state.step, shard_metrics,
                                          host_step_ms=call.duration_ms,
                                          fired=fired)
                if metrics_sink is not None:
                    # Drain queued compile/retrace telemetry from the
                    # step builder's variant cache (r10): rare,
                    # host-side, and written as event records so the
                    # gate can regress the retrace count offline.
                    # Duck-typed sinks that predate event records
                    # (tests pass minimal step/epoch-only stand-ins)
                    # just leave the queue in place.
                    emit_event = getattr(metrics_sink, 'event_record',
                                         None)
                    if pending and emit_event is not None:
                        for ev in list(pending):
                            data = {k: v for k, v in ev.items()
                                    if k != 'event'}
                            emit_event(ev['event'], **data)
                        pending.clear()
                    # Autotune policy decisions (stretch/relax) ride
                    # the same event channel so the report/gate can see
                    # them offline.
                    if (cadence_policy is not None
                            and emit_event is not None
                            and cadence_policy.pending_events):
                        for ev in cadence_policy.drain_events():
                            data = {k: v for k, v in ev.items()
                                    if k != 'event'}
                            emit_event(ev['event'], **data)
            if selfheal is not None:
                # Ladder observation (r16): host arithmetic except at
                # its window boundaries. May reset quarantined factor
                # EWMAs in state.kfac_state; may raise Rollback — the
                # drain persists the ladder's own escalation events on
                # both paths, and the except additionally flushes the
                # sinks so the completed steps' records survive the
                # unwind, exactly like a preemption.
                with tracing.span('kfac/host/hook/selfheal'):
                    try:
                        selfheal.observe(state, metrics)
                    except BaseException:
                        _drain_selfheal(selfheal, metrics_sink)
                        if metrics_sink is not None:
                            metrics_sink.flush()
                        if rank_sink is not None:
                            rank_sink.flush()
                        raise
                    _drain_selfheal(selfheal, metrics_sink)
            state.step += 1
            n_batches += 1
            with tracing.span('kfac/host/meters'):
                meters.update(metrics)
            if heartbeat is not None:
                # Liveness lease (r17): published before the
                # checkpointer hook so a hang inside it (the chaos hang
                # fault, a wedged collective save) leaves a fresh lease
                # AT the hang step — the supervisor then sees the lease
                # stop advancing.
                with tracing.span('kfac/host/hook/heartbeat'):
                    heartbeat.beat(state.step)
            if checkpointer is not None:
                # May raise Preempted (after a blocking save). Flush
                # the sink first so the completed steps' records are
                # durable alongside the checkpoint the relaunch resumes
                # from.
                with tracing.span('kfac/host/hook/checkpointer'):
                    try:
                        checkpointer.after_step(
                            state, start_step_in_epoch + n_batches)
                    except BaseException:
                        if metrics_sink is not None:
                            metrics_sink.flush()
                        if rank_sink is not None:
                            rank_sink.flush()
                        raise
    elapsed = time.perf_counter() - t0
    if n_batches == 0:
        if start_step_in_epoch > 0:
            # Resumed exactly at the epoch boundary: nothing left to
            # replay; count the epoch as completed.
            state.epoch += 1
            return {'time_s': elapsed, 'ms_per_iter': 0.0}
        raise ValueError(
            'train_epoch: the batch iterator yielded ZERO batches — '
            'usually batch_size larger than the dataset (full batches '
            'are required for static shapes). Lower the batch size or '
            'enlarge the dataset.')
    out = meters.averages()
    out['time_s'] = elapsed
    out['ms_per_iter'] = elapsed / max(n_batches, 1) * 1000.0
    if metrics_sink is not None:
        metrics_sink.epoch_record(state.epoch, out,
                                  trace=tracing.snapshot_trace(),
                                  counters=tracing.counters())
        metrics_sink.flush()
    if rank_sink is not None:
        rank_sink.flush()
    if log_writer is not None:
        for k, v in out.items():
            log_writer.scalar(f'train/{k}', v, state.epoch)
    if verbose:
        shown = {k: round(v, 4) for k, v in out.items()}
        print(f'epoch {state.epoch}: train {shown}')
    state.epoch += 1
    return out


def build_sgd_train_step(model, loss_fn, tx, mesh=None, *,
                         model_args_fn=None, model_kwargs_fn=None,
                         metrics_fn=None,
                         mutable_cols=(), batch_spec=None,
                         grad_accum_steps: int = 1,
                         donate: bool = True):
    """Plain data-parallel first-order train step (no K-FAC).

    The ``--kfac-update-freq 0`` path: the reference's examples fall back
    to bare SGD when K-FAC is disabled (cnn_utils/optimizers.py:28), so
    the same CLI flag must produce a working first-order baseline here.
    Signature matches ``DistributedKFAC.build_train_step``'s output
    (the ``kfac_state`` slot is threaded through untouched) so
    ``train_epoch`` works with either; ``grad_accum_steps`` splits the
    per-device shard into micro-batches with carry-summed gradients,
    keeping batch semantics identical to the K-FAC step it is compared
    against.

    The batch is sharded over the K-FAC data axes (same default as
    ``DistributedKFAC.build_train_step``); extra mesh axes are still
    averaged over so the step stays correct on any ``make_kfac_mesh``.

    ``model_kwargs_fn`` mirrors the K-FAC builder's parameter: a
    ``batch -> kwargs`` callable evaluated inside the (sharded) step,
    so it may use ``jax.lax.axis_index`` — e.g. the LM CLI's per-device
    dropout key fold (its SGD baseline needs the same dropout semantics
    as the K-FAC step it is compared against).
    """
    import optax
    from jax.sharding import PartitionSpec as P

    from distributed_kfac_pytorch_tpu.parallel.distributed import (
        KFAC_AXES,
        SLICE_AXIS,
    )

    if model_args_fn is None:
        model_args_fn = lambda batch: (batch[0],)
    mutable_cols = tuple(mutable_cols)
    data_axes = tuple(mesh.axis_names) if mesh is not None else ()
    if batch_spec is None and mesh is not None:
        batch_spec = P(tuple(a for a in (SLICE_AXIS,) + KFAC_AXES
                             if a in mesh.axis_names) or data_axes)
    if grad_accum_steps < 1:
        raise ValueError(f'{grad_accum_steps=} must be >= 1')

    def fwd_bwd(params, extra_vars, batch):
        kwargs = model_kwargs_fn(batch) if model_kwargs_fn else {}

        def wrapped(params):
            out = model.apply({'params': params, **extra_vars},
                              *model_args_fn(batch), **kwargs,
                              mutable=list(mutable_cols) or False)
            out, updated = out if mutable_cols else (out, {})
            extra = metrics_fn(out, batch) if metrics_fn else {}
            return loss_fn(out, batch), (extra, dict(updated))

        with jax.named_scope('kfac_step/fwd_bwd'):
            (loss, (extra_metrics, updated)), grads = jax.value_and_grad(
                wrapped, has_aux=True)(params)
        return loss, extra_metrics, updated, grads

    def local_step(params, opt_state, kstate, extra_vars, batch, hyper):
        if grad_accum_steps == 1:
            loss, extra_metrics, updated, grads = fwd_bwd(
                params, extra_vars, batch)
        else:
            from jax.sharding import PartitionSpec as P

            from distributed_kfac_pytorch_tpu.parallel.distributed import (
                normalize_batch_specs)
            specs = normalize_batch_specs(batch_spec, batch)

            def split(x, spec):
                if spec == P():
                    # Replicated per-step leaf (e.g. a PRNG key):
                    # broadcast, not sliced (same as the K-FAC step).
                    return jnp.broadcast_to(
                        x[None], (grad_accum_steps,) + x.shape)
                if x.shape[0] % grad_accum_steps:
                    raise ValueError(
                        f'per-device batch shard of {x.shape[0]} is not '
                        f'divisible by {grad_accum_steps=}')
                return x.reshape((grad_accum_steps,
                                  x.shape[0] // grad_accum_steps)
                                 + x.shape[1:])

            micro = jax.tree.map(split, batch, specs)
            first = jax.tree.map(lambda x: x[0], micro)
            shapes = jax.eval_shape(fwd_bwd, params, extra_vars, first)
            zeros = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype),
                (shapes[0], shapes[1], shapes[3]))

            def body(carry, mb):
                extra_c, (loss_s, extras_s, grads_s) = carry
                loss, extra_metrics, updated, grads = fwd_bwd(
                    params, extra_c, mb)
                new_extra = ({**extra_c, **updated} if updated
                             else extra_c)
                sums = jax.tree.map(jnp.add,
                                    (loss_s, extras_s, grads_s),
                                    (loss, extra_metrics, grads))
                return (new_extra, sums), None

            (extra_out, sums), _ = jax.lax.scan(
                body, (extra_vars, zeros), micro)
            inv_n = 1.0 / grad_accum_steps
            loss, extra_metrics, grads = jax.tree.map(
                lambda x: x * inv_n, sums)
            updated = {c: extra_out[c] for c in mutable_cols
                       if c in extra_out}
        if data_axes:
            grads = jax.lax.pmean(grads, data_axes)
            loss = jax.lax.pmean(loss, data_axes)
            extra_metrics = jax.lax.pmean(extra_metrics, data_axes)
            if updated:
                updated = jax.lax.pmean(updated, data_axes)
        with jax.named_scope('kfac_step/optimizer'):
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        if updated:
            extra_vars = {**extra_vars, **updated}
        metrics = {'loss': loss, **extra_metrics}
        return params, opt_state, kstate, extra_vars, metrics

    if mesh is None:
        return jax.jit(local_step,
                       donate_argnums=(0, 1, 3) if donate else ())

    def step(params, opt_state, kstate, extra_vars, batch, hyper):
        from distributed_kfac_pytorch_tpu.parallel.distributed import (
            normalize_batch_specs)
        batch_specs = normalize_batch_specs(batch_spec, batch)
        fn = jax.shard_map(
            local_step, mesh=mesh,
            in_specs=(_replicated_specs(params),
                      _replicated_specs(opt_state),
                      _replicated_specs(kstate),
                      _replicated_specs(extra_vars),
                      batch_specs,
                      _replicated_specs(hyper)),
            out_specs=(_replicated_specs(params),
                       _replicated_specs(opt_state),
                       _replicated_specs(kstate),
                       _replicated_specs(extra_vars), P()),
            check_vma=False)
        return fn(params, opt_state, kstate, extra_vars, batch, hyper)

    return jax.jit(step, donate_argnums=(0, 1, 3) if donate else ())


def make_eval_step(model, loss_fn, mesh=None, *,
                   model_args_fn=None, model_kwargs=None, metrics_fn=None):
    """Jitted eval step: global-mean loss/accuracy over the mesh.

    Reference parity: engine.py:96-125 (test loop). With a mesh, the batch
    is sharded over the K-FAC axes and metrics are ``pmean``ed; without,
    it is a plain jitted forward. ``model_kwargs`` are static keyword
    arguments for the model call (e.g. ``{'train': False}``).
    """
    if model_args_fn is None:
        model_args_fn = lambda batch: (batch[0],)
    if metrics_fn is None:
        metrics_fn = lambda out, batch: {'acc': accuracy(out, batch[1])}
    model_kwargs = model_kwargs or {}

    def compute(params, extra_vars, batch):
        out = model.apply({'params': params, **extra_vars},
                          *model_args_fn(batch), **model_kwargs)
        metrics = {'loss': loss_fn(out, batch), **metrics_fn(out, batch)}
        if mesh is not None:
            metrics = jax.lax.pmean(metrics, KFAC_AXES)
        return metrics

    if mesh is None:
        return jax.jit(compute)

    from jax.sharding import PartitionSpec as P

    def step(params, extra_vars, batch):
        return jax.shard_map(
            compute, mesh=mesh,
            in_specs=(_replicated_specs(params),
                      _replicated_specs(extra_vars),
                      jax.tree.map(lambda _: P(KFAC_AXES), batch)),
            out_specs=P(), check_vma=False)(params, extra_vars, batch)

    return jax.jit(step)


def make_precise_bn_steps(model, mesh=None, *, model_args_fn=None,
                          stats_col: str = 'batch_stats'):
    """Jitted helpers for precise-BN recalibration (see
    :func:`precise_bn_recalibrate`); build once, reuse every epoch.

    Returns ``(momentum_fn, stat_fn)``:

    - ``momentum_fn(params, others, batch)`` extracts each BatchNorm
      leaf's EWMA momentum from the model itself by running the stats
      update from all-zeros and all-ones starting points (flax
      semantics: ``new = m*old + (1-m)*batch_stat`` is affine in
      ``old``, so ``u1 - u0 == m`` exactly, elementwise). This avoids
      requiring the caller to know every BN layer's momentum — any
      flax model with standard BatchNorm semantics works.
    - ``stat_fn(params, others, batch, m)`` returns that batch's raw
      statistics ``u0 / (1 - m)`` (mesh: ``pmean`` over the K-FAC data
      axes, i.e. the average of per-shard batch statistics).
    """
    from jax.sharding import PartitionSpec as P

    if model_args_fn is None:
        model_args_fn = lambda batch: (batch[0],)

    def updated(params, others, stats0, batch):
        _, upd = model.apply({'params': params, **others,
                              stats_col: stats0},
                             *model_args_fn(batch), mutable=[stats_col])
        return upd[stats_col]

    def momentum(params, others, batch, zeros, ones):
        u0 = updated(params, others, zeros, batch)
        u1 = updated(params, others, ones, batch)
        return jax.tree.map(
            lambda a, b: jnp.clip(b - a, 0.0, 1.0 - 1e-6), u0, u1)

    def stat(params, others, batch, m, zeros):
        u0 = updated(params, others, zeros, batch)
        s = jax.tree.map(lambda u, mm: u / (1.0 - mm), u0, m)
        if mesh is not None:
            s = jax.lax.pmean(s, KFAC_AXES)
        return s

    def wrap(fn, n_batch_arg):
        if mesh is None:
            return jax.jit(fn)

        def sharded(*args):
            in_specs = tuple(
                jax.tree.map(lambda _: P(KFAC_AXES), a)
                if i == n_batch_arg else _replicated_specs(a)
                for i, a in enumerate(args))
            # Both fns return a stats-shaped tree (arg 3's structure);
            # eval_shape can't trace fn here (the pmean needs the mesh).
            return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=_replicated_specs(args[3]),
                                 check_vma=False)(*args)

        return jax.jit(sharded)

    return wrap(momentum, 2), wrap(stat, 2)


def precise_bn_recalibrate(model, params, extra_vars: dict,
                           batches: Iterable, mesh=None, *,
                           model_args_fn=None,
                           stats_col: str = 'batch_stats',
                           steps=None) -> dict:
    """Re-estimate BatchNorm running statistics as the plain average of
    per-batch statistics over ``batches`` ("precise BN").

    Why: under K-FAC's large preconditioned steps the EWMA running
    statistics lag the weights, so eval-time normalization is stale —
    the round-3/4 convergence studies isolated exactly this interaction
    as the BN conv-net instability (GroupNorm control wins decisively;
    CONVERGENCE_CONV_{BN,GN}.json). A handful of forward-only batches
    re-estimates the statistics at the *current* weights, which is
    cheap (no backward pass) and touches nothing else: training state,
    params and optimizer are unchanged. The reference has no analogue —
    its eval loop consumes whatever running stats training left behind
    (examples/cnn_utils/engine.py:96-125).

    Models without a ``stats_col`` collection (GroupNorm nets) pass
    through unchanged. Returns a new ``extra_vars``; callers decide
    whether to use it for eval only or adopt it into training state.
    ``steps`` accepts the pair from :func:`make_precise_bn_steps` to
    reuse compiled programs across epochs.
    """
    stats = extra_vars.get(stats_col)
    if not stats:
        return extra_vars
    # Only dict-shaped entries are flax variable collections the model
    # can consume; framework state riding in extra_vars (e.g. the fp16
    # loss-scale pytree) is not passed to apply.
    others = {k: v for k, v in extra_vars.items()
              if k != stats_col and isinstance(v, dict)}
    momentum_fn, stat_fn = steps or make_precise_bn_steps(
        model, mesh, model_args_fn=model_args_fn, stats_col=stats_col)
    zeros = jax.tree.map(jnp.zeros_like, stats)
    ones = jax.tree.map(jnp.ones_like, stats)
    m = None
    total, n = None, 0
    for batch in batches:
        if m is None:
            m = momentum_fn(params, others, batch, zeros, ones)
        s = stat_fn(params, others, batch, m, zeros)
        total = s if total is None else jax.tree.map(jnp.add, total, s)
        n += 1
    if n == 0:
        raise ValueError('precise_bn_recalibrate: zero batches provided')
    new_stats = jax.tree.map(lambda t: t / n, total)
    return {**extra_vars, stats_col: new_stats}


def evaluate(eval_step, state: TrainState, batches: Iterable, *,
             log_writer=None, verbose: bool = False) -> dict[str, float]:
    """Run the eval loop; returns averaged metrics."""
    meters: dict[str, Metric] = {}
    n_batches = 0
    for batch in batches:
        metrics = eval_step(state.params, state.extra_vars, batch)
        n_batches += 1
        for k, v in metrics.items():
            meters.setdefault(k, Metric(k)).update(v)
    if n_batches == 0:
        raise ValueError(
            'evaluate: the batch iterator yielded ZERO batches — '
            'usually val_batch_size larger than the val set (full '
            'batches are required for static shapes). Lower the batch '
            'size or enlarge the dataset.')
    out = {k: m.avg for k, m in meters.items()}
    if log_writer is not None:
        for k, v in out.items():
            log_writer.scalar(f'val/{k}', v, state.epoch)
    if verbose:
        shown = {k: round(v, 4) for k, v in out.items()}
        print(f'epoch {state.epoch}: val {shown}')
    return out


class TensorBoardWriter:
    """TensorBoard scalar writer (the reference uses torch's
    SummaryWriter, engine.py:89-93) on tensorboardX: Python and
    protobuf only, so the host loop brings no second accelerator
    runtime into the process that owns the TPU. Without the package
    the writer says so once and drops the scalars."""

    def __init__(self, log_dir: str):
        try:
            from tensorboardX import SummaryWriter
        except ImportError:
            import warnings
            warnings.warn('tensorboardX is not installed: no TensorBoard '
                          f'scalars will be written to {log_dir}')
            self._writer = None
        else:
            self._writer = SummaryWriter(log_dir)

    def scalar(self, tag: str, value, step: int):
        if self._writer is not None:
            self._writer.add_scalar(tag, float(value), step)

    def flush(self):
        if self._writer is not None:
            self._writer.flush()
