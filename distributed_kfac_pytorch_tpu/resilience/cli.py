"""Shared CLI wiring for the resilience flags (mirrors
``observability.cli``).

All three example entry points expose the same resilience surface;
this module is its single implementation:

    add_resilience_args(parser)     # --checkpoint-steps /
                                    # --checkpoint-secs /
                                    # --preemption-grace / --resume-step
    handler = install_preemption(args)          # SIGTERM/SIGINT + env
    step_mgr = make_step_manager(args)
    ckpt = make_step_checkpointer(args, step_mgr, bundle_fn,
                                  preemption=handler, sink=sink,
                                  start_step=0)
    resumed = resume(args, epoch_mgr, step_mgr, like, sink=sink,
                     elastic=ElasticResume(mesh, dkfac, params))

``resume`` unifies the two checkpoint trees: epoch-indexed checkpoints
(the pre-r8 format, still written at ``--checkpoint-freq``) and
global-step-indexed ones under ``<checkpoint-dir>/steps/``. Both bundle
kinds carry the resume point in their scalars (``epoch`` = the epoch to
(re)enter, offset by ``step_in_epoch`` batches — see
``resilience.dataiter``); the newest point wins, so a stale step
checkpoint left behind by an old preemption can never resume training
backwards past a newer epoch checkpoint.
"""

from __future__ import annotations

import os
import traceback
import warnings

from distributed_kfac_pytorch_tpu.resilience import faults as faults_lib
from distributed_kfac_pytorch_tpu.resilience import (
    integrity as integrity_lib,
    policy as policy_lib,
    preemption as preemption_lib,
)
from distributed_kfac_pytorch_tpu.training import checkpoint as ckpt_lib

STEP_SUBDIR = 'steps'


def add_resilience_args(p) -> None:
    """Resilience flags (r8; see README "Fault tolerance")."""
    p.add_argument('--checkpoint-steps', type=int, default=0,
                   metavar='N',
                   help='save a global-step-indexed checkpoint every N '
                        'optimizer steps into <checkpoint-dir>/steps '
                        '(0 = epoch checkpoints only) — bounds '
                        'preemption loss for long epochs')
    p.add_argument('--checkpoint-secs', type=float, default=0.0,
                   metavar='S',
                   help='also step-checkpoint when S wall-clock seconds '
                        'have passed since the last one (0 = off; on a '
                        "pod, rank 0's clock decides and the verdict "
                        'is broadcast so the collective save stays in '
                        'lockstep)')
    p.add_argument('--preemption-grace', type=float, default=30.0,
                   metavar='S',
                   help='grace budget after SIGTERM/SIGINT (or a '
                        'KFAC_PREEMPT_FILE sentinel): finish the '
                        'in-flight step, force a blocking step '
                        'checkpoint, exit with code '
                        f'{preemption_lib.RELAUNCH_EXIT_CODE} so a '
                        'relaunch loop restarts the run (a second '
                        'signal kills immediately)')
    p.add_argument('--resume-step', type=int, default=None, metavar='G',
                   help='resume from this exact global-step checkpoint '
                        'in <checkpoint-dir>/steps (default: the '
                        'newest of step/epoch checkpoints)')
    # r17 heartbeat leases (README "Supervision & failover"). Off by
    # default; the supervisor arms them via KFAC_HEARTBEAT_DIR so the
    # command line needs no rewriting.
    p.add_argument('--heartbeat-dir', default=None, metavar='DIR',
                   help='publish a per-rank liveness lease (atomic '
                        'JSON file rank<r>.lease with global step, '
                        'wall time, incarnation) into DIR from the '
                        'train loop — the failure supervisor\'s hang/'
                        'dead-worker signal (default: the '
                        'KFAC_HEARTBEAT_DIR env var, unset = no '
                        'heartbeats; pure host-side file I/O, '
                        'bit-identical off AND on)')
    p.add_argument('--heartbeat-every', type=int, default=1,
                   metavar='N',
                   help='publish the lease every N optimizer steps '
                        '(keyed to the global step, so a resumed run '
                        'keeps the cadence); budget --hang-timeout '
                        'above N steps + the eval/checkpoint gaps')
    # r16 self-healing ladder (README "Self-healing"). Off by default:
    # with the ladder unarmed the engine is byte-for-byte the pre-r16
    # program (per-step-loss bit-identity pinned).
    p.add_argument('--selfheal', action='store_true',
                   help='arm the fault-response escalation ladder: '
                        'skip-window (the nonfinite guard, forced on) '
                        '-> damping escalation -> per-bucket layer '
                        'quarantine (identity/SGD fallback while '
                        'factors re-accumulate) -> in-process rollback '
                        'to the newest VERIFIED step checkpoint. '
                        'Requires --kfac-metrics (the ladder reads the '
                        'on-device metrics stream); adds one host '
                        'sync per --selfheal-window steps')
    p.add_argument('--selfheal-window', type=int, default=0,
                   metavar='N',
                   help='ladder observation window in optimizer steps '
                        '(0 = half the K-FAC inverse-update frequency: '
                        'two observations per cadence window, so a '
                        'factor corruption can be quarantined BEFORE '
                        'the next inverse firing decomposes it; '
                        'smaller = faster containment, one more host '
                        'sync per window)')
    p.add_argument('--selfheal-damping-factor', type=float,
                   default=10.0, metavar='F',
                   help='damping multiplier applied per escalation on '
                        'repeated bad windows, decayed one notch per '
                        'clean window (rung 2)')
    p.add_argument('--selfheal-diverge-ratio', type=float,
                   default=10.0, metavar='R',
                   help='a window whose loss exceeds R x the running '
                        'boundary-loss average counts as a divergence '
                        'window (rung-2 trigger). Workload-dependent: '
                        'quadratic losses spike multiplicatively, '
                        'cross-entropy saturates near log(vocab) — '
                        'lower R (e.g. 1.5) for CE workloads')
    p.add_argument('--selfheal-no-quarantine', action='store_true',
                   help='skip the per-bucket quarantine rung (the '
                        'ladder then goes skip -> damping -> '
                        'rollback); also the fallback when a workload '
                        'cannot serve identity directions')
    p.add_argument('--selfheal-max-rollbacks', type=int, default=1,
                   metavar='N',
                   help='in-process rollback budget; past it the '
                        'ladder is exhausted and the process dies '
                        'into the r8 relaunch loop (the last rung)')


def make_heartbeat(args, info):
    """The per-rank :class:`resilience.heartbeat.HeartbeatEmitter` for
    a CLI run, or None when heartbeats are off.

    ``--heartbeat-dir`` wins; the ``KFAC_HEARTBEAT_DIR`` env var is
    the supervisor's hands-off wiring (it exports the var so the
    supervised command line runs unmodified — the same pattern as
    ``KFAC_CHAOS``/``KFAC_PREEMPT_FILE``). EVERY rank emits its own
    lease (the inverse of the rank-0-gated metrics sink): liveness is
    per-host by nature.
    """
    directory = (getattr(args, 'heartbeat_dir', None)
                 or os.environ.get('KFAC_HEARTBEAT_DIR'))
    if not directory:
        return None
    from distributed_kfac_pytorch_tpu.resilience import (
        heartbeat as heartbeat_lib,
    )
    return heartbeat_lib.HeartbeatEmitter(
        directory, info['process_index'],
        every=max(1, int(getattr(args, 'heartbeat_every', 1) or 1)))


def install_preemption(args) -> preemption_lib.PreemptionHandler:
    """Install the signal handler (plus the ``KFAC_PREEMPT_FILE``
    sentinel source when set). Call EARLY in main() — a preemption
    notice arriving before installation kills the process with the
    default disposition."""
    handler = preemption_lib.PreemptionHandler(
        grace_secs=args.preemption_grace).install()
    sentinel = os.environ.get('KFAC_PREEMPT_FILE')
    if sentinel:
        handler.add_source(preemption_lib.file_source(sentinel))
    return handler


def make_step_manager(args) -> ckpt_lib.CheckpointManager:
    """The global-step-indexed manager under ``<checkpoint-dir>/steps``
    (orbax ignores the non-integer subdirectory when scanning the
    parent epoch tree).

    With ``--selfheal`` the retention deepens (10 bundles instead of
    2): the rung-4 rollback must find a VERIFIED bundle saved BEFORE
    the fault onset, and onset detection trails the fault by up to
    ``rollback_after`` observation windows — two kept bundles are
    routinely both post-fault by then (README "Self-healing").
    """
    keep = 10 if getattr(args, 'selfheal', False) else 2
    return ckpt_lib.CheckpointManager(
        os.path.join(args.checkpoint_dir, STEP_SUBDIR),
        max_to_keep=keep)


def make_step_checkpointer(args, step_mgr, bundle_fn, *,
                           preemption=None, sink=None,
                           start_step: int = 0
                           ) -> policy_lib.StepCheckpointer:
    """Assemble the per-step hook: interval policy + preemption forcing
    + any ``KFAC_CHAOS`` fault plan. Always constructed (even with both
    intervals at 0) because preemption must be able to force a save."""
    pol = policy_lib.CheckpointPolicy(
        every_steps=args.checkpoint_steps,
        every_secs=args.checkpoint_secs, start_step=start_step)
    return policy_lib.StepCheckpointer(
        step_mgr, pol, bundle_fn, preemption=preemption, sink=sink,
        plan=faults_lib.plan_from_env())


def wants_selfheal_guard(args) -> bool:
    """True when the CLI must arm the on-device non-finite factor
    guard because the ladder is armed (rung 1 is the guard; without it
    a poisoned candidate silently enters the EWMA and the ladder's
    ``nonfinite_skips`` signal never fires)."""
    return bool(getattr(args, 'selfheal', False))


def make_selfheal(args, *, kfac, params, sink=None):
    """Build the :class:`resilience.selfheal.SelfHealController` for a
    CLI run (or None when ``--selfheal`` is off).

    Fail-closed wiring: the ladder needs the on-device metrics stream
    (``--kfac-metrics``) and a K-FAC step — arming it without either
    is a usage error, not a silent no-op.
    """
    if not getattr(args, 'selfheal', False):
        return None
    from distributed_kfac_pytorch_tpu.resilience import (
        selfheal as selfheal_lib,
    )
    if not getattr(args, 'kfac_metrics', None):
        raise SystemExit('--selfheal requires --kfac-metrics (the '
                         'ladder is driven by the on-device metrics '
                         'stream)')
    if kfac is None:
        raise SystemExit('--selfheal requires the K-FAC step '
                         '(--kfac-update-freq > 0)')
    window = int(getattr(args, 'selfheal_window', 0) or 0)
    if window <= 0:
        # Half the inverse cadence: the quarantine rung can only
        # CONTAIN a factor corruption if it is detected (and the EWMA
        # reset) before the next inverse firing decomposes the poison
        # into the preconditioner — two observations per firing window
        # give it that head start (README "Self-healing"; a fault the
        # gate cannot outrun escalates to rollback instead, which is
        # the correct rung once parameters are contaminated).
        window = max(1, int(getattr(args, 'kfac_update_freq', 10)) // 2)
    cfg = selfheal_lib.SelfHealConfig(
        check_every=window,
        damping_factor=args.selfheal_damping_factor,
        diverge_ratio=args.selfheal_diverge_ratio,
        quarantine=not args.selfheal_no_quarantine,
        max_rollbacks=args.selfheal_max_rollbacks)
    bucket_layers = (None if args.selfheal_no_quarantine
                     else selfheal_lib.bucket_layer_map(kfac, params))
    return selfheal_lib.SelfHealController(
        cfg, bucket_layers=bucket_layers, sink=sink)


def resume(args, epoch_mgr, step_mgr, like, *, sink=None,
           verbose: bool = False, elastic=None):
    """Restore the newest checkpoint (step or epoch tree), if any.

    Returns ``(restored_tree, start_epoch, start_offset, source)`` or
    None when there is nothing to resume (or ``--no-resume``).

    r16 integrity: every candidate bundle's content checksum
    (``resilience.integrity``, recorded by ``bundle_state``) is
    verified after restore; a bundle that fails restore OR
    verification is quarantined (``ckpt_quarantine`` event + warning)
    and the walk continues to the next-older bundle in that tree —
    resume lands on the newest VERIFIABLE state instead of crashing
    on a torn/bit-rotted one. If bundles exist but none verifies,
    resume raises ``SystemExit`` rather than silently cold-starting.
    Pre-r16 bundles (no checksum field) restore unverified with a
    warning.
    ``like`` must be a live-state bundle template: restore always goes
    through ``like=`` so sharded SPMD state comes back with its
    committed shardings (restore without ``like`` yields host arrays —
    see ``CheckpointManager.restore``).

    ``elastic``: an ``elastic.ElasticResume(mesh=, dkfac=, params=)``
    describing the LIVE world. With it, a bundle saved on a DIFFERENT
    topology (detected from its recorded ``topo_*`` scalars,
    ``elastic.topology``) is restored replicated onto the live mesh
    (``CheckpointManager.restore_replicated``) and its K-FAC slot
    stacks are repacked for the new KAISA grid
    (``elastic.reshard``) instead of the restore failing — the
    grow/shrink resume path (README "Elastic training"). A
    ``topology_change`` event is emitted into ``sink``. Bundles that
    predate the topology record restore same-topology-only (their
    inverse stacks are rebuilt from factors if the layout happens to
    differ — ``DistributedKFAC.load_state_dict``'s shape check).
    Without ``elastic``, behavior is unchanged (same-topology
    ``like=`` restores).
    """
    if getattr(args, 'no_resume', False):
        return None
    # Known tradeoff: picking the winner needs the step bundle's
    # scalars, and orbax StandardRestore is whole-tree, so a stale step
    # checkpoint costs one discarded full restore before the epoch one
    # loads. That only happens on the first relaunch after an old
    # preemption was overtaken by epoch checkpoints — accepted over
    # maintaining a second scalars-only manifest.
    candidates = []  # ((epoch, offset), tree, source, label, relaid, mgr)
    quarantined: list[str] = []
    found = _walk_restore(step_mgr, like, args, kind='step',
                          sink=sink, elastic=elastic,
                          explicit=args.resume_step,
                          quarantined=quarantined)
    if found is not None:
        label, tree, relaid = found
        sc = tree['scalars']
        candidates.append(((int(sc['epoch']), int(sc['step_in_epoch'])),
                           tree, 'step', label, relaid, step_mgr))
    if args.resume_step is None:
        # Epoch bundles record their resume point too ((e+1, 0) — the
        # epoch completed); walk only the labels that could win over
        # the step candidate (older epoch bundles resume strictly
        # earlier, so the filtered list stays newest-first-best).
        step_point = candidates[0][0] if candidates else None
        epoch_labels = [e for e in sorted(epoch_mgr.all_steps(),
                                          reverse=True)
                        if step_point is None or (e + 1, 0) > step_point]
        found = _walk_restore(epoch_mgr, like, args, kind='epoch',
                              sink=sink, elastic=elastic,
                              labels=epoch_labels,
                              quarantined=quarantined)
        if found is not None:
            label, tree, relaid = found
            sc = tree['scalars']
            candidates.append(
                ((int(sc['epoch']), int(sc['step_in_epoch'])),
                 tree, 'epoch', label, relaid, epoch_mgr))
    if not candidates:
        if quarantined:
            # Bundles exist but none verifies: training from scratch
            # here would silently discard the run's history — that is
            # a decision for the operator, not a default.
            raise SystemExit(
                f'cannot resume under {args.checkpoint_dir}: every '
                f'checkpoint bundle failed restore/verification '
                f'({"; ".join(quarantined)}). Pass --no-resume to '
                'train from scratch or point --checkpoint-dir at a '
                'healthy tree.')
        return None
    (start_epoch, offset), tree, source, label, relaid, won_mgr = max(
        candidates, key=lambda c: c[0])
    if elastic is not None:
        tree = _adopt_topology(tree, elastic, relaid, won_mgr, label,
                               like, sink=sink, verbose=verbose)
    # The bundle's data_seed is part of the data-stream position
    # (resilience.dataiter): adopt it, or a supervisor that relaunches
    # without --seed would skip `offset` batches of a DIFFERENT
    # permutation — silently double-training some samples and never
    # seeing others.
    saved_seed = tree['scalars'].get('data_seed')
    if saved_seed is not None and hasattr(args, 'seed'):
        saved_seed = int(saved_seed)
        if saved_seed != args.seed:
            if verbose:
                print(f'resume: adopting checkpoint data_seed '
                      f'{saved_seed} (relaunch passed --seed '
                      f'{args.seed}) to keep the batch replay exact')
            args.seed = saved_seed
    if sink is not None:
        sink.event_record('restore', source=source, label=int(label),
                          global_step=int(tree['scalars']['step']),
                          epoch=start_epoch, step_in_epoch=offset)
    if verbose:
        at = f', mid-epoch offset {offset}' if offset else ''
        print(f'resumed from {source} checkpoint {label} '
              f'(epoch {start_epoch}{at})')
    return tree, start_epoch, offset, source


def _template_for(mgr, label, like):
    """The restore template for one bundle: ``like`` as-is for r16
    bundles, ``like`` minus the checksum scalar for bundles that
    predate it (orbax StandardRestore structures must match exactly;
    detected from the bundle's own metadata, no array reads)."""
    try:
        scalars = mgr.metadata_tree(label).get('scalars', {})
        if integrity_lib.CHECKSUM_KEY not in scalars:
            return integrity_lib.strip_checksum(like)
    except Exception:
        pass  # unreadable metadata: try the full template; the
        # restore itself is the arbiter (and the walk quarantines).
    return like


def _walk_restore(mgr, like, args, *, kind: str, sink=None, elastic=None,
                  explicit: int | None = None,
                  labels: list[int] | None = None,
                  quarantined: list[str] | None = None):
    """Restore the newest VERIFIABLE bundle of one checkpoint tree.

    Walks ``labels`` (default: everything on disk, newest first); a
    bundle that fails to restore (torn/incompatible) or fails its
    content-checksum verification (bit rot — ``resilience.integrity``)
    is QUARANTINED: a ``ckpt_quarantine`` event goes into ``sink``, a
    warning names the reason, and the walk continues to the next-older
    bundle instead of crashing resume (r16). Bundles without a
    recorded checksum (pre-r16 / multi-process saves) restore
    unverified with a warning.

    ``explicit`` (``--resume-step``) pins the walk to exactly one
    label and converts its failures into a hard ``SystemExit`` — an
    operator who names a bundle should not be silently handed a
    different one.

    Returns ``(label, tree, relaid)`` or None when nothing restored.
    """
    if labels is None:
        labels = ([explicit] if explicit is not None
                  else sorted(mgr.all_steps(), reverse=True))
    if explicit is not None:
        # An operator naming a QUARANTINED label deserves the real
        # story — which directory the bundle was moved to and why the
        # verified walk moved it — not the generic not-found that a
        # never-saved step gets (r17 satellite; the quarantine reason
        # is recorded by CheckpointManager.quarantine).
        qinfo = getattr(mgr, 'quarantine_info', lambda _l: None)(
            explicit)
        if qinfo is not None:
            qpath, qreason = qinfo
            raise SystemExit(
                f'cannot resume from {kind} checkpoint {explicit}: '
                f'that bundle was QUARANTINED by a previous verified '
                f'resume walk — moved to {qpath} because {qreason}. '
                'Quarantined bundles failed restore or integrity '
                'verification and are kept only for forensics; pick a '
                'different --resume-step or drop the flag to resume '
                'from the newest verifiable checkpoint.')
    for label in labels:
        what = f'{kind} checkpoint {label}'
        use_like = _template_for(mgr, label, like)
        try:
            if elastic is None:
                tree, relaid = mgr.restore(label, like=use_like), False
            else:
                tree, relaid = _elastic_restore(mgr, label, use_like,
                                                elastic)
        except FileNotFoundError as e:
            if explicit is not None:
                # Already self-explanatory (names the requested step
                # and the steps on disk) — no format advice on top.
                raise SystemExit(f'cannot resume from {what}: {e}')
            _quarantine(sink, kind, label, f'restore failed: {e}',
                        quarantined)
            continue
        except Exception as e:
            if explicit is not None:
                traceback.print_exc()  # keep the real cause diagnosable
                raise SystemExit(
                    f'cannot resume from {what} under '
                    f'{args.checkpoint_dir}: {e}\nThe checkpoint was '
                    'likely written with a different model/K-FAC '
                    'configuration, or by a version predating the '
                    'resilience checkpoint-format extension (see '
                    'MIGRATION.md "Checkpoint format") — pass '
                    '--no-resume or a fresh --checkpoint-dir.')
            # No on-disk move here: a generic restore failure is
            # AMBIGUOUS — it hits every bundle identically when the
            # operator relaunched with a changed model/K-FAC config,
            # and renaming the whole history would make the NEXT
            # (fixed) relaunch silently cold-start. Only a confirmed
            # checksum mismatch (below) is unambiguous bit rot worth
            # moving aside; a replay re-saving over a still-present
            # corrupt label is handled by the force-replace in
            # CheckpointManager.save.
            _quarantine(sink, kind, label, f'restore failed: {e}',
                        quarantined)
            continue
        ok, recorded, actual = integrity_lib.verify_tree(tree)
        if ok is False:
            reason = integrity_lib.describe_mismatch(recorded, actual)
            if explicit is not None:
                raise SystemExit(
                    f'cannot resume from {what}: {reason}. The bundle '
                    'is corrupt on disk; drop --resume-step to walk '
                    'back to the newest verifiable checkpoint.')
            _quarantine(sink, kind, label, reason, quarantined,
                        mgr=mgr)
            continue
        if ok is None:
            warnings.warn(
                f'resume: {what} restored UNVERIFIED '
                f'({integrity_lib.describe_mismatch(recorded, actual)} '
                '— see MIGRATION.md "Checkpoint integrity")',
                RuntimeWarning)
        return label, tree, relaid
    return None


def _quarantine(sink, kind: str, label, reason: str,
                quarantined: list[str] | None, mgr=None) -> None:
    """One rejected bundle: durable event + loud warning + walk on.

    With ``mgr``, the bundle's directory is also MOVED aside
    (``CheckpointManager.quarantine`` — kept as ``<label>.quarantined``
    for forensics). Pass ``mgr`` ONLY for confirmed-bad content
    (checksum mismatch, non-finite state) — a generic restore failure
    may be a config mismatch hitting every bundle, and moving the
    whole history would make the next relaunch silently cold-start.
    """
    note = f'{kind} checkpoint {label}: {reason}'
    if quarantined is not None:
        quarantined.append(note)
    warnings.warn(f'resume: quarantining {note} — walking back to the '
                  'next older bundle', RuntimeWarning)
    if mgr is not None:
        try:
            mgr.quarantine(int(label), reason=str(reason))
        except Exception as e:  # best effort: never break the walk
            warnings.warn(f'resume: could not move quarantined '
                          f'{kind} checkpoint {label} aside: {e}',
                          RuntimeWarning)
    if sink is not None:
        sink.event_record('ckpt_quarantine', source=kind,
                          label=int(label), reason=str(reason)[:300])


def _elastic_restore(mgr, label, like, elastic):
    """Same-topology fast path when the saved shapes match the live
    template; otherwise the replicated cross-topology restore."""
    from distributed_kfac_pytorch_tpu.elastic import (
        reshard as reshard_lib,
    )
    md = None
    try:
        md = mgr.metadata_tree(label)
    except Exception:
        md = None  # metadata unreadable: same-topology restore only
    if md is None or reshard_lib.like_matches_metadata(md, like):
        try:
            return mgr.restore(label, like=like), False
        except Exception:
            if md is None:
                raise
            # The positional shape match was a coincidence (structure
            # differed) — the replicated restore below is authoritative.
    return mgr.restore_replicated(label, mesh=elastic.mesh,
                                  like=like), True


def _adopt_topology(tree, elastic, relaid, mgr, label, like, *,
                    sink=None, verbose=False):
    """Post-restore elastic step: reshard the winner's K-FAC state for
    the live world when its recorded topology differs, and re-commit
    replicated-restored groups onto the live mesh."""
    from distributed_kfac_pytorch_tpu.elastic import (
        topology as topo_lib,
    )
    saved = topo_lib.TopologySpec.from_scalars(tree.get('scalars', {}))
    live = elastic.topology
    if saved is not None and saved.needs_reshard(live):
        if not relaid:
            # Same shapes, different slot layout (possible when two
            # KAISA grids coincide in slot counts): the like= restore
            # handed back row-sharded arrays, which cannot be gathered
            # host-side on a pod — re-restore replicated.
            tree = mgr.restore_replicated(label, mesh=elastic.mesh,
                                          like=like)
        tree = elastic.reshard_tree(tree, saved)
    elif relaid:
        # Same layout (or a pre-topology bundle) through the replicated
        # path: no reshard, but the groups still need committing onto
        # the live mesh.
        tree = elastic.reshard_tree(tree, None)
    if saved is not None and saved != live:
        if sink is not None:
            sink.event_record(
                'topology_change',
                global_step=int(tree['scalars']['step']),
                resharded=bool(saved.needs_reshard(live)),
                from_processes=saved.processes, to_processes=live.processes,
                from_devices=saved.devices, to_devices=live.devices,
                from_grid=f'{saved.rows}x{saved.cols}',
                to_grid=f'{live.rows}x{live.cols}')
        if verbose:
            print(f'elastic resume: topology changed — saved on '
                  f'{saved.describe()}, resuming on {live.describe()}'
                  + ('' if saved.needs_reshard(live)
                     else ' (layout-compatible, no reshard)'))
    return tree
