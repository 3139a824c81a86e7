"""ImageNet ResNet training with distributed K-FAC on a TPU mesh.

TPU-native counterpart of the reference entry point
(examples/torch_imagenet_resnet.py): same flag surface and recipe — 55
epochs, lr decay @ 25/35/40/45/50, base-lr 0.0125 per worker linearly
scaled, 5 warmup epochs, label smoothing 0.1, wd 5e-5
(torch_imagenet_resnet.py:57-70), K-FAC inv every 100 iters / factors
every 10 (:75-78) — on the jitted SPMD train step instead of DDP + hooks.

Run:
    python examples/train_imagenet_resnet.py --epochs 55 --model resnet50
Without --data-dir a synthetic ImageNet-shaped set keeps it runnable
offline (the bench/smoke path).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

from distributed_kfac_pytorch_tpu import autotune
from distributed_kfac_pytorch_tpu import capture as capture_lib
from distributed_kfac_pytorch_tpu import elastic as elastic_lib
from distributed_kfac_pytorch_tpu import fp16 as fp16_lib
from distributed_kfac_pytorch_tpu import launch
from distributed_kfac_pytorch_tpu import observability as obs
from distributed_kfac_pytorch_tpu import resilience as resil
from distributed_kfac_pytorch_tpu import multislice
from distributed_kfac_pytorch_tpu.models import imagenet_resnet, vit
from distributed_kfac_pytorch_tpu.parallel import distributed as D
from distributed_kfac_pytorch_tpu.training import (
    checkpoint as ckpt_lib,
    datasets,
    engine,
    optimizers,
    utils,
)

from distributed_kfac_pytorch_tpu.utils import enable_compilation_cache

enable_compilation_cache()  # JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        description='ImageNet ResNet + distributed K-FAC (TPU-native)')
    # Training settings (reference torch_imagenet_resnet.py:40-70).
    p.add_argument('--data-dir', default=None,
                   help='ImageFolder-style tree (synthetic if absent)')
    p.add_argument('--log-dir', default='./logs/imagenet')
    p.add_argument('--checkpoint-dir', default='./checkpoints/imagenet')
    p.add_argument('--checkpoint-freq', type=int, default=5)
    p.add_argument('--model', default='resnet50',
                   help="resnet<depth> or 'vit_<tiny|small|base>' "
                        '(ViT-*/16; --image-size must divide by 16)')
    p.add_argument('--image-size', type=int, default=224)
    p.add_argument('--batch-size', type=int, default=256,
                   help='global batch size')
    p.add_argument('--val-batch-size', type=int, default=256)
    p.add_argument('--epochs', type=int, default=55)
    p.add_argument('--base-lr', type=float, default=0.0125,
                   help='per-worker lr, linearly scaled by worker count')
    p.add_argument('--lr-decay', type=int, nargs='+',
                   default=[25, 35, 40, 45, 50])
    p.add_argument('--warmup-epochs', type=float, default=5)
    p.add_argument('--momentum', type=float, default=0.9)
    p.add_argument('--wd', type=float, default=5e-5)
    p.add_argument('--label-smoothing', type=float, default=0.1)
    p.add_argument('--grad-accum', type=int, default=1,
                   help='micro-batches per step (batches-per-allreduce)')
    p.add_argument('--precise-bn-batches', type=int, default=0,
                   help='re-estimate BN running statistics over this '
                        'many forward-only train batches before each '
                        'eval (precise-BN — the round-5 mitigation for '
                        'BN stats lagging large preconditioned steps; '
                        '0 = off). Eval-only: training EWMA state is '
                        'untouched.')
    p.add_argument('--bn-momentum', type=float, default=None,
                   help='BatchNorm running-stat EWMA momentum (flax '
                        'convention; default 0.9 = torch momentum 0.1; '
                        'rejected for models without BatchNorm)')
    p.add_argument('--remat', action='store_true',
                   help='block-level gradient checkpointing: ~1/3 extra '
                        'forward FLOPs for O(depth) activation memory — '
                        'fits larger monolithic batches (the bf16 K-FAC '
                        'capture path OOMs at b128@224 without it)')
    p.add_argument('--seed', type=int, default=42)
    p.add_argument('--no-resume', action='store_true')
    # K-FAC hyperparameters (reference torch_imagenet_resnet.py:71-105).
    p.add_argument('--kfac-update-freq', type=int, default=100,
                   help='inverse update interval; 0 disables K-FAC')
    p.add_argument('--inv-pipeline-chunks', type=int, default=1,
                   help='pipeline the per-firing inverse work into K '
                        'cost-balanced chunks fired across the cadence '
                        'window (step-time uniformity, r9); 1 = '
                        'reference parity (monolithic firing). K must '
                        'divide --kfac-update-freq and not exceed the '
                        "model's inverse bucket count")
    p.add_argument('--deferred-factor-reduction', action='store_true',
                   help='accumulate factor statistics locally and '
                        'reduce across replicas once per cadence '
                        'window instead of every factor step (r14 '
                        'compute/communication overlap; exact by EMA '
                        'linearity — off (default) keeps the '
                        'bit-identical eager per-step reduction)')
    p.add_argument('--hierarchical-reduce', action='store_true',
                   help='two-level factor reduction (r20; requires '
                        '--num-slices > 1, mutually exclusive with '
                        '--deferred-factor-reduction): intra-slice '
                        'pmean on ICI every factor step, one bucketed '
                        'inter-slice DCN reduce per cadence window')
    p.add_argument('--num-slices', type=int,
                   default=int(os.environ.get('KFAC_NUM_SLICES', 1)),
                   help='multi-slice mesh: outer kfac_slice axis over '
                        'N contiguous device slabs (r20). 1 (default) '
                        '= the flat mesh, bit-identical to pre-r20 '
                        'runs. Defaults from KFAC_NUM_SLICES (set by '
                        'the supervisor on slice-failure failover)')
    p.add_argument('--inv-staleness', type=int, default=0,
                   choices=[0, 1],
                   help='1 = one-window-stale off-critical-path '
                        'inverses (r14): decompositions fire across '
                        "the window's plain steps from the frozen "
                        'window-head factor snapshot, overlapping '
                        'plain compute instead of blocking the mesh '
                        '(needs update-freq/chunks >= 2). '
                        'Convergence-gated like --inv-pipeline-chunks '
                        '(PERF.md r14)')
    p.add_argument('--inv-lowrank-rank', type=int, default=0,
                   help='rank of the randomized truncated '
                        'eigendecomposition for large factor dims '
                        '(r19, arXiv:2206.15397): dims >= '
                        '--inv-lowrank-dim-threshold fire a rank-r '
                        'sketch + warm subspace polish (r*d^2 work) '
                        'instead of the O(d^3) exact decomposition; '
                        'preconditioning adds the damping-only tail '
                        'complement so it stays full-rank correct. '
                        '0 (default) = off, the bit-identical exact '
                        'path; rank >= an engaged dim is a hard error')
    p.add_argument('--inv-lowrank-dim-threshold', type=int,
                   default=2048,
                   help='smallest dense factor dim the low-rank path '
                        'engages (transformer-scale factors by '
                        'default; ignored at --inv-lowrank-rank 0)')
    p.add_argument('--kfac-cov-update-freq', type=int, default=10)
    p.add_argument('--kfac-approx', default='expand',
                   choices=['expand', 'reduce'],
                   help='weight-sharing Kronecker approximation (r13, '
                        'arXiv:2311.00636): expand (default) is the '
                        'bit-identical historical path; reduce '
                        'collapses the shared patch axis before the '
                        'covariance — the paper\'s ViT treatment '
                        '(patch-embed conv + every encoder Dense); a '
                        'no-op for plain conv nets')
    p.add_argument('--kfac-update-freq-alpha', type=float, default=10)
    p.add_argument('--kfac-update-freq-decay', type=int, nargs='+',
                   default=[])
    p.add_argument('--inverse-method', default='auto',
                   choices=['auto', 'eigen', 'cholesky', 'newton'],
                   help='auto = per-dim dispatch: eigen below the '
                        'measured cutoff, cholesky above (the TPU '
                        'default that is fast at flagship factor dims)')
    p.add_argument('--eigh-method', default='auto',
                   choices=['auto', 'xla', 'jacobi', 'warm'],
                   help='eigen-path decomposition backend; auto = '
                        'warm-start matmul-only basis polish (TPU '
                        'fast path)')
    p.add_argument('--factor-batch-fraction', type=float, default=1.0,
                   help='fraction of the batch used for factor '
                        'statistics (1.0 = reference parity; <1 thins '
                        'the covariance sample within the step)')
    p.add_argument('--eigh-polish-iters', type=int, default=8,
                   help='warm-polish iterations per eigh firing (8: ~1e-3 '
                        'tracking, the measured-equivalent fast default; 16: '
                        '~1e-5)')
    p.add_argument('--stat-decay', type=float, default=0.95)
    p.add_argument('--damping', type=float, default=0.001)
    p.add_argument('--damping-alpha', type=float, default=0.5)
    p.add_argument('--damping-decay', type=int, nargs='+', default=[])
    p.add_argument('--kl-clip', type=float, default=0.001)
    p.add_argument('--skip-layers', nargs='+', default=[])
    p.add_argument('--comm-method', default='comm-opt',
                   choices=sorted(optimizers.COMM_METHODS))
    p.add_argument('--grad-worker-fraction', type=float, default=0.25)
    p.add_argument('--coallocate-layer-factors', action='store_true',
                   help='place A and G of a layer on the same worker '
                        '(reference --coallocate-layer-factors)')
    p.add_argument('--symmetry-aware-comm', action='store_true',
                   help='triu-packed factor allreduce (halved bytes)')
    p.add_argument('--bf16-factors', action='store_true',
                   help='bf16 factor storage/averaging + bf16 covariance '
                        'matmul inputs (matmuls accumulate fp32); the '
                        'reference fp16 factor mode')
    p.add_argument('--bf16-inverses', action='store_true',
                   help='bf16 inverse storage (decompositions stay '
                        'fp32); with --bf16-factors this is the '
                        'measured b256 production config on 16 GB '
                        'chips (PERF.md round 5)')
    p.add_argument('--bf16-precond', action='store_true',
                   help='bf16 precondition-contraction operands (fp32 '
                        'accumulation; KFAC precond_compute_dtype) — '
                        'the every-step inverse-times-grad matmuls on '
                        'the MXU bf16 path; with --bf16-inverses the '
                        'stored inverses are consumed resident (r6)')
    p.add_argument('--fp16', action='store_true',
                   help='fp16 model compute with dynamic loss scaling + '
                        'overflow-skip (GradScaler parity — the '
                        "reference's production ImageNet recipe passes "
                        '--fp16, launch_node_torch_imagenet.sh:73-87; '
                        'engine.py:38-41,75-80). On TPU, bf16 is the '
                        'native half mode and needs no scaler; --fp16 '
                        'exists for exact reference-recipe parity.')
    obs.cli.add_observability_args(p)
    resil.cli.add_resilience_args(p)
    autotune.cli.add_autotune_args(p)
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    # Preemption handling installs FIRST: a SIGTERM during bring-up
    # should still drain gracefully (r8).
    preemption = resil.cli.install_preemption(args)
    # Multi-host init BEFORE any backend use (reference analogue:
    # init_process_group at torch_imagenet_resnet.py:113, driven by
    # scripts/launch_tpu_pod.sh; single-host no-op).
    info = launch.initialize_multihost()
    is_main = info['process_index'] == 0
    n_dev = jax.device_count()
    if is_main:
        print(f'devices: {n_dev} global / {info["local_devices"]} local '
              f'x {info["process_count"]} processes '
              f'({jax.default_backend()})')

    data = datasets.get_imagenet(args.data_dir,
                                 image_size=args.image_size)
    nproc = info['process_count']
    batches_local = False  # True: iterators yield per-process shards
    if isinstance(data[0], tuple):
        (train_x, train_y), (val_x, val_y) = data
        # skip= is the mid-epoch resume offset (resilience r8): the
        # seeded numpy pipeline replays the remaining batches
        # bit-identically (see resilience.dataiter).
        train_iter_fn = lambda epoch, skip=0: datasets.epoch_batches(
            train_x, train_y, args.batch_size, seed=args.seed,
            epoch=epoch, skip_batches=skip)
        val_iter_fn = lambda: datasets.epoch_batches(
            val_x, val_y, args.val_batch_size, shuffle=False)
    else:
        train_ds, val_ds = data
        tb, vb = args.batch_size, args.val_batch_size
        if nproc > 1:
            # Shard the input pipeline per process (the reference's
            # DistributedSampler analogue, datasets.py:57-63) so no host
            # pays the full global decode cost; global_batches then
            # assembles the local shards without re-slicing.
            if tb % nproc or vb % nproc:
                raise SystemExit(
                    f'batch sizes ({tb}, {vb}) must divide evenly over '
                    f'{nproc} processes')
            train_ds = train_ds.shard(nproc, info['process_index'])
            val_ds = val_ds.shard(nproc, info['process_index'])
            tb, vb = tb // nproc, vb // nproc
            batches_local = True
        # tf.data path: mid-epoch resume is BEST-EFFORT — the model
        # state restores exactly, but shuffle order is per iterator
        # creation (not epoch-seeded), so the skipped-batch replay is
        # not bit-identical here (resilience.dataiter documents this;
        # the numpy pipelines above carry the replay guarantee).
        train_iter_fn = lambda epoch, skip=0: (
            (x.numpy(), y.numpy()) for x, y in
            train_ds.batch(tb, drop_remainder=True).skip(skip))
        val_iter_fn = lambda: (
            (x.numpy(), y.numpy()) for x, y in
            val_ds.batch(vb, drop_remainder=True))

    dtype = jnp.float16 if args.fp16 else jnp.float32
    # Strict name parsing: exactly 'vit' or 'vit_<size>'. A prefix match
    # alone would let 'vitbase'/'vit-base' fall through and silently
    # train the default config (ADVICE r5).
    model_head, _, vit_size = args.model.partition('_')
    if model_head == 'vit':
        if args.remat:
            raise SystemExit('--remat is the ResNet block-level knob; '
                             'for ViT memory use chunked attention '
                             '(models/vit.py attn_block_size)')
        model = vit.get_model(1000, vit_size or 'small', dtype=dtype)
    elif args.model.startswith('vit'):
        raise SystemExit(
            f'unknown model {args.model!r}: ViT configs are spelled '
            "'vit' or 'vit_<tiny|small|base>'")
    else:
        model = imagenet_resnet.get_model(
            args.model, dtype=dtype,
            bn_momentum=0.9 if args.bn_momentum is None
            else args.bn_momentum, remat=args.remat)
    cfg = optimizers.OptimConfig(
        base_lr=args.base_lr, momentum=args.momentum,
        weight_decay=args.wd, warmup_epochs=args.warmup_epochs,
        lr_decay=args.lr_decay, workers=n_dev,
        kfac_inv_update_freq=args.kfac_update_freq,
        kfac_cov_update_freq=args.kfac_cov_update_freq,
        inv_pipeline_chunks=args.inv_pipeline_chunks,
        deferred_factor_reduction=args.deferred_factor_reduction,
        hierarchical_reduce=args.hierarchical_reduce,
        inv_staleness=args.inv_staleness,
        kfac_approx=args.kfac_approx,
        inv_lowrank_rank=args.inv_lowrank_rank,
        inv_lowrank_dim_threshold=args.inv_lowrank_dim_threshold,
        damping=args.damping, factor_decay=args.stat_decay,
        kl_clip=args.kl_clip, inverse_method=args.inverse_method,
        eigh_method=args.eigh_method,
        eigh_polish_iters=args.eigh_polish_iters,
        factor_batch_fraction=args.factor_batch_fraction,
        skip_layers=args.skip_layers, comm_method=args.comm_method,
        grad_worker_fraction=args.grad_worker_fraction,
        symmetry_aware_comm=args.symmetry_aware_comm,
        damping_alpha=args.damping_alpha,
        damping_schedule=args.damping_decay,
        kfac_update_freq_alpha=args.kfac_update_freq_alpha,
        kfac_update_freq_schedule=args.kfac_update_freq_decay,
        bf16_factors=args.bf16_factors,
        bf16_inverses=args.bf16_inverses,
        bf16_precond=args.bf16_precond,
        kfac_metrics=bool(args.kfac_metrics),
        # --selfheal forces the guard on: the ladder's rung 1 IS the
        # on-device skip-window (README "Self-healing").
        nonfinite_guard=(obs.cli.wants_guard(args)
                         or resil.cli.wants_selfheal_guard(args)))
    # Tuned-config overlay (fail-closed): the queued apply/fallback
    # events land in the metrics stream once the sink exists below.
    cfg, tune_events = autotune.cli.maybe_apply_tuned(args, cfg)
    cadence_policy = autotune.cli.make_cadence_policy(args)
    tx, lr_schedule, kfac, kfac_sched = optimizers.get_optimizer(model, cfg)
    if args.kfac_metrics and kfac is None:
        raise SystemExit('--kfac-metrics requires the K-FAC step '
                         '(--kfac-update-freq > 0)')
    if cadence_policy is not None and kfac is None:
        raise SystemExit('--cadence-backoff requires the K-FAC step '
                         '(--kfac-update-freq > 0)')
    metrics_sink = obs.cli.make_metrics_sink(
        args, info, meta={'cli': 'train_imagenet_resnet',
                          'model': args.model,
                          'batch_size': args.batch_size,
                          'devices': n_dev,
                          'metrics_interval': args.metrics_interval})
    autotune.emit_events(metrics_sink, tune_events)
    shard_meta = {'cli': 'train_imagenet_resnet'}
    if (args.num_slices > 1
            and info['process_count'] % args.num_slices == 0):
        # Slice id into the shard meta -> per-slice skew rows in the
        # report's straggler section (r20).
        shard_meta['slice'] = multislice.slice_of_rank(
            info['process_index'], info['process_count'],
            args.num_slices)
    rank_sink = obs.cli.make_rank_shard_sink(args, info, meta=shard_meta)
    # r17 liveness lease (per rank; armed by --heartbeat-dir or the
    # supervisor's KFAC_HEARTBEAT_DIR — None otherwise, and the engine
    # path is byte-identical without it).
    heartbeat = resil.cli.make_heartbeat(args, info)

    x0 = jnp.zeros((2, args.image_size, args.image_size, 3), jnp.float32)
    if kfac is not None:
        # [0]: kfac.init also returns a single-chip K-FAC state. This
        # path builds its own layout (DistributedKFAC.init_state); a
        # name bound to the other one would keep a second copy of every
        # factor and inverse on the device for the whole run.
        variables = kfac.init(jax.random.PRNGKey(args.seed), x0)[0]
        obs.cli.emit_layer_meta(metrics_sink, kfac)
    else:
        variables = model.init(jax.random.PRNGKey(args.seed), x0)
    params = variables['params']
    # batch_stats exists only for BatchNorm models (absent for ViT —
    # stateless LayerNorm).
    extra = capture_lib.extra_vars_of(variables)
    mutable = ('batch_stats',) if 'batch_stats' in extra else ()
    if args.precise_bn_batches > 0 and not mutable:
        raise SystemExit('--precise-bn-batches requires a BatchNorm '
                         f'model; {args.model!r} has no batch_stats')
    if args.bn_momentum is not None and not mutable:
        raise SystemExit('--bn-momentum requires a BatchNorm model; '
                         f'{args.model!r} has no batch_stats')
    if args.fp16:
        if kfac is None:
            raise SystemExit('--fp16 requires the K-FAC step '
                             '(--kfac-update-freq > 0); the SGD baseline '
                             'path does not wire the loss scaler.')
        extra['loss_scale'] = fp16_lib.init_loss_scale()

    # num_slices == 1 returns the flat make_kfac_mesh mesh (the
    # --num-slices 1 bit-identity guarantee).
    mesh = multislice.make_multislice_mesh(
        num_slices=args.num_slices,
        comm_method=optimizers.COMM_METHODS[args.comm_method],
        grad_worker_fraction=args.grad_worker_fraction)
    # Commit params/extra replicated on the mesh up front: the resume
    # path builds its restore template (like=) from live state, and an
    # uncommitted single-device init would restore a pod checkpoint
    # onto one device (caught by the r8 multihost kill test).
    params, extra = launch.replicate_on_mesh(mesh, (params, extra))
    opt_state = tx.init(params)

    def loss_fn(out, batch):
        return utils.label_smooth_loss(out, batch[1],
                                       args.label_smoothing)

    def metrics_fn(out, batch):
        return {'acc': utils.accuracy(out, batch[1])}

    if kfac is not None:
        dkfac = D.DistributedKFAC(
            kfac, mesh, params,
            distribute_layer_factors=(
                False if args.coallocate_layer_factors else None))
        kstate = dkfac.init_state(params)
        step_fn = dkfac.build_train_step(
            loss_fn, tx, metrics_fn=metrics_fn,
            mutable_cols=mutable,
            grad_accum_steps=args.grad_accum,
            loss_scale='dynamic' if args.fp16 else None)
    else:  # --kfac-update-freq 0: plain SGD (reference optimizers.py:28)
        dkfac, kstate = None, None
        step_fn = engine.build_sgd_train_step(
            model, loss_fn, tx, mesh, metrics_fn=metrics_fn,
            mutable_cols=mutable,
            grad_accum_steps=args.grad_accum)
    eval_step = engine.make_eval_step(
        model, lambda out, b: utils.label_smooth_loss(out, b[1], 0.0),
        mesh, model_args_fn=lambda b: (b[0],),
        model_kwargs={'train': False})
    # Straggler barrier probe: shards requested (or the cadence-backoff
    # policy armed) + a K-FAC step (the probe reduces over the K-FAC
    # data axes).
    barrier_probe = (dkfac.build_barrier_probe()
                     if (rank_sink is not None
                         or cadence_policy is not None)
                     and dkfac is not None
                     else None)

    state = engine.TrainState(params=params, opt_state=opt_state,
                              kfac_state=kstate, extra_vars=extra)
    if dkfac is None and args.checkpoint_dir == './checkpoints/imagenet':
        # Keep the SGD comparison's checkpoints apart from a K-FAC run's
        # (the state trees differ, so cross-mode resume cannot work).
        args.checkpoint_dir += '-sgd'
    mgr = ckpt_lib.CheckpointManager(args.checkpoint_dir)
    step_mgr = resil.cli.make_step_manager(args)
    # The saving world, recorded in every bundle's scalars so a
    # relaunch on a grown/shrunk pod can reshard instead of cold
    # restarting (elastic resume — README "Elastic training").
    topo = elastic_lib.TopologySpec.of_mesh(
        mesh, distribute_layer_factors=(
            dkfac.distribute_layer_factors if dkfac else None))

    def bundle_fn(st, step_in_epoch, integrity=True):
        # Must match the SAVED structure exactly (orbax StandardRestore
        # is strict): scheduler states + the resume-point scalars
        # (MIGRATION.md "Checkpoint format").
        return ckpt_lib.bundle_state(
            st.params, st.opt_state,
            dkfac.state_dict(st.kfac_state) if dkfac else {},
            st.extra_vars,
            schedulers={'kfac': kfac_sched} if kfac_sched else None,
            topology=topo,
            integrity=integrity,
            step=st.step, epoch=st.epoch, step_in_epoch=step_in_epoch,
            data_seed=args.seed)

    start_epoch, start_offset = 0, 0
    # integrity='template': the like= tree needs the checksum FIELD
    # (orbax structures are exact) but hashing the whole live state
    # for a digest nobody reads was pure startup cost.
    resumed = resil.cli.resume(args, mgr, step_mgr,
                               bundle_fn(state, 0,
                                         integrity='template'),
                               sink=metrics_sink, verbose=is_main,
                               elastic=elastic_lib.ElasticResume(
                                   mesh=mesh, dkfac=dkfac,
                                   params=state.params))
    if resumed is not None:
        restored, start_epoch, start_offset, _src = resumed
        state.params = restored['params']
        state.opt_state = restored['opt_state']
        if dkfac:
            state.kfac_state = dkfac.load_state_dict(
                restored['kfac'], state.params)
        state.extra_vars = restored['extra_vars']
        state.epoch = start_epoch
        state.step = int(restored['scalars']['step'])
        if kfac_sched:
            kfac_sched.step(start_epoch)
    step_ckpt = resil.cli.make_step_checkpointer(
        args, step_mgr, bundle_fn, preemption=preemption,
        sink=metrics_sink, start_step=state.step)
    # r16 self-healing ladder (None when --selfheal is off — the
    # engine then runs the byte-identical pre-r16 path).
    selfheal_ctl = resil.cli.make_selfheal(
        args, kfac=kfac, params=state.params, sink=metrics_sink)

    writer = engine.TensorBoardWriter(args.log_dir) if is_main else None
    bn_steps = (engine.make_precise_bn_steps(model, mesh)
                if args.precise_bn_batches > 0 else None)
    t_start = time.perf_counter()
    try:
        epoch = start_epoch
        while epoch < args.epochs:
            skip = start_offset if epoch == start_epoch else 0
            # Drain a preemption notice that landed during eval/
            # checkpointing of the previous epoch (forced save + exit).
            step_ckpt.poll(state, skip)
            lr = lr_schedule(epoch)
            state.opt_state = optimizers.set_lr(state.opt_state, lr)
            hyper = {'lr': lr,
                     **(kfac_sched.params() if kfac_sched else {})}
            raw = resil.faults.poison_at(train_iter_fn(epoch, skip),
                                         step_ckpt.plan,
                                         first_step=state.step)
            try:
                with obs.cli.profile_epoch(args, info, epoch,
                                           start_epoch):
                    train_m = engine.train_epoch(
                        step_fn, state,
                        launch.global_batches(
                            mesh, raw, already_sharded=batches_local),
                        hyper, log_writer=writer, verbose=is_main,
                        metrics_sink=metrics_sink,
                        checkpointer=step_ckpt,
                        start_step_in_epoch=skip,
                        rank_sink=rank_sink,
                        barrier_probe=barrier_probe,
                        straggler_sample_every=(
                            args.straggler_sample_every),
                        memory_interval=args.memory_interval,
                        cadence_policy=cadence_policy,
                        selfheal=selfheal_ctl,
                        heartbeat=heartbeat)
            except resil.selfheal.Rollback as rb:
                # Rung 4: restore the newest VERIFIED pre-fault step
                # checkpoint into the live state and keep training IN
                # THIS PROCESS (die-and-relaunch is the rung after).
                start_epoch, start_offset = resil.selfheal.\
                    handle_rollback(
                        rb, args=args, step_mgr=step_mgr,
                        like=bundle_fn(state, 0,
                                       integrity='template'),
                        state=state,
                        dkfac=dkfac, sink=metrics_sink,
                        controller=selfheal_ctl,
                        kfac_sched=kfac_sched, checkpointer=step_ckpt,
                        verbose=is_main)
                epoch = start_epoch
                continue
            if args.precise_bn_batches > 0:
                # Precise-BN: eval with stats re-estimated at the current
                # weights; the training EWMA state is restored afterwards.
                import itertools
                recal = engine.precise_bn_recalibrate(
                    model, state.params, state.extra_vars,
                    launch.global_batches(
                        mesh,
                        itertools.islice(train_iter_fn(epoch),
                                         args.precise_bn_batches),
                        already_sharded=batches_local),
                    mesh, steps=bn_steps)
                train_extra, state.extra_vars = state.extra_vars, recal
            engine.evaluate(
                eval_step, state,
                launch.global_batches(mesh, val_iter_fn(),
                                      already_sharded=batches_local),
                log_writer=writer, verbose=is_main)
            if args.precise_bn_batches > 0:
                state.extra_vars = train_extra
            if kfac_sched:
                kfac_sched.step(epoch + 1)
            if (epoch + 1) % args.checkpoint_freq == 0 or \
                    epoch == args.epochs - 1:
                # force=: a cross-epoch self-heal rollback replays
                # epochs whose bundles already exist on disk; the
                # replayed save must overwrite, not crash (the step
                # checkpointer already saves with force for the same
                # reason).
                mgr.save(epoch, bundle_fn(state, 0), force=True)
            epoch += 1
    except resil.preemption.Preempted as p:
        # The step checkpoint is already durable (blocking save).
        step_ckpt.close()
        mgr.wait_until_finished()
        if metrics_sink is not None:
            metrics_sink.close()
        if rank_sink is not None:
            rank_sink.close()
        if heartbeat is not None:
            heartbeat.close()
        if is_main:
            print(f'preempted ({p.reason}) at global step '
                  f'{p.global_step}; checkpoint saved — exiting '
                  f'{resil.preemption.RELAUNCH_EXIT_CODE} for relaunch')
        return resil.preemption.RELAUNCH_EXIT_CODE
    step_ckpt.close()
    mgr.wait_until_finished()  # async saves: durable before exit
    if metrics_sink is not None:
        metrics_sink.close()
    if rank_sink is not None:
        rank_sink.close()
    if heartbeat is not None:
        heartbeat.close()
    if writer is not None:
        writer.flush()
    if is_main:
        print(f'total: {time.perf_counter() - t_start:.1f}s')
    return 0


if __name__ == '__main__':
    sys.exit(main())
