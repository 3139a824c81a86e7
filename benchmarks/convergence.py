"""Convergence evidence: K-FAC vs SGD epochs-to-accuracy on CIFAR.

The reference codebase's whole point is *faster convergence* (SC'20 /
KAISA: reduced time-to-75.9% on ImageNet; 5-epoch CIFAR smoke recipe,
scripts/longhorn_setup.md:20-29). This runner produces that evidence for
the TPU-native rebuild: identical model, data, LR schedule, weight
decay and momentum — the only difference is the K-FAC preconditioner —
and records per-epoch validation accuracy, epochs-to-target and final
accuracy.

Data: the deterministic synthetic class-conditional CIFAR set (this
environment has no data egress; pass --data-dir for real CIFAR pickles
— the code path is identical). Runs on whatever backend JAX resolves
(one TPU chip, or the CPU mesh for CI).

    python benchmarks/convergence.py --epochs 30 --out CONVERGENCE.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

from distributed_kfac_pytorch_tpu.models import cifar_resnet
from distributed_kfac_pytorch_tpu.parallel import distributed as D
from distributed_kfac_pytorch_tpu.training import (
    datasets,
    engine,
    optimizers,
    utils,
)

from distributed_kfac_pytorch_tpu.utils import enable_compilation_cache


class _MLP:
    """BN-free MLP classifier over flattened images — the workload
    family K-FAC's advantage is cleanest on (no batch-stat lag under
    large preconditioned steps; the original K-FAC papers' domain)."""

    @staticmethod
    def build():
        import flax.linen as nn

        class MLP(nn.Module):
            @nn.compact
            def __call__(self, x, train=True):
                x = x.reshape(x.shape[0], -1)
                x = nn.Dense(512)(x)
                x = nn.relu(x)
                x = nn.Dense(256)(x)
                x = nn.relu(x)
                return nn.Dense(10)(x)
        return MLP()


def run_one(use_kfac: bool, args, data):
    (train_x, train_y), (val_x, val_y) = data
    model = (_MLP.build() if args.model == 'mlp'
             else cifar_resnet.get_model(
                 args.model, bn_momentum=args.bn_momentum))
    cfg = optimizers.OptimConfig(
        base_lr=args.base_lr, momentum=0.9, weight_decay=5e-4,
        warmup_epochs=args.warmup, lr_decay=args.lr_decay,
        workers=1,
        kfac_inv_update_freq=args.kfac_update_freq if use_kfac else 0,
        inv_pipeline_chunks=args.inv_pipeline_chunks,
        deferred_factor_reduction=args.deferred_factor_reduction,
        inv_staleness=args.inv_staleness,
        inv_lowrank_rank=args.inv_lowrank_rank,
        inv_lowrank_dim_threshold=args.inv_lowrank_dim_threshold,
        kfac_cov_update_freq=1, damping=args.damping,
        kl_clip=0.001, eigh_method=args.eigh_method,
        eigh_polish_iters=args.eigh_polish_iters,
        factor_batch_fraction=args.factor_batch_fraction,
        damping_alpha=args.damping_alpha,
        damping_schedule=args.damping_decay,
        kfac_update_freq_alpha=args.kfac_freq_alpha,
        kfac_update_freq_schedule=args.kfac_freq_decay)
    tx, lr_schedule, kfac, kfac_sched = optimizers.get_optimizer(
        model, cfg)

    x0 = jnp.zeros((2, 32, 32, 3), jnp.float32)
    if kfac is not None:
        variables, _ = kfac.init(jax.random.PRNGKey(args.seed), x0)
    else:
        variables = model.init(jax.random.PRNGKey(args.seed), x0)
    params = variables['params']
    extra = ({'batch_stats': variables['batch_stats']}
             if 'batch_stats' in variables else {})
    mutable = tuple(extra)
    mesh = D.make_kfac_mesh()
    opt_state = tx.init(params)

    def loss_fn(out, batch):
        return utils.label_smooth_loss(out, batch[1], 0.0)

    def metrics_fn(out, batch):
        return {'acc': utils.accuracy(out, batch[1])}

    if kfac is not None:
        dkfac = D.DistributedKFAC(kfac, mesh, params)
        kstate = dkfac.init_state(params)
        step_fn = dkfac.build_train_step(
            loss_fn, tx, metrics_fn=metrics_fn, mutable_cols=mutable)
    else:
        dkfac, kstate = None, None
        step_fn = engine.build_sgd_train_step(
            model, loss_fn, tx, mesh, metrics_fn=metrics_fn,
            mutable_cols=mutable)
    eval_step = engine.make_eval_step(
        model, loss_fn, mesh, model_args_fn=lambda b: (b[0], False))

    state = engine.TrainState(params=params, opt_state=opt_state,
                              kfac_state=kstate, extra_vars=extra)
    bn_steps = (engine.make_precise_bn_steps(model, mesh)
                if args.precise_bn > 0 and extra else None)
    curve = []
    t0 = time.perf_counter()
    for epoch in range(args.epochs):
        lr = lr_schedule(epoch)
        state.opt_state = optimizers.set_lr(state.opt_state, lr)
        hyper = {'lr': lr,
                 **(kfac_sched.params() if kfac_sched else {})}
        batches = datasets.epoch_batches(
            train_x, train_y, args.batch_size, seed=args.seed,
            epoch=epoch, augment=True)
        tm = engine.train_epoch(step_fn, state, batches, hyper)
        if bn_steps is not None:
            # Precise-BN: re-estimate running stats at the current
            # weights over a few forward-only training batches; used
            # for EVAL ONLY (training keeps its own EWMA state so the
            # optimization trajectory is untouched by the flag).
            import itertools
            recal = engine.precise_bn_recalibrate(
                model, state.params, state.extra_vars,
                itertools.islice(
                    datasets.epoch_batches(
                        train_x, train_y, args.batch_size,
                        seed=args.seed, epoch=10_000 + epoch,
                        augment=True),
                    args.precise_bn),
                mesh, steps=bn_steps)
            train_extra, state.extra_vars = state.extra_vars, recal
        vm = engine.evaluate(
            eval_step, state,
            datasets.epoch_batches(val_x, val_y, args.batch_size,
                                   shuffle=False, augment=False))
        if bn_steps is not None:
            state.extra_vars = train_extra
        if kfac_sched:
            kfac_sched.step(epoch + 1)
        curve.append({'epoch': epoch,
                      'train_loss': round(float(tm['loss']), 4),
                      'train_acc': round(float(tm['acc']), 4),
                      'val_loss': round(float(vm['loss']), 4),
                      'val_acc': round(float(vm['acc']), 4)})
        print(f'[{"kfac" if use_kfac else "sgd"}] {curve[-1]}',
              flush=True)
    wall = time.perf_counter() - t0
    return curve, wall


def epochs_to_target(curve, target):
    for row in curve:
        if row['val_acc'] >= target:
            return row['epoch'] + 1
    return None


def run_sweep(args, data):
    """Both-tuned comparison: LR-sweep each optimizer, pick each one's
    best configuration, compare epochs-to-target at a common target.

    This is the round-2 verdict's Missing #2 ask (and the papers'
    framing, BASELINE.md): K-FAC vs *LR-swept* SGD, both tuned, fixed
    seeds, on a non-separable task (--label-noise) — an honest
    quantitative epochs-to-accuracy table instead of a single-LR
    anecdote.
    """
    sweep: dict[str, dict] = {'kfac': {}, 'sgd': {}}
    damp_grid = args.kfac_damping_grid or [args.damping]
    bnm_grid = args.kfac_bn_momentum_grid or [args.bn_momentum]
    for use_kfac in (True, False):
        name = 'kfac' if use_kfac else 'sgd'
        for lr in args.lr_grid:
            for damping in (damp_grid if use_kfac else [args.damping]):
                for bnm in (bnm_grid if use_kfac
                            else [args.bn_momentum]):
                    a = argparse.Namespace(**vars(args))
                    a.base_lr = lr
                    a.damping = damping
                    a.bn_momentum = bnm
                    key = f'lr={lr}'
                    if use_kfac:
                        key += f',damping={damping}'
                        if len(bnm_grid) > 1:
                            key += f',bn_momentum={bnm}'
                    print(f'=== {name} {key} ===', flush=True)
                    curve, wall = run_one(use_kfac, a, data)
                    sweep[name][key] = {
                        'curve': curve, 'wall_s': round(wall, 1),
                        'best_val_acc': max(r['val_acc']
                                            for r in curve)}

    # Common target: the weaker optimizer's best achievable accuracy
    # (x0.995 tolerance) — both optimizers can reach it, so
    # epochs-to-target is defined for the comparison.
    best_per_opt = {n: max(e['best_val_acc'] for e in runs.values())
                    for n, runs in sweep.items()}
    target = min(best_per_opt.values()) * 0.995
    chosen = {}
    for name, runs in sweep.items():
        scored = []
        for key, entry in runs.items():
            ett = epochs_to_target(entry['curve'], target)
            entry['epochs_to_target'] = ett
            scored.append((ett if ett is not None else 10 ** 9,
                           -entry['best_val_acc'], key))
        scored.sort()
        best = scored[0][2]
        chosen[name] = {'config': best,
                        'epochs_to_target':
                            runs[best]['epochs_to_target'],
                        'best_val_acc': runs[best]['best_val_acc'],
                        'wall_s': runs[best]['wall_s']}

    result = {
        'study': 'both_tuned_lr_sweep',
        'workload': f'{args.model}_cifar_'
                    f'{"synthetic" if args.data_dir is None else "real"}',
        'backend': jax.default_backend(),
        'devices': jax.device_count(),
        'epochs': args.epochs, 'batch_size': args.batch_size,
        'label_noise': args.label_noise,
        'lr_grid': args.lr_grid,
        'kfac_damping_grid': damp_grid,
        'kfac_bn_momentum_grid': bnm_grid,
        'precise_bn': args.precise_bn,
        'sgd_damping_na': 'damping applies to K-FAC only',
        'target_val_acc': round(target, 4),
        'chosen': chosen,
        'sweep': {n: {key: {k: v for k, v in e.items() if k != 'curve'}
                      for key, e in runs.items()}
                  for n, runs in sweep.items()},
        'curves': {n: {key: e['curve'] for key, e in runs.items()}
                   for n, runs in sweep.items()},
    }
    with open(args.out, 'w') as f:
        json.dump(result, f, indent=1)
    summary = {k: result[k] for k in
               ('study', 'workload', 'label_noise', 'target_val_acc',
                'chosen')}
    print(json.dumps(summary))


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument('--model', default='resnet32')
    p.add_argument('--epochs', type=int, default=30)
    p.add_argument('--batch-size', type=int, default=256)
    p.add_argument('--base-lr', type=float, default=0.1)
    p.add_argument('--warmup', type=float, default=2)
    p.add_argument('--lr-decay', type=int, nargs='+', default=[15, 23])
    p.add_argument('--kfac-update-freq', type=int, default=10)
    p.add_argument('--inv-pipeline-chunks', type=int, default=1,
                   help='pipelined inverse firing (r9): fire the '
                        'inverse work in K cost-balanced chunks across '
                        'each cadence window — the end-of-window drift '
                        'A/B arm for the step-time-uniformity knob '
                        '(chunked firings see fresher factors but '
                        'layer inverses are no longer simultaneous)')
    p.add_argument('--deferred-factor-reduction', action='store_true',
                   help='r14 deferred window-boundary factor '
                        'reduction (exact by EMA linearity; the A/B '
                        'arm only checks the composed schedule)')
    p.add_argument('--inv-staleness', type=int, default=0,
                   choices=[0, 1],
                   help='r14 one-window-stale off-critical-path '
                        'inverses — the staleness convergence A/B arm '
                        '(PERF.md r14 decision rule)')
    p.add_argument('--inv-lowrank-rank', type=int, default=0,
                   help='r19 randomized truncated-eigendecomposition '
                        'rank for dims >= --inv-lowrank-dim-threshold '
                        '(0 = exact dispatch) — the low-rank '
                        'convergence A/B arm (PERF.md r19)')
    p.add_argument('--inv-lowrank-dim-threshold', type=int,
                   default=2048)
    p.add_argument('--damping', type=float, default=0.003)
    # KFACParamScheduler knobs (the round-3 analysis prescribed a
    # damping/update-freq schedule for the conv/BN study; VERDICT r3 #6).
    p.add_argument('--damping-alpha', type=float, default=1.0)
    p.add_argument('--damping-decay', type=int, nargs='+', default=[])
    p.add_argument('--kfac-freq-alpha', type=float, default=1.0)
    p.add_argument('--kfac-freq-decay', type=int, nargs='+', default=[])
    p.add_argument('--precise-bn', type=int, default=0,
                   help='re-estimate BN running statistics over this '
                        'many forward-only train batches before each '
                        'eval (precise-BN; 0 = off). Eval-only: the '
                        'training EWMA state is untouched.')
    p.add_argument('--bn-momentum', type=float, default=0.9,
                   help='BatchNorm running-stat EWMA momentum (flax '
                        'convention; 0.9 = torch momentum 0.1, the '
                        'reference default)')
    p.add_argument('--kfac-bn-momentum-grid', type=float, nargs='+',
                   default=None,
                   help='sweep mode: BN momentum values for the K-FAC '
                        'leg (the stats-lag timescale is a K-FAC-'
                        'specific knob; default: just --bn-momentum)')
    p.add_argument('--eigh-method', default='auto')
    p.add_argument('--eigh-polish-iters', type=int, default=8)
    p.add_argument('--factor-batch-fraction', type=float, default=1.0,
                   help='thin the factor statistics to this fraction of '
                        'the batch (convergence A/B for the opt-in '
                        'factor_batch_fraction knob)')
    p.add_argument('--label-noise', type=float, default=0.0,
                   help='fraction of train labels flipped (fixed seed): '
                        'makes the synthetic task non-separable so the '
                        'accuracy target is meaningful')
    p.add_argument('--only', default=None, choices=['kfac', 'sgd'],
                   help='run a single optimizer (hyperparameter sweeps)')
    p.add_argument('--sweep', action='store_true',
                   help='LR-sweep BOTH optimizers over --lr-grid (both '
                        'tuned — the fair epochs-to-target comparison '
                        'the papers make) and record per-optimizer '
                        'bests plus the full sweep table')
    p.add_argument('--lr-grid', type=float, nargs='+',
                   default=[0.003, 0.01, 0.03, 0.1])
    p.add_argument('--kfac-damping-grid', type=float, nargs='+',
                   default=None,
                   help='sweep mode: damping values for the K-FAC leg '
                        '(its step-size-control knob, swept like SGD '
                        'sweeps lr; default: just --damping)')
    p.add_argument('--synthetic-size', type=int, default=4096)
    p.add_argument('--data-dir', default=None)
    p.add_argument('--seed', type=int, default=42)
    p.add_argument('--out', default='CONVERGENCE.json')
    p.add_argument('--platform', default=None, choices=['cpu', 'tpu'],
                   help='force a JAX platform (before first backend '
                        'use); cpu also simulates an 8-device mesh')
    args = p.parse_args(argv)

    if args.platform:
        jax.config.update('jax_platforms', args.platform)
        if args.platform == 'cpu':
            from distributed_kfac_pytorch_tpu.utils import (
                raise_cpu_collective_timeouts)
            raise_cpu_collective_timeouts()
            jax.config.update('jax_num_cpu_devices', 8)
    # Persistent compile cache, AFTER platform resolution (the helper
    # itself refuses on a multi-device CPU configuration — the warm-read
    # segfault workaround, see utils.enable_compilation_cache).
    enable_compilation_cache()

    data = datasets.get_cifar(args.data_dir,
                              synthetic_size=args.synthetic_size)
    if args.label_noise > 0:
        (tx_, ty_), val = data
        rng = np.random.default_rng(123)
        flip = rng.random(len(ty_)) < args.label_noise
        noisy = rng.integers(0, int(ty_.max()) + 1,
                             len(ty_)).astype(ty_.dtype)
        ty_ = np.where(flip, noisy, ty_)
        data = ((tx_, ty_), val)
    print(f'backend={jax.default_backend()} devices={jax.device_count()} '
          f'train={data[0][0].shape} val={data[1][0].shape} '
          f'label_noise={args.label_noise}', flush=True)

    if args.sweep:
        return run_sweep(args, data)

    results_blocks = {}
    if args.only in (None, 'kfac'):
        kfac_curve, kfac_wall = run_one(True, args, data)
        results_blocks['kfac'] = (kfac_curve, kfac_wall)
    if args.only in (None, 'sgd'):
        sgd_curve, sgd_wall = run_one(False, args, data)
        results_blocks['sgd'] = (sgd_curve, sgd_wall)

    bests = {k: max(r['val_acc'] for r in c)
             for k, (c, _) in results_blocks.items()}
    # Epochs-to-target at the best accuracy EVERY ran optimizer reaches
    # (the papers' time-to-accuracy framing, BASELINE.md).
    target = min(bests.values()) * 0.995
    result = {
        'workload': f'{args.model}_cifar_'
                    f'{"synthetic" if args.data_dir is None else "real"}',
        'backend': jax.default_backend(),
        'devices': jax.device_count(),
        'epochs': args.epochs,
        'batch_size': args.batch_size,
        'label_noise': args.label_noise,
        'damping': args.damping,
        'inv_pipeline_chunks': args.inv_pipeline_chunks,
        'deferred_factor_reduction': args.deferred_factor_reduction,
        'inv_staleness': args.inv_staleness,
        'target_val_acc': round(target, 4),
    }
    if args.only:
        # Single-optimizer sweep artifact: emit ONLY the ran block so
        # the file can never masquerade as a two-optimizer comparison.
        result['only'] = args.only
    for k, (curve, wall) in results_blocks.items():
        result[k] = {'best_val_acc': bests[k],
                     'epochs_to_target': epochs_to_target(curve, target),
                     'wall_s': round(wall, 1),
                     'curve': curve}
    with open(args.out, 'w') as f:
        json.dump(result, f, indent=1)
    summary = {k: v for k, v in result.items()
               if k not in ('kfac', 'sgd')}
    for k in results_blocks:
        summary[f'{k}_best'] = bests[k]
        summary[f'{k}_epochs_to_target'] = result[k]['epochs_to_target']
    print(json.dumps(summary))


if __name__ == '__main__':
    main()
