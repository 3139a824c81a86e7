"""Language-model training with distributed K-FAC: LSTM or Transformer.

Working TPU-native counterpart of the reference's WIP LM entry point
(examples/torch_language_model.py — broken as shipped: SURVEY.md §8 notes
the lr and factory-unpacking bugs at :253,:277). Four architectures:

- ``--arch lstm``: the K-FAC-friendly LSTM LM (reference rnn_utils/lstm.py
  + kfac/modules/lstm.py), BPTT windows (``--bptt 35``,
  torch_language_model.py:52), K-FAC on the LSTM-cell Linears with
  embedding/decoder skipped by default (torch_language_model.py:102-104).
  Hidden state is reset per window (the reference carries it detached;
  with windows shuffled per epoch the difference is negligible).
- ``--arch transformer``: decoder-only Transformer with Linear-layer
  K-FAC on every projection (BASELINE config 4), and optional
  ``--seq-parallel N`` ring-attention context parallelism over the mesh
  (no reference analogue — SURVEY.md §5: long-context machinery absent).
- ``--arch mla_moe``: the DeepSeek-V3-shaped decoder of
  ``models/mla_moe_lm.py`` (latent attention, sigmoid-routed stacked
  experts beside shared ones, SwiGLU, RMSNorm, RoPE, untied head) at
  ``--mla-moe-size``; K-FAC on every projection, the router and every
  expert matrix (layer kind ``experts``), the head left to SGD by
  default.
- ``--arch looped``: the looped (depth-recurrent) decoder of
  ``models/looped_lm.py`` (Ouro's: one stack of layers run
  ``total_ut_steps`` times with the same weights, an exit after every
  pass, a gate that spreads the loss over the exits) at
  ``--looped-size``; every projection is one K-FAC layer called once a
  pass (``num_calls``), the head left to SGD by default. The step hands
  the model its targets and takes the mean of the objective it returns.

Data: whitespace-tokenized train.txt/valid.txt under --data-dir
(PTB/WikiText layout), else a synthetic Markov corpus (offline default).
"""

from __future__ import annotations

import argparse
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

from jax.sharding import PartitionSpec as P

from distributed_kfac_pytorch_tpu import autotune
from distributed_kfac_pytorch_tpu import elastic as elastic_lib
from distributed_kfac_pytorch_tpu import fp16 as fp16_lib
from distributed_kfac_pytorch_tpu import launch
from distributed_kfac_pytorch_tpu import observability as obs
from distributed_kfac_pytorch_tpu import resilience as resil
from distributed_kfac_pytorch_tpu import multislice
from distributed_kfac_pytorch_tpu.models import (
    looped_lm,
    lstm_lm,
    mla_moe_lm,
    transformer_lm,
)
from distributed_kfac_pytorch_tpu.parallel import distributed as D
from distributed_kfac_pytorch_tpu.parallel import sequence as seq
from distributed_kfac_pytorch_tpu.training import (
    checkpoint as ckpt_lib,
    datasets,
    engine,
    optimizers,
)

from distributed_kfac_pytorch_tpu.utils import enable_compilation_cache

enable_compilation_cache()  # JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        description='LM + distributed K-FAC (TPU-native)')
    p.add_argument('--data-dir', default=None,
                   help='dir with train.txt/valid.txt (synthetic if '
                        'absent)')
    p.add_argument('--log-dir', default='./logs/lm')
    p.add_argument('--checkpoint-dir', default='./checkpoints/lm')
    p.add_argument('--checkpoint-freq', type=int, default=5)
    p.add_argument('--arch', default='lstm',
                   choices=['lstm', 'transformer', 'mla_moe', 'looped'])
    p.add_argument('--mla-moe-size', default='tiny',
                   choices=['tiny', 'kanana2'],
                   help="--arch mla_moe: mla_moe_lm.get_model's named "
                        "shape ('kanana2': kanana-2-30b-a3b's widths at "
                        "one chip's share; needs a 16 GB chip)")
    p.add_argument('--looped-size', default='tiny',
                   choices=['tiny', 'ouro_2p6b'],
                   help="--arch looped: looped_lm.get_model's named shape "
                        "('ouro_2p6b': Ouro-2.6B as published, 48 layers "
                        "run 4 times; more than one 16 GB chip holds)")
    # Model size (reference torch_language_model.py:41-50).
    p.add_argument('--emsize', type=int, default=650)
    p.add_argument('--nhid', type=int, default=650)
    p.add_argument('--nlayers', type=int, default=2)
    p.add_argument('--nheads', type=int, default=10,
                   help='attention heads (transformer)')
    p.add_argument('--dropout', type=float, default=0.5)
    p.add_argument('--tied', action='store_true')
    p.add_argument('--bptt', type=int, default=35,
                   help='sequence window (reference :52)')
    p.add_argument('--batch-size', type=int, default=20)
    p.add_argument('--epochs', type=int, default=40)
    p.add_argument('--base-lr', type=float, default=1.0)
    p.add_argument('--lr-decay', type=int, nargs='+', default=[20, 30])
    p.add_argument('--warmup-epochs', type=float, default=1)
    p.add_argument('--momentum', type=float, default=0.9)
    p.add_argument('--wd', type=float, default=0.0)
    p.add_argument('--grad-clip', type=float, default=0.25,
                   help='global-norm clip (reference :205)')
    p.add_argument('--seed', type=int, default=42)
    p.add_argument('--no-resume', action='store_true')
    p.add_argument('--seq-parallel', type=int, default=1,
                   help='sequence-parallel degree (transformer only)')
    p.add_argument('--num-slices', type=int,
                   default=int(os.environ.get('KFAC_NUM_SLICES', 1)),
                   help='multi-slice mesh: outer kfac_slice axis over '
                        'N contiguous device slabs (r20). 1 (default) '
                        '= the flat mesh, bit-identical to pre-r20 '
                        'runs. Defaults from KFAC_NUM_SLICES (set by '
                        'the supervisor on slice-failure failover)')
    p.add_argument('--attn-block-size', type=int, default=None,
                   help='single-device memory-efficient attention: fold '
                        'K/V in blocks of this many tokens (O(seq*block) '
                        'live logits instead of O(seq^2)); transformer '
                        'only, ignored under --seq-parallel')
    # K-FAC (reference torch_language_model.py:74-104).
    p.add_argument('--kfac-update-freq', type=int, default=10,
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
    p.add_argument('--kfac-cov-update-freq', type=int, default=1)
    p.add_argument('--kfac-approx', default='expand',
                   choices=['expand', 'reduce'],
                   help='weight-sharing Kronecker approximation '
                        '(r13, arXiv:2311.00636): expand (default) '
                        'flattens the sequence axis into covariance '
                        'rows — the bit-identical historical path; '
                        'reduce averages activations / sums grads '
                        'over it first — a factor-seq cheaper factor '
                        'update on every attention/MLP Dense, with '
                        'tied in/out embeddings sharing one factor '
                        'pair (see README "Transformer & ViT '
                        'preconditioning")')
    p.add_argument('--inverse-method', default='auto',
                   choices=['auto', 'eigen', 'cholesky', 'newton'],
                   help='auto = per-dim dispatch: eigen below the '
                        'measured cutoff, cholesky above (the TPU '
                        'default that is fast at LM factor dims)')
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
    p.add_argument('--damping', type=float, default=0.003)
    p.add_argument('--kl-clip', type=float, default=0.001)
    p.add_argument('--skip-layers', nargs='+', default=None,
                   help="default: ['embed', 'decoder'] for lstm (the "
                        'reference preconditions LSTM cells only), [] '
                        'for transformer')
    p.add_argument('--comm-method', default='comm-opt',
                   choices=sorted(optimizers.COMM_METHODS))
    p.add_argument('--grad-worker-fraction', type=float, default=0.25)
    p.add_argument('--symmetry-aware-comm', action='store_true',
                   help='triu-packed factor allreduce (halved bytes)')
    p.add_argument('--bf16-inverses', action='store_true',
                   help='bf16 inverse storage (decompositions stay fp32) '
                        '— at Transformer-XL scale the fp32 inverse '
                        'stacks alone are ~3.2 GB (PERF.md round 5)')
    p.add_argument('--bf16-factors', action='store_true',
                   help='bf16 factor storage/averaging + bf16 covariance '
                        'matmul inputs (matmuls accumulate fp32); the '
                        'reference fp16 factor mode')
    p.add_argument('--bf16-precond', action='store_true',
                   help='bf16 precondition-contraction operands (fp32 '
                        'accumulation; KFAC precond_compute_dtype) — '
                        'the every-step inverse-times-grad matmuls on '
                        'the MXU bf16 path; with --bf16-inverses the '
                        'stored inverses are consumed resident (r6)')
    p.add_argument('--fp16', action='store_true',
                   help='fp16 model compute with dynamic loss scaling + '
                        'overflow-skip (GradScaler parity, reference '
                        'engine.py:38-41,75-80; the reference LM example '
                        'lacks AMP — this completes the CLI surface). On '
                        'TPU, bf16 is the native half mode and needs no '
                        'scaler.')
    obs.cli.add_observability_args(p)
    resil.cli.add_resilience_args(p)
    autotune.cli.add_autotune_args(p)
    return p.parse_args(argv)


def build_model(args, vocab_size, seq_axis=None, dtype=None):
    if dtype is None:
        dtype = jnp.float16 if args.fp16 else None
    if args.arch == 'lstm':
        return lstm_lm.LSTMLanguageModel(
            vocab_size=vocab_size, embedding_dim=args.emsize,
            hidden_dim=args.nhid, num_layers=args.nlayers,
            dropout=args.dropout, tie_weights=args.tied, dtype=dtype)
    if args.arch == 'mla_moe':
        return mla_moe_lm.get_model(vocab_size, args.mla_moe_size,
                                    dtype=dtype)
    if args.arch == 'looped':
        return looped_lm.get_model(vocab_size, args.looped_size,
                                   dtype=dtype)
    return transformer_lm.TransformerLM(
        vocab_size=vocab_size, d_model=args.emsize,
        num_layers=args.nlayers, num_heads=args.nheads,
        max_len=max(args.bptt, 16), dropout=args.dropout,
        tie_weights=args.tied, seq_axis=seq_axis,
        attn_block_size=(args.attn_block_size
                         if seq_axis is None else None),
        dtype=dtype)


def main(argv=None):
    args = parse_args(argv)
    # Preemption handling installs FIRST: a SIGTERM during bring-up
    # should still drain gracefully (r8).
    preemption = resil.cli.install_preemption(args)
    # Multi-host init BEFORE any backend use (single-host no-op; see
    # launch.initialize_multihost / scripts/launch_tpu_pod.sh).
    info = launch.initialize_multihost()
    is_main = info['process_index'] == 0
    n_dev = jax.device_count()
    sp = args.seq_parallel
    if sp > 1 and args.arch != 'transformer':
        raise SystemExit('--seq-parallel requires --arch transformer')
    if args.attn_block_size:
        if args.arch != 'transformer':
            raise SystemExit('--attn-block-size requires '
                             '--arch transformer')
        # Under --seq-parallel the knob is dropped (ring folds per
        # device already); bptt <= block degenerates to exact
        # monolithic attention — both fine. Only a true partial-block
        # split is rejected.
        if (sp == 1 and args.bptt > args.attn_block_size
                and args.bptt % args.attn_block_size):
            raise SystemExit(
                f'--bptt {args.bptt} must be divisible by '
                f'--attn-block-size {args.attn_block_size} '
                '(e.g. --bptt 1024 --attn-block-size 256)')
    if is_main:
        print(f'devices: {n_dev} global / {info["local_devices"]} local '
              f'x {info["process_count"]} processes '
              f'({jax.default_backend()}), seq_parallel={sp}')

    train_ids, val_ids, vocab_size = datasets.get_lm_corpus(args.data_dir)
    if is_main:
        print(f'corpus: {len(train_ids)} train / {len(val_ids)} val '
              f'tokens, vocab {vocab_size}')

    if args.skip_layers is None:
        args.skip_layers = {'lstm': ['embed', 'decoder'],
                            'mla_moe': ['head'],
                            'looped': ['head']}.get(args.arch, [])

    seq_axis = seq.SEQ_AXIS if sp > 1 else None
    model = build_model(args, vocab_size, seq_axis=seq_axis)

    cfg = optimizers.OptimConfig(
        base_lr=args.base_lr, momentum=args.momentum,
        weight_decay=args.wd, warmup_epochs=args.warmup_epochs,
        lr_decay=args.lr_decay, workers=1,
        kfac_inv_update_freq=args.kfac_update_freq,
        kfac_cov_update_freq=args.kfac_cov_update_freq,
        inv_pipeline_chunks=args.inv_pipeline_chunks,
        deferred_factor_reduction=args.deferred_factor_reduction,
        hierarchical_reduce=args.hierarchical_reduce,
        inv_staleness=args.inv_staleness,
        kfac_approx=args.kfac_approx,
        damping=args.damping, factor_decay=args.stat_decay,
        kl_clip=args.kl_clip, inverse_method=args.inverse_method,
        inv_lowrank_rank=args.inv_lowrank_rank,
        inv_lowrank_dim_threshold=args.inv_lowrank_dim_threshold,
        eigh_method=args.eigh_method,
        eigh_polish_iters=args.eigh_polish_iters,
        factor_batch_fraction=args.factor_batch_fraction,
        skip_layers=args.skip_layers, comm_method=args.comm_method,
        grad_worker_fraction=args.grad_worker_fraction,
        symmetry_aware_comm=args.symmetry_aware_comm,
        bf16_factors=args.bf16_factors,
        bf16_inverses=args.bf16_inverses,
        bf16_precond=args.bf16_precond,
        kfac_metrics=bool(args.kfac_metrics),
        # --selfheal forces the guard on: the ladder's rung 1 IS the
        # on-device skip-window, and its nonfinite_skips counter is the
        # ladder's primary detection signal (README "Self-healing").
        nonfinite_guard=(obs.cli.wants_guard(args)
                         or resil.cli.wants_selfheal_guard(args)))
    # Tuned-config overlay (fail-closed): the queued apply/fallback
    # events land in the metrics stream once the sink exists below.
    cfg, tune_events = autotune.cli.maybe_apply_tuned(args, cfg)
    cadence_policy = autotune.cli.make_cadence_policy(args)
    tx, lr_schedule, kfac, kfac_sched = optimizers.get_optimizer(model, cfg)
    if kfac is None:
        # --kfac-update-freq 0: plain SGD baseline (reference
        # optimizers.py:28) — same fallback the CNN CLIs expose.
        if sp > 1:
            raise SystemExit('--seq-parallel requires the K-FAC step '
                             '(--kfac-update-freq > 0)')
        if args.kfac_metrics:
            raise SystemExit('--kfac-metrics requires the K-FAC step '
                             '(--kfac-update-freq > 0)')
        if args.fp16:
            raise SystemExit('--fp16 requires the K-FAC step '
                             '(--kfac-update-freq > 0); the SGD baseline '
                             'path does not wire the loss scaler.')
        if cadence_policy is not None:
            raise SystemExit('--cadence-backoff requires the K-FAC '
                             'step (--kfac-update-freq > 0)')
    metrics_sink = obs.cli.make_metrics_sink(
        args, info, meta={'cli': 'train_language_model',
                          'arch': args.arch,
                          'batch_size': args.batch_size,
                          'bptt': args.bptt,
                          'devices': n_dev,
                          'metrics_interval': args.metrics_interval})
    autotune.emit_events(metrics_sink, tune_events)
    shard_meta = {'cli': 'train_language_model'}
    if (args.num_slices > 1
            and info['process_count'] % args.num_slices == 0):
        # Stamp the slice id into the shard meta so the report's
        # straggler section can aggregate per-slice skew rows (r20).
        shard_meta['slice'] = multislice.slice_of_rank(
            info['process_index'], info['process_count'],
            args.num_slices)
    rank_sink = obs.cli.make_rank_shard_sink(args, info, meta=shard_meta)
    # r17 liveness lease (per rank; armed by --heartbeat-dir or the
    # supervisor's KFAC_HEARTBEAT_DIR — None otherwise, and the engine
    # path is byte-identical without it).
    heartbeat = resil.cli.make_heartbeat(args, info)
    if args.grad_clip:
        tx = optax.chain(optax.clip_by_global_norm(args.grad_clip), tx)

    ids0 = jnp.zeros((2, args.bptt), jnp.int32)
    twin = (build_model(args, vocab_size, seq_axis=None)
            if seq_axis else None)
    if kfac is not None:
        # [0]: kfac.init also returns a single-chip K-FAC state. This
        # path builds its own layout (DistributedKFAC.init_state); a
        # name bound to the other one would keep a second copy of every
        # factor and inverse on the device for the whole run.
        variables = kfac.init(jax.random.PRNGKey(args.seed), ids0,
                              train=False, init_model=twin)[0]
        # Registry provenance (r13): the per-layer resolved approx map
        # rides as a meta record so the recorded run says which layers
        # actually ran reduce/tied (asserted by sharing_smoke.sh).
        obs.cli.emit_layer_meta(metrics_sink, kfac)
    else:
        variables = model.init(jax.random.PRNGKey(args.seed), ids0,
                               train=False)
    params = variables['params']
    del variables  # or the init-time copy outlives replicate_on_mesh

    # num_slices == 1 returns the flat make_kfac_mesh mesh (the
    # --num-slices 1 bit-identity guarantee); > 1 adds the outer
    # kfac_slice axis over contiguous device slabs.
    mesh = multislice.make_multislice_mesh(
        num_slices=args.num_slices,
        comm_method=optimizers.COMM_METHODS[args.comm_method],
        grad_worker_fraction=args.grad_worker_fraction, seq_parallel=sp)
    # Commit params replicated on the mesh up front: the resume path
    # builds its restore template (like=) from live state, and an
    # uncommitted single-device init would restore a pod checkpoint
    # onto one device (caught by the r8 multihost kill test).
    params = launch.replicate_on_mesh(mesh, params)
    if kfac is not None:
        dkfac = D.DistributedKFAC(kfac, mesh, params)
        kstate = dkfac.init_state(params)
    else:
        dkfac, kstate = None, None
    opt_state = tx.init(params)

    def logits_of(out):
        return out[0] if args.arch == 'lstm' else out

    def loss_fn(out, batch):
        if args.arch == 'looped':
            # Handed the targets, the looped decoder returns its own
            # objective a token (it needs the head at every exit).
            return out.mean()
        return optax.softmax_cross_entropy_with_integer_labels(
            logits_of(out), batch[1]).mean()

    t_local = args.bptt // sp
    data_axes = (dkfac.data_axes if dkfac is not None
                 else tuple(a for a in D.KFAC_AXES
                            if a in mesh.axis_names))

    def model_kwargs_fn(batch):
        # Per-device dropout key: fold the step key with the device's
        # linear mesh index so masks decorrelate across shards.
        idx = jax.lax.axis_index(data_axes[0])
        for ax in data_axes[1:]:
            idx = idx * jax.lax.psum(1, ax) + jax.lax.axis_index(ax)
        kwargs = {'train': True,
                  'rngs': {'dropout': jax.random.fold_in(batch[2], idx)}}
        if seq_axis:
            kwargs['pos_offset'] = (
                jax.lax.axis_index(seq.SEQ_AXIS) * t_local)
        if args.arch == 'looped':
            kwargs['targets'] = batch[1]
        return kwargs

    batch_axes = multislice.batch_axes(mesh)
    data_spec = (P(batch_axes, seq.SEQ_AXIS) if seq_axis
                 else P(batch_axes))
    if dkfac is not None:
        step_fn = dkfac.build_train_step(
            loss_fn, tx, model_kwargs_fn=model_kwargs_fn,
            batch_spec=(data_spec, data_spec, P()),
            loss_scale='dynamic' if args.fp16 else None)
    else:  # --kfac-update-freq 0: plain SGD (reference optimizers.py:28)
        step_fn = engine.build_sgd_train_step(
            model, loss_fn, tx, mesh,
            model_kwargs_fn=model_kwargs_fn,
            batch_spec=(data_spec, data_spec, P()),
            metrics_fn=lambda out, b: {})

    def eval_loss(out, batch):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits_of(out), batch[1]).mean()

    eval_step = engine.make_eval_step(
        build_model(args, vocab_size, seq_axis=None), eval_loss, None,
        model_args_fn=lambda b: (b[0],), model_kwargs={'train': False},
        metrics_fn=lambda o, b: {})
    # Straggler barrier probe: shards requested (or the cadence-backoff
    # policy armed) + a K-FAC step (the probe reduces over the K-FAC
    # data axes).
    barrier_probe = (dkfac.build_barrier_probe()
                     if (rank_sink is not None
                         or cadence_policy is not None)
                     and dkfac is not None
                     else None)

    state = engine.TrainState(params=params, opt_state=opt_state,
                              kfac_state=kstate,
                              extra_vars=(
                                  {'loss_scale':
                                   fp16_lib.init_loss_scale()}
                                  if args.fp16 else {}))
    if dkfac is None and args.checkpoint_dir == './checkpoints/lm':
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
        # Restore the host step counter: the engine's static cadence is
        # driven by it, so it must stay in phase with kstate['step'].
        state.step = int(restored['scalars']['step'])
        if kfac_sched:
            kfac_sched.step(start_epoch)
    step_ckpt = resil.cli.make_step_checkpointer(
        args, step_mgr, bundle_fn, preemption=preemption,
        sink=metrics_sink, start_step=state.step)
    # r16 self-healing ladder (None when --selfheal is off — the
    # engine then runs the byte-identical pre-r16 path).
    selfheal_ctl = resil.cli.make_selfheal(
        args, kfac=kfac, params=params, sink=metrics_sink)

    def batches(epoch, skip=0):
        # skip= is the mid-epoch resume offset; the per-step dropout
        # keys fold the ABSOLUTE window index so the replayed tail is
        # bit-identical to the uninterrupted epoch's.
        root = jax.random.PRNGKey(args.seed * 1000 + epoch)
        for i, (x, y) in enumerate(datasets.bptt_batches(
                train_ids, args.batch_size, args.bptt,
                shuffle_offset=True, seed=args.seed, epoch=epoch,
                skip_batches=skip), start=skip):
            yield x, y, jax.random.fold_in(root, i)

    writer = engine.TensorBoardWriter(args.log_dir) if is_main else None
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
            raw = resil.faults.poison_at(batches(epoch, skip),
                                         step_ckpt.plan,
                                         first_step=state.step)
            try:
                with obs.cli.profile_epoch(args, info, epoch,
                                           start_epoch):
                    train_m = engine.train_epoch(
                        step_fn, state,
                        launch.global_batches(
                            mesh, raw,
                            batch_spec=(data_spec, data_spec, P())),
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
            val_m = engine.evaluate(
                eval_step, state,
                launch.global_batches(
                    mesh,
                    datasets.bptt_batches(val_ids, args.batch_size,
                                          args.bptt),
                    batch_spec=(data_spec, data_spec)),
                log_writer=writer, verbose=is_main)
            if is_main and 'loss' in train_m:
                print(f'epoch {epoch}: train ppl '
                      f'{math.exp(min(train_m["loss"], 20)):.2f}, '
                      f'val ppl '
                      f'{math.exp(min(val_m["loss"], 20)):.2f}')
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
