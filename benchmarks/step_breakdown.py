"""Per-phase breakdown of the tracked-config K-FAC step (on-chip).

Times cumulative program variants of the bench.py workload (ResNet-32 /
CIFAR-10, batch 512, reference CIFAR cadence) so the per-phase cost of
every pipeline stage is a recorded number, not an inference:

  sgd            plain SGD step (fwd+bwd+momentum)
  capture        fwd+bwd through the K-FAC capture machinery, SGD update
                 (isolates the interception cost vs plain value_and_grad)
  precond        + preconditioning with frozen inverses + KL clip
                 (factor_update=False, inv_update=False)
  factors        + factor EWMA every iter (factor_update=True)
  factors_deferred  the 'factors' phase under r14 deferred reduction:
                 per-iter local accumulation, the EWMA boundary update
                 once per ``inv_freq`` window (single-chip: the delta
                 vs 'factors' is the accumulate-vs-EWMA program cost —
                 the collective saving only exists on a mesh)
  full           + amortized inverse updates every ``inv_freq`` iters
  full_polishN   full with eigh_polish_iters=N variants
  precond_bf16   the 'precond' phase with precond_compute_dtype=bf16
                 (r6 A/B: attributes the every-step precondition tax
                 per contraction dtype)

The phase cost is the difference between adjacent rows; the rows are
cumulative so each is independently meaningful. Methodology = bench.py
(scanned loop, chained carries, median-of-repeats, FLOPs floor).

Reference cost centers this decomposes: compute_factors / allreduce
(preconditioner.py:566-575), compute_inverses (:555-564),
precondition+clip (:577-585,661-682).

    python benchmarks/step_breakdown.py [--iters 30] [--polish 8 16]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import jax
import optax

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import bench as B  # noqa: E402  (repo root: the timing methodology)
from distributed_kfac_pytorch_tpu import KFAC
from distributed_kfac_pytorch_tpu.models import cifar_resnet


def emit(obj):
    print(json.dumps(obj), flush=True)


def build(model, x, y, inv_freq, n_iters, mode, polish_iters=None,
          precond_dtype=None, kfac_kwargs=None):
    """One scanned runner for a cumulative phase ``mode``."""
    kw = dict(kfac_kwargs or {})
    if mode == 'factors_deferred':
        kw.setdefault('deferred_factor_reduction', True)
    if polish_iters is not None:
        kw['eigh_polish_iters'] = polish_iters
    if precond_dtype is not None:
        kw['precond_compute_dtype'] = precond_dtype
    kfac = KFAC(model, factor_update_freq=1, inv_update_freq=inv_freq,
                damping=0.003, lr=0.1, **kw)
    variables, kstate = kfac.init(jax.random.PRNGKey(0), x)
    params = variables['params']
    extra = {k: v for k, v in variables.items() if k != 'params'}
    tx = optax.sgd(0.1, momentum=0.9)
    opt_state = tx.init(params)

    def loss(out):
        return B.loss_fn(out, y)

    def make_body(factor_update, inv_update, use_precond):
        def body(carry, _):
            params, opt_state, kstate, extra = carry
            loss_v, _, grads, captures, updated = (
                kfac.capture.loss_and_grads(
                    loss, params, x, extra_vars=extra,
                    mutable_cols=('batch_stats',)))
            if use_precond:
                g, kstate2 = kfac.step(kstate, grads, captures,
                                       factor_update=factor_update,
                                       inv_update=inv_update)
            else:
                g, kstate2 = grads, kstate
            updates, opt_state = tx.update(g, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state, kstate2, {**extra, **updated}), loss_v
        return body

    if mode == 'sgd':
        def sgd_body(carry, _):
            params, opt_state, extra = carry

            def wrapped(p):
                out, updated = model.apply({'params': p, **extra}, x,
                                           mutable=['batch_stats'])
                return loss(out), updated
            (l, updated), grads = jax.value_and_grad(
                wrapped, has_aux=True)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state, {**extra, **updated}), l

        @jax.jit
        def run(carry):
            carry, losses = jax.lax.scan(sgd_body, carry, None,
                                         length=n_iters)
            return carry, losses[-1]
        return run, (params, opt_state, extra)

    if mode == 'capture':
        body = make_body(False, False, use_precond=False)
    elif mode == 'precond':
        body = make_body(False, False, use_precond=True)
    elif mode == 'factors':
        body = make_body(True, False, use_precond=True)
    elif mode == 'factors_deferred':
        # r14 deferred reduction at the same cadence shape as
        # 'factors': accumulate every iter, apply (factor_reduce) once
        # per inv_freq window — no firing, so the row isolates the
        # factor-statistics path like 'factors' does.
        def make_deferred_body(reduce_flag):
            def body(carry, _):
                params, opt_state, kstate, extra = carry
                loss_v, _, grads, captures, updated = (
                    kfac.capture.loss_and_grads(
                        loss, params, x, extra_vars=extra,
                        mutable_cols=('batch_stats',)))
                g, kstate2 = kfac.step(kstate, grads, captures,
                                       factor_update=True,
                                       inv_update=False,
                                       factor_reduce=reduce_flag)
                updates, opt_state = tx.update(g, opt_state, params)
                params = optax.apply_updates(params, updates)
                return (params, opt_state, kstate2,
                        {**extra, **updated}), loss_v
            return body

        reduce_body = make_deferred_body(True)
        accum_body = make_deferred_body(False)

        def block(carry, _):
            carry, l0 = reduce_body(carry, None)
            carry, ls = jax.lax.scan(accum_body, carry, None,
                                     length=inv_freq - 1)
            return carry, ls[-1]

        @jax.jit
        def run(carry):
            carry, losses = jax.lax.scan(block, carry, None,
                                         length=n_iters // inv_freq)
            return carry, losses[-1]
        return run, (params, opt_state, kstate, extra)
    elif mode == 'full':
        inv_body = make_body(True, True, use_precond=True)
        plain_body = make_body(True, False, use_precond=True)

        def block(carry, _):
            carry, l0 = inv_body(carry, None)
            carry, ls = jax.lax.scan(plain_body, carry, None,
                                     length=inv_freq - 1)
            return carry, ls[-1]

        @jax.jit
        def run(carry):
            carry, losses = jax.lax.scan(block, carry, None,
                                         length=n_iters // inv_freq)
            return carry, losses[-1]
        return run, (params, opt_state, kstate, extra)
    else:
        raise ValueError(mode)

    @jax.jit
    def run(carry):
        carry, losses = jax.lax.scan(body, carry, None, length=n_iters)
        return carry, losses[-1]
    return run, (params, opt_state, kstate, extra)


def tuned_vs_default(args, model, x, y, inv_freq):
    """Replay a committed ``TUNED_*.json`` against the defaults.

    Both legs run the cumulative 'full' phase (factor EWMA every iter,
    amortized inverse firing) — the default at the reference cadence
    and the tuned leg with the artifact's knobs mapped onto raw KFAC
    kwargs (``autotune.kfac_overrides``); the composed ms/iter delta
    is the whole win/regression the artifact claims. Knobs the scanned
    harness cannot express (e.g. ``inv_pipeline_chunks`` — the scan
    fires monolithically) are surfaced in the row, not silently
    dropped.
    """
    from distributed_kfac_pytorch_tpu import autotune

    artifact = autotune.read_tuned(args.tuned_config)
    kw, tuned_inv_freq, ignored = autotune.kfac_overrides(
        artifact['best'])
    tuned_freq = tuned_inv_freq or inv_freq
    rows = {}
    for leg, kwargs, freq in (('default', None, inv_freq),
                              ('tuned', kw, tuned_freq)):
        n = (args.iters // freq) * freq or freq
        run, carry = build(model, x, y, freq, n, 'full',
                           kfac_kwargs=kwargs)
        rows[leg] = round(B.time_chained(run, carry, n,
                                         leg=f'tuned_ab_{leg}'), 2)
    emit({'phase': 'tuned_vs_default',
          'tuned_config': args.tuned_config,
          'workload': artifact.get('workload'),
          'artifact_platform': artifact.get('platform'),
          'backend': jax.default_backend(),
          'knobs': artifact['best'],
          'ignored_knobs': ignored,
          'default_inv_freq': inv_freq,
          'tuned_inv_freq': tuned_freq,
          'default_ms_per_iter': rows['default'],
          'tuned_ms_per_iter': rows['tuned'],
          'delta_ms_per_iter': round(rows['default'] - rows['tuned'],
                                     2)})


def lm_approx_rows(args):
    """Per-approximation factor-update cost rows (r13).

    For each ``--lm-d`` rung of the LM ladder: a scanned
    capture+precondition baseline (factor_update=False — everything
    the step pays EXCEPT the factor statistics, the r6 cumulative-
    phase methodology) and a capture+precondition+factor-EWMA leg per
    weight-sharing approximation ('expand' flattens B*T covariance
    rows, 'reduce' sums/averages over T first). The deltas isolate the
    A/G factor-statistic cost per approx — on the d2048 rung reduce's
    contraction sees seq x fewer rows, so its factor cost should drop
    toward ~T x, bounded by the rows-independent EWMA/symmetrize
    dim^2 passes that remain in both legs (the r13 claim the
    committed BENCH_r13_APPROX_COST.jsonl records; CPU provenance
    caveats per PERF.md).
    """
    import jax.numpy as jnp
    import optax as _optax

    from distributed_kfac_pytorch_tpu.models import transformer_lm

    for d in args.lm_d:
        model = transformer_lm.TransformerLM(
            vocab_size=args.lm_vocab, d_model=d, num_layers=1,
            num_heads=8, max_len=args.lm_seq, dropout=0.0,
            tie_weights=False)
        ids = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.lm_batch, args.lm_seq), 0,
                                 args.lm_vocab)
        tgt = jax.random.randint(jax.random.PRNGKey(2),
                                 (args.lm_batch, args.lm_seq), 0,
                                 args.lm_vocab)

        def loss(out, tgt=tgt):
            return _optax.softmax_cross_entropy_with_integer_labels(
                out, tgt).mean()

        def make_run(approx, factor_update):
            kfac = KFAC(model, factor_update_freq=1,
                        inv_update_freq=args.iters * 10,
                        damping=0.003, lr=0.1,
                        kfac_approx=approx)
            variables, kstate = kfac.init(jax.random.PRNGKey(0), ids,
                                          train=False)
            params = variables['params']
            tx = _optax.sgd(0.1, momentum=0.9)
            opt_state = tx.init(params)

            def body(carry, _):
                params, opt_state, kstate = carry
                l, _, grads, captures, _ = (
                    kfac.capture.loss_and_grads(loss, params, ids,
                                                train=False))
                # The baseline leg still PRECONDITIONS (frozen
                # inverses): the factor-cost delta must not absorb
                # the approx-independent precondition matmuls.
                g, kstate = kfac.step(kstate, grads, captures,
                                      factor_update=factor_update,
                                      inv_update=False)
                updates, opt_state = tx.update(g, opt_state, params)
                params = _optax.apply_updates(params, updates)
                return (params, opt_state, kstate), l

            @jax.jit
            def run(carry):
                carry, losses = jax.lax.scan(body, carry, None,
                                             length=args.iters)
                return carry, losses[-1]
            return run, (params, opt_state, kstate)

        run, carry = make_run('expand', factor_update=False)
        base = B.time_chained(run, carry, args.iters,
                              leg=f'lm{d}_precond')
        row = {'phase': 'lm_approx_factor_cost', 'd_model': d,
               'seq': args.lm_seq, 'batch': args.lm_batch,
               'vocab': args.lm_vocab,
               'backend': jax.default_backend(),
               'precond_ms_per_iter': round(base, 2)}
        for approx in ('expand', 'reduce'):
            run, carry = make_run(approx, factor_update=True)
            ms = B.time_chained(run, carry, args.iters,
                                leg=f'lm{d}_factors_{approx}')
            row[f'factors_{approx}_ms_per_iter'] = round(ms, 2)
            row[f'factor_cost_{approx}'] = round(ms - base, 2)
        ce, cr = row['factor_cost_expand'], row['factor_cost_reduce']
        if cr > 0:
            row['expand_over_reduce'] = round(ce / cr, 2)

        # Statistics-only rows: time the A/G covariance COMPUTATION
        # alone (no EWMA write-back, no precondition) — the part of
        # the factor stage the approximation actually changes. The
        # whole-step deltas above bound the end-to-end win; these
        # isolate the ~T x contraction claim, which on a memory-bound
        # CPU is otherwise buried under the rows-independent dim^2
        # EWMA/assembly traffic both approxes pay equally (on TPU the
        # MXU contraction dominates the factor phase — PERF.md
        # roofline — so the whole-stage ratio tracks this number).
        kexp = KFAC(model, kfac_approx='expand')
        kred = KFAC(model, kfac_approx='reduce')
        variables, _ = kexp.init(jax.random.PRNGKey(0), ids,
                                 train=False)
        kred.init(jax.random.PRNGKey(0), ids, train=False)
        # kfaclint: waive[retrace-jit-in-loop] per-approx bench harness: one capture program per approx row
        _, _, _, captures, _ = jax.jit(
            lambda p: kexp.capture.loss_and_grads(
                loss, p, ids, train=False))(variables['params'])

        def stat_runner(specs):
            from distributed_kfac_pytorch_tpu import layers as L

            def body(carry, _):
                caps = carry
                probe = jnp.zeros((), jnp.float32)
                for name, spec in specs.items():
                    a = L.compute_a_factor(spec, caps[name]['a'])
                    g = L.compute_g_factor(spec, caps[name]['g'])
                    probe = probe + a.reshape(-1)[0] + g.reshape(-1)[0]
                # Perturb float captures so the chain cannot be CSE'd
                # across scan iterations (ids stay ints).
                caps = jax.tree.map(
                    lambda x: x * (1.0 + 1e-6)
                    if jnp.issubdtype(x.dtype, jnp.floating) else x,
                    caps)
                return caps, probe

            @jax.jit
            def run(caps):
                caps, probes = jax.lax.scan(body, caps, None,
                                            length=args.iters)
                return caps, probes[-1]
            return run

        for approx, k in (('expand', kexp), ('reduce', kred)):
            run = stat_runner(k.specs)
            ms = B.time_chained(run, captures, args.iters,
                                leg=f'lm{d}_stats_{approx}')
            row[f'factor_stats_{approx}_ms_per_iter'] = round(ms, 2)
        se = row['factor_stats_expand_ms_per_iter']
        sr = row['factor_stats_reduce_ms_per_iter']
        if sr > 0:
            row['stats_expand_over_reduce'] = round(se / sr, 2)
        emit(row)


def lm_lowrank_rows(args):
    """Per-firing decomposition cost of the ENGAGED (transformer-FFN)
    factor bucket: exact eigh vs damped Cholesky vs r19 low-rank.

    For each ``--lm-d`` rung: build the rung's engaged factor stack —
    the ``(2, 4d, 4d)`` Wishart-class SPD bucket the config-4
    transformer's two FFN G-factors form — and time one firing of it
    under each backend:

      ``eigh``      the exact eigendecomposition (the reference eigen
                    path and the r19 parity oracle);
      ``cholesky``  the damped Cholesky inverse (today's 'auto'
                    large-dim dispatch);
      ``lowrank``   ``batched_lowrank_eigh`` in the WARM steady state
                    (the carried basis rides the chained carry, so
                    every timed call is the subspace-refresh +
                    projected-polish program a real firing runs).

    ``eigh_over_lowrank`` is the "per-firing decomposition cost
    reduced >= 3x vs exact eigh" acceptance number (PERF.md r19);
    ``cholesky_over_lowrank`` is the win over the current large-dim
    default. The whole-model firing (which dilutes both with the
    unchanged small-dim eigen work) rides in ``flagship_lm.py`` /
    ``firing_spread.py --lowrank``; quality in
    ``flagship_lm.py --lowrank-ab``.
    """
    import jax.numpy as jnp

    from distributed_kfac_pytorch_tpu.ops import (
        linalg,
        pallas_kernels,
    )

    for d in args.lm_d:
        dim = 4 * d
        rng = jax.random.PRNGKey(7)
        xs = jax.random.normal(rng, (2, 2 * dim, dim), jnp.float32)
        stack = (jnp.einsum('bni,bnj->bij', xs, xs) / (2 * dim)
                 + 1e-3 * jnp.eye(dim))
        row = {'phase': 'lm_lowrank_firing_cost', 'd_model': d,
               'engaged_dim': dim, 'stack': 2,
               'inv_lowrank_rank': args.lowrank_rank,
               'backend': jax.default_backend()}

        def timed(run, carry, leg):
            return round(B.time_chained(run, carry, 1, repeats=3,
                                        leg=f'lm{d}_lowrank_{leg}'),
                         2)

        def run_eigh(carry):
            s, t = carry
            qs, ds = jax.vmap(jnp.linalg.eigh)(s + t * 1e-6)
            return (s, t + 1), jnp.sum(ds).astype(jnp.float32)

        def run_chol(carry):
            s, t = carry
            inv = pallas_kernels.damped_inverse_stack(
                s + t * 1e-6, 0.003, 'cholesky')
            return (s, t + 1), jnp.sum(inv[:, 0, :]).astype(
                jnp.float32)

        def run_lowrank(carry):
            s, t, q = carry
            qs, ds = linalg.batched_lowrank_eigh(
                s + t * 1e-6, args.lowrank_rank, q_prev=q)
            return (s, t + 1, qs), jnp.sum(ds).astype(jnp.float32)

        # t*1e-6 perturbs the input each chained call so no backend
        # can cache a repeated decomposition out of the timed window.
        # kfaclint: waive[retrace-jit-in-loop] per-rung bench harness: one program per (rung, backend) row
        jit_eigh = jax.jit(run_eigh)
        # kfaclint: waive[retrace-jit-in-loop] per-rung bench harness: one program per (rung, backend) row
        jit_chol = jax.jit(run_chol)
        # kfaclint: waive[retrace-jit-in-loop] per-rung bench harness: one program per (rung, backend) row
        jit_lowrank = jax.jit(run_lowrank)
        row['firing_eigh_ms'] = timed(
            jit_eigh, (stack, jnp.float32(0)), 'eigh')
        row['firing_cholesky_ms'] = timed(
            jit_chol, (stack, jnp.float32(0)), 'cholesky')
        q0 = jnp.broadcast_to(jnp.eye(dim, args.lowrank_rank),
                              (2, dim, args.lowrank_rank))
        row['firing_lowrank_ms'] = timed(
            jit_lowrank, (stack, jnp.float32(0), q0), 'lowrank')
        if row['firing_lowrank_ms'] > 0:
            row['eigh_over_lowrank'] = round(
                row['firing_eigh_ms'] / row['firing_lowrank_ms'], 2)
            row['cholesky_over_lowrank'] = round(
                row['firing_cholesky_ms'] / row['firing_lowrank_ms'],
                2)
        emit(row)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument('--iters', type=int, default=30)
    p.add_argument('--polish', type=int, nargs='*', default=[16, 8])
    p.add_argument('--tuned-config', default=None, metavar='PATH',
                   help='replay a committed TUNED_*.json against the '
                        'defaults (tuned_vs_default row only; skips '
                        'the phase decomposition)')
    p.add_argument('--lm-approx', action='store_true',
                   help='r13 per-approx factor-update cost rows on the '
                        'LM ladder (expand vs reduce; skips the CIFAR '
                        'phase decomposition)')
    p.add_argument('--lm-lowrank', action='store_true',
                   help='r19 per-firing decomposition-cost rows on '
                        'the LM ladder (exact dispatch vs randomized '
                        'low-rank on the FFN dims; skips the CIFAR '
                        'phase decomposition)')
    p.add_argument('--lowrank-rank', type=int, default=64,
                   help='--lm-lowrank truncation rank')
    p.add_argument('--lm-d', type=int, nargs='+',
                   default=[512, 1024, 2048],
                   help='--lm-approx / --lm-lowrank d_model rungs')
    p.add_argument('--lm-seq', type=int, default=128)
    p.add_argument('--lm-batch', type=int, default=4)
    p.add_argument('--lm-vocab', type=int, default=512)
    args = p.parse_args(argv)

    if args.lm_approx:
        return lm_approx_rows(args)

    if args.lm_lowrank:
        return lm_lowrank_rows(args)

    on_tpu = jax.default_backend() == 'tpu'
    if on_tpu:
        model = cifar_resnet.get_model('resnet32')
        b = 512
    else:
        model = cifar_resnet.get_model('resnet20')
        b = 16
    x = jax.random.normal(jax.random.PRNGKey(1), (b, 32, 32, 3))
    y = jax.random.randint(jax.random.PRNGKey(2), (b,), 0, 10)
    inv_freq = 10
    n_iters = (args.iters // inv_freq) * inv_freq or inv_freq

    if args.tuned_config:
        return tuned_vs_default(args, model, x, y, inv_freq)

    kfac = KFAC(model, factor_update_freq=1, inv_update_freq=inv_freq)
    variables, _ = kfac.init(jax.random.PRNGKey(0), x)
    floor_ms = B.flops_floor_ms(
        kfac, variables, x, y,
        mutable_cols=('batch_stats',)) if on_tpu else 0.0

    rows = {}
    for mode in ('sgd', 'capture', 'precond', 'factors',
                 'factors_deferred', 'full'):
        run, carry = build(model, x, y, inv_freq, n_iters, mode)
        ms = B.time_chained(run, carry, n_iters, floor_ms=floor_ms,
                            leg=mode)
        rows[mode] = round(ms, 2)
        print(json.dumps({'phase': mode, 'ms_per_iter': rows[mode]}),
              flush=True)
    # bf16 precondition A/B on the same cumulative 'precond' phase, so
    # the every-step precondition tax is attributed per dtype (the r6
    # knob; the delta against 'precond' is the whole saving/regression).
    import jax.numpy as jnp
    run, carry = build(model, x, y, inv_freq, n_iters, 'precond',
                       precond_dtype=jnp.bfloat16)
    ms = B.time_chained(run, carry, n_iters, floor_ms=floor_ms,
                        leg='precond_bf16')
    rows['precond_bf16'] = round(ms, 2)
    print(json.dumps({'phase': 'precond_bf16',
                      'ms_per_iter': rows['precond_bf16']}), flush=True)
    for n in args.polish:
        run, carry = build(model, x, y, inv_freq, n_iters, 'full',
                           polish_iters=n)
        ms = B.time_chained(run, carry, n_iters, floor_ms=floor_ms,
                            leg=f'full_polish{n}')
        rows[f'full_polish{n}'] = round(ms, 2)
        print(json.dumps({'phase': f'full_polish{n}',
                          'ms_per_iter': rows[f'full_polish{n}']}),
              flush=True)
    deltas = {
        'capture_cost': round(rows['capture'] - rows['sgd'], 2),
        'precond_clip_cost': round(rows['precond'] - rows['capture'], 2),
        'precond_bf16_saving': round(rows['precond']
                                     - rows['precond_bf16'], 2),
        'factor_cost': round(rows['factors'] - rows['precond'], 2),
        # r14: single-chip program-cost delta of deferring the EWMA to
        # the window boundary (the collective saving needs a mesh).
        'deferred_reduce_delta': round(rows['factors_deferred']
                                       - rows['factors'], 2),
        'inverse_amortized_cost': round(rows['full'] - rows['factors'], 2),
    }
    print(json.dumps({'summary': rows, 'deltas': deltas}), flush=True)


if __name__ == '__main__':
    main()
