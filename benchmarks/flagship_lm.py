"""Flagship LM on-chip numbers: Transformer-XL-scale decoder LM with
Linear-layer K-FAC (BASELINE tracked config 4, at the scale the round-4
verdict asked for: d_model >= 1024, FFN 4096, seq >= 1024).

The reference's LM example is broken as shipped
(torch_language_model.py:253 sets base_lr from the rank; :277 unpacks a
3-tuple into 4 — SURVEY.md §8), so there is no reference number to
match here: the bar is the framework's own SGD leg, with the same
<=1.3x production-cadence criterion the CNN flagship met.

Same phase-isolation design as flagship_resnet50.py (each leg is its
own subprocess, one at a time, so a leg that runs out of HBM takes only
its own process down; the parent never initialises a JAX backend — a
chip belongs to one process):

  sgd        plain autodiff + SGD momentum step
  nofactor   plain autodiff + precondition + KL clip (intercept=False —
             what (1-1/f) of production steps run)
  factors    capture + factor EWMA + precondition (the 1-in-f step)
  firing     inverse firing over the REAL factor set per method
             ('auto' first: it is the default; the xl factor set
             straddles the 640 eigen/cholesky cutoff — q/k/v/o sides
             1024/1025 go cholesky, nothing here is eigen except
             when --size small)

MFU is hand-counted with an LM-specific FLOP model (bench's
model_flops_per_step counts only K-FAC-registered matmuls — on a
transformer that misses attention scores/values and the tied-embedding
decoder matmul, which at vocab 32k is one of the largest matmuls in
the step):

  per layer fwd:   2*tok*4*d^2 (qkvo) + 4*B*T^2*d (QK^T + AV, full
                   T^2 — the causal mask zeroes but does not skip) +
                   2*tok*2*d*ffn (mlp in+out)
  head fwd:        2*tok*d*vocab (tied-embedding attend)
  fwd+bwd = 3x fwd (two same-size contractions per matmul backward).

    python benchmarks/flagship_lm.py [--size xl] [--seq 1024]
        [--batch 4] [--vocab 32768] [--model-dtype bf16]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def emit(obj):
    print(json.dumps(obj), flush=True)


def lm_flops_per_step(d_model, num_layers, mlp_ratio, batch, seq, vocab):
    tok = batch * seq
    per_layer = (2 * tok * 4 * d_model * d_model
                 + 4 * batch * seq * seq * d_model
                 + 2 * tok * 2 * d_model * (mlp_ratio * d_model))
    head = 2 * tok * d_model * vocab
    return 3 * (num_layers * per_layer + head)


# ---------------------------------------------------------------------------
# Single-phase worker (fresh process via --phase)
# ---------------------------------------------------------------------------

def _setup(args, with_kfac=True):
    import jax
    import jax.numpy as jnp
    import optax

    import bench as B  # noqa: F401  (enables the compile cache)
    from distributed_kfac_pytorch_tpu import KFAC
    from distributed_kfac_pytorch_tpu.models import transformer_lm

    dt = {None: None, 'fp32': jnp.float32, 'bf16': jnp.bfloat16}[
        args.model_dtype]
    model = transformer_lm.get_model(
        vocab_size=args.vocab, size=args.size, max_len=args.seq,
        dropout=0.0, dtype=dt,
        attn_block_size=args.attn_block_size)
    ids = jax.random.randint(jax.random.PRNGKey(1),
                             (args.batch, args.seq), 0, args.vocab)
    tgt = jax.random.randint(jax.random.PRNGKey(2),
                             (args.batch, args.seq), 0, args.vocab)
    if not with_kfac:
        # The SGD leg must not carry the multi-GB factor/inverse state
        # (at xl scale it alone RESOURCE_EXHAUSTs a 16 GB chip).
        variables = model.init(jax.random.PRNGKey(0), ids, train=False)
        return jax, jnp, optax, model, None, variables, None, ids, tgt
    kw = {}
    if args.inverse_method:
        kw['inverse_method'] = args.inverse_method
    if args.precond_dtype:
        # The r6 tentpole knob: bf16 precondition-contraction operands
        # (fp32 accumulation). With --bf16-inverses the stored inverses
        # are consumed resident — no fp32 upcast-on-read copy of the
        # 4096^2 operands that dominate the non-factor step.
        kw['precond_compute_dtype'] = {
            'fp32': jnp.float32, 'bf16': jnp.bfloat16}[args.precond_dtype]
    if args.bf16_factors:
        kw['factor_dtype'] = jnp.bfloat16
        kw['factor_compute_dtype'] = jnp.bfloat16
    if args.bf16_inverses:
        # Reference-legitimate storage policy (it computes inverses in
        # fp32 and stores in inv_dtype, which may be half precision —
        # kfac/layers/base.py:435,439 + preconditioner.py:149); at xl
        # scale fp32 inverse stacks alone are 3.2 GB and the scan
        # carry double-buffers.
        kw['inv_dtype'] = jnp.bfloat16
    if args.kfac_approx and args.kfac_approx != 'expand':
        # r13 weight-sharing approximation: 'reduce' switches every
        # sequence-shared Dense's factor statistics to the
        # sum-over-sequence form (and ties the embedding factor pair).
        kw['kfac_approx'] = args.kfac_approx
    kfac = KFAC(model, factor_update_freq=1, inv_update_freq=1,
                damping=0.003, lr=0.1, **kw)
    variables, kstate = kfac.init(jax.random.PRNGKey(0), ids, train=False)
    return jax, jnp, optax, model, kfac, variables, kstate, ids, tgt


def run_phase(args):
    import bench as B
    jax, jnp, optax, model, kfac, variables, kstate, ids, tgt = _setup(
        args, with_kfac=args.phase != 'sgd')
    params = variables['params']
    tx = optax.sgd(0.1, momentum=0.9)
    opt_state = tx.init(params)

    def loss_fn(out):
        logits = out[0] if isinstance(out, tuple) else out
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, tgt).mean()

    mode = args.phase
    if mode == 'firing':
        # One real factor update so decomposed matrices are
        # covariance-shaped; factor shapes are batch/seq-independent,
        # so this shaping pass runs on TINY inputs (the full-size
        # forward + captures + full state RESOURCE_EXHAUSTs at xl).
        tiny = ids[:1, :128]
        tiny_tgt = tgt[:1, :128]

        def tiny_loss(out):
            logits = out[0] if isinstance(out, tuple) else out
            import optax as _o
            return _o.softmax_cross_entropy_with_integer_labels(
                logits, tiny_tgt).mean()

        _, _, _, captures, _ = jax.jit(
            lambda p: kfac.capture.loss_and_grads(
                tiny_loss, p, tiny, train=False))(params)
        factors = jax.jit(kfac.update_factors)(kstate, captures)
        del kstate, captures

        # The monolithic all-bucket firing program peaks at ~21 GB at
        # xl scale (fp32 stacks + Cholesky workspace + state double
        # buffer). The firing is embarrassingly separable by factor
        # dim, so each bucket is timed as its own chained program and
        # the per-firing cost is the sum — same methodology class as
        # the phase decomposition itself.
        import collections
        import functools

        by_dim = collections.defaultdict(list)
        for name, spec in kfac.specs.items():
            f = factors[name]
            for which in ('A', 'G'):
                m = f[which]
                if m.ndim != 2 or m.shape[0] != m.shape[-1]:
                    continue  # diagonal embedding A
                by_dim[m.shape[-1]].append(m)
        del factors
        # Free everything the bucket programs don't need: params,
        # momentum and the rest add ~3 GB that pushed the 4096/4097
        # bucket compiles over HBM.
        del params, opt_state, variables
        n = min(args.iters, 3)
        total_ms = 0.0
        parts = {}
        for dim in sorted(by_dim):
            stack = jnp.stack([m.astype(jnp.float32)
                               for m in by_dim[dim]])
            del by_dim[dim]
            method = kfac.method_for_dim(dim)
            if args.inverse_method == 'eigen':
                method = 'eigen'

            # Large-dim stacks (18 x 4096^2 fp32 = 1.2 GB) push the
            # batched Cholesky's workspace past HBM inside the scan —
            # lax.map over sub-chunks sequences the workspace (peak =
            # one chunk) without changing the work measured.
            k = stack.shape[0]
            chunks = 1
            if dim > 2048:
                chunks = next(c for c in range(1, k + 1)
                              if k % c == 0 and k // c <= 3)

            def chunked(fn, s):
                if chunks == 1:
                    return fn(s)
                cs = s.reshape(chunks, s.shape[0] // chunks,
                               *s.shape[1:])
                return jax.lax.map(fn, cs)

            if method == 'eigen':
                # The PRODUCTION eigen firing is the warm-start polish
                # (eigh_method 'auto' steady state), not a cold XLA
                # eigh — carry the basis through the chain like the
                # training path does.
                def body(carry, _):
                    from distributed_kfac_pytorch_tpu.ops import linalg
                    s, q = carry

                    def one(args_):
                        si, qi = args_
                        return linalg.batched_eigh(
                            si, 'auto', q_prev=qi,
                            polish_iters=kfac.eigh_polish_iters)

                    if chunks > 1:
                        cs = s.reshape(chunks, -1, *s.shape[1:])
                        cq = q.reshape(chunks, -1, *q.shape[1:])
                        qs, ds = jax.lax.map(one, (cs, cq))
                        qs = qs.reshape(q.shape)
                        ds = ds.reshape(s.shape[:2])
                    else:
                        qs, ds = one((s, q))
                    probe = qs.reshape(-1)[0] + ds.reshape(-1)[0]
                    return (s * (1.0 + 1e-5), qs), probe

                _, qs0 = jnp.linalg.eigh(stack)
                carry0 = (stack, qs0)
            else:
                def body(carry, _):
                    from distributed_kfac_pytorch_tpu.ops import (
                        pallas_kernels)
                    s = carry

                    def one(c):
                        return pallas_kernels.damped_inverse_stack(
                            c, 0.003, method)

                    inv = chunked(one, s)
                    probe = inv.reshape(-1)[0]
                    return s * (1.0 + 1e-5), probe

                carry0 = stack

            @functools.partial(jax.jit, donate_argnums=(0,))
            def run(c):
                c, probes = jax.lax.scan(body, c, None, length=n)
                return c, probes[-1]

            ms = B.time_chained(run, carry0, n, repeats=2,
                                max_attempts=2)
            parts[f'{dim}x{k}_{method}'] = round(ms, 2)
            total_ms += ms
            del stack
        out = {'phase_result': round(total_ms, 2),
               'bucket_parts': parts, **B.device_fields()}
        if args.inv_pipeline_chunks > 1:
            # Firing-spread leg (r9): project the pipelined per-chunk
            # firing costs from the MEASURED per-bucket ms — the same
            # per-matrix granularity + LPT packer the runtime plan
            # uses (and the 'refinable from measured bucket_parts'
            # hook: these parts are exactly what inv_pipeline_costs
            # accepts). max_chunk_ms is the projected residual spike;
            # spike_reduction is the step-time-uniformity win the
            # on-chip rerun must confirm (PERF.md r9 decision rule).
            from distributed_kfac_pytorch_tpu.preconditioner import (
                plan_inverse_chunks)
            kc = args.inv_pipeline_chunks
            items = []
            for key, part_ms in parts.items():
                cnt = int(key.rsplit('_', 1)[0].split('x')[1])
                items += [((key, i), part_ms / cnt)
                          for i in range(cnt)]
            plan = plan_inverse_chunks(items, kc)
            loads = [0.0] * kc
            for key, cost in items:
                loads[plan[key]] += cost
            out['firing_spread'] = {
                'chunks': kc,
                'chunk_ms': [round(v, 2) for v in loads],
                'max_chunk_ms': round(max(loads), 2),
                'monolithic_ms': round(total_ms, 2),
                'spike_reduction': round(total_ms / max(loads), 2)}
        emit(out)
        return

    if mode == 'sgd':
        def body(carry, _):
            params, opt_state, kst = carry

            def wrapped(p):
                return loss_fn(model.apply({'params': p}, ids,
                                           train=False))
            l, grads = jax.value_and_grad(wrapped)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state, kst), l
    else:
        flags = {'nofactor': (False, False),
                 'factors': (True, False)}[mode]

        def body(carry, _):
            params, opt_state, kst = carry
            l, _, grads, captures, _ = kfac.capture.loss_and_grads(
                loss_fn, params, ids, train=False,
                intercept=flags[0])
            g, kst = kfac.step(kst, grads, captures,
                               factor_update=flags[0],
                               inv_update=flags[1])
            updates, opt_state = tx.update(g, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state, kst), l

    # Donated carry: time_chained feeds each call the previous call's
    # output, so the multi-GB state is single-buffered (without this
    # the xl nofactor leg's carry alone double-buffers past 16 GB).
    import functools

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run(carry):
        carry, losses = jax.lax.scan(body, carry, None,
                                     length=args.iters)
        return carry, losses[-1]

    flops = lm_flops_per_step(model.d_model, model.num_layers, 4,
                              args.batch, args.seq, args.vocab)
    peak = (B.detected_tpu_peak() if jax.default_backend() == 'tpu'
            else None)
    floor = flops / peak * 1e3 if peak else 0.0
    ms = B.time_chained(run, (params, opt_state, kstate), args.iters,
                        floor_ms=floor, leg=f'lm_{mode}')
    mfu = round(flops / (ms * 1e-3) / peak, 4) if peak else None
    emit({'phase_result': round(ms, 2), 'mfu': mfu,
          **B.device_fields()})


# ---------------------------------------------------------------------------
# KFAC-expand vs KFAC-reduce vs SGD quality ladder (r13)
# ---------------------------------------------------------------------------

def run_quality_leg(args):
    """One (d_model, leg) rung of the --approx-ab scaling ladder.

    A short REAL training run (synthetic Markov corpus, the LM CLI's
    offline default) recording the per-step loss curve and steady-state
    ms/iter: legs 'sgd' (momentum baseline), 'expand' and 'reduce'
    (K-FAC under each weight-sharing approximation, identical
    hyperparameters otherwise — the curve difference isolates the
    approximation), plus the r14 staleness pair 'eager' (the default
    firing schedule) and 'stale' (``inv_staleness=1`` +
    ``deferred_factor_reduction=True`` — the composed overlap config a
    promotion would ship; the curve difference isolates the one-window
    inverse staleness, since deferred reduce is exact). Static cadence
    f=--ab-f / i=--ab-i through ``engine.cadence_flags`` like a
    production run; one jit variant per flag combination; step 0's
    compile wall is excluded from ms/iter. Quality curves, not
    microbenches — the PERF.md r13/r14 decision rules consume these
    next to step_breakdown's cost rows.
    """
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import bench as B  # noqa: F401  (compile cache)
    from distributed_kfac_pytorch_tpu import KFAC
    from distributed_kfac_pytorch_tpu.models import transformer_lm
    from distributed_kfac_pytorch_tpu.training import datasets, engine

    d = args.ab_d
    leg = args.quality_leg
    train_ids, _, vocab = datasets.get_lm_corpus(
        None, synthetic_size=max(args.ab_steps * args.ab_batch
                                 * args.ab_seq + args.ab_seq + 1,
                                 20_000),
        vocab_size=args.ab_vocab)
    model = transformer_lm.TransformerLM(
        vocab_size=vocab, d_model=d, num_layers=args.ab_layers,
        num_heads=8, max_len=args.ab_seq, dropout=0.0, tie_weights=True)

    def loss_of(logits, tgt):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, tgt).mean()

    tx = optax.sgd(args.ab_lr, momentum=0.9)
    f_freq, i_freq = args.ab_f, args.ab_i
    # Steps whose wall time is a jit trace+compile (each variant's
    # FIRST invocation), excluded from the spike stat below — every
    # flag combination compiles lazily mid-run, and a multi-second
    # compile wall would drown the eigh spike the metric exists to
    # show.
    compiled_at: set = set()
    cur_step = [0]
    if leg == 'sgd':
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, args.ab_seq), jnp.int32),
                               train=False)
        params = variables['params']
        opt_state = tx.init(params)

        @jax.jit
        def sgd_step(params, opt_state, x, y):
            def wrapped(p):
                return loss_of(model.apply({'params': p}, x,
                                           train=False), y)
            l, grads = jax.value_and_grad(wrapped)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, l

        def step(st, x, y, flags):
            if not compiled_at:
                compiled_at.add(cur_step[0])
            p, o, l = sgd_step(st[0], st[1], x, y)
            return (p, o), l
        state0 = (params, opt_state)
    else:
        # 'stale' = the composed r14 overlap config (staleness + the
        # exact deferred reduce); 'eager' = its matched default-
        # schedule baseline; 'expand'/'reduce' = the r13 approx legs;
        # 'lowrank' = the r19 randomized truncated path engaged on the
        # rung's FFN dims vs its matched 'exact' baseline.
        overlap = (dict(deferred_factor_reduction=True,
                        inv_staleness=1) if leg == 'stale' else {})
        if leg == 'lowrank':
            thr = args.ab_lowrank_threshold or 2 * d
            overlap = dict(inv_lowrank_rank=args.ab_lowrank_rank,
                           inv_lowrank_dim_threshold=thr)
        kfac = KFAC(model, factor_update_freq=f_freq,
                    inv_update_freq=i_freq, damping=0.003,
                    lr=args.ab_lr, kl_clip=0.001,
                    kfac_approx=(leg if leg in ('expand', 'reduce')
                                 else 'expand'),
                    **overlap)
        variables, kstate = kfac.init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, args.ab_seq), jnp.int32), train=False)
        params = variables['params']
        opt_state = tx.init(params)
        variants = {}

        def step(st, x, y, flags):
            key = tuple(sorted(flags.items()))
            if key not in variants:
                compiled_at.add(cur_step[0])
                def impl(params, opt_state, kstate, x, y,
                         _flags=dict(flags)):
                    l, _, grads, captures, _ = (
                        kfac.capture.loss_and_grads(
                            lambda out: loss_of(out, y), params, x,
                            train=False,
                            intercept=_flags.get('factor_update',
                                                 True)))
                    g, kstate = kfac.step(kstate, grads, captures,
                                          **_flags)
                    updates, opt_state = tx.update(g, opt_state,
                                                   params)
                    params = optax.apply_updates(params, updates)
                    return params, opt_state, kstate, l
                variants[key] = jax.jit(impl)
            p, o, k, l = variants[key](st[0], st[1], st[2], x, y)
            return (p, o, k), l
        state0 = (params, opt_state, kstate)

    def leg_flags(i):
        return engine.cadence_flags(
            i, f_freq, i_freq,
            deferred_reduce=leg == 'stale',
            inv_staleness=1 if leg == 'stale' else 0)

    losses, times = [], []
    st = state0
    batches = datasets.bptt_batches(train_ids, args.ab_batch,
                                    args.ab_seq)
    for i, (x, y) in enumerate(batches):
        if i >= args.ab_steps:
            break
        flags = leg_flags(i)
        cur_step[0] = i
        t0 = _time.perf_counter()
        st, l = step(st, jnp.asarray(x), jnp.asarray(y), flags)
        jax.block_until_ready(l)
        times.append((_time.perf_counter() - t0) * 1000.0)
        losses.append(float(l))
    tail = losses[-max(len(losses) // 4, 1):]
    # Steady-state ms/iter over plain (non-fired, non-compile) steps.
    plain = [t for i, t in enumerate(times)
             if i not in compiled_at
             and engine.fired_stage(leg_flags(i)) is None]
    # Spike stat over every non-compile step: fired steps stay IN (the
    # spike is what staleness re-times), compile walls stay OUT.
    post = [t for i, t in enumerate(times) if i not in compiled_at]
    emit({'phase_result': round(float(np.mean(tail)), 4),
          'losses': [round(v, 4) for v in losses],
          'final_loss': round(float(np.mean(tail)), 4),
          'first_loss': round(losses[0], 4),
          'ms_per_iter_plain': (round(float(np.median(plain)), 2)
                                if plain else None),
          # Firing-spike uniformity (the number staleness moves):
          # max/median over post-warm steps.
          'spike_max_over_median': (
              round(float(np.max(post) / np.median(post)), 2)
              if post else None),
          'steps': len(losses), **B.device_fields()})


# ---------------------------------------------------------------------------
# Observability baseline (r10): reduce a short measured run to the
# committed gate baseline (BASELINE_OBS.json)
# ---------------------------------------------------------------------------

def run_obs_baseline(args):
    """Record a per-step metrics stream and write a gate baseline.

    Unlike the scan-based timing legs above, this loop dispatches the
    jitted step ONE host call at a time — the gate regresses the
    host-visible step-time distribution (p50/p95/p99), which only
    exists when the host sees every step. Cadence f=5/i=10 via the
    engine's own ``cadence_flags`` so fired-stage labels and the
    compile-per-variant shape match a real training run; memory
    records every 10 steps feed the peak-HBM metric (device allocator
    stats permitting — CPU runs record the state footprint only, and
    the committed baseline then simply carries no peak_hbm_bytes for
    the gate to compare). The recorded stream lands next to the
    baseline as ``<path>.source.jsonl`` — the evidence the committed
    number came from.
    """
    import time as _time

    jax, jnp, optax, model, kfac, variables, kstate, ids, tgt = _setup(
        args)
    from distributed_kfac_pytorch_tpu.observability import (
        gate as obs_gate,
        memory as obs_memory,
        sink as obs_sink,
    )
    from distributed_kfac_pytorch_tpu.training import engine

    params = variables['params']
    tx = optax.sgd(0.1, momentum=0.9)
    opt_state = tx.init(params)

    def loss_fn(out):
        return optax.softmax_cross_entropy_with_integer_labels(
            out, tgt).mean()

    variants = {}

    def step(params, opt_state, kstate, f_flag, i_flag):
        key = (f_flag, i_flag)
        if key not in variants:
            def impl(params, opt_state, kstate, _f=f_flag, _i=i_flag):
                loss, _, grads, captures, _ = (
                    kfac.capture.loss_and_grads(
                        loss_fn, params, ids, train=False,
                        intercept=_f))
                g, kstate = kfac.step(kstate, grads, captures,
                                      factor_update=_f, inv_update=_i)
                updates, opt_state = tx.update(g, opt_state, params)
                params = optax.apply_updates(params, updates)
                return params, opt_state, kstate, loss
            variants[key] = jax.jit(impl)
        return variants[key](params, opt_state, kstate)

    f_freq, i_freq = 5, 10
    n_steps = max(int(args.iters), 4 * i_freq)
    spath = args.obs_baseline + '.source.jsonl'
    sink = obs_sink.JsonlMetricsSink(
        spath, meta={'bench': 'flagship_lm_obs_baseline',
                     'size': args.size, 'seq': args.seq,
                     'batch': args.batch, 'vocab': args.vocab,
                     'backend': jax.default_backend()})
    footprint = None
    # Warm every variant outside the recorded window (first calls are
    # compiles, not step times).
    for flags in ((True, True), (True, False), (False, False)):
        out = step(params, opt_state, kstate, *flags)
        jax.block_until_ready(out[0])
    for i in range(n_steps):
        flags = engine.cadence_flags(i, f_freq, i_freq)
        t0 = _time.perf_counter()
        params, opt_state, kstate, loss = step(
            params, opt_state, kstate, flags['factor_update'],
            flags['inv_update'])
        jax.block_until_ready(params)
        dt = (_time.perf_counter() - t0) * 1000.0
        sink.step_record(i, {'loss': loss}, host_step_ms=dt,
                         fired=engine.fired_stage(flags))
        if i % i_freq == 0:
            if footprint is None:
                footprint = obs_memory.state_footprint(kstate)
            sink.memory_record(
                i, device=obs_memory.device_memory_stats(),
                state=footprint)
    sink.close()
    records, _ = obs_sink.read_jsonl_tolerant(spath)
    metrics = obs_gate.gate_metrics(records)
    obj = obs_gate.write_baseline(
        metrics, args.obs_baseline,
        meta={'bench': 'flagship_lm_obs_baseline',
              'workload': (f'transformer_lm_{args.size}_seq{args.seq}'
                           f'_b{args.batch}_v{args.vocab}'),
              'backend': jax.default_backend(),
              'cadence': f'f{f_freq}_i{i_freq}',
              'source': spath})
    emit({'obs_baseline': args.obs_baseline, **obj['metrics']})


# ---------------------------------------------------------------------------
# Orchestrator
# ---------------------------------------------------------------------------

def spawn_phase(args, phase, inverse_method=None):
    cmd = [sys.executable, os.path.abspath(__file__), '--phase', phase,
           '--size', args.size, '--seq', str(args.seq),
           '--batch', str(args.batch), '--vocab', str(args.vocab),
           '--iters', str(args.iters)]
    if args.model_dtype:
        cmd += ['--model-dtype', args.model_dtype]
    if args.bf16_factors:
        cmd.append('--bf16-factors')
    if args.bf16_inverses:
        cmd.append('--bf16-inverses')
    if args.precond_dtype:
        cmd += ['--precond-dtype', args.precond_dtype]
    if inverse_method:
        cmd += ['--inverse-method', inverse_method]
    if args.kfac_approx:
        cmd += ['--kfac-approx', args.kfac_approx]
    if args.attn_block_size:
        cmd += ['--attn-block-size', str(args.attn_block_size)]
    if args.inv_pipeline_chunks > 1:
        cmd += ['--inv-pipeline-chunks', str(args.inv_pipeline_chunks)]
    try:
        out = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=2400, cwd=REPO)
    except subprocess.TimeoutExpired:
        return 'failed: timeout', None, {}
    for line in reversed(out.stdout.strip().splitlines()):
        try:
            obj = json.loads(line)
            extras = {k: v for k, v in obj.items()
                      if k not in ('phase_result', 'mfu')}
            return obj['phase_result'], obj.get('mfu'), extras
        except Exception:
            continue
    from bench import extract_failure_line
    msg = extract_failure_line(out.stderr, limit=160)
    return ('failed: ' + (msg or f'rc={out.returncode}'), None, {})


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument('--size', default='xl')
    p.add_argument('--seq', type=int, default=1024)
    p.add_argument('--batch', type=int, default=4)
    p.add_argument('--vocab', type=int, default=32768)
    p.add_argument('--iters', type=int, default=10)
    p.add_argument('--model-dtype', default='bf16',
                   choices=['fp32', 'bf16'])
    p.add_argument('--bf16-factors', action='store_true',
                   help='bf16 factor storage (halves the multi-GB '
                        'factor state at xl scale; decompositions stay '
                        'fp32 — the config-5 policy)')
    p.add_argument('--bf16-inverses', action='store_true',
                   help='bf16 inverse storage (inv_dtype; the '
                        'reference supports half-precision inverse '
                        'storage too — preconditioner.py:149)')
    p.add_argument('--inverse-method', default=None)
    p.add_argument('--precond-dtype', default=None,
                   choices=['fp32', 'bf16'],
                   help='precondition-contraction operand dtype (KFAC '
                        'precond_compute_dtype; default None = the '
                        'bit-identical legacy fp32-upcast path). bf16 '
                        'is the r6 A/B leg targeting the +18%% '
                        'every-step precondition tax; pair with '
                        '--bf16-inverses for the bf16-resident read.')
    p.add_argument('--attn-block-size', type=int, default=None,
                   help='memory-efficient chunked attention (long-seq '
                        'single-chip legs)')
    p.add_argument('--firing-methods', nargs='+',
                   default=['auto', 'cholesky', 'eigen'],
                   help='inverse methods to measure standalone firings '
                        'for (drop eigen at xl dims: the fp32-HIGHEST '
                        'polish at 4096+ is the recorded CNN-flagship '
                        'negative, seconds per firing)')
    p.add_argument('--precond-ab', action='store_true',
                   help='r6 precondition-dtype A/B: one sgd leg, then '
                        'the capture-free nofactor leg per dtype '
                        'variant (fp32 legacy / bf16 / bf16 with '
                        'bf16-resident inverses) — isolates the '
                        'every-step precondition tax per contraction '
                        'dtype without re-measuring the shared legs')
    p.add_argument('--inv-pipeline-chunks', type=int, default=1,
                   help='r9 firing-spread leg: with K > 1 the firing '
                        'phase additionally projects the pipelined '
                        'per-chunk firing costs from the measured '
                        'bucket_parts (LPT per-matrix packing, the '
                        'runtime plan) — max_chunk_ms is the residual '
                        'spike a pipelined window pays per step')
    p.add_argument('--kfac-approx', default=None,
                   choices=['expand', 'reduce'],
                   help='r13 weight-sharing approximation for the '
                        'K-FAC phases (factors/firing legs): reduce '
                        'sums/averages over the sequence axis before '
                        'the factor covariance')
    p.add_argument('--approx-ab', action='store_true',
                   help='r13 expand/reduce/SGD quality ladder: for '
                        'each --ladder d_model, run a short REAL '
                        'training leg per approximation (identical '
                        'hyperparameters) and emit the loss curves + '
                        'steady-state ms/iter — the committed evidence '
                        'rows (FLAGSHIP_LM_r13_APPROX.jsonl; PERF.md '
                        'r13 decision rule)')
    p.add_argument('--ladder', type=int, nargs='+',
                   default=[512, 1024, 2048],
                   help='--approx-ab d_model rungs (d512 -> d2048)')
    p.add_argument('--ab-steps', type=int, default=60,
                   help='training steps per --approx-ab leg')
    p.add_argument('--ab-seq', type=int, default=64)
    p.add_argument('--ab-batch', type=int, default=8)
    p.add_argument('--ab-vocab', type=int, default=512)
    p.add_argument('--ab-layers', type=int, default=2)
    p.add_argument('--ab-lr', type=float, default=0.1)
    p.add_argument('--ab-f', type=int, default=5,
                   help='--approx-ab factor-update cadence')
    p.add_argument('--ab-i', type=int, default=20,
                   help='--approx-ab inverse-update cadence')
    p.add_argument('--ab-d', type=int, default=512,
                   help='internal: quality-phase d_model')
    p.add_argument('--staleness-ab', action='store_true',
                   help='r14 inv_staleness convergence A/B: for each '
                        '--ladder d_model, run a short REAL training '
                        'leg with the default firing schedule '
                        '("eager") and one with inv_staleness=1 + '
                        'deferred_factor_reduction ("stale"), '
                        'identical hyperparameters — the loss-curve '
                        'difference isolates the one-window inverse '
                        'staleness (PERF.md r14 decision rule; '
                        'committed FLAGSHIP_LM_r14_STALENESS.jsonl)')
    p.add_argument('--lowrank-ab', action='store_true',
                   help='r19 randomized low-rank convergence A/B: for '
                        'each --ladder d_model, one leg with the '
                        'default exact dispatch ("exact") and one '
                        'with --ab-lowrank-rank engaged on the '
                        "rung's FFN factor dims (\"lowrank\", "
                        'threshold 2*d by default), identical '
                        'hyperparameters — the loss-curve difference '
                        'isolates the truncation (PERF.md r19 '
                        'decision rule; committed '
                        'FLAGSHIP_LM_r19_LOWRANK.jsonl)')
    p.add_argument('--ab-lowrank-rank', type=int, default=64,
                   help='--lowrank-ab truncation rank (must be below '
                        'every engaged dim)')
    p.add_argument('--ab-lowrank-threshold', type=int, default=0,
                   help='--lowrank-ab engagement threshold; 0 = '
                        "2*d_model (engages the rung's 4d FFN dims, "
                        'keeps the d-dim attention projections exact)')
    p.add_argument('--quality-leg', default=None,
                   choices=['sgd', 'expand', 'reduce', 'eager',
                            'stale', 'exact', 'lowrank'],
                   help='internal: which --approx-ab/--staleness-ab/'
                        '--lowrank-ab leg this subprocess runs')
    p.add_argument('--obs-baseline', default=None, metavar='PATH',
                   help='record a per-step metrics stream at this '
                        'config and reduce it to a committed '
                        'observability-gate baseline JSON (see '
                        'observability.gate; the stream itself lands '
                        'at PATH.source.jsonl). Use --size small on '
                        'CPU.')
    p.add_argument('--phase', default=None,
                   help='internal: run one phase in this process')
    args = p.parse_args(argv)

    if args.obs_baseline:
        return run_obs_baseline(args)

    if args.phase == 'quality':
        return run_quality_leg(args)

    if args.phase:
        return run_phase(args)

    # This parent starts children that each need the chip, and a chip
    # belongs to one process: it never initialises a JAX backend. Every
    # child says in its own row where it ran (bench.device_fields).
    if args.approx_ab or args.staleness_ab or args.lowrank_ab:
        if args.approx_ab:
            legs, ab_label = ('sgd', 'expand', 'reduce'), 'kfac_approx'
        elif args.staleness_ab:
            legs, ab_label = ('eager', 'stale'), 'inv_staleness'
        else:
            legs, ab_label = ('exact', 'lowrank'), 'inv_lowrank'
        for d in args.ladder:
            for leg in legs:
                cmd = [sys.executable, os.path.abspath(__file__),
                       '--phase', 'quality', '--quality-leg', leg,
                       '--ab-d', str(d),
                       '--ab-steps', str(args.ab_steps),
                       '--ab-seq', str(args.ab_seq),
                       '--ab-batch', str(args.ab_batch),
                       '--ab-vocab', str(args.ab_vocab),
                       '--ab-layers', str(args.ab_layers),
                       '--ab-lr', str(args.ab_lr),
                       '--ab-f', str(args.ab_f),
                       '--ab-i', str(args.ab_i),
                       '--ab-lowrank-rank', str(args.ab_lowrank_rank),
                       '--ab-lowrank-threshold',
                       str(args.ab_lowrank_threshold)]
                row = {'config': 4, 'ab': ab_label,
                       'd_model': d, 'leg': leg,
                       'seq': args.ab_seq, 'batch': args.ab_batch,
                       'vocab': args.ab_vocab,
                       'layers': args.ab_layers,
                       'steps': args.ab_steps, 'lr': args.ab_lr,
                       'cadence': f'f{args.ab_f}_i{args.ab_i}'}
                if leg == 'lowrank':
                    row['inv_lowrank_rank'] = args.ab_lowrank_rank
                    row['inv_lowrank_dim_threshold'] = (
                        args.ab_lowrank_threshold or 2 * d)
                try:
                    out = subprocess.run(cmd, capture_output=True,
                                         text=True, timeout=7200,
                                         cwd=REPO)
                except subprocess.TimeoutExpired:
                    emit({**row, 'error': 'timeout'})
                    continue
                for line in reversed(out.stdout.strip().splitlines()):
                    try:
                        obj = json.loads(line)
                        obj.pop('phase_result', None)
                        emit({**row, **obj})
                        break
                    except Exception:
                        continue
                else:
                    from bench import extract_failure_line
                    emit({**row, 'error': extract_failure_line(
                        out.stderr, limit=160)
                        or f'rc={out.returncode}'})
        return

    if args.precond_ab:
        workload = (f'transformer_lm_{args.size}_seq{args.seq}'
                    f'_b{args.batch}_v{args.vocab}')
        sgd_ms, sgd_mfu, where = spawn_phase(args, 'sgd')
        emit({'config': 4, 'ab': 'precond_dtype', 'phase': 'sgd',
              'workload': workload, **where,
              'model_dtype': args.model_dtype,
              'ms_per_iter': sgd_ms, 'mfu': sgd_mfu})
        for label, pdt, binv in (('fp32_legacy', None, False),
                                 ('bf16', 'bf16', False),
                                 ('bf16_resident', 'bf16', True)):
            args.precond_dtype = pdt
            args.bf16_inverses = binv
            ms, mfu, where = spawn_phase(args, 'nofactor')
            row = {'config': 4, 'ab': 'precond_dtype', 'leg': label,
                   'phase': 'nofactor', 'workload': workload,
                   **where, 'model_dtype': args.model_dtype,
                   'precond_dtype': pdt, 'bf16_inverses': binv,
                   'ms_per_iter': ms, 'mfu': mfu, 'sgd': sgd_ms}
            if isinstance(ms, (int, float)) and isinstance(
                    sgd_ms, (int, float)):
                row['nonfactor_vs_sgd'] = round(ms / sgd_ms, 3)
            emit(row)
        return

    rows, mfus = {}, {}
    for mode in ('sgd', 'nofactor', 'factors'):
        rows[mode], mfus[mode], where = spawn_phase(args, mode)
        emit({'config': 4, 'phase': mode, 'size': args.size, **where,
              'seq': args.seq, 'batch': args.batch, 'vocab': args.vocab,
              'model_dtype': args.model_dtype,
              'precond_dtype': args.precond_dtype,
              'attn_block_size': args.attn_block_size,
              'ms_per_iter': rows[mode], 'mfu': mfus.get(mode)})
    firings = {}
    for method in args.firing_methods:
        firings[method], _, extras = spawn_phase(args, 'firing',
                                                 inverse_method=method)
        emit({'config': 4,
              'phase': f'inverse_firing_standalone_{method}',
              'ms_per_firing': firings[method], **extras})

    methods = [(m, v) for m, v in firings.items()
               if isinstance(v, (int, float))]
    ok = all(isinstance(rows.get(k), (int, float))
             for k in ('sgd', 'factors')) and methods
    if not ok:
        emit({'config': 4, 'partial': rows, 'firings': firings})
        return
    base = rows['nofactor'] if isinstance(
        rows.get('nofactor'), (int, float)) else rows['factors']
    factor_cost = max(rows['factors'] - base, 0.0)
    for fire_method, fire_ms in methods:
        out = {'config': 4, 'row_schema': 2,
               'workload': (f'transformer_lm_{args.size}_seq{args.seq}'
                            f'_b{args.batch}_v{args.vocab}'
                            + (f'_ab{args.attn_block_size}'
                               if args.attn_block_size else '')),
               'unit': 'ms/iter', 'sgd': rows['sgd'],
               'mfu_sgd': mfus.get('sgd'),
               'precond_dtype': args.precond_dtype,
               'every_iter': base,
               'factor_step_extra': round(factor_cost, 2),
               'inv_firing_method': fire_method,
               'inv_firing_ms': round(fire_ms, 2)}
        for label, f, i in (('stress_f1_i10', 1, 10),
                            ('imagenet_default_f10_i100', 10, 100),
                            ('production_f50_i500', 50, 500)):
            total = base + factor_cost / f + fire_ms / i
            out[label] = round(total, 2)
            out[label + '_vs_sgd'] = round(total / rows['sgd'], 3)
            if mfus.get('sgd'):
                out[label + '_mfu'] = round(
                    mfus['sgd'] * rows['sgd'] / total, 4)
        emit(out)


if __name__ == '__main__':
    main()
