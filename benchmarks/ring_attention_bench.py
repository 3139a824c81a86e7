"""Ring-attention perf characterization (the round-4 verdict's last
uncharacterized subsystem: "correctness tests + dryrun only").

The reference has no long-context machinery at all (sequence models are
BPTT-35 truncated — reference examples/torch_language_model.py:52,
SURVEY.md §5), so there is no reference number here; the bench
characterizes this framework's own ring attention
(``parallel/sequence.py``) on the axes that decide whether it is usable
at scale:

1. **On-chip per-device compute** (real TPU): one ring device's exact
   compute schedule — s online-softmax folds over (T_local x T_local)
   blocks, the same fold code ``ring_self_attention`` runs between
   ``ppermute``s — vs monolithic ``local_causal_attention`` at the same
   global sequence. A real s-device ring costs ~full/s per device plus
   fold overhead; this leg measures that overhead directly on the MXU.
   (Collectives cannot run single-chip; the fold loop is the entire
   per-device compute, so emulating it IS the honest on-chip number.
   ``tests/test_sequence_parallel.py`` pins the emulation's outputs to
   monolithic attention rows so the bench measures the real algorithm.)

2. **Memory ceiling** (real TPU): peak HBM for monolithic attention's
   O(S^2) logits vs the ring's O(T_local^2) block, including the OOM
   probe at the first monolithic-infeasible S. Each leg is its own
   subprocess, one at a time (flagship methodology: a leg that runs
   out of HBM takes only its own process down; the parent never
   initialises a JAX backend).

3. **ICI overlap model** (analytic, parameterized like
   kaisa_decision_model.py — one real chip, no ICI to measure): per
   ring step a device sends its K/V block (2*B*T_local*H*D*bytes) while
   folding one block; comm hides iff block_bytes/ici_bw < measured
   block compute time. Reports the break-even T_local.

4. **CPU-mesh scaling shape** (8 virtual devices, 1-core host —
   RELATIVE ORDERING ONLY): ring at s in {2,4,8} vs monolithic at the
   same global S. All s devices share one core, so ideal ring wall time
   equals monolithic (same total FLOPs); the measured ratio is the
   fold + ppermute overhead under equal compute.

Timing follows bench.py's documented methodology: chained calls (the
attention output perturbs the next query, so no two calls see the same
input) timed as one window closed by a scalar host fetch, with a
100%-MFU FLOPs floor rejecting impossible readings.

    python benchmarks/ring_attention_bench.py [--batch 4] [--heads 16]
        [--head-dim 64] [--ici-gbps 40] [--out RING_ATTENTION.json]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def attn_fwd_flops(batch, seq_q, seq_k, heads, head_dim):
    """QK^T + AV matmul FLOPs (causal mask zeroes but does not skip)."""
    return 4 * batch * heads * seq_q * seq_k * head_dim


def ring_device_schedule(q, k_stack, v_stack, *, device_idx, ring_size,
                         causal=True):
    """One ring device's exact compute: fold ``ring_size`` K/V blocks
    with the online-softmax update, no collectives.

    Mirrors ``ring_self_attention``'s ``fold_block`` — same
    ``_block_attend`` + shared ``_fold_update`` accumulation
    (parallel/sequence.py), so the measured schedule cannot drift from
    the shipped algorithm — with ``ppermute`` replaced by indexing into
    the pre-staged block stacks: after ``step`` rotations device
    ``idx`` holds the block of device ``(idx - step) % s``.

    q: (B, T_local, H, D); k_stack/v_stack: (s, B, T_local, H, D).
    Returns (B, T_local, H, D) fp32, equal to the corresponding row
    block of monolithic attention (pinned in test_sequence_parallel).
    """
    import jax
    import jax.numpy as jnp

    from distributed_kfac_pytorch_tpu.parallel import sequence as seq

    s = ring_size
    b, t, h, d = q.shape
    scale = 1.0 / (d ** 0.5)
    local_pos = jnp.arange(t)
    qpos = device_idx * t + local_pos

    def body(step, carry):
        o, m, l = carry
        src = (device_idx - step) % s
        kpos = src * t + local_pos
        k_cur = jax.lax.dynamic_index_in_dim(k_stack, src, 0,
                                             keepdims=False)
        v_cur = jax.lax.dynamic_index_in_dim(v_stack, src, 0,
                                             keepdims=False)
        bm, bo, bl = seq._block_attend(q, k_cur, v_cur,
                                       scale, qpos, kpos, causal)
        return seq._fold_update(o, m, l, bm, bo, bl)

    o0 = jnp.zeros((b, t, h, d), jnp.float32)
    m0 = jnp.full((b, h, t), seq._NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, t), jnp.float32)
    o, m, l = jax.lax.fori_loop(0, s, body, (o0, m0, l0))
    l = jnp.maximum(jnp.moveaxis(l, 1, 2)[..., None], 1e-30)
    return o / l


# ---------------------------------------------------------------------------
# On-chip phases (fresh subprocess each, flagship methodology)
# ---------------------------------------------------------------------------

def emit(obj):
    print(json.dumps(obj), flush=True)


def _peak_hbm_bytes():
    """Device peak-allocation high-water mark, or None where the
    backend reports no allocator statistics (the CPU)."""
    import jax
    stats = jax.local_devices()[0].memory_stats()
    return int(stats['peak_bytes_in_use']) if stats else None


def _time_attn(fn, q, k, v, flops, repeats=8, attempts=3):
    """Chained-window timing: each call's output perturbs the next
    query (a hard data dependency),
    one window per batch closed by a scalar host fetch, readings below
    the 100%-MFU floor discarded (bench.py methodology)."""
    import jax
    import jax.numpy as jnp

    import bench as B

    floor_ms = flops / B.detected_tpu_peak() * 1e3

    @jax.jit
    def step(q, k, v):
        out = fn(q, k, v)
        # Perturbation must clear the operand dtype's ULP (bf16 ULP at
        # |q|~0.1 is ~4e-4) or q_next rounds back to q bitwise and the
        # anti-memoization chain goes inert; 1e-3*out flips a large
        # fraction of elements while drifting |q| by <1% over a full
        # timing run.
        q_next = q + (1e-3 * out).astype(q.dtype)
        return q_next, out[0, 0, 0, 0]

    q, probe = step(q, k, v)  # compile + warm
    float(probe)
    readings = []
    for _ in range(attempts):
        t0 = time.perf_counter()
        for _ in range(repeats):
            q, probe = step(q, k, v)
        float(probe)  # closes the window
        per_call = (time.perf_counter() - t0) / repeats * 1000.0
        if per_call >= floor_ms:
            readings.append(per_call)
    if not readings:
        raise RuntimeError(
            f'every reading fell below the {floor_ms:.3f} ms FLOPs '
            'floor — cached/elided execution suspected')
    return sorted(readings)[len(readings) // 2]


def phase_full(args):
    import jax.numpy as jnp
    import numpy as np

    from distributed_kfac_pytorch_tpu.parallel import sequence as seq

    b, h, d, s_len = args.batch, args.heads, args.head_dim, args.seq
    dt = jnp.float32 if args.fp32_operands else jnp.bfloat16
    rng = np.random.RandomState(0)
    mk = lambda: jnp.asarray(rng.randn(b, s_len, h, d) * 0.1, dt)  # noqa: E731
    q, k, v = mk(), mk(), mk()
    flops = attn_fwd_flops(b, s_len, s_len, h, d)
    ms = _time_attn(seq.local_causal_attention, q, k, v, flops)
    emit({'phase_result': round(ms, 3),
          'tflops': round(flops / (ms * 1e-3) / 1e12, 2),
          'peak_hbm_bytes': _peak_hbm_bytes(),
          'logits_bytes': b * h * s_len * s_len * 4})


def phase_ringdev(args):
    import jax.numpy as jnp
    import numpy as np

    b, h, d = args.batch, args.heads, args.head_dim
    s = args.ring
    t_local = args.seq // s
    dt = jnp.float32 if args.fp32_operands else jnp.bfloat16
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, t_local, h, d) * 0.1, dt)
    kst = jnp.asarray(rng.randn(s, b, t_local, h, d) * 0.1, dt)
    vst = jnp.asarray(rng.randn(s, b, t_local, h, d) * 0.1, dt)
    # Device s-1 (every block causally live) — compute cost is
    # idx-independent since masked blocks are computed, not skipped.
    fn = lambda q, k, v: ring_device_schedule(  # noqa: E731
        q, k, v, device_idx=s - 1, ring_size=s)
    flops = s * attn_fwd_flops(b, t_local, t_local, h, d)
    ms = _time_attn(fn, q, kst, vst, flops)
    emit({'phase_result': round(ms, 3),
          'tflops': round(flops / (ms * 1e-3) / 1e12, 2),
          'peak_hbm_bytes': _peak_hbm_bytes(),
          'block_bytes': b * h * t_local * t_local * 4,
          'kv_wire_bytes_per_step': (2 * b * t_local * h * d
                                     * jnp.dtype(dt).itemsize)})


def _time_attn_grad(fn, q, k, v, flops, repeats=4, attempts=3):
    """Chained-window timing of value_and_grad (the training path):
    the q-gradient perturbs the next query.

    Differentiates wrt ALL of (q, k, v) — a q-only grad lets XLA
    dead-code-eliminate the dK = dS^T q and dV = P^T dO matmuls (an
    earlier cut measured exactly 2.04x fwd, the 2-matmul backward,
    while reporting the 3x-fwd convention's TFLOP/s)."""
    import jax

    import bench as B

    floor_ms = flops / B.detected_tpu_peak() * 1e3

    @jax.jit
    def step(q, k, v):
        val, (gq, gk, gv) = jax.value_and_grad(
            lambda q, k, v: fn(q, k, v).sum(), argnums=(0, 1, 2))(q, k, v)
        # Full-tensor reductions of gk/gv keep every backward matmul
        # live (a single-element probe could be slice-simplified away);
        # q carries the anti-memoization chain.
        q_next = q + (1e-3 * gq).astype(q.dtype)
        return q_next, val + gk.mean() + gv.mean()

    q, probe = step(q, k, v)
    float(probe)
    readings = []
    for _ in range(attempts):
        t0 = time.perf_counter()
        for _ in range(repeats):
            q, probe = step(q, k, v)
        float(probe)
        per_call = (time.perf_counter() - t0) / repeats * 1000.0
        if per_call >= floor_ms:
            readings.append(per_call)
    if not readings:
        raise RuntimeError('all readings below FLOPs floor')
    return sorted(readings)[len(readings) // 2]


def phase_chunked(args):
    """Chunked (memory-efficient) single-device attention: fwd or
    fwd+bwd (--grad) at global seq with --ring reused as seq/block."""
    import jax.numpy as jnp
    import numpy as np

    from distributed_kfac_pytorch_tpu.parallel import sequence as seq

    b, h, d, s_len = args.batch, args.heads, args.head_dim, args.seq
    block = s_len // args.ring
    dt = jnp.float32 if args.fp32_operands else jnp.bfloat16
    rng = np.random.RandomState(0)
    mk = lambda: jnp.asarray(rng.randn(b, s_len, h, d) * 0.1, dt)  # noqa: E731
    q, k, v = mk(), mk(), mk()
    fn = lambda q, k, v: seq.chunked_causal_attention(  # noqa: E731
        q, k, v, block_size=block)
    fwd = attn_fwd_flops(b, s_len, s_len, h, d)
    if args.grad:
        ms = _time_attn_grad(fn, q, k, v, 3 * fwd)
        flops = 3 * fwd
    else:
        ms = _time_attn(fn, q, k, v, fwd)
        flops = fwd
    emit({'phase_result': round(ms, 3),
          'tflops': round(flops / (ms * 1e-3) / 1e12, 2),
          'block_size': block,
          'live_logits_gb': round(b * h * s_len * block * 4 / 2**30, 2)})


def phase_full_grad(args):
    """Monolithic attention fwd+bwd — probes the training-memory wall."""
    import jax.numpy as jnp
    import numpy as np

    from distributed_kfac_pytorch_tpu.parallel import sequence as seq

    b, h, d, s_len = args.batch, args.heads, args.head_dim, args.seq
    dt = jnp.float32 if args.fp32_operands else jnp.bfloat16
    rng = np.random.RandomState(0)
    mk = lambda: jnp.asarray(rng.randn(b, s_len, h, d) * 0.1, dt)  # noqa: E731
    q, k, v = mk(), mk(), mk()
    ms = _time_attn_grad(seq.local_causal_attention, q, k, v,
                         3 * attn_fwd_flops(b, s_len, s_len, h, d))
    emit({'phase_result': round(ms, 3)})


def phase_cpumesh(args):
    """Scaling shape on the 8-virtual-device CPU mesh — relative
    ordering only on a shared-core host.

    The worker sets its own platform (the conftest/dryrun mechanism).
    The compilation cache stays off — warm cache reads segfault on the
    multi-device CPU backend."""
    import jax

    jax.config.update('jax_platforms', 'cpu')
    jax.config.update('jax_num_cpu_devices', 8)

    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from distributed_kfac_pytorch_tpu.parallel import sequence as seq
    from distributed_kfac_pytorch_tpu.utils import (
        disable_compilation_cache,
        raise_cpu_collective_timeouts,
    )

    raise_cpu_collective_timeouts()
    disable_compilation_cache()
    assert jax.default_backend() == 'cpu' and jax.device_count() == 8

    b, h, d, s_len = 2, 4, 32, args.seq
    rng = np.random.RandomState(0)
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.randn(b, s_len, h, d) * 0.1, jnp.float32)
    q, k, v = mk(), mk(), mk()

    def timed(fn, *xs):
        out = fn(*xs)
        float(out[0, 0, 0, 0].astype(jnp.float32))
        best = float('inf')
        for _ in range(3):
            t0 = time.perf_counter()
            out = fn(*xs)
            float(out[0, 0, 0, 0].astype(jnp.float32))
            best = min(best, time.perf_counter() - t0)
        return best * 1000.0

    rows = {'full_1dev': round(
        timed(jax.jit(seq.local_causal_attention), q, k, v), 2)}
    for s in (2, 4, 8):
        mesh = Mesh(np.asarray(jax.devices()[:s]), (seq.SEQ_AXIS,))
        # kfaclint: waive[retrace-jit-in-loop] per-mesh-size bench harness: one program per shard count, compile excluded from timing
        ring = jax.jit(jax.shard_map(
            seq.ring_self_attention, mesh=mesh,
            in_specs=(P(None, seq.SEQ_AXIS),) * 3,
            out_specs=P(None, seq.SEQ_AXIS), check_vma=False))
        rows[f'ring_{s}dev'] = round(timed(ring, q, k, v), 2)
    emit({'phase_result': rows})


# ---------------------------------------------------------------------------
# Orchestrator
# ---------------------------------------------------------------------------

def spawn(phase, seq=0, ring=0, args=None, env=None, timeout=1200,
          grad=False):
    cmd = [sys.executable, os.path.abspath(__file__), '--phase', phase,
           '--seq', str(seq), '--ring', str(ring),
           '--batch', str(args.batch), '--heads', str(args.heads),
           '--head-dim', str(args.head_dim)]
    if args.fp32_operands:
        cmd.append('--fp32-operands')
    if grad:
        cmd.append('--grad')
    run_env = dict(os.environ)
    if env:
        run_env.update(env)
    try:
        out = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=timeout, cwd=REPO, env=run_env)
    except subprocess.TimeoutExpired:
        return None, {'error': 'timeout'}
    for line in reversed(out.stdout.strip().splitlines()):
        try:
            obj = json.loads(line)
            return obj['phase_result'], obj
        except Exception:
            continue
    from bench import extract_failure_line
    msg = extract_failure_line(out.stderr)
    return None, {'error': msg or f'rc={out.returncode}'}


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument('--batch', type=int, default=4)
    p.add_argument('--heads', type=int, default=16)
    p.add_argument('--head-dim', type=int, default=64)
    p.add_argument('--ici-gbps', type=float, default=40.0,
                   help='effective per-link ICI bandwidth (PARAMETER, '
                        'not a measurement — one chip here); 40 GB/s is '
                        'a conservative public v4-class figure')
    p.add_argument('--seq', type=int, default=0)
    p.add_argument('--ring', type=int, default=0)
    p.add_argument('--phase', default=None)
    p.add_argument('--cpu-seq', type=int, default=1024)
    p.add_argument('--skip-onchip', action='store_true',
                   help='keep the on-chip rows already in --out and '
                        'rerun only the CPU-mesh leg')
    p.add_argument('--grad', action='store_true',
                   help='time value_and_grad instead of forward '
                        '(chunked / full_grad phases)')
    p.add_argument('--chunked-only', action='store_true',
                   help='keep existing rows in --out and (re)run only '
                        'the chunked/memory-efficient legs')
    p.add_argument('--fp32-operands', action='store_true',
                   help='A/B control: upcast q/k/v to fp32 before the '
                        'attention op (the pre-optimization behavior; '
                        'the product contract is operand-dtype matmuls '
                        'with fp32 accumulation)')
    p.add_argument('--out', default=os.path.join(REPO,
                                                 'RING_ATTENTION.json'))
    args = p.parse_args(argv)

    if args.phase:
        if args.phase != 'cpumesh':
            # On-chip workers see the TPU exactly as bench.py does
            # (incl. the persistent compile cache); the cpumesh
            # worker configures its own platform and must NOT enable
            # the cache (multi-device-CPU segfault gotcha).
            import bench  # noqa: F401
        {'full': phase_full, 'ringdev': phase_ringdev,
         'chunked': phase_chunked, 'full_grad': phase_full_grad,
         'cpumesh': phase_cpumesh}[args.phase](args)
        return

    if args.skip_onchip or args.chunked_only:
        # Partial reruns PATCH an existing artifact; refuse to silently
        # fall back to the full (expensive, OOM-probing) sweep.
        if not os.path.exists(args.out):
            raise SystemExit(f'{args.out} not found: --skip-onchip/'
                             '--chunked-only patch an existing artifact')
        with open(args.out) as f:
            result = json.load(f)
    else:
        result = _run_onchip_legs(args)
        result['fp32_operand_controls'] = _run_fp32_controls(args)

    # --skip-onchip refreshes ONLY the CPU-mesh leg (its help text);
    # chunked on-chip legs run on a full sweep or --chunked-only.
    if args.chunked_only or (not args.skip_onchip
                             and result.get('chunked') is None):
        result['chunked'] = _run_chunked_legs(args)
        with open(args.out, 'w') as f:
            json.dump(result, f, indent=1)
        if args.chunked_only:
            print(json.dumps({'wrote': args.out}))
            return

    # Leg 4: CPU-mesh scaling shape (the worker sets its own platform).
    _, extra = spawn('cpumesh', seq=args.cpu_seq, args=args,
                     timeout=3600)
    result['cpumesh'] = {
        'note': 'RELATIVE ORDERING ONLY: 8 virtual devices on a '
                'shared-core host; equal total FLOPs at every s, so '
                'ratio to full_1dev is pure fold+ppermute overhead',
        'seq': args.cpu_seq,
        'ms': extra.get('phase_result', extra.get('error'))}

    with open(args.out, 'w') as f:
        json.dump(result, f, indent=1)
    print(json.dumps({'wrote': args.out}))


def _run_fp32_controls(args):
    """A/B control rows: operands upcast to fp32 before the attention
    op (the pre-optimization compute behavior; ring wire traffic was
    always input-dtype). Part of the standard sweep so the artifact is
    reproducible from one invocation."""
    import copy

    ctl_args = copy.copy(args)
    ctl_args.fp32_operands = True
    out = {'note': 'operands upcast to fp32 before the attention op '
                   '(pre-optimization compute behavior). '
                   'kv_wire_bytes_per_step reflects the control\'s own '
                   'fp32 inputs; the product ring always permutes '
                   'input-dtype blocks.'}
    for name, phase, s_len, ring in (
            ('full_seq4096', 'full', 4096, 0),
            ('ringdev_seq4096_r8', 'ringdev', 4096, 8),
            ('ringdev_seq16384_r8', 'ringdev', 16384, 8)):
        ms, extra = spawn(phase, seq=s_len, ring=ring, args=ctl_args)
        out[name] = extra if ms else {'error': extra.get('error')}
        print(json.dumps({name: out[name]}), flush=True)
    return out


def _run_chunked_legs(args):
    """Single-device memory-efficient attention: fwd + the TRAINING
    path (fwd+bwd through the checkpointed scan), against monolithic
    attention's gradient wall."""
    out = {'note': 'chunked_causal_attention (block fold + per-block '
                   'jax.checkpoint). grad tflops use the 3x-fwd model-'
                   'FLOPs convention (checkpoint recompute not counted, '
                   'so achieved hardware TFLOP/s is ~4/3 of reported)',
           'rows': []}
    for phase, s_len, ring, grad in (
            ('full_grad', 2048, 1, True),
            # 4096 is monolithic training's largest FITTING size; the
            # wall is 8192, where even the forward OOMs (onchip rows),
            # so no full_grad probe is needed there.
            ('full_grad', 4096, 1, True),
            ('chunked', 4096, 4, True),
            ('chunked', 8192, 8, True),
            ('chunked', 16384, 16, True),
            ('chunked', 16384, 16, False)):
        ms, extra = spawn(phase, seq=s_len, ring=ring, args=args,
                          grad=grad, timeout=2400)
        row = {'phase': phase, 'seq': s_len, 'grad': grad,
               'ms': ms if ms else extra.get('error')}
        if ms:
            for key in ('tflops', 'block_size', 'live_logits_gb'):
                if extra.get(key) is not None:
                    row[key] = extra[key]
        out['rows'].append(row)
        print(json.dumps(row), flush=True)
    return out


def _run_onchip_legs(args):
    dt = 'fp32' if args.fp32_operands else 'bf16'
    result = {'shape': {'batch': args.batch, 'heads': args.heads,
                        'head_dim': args.head_dim,
                        'dtype': f'{dt} operands, fp32 accumulate/'
                                 'softmax (the module contract)'},
              'flops_note': 'fwd-only characterization of the attention '
                            'op; training cost ~3x per matmul-backward '
                            'convention',
              'onchip': [], 'cpumesh': None}

    # Leg 1+2: monolithic vs per-ring-device compute + memory.
    for s_len, ring in ((2048, 8), (4096, 8), (8192, 8), (16384, 8),
                        (32768, 16)):
        row = {'seq': s_len, 'ring': ring}
        if s_len <= 8192:   # 8192: expected OOM probe (17 GB logits)
            ms, extra = spawn('full', seq=s_len, args=args)
            row['full_ms'] = ms if ms else extra.get('error')
            if ms:
                row['full_tflops'] = extra.get('tflops')
                row['full_peak_hbm_gb'] = (
                    round(extra['peak_hbm_bytes'] / 2**30, 2)
                    if extra.get('peak_hbm_bytes') else None)
            row['full_logits_gb'] = round(
                args.batch * args.heads * s_len * s_len * 4 / 2**30, 2)
        ms, extra = spawn('ringdev', seq=s_len, ring=ring, args=args)
        row['ringdev_ms'] = ms if ms else extra.get('error')
        if ms:
            row['ringdev_tflops'] = extra.get('tflops')
            row['ringdev_peak_hbm_gb'] = (
                round(extra['peak_hbm_bytes'] / 2**30, 2)
                if extra.get('peak_hbm_bytes') else None)
            row['block_ms'] = round(ms / ring, 3)
            wire = extra['kv_wire_bytes_per_step']
            row['kv_wire_mb_per_step'] = round(wire / 2**20, 2)
            comm_ms = wire / (args.ici_gbps * 1e9) * 1e3
            row['ici_comm_ms_per_step_at_param_bw'] = round(comm_ms, 3)
            row['comm_hidden'] = bool(comm_ms < ms / ring)
            if isinstance(row.get('full_ms'), float):
                ideal = row['full_ms'] / ring
                row['fold_overhead_vs_ideal'] = round(ms / ideal - 1, 3)
        result['onchip'].append(row)
        print(json.dumps(row), flush=True)

    # ICI overlap verdict from MEASURED rows only (an earlier pure-
    # quadratic extrapolation from the largest block predicted a ~306-
    # token comm-bound crossover that the measured small-block rows
    # refute: small folds are overhead-dominated, i.e. even SLOWER than
    # quadratic, so comm hides even more easily there).
    margins = {}
    for r in result['onchip']:
        if isinstance(r.get('ringdev_ms'), float):
            t_local = r['seq'] // r['ring']
            comm = r['ici_comm_ms_per_step_at_param_bw']
            # Key by (seq, ring): distinct rows can share one T_local.
            margins[f's{r["seq"]}_r{r["ring"]}_tl{t_local}'] = round(
                r['block_ms'] / comm, 1)
    if margins:
        result['ici_overlap_margin'] = margins
        result['ici_overlap_note'] = (
            'block-fold compute time / per-step K/V transfer time at '
            f'the {args.ici_gbps} GB/s ICI parameter; >1 means comm '
            'fully overlaps. Every measured block size overlaps '
            f'(min margin {min(margins.values())}x).')
    return result


if __name__ == '__main__':
    main()
