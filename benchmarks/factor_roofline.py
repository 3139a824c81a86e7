"""HBM roofline audit of the CIFAR conv A-factor phase (round 4).

The round-3 claim "the slices path sits at the materialized-patch HBM
roofline" was asserted from a per-layer decomposition, never
demonstrated as achieved-bytes/s (VERDICT r3 Weak #1). This tool
measures, with the microbench's hoist-proof chained methodology
(value-dependent input nudge per iteration, null-baseline subtraction,
floor-gated timing — see conv_a_microbench.build_runner):

  copy      read+write of an N-byte tensor -> achieved HBM bandwidth
            (the empirical peak the roofline is computed against);
  cov       the covariance contraction alone on a pre-materialized
            patch tensor (its cost is dominated by the patch READ);
  full      the production A-factor call (extraction + covariance,
            fused however XLA chooses).

(An extraction-alone leg is not measurable: with anything less than a
full consumer XLA dead-code-eliminates the unmaterialized patch lanes,
and a full consumer IS a covariance-class read — measured and
discarded in round 4.)

Roofline logic: ``implied_gb_s`` is the full leg's materialization
traffic (patch write + patch read + input read) over its wall time; if
it approaches the achieved copy bandwidth, the phase is memory-bound
at the materialization traffic and further gains require never
materializing patches (the measured negatives: fused Pallas kernel,
crosscov; and 'pairs', which wins only at d > 640). ``full_vs_floor``
< 1 means XLA avoided part of that traffic (partial fusion).

    python benchmarks/factor_roofline.py [--inner 30]

``--leg blocked`` (PR 30) is another question on another shape class:
the dense self-covariance ``a^T a`` of ``ops.factors.get_cov``, bf16
operands, as one contraction (``_cov_full``) against its upper block
triangle (``_cov_blocked``: one loop over the block pairs) at every
way of cutting d into 2-4 equal 128-aligned column blocks 384-3072
wide. It sets the ``COV_BLOCK_*`` constants of ``ops/factors.py``;
``gate`` marks the cut they choose.
Several distinct operands go through one jitted call and every output
is returned, so XLA can drop or share nothing; ``pct_peak`` is the
FLOPs a variant issues over its time, as a share of the chip's bf16
peak (``bench.detected_tpu_peak``: 197 TFLOP/s on a v5e).

    python benchmarks/factor_roofline.py --leg blocked [--shapes 8192x3072 ...]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import bench as B  # noqa: E402
from distributed_kfac_pytorch_tpu.ops import factors as F  # noqa: E402

SHAPES = [
    ('cifar_stage1_c16_32x32', 512, 32, 32, 16),
    ('cifar_stage2_c32_16x16', 512, 16, 16, 32),
    ('cifar_stage3_c64_8x8', 512, 8, 8, 64),
]


def chained(body_fn, carry0, inner):
    """Time a carry-chained scan of ``body_fn`` (hoist-proof: the carry
    is nudged by a value computed FROM each iteration's result, so no
    iteration is loop-invariant)."""
    @jax.jit
    def run(carry):
        carry, out = jax.lax.scan(body_fn, carry, None, length=inner)
        return carry, out[-1]

    return B.time_chained(run, carry0, inner)


def null_leg(x0, inner):
    def body(x, _):
        probe = jnp.float32(1e-9) * x.reshape(-1)[0].astype(jnp.float32)
        return x * (1.0 + 1e-6 * probe.astype(x.dtype)), probe
    return chained(body, x0, inner)


def copy_leg(x0, inner):
    def body(x, _):
        y = x + jnp.asarray(1.0, x.dtype)           # read + write
        probe = y.reshape(-1)[0].astype(jnp.float32)
        return y * (1.0 + 1e-6 * probe.astype(x.dtype) * 0), probe
    return chained(body, x0, inner)


def cov_leg(p0, inner):
    def body(p, _):
        cov = F.get_cov(p, scale=p.shape[0])
        probe = cov[0, 0]
        return p * (1.0 + 1e-6 * probe.astype(p.dtype)), probe
    return chained(body, p0, inner)


def full_leg(x0, inner, kernel):
    os.environ['KFAC_CONV_PATCH_IMPL'] = 'slices'
    try:
        def body(x, _):
            a = F.conv2d_a_factor(x, kernel, (1, 1), 'SAME', True)
            return x * (1.0 + 1e-6 * a[0, 0].astype(x.dtype)), a[0, 0]
        return chained(body, x0, inner)
    finally:
        os.environ.pop('KFAC_CONV_PATCH_IMPL', None)


# The blocked leg's shapes: the two benchmark cells' factor dims at
# their 8192 rows, and conv A dims (3x3 kernels over 128/256/512
# channels) at 128 images of 7x7 positions, which no cell runs.
BLOCKED_SHAPES = [(8192, d) for d in (768, 1536, 2048, 3072, 6144)] + [
    (128 * 49, d) for d in (1152, 2304, 4608)]


def block_sides(d):
    """k = 2..4 column blocks a side that cut d into equal 128-aligned
    blocks 384-3072 wide."""
    return [k for k in (2, 3, 4)
            if d % (k * 128) == 0 and 384 <= d // k <= 3072]


def time_many(fn, operands, calls=5, repeats=3):
    """Median ms per operand of ``fn`` (one jitted call over all of
    ``operands``, every output returned), ``calls`` calls in flight."""
    run = jax.jit(lambda xs: [fn(x) for x in xs])
    jax.block_until_ready(run(operands))
    readings = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        outs = [run(operands) for _ in range(calls)]
        jax.block_until_ready(outs)
        readings.append((time.perf_counter() - t0) * 1e3
                        / (calls * len(operands)))
    return sorted(readings)[len(readings) // 2]


def blocked_leg(shapes, out_path):
    device = jax.devices()[0]
    # Off the TPU the leg is a rehearsal: times, but no share of a peak.
    peak = B.detected_tpu_peak() if device.platform == 'tpu' else None
    lines = []
    for rows, d in shapes:
        full_flops = 2 * rows * d * d
        n = int(max(2, min(32, 2e12 // full_flops)))
        operands = [
            jax.random.normal(jax.random.PRNGKey(i), (rows, d),
                              jnp.bfloat16) for i in range(n)]
        gate = F.cov_block_side(rows, d)
        full_ms = None
        for k in [None, *block_sides(d)]:
            if k is None:
                flops = full_flops
                ms = full_ms = time_many(
                    lambda x: F._cov_full(x, rows, None), operands)
            else:
                flops = full_flops * (k + 1) / (2 * k)
                ms = time_many(
                    lambda x: F._cov_blocked(x, k, rows, None), operands)
            line = {
                'leg': 'blocked', 'rows': rows, 'd': d,
                'k': k or 1, 'width': d // (k or 1),
                'gate': k == gate, 'operands_a_call': n,
                'device': device.device_kind, 'ms': round(ms, 4),
                'vs_full': round(ms / full_ms, 4),
                'flops_share_of_full': round(flops / full_flops, 4),
                'pct_peak': (round(100 * flops / (ms * 1e-3) / peak, 2)
                             if peak else 'not measured'),
            }
            lines.append(line)
            print(json.dumps(line), flush=True)
    if out_path:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, 'w') as f:
            f.writelines(json.dumps(line) + '\n' for line in lines)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument('--inner', type=int, default=30)
    p.add_argument('--leg', choices=['conv', 'blocked'], default='conv')
    p.add_argument('--shapes', nargs='*', default=None,
                   help='blocked leg: ROWSxD ... in place of the '
                        "cells' shapes")
    p.add_argument('--out', default='chiprun_out/factor_roofline/'
                                    'blocked.jsonl',
                   help='blocked leg: where the lines are also written')
    args = p.parse_args(argv)
    if args.leg == 'blocked':
        shapes = BLOCKED_SHAPES if args.shapes is None else [
            tuple(int(v) for v in s.split('x')) for s in args.shapes]
        return blocked_leg(shapes, args.out)
    kernel = (3, 3)

    # Empirical bandwidth: read+write a ~150 MB bf16 tensor.
    big = jax.random.normal(jax.random.PRNGKey(9),
                            (512, 32, 32, 144)).astype(jnp.bfloat16)
    base_big = null_leg(big, args.inner)
    ms_copy = max(copy_leg(big, args.inner) - base_big, 1e-6)
    gbs = big.size * 2 * 2 / ms_copy * 1e3 / 1e9
    print(json.dumps({'leg': 'copy', 'mbytes': round(big.size * 2 / 1e6),
                      'ms': round(ms_copy, 3),
                      'achieved_gb_s': round(gbs, 1)}), flush=True)

    for label, b, h, w, c in SHAPES:
        x0 = jax.random.normal(jax.random.PRNGKey(0),
                               (b, h, w, c)).astype(jnp.bfloat16)
        d = kernel[0] * kernel[1] * c
        rows = b * h * w
        patch_mb = rows * d * 2 / 1e6
        input_mb = b * h * w * c * 2 / 1e6
        base = null_leg(x0, args.inner)
        p0 = jax.random.normal(jax.random.PRNGKey(1),
                               (rows, d)).astype(jnp.bfloat16)
        base_p = null_leg(p0, args.inner)
        ms_cov = max(cov_leg(p0, args.inner) - base_p, 0.0)
        ms_full = max(full_leg(x0, args.inner, kernel) - base, 1e-6)
        # Materialization roofline at the ACHIEVED copy bandwidth:
        # patch write (extract) + patch read (cov operand) + input read.
        mat_mb = 2 * patch_mb + input_mb
        floor_ms = mat_mb * 1e6 / (gbs * 1e9) * 1e3
        implied = mat_mb * 1e6 / (ms_full * 1e-3) / 1e9
        print(json.dumps({
            'shape': label, 'patch_mb': round(patch_mb, 1),
            'cov_ms': round(ms_cov, 3),
            'full_ms': round(ms_full, 3),
            'materialization_floor_ms_at_achieved_bw':
                round(floor_ms, 3),
            'full_vs_floor': round(ms_full / max(floor_ms, 1e-9), 2),
            'implied_gb_s': round(implied, 1),
            'implied_vs_achieved_copy_bw': round(implied / gbs, 2),
        }), flush=True)


if __name__ == '__main__':
    main()
