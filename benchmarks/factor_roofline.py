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
"""

from __future__ import annotations

import argparse
import json
import os
import sys

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


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument('--inner', type=int, default=30)
    args = p.parse_args(argv)
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
