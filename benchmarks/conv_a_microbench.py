"""Per-shape conv A-factor implementation shootout (on-chip).

Times each patch-extraction implementation on each distinct conv shape
class of the tracked ResNet-32/CIFAR workload (plus the ImageNet stem
class), in isolation, so dispatch decisions rest on per-shape
measurements instead of whole-step inference — the discipline the
round-2 crosscov regression bought us.

Each timed leg scans ``inner`` A-factor computations over a chained
f32 carry (the input is nudged each iteration so no two contractions
see identical data), then applies bench.py's batch-window timing.
Every reading has a measured same-structure null-program baseline
(per-call dispatch + chain body) subtracted, so the reported ms are the
A-factor op alone and reproduce across ``--inner`` choices; the
baseline itself is reported per shape as ``overhead_baseline``.

    python benchmarks/conv_a_microbench.py [--inner 20]
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

# (label, batch, h, w, c, kernel, strides) — the distinct conv shape
# classes of the tracked workloads. CIFAR stages from cifar_resnet
# (batch 512); ImageNet classes cover every ResNet-50 3x3 stage plus
# the 7x7/stride-2 stem.
SHAPES = [
    ('cifar_stage1_c16_32x32', 512, 32, 32, 16, (3, 3), (1, 1)),
    ('cifar_stage2_c32_16x16', 512, 16, 16, 32, (3, 3), (1, 1)),
    ('cifar_stage3_c64_8x8', 512, 8, 8, 64, (3, 3), (1, 1)),
    ('imagenet_c64_56x56', 64, 56, 56, 64, (3, 3), (1, 1)),
    ('imagenet_c128_28x28', 64, 28, 28, 128, (3, 3), (1, 1)),
    ('imagenet_c256_14x14', 64, 14, 14, 256, (3, 3), (1, 1)),
    ('imagenet_c512_7x7', 64, 7, 7, 512, (3, 3), (1, 1)),
    ('imagenet_stem_c3_224x224_k7s2', 64, 224, 224, 3, (7, 7), (2, 2)),
    ('imagenet_c128_s2_56to28', 64, 56, 56, 128, (3, 3), (2, 2)),
]

IMPLS = ['slices', 'crosscov', 'dilated', 'pairs']


def build_runner(x0, impl, inner, kernel, strides, null=False):
    """``null=True`` builds the overhead-baseline program: identical
    scan/carry/chain structure with the A-factor computation replaced by
    a trivial stand-in — what it measures is the per-call dispatch
    plus the chain-body cost, which is
    subtracted from every impl reading so the reported numbers are the
    A-factor op alone and reproduce across --inner choices."""
    if impl is not None:
        os.environ['KFAC_CONV_PATCH_IMPL'] = impl
    d = kernel[0] * kernel[1] * x0.shape[-1] + 1

    def body(carry, _):
        x, acc = carry
        if null:
            a = jnp.full((d, d), jnp.float32(1e-9)) * x[0, 0, 0, 0]
        else:
            a = F.conv2d_a_factor(x, kernel, strides, 'SAME', True)
        # Chain: nudge the input by a value-dependent epsilon so the
        # next iteration's contraction is a genuinely new problem.
        x = x * (1.0 + 1e-6 * a[0, 0])
        return (x, acc + a), a[0, 0]

    @jax.jit
    def run(carry):
        carry, out = jax.lax.scan(body, carry, None, length=inner)
        return carry, out[-1]

    return run, (x0, jnp.zeros((d, d), jnp.float32))


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument('--inner', type=int, default=20)
    args = p.parse_args(argv)

    for label, b, h, w, c, kernel, strides in SHAPES:
        x0 = jax.random.normal(jax.random.PRNGKey(0), (b, h, w, c),
                               jnp.float32)
        row = {'shape': label}
        run, carry = build_runner(x0, None, args.inner, kernel, strides,
                                  null=True)
        base = B.time_chained(run, carry, args.inner)
        row['overhead_baseline'] = round(base, 3)
        for impl in IMPLS:
            key = impl
            if impl == 'crosscov':
                # crosscov silently falls back to slices outside its
                # Wp*C <= 1024 regime — label such rows honestly so the
                # table never shows crosscov "competitive" on shapes
                # where it never ran.
                probe = F._conv_a_cov_crosscov(
                    x0[:1].astype(jnp.bfloat16), kernel, strides,
                    'SAME', None)
                if probe is None:
                    row['crosscov'] = 'fallback:slices'
                    continue
            run, carry = build_runner(x0, impl, args.inner, kernel,
                                      strides)
            try:
                ms = B.time_chained(run, carry, args.inner)
                row[key] = round(max(ms - base, 0.0), 3)
            except Exception as e:  # e.g. compile failure on one impl
                row[key] = f'error: {type(e).__name__}'
        os.environ.pop('KFAC_CONV_PATCH_IMPL', None)
        print(json.dumps(row), flush=True)


if __name__ == '__main__':
    main()
