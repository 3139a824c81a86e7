"""Mid-dim eigen extension experiment (VERDICT r4 #7).

The 'auto' inverse dispatch sends factor dims > `auto_eigen_max_dim`
(640) to damped Cholesky because the fp32-HIGHEST warm-polish matmuls
blow up at flagship dims (measured 41x at 4609, PERF.md round 3).
Between 640 and ~2304 the *eigen semantics* (joint damping read at
precondition time) are lost to the split operator. This bench measures
whether a CHEAPER polish — HIGH-precision (bf16 3-pass) matmuls and/or
fewer iterations — makes eigen competitive with Cholesky at 1024/2304,
and what it costs in basis accuracy (preconditioning relative error
vs the exact eigh oracle).

Methodology notes (both learned the hard way):
- the warm basis is the exact basis rotated by a *spectral-angle*-
  normalized rotation (`eigh_methods.rand_rotation`, angle 0.1 rad —
  the tracked steady state one firing later); an entry-scaled skew is
  NOT small at these dims (spectral angle grows ~sqrt(dim) and leaves
  polish's capture range — the first cut of this bench did that and
  measured nonsense 0.9 rel errs).
- every timed repeat runs on a distinct input stack, so no repeat can
  be answered from an earlier identical execution (PERF.md rounds 1-5
  record a 0.05 ms "eigh" reading taken without this).

Per (dim, config): a stack of `n_mats` trained-like SPD factors
(`eigh_methods.trained_like_stack` spectra), one firing =
`eigh_polish` from the warm basis. Cholesky row =
`damped_inverse_stack`. Accuracy metric = `eigh_methods.
precond_rel_err` (the quantity K-FAC consumes).

    python benchmarks/middim_eigen.py [--dims 1024 2304] [--repeats 3]
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

from benchmarks import eigh_methods
from benchmarks.eigh_methods import precond_rel_err, trained_like_stack
from distributed_kfac_pytorch_tpu.ops import linalg, pallas_kernels
from distributed_kfac_pytorch_tpu.utils import enable_compilation_cache


def subspace_rotation(rng, n, angle, k=16):
    """Rotation of exact spectral ``angle`` confined to a random
    rank-``k`` subspace: Q = I + U (R_k - I) U^T with U orthonormal
    (QR of an n x k Gaussian) and R_k a k x k rotation of spectral
    angle ``angle`` (`eigh_methods.rand_rotation` at k x k, trivial).

    `eigh_methods.rand_rotation` is exact over the FULL space but costs
    a complex n x n eigh — minutes per matrix at 2304 on this 1-core
    host (the first run of this bench timed out on exactly that); a
    random-subspace rotation keeps the spectral-angle normalization at
    O(n^2 k) and still forces polish to repair mixing across ``k``
    random directions."""
    k = min(k, n)
    u, _ = np.linalg.qr(rng.standard_normal((n, k)))
    rk = eigh_methods.rand_rotation(rng, k, angle)
    return np.eye(n) + u @ (rk - np.eye(k)) @ u.T


def make_variants(dim, n_mats, n_variants, angle=0.1, seed=0):
    """``n_variants`` (stack, warm_basis) pairs with distinct data so
    repeated timings cannot hit the execution-memoization cache; the
    exact (w, v) of variant 0 is kept as the accuracy oracle.

    Only variant 0 gets the exact-eigh treatment (the expensive host
    prep); timing variants i>0 are variant 0 with a distinct diagonal
    jitter — different bytes (cache-busting) but identical shapes and
    fixed iteration counts, so the measured runtime is the same
    program's."""
    rng = np.random.default_rng(seed)
    stack = np.asarray(trained_like_stack(rng, [dim] * n_mats)[dim])
    ws, qs, warm = [], [], []
    for m in stack:
        w, q = np.linalg.eigh(m)
        ws.append(w)
        qs.append(q)
        warm.append(q @ subspace_rotation(rng, dim, angle))
    oracle = (np.stack(ws), np.stack(qs))
    warm0 = jnp.asarray(np.stack(warm), jnp.float32)
    variants = [(jnp.asarray(stack, jnp.float32), warm0)]
    for vi in range(1, n_variants):
        jit = 1e-4 * (1 + vi) * np.eye(dim, dtype=np.float32)
        variants.append((jnp.asarray(stack + jit, jnp.float32), warm0))
    return variants, oracle


def _fetch_scalar(out):
    """Host-fetch one element of the output — a hard data dependency
    that closes the timing window (bench.py's methodology; this
    bench's first cut recorded a 0.04 ms '2304 eigh' without it)."""
    leaf = jax.tree.leaves(out)[0]
    return float(leaf.reshape(-1)[0].real)


def time_variants(fn, variants, repeats):
    """Compile on variant 0, then time one call per distinct variant;
    returns (best seconds, variant-0 output)."""
    out0 = fn(*variants[0])  # compile
    _fetch_scalar(out0)
    best = float('inf')
    for i in range(1, min(repeats + 1, len(variants))):
        args = variants[i]
        t0 = time.perf_counter()
        _fetch_scalar(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best, out0


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument('--dims', type=int, nargs='+', default=[1024, 2304])
    p.add_argument('--n-mats', type=int, default=4)
    p.add_argument('--repeats', type=int, default=3)
    p.add_argument('--out', default='MIDDIM_EIGEN.json')
    args = p.parse_args(argv)
    if args.repeats < 1:
        p.error('--repeats must be >= 1')
    enable_compilation_cache()

    rows = []
    for dim in args.dims:
        variants, (ws, vs) = make_variants(dim, args.n_mats,
                                           args.repeats + 1)
        configs = [
            ('polish_fp32HIGHEST_8', None, 8),
            ('polish_HIGH_8', jax.lax.Precision.HIGH, 8),
            ('polish_HIGH_4', jax.lax.Precision.HIGH, 4),
        ]
        for label, precision, iters in configs:
            # kfaclint: waive[retrace-jit-in-loop] per-config bench harness: one jit per method config, compile excluded from timing
            fn = jax.jit(jax.vmap(functools.partial(
                linalg.eigh_polish, iters=iters, precision=precision)))
            sec, (qs, ds) = time_variants(fn, variants, args.repeats)
            errs = [precond_rel_err(None, np.asarray(qs[i]),
                                    np.asarray(ds[i]),
                                    exact_wv=(ws[i], vs[i]))
                    for i in range(args.n_mats)]
            rows.append({'dim': dim, 'method': label,
                         'ms_per_firing': round(sec * 1e3, 2),
                         'worst_precond_rel_err':
                             float(f'{np.max(errs):.3g}')})
            print(json.dumps(rows[-1]), flush=True)
        # kfaclint: waive[retrace-jit-in-loop] per-dim bench harness: one jit per dim rung, compile excluded from timing
        fn = jax.jit(lambda s, _q: pallas_kernels.damped_inverse_stack(
            s, 1e-3, 'cholesky'))
        sec, _ = time_variants(fn, variants, args.repeats)
        rows.append({'dim': dim, 'method': 'cholesky',
                     'ms_per_firing': round(sec * 1e3, 2),
                     'worst_precond_rel_err': None})
        print(json.dumps(rows[-1]), flush=True)
        # kfaclint: waive[retrace-jit-in-loop] per-dim bench harness: one jit per dim rung, compile excluded from timing
        fn = jax.jit(lambda s, _q: jnp.linalg.eigh(s))
        sec, _ = time_variants(fn, variants, args.repeats)
        rows.append({'dim': dim, 'method': 'xla_eigh_cold',
                     'ms_per_firing': round(sec * 1e3, 2),
                     'worst_precond_rel_err': 0.0})
        print(json.dumps(rows[-1]), flush=True)

    with open(args.out, 'w') as f:
        json.dump({'n_mats_per_dim': args.n_mats,
                   'backend': jax.default_backend(),
                   'warm_angle_rad': 0.1,
                   'note': 'per-firing decomposition cost of a '
                           f'{args.n_mats}-matrix stack at each dim; '
                           'polish rows = eigh_method auto steady '
                           'state; decide auto_eigen_max_dim',
                   'rows': rows}, f, indent=1)
    print(json.dumps({'rows': rows}))


if __name__ == '__main__':
    main()
