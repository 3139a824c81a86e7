"""Microbenchmark: per-firing eigendecomposition cost on factor stacks.

Times ONE inverse-update firing's worth of decompositions over a
synthetic "trained-like" ResNet-32 factor set (the BASELINE.md north
star workload: many medium SPD matrices, bucketed by size), comparing

  - xla:   bucketed vmapped backend eigh (cold, data-dependent runtime)
  - warm:  ops.linalg.eigh_polish seeded with a mildly-rotated exact
           basis — the steady-state of eigh_method='auto' tracking
  - newton / cholesky: the damped-inverse paths (no eigenbasis), for
           the floor

Trained-like matters: XLA's TPU eigh runs ~5x longer on spread-spectrum
covariance factors than on near-identity ones (PERF.md §6), which is
exactly what EWMA factors become during training. Spectra here span
1e-4..tr with log-uniform spacing.

Run on the target chip:
    python benchmarks/eigh_methods.py [--repeats 5]
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

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

from distributed_kfac_pytorch_tpu.ops import linalg, pallas_kernels

# ResNet-32 / CIFAR-10 factor-size multiset (A: c*9+1 per conv + first
# conv 28 + linear 65; G: out-channels), as the bucketed eigen path sees
# it (preconditioner._size_buckets).
RESNET32_DIMS = ([28] + [145] * 11 + [289] * 10 + [577] * 10 + [65]
                 + [16] * 12 + [32] * 10 + [64] * 11 + [10])


def trained_like_stack(rng, dims):
    """{dim: (B, dim, dim) fp32 stack} with spread covariance spectra."""
    buckets = {}
    for dim in sorted(set(dims)):
        count = dims.count(dim)
        mats = []
        for _ in range(count):
            spec = np.geomspace(1e-4, 1.0, dim) * np.exp(
                rng.standard_normal(dim) * 0.3)
            q, _ = np.linalg.qr(rng.standard_normal((dim, dim)))
            mats.append((q * spec) @ q.T)
        buckets[dim] = jnp.asarray(np.stack(mats), jnp.float32)
    return buckets


def rand_rotation(rng, n, angle):
    """Random orthogonal rotation with spectral angle ``angle`` rad.

    ``expm(S)`` for a random skew-symmetric ``S`` rescaled so its
    largest rotation angle is exactly ``angle``. Canonical helper shared
    with tests/test_warm_eigh.py — keep the two call sites on this one
    implementation.
    """
    s = rng.standard_normal((n, n))
    s = (s - s.T) / 2
    w, v = np.linalg.eigh(1j * s)       # expm via eigh of Hermitian iS
    w = w * (angle / np.max(np.abs(w)))
    return np.real((v * np.exp(-1j * w)) @ v.conj().T)


def precond_rel_err(a, q, d, lam=1e-3, rng=None, exact_wv=None):
    """Relative error of applying ``(A + lam I)^-1`` via (q, d) vs exact.

    The metric K-FAC consumes: basis mixing inside eigenvalue clusters
    cancels here (the damping quotient is ~flat across a cluster), while
    genuine basis/eigenvalue error shows up directly. Canonical helper
    shared with tests/test_warm_eigh.py and benchmarks/middim_eigen.py.

    ``exact_wv``: optional precomputed ``(w, v) = np.linalg.eigh(a)``
    oracle — pass it when cold eighs at the bench's dims are exactly the
    expensive thing under study (middim_eigen); ``a`` is ignored then.
    """
    rng = rng or np.random.default_rng(7)
    dr, qr = exact_wv if exact_wv is not None else np.linalg.eigh(a)
    g = rng.standard_normal((qr.shape[0], 3))
    out = q @ ((q.T @ g) / (np.maximum(d, 0)[:, None] + lam))
    ref = qr @ ((qr.T @ g) / (np.maximum(dr, 0)[:, None] + lam))
    return float(np.linalg.norm(out - ref) / np.linalg.norm(ref))


def warm_bases(rng, buckets, angle=0.1):
    """Exact bases rotated by ``angle`` rad (spectral) — the tracked
    state one firing later. The rotation is normalized to a total
    *angle*, not a per-entry scale: per-firing eigenvector motion under
    EWMA drift is angle-bounded regardless of dimension."""
    out = {}
    for dim, stack in buckets.items():
        qs = []
        for m in np.asarray(stack):
            _, q = np.linalg.eigh(m)
            qs.append(q @ rand_rotation(rng, dim, angle))
        out[dim] = jnp.asarray(np.stack(qs), jnp.float32)
    return out


def _fetch_scalar(out):
    """Host-fetch one element — a hard data dependency that closes the
    timing window (see bench.py's methodology notes)."""
    leaf = jax.tree.leaves(out)[0]
    return float(leaf.reshape(-1)[0].real)


def time_fn(fn, args, repeats):
    """Min-of-repeats timing with a scalar-fetch window close.

    Repeats reuse identical inputs; middim_eigen.time_variants
    (distinct inputs per repeat) is the hardened variant — prefer it
    for new benches; this helper keeps the rounds-3/4 artifact
    methodology reproducible."""
    out = fn(*args)  # compile + warm
    _fetch_scalar(out)
    best = float('inf')
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args)
        _fetch_scalar(out)
        best = min(best, time.perf_counter() - t0)
    return best * 1000.0, out


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument('--repeats', type=int, default=5)
    p.add_argument('--polish-iters', type=int, default=16)
    args = p.parse_args(argv)

    rng = np.random.default_rng(0)
    buckets = trained_like_stack(rng, RESNET32_DIMS)
    bases = warm_bases(rng, buckets)

    @jax.jit
    def run_xla(bk):
        return {d: linalg.batched_eigh(s, 'xla', clip=0.0)
                for d, s in bk.items()}

    @jax.jit
    def run_warm(bk, qs):
        return {d: linalg.batched_eigh(
            s, 'warm', clip=0.0, q_prev=qs[d],
            polish_iters=args.polish_iters) for d, s in bk.items()}

    @jax.jit
    def run_newton(bk):
        return {d: pallas_kernels.damped_inverse_stack(s, 0.003, 'newton')
                for d, s in bk.items()}

    @jax.jit
    def run_cholesky(bk):
        return {d: pallas_kernels.damped_inverse_stack(s, 0.003,
                                                       'cholesky')
                for d, s in bk.items()}

    results = {}
    results['xla_ms'], _ = time_fn(run_xla, (buckets,), args.repeats)
    results['warm_ms'], warm_out = time_fn(run_warm, (buckets, bases),
                                           args.repeats)
    results['newton_ms'], _ = time_fn(run_newton, (buckets,), args.repeats)
    results['cholesky_ms'], _ = time_fn(run_cholesky, (buckets,),
                                        args.repeats)

    # Accuracy of the warm firing (max preconditioning-op error).
    worst = 0.0
    for dim, stack in buckets.items():
        qs, ds = warm_out[dim]
        for i, m in enumerate(np.asarray(stack)):
            worst = max(worst, precond_rel_err(
                m, np.asarray(qs[i]), np.asarray(ds[i]), rng=rng))

    print(json.dumps({
        'workload': 'resnet32_factor_set_trained_like',
        'n_matrices': len(RESNET32_DIMS),
        'backend': jax.default_backend(),
        'unit': 'ms/firing',
        **{k: round(v, 3) for k, v in results.items()},
        'warm_speedup_vs_xla': round(results['xla_ms']
                                     / results['warm_ms'], 2),
        'warm_worst_precond_rel_err': float(f'{worst:.3g}'),
    }))


if __name__ == '__main__':
    main()
