"""Pallas TPU kernels for the K-FAC hot ops.

The O(n^3) factor inversion is the framework's make-or-break kernel
(SURVEY.md §7 "Hard parts"; reference does it with sequential cuSOLVER
calls per layer, kfac/layers/base.py:432-441). Two properties make a
custom kernel pay off on TPU:

  - the iteration that replaces the factorization (Newton–Schulz, see
    ``ops.linalg.newton_schulz_inverse``) is matmul-only, so it runs on
    the MXU at full tilt; and
  - between iterations nothing needs to leave the chip — a VMEM-resident
    kernel holds ``M`` and the iterate ``X`` on-chip for the whole solve,
    eliminating the HBM round trip per matmul that a stock XLA lowering
    of the same loop pays (2 reads + 1 write of n^2 floats per matmul,
    ~60x the arithmetic-intensity at n=512).

``batched_inverse`` dispatches: Pallas kernel on TPU for matrices that
fit VMEM (padded to lane multiples), plain-XLA Newton–Schulz elsewhere.
Both paths are bit-compatible in structure (same iteration, fp32).
"""

from __future__ import annotations

import concurrent.futures
import functools
import os
import warnings

import jax
import jax.numpy as jnp

# Matrices up to this dim run in the VMEM-resident kernel. Measured scoped
# VMEM on v5e is ~45 B/element (M/out blocks double-buffered by Mosaic +
# X carry + Y temp): n_pad=640 allocates 18.7 MB and OOMs the 16 MB limit,
# n_pad=512 ~12 MB fits. Larger factors fall back to the stock-XLA
# Newton–Schulz (still matmul-only, just HBM-streamed between iterations).
MAX_PALLAS_DIM = 512
_LANE = 128


def _round_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m


# ---------------------------------------------------------------------------
# Fallback events
# ---------------------------------------------------------------------------
#
# A fallback to XLA happens only where it was asked for
# (KFAC_PALLAS_FALLBACK=1) and is RECORDED: a fleet run must be able to
# tell "ran fused" from "ran XLA". On a TPU a kernel that fails its
# probe raises instead (_probe_on_tpu). Events accumulate here and are
# drained into the step function's ``compile_events`` list (the same
# channel the compile/retrace events ride — build_train_step drains
# after each dispatch, engine.train_epoch forwards to the metrics sink).

_PENDING_EVENTS: list = []

#: block_batch floor for the fused patch-cov kernel: below this the
#: per-grid-step matmul is too thin to amortize the patch assembly
#: (block_batch=1 on a prime batch size was measured as the silent
#: worst case) — the dispatcher falls back to XLA instead.
MIN_FUSED_BLOCK_BATCH = 8


def record_fallback(kernel: str, reason: str) -> None:
    """Record (and warn about) one kernel's fallback to the XLA path."""
    warnings.warn(
        f'pallas kernel {kernel!r} falling back to XLA: {reason}',
        RuntimeWarning, stacklevel=2)
    _PENDING_EVENTS.append({'event': 'pallas_fallback', 'kernel': kernel,
                            'reason': reason})


def drain_pallas_events() -> list:
    """Pop all pending fallback events (oldest first)."""
    out = list(_PENDING_EVENTS)
    _PENDING_EVENTS.clear()
    return out


def _forced_fallback() -> bool:
    """KFAC_PALLAS_FALLBACK=1 forces every probe to fail (recorded):
    the smoke test's forced-fallback leg and a field kill switch."""
    return os.environ.get('KFAC_PALLAS_FALLBACK', '') not in ('', '0')


#: Largest relative error a kernel may show against its XLA reference
#: in its probe (bf16 multiplicands on the MXU, fp32 accumulation).
PROBE_RTOL = 5e-2


def _probe_on_tpu(kernel: str, rel_error) -> bool:
    """Run one kernel's parity probe on the TPU; True, or raise.

    On a TPU a kernel Mosaic refuses, or one that compiles and computes
    something else, stops the run with the kernel's name and the
    compiler's message — a warning and a quiet hand-over to XLA would
    let a later measurement be of the wrong program. ``rel_error()``
    runs the kernel and returns its largest relative error against the
    reference (NaN when the output is not finite). It runs concrete
    arrays: call it under no trace (the attention gate, asked while a
    model is traced, gives it a thread of its own).
    """
    kind = jax.devices()[0].device_kind
    try:
        rel = float(rel_error())
    except Exception as e:
        raise RuntimeError(
            f'Pallas kernel {kernel!r} does not compile or run on '
            f'{kind}: {type(e).__name__}: {e}') from e
    if not rel < PROBE_RTOL:  # NaN fails too
        raise RuntimeError(
            f'Pallas kernel {kernel!r} disagrees with its XLA reference '
            f'on {kind}: relative error {rel:.3g} >= {PROBE_RTOL}')
    return True


def max_rel_error(got, ref) -> float:
    """Largest |got - ref| over the largest |ref|; NaN if non-finite."""
    import numpy as np

    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    if not np.isfinite(got).all():
        return float('nan')
    return float(np.abs(got - ref).max()
                 / max(float(np.abs(ref).max()), 1e-30))


def _ns_inverse_kernel(m_ref, out_ref, *, iters: int, n_pad: int,
                       tol: float):
    """One matrix per grid cell: damped-inverse Newton–Schulz in VMEM.

    The damping is already folded into the input; padding rows/cols carry
    an identity block so the padded inverse is the inverse of the padded
    matrix (sliced away by the caller). Early-exits on the residual
    ``max|M X - I|`` like :func:`ops.linalg.newton_schulz_inverse`.
    """
    m = m_ref[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (n_pad, n_pad), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n_pad, n_pad), 1)
    eye = (rows == cols).astype(jnp.float32)
    bound = jnp.maximum(jnp.max(jnp.sum(jnp.abs(m), axis=-1)), 1e-30)
    x0 = eye * (1.0 / bound)

    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)

    def cond_fn(state):
        k, _, res = state
        return jnp.logical_and(k < iters, res > tol)

    def body(state):
        k, x, _ = state
        y = dot(m, x)
        res = jnp.max(jnp.abs(y - eye))
        return k + 1, 2.0 * x - dot(x, y), res

    _, out, _ = jax.lax.while_loop(
        cond_fn, body, (jnp.zeros((), jnp.int32), x0,
                        jnp.full((), jnp.inf, jnp.float32)))
    out_ref[0] = out


@functools.partial(jax.jit, static_argnames=('iters', 'tol', 'interpret'))
def _pallas_batched_ns_inverse(mats: jax.Array, damping, *,
                               iters: int = 100, tol: float = 1e-5,
                               interpret: bool = False) -> jax.Array:
    """(B, n, n) stack -> damped inverses via the VMEM-resident kernel."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, n, _ = mats.shape
    n_pad = _round_up(max(n, 8), _LANE)
    m = mats.astype(jnp.float32)
    m = m + damping * jnp.eye(n, dtype=jnp.float32)
    if n_pad != n:
        # Identity padding block: keeps the padded matrix SPD and leaves
        # the top-left inverse block equal to the unpadded inverse.
        m = jnp.pad(m, ((0, 0), (0, n_pad - n), (0, n_pad - n)))
        pad_eye = (jnp.eye(n_pad, dtype=jnp.float32)
                   .at[:n, :n].set(0.0))
        m = m + pad_eye[None]

    kernel = functools.partial(_ns_inverse_kernel, iters=iters, n_pad=n_pad,
                               tol=tol)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, n_pad, n_pad), jnp.float32),
        grid=(b,),
        in_specs=[pl.BlockSpec((1, n_pad, n_pad), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((1, n_pad, n_pad), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(m)
    return out[:, :n, :n]


def batched_inverse(mats: jax.Array, damping, *, iters: int = 100,
                    tol: float = 1e-5,
                    force_pallas: bool | None = None,
                    interpret: bool = False) -> jax.Array:
    """Damped SPD inverses of a (B, n, n) stack, TPU-kernel accelerated.

    Dispatch is static (trace-time): the Pallas path is taken on TPU
    backends for dims that fit VMEM, or when ``force_pallas`` is set
    (tests use ``force_pallas=True, interpret=True`` to exercise the
    kernel on CPU).
    """
    n = mats.shape[-1]
    if damping is None:
        damping = 0.0  # the Pallas path folds damping into the input
    use_pallas = force_pallas
    if use_pallas is None:
        use_pallas = (jax.default_backend() == 'tpu'
                      and n <= MAX_PALLAS_DIM)
    if use_pallas:
        return _pallas_batched_ns_inverse(mats, damping, iters=iters,
                                          tol=tol, interpret=interpret)
    from distributed_kfac_pytorch_tpu.ops import linalg
    return jax.vmap(
        lambda m: linalg.newton_schulz_inverse(m, damping, iters=iters,
                                               tol=tol)
    )(mats)


def _jacobi_eigh_kernel(m_ref, q_ref, d_ref, *, n_pad: int, sweeps: int):
    """One matrix per grid cell: Brent–Luk Jacobi entirely in VMEM.

    The slot iteration (ops.linalg.jacobi_slot_iteration) is pure
    elementwise/slice/concat work, so it runs unchanged inside the
    kernel; A and the eigenvector accumulator V stay on-chip for all
    ``sweeps * (n-1)`` rounds. Outputs are in final slot order — the
    caller sorts by eigenvalue outside (argsort is not Mosaic-friendly,
    and it is O(n log n) host-level work).
    """
    from distributed_kfac_pytorch_tpu.ops import linalg

    a = m_ref[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (n_pad, n_pad), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n_pad, n_pad), 1)
    eye = (rows == cols).astype(jnp.float32)
    a, v = linalg.jacobi_slot_iteration(a, eye, sweeps)
    q_ref[0] = v
    # The d block is (1, 8, n_pad) — Mosaic requires the last two block
    # dims to be (8, 128)-tileable — so replicate the eigenvalue row
    # across the sublane dim; the caller reads row 0.
    d = jnp.sum(a * eye, axis=1)
    d_ref[0] = jnp.broadcast_to(d[None, :], (8, n_pad))


@functools.partial(jax.jit, static_argnames=('sweeps', 'interpret'))
def _pallas_batched_jacobi_eigh(mats: jax.Array, *, sweeps: int,
                                interpret: bool = False):
    """(B, n, n) SPD stack -> (Q, d) ascending via the VMEM kernel."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, n, _ = mats.shape
    n_pad = n + (n % 2)
    m = mats.astype(jnp.float32)
    if n_pad != n:
        # Decoupled unit eigenvalue in the pad slot (stripped after sort).
        m = jnp.pad(m, ((0, 0), (0, 1), (0, 1)))
        pad_eye = jnp.zeros((n_pad, n_pad), jnp.float32).at[n, n].set(1.0)
        m = m + pad_eye[None]

    kernel = functools.partial(_jacobi_eigh_kernel, n_pad=n_pad,
                               sweeps=sweeps)
    q, d = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((b, n_pad, n_pad), jnp.float32),
                   jax.ShapeDtypeStruct((b, 8, n_pad), jnp.float32)),
        grid=(b,),
        in_specs=[pl.BlockSpec((1, n_pad, n_pad), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=(pl.BlockSpec((1, n_pad, n_pad), lambda i: (i, 0, 0),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((1, 8, n_pad), lambda i: (i, 0, 0),
                                memory_space=pltpu.VMEM)),
        interpret=interpret,
    )(m)
    d = d[:, 0, :]
    # Sort ascending (and strip the pad eigenpair) at the JAX level.
    order = jnp.argsort(d, axis=-1)
    d = jnp.take_along_axis(d, order, axis=-1)
    q = jnp.take_along_axis(q, order[:, None, :], axis=-1)
    if n_pad != n:
        keep = q[:, n, :] < 0.5                  # pad eigvec is exactly e_n
        idx = jax.vmap(lambda k: jnp.nonzero(k, size=n)[0])(keep)
        q = jax.vmap(lambda qq, ii: jnp.take(qq[:n], ii, axis=1))(q, idx)
        d = jnp.take_along_axis(d, idx, axis=-1)
    return q, d


def batched_jacobi_eigh(mats: jax.Array, sweeps: int | None = None, *,
                        force_pallas: bool | None = None,
                        interpret: bool = False):
    """Batched Brent–Luk eigh; the VMEM Pallas kernel is opt-in.

    Default is always the vmapped pure-JAX iteration. The Pallas kernel
    runs only with ``force_pallas=True`` and on real TPU fits VMEM only
    for n <= 64 (see the dispatch comment below for the v5e data);
    ``force_pallas=True, interpret=True`` exercises it on CPU.
    """
    from distributed_kfac_pytorch_tpu.ops import linalg

    n = mats.shape[-1]
    if sweeps is None:
        sweeps = linalg.default_jacobi_sweeps(n)
    # Hardware-validated on TPU v5e (2026-07): the kernel lowers and is
    # bit-correct (recon err ~2e-5 at n=64), but the slice/concat systolic
    # exchange makes Mosaic's scoped-VMEM stack hold several full-matrix
    # temporaries per round — n=128 already needs 18.7 MB against the
    # 16 MB limit, and at n<=64 the kernel (62 ms/8 mats) loses to the
    # stock vmapped XLA eigh. So the kernel stays opt-in for study
    # (force_pallas=True; tests exercise it in interpret mode) and the
    # default everywhere is the vmapped pure-JAX iteration. The
    # production fast path for large factors is the Newton-Schulz
    # inverse kernel above (flat ~25 ms/8 mats through n=512 on v5e,
    # vs 105 ms for batched XLA eigh at n=512).
    if force_pallas:
        return _pallas_batched_jacobi_eigh(mats, sweeps=sweeps,
                                           interpret=interpret)
    return jax.vmap(lambda m: linalg.jacobi_eigh(m, sweeps))(
        mats.astype(jnp.float32))


# ---------------------------------------------------------------------------
# Fused im2col + covariance kernel for conv A factors
# ---------------------------------------------------------------------------
#
# The conv A factor is cov(patches) where patches is the im2col expansion
# of the layer input — a KH*KW x blowup that the stock XLA lowering
# *materializes in HBM* (write + read of a ~300 MB tensor per stage-1
# CIFAR conv at batch 512). Measured on v5e, that traffic made the factor
# EWMA ~14 ms/iter of the tracked CIFAR config — the single largest
# K-FAC cost after round 1 eliminated the decompositions. This kernel
# fuses patch extraction into the covariance contraction: per grid step
# it loads a block of images into VMEM once, forms the patch block with
# static (strided) slices + one lane concat, and accumulates
#   A += P^T P      (MXU, fp32 accumulation)
#   s += ones @ P   (bias column sums, same pass)
# so HBM traffic is one read of x plus one (D, D) output — no patch
# tensor ever exists outside VMEM.

def _patch_cov_kernel(x_ref, a_ref, s_ref, *, kh, kw, sh, sw,
                      pads, oh, ow, mult_dtype):
    """One image block per grid step; accumulates into the same output.

    ``x_ref``: (bb, H, W, C) input block. ``a_ref``: (D, D) fp32
    accumulator, D = kh*kw*C in (ki, kj, c) feature order (matching the
    flattened flax kernel — the basis ops.factors.conv2d_a_factor
    permutes *to*; here it is constructed directly). ``s_ref``: (8, D)
    fp32 column-sum accumulator (row 0 meaningful; 8 rows for sublane
    tiling).
    """
    from jax.experimental import pallas as pl

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        a_ref[...] = jnp.zeros_like(a_ref)
        s_ref[...] = jnp.zeros_like(s_ref)

    # Cast BEFORE assembly: the per-shift slices and the concatenated
    # patch block are the large VMEM temporaries — in bf16 they are
    # half-size, which is what lets deep-stage blocks (e.g. 56x56x64,
    # D=576: ~3.6 MB patch block) fit alongside the (D, D) accumulator.
    x = x_ref[...].astype(mult_dtype)
    bb, h, w, c = x.shape
    (ph_lo, ph_hi), (pw_lo, pw_hi) = pads
    if ph_lo or ph_hi or pw_lo or pw_hi:
        x = jnp.pad(x, ((0, 0), (ph_lo, ph_hi), (pw_lo, pw_hi), (0, 0)))
    pieces = []
    for ki in range(kh):
        for kj in range(kw):
            sl = jax.lax.slice(
                x, (0, ki, kj, 0),
                (bb, ki + sh * (oh - 1) + 1, kj + sw * (ow - 1) + 1, c),
                (1, sh, sw, 1))
            pieces.append(sl.reshape(bb * oh * ow, c))
    p = jnp.concatenate(pieces, axis=1)
    # bf16 multiplicands ride the MXU fast path (the default covariance
    # precision contract); fp32 multiplicands request HIGHEST for the
    # strict-fp32 contract (ops.factors.get_cov).
    prec = (None if mult_dtype == jnp.bfloat16
            else jax.lax.Precision.HIGHEST)
    a_ref[...] += jnp.dot(p.T, p, preferred_element_type=jnp.float32,
                          precision=prec)
    ones = jnp.ones((8, p.shape[0]), mult_dtype)
    s_ref[...] += jnp.dot(ones, p, preferred_element_type=jnp.float32,
                          precision=prec)


@functools.partial(
    jax.jit, static_argnames=('kernel_size', 'strides', 'pads',
                              'block_batch', 'mult_bf16', 'interpret'))
def _pallas_patch_cov(x: jax.Array, *, kernel_size, strides, pads,
                      block_batch: int, mult_bf16: bool,
                      interpret: bool = False):
    """(B, H, W, C) NHWC -> (cov (D, D) fp32, colsum (D,) fp32).

    ``cov`` is the *sum* over all B*OH*OW patch rows of p p^T (caller
    applies the 1/scale); ``colsum`` the per-feature row sum.
    """
    from jax.experimental import pallas as pl  # noqa: F811 (module use)
    from jax.experimental.pallas import tpu as pltpu

    b, h, w, c = x.shape
    kh, kw = kernel_size
    sh, sw = strides
    (ph_lo, ph_hi), (pw_lo, pw_hi) = pads
    oh = (h + ph_lo + ph_hi - kh) // sh + 1
    ow = (w + pw_lo + pw_hi - kw) // sw + 1
    d = kh * kw * c
    if b % block_batch:
        raise ValueError(f'batch {b} not divisible by {block_batch=}')
    mult_dtype = jnp.bfloat16 if mult_bf16 else jnp.float32

    kernel = functools.partial(
        _patch_cov_kernel, kh=kh, kw=kw, sh=sh, sw=sw, pads=pads,
        oh=oh, ow=ow, mult_dtype=mult_dtype)
    cov, s = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((d, d), jnp.float32),
                   jax.ShapeDtypeStruct((8, d), jnp.float32)),
        grid=(b // block_batch,),
        in_specs=[pl.BlockSpec((block_batch, h, w, c),
                               lambda i: (i, 0, 0, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=(pl.BlockSpec((d, d), lambda i: (0, 0),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((8, d), lambda i: (0, 0),
                                memory_space=pltpu.VMEM)),
        interpret=interpret,
    )(x)
    return cov, s[0]


@functools.lru_cache(maxsize=1)
def fused_patch_cov_supported() -> bool:
    """One-time probe: can the fused kernel compile AND run here?

    Mosaic failures (VMEM overflow, unsupported lowering) surface at
    jit-compile or run time — not as catchable trace-time errors at the
    dispatch site — so the dispatcher calls this once per process. The
    kernel itself is opt-in (KFAC_FUSED_PATCH_COV=1 at the dispatch
    site, factors.conv2d_a_factor) and TPU-only; on a TPU a failing
    probe raises (:func:`_probe_on_tpu`).
    """
    if _forced_fallback():
        record_fallback('patch_cov', 'forced by KFAC_PALLAS_FALLBACK')
        return False
    if jax.default_backend() != 'tpu':
        return False

    def rel_error():
        import numpy as np

        from distributed_kfac_pytorch_tpu.ops import factors as F
        x = jnp.asarray(np.linspace(0, 1, 4 * 8 * 8 * 3, dtype='float32')
                        .reshape(4, 8, 8, 3))
        # Reference computed INLINE (not via conv2d_a_factor, whose TPU
        # dispatch would re-enter this probe): same formula/scale/bias
        # assembly as conv_a_factor_fused.
        p2 = np.asarray(F.extract_conv2d_patches(
            x, (3, 3), (1, 1), 'SAME')).reshape(-1, 27).astype(np.float64)
        spatial = 64
        rows = p2.shape[0]
        cov = (p2.T @ p2) / (rows * spatial * spatial)
        bias_col = p2.mean(0) / (spatial * spatial)
        ref = F._assemble_bias_factor(
            jnp.asarray(cov, jnp.float32),
            jnp.asarray(bias_col, jnp.float32), 1.0 / (spatial * spatial))
        got = conv_a_factor_fused(x, (3, 3), (1, 1), 'SAME', True,
                                  mult_bf16=True)
        return max_rel_error(got, ref)

    return _probe_on_tpu('patch_cov', rel_error)


def _fused_block_batch(b: int, bytes_per_img: int, budget: int) -> int:
    """Largest divisor of ``b`` whose image block fits ``budget`` bytes.

    Returns 0 when every fitting divisor sits below
    ``MIN_FUSED_BLOCK_BATCH`` (prime batch sizes degrade all the way to
    block_batch=1 — one image per grid step, a matmul far too thin to
    amortize the patch assembly): the caller warns and falls back to
    the XLA path rather than silently running the degenerate kernel.
    Batches smaller than the floor are exempt (the whole batch is one
    block; nothing was degraded).
    """
    block = max(1, budget // max(1, bytes_per_img))
    block = min(block, b)
    while b % block:
        block -= 1
    if block < min(b, MIN_FUSED_BLOCK_BATCH):
        return 0
    return block


def conv_a_factor_fused(a: jax.Array, kernel_size, strides, padding,
                        has_bias: bool, *, mult_bf16: bool = True,
                        block_batch: int | None = None,
                        interpret: bool = False) -> jax.Array:
    """Conv A factor via the fused VMEM patch-covariance kernel.

    Drop-in equal to ``ops.factors.conv2d_a_factor`` (same value up to
    matmul rounding; same (kh, kw, c) feature basis and bias assembly)
    for symmetric spatial padding. ``mult_bf16`` matches the default
    covariance precision contract (bf16 multiplicands, fp32
    accumulation — see ops.factors.get_cov); pass False for strict-fp32
    multiplicands.
    """
    from distributed_kfac_pytorch_tpu.ops import factors as F

    b, h, w, c = a.shape
    kh, kw = kernel_size
    sh, sw = strides
    pads = F._canonical_pad(padding, (kh, kw), (h, w), (sh, sw))
    (ph_lo, ph_hi), (pw_lo, pw_hi) = pads
    oh = (h + ph_lo + ph_hi - kh) // sh + 1
    ow = (w + pw_lo + pw_hi - kw) // sw + 1
    if block_batch is None:
        # VMEM budget: the patch block materializes ~twice (per-shift
        # pieces + their concat), plus the padded x copy (mult dtype),
        # plus the fp32 input block (x2 for Mosaic double-buffering);
        # the fp32 (D, D) + (8, D) accumulators are resident throughout
        # (x1.5 headroom). Target <= ~10 MB of the ~16 MB/core.
        mult_bytes = 2 if mult_bf16 else 4
        d_full = kh * kw * c
        fixed = int(1.5 * (d_full * d_full + 8 * d_full) * 4)
        bytes_per_img = (2 * oh * ow * d_full * mult_bytes
                         + (h + ph_lo + ph_hi) * (w + pw_lo + pw_hi)
                         * c * mult_bytes
                         + 2 * h * w * c * 4)
        # Mosaic's scoped-vmem accounting runs ~2.5x this byte model
        # (measured: a 10 MB target allocated 24.4 MB of the 16 MB
        # limit at (512,32,32,16)); target 4 MB so real usage stays
        # within limits in any surrounding program.
        budget = int(4e6) - fixed
        block_batch = _fused_block_batch(b, bytes_per_img, budget)
        if not block_batch:
            record_fallback(
                'patch_cov',
                f'batch {b} has no divisor >= {MIN_FUSED_BLOCK_BATCH} '
                f'within the VMEM budget for shape {a.shape} — the '
                'degraded block would destroy kernel efficiency')
            raise ValueError(
                f'no usable block_batch for batch {b} at this shape')
    spatial = oh * ow
    rows = b * spatial
    cov, colsum = _pallas_patch_cov(
        a, kernel_size=(kh, kw), strides=(sh, sw), pads=pads,
        block_batch=block_batch, mult_bf16=mult_bf16,
        interpret=interpret)
    cov = cov * (1.0 / (rows * spatial * spatial))
    if not has_bias:
        return cov
    bias_col = colsum * (1.0 / (rows * spatial * spatial))
    return F._assemble_bias_factor(cov, bias_col, 1.0 / (spatial * spatial))


# ---------------------------------------------------------------------------
# Fused attention (PR 26)
# ---------------------------------------------------------------------------
#
# parallel.sequence.local_causal_attention materializes (B, H, T, T)
# float32 scores, the mask and the probabilities in HBM, forward and
# backward: at gpt2-small's T=1024 about eight 402 MB passes a layer,
# half of the model's device time for 13 % of its FLOPs. The kernel
# JAX ships (jax.experimental.pallas.ops.tpu.flash_attention: forward,
# dK/dV and dQ kernels under one custom_vjp) runs the module's own
# online-softmax fold tile by tile in VMEM, builds the causal mask from
# block indices, skips the tiles above the diagonal, and recomputes a
# tile's probabilities in the backward from the saved row statistics.
# Same arithmetic as the plain path: operands enter the matmuls at
# their input dtype with float32 accumulation, max/exp/sum and the
# output accumulator are float32, P is cast to V's dtype for P.V (what
# the MXU's default precision does to the plain path's float32 P).

#: The smallest tile the dispatcher uses: T must be a whole number of
#: these. The kernel itself goes down to 128 (the lane width), but on a
#: v5e tiles of 128 lose to the plain path (8.24 ms against 5.17 ms for
#: one gpt2-small layer, forward + backward; PERF.md, PR 26).
ATTENTION_MIN_BLOCK = 256
#: Head dims the kernel was compiled and probed at on a v5e.
ATTENTION_HEAD_DIMS = (64, 128)


def attention_block_sizes(q: int, k_major: int, k: int,
                          bwd_q: int | None = None):
    """The shipped kernels' ``BlockSizes``: ``q`` query rows and
    ``k_major`` keys a grid step, folded ``k`` keys at a time, in all
    three kernels; ``bwd_q`` query rows in the two backward kernels
    where they differ."""
    from jax.experimental.pallas.ops.tpu import flash_attention as fa

    bwd_q = bwd_q or q
    return fa.BlockSizes(
        block_q=q, block_k_major=k_major, block_k=k, block_b=1,
        block_q_major_dkv=bwd_q, block_k_major_dkv=k_major,
        block_k_dkv=k, block_q_dkv=bwd_q, block_k_major_dq=k_major,
        block_k_dq=k, block_q_dq=bwd_q)


def _attention_block_sizes(t: int):
    """Tile sizes for sequence length ``t`` (a multiple of
    :data:`ATTENTION_MIN_BLOCK`), from the measured table (PERF.md,
    PR 26; that layer, forward / forward + backward): tiles of 512
    where they divide ``t`` (0.48 / 2.70 ms; 256: 0.76 / 4.07; 1024,
    which skips nothing: 0.49 / 2.79), and in the two backward kernels
    1024 query rows a step where those divide ``t`` (0.56 / 2.59)."""
    s = 512 if t % 512 == 0 else ATTENTION_MIN_BLOCK
    return attention_block_sizes(
        s, s, s, bwd_q=1024 if t % 1024 == 0 else s)


def fused_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_sizes=None) -> jax.Array:
    """Exact softmax attention in the shipped flash-attention kernels.

    ``q``/``k``/``v``: (B, T, H, D) of one dtype; returns (B, T, H, D)
    float32 like the plain path. The kernel works heads-first and
    stores its float32 accumulator in the input dtype, so the result is
    the plain path's rounded once to that dtype (the cast
    ``CausalSelfAttention`` applies to it next in any case).
    Differentiable (``custom_vjp``; dq/dk/dv come back in the input
    dtype). No (B, H, T, T) tensor reaches HBM in either direction.
    """
    from jax.experimental.pallas.ops.tpu import flash_attention as fa

    d = q.shape[-1]
    if block_sizes is None:
        block_sizes = _attention_block_sizes(q.shape[1])
    heads_first = lambda x: jnp.swapaxes(x, 1, 2)  # noqa: E731
    o = fa.flash_attention(
        heads_first(q), heads_first(k), heads_first(v), causal=causal,
        sm_scale=1.0 / (d ** 0.5), block_sizes=block_sizes)
    return heads_first(o).astype(jnp.float32)


def _tpu_interpret_forced() -> bool:
    """True inside ``pltpu.force_tpu_interpret_mode()``: every
    ``pallas_call`` traced there runs on Pallas's TPU interpreter, on
    any backend (the parity tests' way onto the kernel on the CPU)."""
    from jax._src import config as jax_config

    state = getattr(jax_config,
                    'pallas_tpu_interpret_mode_context_manager', None)
    return state is not None and state.value is not None


def _attention_probe(kernel: str, fused, q_shape, kv_shape, **kw) -> bool:
    """The kill switch (recorded), else one route's parity probe against
    the plain path, output and dq/dk/dv, on bf16 normals of q's and
    k/v's shapes; ``kw`` goes to both paths. The scalar the gradients
    are taken of is not compared: a sum of terms of either sign, its
    relative error is the paths' rounding divided by how far the sum
    cancels (0.12 at the splash probe's shapes on the CPU's
    interpreter, where output and gradients read 0.005)."""
    if _forced_fallback():
        record_fallback(kernel, 'forced by KFAC_PALLAS_FALLBACK')
        return False
    if jax.default_backend() != 'tpu':
        return True

    def rel_error():
        import numpy as np

        from distributed_kfac_pytorch_tpu.parallel import sequence
        rng = np.random.default_rng(0)
        q, k, v, w = (jnp.asarray(rng.normal(size=s), jnp.bfloat16)
                      for s in (q_shape, kv_shape, kv_shape, q_shape))

        def out_and_grads(attend):
            def loss(*qkv):
                out = attend(*qkv, causal=True, **kw)
                return jnp.sum(out * w.astype(jnp.float32)), out
            return lambda *qkv: jax.grad(loss, (0, 1, 2), has_aux=True)(
                *qkv)[::-1]

        # One program for both paths: run eagerly, each of their
        # operations would compile by itself (7 s on a v5e).
        got, ref = jax.jit(lambda *qkv: (
            out_and_grads(fused)(*qkv),
            out_and_grads(sequence.plain_attention)(*qkv)))(q, k, v)
        return max(max_rel_error(a, b) for a, b in zip(
            jax.tree.leaves(got), jax.tree.leaves(ref)))

    # The gate is asked while a model is being traced. The probe runs
    # concrete arrays through its own jit, which a thread of its own
    # does under no trace, mesh or scope of the caller's; result()
    # raises here what the probe raised there.
    with concurrent.futures.ThreadPoolExecutor(1) as pool:
        return pool.submit(_probe_on_tpu, kernel, rel_error).result()


@functools.lru_cache(maxsize=1)
def _fused_attention_probed() -> bool:
    """Once per process: the kill switch (recorded), else the probe."""
    # Two 512-tiles a side: a skipped tile, a full one, two masked.
    shape = (1, 1024, 1, 64)
    return _attention_probe('attention', fused_attention, shape, shape)


def fused_attention_applies(q, k, v, kvalid=None) -> bool:
    """The gate of :func:`fused_attention`, from what the call shows.

    Open for self-attention without a key mask (``kvalid``), operands
    of one shape and one dtype (bfloat16 or float32), a head dim in
    :data:`ATTENTION_HEAD_DIMS` and ``T`` a whole number of
    :data:`ATTENTION_MIN_BLOCK` tiles — on a TPU, where the kernel's
    once-per-process probe against the plain path must pass or raise
    (:func:`_probe_on_tpu`). On other backends it is closed unless the
    caller forced Pallas's TPU interpret mode. ``KFAC_PALLAS_FALLBACK=1``
    closes it where it would have been open, with one recorded
    ``pallas_fallback`` event. Anything else takes the plain path.
    """
    if kvalid is not None or not (q.shape == k.shape == v.shape):
        return False
    if not (q.dtype == k.dtype == v.dtype
            and q.dtype in (jnp.bfloat16, jnp.float32)):
        return False
    t, d = q.shape[1], q.shape[-1]
    if d not in ATTENTION_HEAD_DIMS or t % ATTENTION_MIN_BLOCK:
        return False
    if jax.default_backend() != 'tpu' and not _tpu_interpret_forced():
        return False
    return _fused_attention_probed()


# ---------------------------------------------------------------------------
# Windowed and grouped-head attention
# ---------------------------------------------------------------------------
#
# The flash kernel above takes one causal mask over q, k and v of one
# shape. A sliding window (query i reads keys i - W < j <= i) or fewer
# key/value heads than query heads (q head j reads kv head j // (H_q /
# H_kv)) take the splash kernels JAX ships instead
# (jax.experimental.pallas.ops.tpu.splash_attention): the mask is
# described tile by tile, the tiles outside it are skipped, forward and
# backward, and each q head reads its kv head where it lies, with no
# repeated copy. Same arithmetic as the flash route: bf16 or fp32
# operands with fp32 accumulation, fp32 row statistics, P cast to V's
# dtype for P.V; q is scaled by 1/sqrt(d) before the kernel, which
# applies no scale of its own.


SPLASH_KERNEL_MODULE = ('jax.experimental.pallas.ops.tpu.splash_attention.'
                        'splash_attention_kernel')


@functools.lru_cache(maxsize=1)
def _splash_kernels():
    """A private copy of JAX's splash kernel module whose kernels keep
    their ``jax.named_scope``; JAX's own module is left as it is.

    The shipped kernels hand ``pallas_call`` profiler ``metadata``,
    which the TPU lowering attaches to the custom call as frontend
    attributes, and XLA's TPU compiler then drops the call's
    ``op_name``: a device trace would tie the kernels to no scope, and
    ``kfac_model/attention`` would read only the glue round them (0.68
    of 14 ms a step, the windowed cell's first traced run on a v5e).
    The copy is executed from the module's own source under no entry of
    ``sys.modules``, and its name for Pallas is then pointed at a
    namespace whose ``pallas_call`` leaves that metadata out. Raises if
    the module no longer reaches Pallas by that name, rather than hand
    back kernels that lose their scope without a word."""
    import importlib.util
    import types

    from jax.experimental import pallas as pl

    spec = importlib.util.find_spec(SPLASH_KERNEL_MODULE)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    if getattr(module, 'pl', None) is not pl:
        raise RuntimeError(
            f'{SPLASH_KERNEL_MODULE} no longer reaches Pallas as `pl`: '
            'its kernels would carry no op_name in a device trace')

    def pallas_call(*args, metadata=None, **kwargs):
        del metadata  # profiler annotations only
        return pl.pallas_call(*args, **kwargs)

    module.pl = types.SimpleNamespace(**{**vars(pl),
                                         'pallas_call': pallas_call})
    return module


# A block-diffusion step lays a sequence's noised copy (positions 0 to
# L - 1) and its clean copy (L to 2L - 1) side by side and attends over
# both under one mask of blocks of B positions (BD3-LM's vectorised
# training): a noised query reads its own noised block both ways and
# every clean block strictly before its own; a clean query reads the
# clean blocks up to and including its own, and nothing noised.


def block_diffusion_keep(q_ids, kv_ids, block: int, length: int):
    """Whether query ``q_ids`` reads key ``kv_ids`` under the block-
    diffusion mask of blocks of ``block`` over two streams of
    ``length`` (numpy or jax integer arrays, broadcast together): the
    rule's definition, which the plain path and the tile map read. The
    splash kernels evaluate :func:`block_diffusion_keep_shifted` where
    the block is a power of two, this one (two ``//`` an element) where
    it is not."""
    qb, kb = q_ids // block, kv_ids // block
    q_noised, k_noised = q_ids < length, kv_ids < length
    return ((q_noised & ((k_noised & (qb == kb))
                         | (~k_noised & (kb < qb + length // block))))
            | (~q_noised & ~k_noised & (kb <= qb)))


def block_diffusion_keep_shifted(q_ids, kv_ids, block: int, length: int):
    """:func:`block_diffusion_keep` for a power-of-two ``block`` without
    a division, for ids that are not negative and a ``length`` of whole
    blocks: ``q & -B`` is q's block start and ``q ^ k < B`` says k lies
    in q's block. A query keeps its own block and the clean keys from L
    up to a bound: ``L + start - 1`` for a noised query (the clean
    blocks before its own), its own block's end for a clean one (whose
    block the first term then covers too)."""
    start = q_ids & -block
    bound = jnp.where(q_ids < length, start + (length - 1),
                      q_ids | (block - 1))
    return (((kv_ids >= length) & (kv_ids <= bound))
            | ((q_ids ^ kv_ids) < block))


def block_diffusion_route(block: int) -> str:
    """How the splash kernels decide a tile's pairs under the mask of
    blocks of ``block``: ``'shifted'`` (:func:`block_diffusion_keep_shifted`)
    where it is a power of two, else ``'divided'``."""
    return 'divided' if block & (block - 1) else 'shifted'


def block_diffusion_length(t: int, block: int) -> int:
    """The length L of each stream where a block-diffusion step lays two
    streams of whole blocks of ``block`` side by side in ``t`` = 2L
    positions; any other ``t`` raises."""
    if t % (2 * block):
        raise ValueError(f'a block-diffusion mask lays two streams of '
                         f'whole blocks of {block} side by side: {t=}')
    return t // 2


@functools.lru_cache(maxsize=None)
def _block_diffusion_mask(t: int, block: int):
    """Splash's computable mask of the block-diffusion rule over ``t``
    positions, two streams of ``t / 2``: the kernel computes it on a
    tile (by :func:`block_diffusion_route`) and never loads an array of
    it."""
    length = block_diffusion_length(t, block)
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as masks,
    )
    keep = (block_diffusion_keep_shifted
            if block_diffusion_route(block) == 'shifted'
            else block_diffusion_keep)

    class BlockDiffusionMask(masks._ComputableMask):
        def __init__(self):
            super().__init__(shape=(t, t), mask_function=functools.partial(
                keep, block=block, length=length))

        def __eq__(self, other):
            return type(other) is type(self)

        def __hash__(self):
            return hash((t, block))

    return BlockDiffusionMask()


def _splash_tile(t: int) -> int:
    return 512 if t % 512 == 0 else ATTENTION_MIN_BLOCK


@functools.lru_cache(maxsize=None)
def splash_tile_fill(t: int, block: int) -> tuple[int, int]:
    """``(kept, visited)`` (query, key) pairs of one head under the
    block-diffusion mask: the pairs it keeps, and the pairs of the tiles
    the splash kernel visits at its tile size (every tile that keeps a
    pair; the empty ones are skipped), from :func:`block_diffusion_keep`
    on the host."""
    import numpy as np

    length, s = block_diffusion_length(t, block), _splash_tile(t)
    ids = np.arange(t)
    kept = visited = 0
    for lo in range(0, t, s):
        for col in range(0, t, s):
            n = int(block_diffusion_keep(ids[lo:lo + s, None],
                                         ids[None, col:col + s], block,
                                         length).sum())
            kept += n
            visited += s * s if n else 0
    return kept, visited


@functools.lru_cache(maxsize=None)
def _splash_kernel(heads: int, t: int, window: int | None,
                   diffusion_block: int | None = None):
    """The kernel for ``heads`` q heads over ``t`` positions, causal, in
    a causal window of ``window`` keys, or under the block-diffusion
    mask of blocks of ``diffusion_block``, in tiles of 512 where they
    divide ``t`` (else 256) in all three of its passes. Its tables of
    which tiles to visit are made once, as concrete arrays (under no
    trace, so that the cached kernel serves every later one)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as masks,
    )

    kernels = _splash_kernels()
    if diffusion_block is not None:
        mask = _block_diffusion_mask(t, diffusion_block)
    elif window is None:
        mask = masks.CausalMask((t, t))
    else:
        mask = masks.LocalMask((t, t), window_size=(window - 1, 0),
                               offset=0)
    s = _splash_tile(t)
    sizes = kernels.BlockSizes(block_q=s, block_kv=s, block_kv_compute=s,
                               block_q_dkv=s, block_kv_dkv=s,
                               block_kv_dkv_compute=s, block_q_dq=s,
                               block_kv_dq=s)
    with jax.ensure_compile_time_eval():
        return kernels.make_splash_mha(masks.MultiHeadMask([mask] * heads),
                                       block_sizes=sizes, head_shards=1,
                                       q_seq_shards=1)


def splash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     causal: bool = True, window: int | None = None,
                     diffusion_block: int | None = None) -> jax.Array:
    """Exact softmax attention in the shipped splash kernels: causal
    over the whole past (``window`` None) or the last ``window`` keys,
    or, in place of the causal rule, under the block-diffusion mask of
    blocks of ``diffusion_block`` (:func:`block_diffusion_keep`).

    ``q``: (B, T, H, D); ``k``/``v``: (B, T, H_kv, D) with H a multiple
    of H_kv, all of one dtype; returns (B, T, H, D) float32 like the
    plain path, rounded once to the input dtype by the kernel.
    Differentiable; no (T, T) tensor reaches HBM in either direction."""
    if not causal and diffusion_block is None:
        raise ValueError('the splash route is causal or block-diffusion')
    b, t, heads, d = q.shape
    kernel = _splash_kernel(heads, t, window, diffusion_block)
    q = (q.astype(jnp.float32) * (1.0 / d ** 0.5)).astype(k.dtype)
    heads_first = lambda x: jnp.swapaxes(x, 1, 2)  # noqa: E731
    o = jax.vmap(kernel)(heads_first(q), heads_first(k), heads_first(v))
    return heads_first(o).astype(jnp.float32)


@functools.lru_cache(maxsize=1)
def _splash_attention_probed() -> bool:
    """Once per process: the kill switch (recorded), else the probe."""
    # Two 512-tiles a side, a window of one tile (the band crosses
    # both, one tile is skipped), 4 q heads over 2 kv heads.
    return _attention_probe('splash_attention', splash_attention,
                            (1, 1024, 4, 128), (1, 1024, 2, 128),
                            window=512)


@functools.lru_cache(maxsize=1)
def _splash_block_diffusion_probed() -> bool:
    """Once per process: the kill switch (recorded), else the probe of
    the block-diffusion mask."""
    # Two 512-tiles a side: the noised-noised and the clean-noised
    # tiles, one skipped, the two others kept in part.
    return _attention_probe('splash_block_diffusion', splash_attention,
                            (1, 1024, 4, 128), (1, 1024, 2, 128),
                            diffusion_block=4)


def splash_attention_applies(q, k, v, kvalid=None,
                             diffusion_block=None) -> bool:
    """The gate of :func:`splash_attention`, from what the call shows.

    Open for self-attention without a key mask, k and v of one shape,
    q of their batch, length and head dim with a whole multiple of
    their heads, one dtype (bfloat16 or float32), a head dim in
    :data:`ATTENTION_HEAD_DIMS` and ``T`` a whole number of
    :data:`ATTENTION_MIN_BLOCK` tiles; on a TPU, where the route's own
    once-per-process probe must pass or raise, or under Pallas's TPU
    interpret mode. ``KFAC_PALLAS_FALLBACK=1`` closes it as it closes
    :func:`fused_attention_applies`. A call with a ``diffusion_block``
    asks the block-diffusion mask's own probe."""
    b, t, heads, d = q.shape
    if kvalid is not None or k.shape != v.shape or heads % k.shape[2]:
        return False
    if k.shape != (b, t, k.shape[2], d):
        return False
    if not (q.dtype == k.dtype == v.dtype
            and q.dtype in (jnp.bfloat16, jnp.float32)):
        return False
    if d not in ATTENTION_HEAD_DIMS or t % ATTENTION_MIN_BLOCK:
        return False
    if jax.default_backend() != 'tpu' and not _tpu_interpret_forced():
        return False
    if diffusion_block is not None:
        return _splash_block_diffusion_probed()
    return _splash_attention_probed()


# ---------------------------------------------------------------------------
# Grouped matmul of stacked experts
# ---------------------------------------------------------------------------
#
# ``modules.experts.ExpertsDense`` multiplies each of its rows, sorted by
# expert, by its expert's weight. On a TPU the product, its input
# gradient and its weight gradient run on the grouped-matmul kernels JAX
# ships (jax.experimental.pallas.ops.tpu.megablox: ``gmm``, ``tgmm``),
# whose grid is built from ``group_sizes``: only the row tiles that hold
# routed rows are visited, and the rows past the last group are neither
# read nor written. bf16 or fp32 operands, fp32 accumulation, the output
# in the operands' dtype: the arithmetic of ``jax.lax.ragged_dot``.

#: Rows a grid step of the grouped kernels. A group that starts inside a
#: tile costs that tile twice, so short tiles waste less where experts
#: get a few hundred rows each, and long ones run faster where the
#: buffer is full. On a v5e (``benchmarks/grouped_experts_bench.py``),
#: kernel alone at 8 experts of 390-520 rows every row tile reads within
#: 20 % of the best tiling found for each product; with the buffer full
#: 256 is within 12 % of 512 and ahead of 128.
GROUPED_ROW_TILE = 256
#: Fast memory a grouped kernel's tiles may take by
#: :func:`_grouped_tile_bytes` (the compiler's scoped limit on a v5e is
#: 16 MiB; the count leaves out the kernel's own scratch).
GROUPED_TILE_BUDGET = 13 * 2 ** 20


def _grouped_tile_bytes(tm: int, tk: int, tn: int, itemsize: int,
                        weight_grad: bool,
                        out_itemsize: int | None = None) -> int:
    """Fast memory of one grid step: the two operand tiles and the
    output tile double-buffered, the fp32 accumulator. ``gmm`` holds a
    ``(tm, tk)`` row tile, a ``(tk, tn)`` weight tile and a ``(tm, tn)``
    output; ``tgmm`` (``weight_grad``) holds ``(tm, tk)`` and
    ``(tm, tn)`` row tiles and a ``(tk, tn)`` output. The output takes
    ``out_itemsize`` bytes an element (default: the operands')."""
    out = tk * tn if weight_grad else tm * tn
    operands = tm * (tk + tn) if weight_grad else tk * (tm + tn)
    out_itemsize = out_itemsize or itemsize
    return 2 * (itemsize * operands + out_itemsize * out) + 4 * out


def lane_divisor_below(d: int, below: int | None = None) -> int | None:
    """The largest multiple of the lane width under ``below`` (default:
    ``d``) that divides ``d``, or None."""
    below = d if below is None else below
    for t in range((below - 1) // _LANE * _LANE, 0, -_LANE):
        if d % t == 0:
            return t
    return None


def grouped_tiling(k: int, n: int, itemsize: int = 2, *,
                   weight_grad: bool = False,
                   out_itemsize: int | None = None) -> tuple[int, int, int]:
    """``(tm, tk, tn)`` for a grouped product that contracts ``k`` into
    ``n`` (``weight_grad``: the ``tgmm`` that makes an ``(E, k, n)``
    weight gradient from rows of ``k`` and ``n``), its output
    ``out_itemsize`` bytes an element (default: the operands'). Whole
    ``k`` and ``n`` where the tiles fit :data:`GROUPED_TILE_BUDGET`, so
    that a group's weight tile is fetched once; else the wider tile is
    cut to the next lane-multiple divisor of its dim until they fit (a
    2304-wide covariance: 2304, 1152, then 768, not 1152's own 384: on
    a v5e (768, 1152) ran 12-14 % faster than (384, 1152),
    ``benchmarks/grouped_experts_bench.py --covariances``)."""
    tm, tk, tn = GROUPED_ROW_TILE, k, n
    while _grouped_tile_bytes(tm, tk, tn, itemsize, weight_grad,
                              out_itemsize) > GROUPED_TILE_BUDGET:
        wide_k = tk >= tn
        cut = (lane_divisor_below(k, tk) if wide_k
               else lane_divisor_below(n, tn))
        if cut is None:
            break
        tk, tn = (cut, tn) if wide_k else (tk, cut)
    return tm, tk, tn


@functools.lru_cache(maxsize=2)
def _grouped_kernel_allowed(kernel: str) -> bool:
    """Once per process and kernel: the kill switch (recorded)."""
    if _forced_fallback():
        record_fallback(kernel, 'forced by KFAC_PALLAS_FALLBACK')
        return False
    return True


def _grouped_kernels_open(kernel: str, rows: int, *dims: int) -> bool:
    """What every grouped kernel's gate asks: ``dims`` multiples of the
    lane width, ``rows`` a whole number of :data:`GROUPED_ROW_TILE`
    tiles, a TPU or Pallas's TPU interpret mode, and no kill switch."""
    if any(d % _LANE for d in dims) or rows % GROUPED_ROW_TILE:
        return False
    if jax.default_backend() != 'tpu' and not _tpu_interpret_forced():
        return False
    return _grouped_kernel_allowed(kernel)


def grouped_matmul_applies(x, kernel) -> bool:
    """The gate of :func:`grouped_matmul`, from what the call shows.

    Open for rows ``x`` ``(rows, k)`` and weights ``kernel``
    ``(E, k, n)`` of one dtype (bfloat16 or float32), ``k`` and ``n``
    multiples of the lane width and ``rows`` a whole number of
    :data:`GROUPED_ROW_TILE` tiles, on a TPU or under Pallas's TPU
    interpret mode. ``KFAC_PALLAS_FALLBACK=1`` closes it (recorded).
    Anything else keeps ``jax.lax.ragged_dot``."""
    if not (x.dtype == kernel.dtype
            and x.dtype in (jnp.bfloat16, jnp.float32)):
        return False
    return _grouped_kernels_open('grouped_matmul', *x.shape,
                                 kernel.shape[-1])


@jax.custom_vjp
def grouped_matmul(x: jax.Array, kernel: jax.Array,
                   group_sizes: jax.Array) -> jax.Array:
    """``y[r] = x[r] @ kernel[e]`` for the ``group_sizes[e]`` rows of
    expert ``e``, the rows sorted by expert: ``jax.lax.ragged_dot`` on
    the shipped grouped kernels. ``x`` ``(rows, k)``, ``kernel``
    ``(E, k, n)`` of one dtype, ``group_sizes`` int32 ``(E,)``.

    Rows past ``sum(group_sizes)`` are undefined in ``y`` and in the
    input gradient; they never reach the weight gradient, whose kernel
    selects a tile's rows of the group (a NaN there is not multiplied by
    zero). The input gradient and the weight gradient are one kernel
    call each, each tiled for its own shape."""
    return _grouped_forward(x, kernel, group_sizes)


def _grouped_forward(x, kernel, group_sizes):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    k, n = kernel.shape[1:]
    return gmm(x, kernel, group_sizes, x.dtype,
               grouped_tiling(k, n, x.dtype.itemsize))


def _grouped_fwd(x, kernel, group_sizes):
    return (_grouped_forward(x, kernel, group_sizes),
            (x, kernel, group_sizes))


def _grouped_bwd(residuals, dy):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    x, kernel, group_sizes = residuals
    k, n = kernel.shape[1:]
    dx = gmm(dy, kernel, group_sizes, x.dtype,
             grouped_tiling(n, k, x.dtype.itemsize), transpose_rhs=True)
    # tgmm takes the rows as (k, rows) and swaps them back before its
    # kernel: the two transposes fold, the buffer is not copied.
    dw = tgmm(x.swapaxes(0, 1), dy, group_sizes, kernel.dtype,
              grouped_tiling(k, n, x.dtype.itemsize, weight_grad=True))
    return dx, dw, None


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)


# ---------------------------------------------------------------------------
# Per-expert covariances of stacked experts
# ---------------------------------------------------------------------------
#
# ``ops.factors`` contracts each expert's routed rows with themselves,
# ``sum_r x_r x_r^T`` a group: on a TPU that is the weight-gradient
# kernel above with both operands the rows, ``tgmm(x^T, x)``, whose grid
# visits only the row tiles that hold routed rows.


def experts_cov_applies(x) -> bool:
    """The gate of :func:`experts_cov`, from what the call shows.

    Open for bfloat16 rows ``x`` ``(rows, d)``, ``d`` a multiple of the
    lane width and ``rows`` a whole number of :data:`GROUPED_ROW_TILE`
    tiles, on a TPU or under Pallas's TPU interpret mode.
    ``KFAC_PALLAS_FALLBACK=1`` closes it (recorded). Anything else,
    float32 rows among them (``get_cov``'s ``HIGHEST`` contract),
    keeps ``jax.lax.ragged_dot_general``."""
    return (x.dtype == jnp.bfloat16
            and _grouped_kernels_open('experts_cov', *x.shape))


def experts_cov(x: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """``(E, d, d)`` float32: per expert ``sum_r x_r x_r^T`` over its
    ``group_sizes[e]`` rows of the expert-sorted ``(rows, d)`` tensor
    ``x``, fp32 accumulation. Rows past ``sum(group_sizes)`` are
    neither read into a sum nor multiplied by zero (a NaN there reaches
    no block); an expert with no row gets a zero block."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm

    d = x.shape[1]
    # As in the products' weight gradient: the transpose folds into
    # tgmm's own, the rows are not copied.
    return tgmm(x.swapaxes(0, 1), x, group_sizes, jnp.float32,
                grouped_tiling(d, d, x.dtype.itemsize, weight_grad=True,
                               out_itemsize=4))
