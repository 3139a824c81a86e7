"""Kronecker-factor statistics ops (pure jnp; jit/vmap/shard_map friendly).

Numerics parity with the reference formulas in kfac/layers/utils.py:13-178 and
kfac/layers/{linear.py,conv.py}, re-expressed functionally: no in-place
mutation, NHWC conv layout, and patch extraction via XLA's
``conv_general_dilated_patches`` instead of torch ``unfold`` (im2col).
"""

from __future__ import annotations

import functools
import operator
import os
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from distributed_kfac_pytorch_tpu.observability import profiling, tracing
from distributed_kfac_pytorch_tpu.ops import pallas_kernels

# The blocked self-covariance's gate (get_cov, b is None), a function of
# the static (rows, d) alone. At rows >= d the full square a^T a runs
# at the MXU's peak and computes both triangles; where d also splits
# into COV_BLOCK_MAX_SIDE column blocks a side, or the most from
# COV_BLOCK_MIN_SIDE up, that are COV_BLOCK_ALIGN-aligned (the lane
# width) and at least COV_BLOCK_MIN_WIDTH wide (so d >= 3072), only the
# upper block triangle is contracted, block by block in one loop. Set
# by the kernel-alone sweep on one v5e (benchmarks/factor_roofline.py
# --leg blocked; PERF.md section 6, PR 30): a block contraction that slices
# its operands at a traced offset holds 45 % of the peak at 768 wide
# and 52-63 % from 1024 on, against 80 % for the whole square with its
# symmetrization, so only 3 x 1024 and wider wins (3072: 0.91 of the
# single contraction, 4608 as 4 x 1152: 0.89, 6144 as 4 x 1536: 0.82;
# 2048 and 1536 lose, and two a side, 3/4 of the FLOPs, gains nothing
# anywhere).
COV_BLOCK_MIN_WIDTH = 1024
COV_BLOCK_MIN_SIDE = 3
COV_BLOCK_MAX_SIDE = 4
COV_BLOCK_ALIGN = 128


def append_bias_ones(x: jax.Array) -> jax.Array:
    """Append a column of ones to the last dim (homogeneous coordinates).

    Reference parity: kfac/layers/utils.py:4-11.
    """
    ones = jnp.ones((*x.shape[:-1], 1), dtype=x.dtype)
    return jnp.concatenate([x, ones], axis=-1)


def cov_block_side(rows: int, d: int) -> int | None:
    """Column blocks a side of the blocked self-covariance of ``rows``
    rows of width ``d`` (they are equal: ``d // k`` wide), or None where
    the whole squares stay (see the ``COV_BLOCK_*`` constants). A layer
    called several times brings the rows of all its calls: the
    statistic kept is ``sum_c a_c^T a_c``."""
    if rows < d:
        return None
    for k in range(COV_BLOCK_MAX_SIDE, COV_BLOCK_MIN_SIDE - 1, -1):
        if (d % (k * COV_BLOCK_ALIGN) == 0
                and d // k >= COV_BLOCK_MIN_WIDTH):
            return k
    return None


def _sum(terms):
    """``terms[0] + terms[1] + ...`` with no leading zero: one term is
    that term, and traces nothing."""
    return functools.reduce(operator.add, terms)


def _cov_full(calls: Sequence[jax.Array], scale, precision) -> jax.Array:
    """``sum_c a_c^T a_c / scale``, a contraction a call, the whole
    squares summed in float32 and the sum symmetrized once."""
    cov = _sum([jnp.matmul(a.T, a, preferred_element_type=jnp.float32,
                           precision=precision) for a in calls])
    return (cov + cov.T) * (0.5 / scale)


def _cov_blocked(calls: Sequence[jax.Array], k: int, scale,
                 precision) -> jax.Array:
    """``sum_c a_c^T a_c / scale`` from its upper block triangle, ``k``
    equal column blocks a side.

    The k(k+1)/2 blocks ``sum_c a_c[:, Bi]^T a_c[:, Bj]``, i <= j, are
    contracted (same operands, accumulator and precision as
    :func:`_cov_full`, the calls summed in float32 block by block) in
    one loop over the block pairs, not k(k+1)/2 contractions side by
    side: unrolled, every block is a kernel of its own in the step's
    program (about 1 MB of code each on a v5e, +0.4 GB of HBM in
    gpt2s_f1i10 at 24 blocked factors in two programs: PERF.md section
    6); the loop is one instruction with one contraction's code a
    call, which slices its operands at a traced offset.

    The loop carries nothing of the square's size: each trip's block
    and its transpose are one slot each of its two stacked outputs,
    ``(k(k+1)/2, d/k, d/k)`` float32, written whole. The square is put
    together once, after the loop, from static slices of the stacks
    (:func:`_square_of_blocks`), as one concatenation that what reads
    it (the bias pad, the running average, the non-finite guard) can
    take into its own fusion. Placed block by block into a zeroed square inside the loop
    instead, each block and its transpose were strided writes into a
    square XLA lays out column-major, and the result took a layout copy
    besides; transposed after the loop, the lower blocks lead XLA to
    the column-major square again, and to copies of the running average
    it meets (PERF.md section 6).
    """
    rows, d = calls[0].shape
    width = d // k
    pairs = [(i, j) for i in range(k) for j in range(i, k)]
    offsets = jnp.asarray([(i * width, j * width) for i, j in pairs],
                          jnp.int32)

    def one_block(carry, lo):
        blk = _sum([jax.lax.dot_general(
            jax.lax.dynamic_slice(a, (0, lo[0]), (rows, width)),
            jax.lax.dynamic_slice(a, (0, lo[1]), (rows, width)),
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
            for a in calls])
        return carry, (blk, blk.T)

    _, (blocks, transposed) = jax.lax.scan(one_block, None, offsets)
    return _square_of_blocks(blocks, transposed, pairs, scale)


def _square_of_blocks(blocks: jax.Array, transposed: jax.Array, pairs,
                      scale) -> jax.Array:
    """The ``(d, d)`` square of :func:`_cov_blocked`'s stacked upper
    blocks, ``blocks[t]`` the block at ``pairs[t] = (i, j)``, i <= j,
    and ``transposed[t]`` its transpose: ``(i, j)`` is the block over
    ``scale``, ``(j, i)`` its transpose over ``scale``, and a diagonal
    block, ``v^T v`` and so symmetric only up to round-off, is
    symmetrized on its own. The square is then symmetric entry for
    entry and needs no full-size ``cov + cov.T`` pass."""
    k = pairs[-1][0] + 1
    square = {}
    for t, (i, j) in enumerate(pairs):
        if i == j:
            square[i, i] = (blocks[t] + transposed[t]) * (0.5 / scale)
        else:
            square[i, j] = blocks[t] * (1.0 / scale)
            square[j, i] = transposed[t] * (1.0 / scale)
    return jnp.concatenate([
        jnp.concatenate([square[i, j] for j in range(k)], axis=1)
        for i in range(k)])


def get_cov(a: jax.Array, b: jax.Array | None = None,
            scale: float | None = None,
            compute_dtype=None) -> jax.Array:
    """Empirical second moment ``a^T @ b / scale`` of 2-D tensors.

    When ``b`` is None this is :func:`get_cov_of_calls` of the one
    call: symmetric entry for entry, by the route its static shape
    chooses.

    ``compute_dtype`` casts the matmul *inputs* (e.g. to bfloat16 for the
    MXU fast path) while always accumulating in float32 — the TPU
    analogue of the reference's keep-autocast-dtype factor policy
    (README.md:150-160); the returned covariance is float32.

    Precision semantics (decided on measured v5e behavior):

      - ``compute_dtype=None`` (default): the backend's native matmul
        precision. On TPU that rounds fp32 inputs to bf16 before the
        MXU with fp32 accumulation (``preferred_element_type`` pins the
        accumulator only) — ~4e-3 relative covariance error, measured.
        This is the fast path and the production default: the factor
        EWMA runs every ``factor_update_freq`` steps on batch-sized
        tensors, and forcing 6-pass fp32 emulation here costs more than
        the whole amortized decomposition pipeline (+15 ms/iter on the
        tracked CIFAR config).
      - ``compute_dtype=jnp.float32``: *strict* fp32 — inputs cast to
        fp32 and the contraction runs at ``Precision.HIGHEST``
        (numerics parity with the reference's fp32 factors,
        kfac/layers/utils.py:40-43), in every block of every call on
        the blocked route too.
      - ``compute_dtype=jnp.bfloat16``: explicit bf16 inputs (the
        reference's ``--fp16`` factor mode analogue) — same MXU cost as
        the default on TPU, and makes the choice visible in configs.

    Reference parity: kfac/layers/utils.py:13-43.
    """
    if b is None:
        return get_cov_of_calls([a], scale=scale,
                                compute_dtype=compute_dtype)
    if a.ndim != 2:
        raise ValueError(f'get_cov expects a 2-D tensor, got shape {a.shape}')
    if a.shape != b.shape:
        raise ValueError(f'shape mismatch: {a.shape} vs {b.shape}')
    if scale is None:
        scale = a.shape[0]
    if compute_dtype is not None:
        a, b = a.astype(compute_dtype), b.astype(compute_dtype)
    return jnp.matmul(a.T, b, preferred_element_type=jnp.float32,
                      precision=_cov_precision(compute_dtype)
                      ) * (1.0 / scale)


def _cov_precision(compute_dtype):
    """Strict float32 contracts at ``Precision.HIGHEST`` (see
    :func:`get_cov`); every other ``compute_dtype`` at the backend's
    own."""
    strict = (compute_dtype is not None
              and jnp.dtype(compute_dtype) == jnp.float32)
    return jax.lax.Precision.HIGHEST if strict else None


def get_cov_of_calls(calls: Sequence[jax.Array],
                     scale: float | None = None,
                     compute_dtype=None) -> jax.Array:
    """``sum_c a_c^T a_c / scale`` over a layer's calls: the
    self-covariance a weight that is applied several times a forward
    keeps (calls added, not averaged; ``scale`` defaults to the rows of
    ONE call, what each call's own :func:`get_cov` divides by).

    The calls are contracted together, accumulated in float32, and the
    result is made symmetric once, entry for entry (``C == C^T``
    exactly), by one of two routes chosen from the static ``(calls,
    rows a call, d)`` alone (:func:`cov_block_side`; no option):

      - the whole squares, one contraction a call, summed and
        explicitly symmetrized ``(C + C^T) / 2`` (:func:`_cov_full`):
        every shape where the contraction is not compute-bound;
      - where the calls' rows TOGETHER are at least ``d >= 3072`` and d
        divides into three or four wide blocks a side: only the upper
        block triangle is contracted, the calls summed block by block,
        6 of 9 blocks or 10 of 16, stacked with their transposes as
        one loop's outputs; the square is assembled once after the
        loop, the lower blocks the upper ones' transposes, where what
        reads the statistic can fuse it (:func:`_cov_blocked`).
        Entries differ from the first
        route's only in float32 summation order.

    The calls are read where they are, no copy, whatever their number
    and length. (Stacked into one operand first, many short calls are
    faster, an LSTM cell's 35 calls of 128 rows at 4096 dims 1.4 ms
    against 5.4 kernel alone on a v5e, and few long ones slower, the
    looped cell 3 % of its step; no cell runs short calls, so no
    threshold is kept for them: benchmarks/factor_roofline.py --leg
    calls, PERF.md section 6, PR 35.) One call traces what
    :func:`get_cov` always traced. Calls of unequal shapes or dtypes (a
    module tied across sites of different batch shapes) are contracted
    and symmetrized one by one and added, each on its own scale.

    Each traced statistic counts its route in the recorder
    (``observability.tracing``): ``kfac/factors/cov_blocked`` or
    ``kfac/factors/cov_full``, and ``kfac/factors/cov_merged`` beside
    it where it was contracted from more than one call.
    """
    calls = list(calls)
    for a in calls:
        if a.ndim != 2:
            raise ValueError(
                f'get_cov expects a 2-D tensor, got shape {a.shape}')
    if len({(a.shape, a.dtype) for a in calls}) > 1:
        if scale is not None:
            raise ValueError('calls of unequal shapes take no common scale')
        return _sum([get_cov_of_calls([a], compute_dtype=compute_dtype)
                     for a in calls])
    rows, d = calls[0].shape
    # Scale the (small) covariance output, not the (batch-sized) input:
    # an elementwise divide of the input materializes a full copy of a
    # tensor that is ~300 MB per conv layer at production batch sizes —
    # profiled on v5e, those copies dominated the whole K-FAC step.
    if scale is None:
        scale = rows
    if compute_dtype is not None:
        calls = [a.astype(compute_dtype) for a in calls]
    precision = _cov_precision(compute_dtype)
    if len(calls) > 1:
        tracing.count('kfac/factors/cov_merged')
    side = cov_block_side(rows * len(calls), d)
    if side is None:
        tracing.count('kfac/factors/cov_full')
        return _cov_full(calls, scale, precision)
    tracing.count('kfac/factors/cov_blocked')
    return _cov_blocked(calls, side, scale, precision)


def update_running_avg(new: jax.Array, current: jax.Array,
                       alpha: float) -> jax.Array:
    """EWMA ``alpha * current + (1 - alpha) * new`` (functional, not in-place).

    Reference parity: kfac/layers/utils.py:164-178 (there, ``alpha`` is the
    ``factor_decay`` hyperparameter, default 0.95).
    """
    return alpha * current + (1.0 - alpha) * new


def collapse_batch_dims(x: jax.Array) -> jax.Array:
    """Collapse all but the last dim: (..., d) -> (prod(...), d).

    Functional analogue of the reference's accumulate-then-reshape
    (kfac/layers/utils.py:107-124): in JAX the captures arrive as one array,
    so concatenation over the accumulation list collapses into this reshape.
    """
    return x.reshape(-1, x.shape[-1])


# ---------------------------------------------------------------------------
# Per-layer-kind factor statistics
# ---------------------------------------------------------------------------

def _column_mean(x: jax.Array) -> jax.Array:
    """Column mean of a 2-D tensor as a ones-row matmul (fp32 accumulate).

    Expressed as a matmul rather than ``jnp.sum(x, axis=0)``: the batched
    column reduction rides the MXU on TPU, and the reduction form
    segfaults XLA:CPU inside large shard_map programs (bisected on the
    distributed embedding-parity test; same fragility class as the
    gather note in :func:`pack_symmetric`).
    """
    ones = jnp.ones((1, x.shape[0]), jnp.float32)
    # HIGHEST: the TPU-default matmul precision would round the fp32
    # inputs to bf16 on the MXU (see get_cov's precision note).
    return jnp.matmul(ones, x.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)[0] / x.shape[0]


def _assemble_bias_factor(cov: jax.Array, bias_col: jax.Array,
                          corner) -> jax.Array:
    """[[cov, b], [b^T, corner]] — the covariance of rows with an appended
    ones column, built without ever materializing the (batch, dim + 1)
    concatenation (a full copy of the activation/patch tensor).

    Assembled as pad + two rank-1 outer products rather than block
    concatenation (keeps every op elementwise/pad — the most portable
    fusion-friendly form on both TPU and XLA:CPU).
    """
    d = cov.shape[0]
    padded = jnp.pad(cov, ((0, 1), (0, 1)))
    onehot = (jnp.arange(d + 1) == d).astype(cov.dtype)
    b_ext = jnp.pad(bias_col, (0, 1)) + (corner / 2.0) * onehot
    return padded + jnp.outer(onehot, b_ext) + jnp.outer(b_ext, onehot)


def linear_a_factor(a: jax.Array, has_bias: bool,
                    compute_dtype=None) -> jax.Array:
    """A = cov(inputs (+ ones column)) for a dense layer.

    ``a`` may have arbitrary leading dims (batch, time, ...); they are
    collapsed. Reference parity: kfac/layers/linear.py:12-18; the bias
    row/column ``[sum(a)/n, 1]`` is assembled analytically instead of
    concatenating a ones column onto the batch tensor.
    """
    return linear_a_factor_of_calls([a], has_bias, compute_dtype)


@profiling.scope('kfac/factors/linear_a')
def linear_a_factor_of_calls(calls: Sequence[jax.Array], has_bias: bool,
                             compute_dtype=None) -> jax.Array:
    """:func:`linear_a_factor` of a layer applied several times a
    forward: the sum of its calls' statistics, contracted together
    (:func:`get_cov_of_calls`). The bias row and column are linear in
    the calls too and are summed before they are assembled."""
    calls = [collapse_batch_dims(a) for a in calls]
    cov = get_cov_of_calls(calls, compute_dtype=compute_dtype)
    if not has_bias:
        return cov
    bias_col = _sum([_column_mean(a).astype(cov.dtype) for a in calls])
    return _assemble_bias_factor(cov, bias_col, float(len(calls)))


def linear_g_factor(g: jax.Array, compute_dtype=None) -> jax.Array:
    """G = cov(grad wrt layer outputs) for a dense layer.

    Reference parity: kfac/layers/linear.py:20-24.
    """
    return linear_g_factor_of_calls([g], compute_dtype)


@profiling.scope('kfac/factors/linear_g')
def linear_g_factor_of_calls(calls: Sequence[jax.Array],
                             compute_dtype=None) -> jax.Array:
    """:func:`linear_g_factor` of all of a layer's calls (see
    :func:`linear_a_factor_of_calls`)."""
    return get_cov_of_calls([collapse_batch_dims(g) for g in calls],
                            compute_dtype=compute_dtype)


# ---------------------------------------------------------------------------
# KFAC-reduce: sum/mean over the shared (sequence/patch) axis BEFORE the
# covariance (arXiv:2311.00636; see sharing.approx for the policy layer)
# ---------------------------------------------------------------------------

def _reduce_shared_axes(x: jax.Array, mean: bool) -> jax.Array:
    """Reduce ``(B, *, d)`` over the middle (shared) axes -> ``(B, d)``.

    Expressed as a batched ones-row matmul rather than ``jnp.sum/mean``
    over the axis — the same portability rule as :func:`_column_mean`
    (axis reductions segfault XLA:CPU inside large shard_map programs,
    and the batched column reduction rides the MXU on TPU). Accumulates
    fp32 (``preferred_element_type``) and returns fp32 rows; the
    downstream covariance's ``compute_dtype`` governs the contraction
    inputs exactly as on the expand path.
    """
    if x.ndim <= 2:
        return x.astype(jnp.float32)
    b, d = x.shape[0], x.shape[-1]
    t = int(np.prod(x.shape[1:-1]))
    x3 = x.reshape(b, t, d)
    ones = jnp.ones((1, t), x3.dtype)
    out = jnp.matmul(ones, x3, preferred_element_type=jnp.float32)[:, 0]
    return out / t if mean else out


@profiling.scope('kfac/factors/linear_a_reduced')
def linear_a_factor_reduced(a: jax.Array, has_bias: bool,
                            compute_dtype=None) -> jax.Array:
    """KFAC-reduce A for a weight-shared dense layer.

    ``a`` is ``(B, T..., d)``; the shared axes are MEAN-reduced before
    the covariance — the paper's Eq. 22 convention, under which the
    appended bias column reduces to exactly 1 (an average of ones), so
    the bias row/column assembly is the ordinary
    :func:`linear_a_factor` over the ``(B, d)`` reduced rows. Scale is
    the reduced row count ``B`` (vs expand's ``B*T``): the factor
    contraction — the dominant factor-phase cost on transformer
    workloads — is a factor ``T`` cheaper. Degenerates bit-identically
    to expand at T=1 (test-pinned).
    """
    return linear_a_factor(_reduce_shared_axes(a, mean=True), has_bias,
                           compute_dtype=compute_dtype)


@profiling.scope('kfac/factors/linear_g_reduced')
def linear_g_factor_reduced(g: jax.Array,
                            compute_dtype=None) -> jax.Array:
    """KFAC-reduce G for a weight-shared dense layer.

    Output-grads are SUMMED over the shared axes (the weight gradient
    is the sum over positions, so the summed probe grad keeps the
    per-sample gradient scale exact — Eq. 22's counterpart to the
    activation mean), then the covariance runs over the ``B`` rows.
    """
    return linear_g_factor(_reduce_shared_axes(g, mean=False),
                           compute_dtype=compute_dtype)


@profiling.scope('kfac/factors/conv2d_a_reduced')
def conv2d_a_factor_reduced(a: jax.Array, kernel_size, strides, padding,
                            has_bias: bool,
                            compute_dtype=None) -> jax.Array:
    """KFAC-reduce A for a patch-embedding conv (NHWC input).

    The shared axis is the conv's output-position grid: patch vectors
    are MEAN-reduced over ``(OH, OW)`` and the covariance runs over the
    ``B`` reduced rows — the paper's ViT patch-embed treatment, with
    the bias column exactly 1 (Eq. 22). Intended for non-overlapping
    patch convs (``sharing.is_patch_conv``), where the patches tile the
    image disjointly; the math is well-defined for any conv geometry.

    NOTE the scaling convention deliberately differs from the expand
    path's reference-parity ``1/(rows * spatial^2)`` folding
    (:func:`conv2d_a_factor`): reduce is a different approximation with
    its own normalization (plain covariance over reduced rows, matching
    :func:`linear_a_factor_reduced`). At OH*OW = 1 the two coincide
    bit-identically (spatial = 1 folds to nothing; test-pinned).
    """
    if (compute_dtype is None and a.dtype == jnp.float32
            and jax.default_backend() == 'tpu'):
        # Same pre-im2col bf16 contract as conv2d_a_factor: under the
        # default precision the covariance rounds to bf16 on the MXU
        # anyway; casting first halves the patch-tensor HBM traffic.
        a = a.astype(jnp.bfloat16)
    patches = extract_conv2d_patches_slices(a, kernel_size, strides,
                                            padding)
    b = patches.shape[0]
    d = patches.shape[-1]
    reduced = _reduce_shared_axes(patches.reshape(b, -1, d), mean=True)
    return linear_a_factor(reduced, has_bias,
                           compute_dtype=compute_dtype)


@profiling.scope('kfac/factors/conv2d_g_reduced')
def conv2d_g_factor_reduced(g: jax.Array,
                            compute_dtype=None) -> jax.Array:
    """KFAC-reduce G for a patch-embedding conv: output-grads summed
    over the ``(OH, OW)`` grid, covariance over the ``B`` rows (the
    counterpart of :func:`conv2d_a_factor_reduced`; same convention
    note applies)."""
    b, c = g.shape[0], g.shape[-1]
    return linear_g_factor(
        _reduce_shared_axes(g.reshape(b, -1, c), mean=False),
        compute_dtype=compute_dtype)


@profiling.scope('kfac/factors/embedding_tied_a')
def embedding_tied_a_diag(g: jax.Array) -> jax.Array:
    """Diagonal vocab-side contribution of a tied ``Embed.attend`` site.

    The attend call site's exact vocab-side factor is the dense
    ``cov(dL/dlogits)`` — ``(vocab, vocab)``, which at LM vocabularies
    would dwarf every other factor in the model. Its DIAGONAL
    (``E[g_v^2]`` per vocab entry) is the projection that preserves the
    embedding layer's diagonal-A structure, so the in/out-tied pair
    keeps ONE factor pair and ONE inverse entry: the combined A is
    ``onehot-frequency (lookup) + diag cov(attend output-grads)``.
    Matmul-form mean (see :func:`_column_mean`'s portability note).
    """
    g2 = collapse_batch_dims(g)
    return _column_mean(g2.astype(jnp.float32) ** 2)


def extract_conv2d_patches(x: jax.Array,
                           kernel_size: Sequence[int],
                           strides: Sequence[int],
                           padding) -> jax.Array:
    """im2col: (B, H, W, C) NHWC -> (B, OH, OW, KH*KW*C) patches.

    The feature dim is ordered (kh, kw, cin) with ``kh`` slowest, matching
    the row order of a flax ``nn.Conv`` kernel of shape (KH, KW, Cin, Cout)
    flattened to (KH*KW*Cin, Cout) — so the A factor and the reshaped
    gradient live in the same basis. (The reference orders (cin, kh, kw)
    to match torch's (Cout, Cin, KH, KW) kernels — conv.py:50-70; same math,
    permuted basis.)

    TPU note: ``conv_general_dilated_patches`` lowers to a convolution with
    an identity kernel, which XLA maps onto the MXU — no gather/scatter.
    """
    kh, kw = kernel_size
    c = x.shape[-1]
    patches = jax.lax.conv_general_dilated_patches(
        x, filter_shape=(kh, kw), window_strides=tuple(strides),
        padding=padding,
        dimension_numbers=('NHWC', 'HWIO', 'NHWC'))
    # conv_general_dilated_patches emits features ordered (c, kh, kw) with
    # channel slowest; reorder to (kh, kw, c) to match the flax kernel.
    b, oh, ow = patches.shape[:3]
    patches = patches.reshape(b, oh, ow, c, kh * kw)
    patches = jnp.swapaxes(patches, -1, -2)
    return patches.reshape(b, oh, ow, kh * kw * c)


def _canonical_pad(padding, kernel_size, spatial, strides):
    """Per-axis (lo, hi) pad amounts matching XLA conventions.

    'SAME' follows the XLA/TF formula — total = max((ceil(dim/s)-1)*s
    + k - dim, 0), lo = total // 2, hi = total - lo (extra on the high
    side; asymmetric for strided convs) — so the kernel reproduces
    conv_general_dilated_patches exactly. Also accepts 'VALID', int,
    and explicit ((lo, hi), (lo, hi)) pairs.
    """
    kh, kw = kernel_size
    h, w = spatial
    sh, sw = strides
    if isinstance(padding, str):
        if padding.upper() == 'VALID':
            return ((0, 0), (0, 0))
        if padding.upper() == 'SAME':
            out = []
            for dim, k, s in ((h, kh, sh), (w, kw, sw)):
                o = -(-dim // s)
                total = max((o - 1) * s + k - dim, 0)
                out.append((total // 2, total - total // 2))
            return tuple(out)
        raise ValueError(f'unsupported padding {padding!r}')
    if isinstance(padding, int):
        return ((padding, padding), (padding, padding))
    (a, b), (c, d) = padding
    return ((a, b), (c, d))


def extract_conv2d_patches_slices(x: jax.Array,
                                  kernel_size: Sequence[int],
                                  strides: Sequence[int],
                                  padding) -> jax.Array:
    """im2col via explicit pad + KH*KW static strided slices + concat.

    Same value and (kh, kw, c) feature order as
    ``extract_conv2d_patches`` but assembled from shifted views instead
    of the identity-kernel convolution that
    ``conv_general_dilated_patches`` lowers to — the conv lowering costs
    ``rows * d * d`` MXU FLOPs (as many as the covariance contraction
    itself), while slicing is pure data movement. The natural piece
    order here is (kh, kw, c), so no basis permutation is needed
    downstream.
    """
    kh, kw = kernel_size
    sh, sw = strides
    b, h, w, c = x.shape
    (ph_lo, ph_hi), (pw_lo, pw_hi) = _canonical_pad(
        padding, (kh, kw), (h, w), (sh, sw))
    xp = jnp.pad(x, ((0, 0), (ph_lo, ph_hi), (pw_lo, pw_hi), (0, 0)))
    oh = (h + ph_lo + ph_hi - kh) // sh + 1
    ow = (w + pw_lo + pw_hi - kw) // sw + 1
    pieces = [
        jax.lax.slice(xp, (0, ki, kj, 0),
                      (b, ki + sh * (oh - 1) + 1, kj + sw * (ow - 1) + 1, c),
                      (1, sh, sw, 1))
        for ki in range(kh) for kj in range(kw)]
    return jnp.concatenate(pieces, axis=-1)


def _conv_a_cov_pairs(a: jax.Array, kernel_size, strides, padding,
                      compute_dtype) -> jax.Array:
    """Blocked pairwise shifted-view contraction (round-4 third angle).

    The A-factor weight block decomposes over kernel offsets:
    ``A[(i, c), (j, c')] = Σ_rows view_i[r, c] · view_j[r, c']`` where
    ``view_i`` is the i-th strided *view* of the padded input (the same
    shifted slices the ``slices`` path concatenates into the patch
    tensor). Each of the ``n(n+1)/2`` upper block pairs
    (``n = kh·kw``) is ONE ``dot_general`` contracting the
    ``(b, oh, ow)`` dims of two views directly; lower blocks are
    transposes. vs the materialized-patch path:

      - ~half the MACs — the block symmetry ``B(j,i) = B(i,j)^T`` is
        exploitable here, while the patch-Gram ``P^T P`` matmul cannot
        skip its lower triangle;
      - no ``(rows, kh·kw·c)`` patch concat is ever written — operands
        are slices of the one padded input buffer (whether XLA fuses
        the slice into the contraction or materializes per-view copies
        is the measured question; see PERF.md round 4);
      - distinct from the failed crosscov band-trace (KFAC_CONV_PATCH_
        IMPL=crosscov, the round-2 3.3x regression): rows are
        contracted directly — the (W_p·C)^2 spatial Gram never exists
        and nothing is gather-assembled.

    Returns the (d, d) fp32 Gram (sum over rows, unscaled), in the
    (kh, kw, c) feature basis.
    """
    kh, kw = kernel_size
    sh, sw = strides
    b, h, w, c = a.shape
    (ph_lo, ph_hi), (pw_lo, pw_hi) = _canonical_pad(
        padding, (kh, kw), (h, w), (sh, sw))
    xp = jnp.pad(a, ((0, 0), (ph_lo, ph_hi), (pw_lo, pw_hi), (0, 0)))
    oh = (h + ph_lo + ph_hi - kh) // sh + 1
    ow = (w + pw_lo + pw_hi - kw) // sw + 1
    precision = None
    if compute_dtype is not None:
        xp = xp.astype(compute_dtype)
        if jnp.dtype(compute_dtype) == jnp.float32:
            precision = jax.lax.Precision.HIGHEST
    views = [
        jax.lax.slice(xp, (0, ki, kj, 0),
                      (b, ki + sh * (oh - 1) + 1,
                       kj + sw * (ow - 1) + 1, c),
                      (1, sh, sw, 1))
        for ki in range(kh) for kj in range(kw)]
    n = kh * kw
    blocks: dict[tuple[int, int], jax.Array] = {}
    for i in range(n):
        for j in range(i, n):
            blocks[(i, j)] = jax.lax.dot_general(
                views[i], views[j],
                dimension_numbers=(((0, 1, 2), (0, 1, 2)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=precision)
    gram = jnp.concatenate(
        [jnp.concatenate(
            [blocks[(i, j)] if i <= j else blocks[(j, i)].T
             for j in range(n)], axis=1)
         for i in range(n)], axis=0)
    # Diagonal blocks are v^T v (symmetric up to fp round-off); one
    # cheap (d, d) symmetrization matches get_cov's contract.
    return 0.5 * (gram + gram.T)


def _conv_out_geometry(a: jax.Array, kernel_size, strides, padding):
    """(oh, ow, rows, spatial) of the conv output for NHWC input ``a``."""
    kh, kw = kernel_size
    sh, sw = strides
    b, h, w, _ = a.shape
    (ph_lo, ph_hi), (pw_lo, pw_hi) = _canonical_pad(
        padding, (kh, kw), (h, w), (sh, sw))
    oh = (h + ph_lo + ph_hi - kh) // sh + 1
    ow = (w + pw_lo + pw_hi - kw) // sw + 1
    spatial = oh * ow
    return oh, ow, b * spatial, spatial


def _conv_bias_col(a: jax.Array, kernel_size, strides, padding,
                   rows: int, spatial: int) -> jax.Array:
    """Per-feature patch-row mean in (kh, kw, c) order, from the padded
    input's batch-sum instead of a second full read of the ~KH*KW x
    blown-up patch tensor (the covariance dot and a column reduce cannot
    be fused into one pass by XLA)."""
    kh, kw = kernel_size
    sh, sw = strides
    b, h, w, c = a.shape
    (ph_lo, ph_hi), (pw_lo, pw_hi) = _canonical_pad(
        padding, (kh, kw), (h, w), (sh, sw))
    oh = (h + ph_lo + ph_hi - kh) // sh + 1
    ow = (w + pw_lo + pw_hi - kw) // sw + 1
    xp_sum = jnp.pad(a, ((0, 0), (ph_lo, ph_hi), (pw_lo, pw_hi),
                         (0, 0))).sum(0, dtype=jnp.float32)
    piece_means = [
        jax.lax.slice(
            xp_sum, (ki, kj, 0),
            (ki + sh * (oh - 1) + 1, kj + sw * (ow - 1) + 1, c),
            (sh, sw, 1)).sum((0, 1)) / rows
        for ki in range(kh) for kj in range(kw)]
    return jnp.concatenate(piece_means) / (spatial * spatial)


def _conv_a_cov_crosscov(a: jax.Array, kernel_size, strides, padding,
                         compute_dtype) -> jax.Array | None:
    """Patch-Gram ``P^T P`` without materializing the im2col tensor.

    Exact reordering of the covariance sum: with ``U_ki`` the h-shifted
    strided view of the padded input flattened to ``(B*OH, Wp*C)``,

        M(ki, ki')[(w, c), (w', c')] = U_ki^T U_ki'
        A[(ki, kj, c), (ki', kj', c')] = sum_q M(ki, ki')
                                           [(kj + sw*q, c), (kj' + sw*q, c')]

    i.e. one full-lane-width matmul per unique (ki <= ki') pair followed
    by a band-trace (diagonal gather + einsum) on the (Wp*C)^2 output.
    The hope was to skip the KH*KW x patch-tensor HBM write+read and the
    lane-starved (rows, KH*KW*C) contraction.

    MEASURED NEGATIVE (round 2 → 3): as the default this regressed the
    tracked-config whole step from 24.3 to 80.2 ms/iter on v5e
    (PERF.md rounds 1-5; VERDICT round 2 bisection). Analytically the
    (Wp*C)^2 pair matmuls do ~2.6x the MACs of the patch contraction,
    and the band trace is built from ``jnp.take``/diagonal-einsum — the
    gather class :func:`pack_symmetric`'s note calls out as slow on
    TPU. Kept as an opt-in study path (KFAC_CONV_PATCH_IMPL=crosscov);
    the production default is the slices path. See PERF.md.

    Returns the unscaled Gram sum in (kh, kw, c) feature order, or None
    when the shape is out of the VMEM-safe regime (Wp*C > 1024 — e.g.
    ImageNet-resolution convs — or 1x1 kernels, where there is no patch
    blowup to avoid); callers fall back to the slices path.
    """
    kh, kw = kernel_size
    sh, sw = strides
    b, h, w, c = a.shape
    (ph_lo, ph_hi), (pw_lo, pw_hi) = _canonical_pad(
        padding, (kh, kw), (h, w), (sh, sw))
    wp = w + pw_lo + pw_hi
    if kh * kw == 1 or wp * c > 1024:
        return None
    xp = jnp.pad(a, ((0, 0), (ph_lo, ph_hi), (pw_lo, pw_hi), (0, 0)))
    oh = (h + ph_lo + ph_hi - kh) // sh + 1
    ow = (w + pw_lo + pw_hi - kw) // sw + 1
    precision = None
    if compute_dtype is not None and jnp.dtype(compute_dtype) == jnp.float32:
        precision = jax.lax.Precision.HIGHEST

    u = [jax.lax.slice(xp, (0, ki, 0, 0),
                       (b, ki + sh * (oh - 1) + 1, wp, c),
                       (1, sh, 1, 1)).reshape(b * oh, wp * c)
         for ki in range(kh)]
    # q-window index grid: row q of the band for w-offset kj
    qidx = (jnp.arange(kw)[:, None] + sw * jnp.arange(ow)[None, :])  # (kw, ow)
    blocks: dict[tuple[int, int], jax.Array] = {}
    for ki in range(kh):
        for ki2 in range(ki, kh):
            m = jnp.matmul(u[ki].T, u[ki2],
                           preferred_element_type=jnp.float32,
                           precision=precision).reshape(wp, c, wp, c)
            g1 = jnp.take(m, qidx, axis=0)           # (kw, ow, c, wp, c)
            g2 = jnp.take(g1, qidx, axis=3)          # (kw, ow, c, kw, ow, c)
            # diagonal over the two q axes + sum: the band trace
            blocks[(ki, ki2)] = jnp.einsum('kqcmqd->kcmd', g2)
    rows_out = []
    for ki in range(kh):
        row = []
        for ki2 in range(kh):
            blk = (blocks[(ki, ki2)] if ki <= ki2
                   else jnp.transpose(blocks[(ki2, ki)], (2, 3, 0, 1)))
            row.append(blk.reshape(kw * c, kw * c))
        rows_out.append(jnp.concatenate(row, axis=1))
    gram = jnp.concatenate(rows_out, axis=0)
    # Explicit symmetrization for consistency with get_cov: the diagonal
    # (ki == ki') blocks rely on u^T u being exactly symmetric otherwise.
    return (gram + gram.T) * 0.5


@profiling.scope('kfac/factors/conv2d_a')
def conv2d_a_factor(a: jax.Array, kernel_size, strides, padding,
                    has_bias: bool, compute_dtype=None) -> jax.Array:
    """A factor for conv2d from NHWC inputs via im2col patches.

    Same value as the reference formula (kfac/layers/conv.py:24-34:
    ``a / spatial_size`` after ``append_bias_ones``, then cov over all
    B*OH*OW rows), restructured so nothing batch-sized is ever copied:
    the 1/spatial scaling folds into the covariance output scale and the
    bias row/column is assembled analytically (profiled on v5e: relayout
    copies, the ones-column concat, and the spatial-size divide were
    ~95% of the whole K-FAC step time in a naive translation).

    Patch-extraction dispatch (``KFAC_CONV_PATCH_IMPL``):

      - ``auto`` (default): measured per-shape rule — ``dilated`` in
        the large-spatial/small-d regime (output spatial >= 2048 and
        d <= 640, e.g. ResNet-50 stem/conv2_x at ImageNet resolution),
        ``slices`` everywhere else (every CIFAR class). Basis:
        benchmarks/conv_a_microbench.py on v5e.
      - ``slices``: pad + KH*KW strided slices + concat in (kh, kw, c)
        order — the measured-fastest path on the tracked CIFAR config
        (24.3 ms/iter whole-step).
      - ``crosscov``: band-trace Gram that never materializes the patch
        tensor — measured 3.3x whole-step regression, opt-in study path
        only (see _conv_a_cov_crosscov).
      - ``dilated``: legacy ``conv_general_dilated_patches`` path with
        the (c, kh, kw) -> (kh, kw, c) permutation applied to the small
        (D, D) covariance; ~38 ms/iter whole-step (PERF.md rounds 1-5).
      - ``KFAC_FUSED_PATCH_COV=1``: opt-in fused Pallas study kernel
        (measured 18x slower than XLA per layer; kept for study).
    """
    kh, kw = kernel_size
    c = a.shape[-1]
    d = kh * kw * c
    if os.environ.get('KFAC_FUSED_PATCH_COV', '') == '1' and (
            jax.default_backend() == 'tpu' and d <= 640):
        # Opt-in fused VMEM patch-covariance Pallas kernel. Measured on
        # v5e (chained, cache-proof methodology): ~11 ms per stage-1
        # CIFAR layer vs ~0.6 ms for the XLA path below — Mosaic lowers
        # the in-kernel patch assembly (strided sublane slices + lane
        # concat of 16-lane pieces) as VPU shuffles that dwarf the
        # matmul, so the HBM-traffic saving never materializes. Kept as
        # an opt-in study kernel (like the Jacobi eigh); see PERF.md §2.
        # Opted in on a TPU: the kernel runs or the step fails with its
        # reason (a failed probe, or no usable image block for this
        # batch). KFAC_PALLAS_FALLBACK=1 alone selects the XLA path.
        from distributed_kfac_pytorch_tpu.ops import pallas_kernels
        if pallas_kernels.fused_patch_cov_supported():
            mult_bf16 = (compute_dtype is None
                         or jnp.dtype(compute_dtype) == jnp.bfloat16)
            return pallas_kernels.conv_a_factor_fused(
                a, kernel_size, strides, padding, has_bias,
                mult_bf16=mult_bf16)
    if (compute_dtype is None and a.dtype == jnp.float32
            and jax.default_backend() == 'tpu'):
        # Under the default precision contract the covariance matmul
        # rounds fp32 inputs to bf16 on the MXU anyway (see get_cov);
        # casting BEFORE the im2col materialization makes the ~KH*KW x
        # blown-up patch tensor bf16, halving the HBM write+read that
        # dominates conv factor updates. Strict fp32
        # (compute_dtype=float32) keeps fp32 patches.
        a = a.astype(jnp.bfloat16)
    impl = os.environ.get('KFAC_CONV_PATCH_IMPL', 'auto')
    if impl not in ('auto', 'slices', 'crosscov', 'dilated', 'pairs'):
        raise ValueError(
            f'KFAC_CONV_PATCH_IMPL={impl!r}: expected one of '
            "'auto', 'slices', 'crosscov', 'dilated', 'pairs'")
    if impl == 'auto':
        # Measured per-shape dispatch (benchmarks/conv_a_microbench.py
        # on v5e — re-run it for current numbers; PERF.md rounds 3-4
        # record the deciding measurements):
        #   - dilated wins the large-spatial small-d regime (c64@56x56
        #     ~1.3x, and the 7x7/s2 ImageNet stem ~60x, where the
        #     49-slice concat relayouts are catastrophic while the
        #     identity-kernel conv tiles well);
        #   - pairs (round 4: blocked pairwise view contraction, ~half
        #     the MACs via block symmetry) wins every measured d > 640
        #     multi-tap class — ImageNet c128/c256/c512 3x3 at 1.2-2.2x
        #     over slices, incl. stride 2;
        #   - slices wins the remaining (CIFAR-class) shapes: at c<=64
        #     the pairs path's c-wide blocks underfeed the MXU lanes
        #     (stage2/3 measured 1.6-2.5x worse) while the 9c-wide
        #     patch matmul tiles fine.
        oh, ow, _, spatial = _conv_out_geometry(a, kernel_size, strides,
                                                padding)
        # kh*kw == 1 stays on slices: a 1x1 "patch extraction" is a
        # single strided slice with no concat relayout, and both other
        # paths' extra work is pure waste there.
        if kh * kw == 1:
            impl = 'slices'
        elif spatial >= 2048 and d <= 640:
            impl = 'dilated'
        elif d > 640:
            impl = 'pairs'
        else:
            impl = 'slices'
    if impl == 'pairs' and kh * kw > 1:
        # Round-4 third angle: blocked pairwise view contraction —
        # ~half the patch path's MACs (block symmetry), no patch
        # concat. Per-shape numbers: benchmarks/conv_a_microbench.py;
        # dispatched from 'auto' only where measured to win (PERF.md
        # round 4). kh*kw == 1 is a plain covariance — slices path.
        gram = _conv_a_cov_pairs(a, kernel_size, strides, padding,
                                 compute_dtype)
        oh, ow, rows, spatial = _conv_out_geometry(
            a, kernel_size, strides, padding)
        cov = gram * (1.0 / (rows * spatial * spatial))
        if not has_bias:
            return cov
        bias_col = _conv_bias_col(a, kernel_size, strides, padding,
                                  rows, spatial).astype(cov.dtype)
        return _assemble_bias_factor(cov, bias_col,
                                     1.0 / (spatial * spatial))
    if impl == 'crosscov':
        # Opt-in ONLY: measured 3.3x whole-step regression as the
        # default on v5e (PERF.md rounds 1-5) — see _conv_a_cov_crosscov's
        # MEASURED NEGATIVE note. Falls through to the slices path
        # outside its shape regime.
        a_cc = a if compute_dtype is None else a.astype(compute_dtype)
        gram = _conv_a_cov_crosscov(a_cc, kernel_size, strides, padding,
                                    compute_dtype)
        if gram is not None:
            oh, ow, rows, spatial = _conv_out_geometry(
                a, kernel_size, strides, padding)
            cov = gram * (1.0 / (rows * spatial * spatial))
            if not has_bias:
                return cov
            bias_col = _conv_bias_col(a, kernel_size, strides, padding,
                                      rows, spatial).astype(cov.dtype)
            return _assemble_bias_factor(cov, bias_col,
                                         1.0 / (spatial * spatial))
    if impl in ('auto', 'slices', 'crosscov', 'pairs'):
        # DEFAULT: pad+slice+concat assembly — measured 24.3 ms/iter
        # whole-step on the tracked v5e config vs 80.2 for crosscov and
        # ~38 for dilated (PERF.md rounds 1-5, round-2 verdict bisection).
        # The dilated-patches op lowers to an identity-kernel conv whose
        # MXU FLOPs equal the covariance contraction itself; slicing is
        # pure data movement and emits (kh, kw, c) feature order
        # directly (no (D, D) basis permutation afterwards).
        patches = extract_conv2d_patches_slices(a, kernel_size, strides,
                                                padding)
        b, oh, ow, d = patches.shape
        spatial = oh * ow
        rows = b * spatial
        p2 = patches.reshape(rows, d)
        cov = get_cov(p2, scale=rows * spatial * spatial,
                      compute_dtype=compute_dtype)
        if not has_bias:
            return cov
        bias_col = _conv_bias_col(a, kernel_size, strides, padding,
                                  rows, spatial).astype(cov.dtype)
        return _assemble_bias_factor(cov, bias_col,
                                     1.0 / (spatial * spatial))
    # impl == 'dilated': legacy identity-kernel-conv im2col.
    patches = jax.lax.conv_general_dilated_patches(
        a, filter_shape=(kh, kw), window_strides=tuple(strides),
        padding=padding, dimension_numbers=('NHWC', 'HWIO', 'NHWC'))
    b, oh, ow, d = patches.shape
    spatial = oh * ow
    rows = b * spatial
    p2 = patches.reshape(rows, d)
    cov = get_cov(p2, scale=rows * spatial * spatial,
                  compute_dtype=compute_dtype)
    # Native feature order is (c, kh*kw) with c slowest; the factor basis
    # is (kh, kw, c) to match the flattened flax kernel. Permuting the
    # (D, D) covariance is ~1 MB of gather vs two relayouts of patches.
    perm = jnp.arange(d).reshape(c, kh * kw).T.reshape(-1)
    cov = cov[perm][:, perm]
    if not has_bias:
        return cov
    bias_col = (_column_mean(p2) / (spatial * spatial)
                ).astype(cov.dtype)[perm]
    return _assemble_bias_factor(cov, bias_col, 1.0 / (spatial * spatial))


@profiling.scope('kfac/factors/conv2d_grouped_a')
def conv2d_grouped_a_factor(a: jax.Array, kernel_size, strides, padding,
                            groups: int, has_bias: bool,
                            compute_dtype=None) -> jax.Array:
    """Per-group A factors for a grouped/depthwise conv: (G, da, da).

    Grouped convolution's Fisher block is block-diagonal over groups
    (group g's outputs see only its ``cin/G`` input channels), so the
    K-FAC approximation factorizes per group: ``A_g`` is the patch
    covariance restricted to group g's channels, with the same
    normalization as :func:`conv2d_a_factor` (cov over ``B*OH*OW`` rows
    of patches pre-divided by the spatial size). ``da = kh*kw*(cin/G)
    [+1]``. For depthwise convs (G = cin) each block is a tiny
    ``(kh*kw [+1])``-dim matrix — the standard K-FAC depthwise
    treatment, batched into one stacked einsum + (downstream) one
    batched damped inverse.

    No reference analogue: the reference's layer registry has no conv
    variant for ``feature_group_count != 1``
    (kfac/layers/__init__.py:13-36).
    """
    kh, kw = kernel_size
    c = a.shape[-1]
    if c % groups:
        raise ValueError(f'{c=} channels not divisible by {groups=}')
    cpg = c // groups
    if (compute_dtype is None and a.dtype == jnp.float32
            and jax.default_backend() == 'tpu'):
        a = a.astype(jnp.bfloat16)  # same contract as conv2d_a_factor
    patches = extract_conv2d_patches_slices(a, kernel_size, strides,
                                            padding)
    b, oh, ow, d = patches.shape
    spatial = oh * ow
    rows = b * spatial
    # (rows, kh*kw, G, cpg) -> (G, rows, kh*kw, cpg): per-group feature
    # order (kh, kw, cpg) matches the flattened flax kernel slice.
    p = patches.reshape(rows, kh * kw, groups, cpg)
    p = p.transpose(2, 0, 1, 3).reshape(groups, rows, kh * kw * cpg)
    precision = None
    if compute_dtype is not None:
        p = p.astype(compute_dtype)
        if jnp.dtype(compute_dtype) == jnp.float32:
            precision = jax.lax.Precision.HIGHEST
    cov = jnp.einsum('gri,grj->gij', p, p,
                     preferred_element_type=jnp.float32,
                     precision=precision)
    cov = (cov + cov.transpose(0, 2, 1)) * (
        0.5 / (rows * spatial * spatial))
    if not has_bias:
        return cov
    ones = jnp.ones((1, rows), jnp.float32)
    bias_cols = jnp.matmul(
        ones[None], p.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST)[:, 0, :] / (
        rows * spatial * spatial)
    corner = 1.0 / (spatial * spatial)
    return jax.vmap(
        lambda cv, bc: _assemble_bias_factor(cv, bc, corner))(
        cov, bias_cols.astype(cov.dtype))


@profiling.scope('kfac/factors/conv2d_grouped_g')
def conv2d_grouped_g_factor(g: jax.Array, groups: int,
                            compute_dtype=None) -> jax.Array:
    """Per-group G factors from NHWC output grads: (G, dg, dg).

    Output channels of a grouped conv are contiguous per group (XLA
    grouped-convolution layout), so group g's G factor is the covariance
    of its ``cout/G`` channel block, normalized like
    :func:`conv2d_g_factor`.
    """
    cout = g.shape[-1]
    if cout % groups:
        raise ValueError(f'{cout=} outputs not divisible by {groups=}')
    spatial = g.shape[1] * g.shape[2]
    g2 = g.reshape(-1, groups, cout // groups)
    rows = g2.shape[0]
    precision = None
    if compute_dtype is not None:
        g2 = g2.astype(compute_dtype)
        if jnp.dtype(compute_dtype) == jnp.float32:
            precision = jax.lax.Precision.HIGHEST
    cov = jnp.einsum('rgi,rgj->gij', g2, g2,
                     preferred_element_type=jnp.float32,
                     precision=precision)
    return (cov + cov.transpose(0, 2, 1)) * (
        0.5 / (rows * spatial * spatial))


# ---------------------------------------------------------------------------
# Stacked experts: per-expert statistics over the rows routed to each
# ---------------------------------------------------------------------------

_RAGGED_ROWS = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _experts_cov(x: jax.Array, group_sizes: jax.Array,
                 compute_dtype=None) -> jax.Array:
    """``(E, d, d)``: per expert ``sum_r x_r x_r^T`` over its own rows
    of the expert-sorted ``(rows, d)`` tensor ``x`` (rows past
    ``sum(group_sizes)`` enter no expert's sum). One grouped contraction
    whose work follows the rows that are there, not ``E x rows``: the
    Pallas ``tgmm`` where ``pallas_kernels.experts_cov_applies`` opens
    (counted ``kfac/factors/experts_cov_pallas``), else
    ``ragged_dot_general`` with the row dimension ragged
    (``experts_cov_xla``). Precision as :func:`get_cov`; float32 out,
    symmetric: ``tgmm``'s blocks are so bit for bit, ``ragged_dot``'s
    are symmetrized."""
    precision = None
    if compute_dtype is not None:
        x = x.astype(compute_dtype)
        if jnp.dtype(compute_dtype) == jnp.float32:
            precision = jax.lax.Precision.HIGHEST
    group_sizes = group_sizes.astype(jnp.int32)
    if pallas_kernels.experts_cov_applies(x):
        tracing.count('kfac/factors/experts_cov_pallas')
        # Entries (i, j) and (j, i) sum the same bf16 products, exact in
        # float32, over the same rows in the same order: max|C - C^T|
        # read 0 on a v5e at every fill, width and tiling swept
        # (benchmarks/grouped_experts_bench.py --covariances). The
        # symmetrization would be a transposing copy of the whole stack
        # that changes no bit.
        return pallas_kernels.experts_cov(x, group_sizes)
    tracing.count('kfac/factors/experts_cov_xla')
    cov = jax.lax.ragged_dot_general(
        x, x, group_sizes, _RAGGED_ROWS, precision=precision,
        preferred_element_type=jnp.float32)
    return (cov + cov.transpose(0, 2, 1)) * 0.5


@profiling.scope('kfac/factors/experts_a')
def experts_a_factor(a: jax.Array, group_sizes: jax.Array,
                     rows_per_token: int, compute_dtype=None) -> jax.Array:
    """Per-expert input sums ``sum_{t in T_e} a_t a_t^T / N``, ``(E, d,
    d)``, ``N`` the step's tokens (``rows / rows_per_token``).

    This is the SUM over the expert's rows on the common scale ``1/N``,
    not yet the expert's mean: its row count travels beside it
    (:func:`experts_row_share`) so that both average exactly over
    devices and micro-batches, and :func:`experts_running_avg` divides
    the two where the running average is updated.
    """
    return _experts_cov(a, group_sizes, compute_dtype) * (
        float(rows_per_token) / a.shape[0])


@profiling.scope('kfac/factors/experts_g')
def experts_g_factor(g: jax.Array, group_sizes: jax.Array,
                     rows_per_token: int, compute_dtype=None) -> jax.Array:
    """Per-expert ``G_e = sum_{t in T_e} g_t g_t^T / N``, ``(E, d, d)``.

    ``g`` is the gradient of the batch-mean loss at the expert's output
    (it carries the routing weight), and ``N`` the step's tokens: the
    normalisation :func:`linear_g_factor` gives every dense layer, so
    that ``A_e (x) G_e`` scales as the expert's Fisher block does.
    """
    return _experts_cov(g, group_sizes, compute_dtype) * (
        float(rows_per_token) / g.shape[0])


def experts_row_share(group_sizes: jax.Array, rows: int,
                      rows_per_token: int) -> jax.Array:
    """``n_e / N`` per expert, float32: the divisor of
    :func:`experts_a_factor`'s sums."""
    return group_sizes.astype(jnp.float32) * (float(rows_per_token) / rows)


def experts_running_avg(old: dict, a_sum: jax.Array, g_new: jax.Array,
                        row_share: jax.Array, alpha) -> dict:
    """EWMA of a stacked-expert layer's ``{'A', 'G'}``: ``A_e = a_sum_e
    / row_share_e`` (the mean over the expert's rows). An expert that
    got no row this step keeps both running averages untouched."""
    live = (row_share > 0)[:, None, None]
    a_new = a_sum / jnp.where(live, row_share[:, None, None], 1.0)
    return {'A': jnp.where(live, update_running_avg(
                a_new.astype(old['A'].dtype), old['A'], alpha), old['A']),
            'G': jnp.where(live, update_running_avg(
                g_new.astype(old['G'].dtype), old['G'], alpha), old['G'])}


@profiling.scope('kfac/factors/conv2d_g')
def conv2d_g_factor(g: jax.Array, compute_dtype=None) -> jax.Array:
    """G factor for conv2d from NHWC output grads.

    Reference parity: kfac/layers/conv.py:36-48 (there NCHW is transposed
    to channels-last first; NHWC already is). The 1/spatial scaling folds
    into the covariance output scale (no batch-sized elementwise copy).
    """
    spatial_size = g.shape[1] * g.shape[2]
    g2 = g.reshape(-1, g.shape[-1])
    return get_cov(g2, scale=g2.shape[0] * spatial_size * spatial_size,
                   compute_dtype=compute_dtype)


@profiling.scope('kfac/factors/embedding_a')
def embedding_a_factor(ids: jax.Array, vocab_size: int) -> jax.Array:
    """Diagonal A factor for an embedding layer: mean one-hot frequency.

    For one-hot input rows, A = E[a a^T] is diagonal with entry v equal to
    the empirical frequency of vocab id v. Returned as a vector (the
    diagonal). The reference's EmbeddingLayer computes ``mean(onehot^2)``
    (kfac/layers/embedding.py:32-63) but is hard-disabled
    (embedding.py:20); this implementation is live.
    """
    ids = ids.reshape(-1)
    counts = jnp.zeros((vocab_size,), jnp.float32).at[ids].add(1.0)
    return counts / ids.shape[0]


def pack_symmetric(m: jax.Array) -> jax.Array:
    """Pack a symmetric (n, n) matrix into ~half the elements, gather-free.

    Rectangular-full-packed-style layout built purely from
    triu/tril/slice/concat (no gather/scatter — XLA:CPU miscompiles
    gathers inside large shard_map programs, and on TPU masked dense ops
    vectorize better anyway): with ``k = ceil(n/2)`` (n padded to even),
    the strictly-lower zeros of the top ``k x n`` band of ``triu(m)``
    are filled with the transposed strict-lower content of the bottom
    ``k x k`` triangle, and the bottom block's diagonal rides in one
    extra row. Output shape ``(k + 1, n_pad)`` — about ``n^2/2 + n``
    elements on the wire instead of ``n^2``.
    """
    n = m.shape[-1]
    n_pad = n + (n % 2)
    if n_pad != n:
        m = jnp.pad(m, ((0, 1), (0, 1)))
    k = n_pad // 2
    u = jnp.triu(m)
    top = u[:k, :]                        # (k, n_pad)
    low = u[k:, k:]                       # (k, k) upper triangular
    # The strictly-lower slots of top[:, :k] are zero in triu(m); adding
    # the bottom triangle's strict-lower transpose fills them losslessly.
    top = top + jnp.concatenate(
        [jnp.tril(low.T, -1), jnp.zeros((k, n_pad - k), m.dtype)], axis=1)
    diag_low = jnp.sum(low * jnp.eye(k, dtype=m.dtype), axis=1)
    extra = jnp.concatenate(
        [diag_low, jnp.zeros((n_pad - k,), m.dtype)])[None, :]
    return jnp.concatenate([top, extra], axis=0)


def unpack_symmetric(packed: jax.Array, n: int) -> jax.Array:
    """Inverse of :func:`pack_symmetric` (gather-free)."""
    n_pad = packed.shape[-1]
    k = n_pad // 2
    top = packed[:k]
    diag_low = packed[k, :k]
    fill = jnp.tril(top[:, :k], -1)       # strict-lower of bottom block^T
    low = fill.T + diag_low[:, None] * jnp.eye(k, dtype=packed.dtype)
    u_top = jnp.concatenate([jnp.triu(top[:, :k]), top[:, k:]], axis=1)
    u_bot = jnp.concatenate([jnp.zeros((k, k), packed.dtype), low],
                            axis=1)
    u = jnp.concatenate([u_top, u_bot], axis=0)
    diag = jnp.sum(u * jnp.eye(n_pad, dtype=packed.dtype), axis=1)
    full = u + u.T - diag[:, None] * jnp.eye(n_pad, dtype=packed.dtype)
    return full[:n, :n]


def get_triu(x: jax.Array) -> jax.Array:
    """Flatten the upper triangle of a symmetric 2-D tensor.

    Reference-parity utility only (kfac/layers/utils.py:126-136): the
    production ``symmetry_aware_comm`` path uses the gather-free
    :func:`pack_symmetric` instead (gathers are slow on TPU and
    miscompile on XLA:CPU inside large shard_map programs). Kept because
    it is the reference's exact wire format (n(n+1)/2 flat elements),
    useful for interop/conversion.
    """
    if x.ndim != 2:
        raise ValueError('get_triu expects a 2-D tensor')
    n, m = x.shape
    if n > m:
        raise ValueError('tensor cannot have more rows than columns')
    rows, cols = jnp.triu_indices(n, k=0, m=m)
    return x[rows, cols]


def fill_triu(shape: Sequence[int], triu: jax.Array) -> jax.Array:
    """Rebuild a symmetric 2-D tensor from its flattened upper triangle.

    Reference parity: kfac/layers/utils.py:138-162.
    """
    if len(shape) != 2:
        raise ValueError('shape must be 2 dimensional')
    n, m = shape
    if n > m:
        raise ValueError('shape cannot have more rows than columns')
    rows, cols = jnp.triu_indices(n, k=0, m=m)
    out = jnp.zeros((n, m), dtype=triu.dtype).at[rows, cols].set(triu)
    # Mirror the strictly-lower triangle from the leading (n, n) square block
    # (all sub-diagonal entries of an n<=m matrix live there).
    sq = out[:, :n]
    strict = jnp.tril(jnp.ones((n, n), dtype=bool), k=-1)
    sym_sq = jnp.where(strict, sq.T, sq)
    return jnp.concatenate([sym_sq, out[:, n:]], axis=1)
