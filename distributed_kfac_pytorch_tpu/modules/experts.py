"""Stacked-expert linear layer: one weight per expert, rows routed to it.

``ExpertsDense`` holds ``(num_experts, in, out)`` and multiplies each
row of its input by the weight of the expert the row was routed to. The
rows arrive sorted by expert, ``group_sizes[e]`` of them for expert
``e``. On a TPU the product, its input gradient and its weight gradient
run on the grouped-matmul kernels JAX ships
(``ops.pallas_kernels.grouped_matmul``), whose grid visits only the row
tiles that hold routed rows, so that their time follows the rows that
are there and not ``num_experts x rows`` nor the buffer. A call the
kernels cannot tile (``in`` or ``out`` not a multiple of 128, rows not
a whole number of row tiles) and every call off the TPU take
``jax.lax.ragged_dot``: XLA's own grouped kernel on a TPU, a dense
expansion elsewhere, which the CPU tests can afford. Each traced call
counts its route: ``kfac/moe/grouped_pallas`` or ``kfac/moe/grouped_xla``
(``observability.tracing``).

**Rows past ``sum(group_sizes)`` are undefined**, in the output and, in
the backward pass, in the input's gradient: the caller's buffer is sized
for the worst routing, so most steps leave a long tail, and the kernel
neither reads nor writes it (on the chip the tail of a fresh output
holds whatever the memory held, NaN included), nor lets it reach the
weight gradient. Whoever moves rows between tokens and this layout
(``dispatch_rows`` / ``combine_rows``) looks at the valid rows only;
everything in between is row-wise.

The K-FAC registry (``capture.py``) sees this module as layer kind
``EXPERTS``: per expert an ``A`` and a ``G`` factor, contracted over the
expert's own rows (``ops.factors.experts_a_factor`` /
``experts_g_factor``), which never touch the tail either.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_kfac_pytorch_tpu.observability import tracing
from distributed_kfac_pytorch_tpu.ops import pallas_kernels

#: Rows a ``lax.cond`` of :func:`dispatch_rows` / :func:`combine_rows`
#: covers: the part of the buffer a step pays for is the routed rows
#: rounded up to this.
ROW_CHUNK = 4096


class ExpertsDense(nn.Module):
    """``y[r] = x[r] @ kernel[expert_of_row(r)]``, bias-free.

    ``rows_per_token``: how many routed rows one token of the step makes
    (the router's top-k), so that the statistics can be normalised by
    the step's tokens as every other layer's are.
    """
    num_experts: int
    features: int
    rows_per_token: int = 1
    dtype: Any = None
    param_dtype: Any = jnp.float32
    kernel_init: Callable = nn.initializers.normal(0.02)

    @nn.compact
    def __call__(self, x, group_sizes):
        kernel = self.param('kernel', self.kernel_init,
                            (self.num_experts, x.shape[-1], self.features),
                            self.param_dtype)
        dtype = self.dtype or x.dtype
        with jax.named_scope('kfac_model/moe/experts'):
            x, kernel = x.astype(dtype), kernel.astype(dtype)
            group_sizes = group_sizes.astype(jnp.int32)
            if pallas_kernels.grouped_matmul_applies(x, kernel):
                tracing.count('kfac/moe/grouped_pallas')
                return pallas_kernels.grouped_matmul(x, kernel, group_sizes)
            tracing.count('kfac/moe/grouped_xla')
            return jax.lax.ragged_dot(x, kernel, group_sizes)


def _row_chunks(rows: int):
    """``(start, stop)`` of the static chunks that cover ``rows``."""
    return [(lo, min(lo + ROW_CHUNK, rows))
            for lo in range(0, rows, ROW_CHUNK)]


def _row_valid(lo, rows: int, rows_here) -> jax.Array:
    return (lo + jnp.arange(rows) < rows_here)[:, None]


def dispatch_rows(h: jax.Array, token_of_row: jax.Array,
                  rows_here: jax.Array) -> jax.Array:
    """``h[token_of_row]`` for the first ``rows_here`` rows, zeros after.

    ``h``: ``(tokens, d)``; ``token_of_row``: ``(rows,)``, the buffer's
    rows sorted by expert. Chunk by chunk under ``lax.cond``, so that
    forward and backward move ``rows_here`` rows (rounded up to
    ``ROW_CHUNK``) and not the buffer; the mask keeps the undefined
    gradients of the tail away from the tokens.
    """
    def take(h, idx, lo):
        return jnp.where(_row_valid(lo, idx.shape[0], rows_here),
                         h[idx], 0)

    def none(h, idx, lo):
        return jnp.zeros((idx.shape[0], h.shape[-1]), h.dtype)

    return jnp.concatenate([
        jax.lax.cond(lo < rows_here, take, none, h, token_of_row[lo:hi], lo)
        for lo, hi in _row_chunks(token_of_row.shape[0])])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def combine_rows(y: jax.Array, weight_of_row: jax.Array,
                 token_of_row: jax.Array, rows_here: jax.Array,
                 tokens: int) -> jax.Array:
    """``(tokens, d)`` float32: each token's sum of ``weight x y`` over
    its rows among the first ``rows_here``; the rows after are not
    read. Chunked like :func:`dispatch_rows`, forward and backward: the
    backward is written out (``custom_vjp``) so that ``y``'s gradient is
    one concatenation of chunks, where differentiating the chunks' own
    slices would sum a buffer-sized padded array a chunk."""
    def add(out, y, w, idx, lo):
        # y is masked BEFORE the product: an undefined (NaN) row times
        # a zero weight is NaN.
        rows = jnp.where(_row_valid(lo, idx.shape[0], rows_here), y,
                         0).astype(jnp.float32) * w[:, None]
        return out + jax.ops.segment_sum(rows, idx, num_segments=tokens)

    def skip(out, y, w, idx, lo):
        return out

    out = jnp.zeros((tokens, y.shape[-1]), jnp.float32)
    for lo, hi in _row_chunks(token_of_row.shape[0]):
        out = jax.lax.cond(lo < rows_here, add, skip, out, y[lo:hi],
                           weight_of_row[lo:hi], token_of_row[lo:hi], lo)
    return out


def _combine_fwd(y, weight_of_row, token_of_row, rows_here, tokens):
    return (combine_rows(y, weight_of_row, token_of_row, rows_here, tokens),
            (y, weight_of_row, token_of_row, rows_here))


def _combine_bwd(tokens, residuals, ct):
    y, weight_of_row, token_of_row, rows_here = residuals

    def grads(ct, y, w, idx, lo):
        valid = _row_valid(lo, idx.shape[0], rows_here)
        g = jnp.where(valid, ct[idx], 0.0)
        dw = jnp.sum(g * jnp.where(valid, y, 0).astype(jnp.float32), axis=-1)
        return (g * w[:, None]).astype(y.dtype), dw.astype(w.dtype)

    def none(ct, y, w, idx, lo):
        return jnp.zeros_like(y), jnp.zeros_like(w)

    parts = [jax.lax.cond(lo < rows_here, grads, none, ct, y[lo:hi],
                          weight_of_row[lo:hi], token_of_row[lo:hi], lo)
             for lo, hi in _row_chunks(token_of_row.shape[0])]
    return (jnp.concatenate([dy for dy, _ in parts]),
            jnp.concatenate([dw for _, dw in parts]), None, None)


combine_rows.defvjp(_combine_fwd, _combine_bwd)
