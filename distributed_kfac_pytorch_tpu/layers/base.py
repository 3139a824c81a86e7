"""Layer-kind dispatch: the functional KFACLayer contract.

The reference expresses per-module math as KFACLayer subclasses holding
mutable state (kfac/layers/{base,linear,conv,embedding}.py); here each kind
is a set of pure functions over a ``LayerSpec`` and that layer's captures:

  - ``compute_a_factor(spec, a_calls)`` / ``compute_g_factor(spec, g_calls)``
    (reference contract: kfac/layers/base.py:443-449);
  - ``grads_to_matrix`` / ``matrix_to_grads`` mapping a flax param subtree
    to the 2-D ``(out_dim, in_dim[+1])`` form the preconditioner works in
    (reference: kfac/layers/base.py:310-319, conv override conv.py:17-22).

Multi-call layers (LSTM cells etc.) sum per-call factors like the
reference's LinearMultiLayer (kfac/layers/linear.py:27-59).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from distributed_kfac_pytorch_tpu.capture import (
    CONV2D,
    CONV2D_GROUPED,
    EMBEDDING,
    EXPERTS,
    KFAC_REDUCE,
    LINEAR,
    LayerSpec,
)
from distributed_kfac_pytorch_tpu.ops import factors as F

KNOWN_KINDS = (LINEAR, CONV2D, CONV2D_GROUPED, EMBEDDING, EXPERTS)


def compute_a_factor(spec: LayerSpec, a_calls: Sequence[jax.Array],
                     compute_dtype=None) -> jax.Array:
    """Input-covariance factor A from per-call activations.

    ``compute_dtype`` selects the covariance matmul input dtype (fp32
    accumulation always) — see ops.factors.get_cov.

    ``spec.kfac_approx`` dispatches the weight-sharing approximation
    for dense/patch-conv layers: 'expand' (default) flattens the
    shared axis into covariance rows (the historical path, untouched);
    'reduce' averages activations over it first (sharing.approx,
    arXiv:2311.00636 Eq. 22). Static per-spec dispatch — the choice is
    program structure, not data. (A stacked-expert layer's statistics
    need its rows' routing too: :func:`experts_contrib`.)
    """
    reduced = spec.kfac_approx == KFAC_REDUCE
    if spec.kind == LINEAR:
        fn = (F.linear_a_factor_reduced if reduced
              else F.linear_a_factor)
        out = None
        for a in a_calls:
            cur = fn(a, spec.has_bias, compute_dtype=compute_dtype)
            out = cur if out is None else out + cur
        return out
    if spec.kind == CONV2D:
        fn = (F.conv2d_a_factor_reduced if reduced
              else F.conv2d_a_factor)
        out = None
        for a in a_calls:
            cur = fn(a, spec.kernel_size, spec.strides,
                     spec.padding, spec.has_bias,
                     compute_dtype=compute_dtype)
            out = cur if out is None else out + cur
        return out
    if spec.kind == CONV2D_GROUPED:
        out = None
        for a in a_calls:
            cur = F.conv2d_grouped_a_factor(
                a, spec.kernel_size, spec.strides, spec.padding,
                spec.feature_group_count, spec.has_bias,
                compute_dtype=compute_dtype)
            out = cur if out is None else out + cur
        return out
    if spec.kind == EMBEDDING:
        out = None
        for ids in a_calls:
            cur = F.embedding_a_factor(ids, spec.vocab_size)
            out = cur if out is None else out + cur
        return out
    raise ValueError(f'unknown layer kind {spec.kind!r}')


def experts_contrib(spec: LayerSpec, entry: dict,
                    compute_dtype=None, a_of: dict | None = None) -> dict:
    """One batch's contribution of a stacked-expert layer, ``{'A', 'G',
    'rows'}``, from its capture entry (``'a'``, ``'g'`` and ``'rows'``,
    the per-call ``group_sizes``): per expert the input SUMS and ``G_e``
    on the common scale ``1/N`` and the expert's share of the rows
    (``ops.factors.experts_*``), each summed over calls. All three are
    linear in the batch, so they average exactly over micro-batches and
    over the mesh; ``ops.factors.experts_running_avg`` divides ``A`` by
    ``rows`` where the running average is updated.

    ``a_of``: the contribution of the layer that owns this layer's A
    (``spec.a_owner``: both were handed the same rows and the same
    group sizes); its ``'A'`` and ``'rows'`` are this layer's, and only
    ``'G'`` is contracted here."""
    k = spec.rows_per_token
    out = None
    for a, g, n in zip(entry['a'], entry['g'], entry['rows']):
        cur = {}
        if a_of is None:
            cur['A'] = F.experts_a_factor(a, n, k,
                                          compute_dtype=compute_dtype)
        cur['G'] = F.experts_g_factor(g, n, k, compute_dtype=compute_dtype)
        if a_of is None:
            cur['rows'] = F.experts_row_share(n, a.shape[0], k)
        out = cur if out is None else jax.tree.map(jnp.add, out, cur)
    if a_of is not None:
        out = {'A': a_of['A'], 'G': out['G'], 'rows': a_of['rows']}
    return out


def compute_g_factor(spec: LayerSpec, g_calls: Sequence[jax.Array],
                     compute_dtype=None) -> jax.Array:
    """Output-gradient covariance factor G from per-call probe grads.

    Under ``spec.kfac_approx == 'reduce'`` the grads are summed over
    the shared axis before the covariance (the Eq. 22 counterpart of
    the activation mean — see :func:`compute_a_factor`).
    """
    reduced = spec.kfac_approx == KFAC_REDUCE
    if spec.kind in (LINEAR, EMBEDDING):
        fn = (F.linear_g_factor_reduced
              if reduced and spec.kind == LINEAR else F.linear_g_factor)
        out = None
        for g in g_calls:
            cur = fn(g, compute_dtype=compute_dtype)
            out = cur if out is None else out + cur
        return out
    if spec.kind == CONV2D:
        fn = (F.conv2d_g_factor_reduced if reduced
              else F.conv2d_g_factor)
        out = None
        for g in g_calls:
            cur = fn(g, compute_dtype=compute_dtype)
            out = cur if out is None else out + cur
        return out
    if spec.kind == CONV2D_GROUPED:
        out = None
        for g in g_calls:
            cur = F.conv2d_grouped_g_factor(
                g, spec.feature_group_count, compute_dtype=compute_dtype)
            out = cur if out is None else out + cur
        return out
    raise ValueError(f'unknown layer kind {spec.kind!r}')


#: capture-entry keys that are QUADRATIC in the output-gradients —
#: under SPMD (local-mean loss) and gradient accumulation these need
#: the ``1/world**2`` / ``1/accum**2`` rescale the primary 'G' gets;
#: everything else ('A', 'G_a') is activation-derived and needs none.
#: Single point of truth for parallel.distributed's contrib scaling.
GRAD_QUADRATIC_KEYS = ('G', 'A_g2')


def compute_tied_factor_extras(spec: LayerSpec, entry: dict,
                               compute_dtype=None):
    """Tied-embedding attend-site factor contributions, or None.

    For an in/out-tied embedding (``spec.tied_calls > 0``, captures
    carrying the ``a_tied``/``g_tied`` attend streams), the attend call
    site's Fisher block folds into the SAME factor pair as the lookup
    (sum of per-site Kronecker approximations — the multi-call /
    LinearMultiLayer semantics applied across the tie):

      - ``A_g2``: diagonal vocab-side term ``diag cov(dL/dlogits)``
        (ops.factors.embedding_tied_a_diag) — added to the lookup's
        one-hot-frequency diagonal. QUADRATIC in the output grads
        (see GRAD_QUADRATIC_KEYS).
      - ``G_a``: d-side term ``cov(attend inputs)`` — added to the
        lookup's output-grad covariance. Activation-derived.

    Returns ``{'A_g2': vec, 'G_a': mat}`` (per-call sums) or None for
    layers without tied captures. One factor pair, one inverse entry:
    the state layout is untouched — only the statistics change.
    """
    if spec.kind != EMBEDDING or not entry.get('g_tied'):
        return None
    a_diag = None
    for g in entry['g_tied']:
        cur = F.embedding_tied_a_diag(g)
        a_diag = cur if a_diag is None else a_diag + cur
    g_cov = None
    for x in entry['a_tied']:
        cur = F.get_cov(F.collapse_batch_dims(x),
                        compute_dtype=compute_dtype)
        g_cov = cur if g_cov is None else g_cov + cur
    return {'A_g2': a_diag, 'G_a': g_cov}


def grads_to_matrix(spec: LayerSpec, grads: dict) -> jax.Array:
    """Flax param-grad subtree -> 2-D (out_dim, in_dim[+1]) matrix.

    Layouts: flax Dense kernels are (in, out) [torch is (out, in)], conv
    kernels (kh, kw, cin, cout) [torch (cout, cin, kh, kw)], embeddings
    (vocab, dim). The matrix form matches the factor bases produced by
    compute_a_factor/compute_g_factor.
    """
    if spec.kind == LINEAR:
        mat = grads['kernel'].T
        if spec.has_bias:
            mat = jnp.concatenate([mat, grads['bias'][:, None]], axis=1)
        return mat
    if spec.kind == CONV2D:
        k = grads['kernel']
        mat = k.reshape(-1, k.shape[-1]).T  # (cout, kh*kw*cin)
        if spec.has_bias:
            mat = jnp.concatenate([mat, grads['bias'][:, None]], axis=1)
        return mat
    if spec.kind == CONV2D_GROUPED:
        # (kh, kw, cpg, cout) -> (G, cout/G, kh*kw*cpg [+1]): output
        # channels are contiguous per group (XLA grouped-conv layout).
        k = grads['kernel']
        groups = spec.feature_group_count
        d = k.shape[0] * k.shape[1] * k.shape[2]
        cout = k.shape[-1]
        mat = k.reshape(d, groups, cout // groups).transpose(1, 2, 0)
        if spec.has_bias:
            b = grads['bias'].reshape(groups, cout // groups, 1)
            mat = jnp.concatenate([mat, b], axis=-1)
        return mat
    if spec.kind == EMBEDDING:
        # (vocab, dim): A is diagonal over vocab, G is (dim, dim).
        return grads['embedding']
    if spec.kind == EXPERTS:
        # (E, in, out) -> (E, out, in): one matrix per expert.
        return grads['kernel'].transpose(0, 2, 1)
    raise ValueError(f'unknown layer kind {spec.kind!r}')


def matrix_to_grads(spec: LayerSpec, mat: jax.Array,
                    like: dict) -> dict:
    """Inverse of grads_to_matrix, shaped like the param subtree ``like``."""
    out = dict(like)
    if spec.kind == LINEAR:
        if spec.has_bias:
            out['bias'] = mat[:, -1].reshape(like['bias'].shape)
            mat = mat[:, :-1]
        out['kernel'] = mat.T.reshape(like['kernel'].shape)
        return out
    if spec.kind == CONV2D:
        if spec.has_bias:
            out['bias'] = mat[:, -1].reshape(like['bias'].shape)
            mat = mat[:, :-1]
        out['kernel'] = mat.T.reshape(like['kernel'].shape)
        return out
    if spec.kind == CONV2D_GROUPED:
        if spec.has_bias:
            out['bias'] = mat[..., -1].reshape(like['bias'].shape)
            mat = mat[..., :-1]
        # (G, cout/G, d) -> (d, G, cout/G) -> (kh, kw, cpg, cout)
        out['kernel'] = mat.transpose(2, 0, 1).reshape(
            like['kernel'].shape)
        return out
    if spec.kind == EMBEDDING:
        out['embedding'] = mat.reshape(like['embedding'].shape)
        return out
    if spec.kind == EXPERTS:
        out['kernel'] = mat.transpose(0, 2, 1)
        return out
    raise ValueError(f'unknown layer kind {spec.kind!r}')


def factor_shapes(spec: LayerSpec, params: dict) -> tuple[int, int]:
    """(A_dim, G_dim) for this layer, from its param subtree shapes.

    Used by worker assignment before any data has flowed — unlike the
    reference, which must defer assignment until first factors exist
    (preconditioner.py:499-504), factor dims are static functions of the
    param shapes.
    """
    if spec.kind == LINEAR:
        in_dim, out_dim = params['kernel'].shape
        return in_dim + int(spec.has_bias), out_dim
    if spec.kind == CONV2D:
        kh, kw, cin, cout = params['kernel'].shape
        return kh * kw * cin + int(spec.has_bias), cout
    if spec.kind == CONV2D_GROUPED:
        # PER-GROUP dims; the layer carries feature_group_count stacked
        # (da, da)/(dg, dg) blocks rather than one dense factor pair.
        kh, kw, cpg, cout = params['kernel'].shape
        return (kh * kw * cpg + int(spec.has_bias),
                cout // spec.feature_group_count)
    if spec.kind == EMBEDDING:
        vocab, dim = params['embedding'].shape
        return vocab, dim  # A is diagonal (vector of length vocab)
    if spec.kind == EXPERTS:
        # PER-EXPERT dims, stacked num_experts times like a grouped
        # conv's per-group blocks.
        _, in_dim, out_dim = params['kernel'].shape
        return in_dim, out_dim
    raise ValueError(f'unknown layer kind {spec.kind!r}')
