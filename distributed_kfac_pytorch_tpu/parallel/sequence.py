"""Sequence/context parallelism: ring attention over a mesh axis.

The reference has no long-context machinery at all — sequence models are
handled by BPTT-35 truncation (reference examples/torch_language_model.py:52,
SURVEY.md §5) because attention/recurrence state never leaves one GPU. On a
TPU mesh, long contexts are first-class: the sequence dimension is sharded
over a mesh axis and attention runs as a *ring* — each device keeps its
query block resident and circulates key/value blocks around the axis via
``ppermute`` (ICI neighbor exchanges), accumulating softmax online with the
numerically-stable running-max trick (blockwise/flash attention). Peak
memory per device is O(T_local^2) for one logits block instead of
O(T_global^2), and the K/V transfer overlaps with the block matmuls.

``ring_self_attention`` is the in-``shard_map`` building block;
``local_causal_attention`` is the single-device fallback with identical
semantics, so models can be written once and run at either scale. It has
two forms of the one algorithm. The plain form (``plain_attention``) is a
single ``_block_attend`` over the whole sequence: (B, H, T, T) float32
scores in HBM, the same dot products as the ring's blocks. The fused form
(``ops.pallas_kernels.fused_attention``, PR 26) runs the same
online-softmax fold tile by tile inside one TPU kernel, forward and
backward, so nothing of size T x T is ever written; which one a call
takes is decided from the call itself (see ``local_causal_attention``).
``chunked_causal_attention`` is the single-device long-context leg:
the same block fold scanned within one device with per-block
rematerialization, pushing the attention-memory wall out by ~block/(3D)
without a mesh (see its docstring for the exact contract).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from distributed_kfac_pytorch_tpu.observability import tracing
from distributed_kfac_pytorch_tpu.ops import pallas_kernels

# Mesh axis name for sequence/context parallelism.
SEQ_AXIS = 'kfac_sp'

_NEG_INF = -1e30


def _block_attend(q, k, v, scale, qpos, kpos, causal, kvalid=None):
    """One blockwise attention contribution with positions for masking.

    q: (B, Tq, H, D), k/v: (B, Tk, H, D); qpos/kpos: (Tq,)/(Tk,) global
    token positions. ``kvalid`` (optional, (Tk,) bool) masks out padding
    keys — the chunked path pads ragged sequences up to a block
    multiple. Returns (scores_max, exp_scores @ v, exp_scores sum)
    per (B, H, Tq).

    Operands enter the QK^T einsum at their INPUT dtype with fp32
    accumulation (``preferred_element_type``) — the native MXU contract
    (bf16 in, fp32 out). Upcasting operands first would halve matmul
    throughput for identical accumulation; each logit is one q.k dot
    product of the same operand rows in either the ring or the local
    path, so blockwise vs monolithic results of this plain path stay
    bitwise-comparable at any operand dtype (the fused kernel computes
    the same products in another summation order: see
    ``local_causal_attention``). Softmax statistics (m, l) and the
    output accumulator are always fp32.
    """
    logits = jnp.einsum('bqhd,bkhd->bhqk', q, k,
                        preferred_element_type=jnp.float32) * scale
    mask = None
    if causal:
        mask = kpos[None, :] <= qpos[:, None]          # (Tq, Tk)
    if kvalid is not None:
        kv = jnp.broadcast_to(kvalid[None, :],
                              (qpos.shape[0], kpos.shape[0]))
        mask = kv if mask is None else mask & kv
    if mask is not None:
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    m = jnp.max(logits, axis=-1)                       # (B, H, Tq)
    p = jnp.exp(logits - m[..., None])
    if mask is not None:
        # Fully-masked rows: m == _NEG_INF and p == 1 everywhere; zero them.
        p = jnp.where((m == _NEG_INF)[..., None], 0.0, p)
    l = jnp.sum(p, axis=-1)                            # (B, H, Tq)
    o = jnp.einsum('bhqk,bkhd->bqhd', p, v,
                   preferred_element_type=jnp.float32)
    return m, o, l


def _fold_update(o, m, l, bm, bo, bl):
    """Fold one block's (max, out, sum) contribution into the running
    online-softmax accumulators. Shared by the ring loop and the bench's
    per-device emulation (benchmarks/ring_attention_bench.py), so the
    measured schedule can never drift from the shipped algorithm.

    exp of (-inf) - (-inf) is NaN; fully-masked contributions carry
    m == _NEG_INF (finite sentinel), so the corrections stay finite.
    """
    new_m = jnp.maximum(m, bm)
    corr_old = jnp.exp(m - new_m)
    corr_new = jnp.exp(bm - new_m)
    l = l * corr_old + bl * corr_new
    o = (o * jnp.moveaxis(corr_old, 1, 2)[..., None]
         + bo * jnp.moveaxis(corr_new, 1, 2)[..., None])
    return o, new_m, l


def ring_self_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        axis_name: str = SEQ_AXIS,
                        causal: bool = True) -> jax.Array:
    """Exact attention over the sequence sharded on ``axis_name``.

    Call inside ``shard_map``; ``q``/``k``/``v`` are this device's
    contiguous sequence block, shape (B, T_local, H, D) — device ``i``
    holds global tokens ``[i*T_local, (i+1)*T_local)``. K/V blocks rotate
    around the ring (``ppermute`` to the next axis index) while the local
    O/M/L accumulators fold each block in with the online-softmax update;
    after ``axis_size`` steps every query has attended to every key.
    Returns (B, T_local, H, D) in fp32.
    """
    s = jax.lax.psum(1, axis_name)          # axis size (static under SPMD)
    idx = jax.lax.axis_index(axis_name)
    b, t, h, d = q.shape
    scale = 1.0 / (d ** 0.5)
    local_pos = jnp.arange(t)
    qpos = idx * t + local_pos

    perm = [(i, (i + 1) % s) for i in range(s)]

    def fold_block(step, o, m, l, k_cur, v_cur):
        """Online-softmax accumulation of the currently-held K/V block."""
        # After `step` rotations we hold the block of device (idx - step).
        src = (idx - step) % s
        kpos = src * t + local_pos
        bm, bo, bl = _block_attend(q, k_cur, v_cur,
                                   scale, qpos, kpos, causal)
        return _fold_update(o, m, l, bm, bo, bl)

    def body(step, carry):
        o, m, l, k_cur, v_cur = carry
        o, m, l = fold_block(step, o, m, l, k_cur, v_cur)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return o, m, l, k_nxt, v_nxt

    o0 = jnp.zeros((b, t, h, d), jnp.float32)
    m0 = jnp.full((b, h, t), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, t), jnp.float32)
    # The last block-attend is peeled out of the loop so the final
    # (discarded) K/V rotation is never issued: s-1 ppermutes, s folds.
    o, m, l, k_last, v_last = jax.lax.fori_loop(
        0, s - 1, body, (o0, m0, l0, k, v))
    o, m, l = fold_block(s - 1, o, m, l, k_last, v_last)
    l = jnp.maximum(jnp.moveaxis(l, 1, 2)[..., None], 1e-30)
    return o / l


def plain_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    kvalid: jax.Array | None = None) -> jax.Array:
    """Monolithic attention: one ``_block_attend`` over the whole
    sequence, (B, H, T, T) float32 scores and probabilities in HBM.
    The path every backend can take, and the fused kernel's reference."""
    b, t, h, d = q.shape
    pos = jnp.arange(t)
    m, o, l = _block_attend(q, k, v, 1.0 / (d ** 0.5), pos, pos, causal,
                            kvalid=kvalid)
    l = jnp.maximum(jnp.moveaxis(l, 1, 2)[..., None], 1e-30)
    return o / l


def local_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           *, causal: bool = True,
                           kvalid: jax.Array | None = None) -> jax.Array:
    """Single-device attention with the same contract as the ring path:
    (B, T, H, D) in, exact softmax attention out, (B, T, H, D) fp32.
    ``kvalid`` ((T,) bool) masks padding keys as in ``_block_attend``.

    The call itself decides between two forms of the same fold
    (``ops.pallas_kernels.fused_attention_applies``): the fused TPU
    kernel when the backend is a TPU, there is no ``kvalid``, q/k/v
    share one shape and one dtype (bf16 or fp32), the head dim is one
    the kernel takes (64, 128) and T is a whole number of 256-row
    tiles; the plain path (``plain_attention``) otherwise — a ViT's
    T = 197, any CPU run — unchanged. ``KFAC_PALLAS_FALLBACK=1`` forces
    the plain path and records a ``pallas_fallback`` event.

    The fused path's contract: exact attention at the same precisions
    (operands enter QK^T and P.V at the input dtype with fp32
    accumulation, max/exp/sum and the output accumulator in fp32; P is
    cast to V's dtype for P.V, which is what the TPU's default matmul
    precision does to the plain path's fp32 P). It equals the plain
    path up to summation order and one rounding of the result to the
    input dtype, and writes no (B, H, T, T) tensor — scores,
    probabilities, mask or their gradients — to HBM, forward or
    backward (the backward recomputes a tile's probabilities from the
    saved row max and sum).

    Each traced call counts itself in the recorder
    (``observability.tracing``): ``kfac/attention/fused`` or
    ``kfac/attention/plain``.
    """
    if pallas_kernels.fused_attention_applies(q, k, v, kvalid):
        tracing.count('kfac/attention/fused')
        return pallas_kernels.fused_attention(q, k, v, causal=causal)
    tracing.count('kfac/attention/plain')
    return plain_attention(q, k, v, causal=causal, kvalid=kvalid)


def chunked_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                             *, block_size: int,
                             causal: bool = True) -> jax.Array:
    """Memory-efficient single-device attention: monolithic attention
    materializes O(S^2) logits (16 GB at B4/H16/S8192 fp32 — past one
    chip's HBM, the OOM wall recorded in PERF.md rounds 1-5), while
    this folds K/V blocks of ``block_size`` tokens through the same
    online-softmax update as the ring (`_block_attend`/`_fold_update`),
    keeping only O(S * block_size) logits live. Each fold is
    ``jax.checkpoint``-ed, so the backward pass recomputes block logits
    instead of storing them — the Rabe & Staats memory-efficient
    attention, here sharing the ring's exact fold code. Exact (not an
    approximation): same dot products, fp32 softmax statistics.

    Memory contract, precisely: logits never materialize beyond one
    (S x block) slab, but the scan backward still saves the carry —
    (S/block) copies of the (B, S, H, D) accumulators — so training
    residuals scale as O(S^2 * D / block): the S^2 wall is *shifted* by
    ~block/(3D) (measured: trains S=16384 on a 16 GB chip at B4/H16/D64
    where monolithic attention cannot run forward past S=4096;
    PERF.md rounds 1-5), not removed. For sequences past
    that, shard over a mesh axis with the ring. No reference analogue
    (BPTT-35 truncation is its only long-sequence mechanism). Returns
    (B, T, H, D) fp32.
    """
    b, t, h, d = q.shape
    if t <= block_size:
        # Degenerate single fold == monolithic attention: lets a model
        # configured for long-context blocks run short sequences (eval
        # batches, factor-shaping passes) without touching the knob.
        return local_causal_attention(q, k, v, causal=causal)
    # Ragged sequences (a ViT's num_patches + 1 cls token, ragged final
    # LM batches): only K/V must reshape into blocks, so they alone pad
    # up to a block multiple — queries stay length ``t`` (they are
    # never blocked). The final (padded) block is peeled out of the
    # scan and folded once with its pad keys masked via ``kvalid``, so
    # the hot scanned fold stays mask-free at ANY length (the online
    # softmax folds commute, so fold order does not matter). Exact at
    # any length.
    pad = -t % block_size
    if pad:
        zeros = jnp.zeros((b, pad, h, d))
        k, v = (jnp.concatenate([a, zeros.astype(a.dtype)], axis=1)
                for a in (k, v))
    s = (t + pad) // block_size
    scale = 1.0 / (d ** 0.5)
    qpos = jnp.arange(t)
    k_blocks = jnp.moveaxis(k.reshape(b, s, block_size, h, d), 1, 0)
    v_blocks = jnp.moveaxis(v.reshape(b, s, block_size, h, d), 1, 0)
    kpos = jnp.arange(t + pad).reshape(s, block_size)

    @jax.checkpoint
    def fold(carry, blk):
        o, m, l = carry
        k_blk, v_blk, kp = blk[:3]
        bm, bo, bl = _block_attend(q, k_blk, v_blk, scale, qpos, kp,
                                   causal,
                                   kvalid=blk[3] if len(blk) > 3 else None)
        return _fold_update(o, m, l, bm, bo, bl), None

    n_full = s - 1 if pad else s    # pad > 0 implies t > block, so >= 1
    o0 = jnp.zeros((b, t, h, d), jnp.float32)
    m0 = jnp.full((b, h, t), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, t), jnp.float32)
    (o, m, l), _ = jax.lax.scan(
        fold, (o0, m0, l0),
        (k_blocks[:n_full], v_blocks[:n_full], kpos[:n_full]))
    if pad:
        (o, m, l), _ = fold((o, m, l),
                            (k_blocks[-1], v_blocks[-1], kpos[-1],
                             kpos[-1] < t))
    l = jnp.maximum(jnp.moveaxis(l, 1, 2)[..., None], 1e-30)
    return o / l
