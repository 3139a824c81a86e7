"""Transformer decoder language model with Linear-layer K-FAC support.

The BASELINE tracked config 4 ("Transformer-XL-style LM with Linear-layer
K-FAC") workload. Attention is built from plain ``nn.Dense`` projections —
not flax's fused ``MultiHeadDotProductAttention`` (whose ``DenseGeneral``
params are invisible to the K-FAC layer registry, capture.py) — so every
projection (q/k/v/o) and MLP matmul is preconditioned exactly like the
reference preconditions LSTM-cell Linears (kfac/layers/linear.py:27-59).

Long contexts: pass ``seq_axis`` to shard the sequence over a mesh axis —
attention runs as a ring (``parallel.sequence.ring_self_attention``), the
rest of the network is token-local, and K-FAC factor statistics average
over the extra axis like any other batch sharding. The reference has no
analogue (SURVEY.md §5: sequence handling = BPTT truncation only).

Weight-sharing preconditioning (r13): every Dense here shares its
weight across the sequence axis, so ``KFAC(kfac_approx='reduce')``
switches their factor statistics to the KFAC-reduce approximation
(sum/mean over the sequence before the covariance, arXiv:2311.00636 —
a factor-seq cheaper factor update; ``sharing.approx``). With
``tie_weights`` the ``Embed.attend`` decoder call site then also feeds
the embedding's single factor pair (one inverse for the tied in/out
weight) instead of contributing gradient with no statistics.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_kfac_pytorch_tpu.parallel.sequence import (
    chunked_causal_attention,
    local_causal_attention,
    ring_self_attention,
)


class CausalSelfAttention(nn.Module):
    """Multi-head causal self-attention from four K-FAC-visible Denses.

    ``attn_block_size`` (single-device only) switches to the
    memory-efficient chunked fold — O(seq * block) live logits instead
    of O(seq^2) — for long contexts that fit one chip's compute but not
    monolithic attention's score tensor.
    """
    num_heads: int
    seq_axis: str | None = None
    attn_block_size: int | None = None
    causal: bool = True  # False = bidirectional (encoder/ViT use)
    dtype: Any = None    # compute dtype (params stay fp32); None = infer

    @nn.compact
    def __call__(self, x):
        d_model = x.shape[-1]
        if d_model % self.num_heads:
            raise ValueError(f'{d_model=} not divisible by '
                             f'{self.num_heads=}')
        head_dim = d_model // self.num_heads

        def heads(y):
            return y.reshape(*y.shape[:-1], self.num_heads, head_dim)

        q = heads(nn.Dense(d_model, dtype=self.dtype, name='q_proj')(x))
        k = heads(nn.Dense(d_model, dtype=self.dtype, name='k_proj')(x))
        v = heads(nn.Dense(d_model, dtype=self.dtype, name='v_proj')(x))
        if self.seq_axis is not None and self.attn_block_size is not None:
            raise ValueError(
                'seq_axis and attn_block_size are mutually exclusive: '
                'the ring already folds blockwise per device (set '
                'attn_block_size=None under sequence parallelism)')
        # One scope for the attention itself on every path, so a device
        # trace can tell its time from the projections' (the backward's
        # operations carry it as transpose(jvp(kfac_model/attention))).
        with jax.named_scope('kfac_model/attention'):
            if self.seq_axis is not None:
                o = ring_self_attention(q, k, v, axis_name=self.seq_axis,
                                        causal=self.causal)
            elif self.attn_block_size is not None:
                o = chunked_causal_attention(
                    q, k, v, block_size=self.attn_block_size,
                    causal=self.causal)
            else:
                o = local_causal_attention(q, k, v, causal=self.causal)
        o = o.reshape(*x.shape[:-1], d_model).astype(x.dtype)
        return nn.Dense(d_model, dtype=self.dtype, name='out_proj')(o)


class TransformerBlock(nn.Module):
    """Pre-LN block: LN -> attention -> LN -> GELU MLP.

    ``causal=True`` is the decoder (LM) form; ``causal=False`` the
    bidirectional encoder form (ViT, ``models/vit.py``).
    """
    num_heads: int
    mlp_ratio: int = 4
    dropout: float = 0.0
    seq_axis: str | None = None
    attn_block_size: int | None = None
    causal: bool = True
    dtype: Any = None

    @nn.compact
    def __call__(self, x, *, train: bool = True):
        d_model = x.shape[-1]
        h = CausalSelfAttention(self.num_heads, seq_axis=self.seq_axis,
                                attn_block_size=self.attn_block_size,
                                causal=self.causal,
                                dtype=self.dtype, name='attn')(
            nn.LayerNorm(dtype=self.dtype, name='ln1')(x))
        h = nn.Dropout(self.dropout, deterministic=not train)(h)
        x = x + h
        y = nn.LayerNorm(dtype=self.dtype, name='ln2')(x)
        y = nn.Dense(self.mlp_ratio * d_model, dtype=self.dtype,
                     name='mlp_in')(y)
        y = nn.gelu(y)
        y = nn.Dense(d_model, dtype=self.dtype, name='mlp_out')(y)
        y = nn.Dropout(self.dropout, deterministic=not train)(y)
        return x + y


class TransformerLM(nn.Module):
    """Decoder-only LM: embed + learned positions -> blocks -> logits.

    With ``seq_axis`` set, ``ids`` is the device-local contiguous sequence
    block and ``pos_offset`` must give its global start (device index *
    local length) so position embeddings line up across the ring.
    ``tie_weights`` reuses the embedding matrix as the decoder
    (``Embed.attend``), the flax-native form of the reference's
    ``register_shared_module`` tied-embedding path
    (kfac/preconditioner.py:404-470, torch_language_model.py:284-286).
    """
    vocab_size: int
    d_model: int = 512
    num_layers: int = 6
    num_heads: int = 8
    max_len: int = 2048
    dropout: float = 0.1
    tie_weights: bool = True
    seq_axis: str | None = None
    attn_block_size: int | None = None
    dtype: Any = None    # compute dtype (params stay fp32); None = infer

    @nn.compact
    def __call__(self, ids, *, train: bool = True, pos_offset=0):
        embed = nn.Embed(self.vocab_size, self.d_model,
                         dtype=self.dtype, name='embed')
        x = embed(ids)
        pos_table = self.param(
            'pos_embed', nn.initializers.normal(0.02),
            (self.max_len, self.d_model))
        pos = pos_offset + jnp.arange(ids.shape[-1])
        x = x + pos_table[pos].astype(x.dtype)
        x = nn.Dropout(self.dropout, deterministic=not train)(x)
        for i in range(self.num_layers):
            x = TransformerBlock(self.num_heads, dropout=self.dropout,
                                 seq_axis=self.seq_axis,
                                 attn_block_size=self.attn_block_size,
                                 dtype=self.dtype,
                                 name=f'block{i}')(x, train=train)
        x = nn.LayerNorm(dtype=self.dtype, name='ln_f')(x)
        if self.tie_weights:
            return embed.attend(x)
        return nn.Dense(self.vocab_size, dtype=self.dtype,
                        name='decoder')(x)


def get_model(vocab_size: int, size: str = 'small',
              **overrides) -> TransformerLM:
    """Named configs akin to the reference's model zoo entry points."""
    configs = {
        'tiny': dict(d_model=128, num_layers=2, num_heads=4),
        'small': dict(d_model=512, num_layers=6, num_heads=8),
        'base': dict(d_model=768, num_layers=12, num_heads=12),
        # Transformer-XL large shape (d1024, 18 layers, FFN 4096 —
        # BASELINE config 4's "Transformer-XL-style"): the factor set
        # straddles the 640 eigen/cholesky dispatch cutoff (q/k/v/o
        # A factors 1025, MLP A factors 1025/4097, G 1024/4096).
        'xl': dict(d_model=1024, num_layers=18, num_heads=16),
        # d2048 — the top rung of the r13 expand/reduce scaling ladder
        # (flagship_lm.py --approx-ab): MLP factors 8192/8193, where
        # KFAC-reduce's sum-over-sequence factor statistics are ~seq x
        # cheaper than the expand flatten (sharing.approx).
        'xxl': dict(d_model=2048, num_layers=24, num_heads=16),
    }
    if size not in configs:
        raise ValueError(f'unknown size {size!r}; have {sorted(configs)}')
    cfg = {**configs[size], **overrides}
    return TransformerLM(vocab_size=vocab_size, **cfg)
