"""Decoder LM with windowed and full attention mixed, grouped key/value
heads and softmax-routed experts, K-FAC-visible.

The Mellum 2 family's block (JetBrains, ``model_type`` ``mellum``; HF
``config.json`` of Mellum2-12B-A2.5B; every projection bias-free, every
norm an RMSNorm computed in float32 with eps 1e-6):

    x += Attn_l(RMSNorm(x));  x += MoE(RMSNorm(x))

- **Attention**: ``q = W_q h`` (``num_heads`` x ``head_dim``), ``k = W_k
  h`` and ``v = W_v h`` (``num_kv_heads`` x ``head_dim`` each); q head
  ``j`` reads kv head ``j // (num_heads / num_kv_heads)``. Scores
  ``q.k / sqrt(head_dim)``, softmax, ``P.v``, ``W_o``. A layer is
  ``sliding_attention`` (query ``i`` reads keys ``i - window < j <= i``)
  or ``full_attention`` (``j <= i``), as ``layer_types`` says, the list
  repeating where it is shorter than the stack. Both go through
  ``parallel.sequence.local_causal_attention``, which takes the splash
  kernels on a TPU.
- **RoPE** in the half-split layout on q and k (``looped_lm.rope_half``),
  two tables in one model: the sliding layers at ``theta`` as it is; the
  full layers YaRN's (:func:`yarn_inv_freq`): with ``r(n) = d ln(L /
  (2 pi n)) / (2 ln theta)``, ``lo = floor(r(beta_fast))``, ``hi =
  ceil(r(beta_slow))`` and ``ramp_i = clip((i - lo) / (hi - lo), 0,
  1)``, ``inv_freq_i = theta**(-2i/d) (ramp_i / factor + 1 - ramp_i)``,
  and cos and sin times the attention factor.
- **MoE** (``mla_moe_lm.MoE``): router logits in float32, softmax over
  all ``num_experts``, the ``num_experts_per_tok`` largest, weights
  renormalised over the choice; no shared expert, no correction bias, no
  scaling. Every expert a SwiGLU of ``moe_intermediate_size``.
- token embedding with nothing added, a final RMSNorm, an untied head.

**Shares.** ``experts_held`` says which of the experts this program
holds (the router scores all of them; see ``mla_moe_lm``); attention
holds every head.

Every projection is an ``nn.Dense`` and every expert matrix an
``ExpertsDense``, so the K-FAC registry sees them all: q/k/v read one
traced value and share q's A, up shares gate's; RMSNorm scales are left
to the optimizer, and so is the head wherever ``skip_layers`` names it.
RMSNorm, the projection and the experts are ``models/mla_moe_lm.py``'s.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_kfac_pytorch_tpu.models.looped_lm import rope_half
from distributed_kfac_pytorch_tpu.models.mla_moe_lm import (
    INIT,
    MoE,
    RMSNorm,
    dense,
)
from distributed_kfac_pytorch_tpu.parallel.sequence import (
    local_causal_attention,
)

SLIDING, FULL = 'sliding_attention', 'full_attention'


def yarn_range(head_dim: int, theta: float, original_max_position: int,
               beta_fast: float, beta_slow: float) -> tuple[int, int]:
    """``(lo, hi)``: the frequency indices between which YaRN's ramp
    runs from extrapolation to interpolation (HF ``rope_type`` yarn,
    truncated)."""
    def r(rotations):
        return (head_dim * math.log(original_max_position
                                    / (2 * math.pi * rotations))
                / (2 * math.log(theta)))
    return (max(math.floor(r(beta_fast)), 0),
            min(math.ceil(r(beta_slow)), head_dim - 1))


def yarn_inv_freq(head_dim: int, theta: float, factor: float,
                  original_max_position: int, beta_fast: float,
                  beta_slow: float) -> jax.Array:
    """YaRN's ``(head_dim / 2,)`` frequencies in float32: the plain ones
    below ``lo``, divided by ``factor`` past ``hi``, a linear ramp
    between (:func:`yarn_range`)."""
    lo, hi = yarn_range(head_dim, theta, original_max_position, beta_fast,
                        beta_slow)
    plain = 1.0 / theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - lo)
                    / max(hi - lo, 1e-3), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


class Attention(nn.Module):
    """Causal attention over the whole past or a window of it, grouped
    key/value heads, half-split RoPE (YaRN's on full layers). ``qk_norm``
    adds a per-head RMSNorm over ``head_dim`` to q and to k before RoPE
    (``q_norm``, ``k_norm``: Qwen3's); ``diffusion_block`` puts the
    block-diffusion mask of blocks of that many positions in the causal
    rule's place (``models/block_diffusion_lm.py``)."""
    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: int | None
    rope_theta: float
    yarn: dict | None = None
    qk_norm: bool = False
    diffusion_block: int | None = None
    dtype: Any = None

    @nn.compact
    def __call__(self, x, pos):
        b, t, d_model = x.shape
        hd = self.head_dim
        q = dense(self.num_heads * hd, self.dtype, 'q_proj')(x)
        k = dense(self.num_kv_heads * hd, self.dtype, 'k_proj')(x)
        v = dense(self.num_kv_heads * hd, self.dtype, 'v_proj')(x)
        q = q.reshape(b, t, self.num_heads, hd)
        k = k.reshape(b, t, self.num_kv_heads, hd)
        v = v.reshape(b, t, self.num_kv_heads, hd)
        if self.qk_norm:
            q = RMSNorm(dtype=self.dtype, name='q_norm')(q)
            k = RMSNorm(dtype=self.dtype, name='k_norm')(k)
        rope = {}
        if self.yarn is not None:
            rope = dict(inv_freq=yarn_inv_freq(
                hd, self.rope_theta, self.yarn['factor'],
                self.yarn['original_max_position_embeddings'],
                self.yarn['beta_fast'], self.yarn['beta_slow']),
                scale=self.yarn['attention_factor'])
        q = rope_half(q, pos, self.rope_theta, **rope)
        k = rope_half(k, pos, self.rope_theta, **rope)
        if self.diffusion_block is not None:
            with jax.named_scope('kfac_model/attention/block_diffusion'):
                o = local_causal_attention(
                    q, k, v, diffusion_block=self.diffusion_block)
        else:
            kind = 'full' if self.window is None else 'sliding'
            with jax.named_scope(f'kfac_model/attention/{kind}'):
                o = local_causal_attention(q, k, v, causal=True,
                                           window=self.window)
        return dense(d_model, self.dtype, 'o_proj')(
            o.reshape(b, t, self.num_heads * hd).astype(x.dtype))


class Block(nn.Module):
    attention: dict
    moe: dict
    dtype: Any = None

    @nn.compact
    def __call__(self, x, pos):
        norm = lambda name: RMSNorm(dtype=self.dtype, name=name)  # noqa: E731
        x = x + Attention(dtype=self.dtype, name='self_attn',
                          **self.attention)(norm('input_layernorm')(x), pos)
        return x + MoE(dtype=self.dtype, name='mlp', **self.moe)(
            norm('post_attention_layernorm')(x))


class WindowMoeLM(nn.Module):
    """Embedding -> blocks -> RMSNorm -> untied head. ``train`` is
    accepted for the training entry points and changes nothing (there is
    no dropout); ``pos_offset`` shifts the RoPE positions. ``yarn``
    holds the full layers' ``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``
    and ``attention_factor`` (None: plain RoPE there too)."""
    vocab_size: int
    d_model: int = 2304
    num_layers: int = 28
    layer_types: tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL)
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 1024
    rope_theta: float = 5e5
    yarn: dict | None = None
    num_experts: int = 64
    experts_held: tuple[int, int] = (0, 64)
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 896
    dtype: Any = None

    @nn.compact
    def __call__(self, ids, *, train: bool = True, pos_offset=0):
        del train
        x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                     embedding_init=INIT, name='embed')(ids)
        pos = pos_offset + jnp.arange(ids.shape[-1])
        moe = dict(n_routed_experts=self.num_experts,
                   experts_held=tuple(self.experts_held),
                   num_experts_per_tok=self.num_experts_per_tok,
                   moe_intermediate_size=self.moe_intermediate_size,
                   n_shared_experts=0, scoring_func='softmax')
        for i in range(self.num_layers):
            kind = self.layer_types[i % len(self.layer_types)]
            if kind not in (SLIDING, FULL):
                raise ValueError(f'unknown layer type {kind!r}')
            full = kind == FULL
            attention = dict(num_heads=self.num_heads,
                             num_kv_heads=self.num_kv_heads,
                             head_dim=self.head_dim,
                             window=None if full else self.sliding_window,
                             rope_theta=self.rope_theta,
                             yarn=self.yarn if full else None)
            x = Block(attention, moe, dtype=self.dtype,
                      name=f'layer{i}')(x, pos)
        x = RMSNorm(dtype=self.dtype, name='norm')(x)
        return dense(self.vocab_size, self.dtype, 'head')(x)


#: Mellum2-12B-A2.5B's full layers' RoPE (``rope_parameters``).
MELLUM2_YARN = {'factor': 16.0, 'original_max_position_embeddings': 8192,
                'beta_fast': 32.0, 'beta_slow': 1.0,
                'attention_factor': 1.2772588722239782}


def get_model(vocab_size: int, size: str = 'tiny',
              **overrides) -> WindowMoeLM:
    """Named shapes. ``mellum2`` is Mellum2-12B-A2.5B as published (the
    class defaults and its YaRN: 28 layers and all 64 experts, far more
    than one 16 GB chip holds under K-FAC, so a cell overrides
    ``num_layers`` and ``experts_held``). ``tiny`` keeps every mechanism
    at test size: a window shorter than the sequences it is run on,
    4 q heads over 2 kv heads, YaRN on the full layer."""
    configs = {
        'tiny': dict(d_model=32, num_layers=4, num_heads=4,
                     num_kv_heads=2, head_dim=16, sliding_window=8,
                     rope_theta=1e4,
                     yarn={**MELLUM2_YARN,
                           'original_max_position_embeddings': 64},
                     num_experts=8, experts_held=(0, 4),
                     num_experts_per_tok=2, moe_intermediate_size=16),
        'mellum2': dict(yarn=MELLUM2_YARN),
    }
    if size not in configs:
        raise ValueError(f'unknown size {size!r}; have {sorted(configs)}')
    return WindowMoeLM(vocab_size=vocab_size, **{**configs[size], **overrides})
