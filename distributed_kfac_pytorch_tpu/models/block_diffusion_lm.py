"""Decoder LM with softmax-routed experts trained by diffusion over
blocks, K-FAC-visible.

SDAR's block-diffusion decoder (JetLM, ``model_type`` ``sdar_moe``;
HF ``config.json`` of SDAR-30B-A3B-Chat) on Qwen3-MoE's block, trained
by BD3-LM's objective (arXiv:2503.09573) in its vectorised form. Every
projection bias-free, every norm an RMSNorm computed in float32 with
eps 1e-6:

    x += Attn(RMSNorm(x));  x += MoE(RMSNorm(x))

- **Two streams.** A training sequence ``x_0`` of ``L`` tokens in
  blocks of ``B`` and its noised copy ``x_t`` (some positions replaced
  by the mask token) are laid side by side, noised first: ``2L``
  positions, both streams at RoPE positions ``0 .. L-1``.
- **Attention** (``window_moe_lm.Attention``): ``num_heads`` q heads
  over ``num_kv_heads`` k/v heads of ``head_dim``, a per-head RMSNorm
  on q and on k before half-split RoPE at ``theta``, under the block-
  diffusion mask with ``b = i // B`` within a stream: a noised query
  reads its own noised block both ways and every clean block strictly
  before its own; a clean query reads the clean blocks up to and
  including its own, and nothing noised
  (``ops.pallas_kernels.block_diffusion_keep``; the splash kernels on a
  TPU).
- **MoE** (``mla_moe_lm.MoE``): router logits in float32, softmax over
  all ``num_experts``, the ``num_experts_per_tok`` largest, weights
  renormalised over the choice; no shared expert. Every expert a
  SwiGLU of ``moe_intermediate_size``. Tokens of both streams route.
- token embedding with nothing added; a final RMSNorm and an untied
  head over the noised stream only (the clean stream's last outputs
  feed nothing).
- **Objective**, when the step hands it ``weights`` (``(batch, L)``:
  ``1 / t`` at the masked positions of a block noised at level ``t``,
  0 elsewhere): ``weights * CE(logits, x_0)`` a position, whose mean
  over ``batch x L`` is the step's loss. Without ``weights`` the model
  returns the noised stream's logits.

**Shares.** ``experts_held`` says which of the experts this program
holds (the router scores all of them; see ``mla_moe_lm``); attention
holds every head.

Every projection is an ``nn.Dense`` and every expert matrix an
``ExpertsDense``, so the K-FAC registry sees them all, over both
streams' ``2L`` rows: q/k/v read one traced value and share q's A, up
shares gate's; RMSNorm scales are left to the optimizer, and so is the
head wherever ``skip_layers`` names it.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from distributed_kfac_pytorch_tpu.models.mla_moe_lm import (
    INIT,
    RMSNorm,
    dense,
)
from distributed_kfac_pytorch_tpu.models.window_moe_lm import Block

#: The noise levels a (row, block) is drawn from: BD3-LM's clipped
#: schedule, which keeps the ``1 / t`` weights at most 3.3.
NOISE_LEVELS = (0.3, 0.8)


def noise(rng, x_0, block: int, mask_id: int, levels=NOISE_LEVELS):
    """``(x_t, weights)`` for the numpy ids ``x_0`` (``(rows, L)``): a
    level ``t ~ U[levels]`` a (row, block of ``block``), each position of
    the block masked with probability ``t`` (its id then ``mask_id``, its
    weight ``1 / t``; 0 elsewhere). ``rng``: a numpy ``Generator``."""
    rows, length = x_0.shape
    t = np.repeat(rng.uniform(*levels, (rows, length // block)), block,
                  axis=1)
    masked = rng.random((rows, length)) < t
    return (np.where(masked, np.asarray(mask_id, x_0.dtype), x_0),
            np.where(masked, 1.0 / t, 0.0).astype(np.float32))


class BlockDiffusionMoeLM(nn.Module):
    """Embedding of both streams -> blocks -> RMSNorm -> untied head
    over the noised stream, and the objective where ``weights`` are
    given. ``x_0`` defaults to ``x_t`` (a pass with nothing masked:
    registration and evaluation). ``train`` is accepted for the
    training entry points and changes nothing (there is no dropout)."""
    vocab_size: int
    d_model: int = 2048
    num_layers: int = 48
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e6
    num_experts: int = 128
    experts_held: tuple[int, int] = (0, 128)
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    block_size: int = 4
    dtype: Any = None

    @nn.compact
    def __call__(self, x_t, x_0=None, weights=None, *, train: bool = True):
        del train
        if x_0 is None:
            x_0 = x_t
        length = x_t.shape[-1]
        x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                     embedding_init=INIT, name='embed')(
            jnp.concatenate([x_t, x_0], axis=-1))
        pos = jnp.tile(jnp.arange(length), 2)
        attention = dict(num_heads=self.num_heads,
                         num_kv_heads=self.num_kv_heads,
                         head_dim=self.head_dim, window=None,
                         rope_theta=self.rope_theta, qk_norm=True,
                         diffusion_block=self.block_size)
        moe = dict(n_routed_experts=self.num_experts,
                   experts_held=tuple(self.experts_held),
                   num_experts_per_tok=self.num_experts_per_tok,
                   moe_intermediate_size=self.moe_intermediate_size,
                   n_shared_experts=0, scoring_func='softmax')
        for i in range(self.num_layers):
            x = Block(attention, moe, dtype=self.dtype,
                      name=f'layer{i}')(x, pos)
        x = RMSNorm(dtype=self.dtype, name='norm')(x[:, :length])
        logits = dense(self.vocab_size, self.dtype, 'head')(x)
        if weights is None:
            return logits
        with jax.named_scope('kfac_model/diffusion/objective'):
            return weights * optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), x_0)


def get_model(vocab_size: int, size: str = 'tiny',
              **overrides) -> BlockDiffusionMoeLM:
    """Named shapes. ``sdar30`` is SDAR-30B-A3B-Chat as published (the
    class defaults: 48 layers and all 128 experts, far more than one
    16 GB chip holds under K-FAC, so a cell overrides ``num_layers`` and
    ``experts_held``). ``tiny`` keeps every mechanism at test size:
    4 q heads over 2 kv heads, blocks of 4, 4 of 8 experts held."""
    configs = {
        'tiny': dict(d_model=32, num_layers=2, num_heads=4,
                     num_kv_heads=2, head_dim=16, rope_theta=1e4,
                     num_experts=8, experts_held=(0, 4),
                     num_experts_per_tok=2, moe_intermediate_size=16),
        'sdar30': {},
    }
    if size not in configs:
        raise ValueError(f'unknown size {size!r}; have {sorted(configs)}')
    return BlockDiffusionMoeLM(vocab_size=vocab_size,
                               **{**configs[size], **overrides})
