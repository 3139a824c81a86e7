"""Decoder LM with latent attention and routed experts, K-FAC-visible.

The DeepSeek-V3 family's block (HF ``DeepseekV3*``; every projection
bias-free, every norm an RMSNorm computed in float32):

    x += MLA(RMSNorm(x));  x += FFN(RMSNorm(x))

- **MLA** (``q_lora_rank`` null): ``q_proj`` gives each head ``[q_nope |
  q_rope]``; ``kv_a_proj_with_mqa`` gives one latent ``c_kv`` and one
  ``k_rope`` that all heads share; ``kv_b_proj(RMSNorm(c_kv))`` gives
  each head ``[k_nope | v]``. RoPE turns adjacent pairs of ``q_rope``
  and ``k_rope`` (HF ``rope_interleave``; HF then moves the turned pairs
  to the half-split layout on q and k alike, which no score can see, so
  they stay in place here). Scores ``q.k / sqrt(nope + rope)``, causal
  softmax, ``P.v``, ``o_proj``. q/k heads are wider than v heads, which
  the fused attention kernel's gate does not take: the call goes the
  plain path of ``parallel.sequence.local_causal_attention`` and counts
  itself there.
- **FFN**: the first ``first_k_dense`` layers a dense SwiGLU,
  ``down(silu(gate(h)) * up(h))``; the others ``shared(h) + sum_k w_k
  expert_k(h)``: router logits in float32, ``s = sigmoid(logits)``, the
  ``num_experts_per_tok`` experts with the largest ``s +
  e_score_correction_bias`` (the bias enters the choice only and no
  gradient reaches it), ``w = s[chosen] / (sum s[chosen] + 1e-20) *
  routed_scaling_factor``; no token is dropped and there is no
  capacity. Every expert is a SwiGLU; the shared experts are one SwiGLU
  of their summed width. ``MoE`` also takes the Qwen-MoE router
  (``scoring_func='softmax'``: softmax over all experts, no bias) and
  no shared expert, as ``models/window_moe_lm.py`` runs it.
- token embedding with nothing added, a final RMSNorm, an untied head.

**Shares.** A deployment spreads a layer's experts and heads over
chips. ``experts_held`` (an index range of ``n_routed_experts``) and
``heads_held`` say what this program holds: the router still scores all
``n_routed_experts`` and normalises over the token's whole choice, and
the layer computes the part of the sum its own experts give; attention
computes its own heads' part of ``o_proj``'s sum. Summed over all
shares, with the replicated parts (router, shared experts, ``kv_a``,
norms) counted once, that is the uncut layer (``tests/``). Nothing here
stands in for the chips that are absent.

Every projection is an ``nn.Dense`` and every expert matrix a
``modules.experts.ExpertsDense``, so the K-FAC registry sees them all;
RMSNorm scales and the correction bias are left to the optimizer.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_kfac_pytorch_tpu.modules.experts import (
    ExpertsDense,
    combine_rows,
    dispatch_rows,
)
from distributed_kfac_pytorch_tpu.observability import tracing
from distributed_kfac_pytorch_tpu.parallel.sequence import (
    local_causal_attention,
)

INIT = nn.initializers.normal(0.02)


def dense(features: int, dtype, name: str, **kw) -> nn.Dense:
    """A bias-free projection, N(0, 0.02): every matrix of this decoder
    and of ``models/looped_lm.py``."""
    return nn.Dense(features, use_bias=False, dtype=dtype,
                    kernel_init=INIT, name=name, **kw)


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * scale`` in float32, cast to
    ``dtype`` (default: the input's)."""
    eps: float = 1e-6
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        scale = self.param('scale', nn.initializers.ones,
                           (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (y * scale).astype(self.dtype or x.dtype)


def rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotate adjacent pairs ``(x[2i], x[2i+1])`` of the last dim by
    ``pos * theta**(-2i/d)``, in float32. ``x``: (B, T, H, d)."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = pos.astype(jnp.float32)[:, None, None] * inv_freq  # (T,1,d/2)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


class MLA(nn.Module):
    """Multi-head latent attention over the ``heads_held`` heads."""
    heads_held: int
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    rope_theta: float = 1e6
    dtype: Any = None

    @nn.compact
    def __call__(self, x, pos):
        with jax.named_scope('kfac_model/mla'):
            return self._attend(x, pos)

    def _attend(self, x, pos):
        b, t, d_model = x.shape
        h, nope, rot = (self.heads_held, self.qk_nope_head_dim,
                        self.qk_rope_head_dim)
        q = dense(h * (nope + rot), self.dtype, 'q_proj')(x)
        q = q.reshape(b, t, h, nope + rot)
        kv_a = dense(self.kv_lora_rank + rot, self.dtype,
                     'kv_a_proj_with_mqa')(x)
        c_kv, k_rope = kv_a[..., :self.kv_lora_rank], kv_a[
            ..., self.kv_lora_rank:]
        kv = dense(h * (nope + self.v_head_dim), self.dtype, 'kv_b_proj')(
            RMSNorm(dtype=self.dtype, name='kv_a_layernorm')(c_kv))
        kv = kv.reshape(b, t, h, nope + self.v_head_dim)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        q_rope = rope(q[..., nope:], pos, self.rope_theta)
        k_rope = rope(k_rope[:, :, None, :], pos, self.rope_theta)
        q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (b, t, h, rot))], axis=-1)
        with jax.named_scope('kfac_model/attention'):
            o = local_causal_attention(q, k, v, causal=True)
        o = o.reshape(b, t, h * self.v_head_dim).astype(x.dtype)
        return dense(d_model, self.dtype, 'o_proj')(o)


class GatedMLP(nn.Module):
    """SwiGLU: ``down(silu(gate(h)) * up(h))``."""
    width: int
    dtype: Any = None

    @nn.compact
    def __call__(self, h):
        gate = dense(self.width, self.dtype, 'gate_proj')(h)
        up = dense(self.width, self.dtype, 'up_proj')(h)
        return dense(h.shape[-1], self.dtype, 'down_proj')(
            nn.silu(gate) * up)


class StackedExperts(nn.Module):
    """The held experts' SwiGLUs over their routed rows: three stacked
    matrices, ``(held, d, width)`` twice and ``(held, width, d)``."""
    num_experts: int
    width: int
    rows_per_token: int
    dtype: Any = None

    @nn.compact
    def __call__(self, x_sorted, group_sizes):
        def stack(features, name):
            return ExpertsDense(self.num_experts, features,
                                rows_per_token=self.rows_per_token,
                                dtype=self.dtype, kernel_init=INIT,
                                name=name)
        gate = stack(self.width, 'gate_proj')(x_sorted, group_sizes)
        up = stack(self.width, 'up_proj')(x_sorted, group_sizes)
        return stack(x_sorted.shape[-1], 'down_proj')(
            nn.silu(gate) * up, group_sizes)


def route(logits: jax.Array, bias: jax.Array | None, top_k: int,
          scaling: float, scoring: str = 'sigmoid'
          ) -> tuple[jax.Array, jax.Array]:
    """``(chosen experts, their weights)``, each ``(tokens, top_k)``:
    ``sigmoid`` scores with the bias in the choice only (DeepSeek-V3),
    or ``softmax`` scores over all experts and no bias (Qwen-MoE);
    weights normalised over the choice and scaled."""
    logits = logits.astype(jnp.float32)
    if scoring == 'softmax':
        scores = jax.nn.softmax(logits, axis=-1)
        _, chosen = jax.lax.top_k(scores, top_k)
    elif scoring == 'sigmoid':
        scores = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(bias),
                                  top_k)
    else:
        raise ValueError(f'unknown scoring {scoring!r}')
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return chosen, weights * scaling


class MoE(nn.Module):
    """``shared(h) + sum_k w_k expert_k(h)`` over the experts held.
    ``scoring_func`` as :func:`route` takes it; a ``sigmoid`` router
    holds the correction bias, a ``softmax`` one none. No shared MLP
    where ``n_shared_experts`` is 0."""
    n_routed_experts: int = 128
    experts_held: tuple[int, int] = (0, 128)
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 768
    n_shared_experts: int = 2
    routed_scaling_factor: float = 1.0
    scoring_func: str = 'sigmoid'
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        with jax.named_scope('kfac_model/moe'):
            return self._mix(x)

    def _mix(self, x):
        lo, hi = self.experts_held
        held, k = hi - lo, self.num_experts_per_tok
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(f'{self.experts_held=} is no range of '
                             f'{self.n_routed_experts} experts')
        tracing.gauge('kfac/moe/experts_held', held)
        tracing.gauge('kfac/moe/experts_total', self.n_routed_experts)
        h = x.reshape(-1, x.shape[-1])
        n, d = h.shape
        logits = dense(self.n_routed_experts, jnp.float32, 'router',
                       precision=jax.lax.Precision.HIGHEST)(
            h.astype(jnp.float32))
        bias = None
        if self.scoring_func == 'sigmoid':
            bias = self.param('e_score_correction_bias',
                              nn.initializers.zeros,
                              (self.n_routed_experts,), jnp.float32)
        chosen, weights = route(logits, bias, k,
                                self.routed_scaling_factor,
                                self.scoring_func)

        # Dispatch: the (token, choice) pairs sorted by the held expert
        # they go to, pairs for experts held elsewhere last. The buffer
        # has a row for every pair (static, and no routing overflows
        # it); group_sizes says how many rows are this share's, and
        # only those are moved and multiplied.
        local = chosen.reshape(-1) - lo
        local = jnp.where((local >= 0) & (local < held), local, held)
        order = jnp.argsort(local, stable=True)
        group_sizes = jnp.sum(
            local[:, None] == jnp.arange(held)[None, :], axis=0,
            dtype=jnp.int32)
        rows_here, token_of_row = jnp.sum(group_sizes), order // k
        y = StackedExperts(held, self.moe_intermediate_size, k,
                           dtype=self.dtype, name='experts')(
            dispatch_rows(h, token_of_row, rows_here), group_sizes)
        routed = combine_rows(y, weights.reshape(-1)[order], token_of_row,
                              rows_here, n).astype(x.dtype)
        if self.n_shared_experts:
            routed = GatedMLP(
                self.n_shared_experts * self.moe_intermediate_size,
                self.dtype, name='shared_experts')(h) + routed
        return routed.reshape(x.shape)


class Block(nn.Module):
    dense_ffn: bool
    heads_held: int
    intermediate_size: int
    moe: dict
    mla: dict
    dtype: Any = None

    @nn.compact
    def __call__(self, x, pos):
        norm = lambda name: RMSNorm(dtype=self.dtype, name=name)  # noqa: E731
        x = x + MLA(self.heads_held, dtype=self.dtype, name='self_attn',
                    **self.mla)(norm('input_layernorm')(x), pos)
        h = norm('post_attention_layernorm')(x)
        if self.dense_ffn:
            return x + GatedMLP(self.intermediate_size, self.dtype,
                                name='mlp')(h)
        return x + MoE(dtype=self.dtype, name='mlp', **self.moe)(h)


class MlaMoeLM(nn.Module):
    """Embedding -> blocks -> RMSNorm -> untied head. ``train`` is
    accepted for the training entry points and changes nothing (there is
    no dropout); ``pos_offset`` shifts the RoPE positions."""
    vocab_size: int
    d_model: int = 2048
    num_layers: int = 5
    first_k_dense: int = 1
    heads_held: int = 4
    intermediate_size: int = 6144
    n_routed_experts: int = 128
    experts_held: tuple[int, int] = (0, 8)
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 768
    n_shared_experts: int = 2
    routed_scaling_factor: float = 2.448
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    rope_theta: float = 1e6
    dtype: Any = None

    @nn.compact
    def __call__(self, ids, *, train: bool = True, pos_offset=0):
        del train
        x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                     embedding_init=INIT, name='embed')(ids)
        pos = pos_offset + jnp.arange(ids.shape[-1])
        moe = dict(n_routed_experts=self.n_routed_experts,
                   experts_held=tuple(self.experts_held),
                   num_experts_per_tok=self.num_experts_per_tok,
                   moe_intermediate_size=self.moe_intermediate_size,
                   n_shared_experts=self.n_shared_experts,
                   routed_scaling_factor=self.routed_scaling_factor)
        mla = dict(qk_nope_head_dim=self.qk_nope_head_dim,
                   qk_rope_head_dim=self.qk_rope_head_dim,
                   v_head_dim=self.v_head_dim,
                   kv_lora_rank=self.kv_lora_rank,
                   rope_theta=self.rope_theta)
        for i in range(self.num_layers):
            x = Block(i < self.first_k_dense, self.heads_held,
                      self.intermediate_size, moe, mla, dtype=self.dtype,
                      name=f'layer{i}')(x, pos)
        x = RMSNorm(dtype=self.dtype, name='norm')(x)
        return dense(self.vocab_size, self.dtype, 'head')(x)


def get_model(vocab_size: int, size: str = 'tiny',
              **overrides) -> MlaMoeLM:
    """Named shapes. ``kanana2`` is kanana-2-30b-a3b's published widths
    (the class defaults) at one chip's share: 8 of 128 experts, 4 of 32
    heads, 1 + 4 layers. ``tiny`` keeps every mechanism at test size."""
    configs = {
        'tiny': dict(d_model=32, num_layers=2, first_k_dense=1,
                     heads_held=2, intermediate_size=48,
                     n_routed_experts=8, experts_held=(0, 4),
                     num_experts_per_tok=2, moe_intermediate_size=16,
                     n_shared_experts=2, qk_nope_head_dim=8,
                     qk_rope_head_dim=4, v_head_dim=8, kv_lora_rank=12),
        'kanana2': {},
    }
    if size not in configs:
        raise ValueError(f'unknown size {size!r}; have {sorted(configs)}')
    return MlaMoeLM(vocab_size=vocab_size, **{**configs[size], **overrides})
