"""Looped decoder LM: one stack of layers run several times a forward.

The Ouro family's model (ByteDance, ``model_type`` ``ouro``; "Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741): a
Llama-shaped stack whose ``num_layers`` blocks are applied
``total_ut_steps`` times with the SAME weights, an exit after every
pass, and a learned gate that spreads the loss over the exits.

    x = E[ids]
    for pass t = 1..T:
        for layer l = 1..L:                      # same weights each pass
            x += RMSNorm_l2(Attn_l(RMSNorm_l1(x)))
            x += RMSNorm_l4(SwiGLU_l(RMSNorm_l3(x)))
        x = RMSNorm_f(x);  h_t = x               # the next pass starts here
        z_t = W_head h_t;  lambda_t = sigmoid(w_g . h_t + b_g)

- **Attention**: full multi-head (``num_kv_heads == num_heads``),
  bias-free q/k/v/o, RoPE in the half-split (``rotate_half``) layout,
  causal softmax at ``1 / sqrt(head_dim)``, through
  ``parallel.sequence.local_causal_attention`` (the fused kernel's gate
  opens at head dim 64/128 and T a multiple of 256).
- **Four RMSNorms a layer** (the sandwich: each sublayer's output is
  normed before it joins the stream) and one final norm applied after
  every pass; all in float32.
- **Exits**: ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` for ``t < T``
  and ``p_T = prod_{j<T} (1 - lambda_j)``. With ``targets`` the model
  returns the training objective a token,
  ``sum_t p_t CE(z_t, y) - beta H(p)``, in float32; without, the last
  pass's logits (``early_exit_threshold`` 1: inference never leaves
  before pass T). The gate runs every pass; ``lambda_T`` enters nothing.

Memory is what makes a looped model different: it keeps T x the
activations of a plain decoder a token. Every block call is
rematerialised (``nn.remat``), so that only its residual input and the
inputs K-FAC captures outlive the forward, and so is every exit, so that
one pass's logits exist at a time, forward and backward.

Every projection is an ``nn.Dense`` applied once a pass: the K-FAC
registry sees each of them ONCE with ``num_calls == T`` (its multi-call
path), q/k/v and gate/up read one traced value in every pass and share
an A, the gate is an ordinary ``Dense(1)`` with bias; RMSNorm scales are
left to the optimizer, and so is the head wherever ``skip_layers`` names
it (a vocab x vocab G does not fit). RMSNorm, the SwiGLU block and the
bias-free projection are ``models/mla_moe_lm.py``'s.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from distributed_kfac_pytorch_tpu.models.mla_moe_lm import (
    INIT,
    GatedMLP,
    RMSNorm,
    dense,
)
from distributed_kfac_pytorch_tpu.parallel.sequence import (
    local_causal_attention,
)


def rope_half(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """RoPE in the half-split layout: with ``x = [x1 | x2]`` the halves
    of the last dim, ``[x1 cos - x2 sin | x2 cos + x1 sin]`` at angle
    ``pos * theta**(-2i/d)`` for entry ``i`` of either half, in float32.
    ``x``: (B, T, H, d)."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = pos.astype(jnp.float32)[:, None, None] * inv_freq  # (T,1,d/2)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def exit_distribution(gate_logits: jax.Array) -> tuple[jax.Array,
                                                       jax.Array]:
    """``(p, H(p))`` of the exits from the gate's logits, ``(T, ...)``
    in float32: ``p_t = lambda_t prod_{j<t} (1 - lambda_j)``, the last
    pass taking what is left (its own logit enters nothing). Worked in
    logs, so a saturated gate gives no NaN."""
    stay = jax.nn.log_sigmoid(-gate_logits[:-1])       # log(1 - lambda_j)
    stayed = jnp.concatenate(
        [jnp.zeros_like(gate_logits[:1]), jnp.cumsum(stay, axis=0)])
    leave = jnp.concatenate(
        [jax.nn.log_sigmoid(gate_logits[:-1]),
         jnp.zeros_like(gate_logits[:1])])
    log_p = stayed + leave
    p = jnp.exp(log_p)
    return p, -jnp.sum(p * log_p, axis=0)


class Attention(nn.Module):
    """Full multi-head causal attention, half-split RoPE on q and k."""
    num_heads: int
    head_dim: int = 128
    rope_theta: float = 1e6
    dtype: Any = None

    @nn.compact
    def __call__(self, x, pos):
        b, t, d_model = x.shape
        shape = (b, t, self.num_heads, self.head_dim)
        width = self.num_heads * self.head_dim
        q = dense(width, self.dtype, 'q_proj')(x).reshape(shape)
        k = dense(width, self.dtype, 'k_proj')(x).reshape(shape)
        v = dense(width, self.dtype, 'v_proj')(x).reshape(shape)
        q = rope_half(q, pos, self.rope_theta)
        k = rope_half(k, pos, self.rope_theta)
        with jax.named_scope('kfac_model/attention'):
            o = local_causal_attention(q, k, v, causal=True)
        return dense(d_model, self.dtype, 'o_proj')(
            o.reshape(b, t, width).astype(x.dtype))


class Block(nn.Module):
    """One layer of the stack, its four norms round its two sublayers."""
    num_heads: int
    head_dim: int
    intermediate_size: int
    rope_theta: float
    dtype: Any = None

    @nn.compact
    def __call__(self, x, pos):
        norm = lambda name: RMSNorm(dtype=self.dtype, name=name)  # noqa: E731
        attn = Attention(self.num_heads, self.head_dim, self.rope_theta,
                         self.dtype, name='self_attn')
        x = x + norm('input_layernorm_2')(
            attn(norm('input_layernorm')(x), pos))
        mlp = GatedMLP(self.intermediate_size, self.dtype, name='mlp')
        return x + norm('post_attention_layernorm_2')(
            mlp(norm('post_attention_layernorm')(x)))


class Exit(nn.Module):
    """What follows every pass: the final norm (its output is the
    stream the next pass starts from), the gate's logit in float32, and
    the untied head. Returns ``(h, gate logit, nll)`` with ``nll`` the
    cross entropy a token against ``targets`` in float32, or the logits
    themselves where there are no targets and ``logits`` is asked for
    (else None)."""
    vocab_size: int
    dtype: Any = None

    @nn.compact
    def __call__(self, x, targets, logits: bool):
        h = RMSNorm(dtype=self.dtype, name='norm')(x)
        gate = nn.Dense(1, use_bias=True, dtype=jnp.float32,
                        kernel_init=INIT, name='early_exit_gate',
                        precision=jax.lax.Precision.HIGHEST)(
            h.astype(jnp.float32))[..., 0]
        head = dense(self.vocab_size, self.dtype, 'head')
        if targets is not None:
            return h, gate, optax.softmax_cross_entropy_with_integer_labels(
                head(h).astype(jnp.float32), targets)
        return h, gate, head(h) if logits else None


class LoopedLM(nn.Module):
    """Embedding -> ``total_ut_steps`` x (blocks -> exit). ``train`` is
    accepted for the training entry points and changes nothing (there is
    no dropout); ``pos_offset`` shifts the RoPE positions. ``targets``:
    see the module docstring."""
    vocab_size: int
    d_model: int = 2048
    num_layers: int = 48
    num_heads: int = 16
    head_dim: int = 128
    intermediate_size: int = 5632
    total_ut_steps: int = 4
    rope_theta: float = 1e6
    exit_entropy_beta: float = 0.1
    dtype: Any = None

    @nn.compact
    def __call__(self, ids, *, train: bool = True, targets=None,
                 pos_offset=0):
        del train
        x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                     embedding_init=INIT, name='embed')(ids)
        pos = pos_offset + jnp.arange(ids.shape[-1])
        # One instance a layer, applied in every pass: flax shares the
        # weights and the K-FAC capture sees one module called T times.
        blocks = [nn.remat(Block)(self.num_heads, self.head_dim,
                                  self.intermediate_size, self.rope_theta,
                                  self.dtype, name=f'layer{i}')
                  for i in range(self.num_layers)]
        leave = nn.remat(Exit, static_argnums=(3,))(
            self.vocab_size, self.dtype, name='exit')
        gates, terms, out = [], [], None
        for step in range(self.total_ut_steps):
            with jax.named_scope('kfac_model/ut_blocks'):
                for block in blocks:
                    x = block(x, pos)
            with jax.named_scope('kfac_model/ut_exits'):
                x, gate, out = leave(
                    x, targets, step == self.total_ut_steps - 1)
            gates.append(gate)
            terms.append(out)
        if targets is None:
            return out
        with jax.named_scope('kfac_model/ut_exits'):
            p, entropy = exit_distribution(jnp.stack(gates))
            return (jnp.sum(p * jnp.stack(terms), axis=0)
                    - self.exit_entropy_beta * entropy)


def get_model(vocab_size: int, size: str = 'tiny',
              **overrides) -> LoopedLM:
    """Named shapes. ``ouro_2p6b`` is Ouro-2.6B as published (the class
    defaults: 48 layers run 4 times; far more than one 16 GB chip holds
    under K-FAC, so a cell overrides ``num_layers``). ``tiny`` keeps
    every mechanism at test size."""
    configs = {
        'tiny': dict(d_model=32, num_layers=2, num_heads=2, head_dim=16,
                     intermediate_size=48, total_ut_steps=3,
                     rope_theta=1e4),
        'ouro_2p6b': {},
    }
    if size not in configs:
        raise ValueError(f'unknown size {size!r}; have {sorted(configs)}')
    return LoopedLM(vocab_size=vocab_size, **{**configs[size], **overrides})
