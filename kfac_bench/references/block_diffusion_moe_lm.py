"""Plain reference of the softmax-routed expert decoder LM trained by
diffusion over blocks, under K-FAC: forward, objective, gradients,
every preconditioned layer's Kronecker statistics, and the K-FAC step
that follows.

Written from the equations (SDAR-30B-A3B-Chat's ``config.json``, the
Qwen3-MoE block SDAR converts, and BD3-LM's vectorised training,
arXiv:2503.09573; every projection bias-free), not from the program.
``d`` = ``head_dim``, ``H`` q heads over ``G`` kv heads, ``L`` training
tokens in blocks of ``B``:

- two streams: the noised sequence ``x_t`` (positions ``0..L-1`` of the
  ``2L``) and the clean ``x_0`` (``L..2L-1``); both at RoPE positions
  ``0..L-1``. Position ``i`` is clean where ``i >= L``; its block is
  ``(i - L [if clean]) // B``.
- block: ``x += Attn(RMSNorm(x))``, ``x += MoE(RMSNorm(x))``; RMSNorm
  ``x / sqrt(mean(x^2) + 1e-6) * scale``; token embedding with nothing
  added; a final RMSNorm and an untied head over the noised stream.
- Attn: ``q = h W_q`` (H heads), ``k = h W_k``, ``v = h W_v`` (G heads);
  q head ``j`` reads kv head ``j // (H / G)``; an RMSNorm over ``d`` of
  each q head and each k head; RoPE in the half-split layout
  (``[x1 cos - x2 sin | x2 cos + x1 sin]``, ``theta**(-2i/d)``); scores
  ``q.k / sqrt(d)``. Query ``i`` reads key ``j`` where: both noised and
  of one block; ``i`` noised, ``j`` clean, ``j``'s block before ``i``'s;
  both clean, ``j``'s block not after ``i``'s. Softmax, ``P.v``,
  ``W_o``.
- MoE: ``references/window_moe_lm.py``'s (softmax over all experts, the
  ``top_k`` largest renormalised over the choice; no shared expert; no
  token dropped). Tokens of both streams route.
- objective: ``sum over noised positions of w * CE(logits, x_0)`` over
  ``rows x L``, with ``w`` the step's weights (``1 / t`` at a masked
  position of a block noised at level ``t``, 0 elsewhere).

float32, every contraction at ``Precision.HIGHEST``, no kernels.
Attention is computed in blocks of at most
``window_moe_lm.QUERY_BLOCK`` queries over all ``2L`` keys under the
rule, each rematerialised in the backward. The experts are computed
densely, each held expert over every token of both streams.

**The share.** ``experts_held`` of the ``num_experts`` (the router
scores all of them), ``vocab_size`` rows of the vocabulary (the mask
token is its last row), every attention head.

**Departures from the published model**, each on purpose: (1) float32
throughout, where it is released in bfloat16. (2) The configuration's
``assumed`` entries: block length, noise schedule, q/k norm, mask row.
(3) The shares above.

**K-FAC conventions**: ``references/mla_moe_lm.py``'s, over the ``N =
rows x 2L`` positions both streams give a layer (the embedding's
diagonal A counts the ids of both, the mask token among them), with
``g`` the gradient of the objective; the state and its step are
``references/window_moe_lm.py``'s ``follow`` as it is.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from kfac_bench.reference import Rounding
from kfac_bench.references.window_moe_lm import (
    EPS,
    MLP,
    QUERY_BLOCK,
    _moe,
    _rms_norm,
    layers,
)


def sizes_of(config: dict) -> dict:
    """The model's sizes under the published keys, with the share."""
    keys = ('hidden_size', 'moe_intermediate_size', 'num_hidden_layers',
            'num_attention_heads', 'num_key_value_heads', 'head_dim',
            'num_experts', 'num_experts_per_tok', 'vocab_size',
            'rope_theta', 'rms_norm_eps')
    sizes = {k: config[k] for k in keys}
    if sizes.pop('rms_norm_eps') != EPS:
        raise ValueError(f'the reference norms with eps {EPS}; the '
                         f"configuration states {config['rms_norm_eps']}")
    if (not config['norm_topk_prob'] or config['attention_bias']
            or config['tie_word_embeddings']
            or config['mlp_only_layers'] or config['decoder_sparse_step'] != 1
            or config['use_sliding_window'] or config['rope_scaling']):
        raise ValueError('the reference renormalises over the choice, has '
                         'no projection bias, an untied head, experts in '
                         'every layer, full attention and plain RoPE')
    sizes['experts_held'] = tuple(config['share']['experts_held'])
    return sizes


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------

def keep(i, j, block: int, length: int):
    """Whether position ``i`` reads position ``j`` of the two streams."""
    clean_i, clean_j = i >= length, j >= length
    block_i = (i - jnp.where(clean_i, length, 0)) // block
    block_j = (j - jnp.where(clean_j, length, 0)) // block
    noised_noised = ~clean_i & ~clean_j & (block_i == block_j)
    noised_clean = ~clean_i & clean_j & (block_j < block_i)
    clean_clean = clean_i & clean_j & (block_j <= block_i)
    return noised_noised | noised_clean | clean_clean


def _rope(x, pos, theta):
    """Half-split RoPE at positions ``pos``; ``x``: (rows, T, ..., d)."""
    d = x.shape[-1]
    inv_freq = theta ** (-2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)
    angle = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (-1,)
    cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, *, first_query, block, length, rounding):
    """One block of queries over every key; ``q``: (b, tq, G, H/G, d),
    ``k``/``v``: (b, 2L, G, d)."""
    scores = rounding.einsum('bqgrd,bkgd->bgrqk', q, k) / math.sqrt(
        q.shape[-1])
    i = first_query + jnp.arange(q.shape[1])[:, None]
    j = jnp.arange(k.shape[1])[None, :]
    scores = jnp.where(keep(i, j, block, length), scores, -jnp.inf)
    return rounding.einsum('bgrqk,bkgd->bqgrd', jax.nn.softmax(scores, -1),
                           v)


def _attention(h, p, probe, sizes, block, rounding, acts):
    b, t, _ = h.shape
    heads, groups, d = (sizes['num_attention_heads'],
                        sizes['num_key_value_heads'], sizes['head_dim'])
    length = t // 2
    pos = jnp.concatenate([jnp.arange(length), jnp.arange(length)])

    def linear(x, name):
        acts[name] = x
        return rounding.einsum('btd,de->bte', x,
                               p[name]['kernel']) + probe[name]

    q = _rms_norm(linear(h, 'q_proj').reshape(
        b, t, groups, heads // groups, d), p['q_norm']['scale'])
    k = _rms_norm(linear(h, 'k_proj').reshape(b, t, groups, d),
                  p['k_norm']['scale'])
    v = linear(h, 'v_proj').reshape(b, t, groups, d)
    q, k = (_rope(x, pos, sizes['rope_theta']) for x in (q, k))
    blocks = []
    for lo in range(0, t, QUERY_BLOCK):
        attend = jax.checkpoint(functools.partial(
            _attend, first_query=lo, block=block, length=length,
            rounding=rounding))
        blocks.append(attend(q[:, lo:lo + QUERY_BLOCK], k, v))
    o = jnp.concatenate(blocks, axis=1).reshape(b, t, heads * d)
    return linear(o, 'o_proj')


def forward(params, probes, x_t, x_0, *, sizes, block, rounding):
    """The noised stream's logits, and every preconditioned layer's
    input."""
    length = x_t.shape[1]
    ids = jnp.concatenate([x_t, x_0], axis=1)
    x = params['embed']['embedding'][ids] + probes['embed']
    acts = {}
    for i in range(sizes['num_hidden_layers']):
        def block_fn(x, p, probe):
            a = {}
            h = _rms_norm(x, p['input_layernorm']['scale'])
            x = x + _attention(h, p['self_attn'], probe, sizes, block,
                               rounding, a)
            h = _rms_norm(x, p['post_attention_layernorm']['scale'])
            return x + _moe(h, p['mlp'], probe, sizes, rounding, a), a

        x, acts[f'layer{i}'] = jax.checkpoint(block_fn)(
            x, params[f'layer{i}'], probes[f'layer{i}'])
    x = _rms_norm(x[:, :length], params['norm']['scale'])
    return rounding.einsum('btd,dv->btv', x, params['head']['kernel']), acts


def _loss_sum(params, probes, x_t, x_0, weights, *, sizes, block,
              rounding, denom):
    """The rows' weighted losses over ``denom`` (the whole batch's
    noised positions), and every preconditioned layer's input."""
    logits, acts = forward(params, probes, x_t, x_0, sizes=sizes,
                           block=block, rounding=rounding)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                               x_0[..., None], axis=-1)[..., 0]
    return jnp.sum(weights * nll) / denom, acts


def probe_shapes(sizes: dict, b: int, t: int) -> dict:
    d, width = sizes['hidden_size'], sizes['head_dim']
    lo, hi = sizes['experts_held']
    layer = {'q_proj': (b, t, sizes['num_attention_heads'] * width),
             'k_proj': (b, t, sizes['num_key_value_heads'] * width),
             'v_proj': (b, t, sizes['num_key_value_heads'] * width),
             'o_proj': (b, t, d), 'router': (b, t, sizes['num_experts'])}
    for name in MLP:
        layer['experts/' + name] = (hi - lo, b, t, d if name == 'down_proj'
                                    else sizes['moe_intermediate_size'])
    out = {'embed': (b, t, d)}
    for i in range(sizes['num_hidden_layers']):
        out[f'layer{i}'] = dict(layer)
    return out


def _rows(params, x_t, x_0, weights, *, sizes_key, block, rounding,
          denom):
    """One block of rows: its share of the objective and of the
    gradients, and its un-normalised sums for every statistic over both
    streams' positions."""
    sizes = dict(sizes_key)
    b, length = x_t.shape
    probes = jax.tree.map(lambda s: jnp.zeros(s, jnp.float32),
                          probe_shapes(sizes, b, 2 * length),
                          is_leaf=lambda s: isinstance(s, tuple))
    (loss, acts), (grads, pgrads) = jax.value_and_grad(
        functools.partial(_loss_sum, sizes=sizes, block=block,
                          rounding=rounding, denom=denom),
        argnums=(0, 1), has_aux=True)(params, probes, x_t, x_0, weights)

    def cov(x):
        x = x.reshape(-1, x.shape[-1])
        return rounding.einsum('nd,ne->de', x, x)

    ids = jnp.concatenate([x_t, x_0], axis=1)
    sums = {'embed': {
        'count': jnp.zeros((sizes['vocab_size'],), jnp.float32)
        .at[ids.reshape(-1)].add(1.0),
        'gg': cov(pgrads['embed'])}}
    for name, kind, path in layers(sizes)[1:]:
        layer, key = path[0], '/'.join(path[2:])
        a, g = acts[layer][key], pgrads[layer][key]
        if kind == 'linear':
            sums[name] = {'aa': cov(a), 'gg': cov(g)}
            continue
        member = acts[layer]['member'].astype(jnp.float32)  # (held, b, t)
        masked = member[..., None] * (a if a.ndim == 4 else a[None])
        sums[name] = {'aa': rounding.einsum('hbtd,hbte->hde', masked,
                                            masked),
                      'gg': rounding.einsum('hbtd,hbte->hde', g, g),
                      'n': jnp.sum(member, axis=(1, 2))}
    return loss, grads, sums


_first_rows = jax.jit(_rows, static_argnames=('sizes_key', 'block',
                                              'rounding', 'denom'))


@functools.partial(jax.jit, donate_argnums=1,
                   static_argnames=('sizes_key', 'block', 'rounding',
                                    'denom'))
def _add_rows(params, carry, x_t, x_0, weights, **static):
    """``carry`` plus one more block of rows, in ``carry``'s memory."""
    return jax.tree.map(jnp.add, carry, _rows(params, x_t, x_0, weights,
                                              **static))


def model_step(sizes: dict, block: int, rows_per_block: int, *,
               half_batch=False):
    """``step(params, batch, rounding) -> (loss, grads, sums, n)`` for
    a batch ``(x_t, x_0, weights)``: the objective and its gradients,
    the un-normalised statistic sums of ``update_factors``, and the
    positions both streams give (``n``). ``half_batch`` plants the fault
    of a step that leaves the second half of its rows out and averages
    over the rest (a one-row batch keeps the first half of each
    stream)."""
    sizes_key = tuple(sorted(sizes.items()))

    def step(params, batch, rounding=Rounding()):
        x_t, x_0, weights = batch
        if half_batch:
            if x_t.shape[0] > 1:
                keep_rows = x_t.shape[0] // 2
                x_t, x_0, weights = (a[:keep_rows]
                                     for a in (x_t, x_0, weights))
            else:
                keep_cols = x_t.shape[1] // 2
                x_t, x_0, weights = (a[:, :keep_cols]
                                     for a in (x_t, x_0, weights))
        rows, length = x_t.shape
        per = math.gcd(rows, rows_per_block)
        static = dict(sizes_key=sizes_key, block=block, rounding=rounding,
                      denom=float(rows * length))
        part = lambda lo: tuple(jnp.asarray(a[lo:lo + per])  # noqa: E731
                                for a in (x_t, x_0, weights))
        total = _first_rows(params, *part(0), **static)
        for lo in range(per, rows, per):
            total = _add_rows(params, total, *part(lo), **static)
        loss, grads, sums = total
        return loss, grads, sums, rows * 2 * length

    return step
