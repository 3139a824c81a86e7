"""Plain reference of the windowed/full-attention, grouped-head,
softmax-routed expert decoder LM under K-FAC: forward, loss, gradients,
every preconditioned layer's Kronecker statistics, and the K-FAC step
that follows.

Written from the model's equations (Mellum2-12B-A2.5B's ``config.json``
under HF's ``rope_type`` default and yarn; every projection bias-free),
not from the program. ``d`` = ``head_dim``, ``H`` q heads over ``G``
kv heads:

- block: ``x += Attn(RMSNorm(x))``, ``x += MoE(RMSNorm(x))``; RMSNorm
  ``x / sqrt(mean(x^2) + 1e-6) * scale``; token embedding with nothing
  added; a final RMSNorm; an untied head. The loss is the mean cross
  entropy over every token, the logits over the held vocabulary rows.
- Attn: ``q = h W_q`` (H heads), ``k = h W_k``, ``v = h W_v`` (G heads);
  q head ``j`` reads kv head ``j // (H / G)``; RoPE in the half-split
  layout (``[x1 cos - x2 sin | x2 cos + x1 sin]``) on q and k at
  positions 0..T-1; scores ``q.k / sqrt(d)``; a ``sliding_attention``
  layer masks all but keys ``i - window < j <= i``, a ``full_attention``
  one all but ``j <= i``; softmax, ``P.v``, ``W_o``.
- RoPE frequencies: sliding layers ``theta**(-2i/d)``; full layers
  YaRN's: ``r(n) = d ln(L / (2 pi n)) / (2 ln theta)``, ``lo =
  floor(r(beta_fast))``, ``hi = ceil(r(beta_slow))``, ``ramp_i =
  clip((i - lo) / (hi - lo), 0, 1)``, ``inv_freq_i = theta**(-2i/d) /
  factor * ramp_i + theta**(-2i/d) * (1 - ramp_i)``, and cos and sin
  both times ``attention_factor``.
- MoE: router logits ``h W_r``, ``s = softmax`` over all experts, the
  ``top_k`` largest, ``w = s[chosen] / sum s[chosen]``, output ``sum_k
  w_k expert_k(h)`` with every expert a SwiGLU ``down(silu(gate(h)) *
  up(h))``. No shared expert; no token is dropped.

float32, every contraction at ``Precision.HIGHEST``, no kernels.
Attention is computed in blocks of at most :data:`QUERY_BLOCK` queries,
each over the keys its mask can reach and rematerialised in the
backward, so that no (T, T) score matrix is ever whole. The experts are
computed densely: each held expert over every token, times the token's
weight for it (zero where the token did not choose it).

**The share.** Like the program, the reference holds ``experts_held`` of
the ``num_experts`` (the router scores all of them and the weights are
normalised over the token's whole choice; the layer adds up what the
held experts give) and ``vocab_size`` rows of the vocabulary; every
attention head.

**Departures from the published model**, each on purpose: (1) float32
throughout, where it is released in bfloat16. (2) The configuration's
``assumed`` entries: no q/k norm, softmax routing renormalised over the
choice, no MTP module, no dropout. (3) The shares above.

**K-FAC conventions**: those of ``references/mla_moe_lm.py`` (a
bias-free linear's ``A = a^T a / N`` and ``G = g^T g / N``; an expert's
``A_e`` over its routed rows, ``G_e`` over the step's tokens, both left
alone that step where no token chose it; the embedding's diagonal A;
identity seeds, running averages, damped Cholesky inverses, the KL clip,
the clip by global norm, SGD with momentum; the head and the RMSNorm
scales are left to SGD), whose ``update_factors``, ``all_inverses``,
``precondition_and_apply`` and ``split_stacks`` are used as they are.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from kfac_bench import reference
from kfac_bench.reference import Rounding
from kfac_bench.references.mla_moe_lm import (
    all_inverses,
    precondition_and_apply,
    split_stacks,
    update_factors,
)

EPS = 1e-6
ATTN = ('q_proj', 'k_proj', 'v_proj', 'o_proj')
MLP = ('gate_proj', 'up_proj', 'down_proj')
SLIDING, FULL = 'sliding_attention', 'full_attention'
#: The most queries one attention block holds scores for.
QUERY_BLOCK = 1024


def sizes_of(config: dict) -> dict:
    """The model's sizes under the published keys, with the share; the
    RoPE of either layer type as a tuple."""
    keys = ('hidden_size', 'moe_intermediate_size', 'num_hidden_layers',
            'num_attention_heads', 'num_key_value_heads', 'head_dim',
            'num_experts', 'num_experts_per_tok', 'sliding_window',
            'vocab_size', 'rms_norm_eps')
    sizes = {k: config[k] for k in keys}
    if sizes.pop('rms_norm_eps') != EPS:
        raise ValueError(f'the reference norms with eps {EPS}; the '
                         f"configuration states {config['rms_norm_eps']}")
    if (not config['norm_topk_prob'] or config['attention_bias']
            or config['tie_word_embeddings']):
        raise ValueError('the reference renormalises over the choice, '
                         'has no projection bias and an untied head')
    n = sizes['num_hidden_layers']
    sizes['layer_types'] = tuple(config['layer_types'][:n])
    rope = config['rope_parameters']
    sliding, full = rope[SLIDING], rope[FULL]
    if sliding['rope_type'] != 'default' or full['rope_type'] != 'yarn':
        raise ValueError(f'the reference knows default RoPE on sliding '
                         f'layers and yarn on full ones, not {rope}')
    sizes['sliding_rope_theta'] = sliding['rope_theta']
    sizes['full_rope'] = tuple(full[k] for k in (
        'rope_theta', 'factor', 'original_max_position_embeddings',
        'beta_fast', 'beta_slow', 'attention_factor'))
    sizes['experts_held'] = tuple(config['share']['experts_held'])
    return sizes


def layers(sizes: dict) -> tuple[tuple[str, str, tuple[str, ...]], ...]:
    """``(name, kind, path)`` of every preconditioned layer; kinds
    'embedding', 'linear' (bias-free) and 'experts'."""
    out = [('embed', 'embedding', ('embed',))]
    for i in range(sizes['num_hidden_layers']):
        base = (f'layer{i}',)
        paths = [base + ('self_attn', n) for n in ATTN]
        paths.append(base + ('mlp', 'router'))
        paths += [base + ('mlp', 'experts', n) for n in MLP]
        for path in paths:
            kind = 'experts' if 'experts' == path[-2] else 'linear'
            out.append(('/'.join(path), kind, path))
    return tuple(out)


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------

def _rms_norm(x, scale):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + EPS) * scale


def rope_frequencies(sizes: dict, layer_type: str):
    """``(inv_freq, scale)`` of a layer type's RoPE."""
    d = sizes['head_dim']
    i = jnp.arange(d // 2, dtype=jnp.float32)
    if layer_type == SLIDING:
        return sizes['sliding_rope_theta'] ** (-2.0 * i / d), 1.0
    theta, factor, length, fast, slow, attention = sizes['full_rope']
    plain = theta ** (-2.0 * i / d)

    def r(n):
        return d * math.log(length / (2 * math.pi * n)) / (
            2 * math.log(theta))
    lo, hi = max(math.floor(r(fast)), 0), min(math.ceil(r(slow)), d - 1)
    ramp = jnp.clip((i - lo) / (hi - lo), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp), attention


def _rope(x, inv_freq, scale):
    """Half-split RoPE at positions 0..T-1; ``x``: (rows, T, ..., d)."""
    t = x.shape[1]
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    shape = (1, t) + (1,) * (x.ndim - 3) + (-1,)
    cos = (jnp.cos(angle) * scale).reshape(shape)
    sin = (jnp.sin(angle) * scale).reshape(shape)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, *, first_query, first_key, window, rounding):
    """One block of queries over the keys from ``first_key`` on; ``q``:
    (b, tq, G, H/G, d), ``k``/``v``: (b, tk, G, d)."""
    scores = rounding.einsum('bqgrd,bkgd->bgrqk', q, k) / math.sqrt(
        q.shape[-1])
    i = first_query + jnp.arange(q.shape[1])[:, None]
    j = first_key + jnp.arange(k.shape[1])[None, :]
    mask = j <= i
    if window is not None:
        mask &= j > i - window
    scores = jnp.where(mask, scores, -jnp.inf)
    return rounding.einsum('bgrqk,bkgd->bqgrd', jax.nn.softmax(scores, -1),
                           v)


def _attention(h, p, probe, sizes, rounding, acts, layer_type):
    b, t, _ = h.shape
    heads, groups, d = (sizes['num_attention_heads'],
                        sizes['num_key_value_heads'], sizes['head_dim'])

    def linear(x, name):
        acts[name] = x
        return rounding.einsum('btd,de->bte', x,
                               p[name]['kernel']) + probe[name]

    inv_freq, scale = rope_frequencies(sizes, layer_type)
    q = _rope(linear(h, 'q_proj').reshape(b, t, groups, heads // groups,
                                          d), inv_freq, scale)
    k = _rope(linear(h, 'k_proj').reshape(b, t, groups, d), inv_freq,
              scale)
    v = linear(h, 'v_proj').reshape(b, t, groups, d)
    window = sizes['sliding_window'] if layer_type == SLIDING else None
    blocks = []
    for lo in range(0, t, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, t)
        first_key = 0 if window is None else max(0, lo - window + 1)
        attend = jax.checkpoint(functools.partial(
            _attend, first_query=lo, first_key=first_key, window=window,
            rounding=rounding))
        blocks.append(attend(q[:, lo:hi], k[:, first_key:hi],
                             v[:, first_key:hi]))
    o = jnp.concatenate(blocks, axis=1).reshape(b, t, heads * d)
    return linear(o, 'o_proj')


def _moe(h, p, probe, sizes, rounding, acts):
    """``sum over the held experts of w_e(t) expert_e(h_t)``, each held
    expert over every token. Also notes, per held expert, which tokens
    chose it (``member``)."""
    lo, hi = sizes['experts_held']
    acts['router'] = h
    logits = rounding.einsum('btd,de->bte', h,
                             p['router']['kernel']) + probe['router']
    scores = jax.nn.softmax(logits, axis=-1)
    _, chosen = jax.lax.top_k(scores, sizes['num_experts_per_tok'])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / jnp.sum(picked, -1, keepdims=True)
    held = jnp.arange(lo, hi)[:, None, None, None]
    hit = chosen[None] == held                        # (held, b, t, k)
    weight = jnp.sum(jnp.where(hit, weights[None], 0.0), axis=-1)
    acts['member'] = jnp.any(hit, axis=-1)

    def expert_linear(x, name):
        # x: (held, b, t, d_in) or (b, t, d_in) shared by all experts
        acts['experts/' + name] = x
        spec = ('btd,hde->hbte' if x.ndim == 3 else 'hbtd,hde->hbte')
        return rounding.einsum(spec, x, p['experts'][name]['kernel']) \
            + probe['experts/' + name]

    y = expert_linear(jax.nn.silu(expert_linear(h, 'gate_proj'))
                      * expert_linear(h, 'up_proj'), 'down_proj')
    return jnp.sum(weight[..., None] * y, axis=0)


def _loss_sum(params, probes, ids, targets, *, sizes, rounding, denom):
    """Sum of the rows' token losses over ``denom`` (the whole batch's
    tokens), and every preconditioned layer's input."""
    x = params['embed']['embedding'][ids] + probes['embed']
    acts = {}
    for i, layer_type in enumerate(sizes['layer_types']):
        def block(x, p, probe, layer_type=layer_type):
            a = {}
            h = _rms_norm(x, p['input_layernorm']['scale'])
            x = x + _attention(h, p['self_attn'], probe, sizes, rounding,
                               a, layer_type)
            h = _rms_norm(x, p['post_attention_layernorm']['scale'])
            return x + _moe(h, p['mlp'], probe, sizes, rounding, a), a

        x, acts[f'layer{i}'] = jax.checkpoint(block)(
            x, params[f'layer{i}'], probes[f'layer{i}'])
    x = _rms_norm(x, params['norm']['scale'])
    logits = rounding.einsum('btd,dv->btv', x, params['head']['kernel'])
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                               targets[..., None], axis=-1)
    return jnp.sum(nll) / denom, acts


def _probe_shapes(sizes: dict, b: int, t: int) -> dict:
    d = sizes['hidden_size']
    width = sizes['head_dim']
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


def _rows(params, ids, targets, *, sizes_key, rounding, denom):
    """One block of rows: its share of the loss and of the gradients,
    and its un-normalised sums for every statistic."""
    sizes = dict(sizes_key)
    b, t = ids.shape
    probes = jax.tree.map(lambda s: jnp.zeros(s, jnp.float32),
                          _probe_shapes(sizes, b, t),
                          is_leaf=lambda s: isinstance(s, tuple))
    (loss, acts), (grads, pgrads) = jax.value_and_grad(
        functools.partial(_loss_sum, sizes=sizes, rounding=rounding,
                          denom=denom), argnums=(0, 1), has_aux=True)(
        params, probes, ids, targets)

    def cov(x):
        x = x.reshape(-1, x.shape[-1])
        return rounding.einsum('nd,ne->de', x, x)

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
        # Each held expert over the tokens that chose it. ``g`` needs no
        # mask: no loss reaches an expert's output at other tokens.
        member = acts[layer]['member'].astype(jnp.float32)  # (held, b, t)
        masked = member[..., None] * (a if a.ndim == 4 else a[None])
        sums[name] = {'aa': rounding.einsum('hbtd,hbte->hde', masked,
                                            masked),
                      'gg': rounding.einsum('hbtd,hbte->hde', g, g),
                      'n': jnp.sum(member, axis=(1, 2))}
    return loss, grads, sums


_first_rows = jax.jit(_rows, static_argnames=('sizes_key', 'rounding',
                                              'denom'))


@functools.partial(jax.jit, donate_argnums=1,
                   static_argnames=('sizes_key', 'rounding', 'denom'))
def _add_rows(params, carry, ids, targets, **static):
    """``carry`` plus one more block of rows, in ``carry``'s memory."""
    return jax.tree.map(jnp.add, carry, _rows(params, ids, targets,
                                              **static))


def model_step(sizes: dict, rows_per_block: int, *, half_batch=False):
    """``step(params, batch, rounding) -> (loss, grads, sums, n)``: the
    batch's loss and gradients, the un-normalised statistic sums of
    ``update_factors``, and its tokens. The batch goes through in blocks
    of rows (sums are exact over blocks; routing is per token).
    ``half_batch`` plants the fault of a step that leaves the second
    half of its rows out and averages over the rest (a one-row batch
    keeps its first half of the sequence)."""
    sizes_key = tuple(sorted(sizes.items()))

    def step(params, batch, rounding=Rounding()):
        ids, targets = batch
        if half_batch:
            if ids.shape[0] > 1:
                keep = ids.shape[0] // 2
                ids, targets = ids[:keep], targets[:keep]
            else:
                keep = ids.shape[1] // 2
                ids, targets = ids[:, :keep], targets[:, :keep]
        rows, t = ids.shape
        per = math.gcd(rows, rows_per_block)
        static = dict(sizes_key=sizes_key, rounding=rounding,
                      denom=float(rows * t))
        block = lambda lo: (jnp.asarray(ids[lo:lo + per]),  # noqa: E731
                            jnp.asarray(targets[lo:lo + per]))
        total = _first_rows(params, *block(0), **static)
        for lo in range(per, rows, per):
            total = _add_rows(params, total, *block(lo), **static)
        loss, grads, sums = total
        return loss, grads, sums, rows * t

    return step


# ---------------------------------------------------------------------------
# The K-FAC step
# ---------------------------------------------------------------------------

def init_factors(sizes: dict, params) -> dict:
    """Identity seeds (ones for the embedding's diagonal A; one
    identity per expert of a stack)."""
    out = {}
    for name, kind, path in layers(sizes):
        sub = reference.get_path(params, path)
        if kind == 'embedding':
            vocab, dim = sub['embedding'].shape
            out[name] = {'A': jnp.ones((vocab,), jnp.float32),
                         'G': jnp.eye(dim, dtype=jnp.float32)}
            continue
        *stack, d_in, d_out = sub['kernel'].shape
        out[name] = {
            'A': jnp.broadcast_to(jnp.eye(d_in, dtype=jnp.float32),
                                  (*stack, d_in, d_in)),
            'G': jnp.broadcast_to(jnp.eye(d_out, dtype=jnp.float32),
                                  (*stack, d_out, d_out))}
    return out


def follow(model_step, sizes, hyper, params, batches, *,
           rounding=Rounding(), unchanged_state=False) -> dict:
    """Drive the reference through ``batches`` (the program's first
    steps); returns what ``reference.compare`` reads, as
    ``reference.follow`` does. Factors and inverses wait in host memory
    while the model step runs: beside the step's float32 activations,
    gradients and statistic sums they would not fit one chip at the
    cell's size."""
    layer_list = layers(sizes)
    start = jax.device_get(params)
    momentum = jax.tree.map(jnp.zeros_like, params)
    factors = jax.device_get(init_factors(sizes, params))
    inverses = None
    losses, first_grad = [], None
    for step, batch in enumerate(batches):
        loss, grads, sums, n = model_step(params, batch, rounding)
        losses.append(float(loss))
        if step % hyper.factor_freq == 0:
            factors = jax.device_get(update_factors(
                jax.device_put(factors), sums, n, hyper.factor_decay))
        del sums
        if step % hyper.inv_freq == 0:
            inverses = None  # freed before the new ones are made
            inverses = jax.device_get(all_inverses(
                jax.device_put(factors), hyper.damping))
        params, momentum, fed = precondition_and_apply(
            params, momentum, inverses, grads, layer_list=layer_list,
            hyper=hyper, rounding=rounding)
        if first_grad is None:
            first_grad = reference.leaf_norms(fed)
        del fed, grads
        if unchanged_state:
            params = jax.device_put(start)
            momentum = jax.tree.map(jnp.zeros_like, params)
    return {'losses': losses, 'grad1': first_grad,
            'dparam': reference.diff_norms(params, start),
            'factors': reference.leaf_arrays(
                reference.sketch_factors(split_stacks(factors)))}
