"""Plain reference of the decoder LM: forward, loss, gradients and the
Kronecker statistics of every Dense and of the embedding.

Written from the model's description, not from the program: pre-LN
blocks (LayerNorm eps 1e-6 -> causal multi-head attention from four
Dense projections -> residual; LayerNorm -> Dense -> tanh-GELU -> Dense
-> residual), token embedding plus learned absolute positions, a final
LayerNorm and the embedding matrix reused as the output projection.
The loss is the mean cross entropy over every token of the batch.
float32, every contraction at ``Precision.HIGHEST``; no dropout (the
configurations state 0).

Statistics, per preconditioned layer and per batch of N = rows x seq
tokens: ``A = [a 1]^T [a 1] / N`` of the layer's inputs, ``G = g^T g /
N`` of the gradient of the (mean) loss at its outputs; for the
embedding ``A`` is the diagonal of token frequencies and ``G`` is taken
at the lookup's output. The output projection's call site adds to the
embedding's gradient and to no statistic.

To fit beside nothing else on a 16 GB chip at the cells' own sizes the
batch goes through in blocks of rows (sums of gradients and of
statistics are exact over blocks) and the layers run under a
checkpointed scan.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from kfac_bench.reference import HIGHEST, Layer, Rounding

DENSES = ('q_proj', 'k_proj', 'v_proj', 'out_proj', 'mlp_in', 'mlp_out')
ATTN = ('q_proj', 'k_proj', 'v_proj', 'out_proj')
LN_EPS = 1e-6


def layers(sizes: dict) -> tuple[Layer, ...]:
    out = [Layer('embed', 'embedding', ('embed',))]
    for i in range(sizes['num_layers']):
        for d in DENSES:
            path = ((f'block{i}', 'attn', d) if d in ATTN
                    else (f'block{i}', d))
            out.append(Layer('/'.join(path), 'linear', path))
    return tuple(out)


def _layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p['scale'] + p['bias']


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _stack_blocks(params, depth):
    blocks = [params[f'block{i}'] for i in range(depth)]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)


def _block(x, p, probe, heads, rounding):
    """One block; returns the new residual stream and the inputs of its
    six Denses (q, k and v share one)."""
    b, t, d = x.shape

    def dense(inp, name, sub):
        y = rounding.einsum('btd,de->bte', inp, sub['kernel'])
        return y + sub['bias'] + probe[name]

    h = _layer_norm(x, p['ln1'])
    q, k, v = (dense(h, n, p['attn'][n]).reshape(b, t, heads, d // heads)
               for n in ('q_proj', 'k_proj', 'v_proj'))
    logits = rounding.einsum('bqhd,bkhd->bhqk', q, k) / math.sqrt(
        d // heads)
    causal = jnp.tril(jnp.ones((t, t), bool))
    logits = jnp.where(causal[None, None], logits, -jnp.inf)
    o = rounding.einsum('bhqk,bkhd->bqhd', jax.nn.softmax(logits, -1), v)
    o = o.reshape(b, t, d)
    x = x + dense(o, 'out_proj', p['attn']['out_proj'])
    y = _layer_norm(x, p['ln2'])
    z = _gelu(dense(y, 'mlp_in', p['mlp_in']))
    x = x + dense(z, 'mlp_out', p['mlp_out'])
    return x, {'qkv': h, 'out_proj': o, 'mlp_in': y, 'mlp_out': z}


def _loss_sum(params, probes, ids, targets, *, sizes, rounding, denom):
    """Sum of the block's token losses over ``denom`` (the whole
    batch's token count), and the Dense inputs per layer."""
    depth, heads = sizes['num_layers'], sizes['num_heads']
    t = ids.shape[1]
    x = params['embed']['embedding'][ids] + probes['embed']
    x = x + params['pos_embed'][:t]
    body = jax.checkpoint(functools.partial(
        _block, heads=heads, rounding=rounding))
    x, acts = jax.lax.scan(
        lambda c, xs: body(c, xs[0], xs[1]), x,
        (_stack_blocks(params, depth), probes['blocks']))
    x = _layer_norm(x, params['ln_f'])
    logits = rounding.einsum('btd,vd->btv', x,
                             params['embed']['embedding'])
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return jnp.sum(nll) / denom, acts


def _cov(x, rounding):
    return rounding.einsum('lnd,lne->lde', x, x)


def _rows(params, ids, targets, *, sizes_key, rounding, denom):
    """One block of rows: its share of the loss and the gradients, and
    its un-normalised sums for every statistic."""
    sizes = dict(sizes_key)
    depth, d = sizes['num_layers'], sizes['d_model']
    b, t = ids.shape
    widths = {n: (sizes['mlp_ratio'] * d if n == 'mlp_in' else d)
              for n in DENSES}
    probes = {'embed': jnp.zeros((b, t, d), jnp.float32),
              'blocks': {n: jnp.zeros((depth, b, t, w), jnp.float32)
                         for n, w in widths.items()}}
    (loss, acts), (grads, pgrads) = jax.value_and_grad(
        functools.partial(_loss_sum, sizes=sizes, rounding=rounding,
                          denom=denom), argnums=(0, 1), has_aux=True)(
        params, probes, ids, targets)
    flat = lambda x: x.reshape(depth, b * t, x.shape[-1])  # noqa: E731
    sums = {'embed': {
        'count': jnp.zeros((sizes['vocab_size'],), jnp.float32)
        .at[ids.reshape(-1)].add(1.0),
        'gg': _cov(pgrads['embed'].reshape(1, b * t, d), rounding)[0]}}
    for name in DENSES:
        a = flat(acts['qkv' if name in ATTN[:3] else name])
        sums[name] = {'aa': _cov(a, rounding),
                      'a1': jnp.sum(a, axis=1),
                      'gg': _cov(flat(pgrads['blocks'][name]), rounding)}
    return loss, grads, sums


@functools.partial(jax.jit, donate_argnums=1,
                   static_argnames=('sizes_key', 'rounding', 'denom'))
def _add_rows(params, carry, ids, targets, **static):
    """``carry`` plus one more block of rows, in ``carry``'s memory."""
    return jax.tree.map(jnp.add, carry, _rows(params, ids, targets,
                                              **static))


@functools.partial(jax.jit, static_argnames=('n', 'depth'))
def _finish(sums, n, depth):
    """Sums over the batch's N tokens -> the statistics per layer.
    ``G`` is of the mean loss's output gradients, which the blocks
    already carry (``denom``), so ``g^T g / N`` takes one more ``N``:
    the sums are of ``g`` scaled by 1, the covariance divides by N."""
    stats = {'embed': {'A': sums['embed']['count'] / n,
                       'G': sums['embed']['gg'] / n}}
    for i in range(depth):
        for name in DENSES:
            s = sums[name]
            aa, a1 = s['aa'][i] / n, s['a1'][i] / n
            a = jnp.block([[aa, a1[:, None]],
                           [a1[None, :], jnp.ones((1, 1), jnp.float32)]])
            key = (f'block{i}/attn/{name}' if name in ATTN
                   else f'block{i}/{name}')
            stats[key] = {'A': a, 'G': s['gg'][i] / n}
    return stats


def model_step(sizes: dict, rows_per_block: int, *, half_batch=False):
    """``step(params, batch, rounding) -> (loss, grads, stats)`` for
    :func:`kfac_bench.reference.follow`. ``batch`` is ``(ids, targets)``
    as host arrays. ``half_batch`` plants the fault of a step that
    leaves the second half of its rows out and averages over the
    rest."""
    sizes_key = tuple(sorted(
        (k, v) for k, v in sizes.items()
        if k in ('num_layers', 'num_heads', 'd_model', 'mlp_ratio',
                 'vocab_size')))

    def step(params, batch, rounding=Rounding()):
        ids, targets = batch
        if half_batch:
            keep = max(1, ids.shape[0] // 2)
            ids, targets = ids[:keep], targets[:keep]
        rows, t = ids.shape
        per = math.gcd(rows, rows_per_block)
        static = dict(sizes_key=sizes_key, rounding=rounding,
                      denom=float(rows * t))
        block = lambda lo: (jnp.asarray(ids[lo:lo + per]),  # noqa: E731
                            jnp.asarray(targets[lo:lo + per]))
        total = jax.tree.map(
            lambda x: jnp.zeros(x.shape, x.dtype),
            jax.eval_shape(functools.partial(_rows, **static), params,
                           *block(0)))
        for lo in range(0, rows, per):
            total = _add_rows(params, total, *block(lo), **static)
        loss, grads, sums = total
        return loss, grads, _finish(sums, n=rows * t,
                                    depth=sizes['num_layers'])

    return step
