"""Plain reference of the latent-attention, routed-expert decoder LM
under K-FAC: forward, loss, gradients, every preconditioned layer's
Kronecker statistics, and the K-FAC step that follows.

Written from the model's equations (HF ``DeepseekV3*`` semantics, every
projection bias-free), not from the program:

- block: ``x += MLA(RMSNorm(x))``, ``x += FFN(RMSNorm(x))``; RMSNorm
  ``x / sqrt(mean(x^2) + 1e-6) * scale``; token embedding with nothing
  added; a final RMSNorm; an untied head. The loss is the mean cross
  entropy over every token, the logits over the held vocabulary rows.
- MLA: ``q = q_proj(h)`` -> heads x ``[q_nope | q_rope]``;
  ``kv_a_proj_with_mqa(h)`` -> ``[c_kv | k_rope]``;
  ``kv_b_proj(RMSNorm(c_kv))`` -> heads x ``[k_nope | v]``; RoPE on
  ``q_rope`` and on the one ``k_rope`` all heads share; scores ``q.k /
  sqrt(nope + rope)``, causal softmax, ``P.v``, ``o_proj``.
- FFN of the first ``first_k_dense`` layers: ``down(silu(gate(h)) *
  up(h))``. Of the others: router logits ``h W_r``, ``s = sigmoid``,
  the ``top_k`` experts with the largest ``s + bias`` (bias zero and
  constant here), ``w = s[chosen] / (sum s[chosen] + 1e-20) *
  routed_scaling_factor``, output ``shared(h) + sum_k w_k expert_k(h)``
  with every expert a SwiGLU. No token is dropped.

float32, every contraction at ``Precision.HIGHEST``, no kernels. The
experts are computed densely: each held expert over every token, times
the token's weight for it (zero where the token did not choose it).

**The share.** Like the program, the reference holds ``experts_held``
of the ``n_routed_experts`` (the router scores all of them and the
weights are normalised over the token's whole choice; the layer adds up
what the held experts give), ``heads_held`` attention heads (``o_proj``
sums over those), and ``vocab_size`` rows of the vocabulary.

**Departures from the HF semantics**, each on purpose: (1) RoPE turns
adjacent pairs in place; HF (``rope_interleave``) then permutes the
turned pairs into the half-split layout, on q and k alike, which leaves
every score unchanged. (2) float32 throughout, where HF runs bfloat16
with float32 norms and router. (3) ``e_score_correction_bias`` is zero
and never updated (its training rule is not in the published config);
the reference carries no such leaf, the program's gets no gradient.
(4) the shares above.

**K-FAC conventions** (the library's, which the issue's agree with),
per batch of ``N = rows x seq`` tokens: a bias-free linear has ``A = a^T
a / N`` and ``G = g^T g / N`` with ``g`` the gradient of the mean loss
at its output; the embedding has the diagonal ``A`` of token
frequencies and ``G`` at the lookup's output. A stacked expert ``e``
with routed tokens ``T_e``, ``n_e = |T_e|``: ``A_e = sum_{t in T_e} a_t
a_t^T / n_e`` and ``G_e = sum_{t in T_e} g_t g_t^T / N`` (``g`` carries
the routing weight); an expert with ``n_e = 0`` keeps both running
averages untouched that step. Factors start at the identity; running
average ``decay * F + (1 - decay) * new``; damped Cholesky inverses
``(F + damping I)^-1``; ``G^-1 grad A^-1``; the KL clip over every
preconditioned leaf; then the clip by global norm over ALL leaves and
SGD with momentum. The head, the RMSNorm scales (and the program's
correction bias) are left to SGD: their gradients pass as they are.

What ``kfac_bench/reference.py:follow`` cannot express (bias-free
linears, expert stacks, leaves left to SGD) is in :func:`follow` here;
its ``Rounding``, ``Hyper``, norms, sketches and ``compare`` are used
as they are. An expert stack's factors are sketched expert by expert
(:func:`split_stacks`).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from kfac_bench import reference
from kfac_bench.reference import Rounding

EPS = 1e-6
ATTN = ('q_proj', 'kv_a_proj_with_mqa', 'kv_b_proj', 'o_proj')
MLP = ('gate_proj', 'up_proj', 'down_proj')


def sizes_of(config: dict) -> dict:
    """The model's sizes under the published keys, with the share."""
    keys = ('hidden_size', 'intermediate_size', 'moe_intermediate_size',
            'num_hidden_layers', 'first_k_dense_replace',
            'n_routed_experts', 'num_experts_per_tok', 'n_shared_experts',
            'routed_scaling_factor', 'qk_nope_head_dim',
            'qk_rope_head_dim', 'v_head_dim', 'kv_lora_rank',
            'rope_theta', 'vocab_size', 'rms_norm_eps')
    sizes = {k: config[k] for k in keys}
    if sizes.pop('rms_norm_eps') != EPS:
        raise ValueError(f'the reference norms with eps {EPS}; the '
                         f"configuration states {config['rms_norm_eps']}")
    share = config['share']
    sizes['heads_held'] = share['heads_held']
    sizes['experts_held'] = tuple(share['experts_held'])
    return sizes


def is_moe(sizes: dict, layer: int) -> bool:
    return layer >= sizes['first_k_dense_replace']


def layers(sizes: dict) -> tuple[tuple[str, str, tuple[str, ...]], ...]:
    """``(name, kind, path)`` of every preconditioned layer; kinds
    'embedding', 'linear' (bias-free) and 'experts'."""
    out = [('embed', 'embedding', ('embed',))]
    for i in range(sizes['num_hidden_layers']):
        base = (f'layer{i}',)
        paths = [base + ('self_attn', n) for n in ATTN]
        if is_moe(sizes, i):
            paths.append(base + ('mlp', 'router'))
            paths += [base + ('mlp', 'experts', n) for n in MLP]
            paths += [base + ('mlp', 'shared_experts', n) for n in MLP]
        else:
            paths += [base + ('mlp', n) for n in MLP]
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


def _rope(x, theta):
    """Adjacent pairs of the last dim turned by ``t * theta**(-2i/d)``;
    ``x``: (rows, T, heads, d)."""
    t, d = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def _mla(h, p, probe, sizes, rounding, acts):
    b, t, _ = h.shape
    heads, nope, rot, dv, rank = (
        sizes['heads_held'], sizes['qk_nope_head_dim'],
        sizes['qk_rope_head_dim'], sizes['v_head_dim'],
        sizes['kv_lora_rank'])

    def linear(x, name):
        acts[name] = x
        return rounding.einsum('btd,de->bte', x,
                               p[name]['kernel']) + probe[name]

    q = linear(h, 'q_proj').reshape(b, t, heads, nope + rot)
    kv_a = linear(h, 'kv_a_proj_with_mqa')
    c_kv, k_rope = kv_a[..., :rank], kv_a[..., rank:]
    kv = linear(_rms_norm(c_kv, p['kv_a_layernorm']['scale']),
                'kv_b_proj').reshape(b, t, heads, nope + dv)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_rope = _rope(q[..., nope:], sizes['rope_theta'])
    k_rope = _rope(k_rope[:, :, None, :], sizes['rope_theta'])
    q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, t, heads, rot))], axis=-1)
    scores = rounding.einsum('bqhd,bkhd->bhqk', q, k) / math.sqrt(
        nope + rot)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    o = rounding.einsum('bhqk,bkhd->bqhd', jax.nn.softmax(scores, -1), v)
    return linear(o.reshape(b, t, heads * dv), 'o_proj')


def _swiglu(h, p, probe, rounding, acts, prefix=''):
    def linear(x, name):
        acts[prefix + name] = x
        return rounding.einsum('...d,de->...e', x,
                               p[name]['kernel']) + probe[prefix + name]
    return linear(jax.nn.silu(linear(h, 'gate_proj'))
                  * linear(h, 'up_proj'), 'down_proj')


def _moe(h, p, probe, sizes, rounding, acts):
    """``shared(h) + sum over the held experts of w_e(t) expert_e(h_t)``,
    each held expert over every token. Also notes, per held expert,
    which tokens chose it (``member``)."""
    lo, hi = sizes['experts_held']
    acts['router'] = h
    logits = rounding.einsum('btd,de->bte', h,
                             p['router']['kernel']) + probe['router']
    scores = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(scores, sizes['num_experts_per_tok'])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20) \
        * sizes['routed_scaling_factor']
    # (held, b, t): the token's weight for each held expert, 0 if not
    # chosen; and whether it was chosen at all.
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
    routed = jnp.sum(weight[..., None] * y, axis=0)
    return routed + _swiglu(h, p['shared_experts'], probe, rounding,
                            acts, prefix='shared_experts/')


def _loss_sum(params, probes, ids, targets, *, sizes, rounding, denom):
    """Sum of the rows' token losses over ``denom`` (the whole batch's
    tokens), and every preconditioned layer's input."""
    x = params['embed']['embedding'][ids] + probes['embed']
    acts = {}
    for i in range(sizes['num_hidden_layers']):
        def block(x, p, probe, moe=is_moe(sizes, i)):
            a = {}
            h = _rms_norm(x, p['input_layernorm']['scale'])
            x = x + _mla(h, p['self_attn'], probe, sizes, rounding, a)
            h = _rms_norm(x, p['post_attention_layernorm']['scale'])
            if moe:
                return x + _moe(h, p['mlp'], probe, sizes, rounding, a), a
            return x + _swiglu(h, p['mlp'], probe, rounding, a), a

        x, acts[f'layer{i}'] = jax.checkpoint(block)(
            x, params[f'layer{i}'], probes[f'layer{i}'])
    x = _rms_norm(x, params['norm']['scale'])
    logits = rounding.einsum('btd,dv->btv', x, params['head']['kernel'])
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                               targets[..., None], axis=-1)
    return jnp.sum(nll) / denom, acts


def _probe_shapes(sizes: dict, b: int, t: int) -> dict:
    d, heads = sizes['hidden_size'], sizes['heads_held']
    qk = sizes['qk_nope_head_dim'] + sizes['qk_rope_head_dim']
    lo, hi = sizes['experts_held']
    attn = {'q_proj': heads * qk,
            'kv_a_proj_with_mqa': (sizes['kv_lora_rank']
                                   + sizes['qk_rope_head_dim']),
            'kv_b_proj': heads * (sizes['qk_nope_head_dim']
                                  + sizes['v_head_dim']),
            'o_proj': d}

    def swiglu(width, prefix=''):
        return {prefix + 'gate_proj': width, prefix + 'up_proj': width,
                prefix + 'down_proj': d}
    out = {'embed': (b, t, d)}
    for i in range(sizes['num_hidden_layers']):
        widths = dict(attn)
        if is_moe(sizes, i):
            widths['router'] = sizes['n_routed_experts']
            widths.update(swiglu(sizes['n_shared_experts']
                                 * sizes['moe_intermediate_size'],
                                 'shared_experts/'))
            layer = {n: (b, t, w) for n, w in widths.items()}
            layer.update({n: (hi - lo, b, t, w) for n, w in swiglu(
                sizes['moe_intermediate_size'], 'experts/').items()})
        else:
            widths.update(swiglu(sizes['intermediate_size']))
            layer = {n: (b, t, w) for n, w in widths.items()}
        out[f'layer{i}'] = layer
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


@functools.partial(jax.jit, donate_argnums=1,
                   static_argnames=('sizes_key', 'rounding', 'denom'))
def _add_rows(params, carry, ids, targets, **static):
    """``carry`` plus one more block of rows, in ``carry``'s memory."""
    return jax.tree.map(jnp.add, carry, _rows(params, ids, targets,
                                              **static))


def model_step(sizes: dict, rows_per_block: int, *, half_batch=False):
    """``step(params, batch, rounding) -> (loss, grads, sums, n)``: the
    batch's loss and gradients, the un-normalised statistic sums of
    :func:`update_factors`, and its tokens. The batch goes through in
    blocks of rows (sums are exact over blocks; routing is per token).
    ``half_batch`` plants the fault of a step that leaves the second
    half of its rows out and averages over the rest."""
    sizes_key = tuple(sorted(sizes.items()))

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


@functools.partial(jax.jit, static_argnames=('n', 'decay'),
                   donate_argnums=0)
def update_factors(factors, sums, n, decay):
    """The batch's statistics from its sums over ``n`` tokens, folded
    into the running averages. An expert that no token chose keeps its
    averages."""
    def blend(old, new):
        return decay * old + (1.0 - decay) * new

    out = {}
    for name, s in sums.items():
        f = factors[name]
        if 'count' in s:
            out[name] = {'A': blend(f['A'], s['count'] / n),
                         'G': blend(f['G'], s['gg'] / n)}
        elif 'n' in s:
            live = (s['n'] > 0)[:, None, None]
            rows = jnp.maximum(s['n'], 1.0)[:, None, None]
            out[name] = {
                'A': jnp.where(live, blend(f['A'], s['aa'] / rows), f['A']),
                'G': jnp.where(live, blend(f['G'], s['gg'] / n), f['G'])}
        else:
            out[name] = {'A': blend(f['A'], s['aa'] / n),
                         'G': blend(f['G'], s['gg'] / n)}
    return out


INVERSE_CHUNK = 8


def all_inverses(factors: dict, damping: float) -> dict:
    """``(F + damping I)^-1`` of every dense factor and of every expert
    of a stack, ``1 / (a + damping)`` of the embedding's diagonal A.
    Same-sized matrices go through one batched call, at most
    ``INVERSE_CHUNK`` at a time: that bounds the reference's compile
    and its peak memory, nothing else."""
    inverses = {name: {} for name in factors}
    by_dim: dict[int, list] = {}
    for name, f in factors.items():
        for side in ('A', 'G'):
            m = f[side]
            if m.ndim == 1:
                inverses[name][side] = 1.0 / (m + damping)
            elif m.ndim == 3:
                inverses[name][side] = reference._inverse_stack(m, damping)
            else:
                by_dim.setdefault(m.shape[0], []).append((name, side))
    for keys in by_dim.values():
        for lo in range(0, len(keys), INVERSE_CHUNK):
            chunk = keys[lo:lo + INVERSE_CHUNK]
            solved = reference._inverse_stack(
                jnp.stack([factors[n][s] for n, s in chunk]), damping)
            for i, (n, s) in enumerate(chunk):
                inverses[n][s] = solved[i]
    return inverses


def _leaf_of(kind: str) -> str:
    return 'embedding' if kind == 'embedding' else 'kernel'


@functools.partial(jax.jit,
                   static_argnames=('layer_list', 'hyper', 'rounding'),
                   donate_argnums=(0, 1, 3))
def precondition_and_apply(params, momentum, inverses, grads, *,
                           layer_list, hyper, rounding):
    """``G^-1 grad A^-1`` per preconditioned leaf (expert by expert for
    a stack), the KL clip over all of them, the clip by global norm
    over every leaf, SGD with momentum. Returns the new parameters and
    momentum, and the gradient as SGD got it."""
    precond, vg = {}, jnp.zeros((), jnp.float32)
    for name, kind, path in layer_list:
        g = reference.get_path(grads, path)[_leaf_of(kind)]
        inv = inverses[name]
        if kind == 'embedding':
            v = rounding.einsum('vd,de->ve', inv['A'][:, None] * g,
                                inv['G'])
        else:
            # kernels are (..., in, out): A^-1 grad G^-1 in that layout
            v = rounding.einsum(
                '...io,...op->...ip',
                rounding.einsum('...ij,...jo->...io', inv['A'], g),
                inv['G'])
        precond[name] = v
        vg += jnp.sum(v * g) * hyper.lr ** 2
    nu = jnp.minimum(1.0, jnp.sqrt(hyper.kl_clip / (jnp.abs(vg) + 1e-30)))
    out = grads
    for name, kind, path in layer_list:
        out = reference.set_path(out, path + (_leaf_of(kind),),
                                 nu * precond[name])
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(out)))
    clip = jnp.minimum(1.0, hyper.grad_clip / jnp.maximum(norm, 1e-30))
    out = jax.tree.map(lambda x: x * clip, out)
    momentum = jax.tree.map(lambda m, g: g + hyper.momentum * m,
                            momentum, out)
    params = jax.tree.map(lambda p, m: p - hyper.lr * m, params, momentum)
    return params, momentum, out


def split_stacks(factors: dict) -> dict:
    """An expert stack's ``(E, d, d)`` factor as ``E`` leaves, so that
    ``reference.sketch_factors`` reads each expert by itself."""
    return {name: {side: ({f'{e:02d}': m[e] for e in range(m.shape[0])}
                          if m.ndim == 3 else m)
                   for side, m in f.items() if side in ('A', 'G')}
            for name, f in factors.items()}


def follow(model_step, sizes, hyper, params, batches, *,
           rounding=Rounding(), unchanged_state=False) -> dict:
    """Drive the reference through ``batches`` (the program's first
    steps); returns what ``reference.compare`` reads, as
    ``reference.follow`` does."""
    layer_list = layers(sizes)
    start = jax.device_get(params)
    momentum = jax.tree.map(jnp.zeros_like, params)
    factors = init_factors(sizes, params)
    inverses = None
    losses, first_grad = [], None
    for step, batch in enumerate(batches):
        loss, grads, sums, n = model_step(params, batch, rounding)
        losses.append(float(loss))
        if step % hyper.factor_freq == 0:
            factors = update_factors(factors, sums, n, hyper.factor_decay)
        del sums
        if step % hyper.inv_freq == 0:
            inverses = None  # freed before the new ones are made
            inverses = all_inverses(factors, hyper.damping)
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
