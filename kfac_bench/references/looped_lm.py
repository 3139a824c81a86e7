"""Plain reference of the looped decoder LM under K-FAC: forward, the
exit-weighted loss, gradients, every preconditioned layer's Kronecker
statistics over all its calls, and the K-FAC step that follows.

Written from the model's equations (Ouro-2.6B's ``config.json``, the
released modeling file's layer and the paper's objective, "Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741), not
from the program. ``d`` = ``hidden_size``, ``T`` = ``total_ut_steps``,
``L`` = ``num_hidden_layers``; every projection bias-free:

- ``x = E[ids]``. For pass ``t = 1..T``, for layer ``l = 1..L`` (the
  SAME weights in every pass):
  ``u = RMSNorm_l1(x)``; ``q, k, v = W_q u, W_k u, W_v u`` split into
  heads of ``head_dim``; RoPE on q and k in the half-split layout
  (``[x1 cos - x2 sin | x2 cos + x1 sin]``, angle ``pos *
  theta**(-2i/head_dim)``), positions 0..seq-1; causal softmax
  attention at ``1 / sqrt(head_dim)``;
  ``x += RMSNorm_l2(W_o attn)``; ``v' = RMSNorm_l3(x)``;
  ``x += RMSNorm_l4(W_down(silu(W_gate v') * W_up v'))``.
  RMSNorm ``x / sqrt(mean(x^2) + 1e-6) * scale``.
- After each pass ``x = RMSNorm_f(x)`` (the next pass starts from the
  normed state), ``h_t = x``, logits ``z_t = W_head h_t`` (one untied
  head for every pass), gate ``lambda_t = sigmoid(w_g . h_t + b_g)``.
- Exits: ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` for ``t < T``,
  ``p_T = prod_{j<T} (1 - lambda_j)`` (``lambda_T`` enters nothing).
- Loss: the mean over tokens of ``sum_t p_t CE(z_t, y) - beta H(p)``,
  ``H(p) = -sum_t p_t log p_t``.

float32, every contraction at ``Precision.HIGHEST``, no kernels.

**Departures from the published description**, each on purpose:
(1) float32 throughout, where the model is released in bfloat16.
(2) ``beta`` is the configuration's assumption (0.1): the paper trains
with an entropy-regularised objective and ``config.json`` carries no
coefficient. (3) ``early_exit_threshold`` plays no part: it is the
inference rule. (4) No dropout, no projection bias (the config states
neither). (5) ``num_hidden_layers`` is the configuration's cut.

**K-FAC conventions** (the library's multi-call rule, which the issue
keeps), per batch of ``N = rows x seq`` tokens. A matrix applied in
``T`` passes has ``T`` calls; call ``c`` reads ``a_c`` (N rows) and
``g_c``, the gradient of the MEAN loss at the call's output. Each call
makes its own covariance over its own N rows and the calls are ADDED:

    A = sum_c a_c^T a_c / N        G = sum_c g_c^T g_c / N

(not averaged over the calls: a matrix run T times has a T-fold A). The
gate has a bias: its ``a_c`` carries a column of ones, so ``A`` is
``(d + 1)^2`` with corner ``T``. The embedding is looked up once: the
diagonal ``A`` of token frequencies and ``G`` at the lookup's output.
Factors start at the identity; running average ``decay * F + (1 -
decay) * new``; damped Cholesky inverses ``(F + damping I)^-1``;
``A^-1 grad G^-1`` in the kernel's (in, out) layout, the gate's bias as
the last row; the KL clip over every preconditioned leaf; then the clip
by global norm over ALL leaves and SGD with momentum. The head and the
RMSNorm scales are left to SGD: their gradients pass as they are.

``kfac_bench/reference.py`` gives ``Rounding``, ``Hyper``, the norms,
the sketches and ``compare``, and ``references/mla_moe_lm.py`` the
damped inverses of a dict of factors, same-sized ones a few at a time
(``all_inverses``); what ``reference.follow`` cannot express
(bias-free linears, several calls a layer, leaves left to SGD) is in
:func:`follow` here.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from kfac_bench import reference
from kfac_bench.reference import Rounding
from kfac_bench.references.mla_moe_lm import all_inverses

EPS = 1e-6
ATTN = ('q_proj', 'k_proj', 'v_proj', 'o_proj')
MLP = ('gate_proj', 'up_proj', 'down_proj')
GATE = 'exit/early_exit_gate'


def sizes_of(config: dict) -> dict:
    """The model's sizes under the published keys."""
    keys = ('hidden_size', 'intermediate_size', 'num_hidden_layers',
            'num_attention_heads', 'num_key_value_heads', 'head_dim',
            'total_ut_steps', 'rope_theta', 'vocab_size', 'rms_norm_eps')
    sizes = {k: config[k] for k in keys}
    if sizes.pop('rms_norm_eps') != EPS:
        raise ValueError(f'the reference norms with eps {EPS}; the '
                         f"configuration states {config['rms_norm_eps']}")
    if sizes.pop('num_key_value_heads') != sizes['num_attention_heads']:
        raise ValueError('the reference is plain multi-head attention; '
                         'the configuration states grouped key/value heads')
    sizes['exit_entropy_beta'] = config['exit_entropy_beta']
    return sizes


def layers(sizes: dict) -> tuple[tuple[str, str, tuple[str, ...]], ...]:
    """``(name, kind, path)`` of every preconditioned layer; kinds
    'embedding', 'linear' (bias-free) and 'linear_bias' (the gate)."""
    out = [('embed', 'embedding', ('embed',))]
    for i in range(sizes['num_hidden_layers']):
        for group, names in (('self_attn', ATTN), ('mlp', MLP)):
            for n in names:
                path = (f'layer{i}', group, n)
                out.append(('/'.join(path), 'linear', path))
    out.append((GATE, 'linear_bias', tuple(GATE.split('/'))))
    return tuple(out)


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------

def _rms_norm(x, scale):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + EPS) * scale


def _rope(x, theta):
    """Half-split RoPE; ``x``: (rows, T, heads, d)."""
    t, d = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(x, p, probe, *, sizes, rounding):
    """One call of one layer; also every matrix's input at this call."""
    b, t, _ = x.shape
    heads, hd = sizes['num_attention_heads'], sizes['head_dim']
    acts = {}

    def linear(h, group, name):
        acts[f'{group}/{name}'] = h
        return rounding.einsum('btd,de->bte', h, p[group][name]['kernel']) \
            + probe[f'{group}/{name}']

    u = _rms_norm(x, p['input_layernorm']['scale'])
    q, k, v = (linear(u, 'self_attn', n).reshape(b, t, heads, hd)
               for n in ATTN[:3])
    q, k = _rope(q, sizes['rope_theta']), _rope(k, sizes['rope_theta'])
    scores = rounding.einsum('bqhd,bkhd->bhqk', q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    o = rounding.einsum('bhqk,bkhd->bqhd', jax.nn.softmax(scores, -1), v)
    x = x + _rms_norm(linear(o.reshape(b, t, heads * hd), 'self_attn',
                             'o_proj'), p['input_layernorm_2']['scale'])
    w = _rms_norm(x, p['post_attention_layernorm']['scale'])
    y = linear(jax.nn.silu(linear(w, 'mlp', 'gate_proj'))
               * linear(w, 'mlp', 'up_proj'), 'mlp', 'down_proj')
    return x + _rms_norm(y, p['post_attention_layernorm_2']['scale']), acts


def _exit(x, p, probe, targets, *, rounding):
    """After a pass: the normed stream, the exit's cross entropy a
    token, the gate's logit, and the gate's input with its ones."""
    h = _rms_norm(x, p['norm']['scale'])
    logits = rounding.einsum('btd,dv->btv', h, p['head']['kernel'])
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                               targets[..., None], axis=-1)[..., 0]
    gate = p['early_exit_gate']
    z = rounding.einsum('btd,de->bte', h, gate['kernel']) + gate['bias'] \
        + probe
    ones = jnp.ones((*h.shape[:-1], 1), h.dtype)
    return h, nll, z[..., 0], jnp.concatenate([h, ones], axis=-1)


def exit_weights(gate_logits):
    """``p`` of the exits, ``(T, ...)``, from the gate's logits."""
    lam = jax.nn.sigmoid(gate_logits[:-1])     # lambda_T enters nothing
    one = jnp.ones_like(gate_logits[:1])
    stayed = jnp.concatenate([one, jnp.cumprod(1.0 - lam, axis=0)])
    return stayed * jnp.concatenate([lam, one])


def objective(nll, gate_logits, beta):
    """``sum_t p_t nll_t - beta H(p)`` a token; both ``(T, ...)``."""
    p = exit_weights(gate_logits)
    entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(
        p > 0, p, 1.0)), 0.0), axis=0)
    return jnp.sum(p * nll, axis=0) - beta * entropy


def _loss_sum(params, probes, ids, targets, *, sizes, rounding, denom):
    """Sum of the rows' token objectives over ``denom`` (the whole
    batch's tokens), and every preconditioned matrix's input at each of
    its calls (``acts[pass][layer][matrix]``, the gate's under
    ``acts[pass]['gate']``)."""
    x = params['embed']['embedding'][ids] + probes['embed']
    acts, nlls, gates = [], [], []
    for t in range(sizes['total_ut_steps']):
        seen = {}
        for i in range(sizes['num_hidden_layers']):
            x, seen[f'layer{i}'] = jax.checkpoint(functools.partial(
                _block, sizes=sizes, rounding=rounding))(
                x, params[f'layer{i}'], probes[f'pass{t}'][f'layer{i}'])
        x, nll, gate, seen['gate'] = jax.checkpoint(functools.partial(
            _exit, rounding=rounding))(
            x, params['exit'], probes[f'pass{t}']['gate'], targets)
        acts.append(seen)
        nlls.append(nll)
        gates.append(gate)
    per_token = objective(jnp.stack(nlls), jnp.stack(gates),
                          sizes['exit_entropy_beta'])
    return jnp.sum(per_token) / denom, acts


def _probe_shapes(sizes: dict, b: int, t: int) -> dict:
    d = sizes['hidden_size']
    width = sizes['num_attention_heads'] * sizes['head_dim']
    layer = {**{f'self_attn/{n}': (b, t, width) for n in ATTN[:3]},
             'self_attn/o_proj': (b, t, d),
             'mlp/gate_proj': (b, t, sizes['intermediate_size']),
             'mlp/up_proj': (b, t, sizes['intermediate_size']),
             'mlp/down_proj': (b, t, d)}
    one_pass = {f'layer{i}': dict(layer)
                for i in range(sizes['num_hidden_layers'])}
    one_pass['gate'] = (b, t, 1)
    return {'embed': (b, t, d),
            **{f'pass{p}': one_pass
               for p in range(sizes['total_ut_steps'])}}


def _rows(params, ids, targets, *, sizes_key, rounding, denom,
          dropped_pass):
    """One block of rows: its share of the loss and of the gradients,
    and its un-normalised sums for every statistic, added over the
    calls (``dropped_pass``: see :func:`model_step`)."""
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

    calls = [p for p in range(sizes['total_ut_steps'])
             if p != dropped_pass]
    sums = {'embed': {
        'count': jnp.zeros((sizes['vocab_size'],), jnp.float32)
        .at[ids.reshape(-1)].add(1.0),
        'gg': cov(pgrads['embed'])}}
    for name, kind, path in layers(sizes)[1:]:
        where = (('gate',) if kind == 'linear_bias'
                 else (path[0], '/'.join(path[1:])))
        sums[name] = {
            'aa': sum(cov(reference.get_path(acts[p], where))
                      for p in calls),
            'gg': sum(cov(reference.get_path(pgrads[f'pass{p}'], where))
                      for p in calls)}
    return loss, grads, sums


@functools.partial(jax.jit, donate_argnums=1,
                   static_argnames=('sizes_key', 'rounding', 'denom',
                                    'dropped_pass'))
def _add_rows(params, carry, ids, targets, **static):
    """``carry`` plus one more block of rows, in ``carry``'s memory."""
    return jax.tree.map(jnp.add, carry, _rows(params, ids, targets,
                                              **static))


def model_step(sizes: dict, rows_per_block: int, *, half_batch=False,
               dropped_pass=None):
    """``step(params, batch, rounding) -> (loss, grads, sums, n)``: the
    batch's loss and gradients, the un-normalised statistic sums of
    :func:`update_factors`, and its tokens. The batch goes through in
    blocks of rows (every sum is exact over blocks). ``half_batch``
    plants the fault of a step that leaves the second half of its rows
    out and averages over the rest; ``dropped_pass`` that of a capture
    that loses one pass's statistics (its calls are left out of every
    factor's sum; loss and gradients are whole)."""
    sizes_key = tuple(sorted(sizes.items()))

    def step(params, batch, rounding=Rounding()):
        ids, targets = batch
        if half_batch:
            keep = max(1, ids.shape[0] // 2)
            ids, targets = ids[:keep], targets[:keep]
        rows, t = ids.shape
        per = math.gcd(rows, rows_per_block)
        static = dict(sizes_key=sizes_key, rounding=rounding,
                      denom=float(rows * t), dropped_pass=dropped_pass)
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
    """Identity seeds (ones for the embedding's diagonal A; the gate's
    A one wider for its bias)."""
    out = {}
    for name, kind, path in layers(sizes):
        sub = reference.get_path(params, path)
        if kind == 'embedding':
            vocab, dim = sub['embedding'].shape
            out[name] = {'A': jnp.ones((vocab,), jnp.float32),
                         'G': jnp.eye(dim, dtype=jnp.float32)}
            continue
        d_in, d_out = sub['kernel'].shape
        d_in += kind == 'linear_bias'
        out[name] = {'A': jnp.eye(d_in, dtype=jnp.float32),
                     'G': jnp.eye(d_out, dtype=jnp.float32)}
    return out


@functools.partial(jax.jit, static_argnames=('n', 'decay'),
                   donate_argnums=0)
def update_factors(factors, sums, n, decay):
    """The batch's statistics from its sums (over rows and calls) and
    its ``n`` tokens, folded into the running averages."""
    def blend(old, new):
        return decay * old + (1.0 - decay) * new

    return {name: {'A': blend(factors[name]['A'],
                              (s['count'] if 'count' in s else s['aa']) / n),
                   'G': blend(factors[name]['G'], s['gg'] / n)}
            for name, s in sums.items()}


def _grad_matrix(kind: str, sub: dict):
    """A layer's gradient in the (in [+ 1], out) layout its factors
    are in: the gate's bias is the last row."""
    if kind == 'embedding':
        return sub['embedding']
    if kind == 'linear_bias':
        return jnp.concatenate([sub['kernel'], sub['bias'][None, :]], 0)
    return sub['kernel']


def _matrix_to_sub(kind: str, mat, sub: dict) -> dict:
    if kind == 'embedding':
        return {**sub, 'embedding': mat}
    if kind == 'linear_bias':
        return {**sub, 'kernel': mat[:-1], 'bias': mat[-1]}
    return {**sub, 'kernel': mat}


@functools.partial(jax.jit,
                   static_argnames=('layer_list', 'hyper', 'rounding'),
                   donate_argnums=(0, 1, 3))
def precondition_and_apply(params, momentum, inverses, grads, *,
                           layer_list, hyper, rounding):
    """``A^-1 grad G^-1`` per preconditioned layer, the KL clip over
    all of them, the clip by global norm over every leaf, SGD with
    momentum. Returns the new parameters and momentum, and the gradient
    as SGD got it."""
    precond, vg = {}, jnp.zeros((), jnp.float32)
    for name, kind, path in layer_list:
        g = _grad_matrix(kind, reference.get_path(grads, path))
        inv = inverses[name]
        if kind == 'embedding':
            v = rounding.einsum('vd,de->ve', inv['A'][:, None] * g,
                                inv['G'])
        else:
            v = rounding.einsum(
                'io,op->ip', rounding.einsum('ij,jo->io', inv['A'], g),
                inv['G'])
        precond[name] = v
        vg += jnp.sum(v * g) * hyper.lr ** 2
    nu = jnp.minimum(1.0, jnp.sqrt(hyper.kl_clip / (jnp.abs(vg) + 1e-30)))
    out = grads
    for name, kind, path in layer_list:
        out = reference.set_path(out, path, _matrix_to_sub(
            kind, nu * precond[name], reference.get_path(grads, path)))
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(out)))
    clip = jnp.minimum(1.0, hyper.grad_clip / jnp.maximum(norm, 1e-30))
    out = jax.tree.map(lambda x: x * clip, out)
    momentum = jax.tree.map(lambda m, g: g + hyper.momentum * m,
                            momentum, out)
    params = jax.tree.map(lambda p, m: p - hyper.lr * m, params, momentum)
    return params, momentum, out


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
                reference.sketch_factors(factors))}
