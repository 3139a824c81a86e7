"""The plain reference of a K-FAC training step, and the comparison.

Nothing here imports the program. A family's reference model
(``references/<family>.py``) gives, for one batch, the loss, the
gradients and every preconditioned layer's fresh Kronecker statistics;
this file does what follows: the running average of the factors from
their identity seed, the damped Cholesky inverses, ``G^-1 grad A^-1``,
the KL clip, the clip by global norm and SGD with momentum, all in
float32 with every contraction at ``Precision.HIGHEST``.

Departures from the program, each on purpose: float32 throughout where
the program computes in bfloat16; one dense inverse per factor where the
program batches same-sized factors into stacks. Same mathematics.

One reading sees the K-FAC state itself, which the preconditioned
gradient cannot (under bfloat16 compute a rounded factor moves no
leaf's norm): a sketch ``F V`` of every stored factor after the last
followed step, compared with the reference's by the norm of the
difference. ``V`` is eight fixed random columns, so a sketch's
difference norm estimates the Frobenius one.

``Rounding`` is the control: the same reference with the operands of
every contraction rounded to a narrower type (float8_e4m3 stands below
the configurations' bfloat16), and the faults a step can have
(``half_batch``) are planted here too, so that they can be read on the
chip without the program.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import statistics

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Rounding:
    """How a reference run rounds the operands of its contractions.

    ``dtype`` None is the reference itself. Otherwise every operand is
    scaled per tensor to the type's range, rounded to it and scaled back
    (what a narrow matmul path with per-tensor scales does); the
    gradient passes straight through the rounding."""
    dtype: str | None = None

    def __call__(self, x):
        if self.dtype is None:
            return x
        dt = jnp.dtype(self.dtype)
        top = float(jnp.finfo(dt).max)
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
        rounded = (x / scale).astype(dt).astype(x.dtype) * scale
        return x + jax.lax.stop_gradient(rounded - x)

    def einsum(self, spec, a, b):
        return jnp.einsum(spec, self(a), self(b), precision=HIGHEST,
                          preferred_element_type=jnp.float32)


@dataclasses.dataclass(frozen=True)
class Layer:
    """One preconditioned layer: ``kind`` 'linear' (flax Dense: kernel
    (in, out) and bias) or 'embedding' (table (vocab, dim), diagonal A),
    and the path of its parameters in the tree."""
    name: str
    kind: str
    path: tuple[str, ...]


@dataclasses.dataclass(frozen=True)
class Hyper:
    lr: float
    momentum: float
    damping: float
    factor_decay: float
    kl_clip: float
    grad_clip: float
    factor_freq: int
    inv_freq: int


def get_path(tree, path):
    for part in path:
        tree = tree[part]
    return tree


def set_path(tree, path, value):
    if not path:
        return value
    out = dict(tree)
    out[path[0]] = set_path(tree[path[0]], path[1:], value)
    return out


def init_factors(layers, params):
    """Identity seeds (ones for an embedding's diagonal A)."""
    out = {}
    for layer in layers:
        sub = get_path(params, layer.path)
        if layer.kind == 'embedding':
            vocab, dim = sub['embedding'].shape
            out[layer.name] = {'A': jnp.ones((vocab,), jnp.float32),
                               'G': jnp.eye(dim, dtype=jnp.float32)}
        else:
            d_in, d_out = sub['kernel'].shape
            out[layer.name] = {'A': jnp.eye(d_in + 1, dtype=jnp.float32),
                               'G': jnp.eye(d_out, dtype=jnp.float32)}
    return out


def _damped_inverse(m, damping):
    n = m.shape[-1]
    eye = jnp.eye(n, dtype=jnp.float32)
    chol = jnp.linalg.cholesky(m + damping * eye)
    inv_l = jax.scipy.linalg.solve_triangular(chol, eye, lower=True)
    return jnp.matmul(inv_l.T, inv_l, precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=('damping',))
def _inverse_stack(stack, damping):
    return jax.lax.map(lambda m: _damped_inverse(m, damping), stack)


def all_inverses(layers, factors, damping):
    """``(F + damping I)^-1`` of every dense factor, ``1 / (a +
    damping)`` of an embedding's diagonal A. Same-sized factors go
    through one batched call, one size after the other: that keeps the
    reference's compile and its peak memory small, nothing else."""
    by_dim: dict[int, list] = {}
    inverses = {layer.name: {} for layer in layers}
    for layer in layers:
        for side in ('A', 'G'):
            f = factors[layer.name][side]
            if f.ndim == 2:
                by_dim.setdefault(f.shape[0], []).append(
                    (layer.name, side))
            else:
                inverses[layer.name][side] = 1.0 / (f + damping)
    for keys in by_dim.values():
        solved = _inverse_stack(
            jnp.stack([factors[n][s] for n, s in keys]), damping)
        for i, (n, s) in enumerate(keys):
            inverses[n][s] = solved[i]
    return inverses


@functools.partial(jax.jit, static_argnames=('decay',), donate_argnums=0)
def update_factors(factors, stats, decay):
    return jax.tree.map(lambda f, s: decay * f + (1.0 - decay) * s,
                        factors, stats)


PROBE_COLUMNS = 8


def _probe(dim: int):
    return jax.random.normal(jax.random.PRNGKey(dim),
                             (dim, PROBE_COLUMNS), jnp.float32)


@jax.jit
def sketch_factors(factors):
    """``F V`` of every dense factor (a diagonal one as it is), in
    float32 whatever the factor is stored in."""
    def one(f):
        f = f.astype(jnp.float32)
        if f.ndim == 1:
            return f
        return jnp.matmul(f, _probe(f.shape[0]), precision=HIGHEST)
    return jax.tree.map(one, factors)


def leaf_arrays(tree) -> dict:
    """{path: array} of every leaf, on the host."""
    return {jax.tree_util.keystr(path): np.asarray(leaf) for path, leaf in
            jax.tree_util.tree_leaves_with_path(jax.device_get(tree))}


def _grad_matrix(layer, sub):
    if layer.kind == 'embedding':
        return sub['embedding']
    return jnp.concatenate([sub['kernel'].T, sub['bias'][:, None]], axis=1)


def _matrix_to_sub(layer, mat, sub):
    if layer.kind == 'embedding':
        return {**sub, 'embedding': mat}
    return {**sub, 'kernel': mat[:, :-1].T, 'bias': mat[:, -1]}


@functools.partial(jax.jit, static_argnames=('layers', 'hyper', 'rounding'),
                   donate_argnums=(0, 1, 3))
def precondition_and_apply(params, momentum, inverses, grads, *, layers,
                           hyper, rounding):
    """``G^-1 grad A^-1`` per layer, the KL clip over all of them, the
    clip by global norm, SGD with momentum. Returns the new parameters
    and momentum, and the gradient as SGD got it."""
    precond, vg = {}, jnp.zeros((), jnp.float32)
    for layer in layers:
        g = _grad_matrix(layer, get_path(grads, layer.path))
        inv = inverses[layer.name]
        if layer.kind == 'embedding':
            v = rounding.einsum('vd,de->ve', inv['A'][:, None] * g,
                                inv['G'])
        else:
            v = rounding.einsum(
                'oi,ij->oj', rounding.einsum('op,pi->oi', inv['G'], g),
                inv['A'])
        precond[layer.name] = v
        vg += jnp.sum(v * g) * hyper.lr ** 2
    nu = jnp.minimum(1.0, jnp.sqrt(hyper.kl_clip / (jnp.abs(vg) + 1e-30)))
    out = grads
    for layer in layers:
        sub = get_path(grads, layer.path)
        out = set_path(out, layer.path, _matrix_to_sub(
            layer, nu * precond[layer.name], sub))
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(out)))
    clip = jnp.minimum(1.0, hyper.grad_clip / jnp.maximum(norm, 1e-30))
    out = jax.tree.map(lambda x: x * clip, out)
    momentum = jax.tree.map(lambda m, g: g + hyper.momentum * m,
                            momentum, out)
    params = jax.tree.map(lambda p, m: p - hyper.lr * m, params, momentum)
    return params, momentum, out


def leaf_norms(tree) -> dict[str, float]:
    """{path: Frobenius norm} of every leaf, on the host."""
    flat = jax.tree_util.tree_leaves_with_path(tree)
    return {jax.tree_util.keystr(path): float(np.sqrt(np.sum(
        np.square(np.asarray(leaf, np.float64))))) for path, leaf in flat}


def diff_norms(new, old) -> dict[str, float]:
    return leaf_norms(jax.tree.map(
        lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
        new, old))


def follow(model_step, layers, hyper, params, batches, *,
           rounding=Rounding(), unchanged_state=False) -> dict:
    """Drive the reference through ``batches`` (the program's first
    steps) and return what the comparison reads: every step's loss, the
    per-leaf norm of the first gradient as SGD gets it, the per-leaf
    norm of the parameters' change after the last step, and the
    sketches of the factors then.

    ``model_step(params, batch, rounding) -> (loss, grads, stats)``.
    ``unchanged_state`` plants the fault of a step that returns the
    parameters and the optimizer's state as it got them."""
    start = jax.device_get(params)
    momentum = jax.tree.map(jnp.zeros_like, params)
    factors = init_factors(layers, params)
    inverses = None
    losses, first_grad = [], None
    for step, batch in enumerate(batches):
        loss, grads, stats = model_step(params, batch, rounding)
        losses.append(float(loss))
        if step % hyper.factor_freq == 0:
            factors = update_factors(factors, stats, hyper.factor_decay)
        del stats
        if step % hyper.inv_freq == 0:
            inverses = None  # freed before the new ones are made
            inverses = all_inverses(layers, factors, hyper.damping)
        params, momentum, fed = precondition_and_apply(
            params, momentum, inverses, grads, layers=layers,
            hyper=hyper, rounding=rounding)
        if first_grad is None:
            first_grad = leaf_norms(fed)
        del fed, grads
        if unchanged_state:
            params = jax.device_put(start)
            momentum = jax.tree.map(jnp.zeros_like, params)
    return {'losses': losses, 'grad1': first_grad,
            'dparam': diff_norms(params, start),
            'factors': leaf_arrays(sketch_factors(factors))}


# ---------------------------------------------------------------------------
# The comparison
# ---------------------------------------------------------------------------

#: A leaf whose first gradient in the reference is under this share of
#: the median leaf's moves by round-off alone: left out of the change.
DEAD_LEAF_SHARE = 1e-3


def leaf_gaps(got: dict, want: dict, keep=None) -> dict[str, float]:
    """Per leaf, the gap between its norm in ``got`` and in ``want``,
    against ``want``'s norm of that leaf or of its median leaf,
    whichever is larger. A leaf missing from ``got`` reads 1, a norm
    that is not a number reads infinity."""
    floor = statistics.median(want.values())
    gaps = {}
    for name, ref in want.items():
        if keep is None or name in keep:
            gap = abs(got.get(name, 0.0) - ref) / max(ref, floor, 1e-300)
            gaps[name] = gap if gap == gap else math.inf
    return gaps


def sketch_gaps(got: dict, want: dict) -> dict[str, float]:
    """Per factor, the norm of the difference of its two sketches
    against the norm of ``want``'s. A factor missing from ``got``, or
    of another shape, reads infinity."""
    gaps = {}
    for name, ref in want.items():
        mine = got.get(name)
        if mine is None or mine.shape != ref.shape:
            gaps[name] = math.inf
            continue
        gap = float(np.linalg.norm(mine.astype(np.float64) - ref)
                    / max(np.linalg.norm(ref), 1e-300))
        gaps[name] = gap if gap == gap else math.inf
    return gaps


def compare(got: dict, want: dict, limits: dict) -> dict:
    """Each number compared, beside its limit: every step's loss; for
    the first gradient and for the parameters' change the worst leaf's
    gap (``*_gap``) and the median leaf's (``*_median_gap``, steady
    from seed to seed where the worst is one small leaf's noise); the
    worst and the median stored factor's distance from the reference's
    (``factor_gap``, ``factor_median_gap``). ``got`` and
    ``want`` are what :func:`follow` returns (the program's side
    measured the same way). ``limits`` maps a number's name to its
    limit; a number with no limit (null) is reported and not held."""
    numbers = {}
    for i, (a, b) in enumerate(zip(got['losses'], want['losses']), 1):
        numbers[f'loss{i}_gap'] = (abs(a - b) / max(abs(b), 1e-300), '')
    if len(got['losses']) != len(want['losses']):
        numbers['loss_count_gap'] = (1.0, 'steps')
    floor = DEAD_LEAF_SHARE * statistics.median(want['grad1'].values())
    alive = {k for k, v in want['grad1'].items() if v >= floor}
    for name, gaps in (
            ('grad1', leaf_gaps(got['grad1'], want['grad1'])),
            ('dparam', leaf_gaps(got['dparam'], want['dparam'], alive))):
        worst = max(gaps, key=gaps.get)
        numbers[f'{name}_gap'] = (gaps[worst], worst)
        numbers[f'{name}_median_gap'] = (
            statistics.median(gaps.values()), '')
    gaps = sketch_gaps(got['factors'], want['factors'])
    worst = max(gaps, key=gaps.get)
    numbers['factor_gap'] = (gaps[worst], worst)
    numbers['factor_median_gap'] = (statistics.median(gaps.values()), '')
    checks = {}
    for name, (value, where) in numbers.items():
        limit = limits.get(name)
        if name == 'loss_count_gap':
            limit = 0.0
        ok = limit is None or (value == value and value <= limit)
        checks[name] = {'value': value, 'limit': limit, 'ok': bool(ok)}
        if where:
            checks[name]['at'] = where
    return checks
