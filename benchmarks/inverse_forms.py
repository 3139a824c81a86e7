"""The damped Cholesky inverse alone: today's whole-matrix route against
the forms by halves (PR 32).

``ops.linalg.get_inverse`` is what every firing's top device operations
run (``damped_inverse_stack`` vmaps it over a bucket's sub-stack or an
expert stack). This script runs it outside the step, at the sub-stack
shapes the two benchmark cells give it, in each form that met the chip:

  whole    XLA's ``cholesky`` and ``solve_triangular`` against the
           identity on the whole matrix, then ``X^T X`` (every dim
           before PR 32, and dims under ``INVERSE_HALVE_MIN_DIM``
           since);
  F1/<l>   the factor's inverse ``X`` by recursive halving down to
           leaves of at most ``l``, assembled, then ``X^T X`` whole;
  F2/<l>   XLA's ``cholesky`` on the whole matrix, only the triangular
           inverse by halves (``X21 = -X22 (L21 X11)``);
  F3/<l>   F1 with ``X^T X`` taken block by block at the top split, so
           the zero block of ``X`` is not multiplied there;
  kept/<l> F3 at every split: each level of the recursion hands up its
           own ``X^T X`` with its ``X`` (``ops.linalg._halved_inverse``
           with ``INVERSE_HALVE_LEAF = l``): what ``get_inverse`` runs
           from the gate up.

For each form and shape it prints one JSON line: ms a matrix (median of
``--repeats`` timings of ``--calls`` calls in flight), the residual
``max|M X - I|`` of the first matrix (``M = x + damping I``, product at
``Precision.HIGHEST``), the relative Frobenius distance to the whole
route's result, and the compiled program's ``memory_analysis()``
(generated code, temporaries) and ``cost_analysis()`` (flops, bytes
accessed). It sets the ``INVERSE_HALVE_*`` constants of
``ops/linalg.py``; ``kept`` marks the form they choose.

    chiprun --chips 1 --timeout 1800 -- python3 benchmarks/inverse_forms.py

Without a TPU it compiles every form for a described v5e (the
on-chip-measurement guide, section 2) and prints the compiler's numbers
with ``ms`` and the residual "not measured": nothing runs.

    python benchmarks/inverse_forms.py --shapes 8x2048 --forms whole kept/512
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys
import time

os.environ.setdefault('TPU_LOG_DIR', 'disabled')

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from distributed_kfac_pytorch_tpu.ops import linalg  # noqa: E402

# (matrices, dim): the sub-stacks one call of the firing program works
# on in the two cells (kanana's dense 2048 bucket as 2 x 13, its expert
# stacks of 8, gpt2s's 3072 and 3073 buckets as 2 x 6, kanana's three
# 6144 singles), and the dims under them that the gate has to place.
SHAPES = [(13, 2048), (8, 2048), (6, 3072), (6, 3073), (1, 6144),
          (8, 1536), (12, 769), (8, 768)]
FORMS = ['whole', 'F1/1024', 'F2/512', 'F3/1024', 'kept/512', 'kept/1024']
# Under 2048 dims only the kept recursion is asked whether it pays
# (where the gate goes).
SMALL_DIM_FORMS = ['whole', 'kept/512', 'kept/1024']
DAMPING = 0.003

_mm = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)


@contextlib.contextmanager
def halving(min_dim, leaf):
    """``ops.linalg``'s gate and leaf set for the traces made inside."""
    saved = linalg.INVERSE_HALVE_MIN_DIM, linalg.INVERSE_HALVE_LEAF
    linalg.INVERSE_HALVE_MIN_DIM, linalg.INVERSE_HALVE_LEAF = min_dim, leaf
    try:
        yield
    finally:
        linalg.INVERSE_HALVE_MIN_DIM, linalg.INVERSE_HALVE_LEAF = saved


def _lower_blocks(x11, x21, x22):
    """``[[x11, 0], [x21, x22]]``."""
    zeros = jnp.zeros((x11.shape[-1], x22.shape[-1]), x11.dtype)
    return jnp.block([[x11, zeros], [x21, x22]])


def _triangular_inverse_halved(chol, leaf):
    """``L^-1`` of a lower-triangular ``L`` by halves (F2)."""
    n = chol.shape[-1]
    if n <= leaf:
        return jax.scipy.linalg.solve_triangular(
            chol, jnp.eye(n, dtype=chol.dtype), lower=True)
    h = linalg._halving_point(n)
    x11 = _triangular_inverse_halved(chol[:h, :h], leaf)
    x22 = _triangular_inverse_halved(chol[h:, h:], leaf)
    return _lower_blocks(x11, -_mm(x22, _mm(chol[h:, :h], x11)), x22)


def _factor_inverse_blocks(x, leaf):
    """The blocks ``(X11, X21, X22)`` of ``L^-1`` for ``x = L L^T`` at
    ``x``'s split, each half by :func:`_factor_inverse_halved` (the
    recursion of ``linalg._halved_inverse`` without its inverse)."""
    h = linalg._halving_point(x.shape[-1])
    x11 = _factor_inverse_halved(x[:h, :h], leaf)
    l21 = _mm(x[h:, :h], x11.T)
    x22 = _factor_inverse_halved(x[h:, h:] - _mm(l21, l21.T), leaf)
    return x11, -_mm(x22, _mm(l21, x11)), x22


def _factor_inverse_halved(x, leaf):
    """``L^-1`` for ``x = L L^T`` by halves, assembled whole (F1, F3)."""
    if x.shape[-1] <= leaf:
        return linalg._whole_inverse(x)[0]
    return _lower_blocks(*_factor_inverse_blocks(x, leaf))


def _damped(x):
    x = x.astype(jnp.float32)
    return x + DAMPING * jnp.eye(x.shape[-1], dtype=x.dtype)


def form_fn(form):
    """The per-matrix function of ``form`` (traced under ``vmap``)."""
    kind, _, leaf = form.partition('/')
    leaf = int(leaf or 0)
    if kind != 'whole' and leaf < 2 * linalg.INVERSE_HALVE_ALIGN:
        # A dim over the leaf must have a lane-aligned half.
        raise SystemExit(f'{form}: a leaf under '
                         f'{2 * linalg.INVERSE_HALVE_ALIGN}')
    if kind == 'whole':
        def fn(x):
            with halving(1 << 62, linalg.INVERSE_HALVE_LEAF):
                return linalg.get_inverse(x, DAMPING)
    elif kind == 'kept':
        def fn(x):
            with halving(leaf + 1, leaf):
                return linalg.get_inverse(x, DAMPING)
    elif kind == 'F1':
        def fn(x):
            inv_l = _factor_inverse_halved(_damped(x), leaf)
            return inv_l.T @ inv_l
    elif kind == 'F2':
        def fn(x):
            inv_l = _triangular_inverse_halved(
                jnp.linalg.cholesky(_damped(x)), leaf)
            return inv_l.T @ inv_l
    elif kind == 'F3':
        def fn(x):
            x11, x21, x22 = _factor_inverse_blocks(_damped(x), leaf)
            low = x22.T @ x21
            return jnp.block([[x11.T @ x11 + x21.T @ x21, low.T],
                              [low, x22.T @ x22]])
    else:
        raise SystemExit(f'unknown form {form!r}')
    return fn


def kept_form(n):
    """The form ``ops/linalg.py``'s constants give a dim ``n``."""
    if not linalg.inverse_is_halved(n):
        return 'whole'
    return f'kept/{linalg.INVERSE_HALVE_LEAF}'


def make_stack(count, n, seed=0):
    """K-FAC-like factors: ``a^T a / rows`` of activations whose column
    scales are graded over three decades (so damping 0.003 matters)."""
    def one(key):
        a = jax.random.normal(key, (2 * n, n), jnp.float32)
        a = a * jnp.logspace(0.0, -3.0, n, dtype=jnp.float32)
        return _mm(a.T, a) / (2 * n)
    keys = jax.random.split(jax.random.PRNGKey(seed), count)
    return jax.lax.map(jax.jit(one), keys)


def compile_form(form, spec):
    """``form`` vmapped over ``spec``'s stack, compiled: the program,
    the seconds it took and the compiler's own numbers."""
    t0 = time.perf_counter()
    compiled = jax.jit(jax.vmap(form_fn(form))).lower(spec).compile()
    seconds = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return compiled, {
        'compile_s': round(seconds, 1),
        'code_mb': round(mem.generated_code_size_in_bytes / 1e6, 2),
        'temp_mb': round(mem.temp_size_in_bytes / 1e6, 2),
        'gflops': round(cost.get('flops', 0.0) / 1e9, 2),
        'gb_accessed': round(cost.get('bytes accessed', 0.0) / 1e9, 3),
    }


def described_v5e():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform='tpu',
                                        topology_name='v5e:2x2')
    return SingleDeviceSharding(topo.devices[0])


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument('--shapes', nargs='*', default=None,
                   help='COUNTxDIM ... in place of the cells\' shapes')
    p.add_argument('--forms', nargs='*', default=None,
                   help=f'default: {FORMS}, under 2048 dims '
                        f'{SMALL_DIM_FORMS}')
    p.add_argument('--calls', type=int, default=3)
    p.add_argument('--repeats', type=int, default=3)
    p.add_argument('--rehearse', action='store_true',
                   help='run on whatever device is there (its times are '
                        'that device\'s: a rehearsal of the script, '
                        'not a measurement)')
    p.add_argument('--out', default='chiprun_out/inverse_forms/forms.jsonl')
    args = p.parse_args(argv)
    shapes = SHAPES if args.shapes is None else [
        tuple(int(v) for v in s.split('x')) for s in args.shapes]
    device = jax.devices()[0]
    on_chip = device.platform == 'tpu' or args.rehearse
    sharding = None if on_chip else described_v5e()
    lines = []
    for count, n in shapes:
        stack = make_stack(count, n) if on_chip else None
        spec = jax.ShapeDtypeStruct((count, n, n), jnp.float32,
                                    sharding=sharding)
        whole = None
        for form in args.forms or (FORMS if n >= 2048
                                   else SMALL_DIM_FORMS):
            compiled, numbers = compile_form(form, spec)
            line = {'count': count, 'dim': n, 'form': form,
                    'kept': form == kept_form(n),
                    'device': (device.device_kind if on_chip
                               else 'described v5e, nothing ran'),
                    **numbers}
            if on_chip:
                out = jax.block_until_ready(compiled(stack))
                readings = []
                for _ in range(args.repeats):
                    t0 = time.perf_counter()
                    jax.block_until_ready(
                        [compiled(stack) for _ in range(args.calls)])
                    readings.append((time.perf_counter() - t0) * 1e3
                                    / (args.calls * count))
                first = out[0]
                eye = jnp.eye(n, dtype=jnp.float32)
                line['ms_a_matrix'] = round(
                    sorted(readings)[len(readings) // 2], 4)
                line['residual_max'] = float(
                    jnp.max(jnp.abs(_mm(_damped(stack[0]), first) - eye)))
                if form == 'whole':
                    whole = first
                if whole is not None:
                    line['vs_whole_fro'] = float(
                        jnp.linalg.norm(first - whole)
                        / jnp.linalg.norm(whole))
            else:
                line['ms_a_matrix'] = line['residual_max'] = 'not measured'
            lines.append(line)
            print(json.dumps(line), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, 'w') as f:
            f.writelines(json.dumps(line) + '\n' for line in lines)


if __name__ == '__main__':
    main()
