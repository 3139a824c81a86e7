#!/usr/bin/env python3
"""Stacked-expert products alone on the chip: XLA's ``ragged_dot``
against the shipped Pallas grouped kernels, tiling by tiling.

    chiprun --chips 1 -- python3 benchmarks/grouped_experts_bench.py

At the two MoE cells' buffers and expert widths (8 experts held, bf16):
``mellum2`` 32 768 rows, 2304 <-> 896; ``kanana2`` 49 152 rows,
2048 <-> 768; each matrix shape both ways (gate/up: d -> f, down:
f -> d). For each, at the expected fill (8 x the cell's rows an
expert), a skewed one (the same rows over four experts, empty experts,
groups starting inside a tile) and the buffer full:

- ``ragged_dot`` forward and forward + backward;
- each Pallas product alone (``fwd``: ``gmm``; ``dx``: ``gmm`` with
  the weight transposed; ``dw``: ``tgmm``) at every ``(tm, tk, tn)``
  tried, one row a tiling, with its share of the 197 TFLOP/s peak
  (2 x routed rows x k x n a product); a tiling Mosaic refuses is a row
  with its error;
- ``ops.pallas_kernels.grouped_matmul`` forward and forward + backward
  at the tiles ``grouped_tiling`` picks, and its largest relative
  error against ``ragged_dot`` on the routed rows (y, dx) and on dW,
  with NaN written into the rows past the last group.

Then one kanana MoE layer, forward + backward, on either route. Every
row goes to ``chiprun_out/grouped_experts/table.jsonl`` (``--no-sweep``:
the routes and the layer only, ``routes.jsonl``); standard output gets
the fastest tiling of each product and fill. It needs a
TPU: elsewhere both routes are something else.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental.pallas.ops.tpu.megablox.gmm import (  # noqa: E402
    gmm, tgmm)

from distributed_kfac_pytorch_tpu.models import mla_moe_lm  # noqa: E402
from distributed_kfac_pytorch_tpu.ops import pallas_kernels as P  # noqa: E402

HELD, PEAK = 8, 197e12
#: name: (buffer rows, d, f, routed rows an expert in the cell)
CELLS = {'mellum2': (32768, 2304, 896, 517),
         'kanana2': (49152, 2048, 768, 391)}
OUT = os.path.join('chiprun_out', 'grouped_experts')


def fills(rows: int, per_expert: int) -> dict:
    here = HELD * per_expert
    skew = [4, 2, 0, 0, 1, 0, 1, 0]
    skewed = [here * s // 8 for s in skew]
    skewed[0] += here - sum(skewed)
    return {'expected': [per_expert] * HELD, 'skewed': skewed,
            'full': [rows // HELD] * HELD}


def timed(fn, *args, reps=30) -> float:
    """ms a call, ``reps`` chained calls after one warm-up."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) * 1e3 / reps


def tilings(k: int, n: int, weight_grad: bool) -> list:
    """The picked tiles, the whole dims, and each picked dim cut once
    more, at row tiles of 128, 256 and 512."""
    _, tk, tn = P.grouped_tiling(k, n, weight_grad=weight_grad)
    pairs = [(tk, tn), (k, n)]
    for cut_k in (True, False):
        d = tk if cut_k else tn
        cut = P.lane_divisor_below(d)
        if cut and cut >= 256:
            pairs.append((cut, tn) if cut_k else (tk, cut))
    pairs = list(dict.fromkeys(pairs))
    return [(tm, a, b) for tm in (128, 256, 512) for a, b in pairs]


def products(k: int, n: int) -> dict:
    """name -> (weight_grad, k, n) of the kernel call :func:`product`
    makes."""
    return {'fwd': (False, k, n), 'dx': (False, n, k), 'dw': (True, k, n)}


@functools.partial(jax.jit, static_argnums=(0, 1))
def product(name, tiling, x, w, dy, gs):
    """One Pallas product alone at ``tiling``."""
    if name == 'fwd':
        return gmm(x, w, gs, x.dtype, tiling)
    if name == 'dx':
        return gmm(dy, w, gs, x.dtype, tiling, transpose_rhs=True)
    return tgmm(x.swapaxes(0, 1), dy, gs, w.dtype, tiling)


def _loss(route):
    return lambda x, w, gs: jnp.sum(route(x, w, gs).astype(jnp.float32))


#: route -> (forward, the loss's value with its gradients in x and w).
#: The value is returned too: a gradient of a sum alone needs no
#: forward, and XLA would drop its kernel.
ROUTES = {name: (jax.jit(fn), jax.jit(jax.value_and_grad(_loss(fn), (0, 1))))
          for name, fn in (('ragged_dot', jax.lax.ragged_dot),
                           ('pallas', P.grouped_matmul))}


@functools.partial(jax.jit, static_argnums=0)
def vjp(route, x, w, dy, gs):
    """``route``'s output and its gradients in x and w."""
    y, back = jax.vjp(lambda x, w: route(x, w, gs), x, w)
    return (y, *back(dy))


def rel(a, b, rows=None) -> float:
    return P.max_rel_error(np.asarray(a[:rows], np.float32),
                           np.asarray(b[:rows], np.float32))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    parser.add_argument('--no-sweep', dest='sweep', action='store_false',
                        help='time the two routes only, no tiling sweep')
    sweep = parser.parse_args(argv).sweep
    if jax.default_backend() != 'tpu':
        print('needs a TPU', file=sys.stderr)
        return 2
    os.makedirs(OUT, exist_ok=True)
    table = open(os.path.join(OUT, 'table.jsonl' if sweep
                              else 'routes.jsonl'), 'w')
    device = jax.devices()[0].device_kind

    def emit(row, show=False):
        row = {'device': device, **row}
        table.write(json.dumps(row) + '\n')
        table.flush()
        if show:
            print(json.dumps(row), flush=True)

    key = jax.random.PRNGKey(0)
    for cell, (rows, d, f, per_expert) in CELLS.items():
        for matrix, (k, n) in (('gate', (d, f)), ('down', (f, d))):
            kx, kw, ky = jax.random.split(key, 3)
            x = jax.random.normal(kx, (rows, k), jnp.bfloat16)
            w = (k ** -0.5 * jax.random.normal(kw, (HELD, k, n))).astype(
                jnp.bfloat16)
            dy = jax.random.normal(ky, (rows, n), jnp.bfloat16)
            base = {'cell': cell, 'matrix': matrix, 'k': k, 'n': n}
            sizes = {name: jnp.asarray(fill, jnp.int32)
                     for name, fill in fills(rows, per_expert).items()}
            for route, (fwd, both) in ROUTES.items():
                for fill, gs in sizes.items():
                    here = int(gs.sum())
                    flops = 2 * here * k * n
                    ms, ms2 = timed(fwd, x, w, gs), timed(both, x, w, gs)
                    emit({**base, 'route': route, 'fill': fill,
                          'rows': here, 'fwd_ms': ms, 'fwd_bwd_ms': ms2,
                          'fwd_peak_pct': 100 * flops / PEAK / ms * 1e3,
                          'fwd_bwd_peak_pct':
                              300 * flops / PEAK / ms2 * 1e3}, show=True)

            gs = sizes['expected']
            here = int(gs.sum())
            tail = (jnp.arange(rows) < here)[:, None]
            want = vjp(jax.lax.ragged_dot, jnp.where(tail, x, 0), w,
                       jnp.where(tail, dy, 0), gs)
            got = vjp(P.grouped_matmul, jnp.where(tail, x, jnp.nan), w,
                      jnp.where(tail, dy, jnp.nan), gs)
            emit({**base, 'check': 'pallas_vs_ragged_dot_nan_tail',
                  'rows': here, 'y_rel': rel(got[0], want[0], here),
                  'dx_rel': rel(got[1], want[1], here),
                  'dw_rel': rel(got[2], want[2]),
                  'dw_finite': bool(jnp.isfinite(got[2]).all())},
                 show=True)

            if not sweep:
                continue
            for name, (wg, kk, nn) in products(k, n).items():
                best = {}
                for tiling in tilings(kk, nn, wg):
                    row = {**base, 'product': name, 'tiling': tiling,
                           'picked': tiling == P.grouped_tiling(
                               kk, nn, weight_grad=wg)}
                    try:
                        call = product.lower(name, tiling, x, w, dy,
                                             gs).compile()
                    except Exception as e:  # Mosaic refuses the tiles
                        emit({**row, 'refused': str(e).splitlines()[0]})
                        continue
                    for fill, gs_f in sizes.items():
                        here = int(gs_f.sum())
                        ms = timed(call, x, w, dy, gs_f)
                        pct = 100 * 2 * here * k * n / PEAK / ms * 1e3
                        emit({**row, 'fill': fill, 'rows': here, 'ms': ms,
                              'peak_pct': pct})
                        if fill not in best or ms < best[fill]['ms']:
                            best[fill] = {'tiling': tiling, 'ms': ms,
                                          'peak_pct': pct}
                print(json.dumps({**base, 'product': name, 'best': best}),
                      flush=True)

    layer = mla_moe_lm.MoE(experts_held=(0, HELD),
                           routed_scaling_factor=2.448, dtype=jnp.bfloat16)
    h = jax.random.normal(key, (8, 1024, 2048), jnp.bfloat16)
    params = jax.jit(layer.init)(key, h)

    def layer_step():
        return jax.jit(jax.grad(lambda params, h: jnp.sum(
            layer.apply(params, h).astype(jnp.float32) ** 2), (0, 1)))

    emit({'cell': 'kanana2', 'route': 'pallas',
          'moe_layer_fwd_bwd_ms': timed(layer_step(), params, h)}, show=True)
    # A trace of its own with the gate shut: the layer on ragged_dot.
    applies = P.grouped_matmul_applies
    P.grouped_matmul_applies = lambda x, kernel: False
    try:
        emit({'cell': 'kanana2', 'route': 'ragged_dot',
              'moe_layer_fwd_bwd_ms': timed(layer_step(), params, h)},
             show=True)
    finally:
        P.grouped_matmul_applies = applies
    table.close()
    return 0


if __name__ == '__main__':
    sys.exit(main())
