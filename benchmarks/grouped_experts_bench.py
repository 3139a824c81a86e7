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
the fastest tiling of each product and fill.

``--covariances`` times the per-expert covariances instead
(``ops.factors``' statistics, ``sum_r x_r x_r^T`` an expert) at each MoE
cell's buffer, both widths and routed rows, at the same three fills:
``ragged_dot_general`` against the Pallas ``tgmm(x^T, x)`` at the
tiling ``grouped_tiling`` picks and at the other tilings of lane-multiple
divisors whose tiles fit the budget, one row a tiling, with its share
of the 197 TFLOP/s peak (2 x routed rows x d^2 a stack), its largest
relative error against ``ragged_dot_general`` (NaN written into the
Pallas operand's rows past the last group) and ``max|C - C^T|``; then
the whole statistic, ``ops.factors._experts_cov``, on either route (the
XLA one symmetrizes). Rows go to ``covariances.jsonl`` beside the
table. It needs a TPU: elsewhere both routes are something else.
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
from distributed_kfac_pytorch_tpu.ops import factors as F  # noqa: E402
from distributed_kfac_pytorch_tpu.ops import pallas_kernels as P  # noqa: E402

HELD, PEAK = 8, 197e12
#: name: (buffer rows, d, f, routed rows an expert in the cell)
CELLS = {'mellum2': (32768, 2304, 896, 517),
         'kanana2': (49152, 2048, 768, 391)}
#: name: (buffer rows, (d, f), routed rows an expert in the cell (its
#: ``moe_rows_per_expert``), tilings swept). sdar has kanana's widths:
#: its rows take the picked tiling only.
COV_CELLS = {'mellum2': (32768, (2304, 896), 508, True),
             'kanana2': (49152, (2048, 768), 391, True),
             'sdar': (32768, (2048, 768), 243, False)}
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


def cov_tilings(d: int) -> list:
    """The picked tiling first, then the ``(tm, tk, tn)`` of lane-multiple
    divisors of ``d`` from 256 whose tiles fit the budget and cover at
    least half the largest such output tile, at row tiles of 256 and
    512."""
    picked = P.grouped_tiling(d, d, weight_grad=True, out_itemsize=4)
    cuts = [t for t in range(d, 255, -P._LANE) if d % t == 0]

    def fits(tm, a, b):
        return P._grouped_tile_bytes(tm, a, b, 2, True,
                                     4) <= P.GROUPED_TILE_BUDGET

    pairs = [(a, b) for a in cuts for b in cuts
             if fits(P.GROUPED_ROW_TILE, a, b)]
    largest = max((a * b for a, b in pairs), default=0)
    found = [(tm, a, b) for tm in (256, 512) for a, b in pairs
             if 2 * a * b >= largest and fits(tm, a, b)]
    return [picked] + [t for t in found if t != picked]


@functools.partial(jax.jit, static_argnums=0)
def cov_kernel(tiling, x, gs):
    """The Pallas covariance alone at ``tiling``, no symmetrization."""
    return tgmm(x.swapaxes(0, 1), x, gs, jnp.float32, tiling)


@jax.jit
def cov_ragged(x, gs):
    """XLA's grouped contraction alone, no symmetrization."""
    return jax.lax.ragged_dot_general(
        x, x, gs, F._RAGGED_ROWS, preferred_element_type=jnp.float32)


@jax.jit
def cov_check(got, want, gs):
    """On the device: the largest relative error against ``want`` (NaN
    if ``got`` is not finite), ``max|C - C^T|``, and whether every empty
    expert's block is zero."""
    err = jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))
    err = jnp.where(jnp.isfinite(got).all(), err, jnp.nan)
    empty_zero = jnp.all((gs > 0) | ~jnp.any(got != 0, axis=(1, 2)))
    return err, jnp.max(jnp.abs(got - got.swapaxes(1, 2))), empty_zero


def statistic_routes() -> dict:
    """``ops.factors._experts_cov`` on each route, the XLA one traced
    with the gate shut."""
    applies = P.experts_cov_applies

    def shut(x, gs):
        P.experts_cov_applies = lambda x: False
        try:
            return F._experts_cov(x, gs)
        finally:
            P.experts_cov_applies = applies
    return {'pallas': jax.jit(F._experts_cov), 'ragged_dot': jax.jit(shut)}


def covariances(emit, sweep: bool) -> None:
    """The per-expert covariances alone, both routes, tiling by tiling
    (``sweep``; else the picked tiling only); then the whole statistic
    on either route."""
    key = jax.random.PRNGKey(1)
    routes = statistic_routes()
    for cell, (rows, dims, per_expert, swept) in COV_CELLS.items():
        for d in dims:
            key, kx = jax.random.split(key)
            x = jax.random.normal(kx, (rows, d), jnp.bfloat16)
            base = {'cell': cell, 'd': d, 'buffer_rows': rows}
            sizes = {name: jnp.asarray(fill, jnp.int32)
                     for name, fill in fills(rows, per_expert).items()}
            picked = P.grouped_tiling(d, d, weight_grad=True,
                                      out_itemsize=4)

            def emit_timed(row, fn, *args, show=False):
                for fill, gs in sizes.items():
                    here = int(gs.sum())
                    ms = timed(fn, *args, gs)
                    emit({**base, **row, 'fill': fill, 'rows': here,
                          'ms': ms, 'peak_pct':
                              100 * 2 * here * d * d / PEAK / ms * 1e3},
                         show=show)

            emit_timed({'route': 'ragged_dot'}, cov_ragged, x, show=True)
            for tiling in (cov_tilings(d) if sweep and swept
                           else [picked]):
                row = {'route': 'pallas', 'tiling': tiling,
                       'picked': tiling == picked}
                try:
                    call = cov_kernel.lower(tiling, x, sizes['full']
                                            ).compile()
                except Exception as e:  # Mosaic refuses the tiles
                    emit({**base, **row, 'refused': str(e).splitlines()[0]})
                    continue
                emit_timed(row, call, x, show=tiling == picked)
                for fill, gs in sizes.items():
                    here = int(gs.sum())
                    tail = (jnp.arange(rows) < here)[:, None]
                    err, asym, empty_zero = cov_check(
                        call(jnp.where(tail, x, jnp.nan), gs),
                        cov_ragged(jnp.where(tail, x, 0), gs), gs)
                    emit({**base, **row, 'fill': fill, 'check': 'nan_tail',
                          'rel': float(err),
                          'max_abs_c_minus_ct': float(asym),
                          'empty_blocks_zero': bool(empty_zero)},
                         show=tiling == picked)
            for route, fn in routes.items():
                emit_timed({'route': route, 'statistic': True}, fn, x,
                           show=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    parser.add_argument('--no-sweep', dest='sweep', action='store_false',
                        help='time the two routes only, no tiling sweep')
    parser.add_argument('--covariances', action='store_true',
                        help='time the per-expert covariances instead')
    args = parser.parse_args(argv)
    sweep = args.sweep
    if jax.default_backend() != 'tpu':
        print('needs a TPU', file=sys.stderr)
        return 2
    os.makedirs(OUT, exist_ok=True)
    name = ('covariances.jsonl' if args.covariances
            else 'table.jsonl' if sweep else 'routes.jsonl')
    table = open(os.path.join(OUT, name), 'w')
    device = jax.devices()[0].device_kind

    def emit(row, show=False):
        row = {'device': device, **row}
        table.write(json.dumps(row) + '\n')
        table.flush()
        if show:
            print(json.dumps(row), flush=True)

    if args.covariances:
        covariances(emit, sweep)
        table.close()
        return 0
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
