#!/usr/bin/env python3
"""The splash kernels alone under five masks, on the chip: what a tile
of the block-diffusion mask costs beside a causal or windowed one.

    python3 benchmarks/splash_mask_bench.py  # one TPU chip

At the block-diffusion cell's attention (``sdar_d4_bd4_f1i10``: 1 row,
T = 4096 = two streams of 2048, 32 q heads over 4 kv heads of 128,
bfloat16, blocks of 4, tiles of 512 in all three passes), the kernels
``ops.pallas_kernels._splash_kernel`` builds, under

- ``divided``: the block-diffusion mask computed in the kernel from
  ``block_diffusion_keep`` (two floor divisions by the block an
  element);
- ``shifted``: the same mask computed in the kernel without a
  division (``block_diffusion_keep_shifted``: ``q & -B`` is q's block
  start where B is a power of two), what ``_block_diffusion_mask``
  hands the kernels;
- ``stored``: the same mask as a plain ``Mask`` (measured, not
  shipped): splash keeps its distinct partial tiles as arrays and each
  pass loads the one a tile needs;
- ``causal`` and ``local``: JAX's ``CausalMask`` and ``LocalMask`` of
  a 1024 window, the windowed cell's masks, as the floor.

For each: forward, and forward + dQ + dK/dV (every gradient returned,
or XLA drops its kernel), as ``--iters`` chained calls closed by one
host fetch, best of ``--repeats`` windows; the tiles the kernels visit
(a head, times the heads), and the forward + backward time a tile visit
in µs. Then whether ``shifted`` and ``stored`` give the output and
dQ/dK/dV of ``divided`` bit for bit, from one seed's inputs. One JSON
line a mask on standard output, the whole table to ``--out``
(``chiprun_out/splash_mask/table.json``).

Without a TPU, ``--rehearse`` runs the same rows on Pallas's TPU
interpreter at a toy shape (``--seq 1024 --heads 4 --kv-heads 2``
sensible): the equality check holds there too, the times say nothing.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402
from jax.experimental.pallas.ops.tpu.splash_attention import (  # noqa: E402
    splash_attention_mask as masks,
)

from distributed_kfac_pytorch_tpu.ops import pallas_kernels as P  # noqa: E402

MASKS = ('divided', 'shifted', 'stored', 'causal', 'local')
OUT = os.path.join('chiprun_out', 'splash_mask', 'table.json')


class _Computed(masks._ComputableMask):
    def __init__(self, t, keep):
        super().__init__(shape=(t, t), mask_function=keep)

    __eq__, __hash__ = object.__eq__, object.__hash__


class _Stored(masks.Mask):
    """The block-diffusion rule as a plain ``Mask``, read tile by tile
    on the host (measured, not shipped)."""

    def __init__(self, t, block):
        self.t, self.block = t, block

    @property
    def shape(self):
        return (self.t, self.t)

    def __getitem__(self, idx):
        rows, cols = (np.arange(self.t)[i] for i in idx)
        return P.block_diffusion_keep(rows[:, None], cols[None, :],
                                      self.block, self.t // 2)

    __eq__, __hash__ = object.__eq__, object.__hash__


def build_mask(name: str, t: int, block: int, window: int):
    if name == 'divided':
        return _Computed(t, functools.partial(
            P.block_diffusion_keep, block=block, length=t // 2))
    if name == 'shifted':
        return _Computed(t, functools.partial(
            P.block_diffusion_keep_shifted, block=block, length=t // 2))
    if name == 'stored':
        return _Stored(t, block)
    if name == 'causal':
        return masks.CausalMask((t, t))
    return masks.LocalMask((t, t), window_size=(window - 1, 0), offset=0)


def make_kernel(mask, heads: int, t: int):
    """``_splash_kernel``'s kernel, under any mask."""
    kernels = P._splash_kernels()
    s = P._splash_tile(t)
    sizes = kernels.BlockSizes(block_q=s, block_kv=s, block_kv_compute=s,
                               block_q_dkv=s, block_kv_dkv=s,
                               block_kv_dkv_compute=s, block_q_dq=s,
                               block_kv_dq=s)
    with jax.ensure_compile_time_eval():
        return kernels.make_splash_mha(masks.MultiHeadMask([mask] * heads),
                                       block_sizes=sizes, head_shards=1,
                                       q_seq_shards=1)


def tiles_visited(mask, t: int) -> int:
    s = P._splash_tile(t)
    return sum(bool(mask[lo:lo + s, col:col + s].any())
               for lo in range(0, t, s) for col in range(0, t, s))


def timed(fn, x, iters: int, repeats: int) -> float:
    """ms a call: ``iters`` chained calls, best of ``repeats`` windows."""
    x = fn(x)
    float(jnp.sum(x.astype(jnp.float32)))             # compiled, ran
    best = float('inf')
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            x = fn(x)
        float(jnp.sum(x.astype(jnp.float32)))
        best = min(best, (time.perf_counter() - t0) / iters * 1e3)
    return best


def measure(name: str, mask, qkvw, iters: int, repeats: int):
    """One mask's row (build time, tiles visited, forward and forward +
    backward ms, µs a tile visit) and its output and dQ/dK/dV as numpy
    arrays (None where Mosaic refuses the mask: the row says why)."""
    q, k, v, w = qkvw
    heads, t = q.shape[1], q.shape[2]
    t0 = time.perf_counter()
    kernel = jax.vmap(make_kernel(mask, heads, t))
    row = {'mask': name, 'kernel_build_s': time.perf_counter() - t0,
           'tiles_visited': tiles_visited(mask, t) * heads}

    def loss(q, k, v):
        out = kernel(q, k, v)
        return jnp.sum(out.astype(jnp.float32) * w), out

    grad = jax.grad(loss, (0, 1, 2), has_aux=True)

    def fwd(x):
        return x + (1e-3 * kernel(x, k, v)).astype(x.dtype)

    def fwd_bwd(x):
        (dq, dk, dv), _ = grad(x, k, v)
        return x + (1e-3 * (dq + jnp.sum(dk + dv))).astype(x.dtype)

    try:
        row['fwd_ms'] = timed(jax.jit(fwd), q, iters, repeats)
        row['fwd_bwd_ms'] = timed(jax.jit(fwd_bwd), q, iters, repeats)
        row['us_per_tile_visit'] = (row['fwd_bwd_ms'] * 1e3
                                    / row['tiles_visited'])
        (dq, dk, dv), out = jax.jit(grad)(q, k, v)
    except Exception as e:  # a mask Mosaic refuses is a row
        row['error'] = f'{type(e).__name__}: {str(e)[:300]}'
        return row, None
    return row, [np.asarray(a) for a in (out, dq, dk, dv)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument('--seq', type=int, default=4096)
    ap.add_argument('--heads', type=int, default=32)
    ap.add_argument('--kv-heads', type=int, default=4)
    ap.add_argument('--head-dim', type=int, default=128)
    ap.add_argument('--block', type=int, default=4)
    ap.add_argument('--window', type=int, default=1024)
    ap.add_argument('--iters', type=int, default=20)
    ap.add_argument('--repeats', type=int, default=3)
    ap.add_argument('--seed', type=int, default=0)
    ap.add_argument('--masks', default=','.join(MASKS))
    ap.add_argument('--rehearse', action='store_true')
    ap.add_argument('--out', default=OUT)
    args = ap.parse_args(argv)

    on_chip = jax.default_backend() == 'tpu'
    if not (on_chip or args.rehearse):
        print('splash_mask_bench: no TPU, nothing measured (--rehearse '
              'runs the interpreter)', file=sys.stderr)
        return 1
    mode = (contextlib.nullcontext if on_chip
            else pltpu.force_tpu_interpret_mode)
    t, h, hk, d = args.seq, args.heads, args.kv_heads, args.head_dim
    iters, repeats = (args.iters, args.repeats) if on_chip else (1, 1)
    rng = np.random.default_rng(args.seed)
    q, k, v, w = (jnp.asarray(rng.normal(size=(1, heads, t, d)),
                              jnp.bfloat16)
                  for heads in (h, hk, hk, h))
    q = (q.astype(jnp.float32) * (1.0 / d ** 0.5)).astype(jnp.bfloat16)

    rows, results = [], {}
    for name in args.masks.split(','):
        with mode():
            row, arrays = measure(name, build_mask(name, t, args.block,
                                                   args.window),
                                  (q, k, v, w), iters, repeats)
        if arrays is not None:
            results[name] = arrays
        rows.append(row)
        print(json.dumps(row), flush=True)

    equal = {}
    for name in ('shifted', 'stored'):
        if name in results and 'divided' in results:
            equal[name] = [bool(np.array_equal(a, b)) for a, b in zip(
                results[name], results['divided'])]
    print(json.dumps({'bitwise_equal_to_divided (out, dq, dk, dv)': equal}),
          flush=True)
    table = {'device': jax.devices()[0].device_kind,
             'shape': {'seq': t, 'heads': h, 'kv_heads': hk,
                       'head_dim': d, 'block': args.block,
                       'window': args.window, 'dtype': 'bfloat16',
                       'tile': P._splash_tile(t)},
             'rows': rows, 'bitwise_equal_to_divided': equal}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, 'w') as f:
        json.dump(table, f, indent=1)
    return 0


if __name__ == '__main__':
    sys.exit(main())
