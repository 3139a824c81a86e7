#!/usr/bin/env python3
"""Stacked-expert products alone on the chip: does their time follow
the routed rows or the buffer?

    chiprun --chips 1 -- python3 benchmarks/grouped_experts_bench.py

At the kanana-2 cell's shape (a buffer of 8192 x 6 rows, 8 experts held,
2048 -> 768): ``ragged_dot`` forward and forward + backward, and the
per-expert covariance (``ops.factors``), each at the expected fill
(8 x 384 rows), at a skewed one and with the buffer full; then one whole
MoE layer, forward + backward. Prints one JSON line per row and what
the product leaves in the rows past the last group. It needs a TPU:
elsewhere ``ragged_dot`` is a dense expansion and says nothing.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from distributed_kfac_pytorch_tpu.models import mla_moe_lm  # noqa: E402
from distributed_kfac_pytorch_tpu.ops import factors as F  # noqa: E402

ROWS, D, WIDTH, HELD = 8192 * 6, 2048, 768, 8
FILLS = {'expected': [384] * HELD,
         'skewed': [2048, 1024, 0, 0, 0, 0, 0, 0],
         'full': [ROWS // HELD] * HELD}


def timed(fn, *args, reps=20) -> float:
    """ms a call, ``reps`` chained calls after one warm-up."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) * 1e3 / reps


def main() -> int:
    if jax.default_backend() != 'tpu':
        print('needs a TPU', file=sys.stderr)
        return 2
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (ROWS, D), jnp.bfloat16)
    w = 0.02 * jax.random.normal(key, (HELD, D, WIDTH), jnp.bfloat16)

    fwd = jax.jit(jax.lax.ragged_dot)

    def loss(x, w, gs):
        return jnp.sum(jax.lax.ragged_dot(x, w, gs).astype(jnp.float32))
    both = jax.jit(jax.grad(loss, argnums=(0, 1)))
    cov = jax.jit(lambda x, gs: F.experts_a_factor(x, gs, 6))
    for name, fill in FILLS.items():
        gs = jnp.asarray(fill, jnp.int32)
        rows = int(sum(fill))
        print(json.dumps({
            'fill': name, 'rows': rows,
            'fwd_ms': timed(fwd, x, w, gs),
            'fwd_bwd_ms': timed(both, x, w, gs),
            'cov_2048_ms': timed(cov, x, gs),
            'fwd_ms_at_peak': 2 * rows * D * WIDTH / 197e12 * 1e3,
            'cov_ms_at_peak': 2 * rows * D * D / 197e12 * 1e3}),
            flush=True)
    gs = jnp.asarray(FILLS['expected'], jnp.int32)
    tail = np.asarray(fwd(x, w, gs)[int(gs.sum()):].astype(jnp.float32))
    print(json.dumps({'tail_rows_nonzero': int(np.count_nonzero(tail)),
                      'tail_rows_finite': bool(np.isfinite(tail).all())}))

    layer = mla_moe_lm.MoE(experts_held=(0, HELD),
                           routed_scaling_factor=2.448, dtype=jnp.bfloat16)
    h = jax.random.normal(key, (8, 1024, D), jnp.bfloat16)
    params = jax.jit(layer.init)(key, h)

    def layer_loss(params, h):
        return jnp.sum(layer.apply(params, h).astype(jnp.float32) ** 2)
    print(json.dumps({'moe_layer_fwd_bwd_ms': timed(
        jax.jit(jax.grad(layer_loss, argnums=(0, 1))), params, h)}))
    return 0


if __name__ == '__main__':
    sys.exit(main())
