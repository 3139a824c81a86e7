"""The fused attention kernel alone against the plain path, on the chip.

One attention layer of the benchmark's cell (``gpt2s_f1i10``: B 8,
T 1024, H 12, D 64, bfloat16, causal), forward and forward + backward,
through ``parallel.sequence.plain_attention`` and through
``ops.pallas_kernels.fused_attention`` over a handful of tile sizes.
The table this prints is where ``_attention_block_sizes`` comes from
(PERF.md, PR 26). One process; needs a TPU (a CPU timing of a Pallas
interpreter says nothing).

    python benchmarks/fused_attention_bench.py [--batch 8] [--seq 1024]
        [--heads 12] [--head-dim 64] [--iters 30] [--out PATH.json]

Timing: ``iters`` chained calls (each call's gradients perturb the next
q, so no two calls see the same input) as one window closed by a host
fetch.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument('--batch', type=int, default=8)
    ap.add_argument('--seq', type=int, default=1024)
    ap.add_argument('--heads', type=int, default=12)
    ap.add_argument('--head-dim', type=int, default=64)
    ap.add_argument('--iters', type=int, default=30)
    ap.add_argument('--out', default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from distributed_kfac_pytorch_tpu.ops import pallas_kernels
    from distributed_kfac_pytorch_tpu.parallel import sequence

    if jax.default_backend() != 'tpu':
        print('fused_attention_bench: no TPU, nothing measured',
              file=sys.stderr)
        return 1
    b, t, h, d = args.batch, args.seq, args.heads, args.head_dim
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, w = (jax.random.normal(key, (b, t, h, d), jnp.bfloat16)
                  for key in keys)
    # Matmul FLOPs of dense attention: QK^T and PV forward; forward +
    # backward is three times that. Causality needs half of them.
    dense = 4 * b * h * t * t * d

    def timed(attend, backward: bool) -> float:
        def loss(q, k, v):
            return jnp.sum(attend(q, k, v) * w.astype(jnp.float32))

        if backward:
            # All three gradients reach the result: a gradient the
            # caller drops takes its kernel (dK/dV) out of the program.
            def call(q):
                dq, dk, dv = jax.grad(loss, (0, 1, 2))(q, k, v)
                return q + 1e-3 * (dq + dk + dv).astype(q.dtype)
        else:
            def call(q):
                return q + (1e-3 * attend(q, k, v)).astype(q.dtype)
        call = jax.jit(call)
        x = call(q)
        float(jnp.sum(x.astype(jnp.float32)))         # compiled, ran
        t0 = time.perf_counter()
        for _ in range(args.iters):
            x = call(x)
        float(jnp.sum(x.astype(jnp.float32)))
        return (time.perf_counter() - t0) / args.iters * 1e3

    def plain(q, k, v):
        return sequence.plain_attention(q, k, v, causal=True)

    def fused(sizes):
        return lambda q, k, v: pallas_kernels.fused_attention(
            q, k, v, causal=True, block_sizes=sizes)

    tiles = [(s, s, s) for s in (128, 256, 512, 1024) if t % s == 0]
    if t % 1024 == 0:
        tiles += [(512, 1024, 512), (1024, 512, 512), (256, 1024, 512),
                  (1024, 1024, 512), (512, 512, 256)]
    rows = []
    for name, attend in [('plain', plain)] + [
            ('fused q%d kmajor%d k%d' % tile,
             fused(pallas_kernels.attention_block_sizes(*tile)))
            for tile in tiles]:
        row = {'path': name}
        try:
            row['fwd_ms'] = timed(attend, backward=False)
            row['fwd_bwd_ms'] = timed(attend, backward=True)
            row['dense_equiv_tflops_fwd_bwd'] = (
                3 * dense / row['fwd_bwd_ms'] / 1e9)
        except Exception as e:  # a tile Mosaic refuses is a table row
            row['error'] = f'{type(e).__name__}: {str(e)[:300]}'
        rows.append(row)
        print(json.dumps(row), flush=True)
    result = {'device': jax.devices()[0].device_kind,
              'shape': {'batch': b, 'seq': t, 'heads': h, 'head_dim': d,
                        'dtype': 'bfloat16', 'causal': True},
              'dense_matmul_flops_fwd': dense, 'rows': rows}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, 'w') as f:
            json.dump(result, f, indent=1)
    return 0


if __name__ == '__main__':
    sys.exit(main())
