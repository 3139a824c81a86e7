"""Microbenchmark: K-FAC step time across factor-inversion methods.

Times the full jitted K-FAC + SGD training step on ResNet-32/CIFAR-10 at
the reference CIFAR cadence (factors every iter, inverses every 10 —
reference torch_cifar10_resnet.py:68-71) for each ``inverse_method``:

  - eigen:      the default eigen path (eigh_method='auto': warm-start
                matmul-only basis polish, ops.linalg.eigh_polish)
  - eigen-xla:  bucketed vmapped backend eigh every firing (the
                reference-style cold decomposition; data-dependent
                runtime on TPU, PERF.md §6)
  - cholesky:   damped Cholesky inverse (reference --use-inv-kfac)
  - newton:     matmul-only Newton-Schulz (Pallas VMEM-resident on TPU)

(For the plain-SGD floor / overhead ratio, see bench.py.) Run on the
target chip:
    python benchmarks/inverse_methods.py [--batch-size 128] [--iters 50]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import optax

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

from distributed_kfac_pytorch_tpu import KFAC
from distributed_kfac_pytorch_tpu.models import cifar_resnet


def build_kfac_step(model, x, y, method):
    inverse_method, _, eigh = method.partition('-')
    kfac = KFAC(model, factor_update_freq=1, inv_update_freq=10,
                damping=0.003, lr=0.1, inverse_method=inverse_method,
                eigh_method=eigh or 'auto')
    variables, kstate = kfac.init(jax.random.PRNGKey(0), x)
    params = variables['params']
    extra = {k: v for k, v in variables.items() if k != 'params'}
    tx = optax.sgd(0.1, momentum=0.9)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, kstate, extra):
        loss, _, grads, captures, updated = kfac.capture.loss_and_grads(
            lambda out: optax.softmax_cross_entropy_with_integer_labels(
                out, y).mean(),
            params, x, extra_vars=extra, mutable_cols=('batch_stats',))
        precond, kstate = kfac.step(kstate, grads, captures)
        updates, opt_state = tx.update(precond, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, kstate, {**extra, **updated}, loss

    return step, (params, opt_state, kstate, extra)


def time_step(step, state, iters, warmup=12):
    for _ in range(warmup):
        *state, loss = step(*state)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        *state, loss = step(*state)
    jax.block_until_ready(loss)
    return (time.perf_counter() - t0) / iters * 1000


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument('--batch-size', type=int, default=128)
    p.add_argument('--iters', type=int, default=50)
    p.add_argument('--model', default='resnet32')
    args = p.parse_args(argv)

    model = cifar_resnet.get_model(args.model)
    # Random data, never constants: constant inputs degenerate batchnorm
    # (zero variance -> NaNs), poisoning the measurement.
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (args.batch_size, 32, 32, 3))
    y = jax.random.randint(jax.random.PRNGKey(2), (args.batch_size,),
                           0, 10)

    results = {}
    for method in ('eigen', 'eigen-xla', 'cholesky', 'newton'):
        step, state = build_kfac_step(model, x, y, method)
        results[method] = round(time_step(step, state, args.iters), 3)
    print(json.dumps({'model': args.model, 'batch': args.batch_size,
                      'unit': 'ms/iter', **results}))


if __name__ == '__main__':
    main()
