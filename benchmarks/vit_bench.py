"""Measured ViT bench: Vision Transformer under encoder-attention K-FAC.

BEYOND the reference: it has no working attention workload (its LM
example ships broken — ``torch_language_model.py:253,277`` — and its
registry has no attention-bearing kinds: Linear/Conv2d/Embedding/
LSTMCell only, ``kfac/layers/__init__.py:13-36``). Here every ViT
weight layer is
preconditioned — the stride-P patch-embed conv plus the 6 encoder
Denses per block (``models/vit.py``) — and this bench records what
that costs on a real chip.

Cumulative phases (depthwise_bench methodology — scanned loop, chained
carries, median-of-repeats):

  sgd       plain SGD step (fwd+bwd+momentum)
  precond   + capture + preconditioning with frozen inverses + KL clip
  factors   + factor EWMA every iter
  full      + amortized inverse firing every ``inv_freq`` iters

MFU note: the reported ``mfu`` fields count registered-layer matmul
FLOPs only (``bench.model_flops_per_step``) — the attention
QK^T/AV einsums are excluded, so MFU is an underestimate (at S=197,
D=384 the attention terms are ~2*S/(12*D) ~ 9% of the projection
FLOPs).

    python benchmarks/vit_bench.py [--size small] [--batch 64]
        [--image 224] [--out VIT_r05.json]
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import optax

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import bench as B  # noqa: E402  (repo root: the timing methodology)
from distributed_kfac_pytorch_tpu import KFAC
from distributed_kfac_pytorch_tpu.capture import extra_vars_of
from distributed_kfac_pytorch_tpu.models import vit
from distributed_kfac_pytorch_tpu.utils import enable_compilation_cache


def build(kfac, variables, kstate, model, x, y, inv_freq, n_iters, mode):
    params = variables['params']
    extra = extra_vars_of(variables)
    tx = optax.sgd(0.1, momentum=0.9)
    opt_state = tx.init(params)

    def loss(out):
        return B.loss_fn(out, y)

    def make_body(factor_update, inv_update):
        def body(carry, _):
            params, opt_state, kstate, extra = carry
            loss_v, _, grads, captures, _ = (
                kfac.capture.loss_and_grads(
                    loss, params, x, extra_vars=extra,
                    intercept=factor_update))
            g, kstate2 = kfac.step(kstate, grads, captures,
                                   factor_update=factor_update,
                                   inv_update=inv_update)
            updates, opt_state = tx.update(g, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state, kstate2, extra), loss_v
        return body

    if mode == 'sgd':
        def sgd_body(carry, _):
            params, opt_state, extra = carry

            def wrapped(p):
                return loss(model.apply({'params': p, **extra}, x))
            l, grads = jax.value_and_grad(wrapped)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state, extra), l

        @jax.jit
        def run(carry):
            carry, losses = jax.lax.scan(sgd_body, carry, None,
                                         length=n_iters)
            return carry, losses[-1]
        return run, (params, opt_state, extra)

    if mode == 'precond':
        # Static-cadence non-factor step: capture-free (intercept=False),
        # preconditioning through the frozen inverses — the production
        # gated path (PERF.md round 4).
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

    # Donated carry on a fresh device copy (depthwise_bench rationale:
    # legs share one process, so donating the originals would delete
    # them for the next leg).
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
    p.add_argument('--image', type=int, default=224)
    p.add_argument('--size', default='small',
                   choices=['cifar', 'tiny', 'small', 'base'])
    p.add_argument('--model-dtype', default='bf16',
                   choices=['fp32', 'bf16'])
    p.add_argument('--bf16-factors', action='store_true')
    p.add_argument('--out', default='VIT_r05.json')
    args = p.parse_args(argv)
    enable_compilation_cache()

    on_tpu = jax.default_backend() == 'tpu'
    if not on_tpu:  # CPU shake-out config
        args.batch, args.image, args.size = 4, 32, 'cifar'
    dt = jnp.bfloat16 if args.model_dtype == 'bf16' else jnp.float32
    model = vit.get_model(1000, args.size, dtype=dt)
    if args.image % model.patch_size:
        raise SystemExit(f'--image {args.image} not divisible by '
                         f'patch {model.patch_size}')
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (args.batch, args.image, args.image, 3))
    y = jax.random.randint(jax.random.PRNGKey(2), (args.batch,), 0, 1000)
    inv_freq = 10
    n_iters = (args.iters // inv_freq) * inv_freq or inv_freq

    kfac = KFAC(model, factor_update_freq=1, inv_update_freq=inv_freq,
                damping=0.003, lr=0.1,
                factor_dtype=jnp.bfloat16 if args.bf16_factors else None)
    variables, kstate = kfac.init(jax.random.PRNGKey(0), x)
    floor_ms = B.flops_floor_ms(kfac, variables, x, y) if on_tpu else 0.0
    flops = B.model_flops_per_step(
        kfac, variables['params'], x, y, extra_vars_of(variables),
        mutable_cols=())
    peak = B.detected_tpu_peak() if on_tpu else None

    rows, mfu = {}, {}
    for mode in ('sgd', 'precond', 'factors', 'full'):
        run, carry = build(kfac, variables, kstate, model, x, y,
                           inv_freq, n_iters, mode)
        ms = B.time_chained(run, carry, n_iters, floor_ms=floor_ms,
                            leg=mode)
        rows[mode] = round(ms, 2)
        if peak:
            mfu[mode] = round(flops / (ms / 1e3) / peak, 4)
        print(json.dumps({'phase': mode, 'ms_per_iter': rows[mode]}),
              flush=True)

    # Composed production cadence (factors/50, inverses/500): base =
    # the gated capture-free step; the factor premium paid 1-in-50 and
    # the firing premium (read off the full leg's amortization) 1-in-500.
    factor_extra = rows['factors'] - rows['precond']
    firing_extra_per_iter = rows['full'] - rows['factors']  # at /10
    production = (rows['precond'] + factor_extra / 50
                  + firing_extra_per_iter * inv_freq / 500)
    out = {
        # Patch size from the model config, not a hardcoded 16: the
        # patch-4 'cifar' config used to mislabel as vit_cifar16_32px.
        'workload': f'vit_{args.size}{model.patch_size}_{args.image}px_'
                    f'b{args.batch}_{args.model_dtype}',
        'backend': jax.default_backend(),
        'n_registered_layers': len(kfac.specs),
        'unit': 'ms/iter',
        'phases': rows,
        'mfu_registered_layer_flops': mfu,
        'deltas': {
            'precond_gated_cost': round(rows['precond'] - rows['sgd'], 2),
            'factor_capture_cost': round(factor_extra, 2),
            'inverse_amortized_cost_at_10': round(firing_extra_per_iter,
                                                  2),
        },
        'vs_sgd': {
            'every_iter_factors': round(rows['factors'] / rows['sgd'], 3),
            'cifar_cadence_full': round(rows['full'] / rows['sgd'], 3),
            'production_f50_i500': round(production / rows['sgd'], 3),
        },
        'note': 'encoder-attention workload the reference has no '
                'working analogue of; mfu counts registered-layer '
                'matmuls only (attention einsums excluded — see '
                'module docstring)'
                + ('' if on_tpu else
                   '; NOT-TPU CAVEAT: measured on the CPU shake-out '
                   'config (batch 4, 32px, cifar size) — relative '
                   'phase structure only, no MFU, not comparable to '
                   'the v5e flagship rows'),
    }
    with open(args.out, 'w') as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)


if __name__ == '__main__':
    main()
