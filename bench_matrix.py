"""Per-config benchmark matrix over BASELINE.md's tracked configs.

Emits one JSON line per measured config (plus a final summary line), so
every tracked config has a *recorded number* rather than prose:

  1. resnet32_cifar10        — full K-FAC+SGD step, eigen/cholesky/
                               newton/eigen-xla (bench.py's config,
                               broken out per method)
  2. resnet18_imagenet       — steady state as ONE program
                               (--imagenet-model resnet50 for the
                               flagship; benchmarks/flagship_resnet50.py
                               measures it per phase)
  3. hybrid_sweep            — HYBRID grad_worker_fraction step times
                               across KAISA placements; needs more
                               than one chip and is skipped on one
  4. transformer_lm          — Linear-layer K-FAC over a decoder-only
                               Transformer, on-chip step time
  5. resnet32_bf16_factors   — bf16 factor storage+compute vs fp32, and
                               strict-fp32 covariance, on-chip

Methodology per bench.py: the iteration loop runs inside one compiled
program (lax.scan blocks of [inverse step, inv_freq-1 plain steps]);
timed calls chain the carry. Like bench.py it exits non-zero without a
TPU, and every row says where it ran (platform, device_kind, count).

    python bench_matrix.py [--configs 1 3 5] [--iters 30]
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from distributed_kfac_pytorch_tpu.utils import enable_compilation_cache

import numpy as np
import optax

# Single source of truth for the chained-carry timing methodology and
# the FLOPs-floor sanity gate (see bench.py).
from bench import device_fields, flops_floor_ms, require_tpu, time_chained


def emit(obj):
    print(json.dumps({**obj, **device_fields()}), flush=True)


def rounded_iters(n_iters, inv_freq):
    """Largest multiple of inv_freq <= n_iters (>= inv_freq).

    The scanned program executes whole [inverse step, inv_freq-1 plain
    steps] blocks; timing must divide by the step count actually run
    (bench.py asserts the same invariant)."""
    return max(inv_freq, (n_iters // inv_freq) * inv_freq)


def scan_block_runner(make_body_pair, carry, inv_freq, n_iters):
    """Jitted [inv step, inv_freq-1 plain steps] x (n_iters/inv_freq).
    ``n_iters`` must be a multiple of ``inv_freq`` (see rounded_iters)."""
    assert n_iters % inv_freq == 0, (n_iters, inv_freq)
    inv_body, plain_body = make_body_pair

    def block(c, _):
        c, l0 = inv_body(c, None)
        if inv_freq > 1:
            c, ls = jax.lax.scan(plain_body, c, None, length=inv_freq - 1)
            return c, ls[-1]
        return c, l0

    @jax.jit
    def run(c):
        c, losses = jax.lax.scan(block, c, None,
                                 length=n_iters // inv_freq)
        return c, losses[-1]

    return run


def build_cnn_bodies(model, x, y, kfac_kwargs, inv_freq, floor=None):
    """``floor=None`` computes the FLOPs floor (shape-only; identical
    across a kfac_kwargs sweep, so sweeps pass the first label's floor
    back in to skip the redundant eval_shape traces)."""
    from distributed_kfac_pytorch_tpu import KFAC

    kfac = KFAC(model, factor_update_freq=1, inv_update_freq=inv_freq,
                damping=0.003, lr=0.1, **kfac_kwargs)
    variables, kstate = kfac.init(jax.random.PRNGKey(0), x)
    params = variables['params']
    extra = {k: v for k, v in variables.items() if k != 'params'}
    tx = optax.sgd(0.1, momentum=0.9)
    opt_state = tx.init(params)

    def loss_fn(out):
        return optax.softmax_cross_entropy_with_integer_labels(
            out, y).mean()

    def make_body(inv_update):
        def body(carry, _):
            params, opt_state, kstate, extra = carry
            loss, _, grads, captures, updated = (
                kfac.capture.loss_and_grads(
                    loss_fn, params, x, extra_vars=extra,
                    mutable_cols=('batch_stats',)))
            precond, kstate = kfac.step(kstate, grads, captures,
                                        factor_update=True,
                                        inv_update=inv_update)
            updates, opt_state = tx.update(precond, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state, kstate, {**extra, **updated}), loss
        return body

    if floor is None:
        floor = flops_floor_ms(kfac, variables, x, y,
                               mutable_cols=('batch_stats',))
    return ((make_body(True), make_body(False)),
            (params, opt_state, kstate, extra), floor)


def config1_cifar_methods(args):
    from distributed_kfac_pytorch_tpu.models import cifar_resnet

    model = cifar_resnet.get_model('resnet32')
    x = jax.random.normal(jax.random.PRNGKey(1), (512, 32, 32, 3))
    y = jax.random.randint(jax.random.PRNGKey(2), (512,), 0, 10)
    out = {}
    n = rounded_iters(args.iters, 10)
    floor = None
    for label, kw in (('eigen', {}),
                      ('eigen-xla', {'eigh_method': 'xla'}),
                      ('cholesky', {'inverse_method': 'cholesky'}),
                      ('newton', {'inverse_method': 'newton'}),
                      # Opt-in within-step factor thinning (the factor
                      # phase is the dominant K-FAC overhead at CIFAR
                      # scale and is HBM-bound in the batch dim —
                      # PERF.md roofline). Default stays 1.0 (parity).
                      ('frac0.25', {'factor_batch_fraction': 0.25})):
        bodies, carry, floor = build_cnn_bodies(model, x, y, kw,
                                                inv_freq=10, floor=floor)
        run = scan_block_runner(bodies, carry, 10, n)
        out[label] = round(time_chained(run, carry, n, floor_ms=floor,
                                        leg=label), 2)
    emit({'config': 1, 'workload': 'resnet32_cifar10_b512_invfreq10',
          'unit': 'ms/iter', **out})


def config2_imagenet(args):
    from distributed_kfac_pytorch_tpu.models import imagenet_resnet

    model = imagenet_resnet.get_model(args.imagenet_model)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 176, 176, 3))
    y = jax.random.randint(jax.random.PRNGKey(2), (64,), 0, 1000)
    # Measured at a STRESS cadence (factors every iter, inverses/10) —
    # far more K-FAC-intensive than the ImageNet default (factors/10,
    # inverses/100, reference torch_imagenet_resnet.py:75-78), so the
    # recorded number upper-bounds the production overhead.
    n = rounded_iters(args.iters, 10)
    bodies, carry, floor = build_cnn_bodies(model, x, y, {}, inv_freq=10)
    run = scan_block_runner(bodies, carry, 10, n)
    ms = time_chained(run, carry, n, floor_ms=floor, leg='imagenet')
    emit({'config': 2,
          'workload': f'{args.imagenet_model}_imagenet176_b64'
                      '_stress_cadence_f1_inv10',
          'unit': 'ms/iter',
          'eigen': round(ms, 2)})


def config3_hybrid_sweep(args):
    from distributed_kfac_pytorch_tpu import CommMethod, KFAC
    from distributed_kfac_pytorch_tpu.models import cifar_resnet
    from distributed_kfac_pytorch_tpu.parallel import distributed as D

    model = cifar_resnet.get_model('resnet20')
    x0 = jnp.zeros((2, 32, 32, 3))
    rng = np.random.default_rng(0)
    xb = rng.normal(size=(128, 32, 32, 3)).astype(np.float32)
    yb = rng.integers(0, 10, 128).astype(np.int32)
    out = {}
    for label, cm, frac in (('comm_opt', CommMethod.COMM_OPT, 1.0),
                            ('hybrid_0.5', CommMethod.HYBRID_OPT, 0.5),
                            ('hybrid_0.25', CommMethod.HYBRID_OPT, 0.25),
                            ('mem_opt', CommMethod.MEM_OPT, 0.0)):
        kfac = KFAC(model, factor_update_freq=1, inv_update_freq=2,
                    damping=0.003, lr=0.1, comm_method=cm,
                    grad_worker_fraction=frac)
        variables, _ = kfac.init(jax.random.PRNGKey(0), x0)
        params = variables['params']
        extra = {'batch_stats': variables['batch_stats']}
        mesh = D.make_kfac_mesh(comm_method=cm,
                                grad_worker_fraction=frac)
        dkfac = D.DistributedKFAC(kfac, mesh, params)
        kstate = dkfac.init_state(params)
        tx = optax.sgd(0.1, momentum=0.9)
        opt_state = tx.init(params)
        step = dkfac.build_train_step(
            lambda out, b: optax.softmax_cross_entropy_with_integer_labels(
                out, b[1]).mean(),
            tx, mutable_cols=('batch_stats',), donate=False)
        hyper = {'lr': 0.1, 'damping': 0.003}
        state = (jax.tree.map(jnp.asarray, params), opt_state, kstate,
                 extra)

        def one_pass(state, n):
            p, o, k, e = state
            for i in range(n):
                p, o, k, e, m = step(p, o, k, e, (xb, yb), hyper,
                                     factor_update=True,
                                     inv_update=(i % 2 == 0))
            jax.block_until_ready(m['loss'])
            return (p, o, k, e)

        state = one_pass(state, 4)  # compile both variants + warm
        t0 = time.perf_counter()
        state = one_pass(state, args.sweep_iters)
        out[label] = round((time.perf_counter() - t0)
                           / args.sweep_iters * 1000.0, 2)
    emit({'config': 3,
          'workload': 'resnet20_cifar_b128_invfreq2',
          'note': 'step times across KAISA placements on this mesh '
                  '(per-step dispatch included)',
          'unit': 'ms/iter', **out})


def config4_transformer_lm(args):
    from distributed_kfac_pytorch_tpu import KFAC
    from distributed_kfac_pytorch_tpu.models import transformer_lm

    model = transformer_lm.TransformerLM(
        vocab_size=4096, d_model=512, num_layers=4, num_heads=8,
        max_len=256, dropout=0.0)
    ids = jax.random.randint(jax.random.PRNGKey(1), (16, 256), 0, 4096)
    tgt = jax.random.randint(jax.random.PRNGKey(2), (16, 256), 0, 4096)

    kfac = KFAC(model, factor_update_freq=1, inv_update_freq=10,
                damping=0.003, lr=0.1)
    variables, kstate = kfac.init(jax.random.PRNGKey(0), ids)
    params = variables['params']
    tx = optax.sgd(0.1, momentum=0.9)
    opt_state = tx.init(params)

    def loss_fn(out):
        logits = out[0] if isinstance(out, tuple) else out
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, tgt).mean()

    def make_body(inv_update, factor_update=True):
        def body(carry, _):
            params, opt_state, kstate = carry
            loss, _, grads, captures, _ = kfac.capture.loss_and_grads(
                loss_fn, params, ids, intercept=factor_update)
            precond, kstate = kfac.step(kstate, grads, captures,
                                        factor_update=factor_update,
                                        inv_update=inv_update)
            updates, opt_state = tx.update(precond, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state, kstate), loss
        return body

    carry = (params, opt_state, kstate)
    n = rounded_iters(args.iters, 10)
    run = scan_block_runner((make_body(True), make_body(False)), carry,
                            10, n)
    floor = flops_floor_ms(kfac, variables, ids, tgt, loss=loss_fn)
    ms = time_chained(run, carry, n, floor_ms=floor, leg='transformer')
    # Gated non-factor step (production cadences run this on (1-1/f) of
    # steps): plain autodiff, no capture machinery.
    @jax.jit
    def run_nf(c):
        c, losses = jax.lax.scan(make_body(False, factor_update=False),
                                 c, None, length=n)
        return c, losses[-1]
    ms_nf = time_chained(run_nf, carry, n, floor_ms=floor,
                         leg='transformer_nofactor')
    emit({'config': 4,
          'workload': 'transformer_lm_d512_L4_seq256_b16_invfreq10',
          'unit': 'ms/iter',
          'eigen': round(ms, 2), 'nofactor_step': round(ms_nf, 2)})

    # KAISA precondition-compute sharding, measured (round 4; VERDICT
    # r3 ask #4): one chip cannot run a 4-row mesh, so emulate each
    # path's PER-DEVICE matmul work with the single-chip pipeline —
    # the replicate-and-mask path preconditions every layer on every
    # device; the row-sharded path 1/n_rows of them (layer_filter is
    # exactly that subset selector). The delta is the per-device FLOP
    # saving the sharded path realizes on this config's d512/vocab-dim
    # grad matrices.
    n_rows = 4
    names = list(kfac.specs)
    quarter = names[:max(1, len(names) // n_rows)]
    _, _, grads0, captures0, _ = jax.jit(
        lambda p: kfac.capture.loss_and_grads(loss_fn, p, ids))(params)
    kstate_f = {**kstate,
                'inverses': jax.jit(kfac.update_inverses)(kstate, 0.003)}

    def precond_body(layer_filter):
        def body(g, _):
            v = kfac.precondition(kstate_f, g, 0.003, 0.1,
                                  layer_filter=layer_filter)
            leaf = jax.tree.leaves(v)[0]
            probe = leaf.reshape(-1)[0]
            g = jax.tree.map(lambda t: t * (1.0 + 1e-6 * probe), g)
            return g, probe
        return body

    out = {}
    for label, filt in (('all_layers', None), ('quarter', quarter)):
        @jax.jit
        def run(g, _filt=filt, _label=label):
            g, probes = jax.lax.scan(precond_body(_filt), g, None,
                                     length=args.iters)
            return g, probes[-1]
        out[label] = round(time_chained(run, grads0, args.iters,
                                        leg=f'precond_{label}'), 3)
    emit({'config': 4, 'study': 'kaisa_precond_compute_sharding',
          'n_rows_emulated': n_rows,
          'n_layers': len(names), 'quarter_layers': len(quarter),
          'per_device_precond_all_layers_ms': out['all_layers'],
          'per_device_precond_quarter_ms': out['quarter'],
          'saving_per_device_ms_per_iter': round(
              out['all_layers'] - out['quarter'], 3)})


def config5_bf16_factors(args):
    from distributed_kfac_pytorch_tpu.models import cifar_resnet

    model = cifar_resnet.get_model('resnet32')
    x = jax.random.normal(jax.random.PRNGKey(1), (512, 32, 32, 3))
    y = jax.random.randint(jax.random.PRNGKey(2), (512,), 0, 10)
    out = {}
    floor = None
    for label, kw in (
            ('fp32_default', {}),
            ('bf16_factors', {'factor_dtype': jnp.bfloat16,
                              'factor_compute_dtype': jnp.bfloat16}),
            ('fp32_strict', {'factor_compute_dtype': jnp.float32})):
        bodies, carry, floor = build_cnn_bodies(model, x, y, kw,
                                                inv_freq=10, floor=floor)
        n = rounded_iters(args.iters, 10)
        run = scan_block_runner(bodies, carry, 10, n)
        out[label] = round(time_chained(run, carry, n, floor_ms=floor,
                                        leg=label), 2)
    emit({'config': 5,
          'workload': 'resnet32_cifar10_b512_factor_dtype_sweep',
          'unit': 'ms/iter', **out})


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument('--configs', type=int, nargs='+',
                   default=[1, 2, 3, 4, 5])
    p.add_argument('--iters', type=int, default=30)
    p.add_argument('--sweep-iters', type=int, default=20)
    p.add_argument('--imagenet-model', default='resnet18',
                   help='resnet18 compiles in a fraction of the time; '
                        'resnet50 is the flagship')
    args = p.parse_args(argv)

    enable_compilation_cache()
    require_tpu('bench_matrix.py')

    runners = {1: config1_cifar_methods, 2: config2_imagenet,
               3: config3_hybrid_sweep, 4: config4_transformer_lm,
               5: config5_bf16_factors}
    ran = []
    for c in args.configs:
        if c == 3 and jax.device_count() == 1:
            emit({'config': 3, 'skipped':
                  'HYBRID sweep needs more than one chip'})
            continue
        runners[c](args)
        ran.append(c)
    emit({'summary': 'done', 'configs': ran})


if __name__ == '__main__':
    main()
