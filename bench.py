"""Benchmark: K-FAC training-step time on tracked config 1.

Measures steady-state wall-clock per iteration of the full K-FAC + SGD
training step (forward, backward with capture, factor EWMA, amortized
eigendecompositions, preconditioning, KL clip, SGD update) on
ResNet-32 / CIFAR-10 at the reference's default CIFAR cadence (factors
every iter, inverses every 10 — torch_cifar10_resnet.py:68-71), the most
K-FAC-intensive tracked config in BASELINE.md.

Prints ONE JSON line:
  {"metric": ..., "value": <ms/iter>, "unit": "ms/iter", "vs_baseline": R}

The reference repo publishes no wall-clock numbers (BASELINE.md), so
``vs_baseline`` reports the K-FAC overhead ratio ``kfac_ms / sgd_ms``
against a plain-SGD step of the same model on the same chip — the
reference papers' own headline framing (K-FAC at small overhead over SGD);
lower is better, 1.0 means free preconditioning.

It measures the device or nothing: without a TPU it exits non-zero, a
``device_kind`` missing from ``TPU_BF16_PEAK`` is an error, and the row
carries ``platform``, ``device_kind`` and the device count.

Measurement methodology:
  - the iteration loop runs INSIDE the program (``lax.scan``), so a
    timing call is one device program and per-step host dispatch stays
    out of the ratio;
  - the inverse cadence is STATIC program structure (blocks of one
    inverse-updating step followed by ``inv_freq - 1`` plain steps) —
    the measured-on-v5e fast path (see KFAC.step on why on-device
    ``lax.cond`` gating is pathological on TPU);
  - timed calls CHAIN the carry returned by the previous call, so no two
    calls see identical inputs;
  - every leg is timed as whole batches of chained calls closed by a
    host fetch, the reported value is the median over attempt batches,
    and every batch average is validated against a 100%-MFU FLOPs floor
    computed from hand-counted model FLOPs (PERF.md rounds 1-5 record a
    physically impossible 0.052 ms/iter reading that chaining alone did
    not prevent); if no batch passes the floor the bench exits non-zero
    instead of printing a garbage ratio.

FLOPs accounting: XLA's ``cost_analysis`` counts a ``lax.scan`` body ONCE
regardless of trip count, which made round 2's ``model_tflops_per_step``
~n_iters× too small. Model FLOPs are now hand-counted analytically from
the registered layer shapes (conv/dense matmul FLOPs, fwd + both backward
contractions); BN/residual elementwise work is excluded, so reported MFU
is a slight *underestimate*.
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import optax

from distributed_kfac_pytorch_tpu import KFAC
from distributed_kfac_pytorch_tpu.utils import enable_compilation_cache

enable_compilation_cache()  # persistent compile cache (KFAC_COMPILE_CACHE=0 disables)
from distributed_kfac_pytorch_tpu.models import cifar_resnet


# Per-generation bf16 peak FLOP/s — the FLOPs-floor and MFU denominator
# shared by every bench in this repo (bench_matrix / benchmarks import
# from here).
TPU_BF16_PEAK = {
    'v4': 275e12,
    'v5e': 197e12,
    'v5p': 459e12,
    'v6e': 918e12,
}
V5E_BF16_PEAK = TPU_BF16_PEAK['v5e']

# device_kind spellings that don't contain the canonical generation tag
# (ADVICE r3: some stacks report v5e as 'TPU v5 lite', silently dropping
# MFU fields). Checked before the substring scan.
TPU_KIND_ALIASES = {
    'v5 lite': 'v5e',
    'v5litepod': 'v5e',
    'v5lite': 'v5e',
    'v6 lite': 'v6e',
}


def extract_failure_line(stderr: str, limit: int = 200) -> str:
    """Best failure line from a dead subprocess's stderr, ANSI-stripped.

    The LAST stderr line is often JAX's traceback-filter note ("For
    simplicity, JAX has removed its internal frames..."), so scan
    backwards for the line naming the actual failure (OOM probes must
    read as OOM in recorded artifacts). Shared by the subprocess-leg
    benchmarks (flagship_lm, ring_attention_bench) so their failure-row
    heuristics cannot drift.
    """
    import re
    clean = lambda s: re.sub(  # noqa: E731  (no control chars in rows)
        r'\x1b\[[0-9;]*m', '', s).strip()[-limit:]
    lines = (stderr or '').strip().splitlines()
    for line in reversed(lines):
        if ('RESOURCE_EXHAUSTED' in line or 'Error' in line
                or 'error' in line):
            return clean(line)
    return clean(lines[-1]) if lines else ''


def detected_tpu_peak() -> float:
    """bf16 peak FLOP/s of the chip this process runs on: the MFU
    denominator and the FLOPs floor. A ``device_kind`` the table does
    not know is an error, not a default — a guessed peak makes every
    utilization printed under it wrong."""
    kind = jax.devices()[0].device_kind.lower()
    gen = next((v for k, v in TPU_KIND_ALIASES.items() if k in kind), None)
    gen = gen or next((g for g in TPU_BF16_PEAK if g in kind), None)
    if gen is None:
        raise ValueError(
            f'unknown TPU device_kind {kind!r}: add its bf16 peak to '
            'TPU_BF16_PEAK / TPU_KIND_ALIASES (bench.py) with its source')
    return TPU_BF16_PEAK[gen]


def device_fields() -> dict:
    """What every printed row says about where it was measured."""
    dev = jax.devices()[0]
    return {'platform': dev.platform, 'device_kind': dev.device_kind,
            'device_count': jax.device_count()}


def require_tpu(script: str) -> None:
    """Exit non-zero unless JAX's default backend is a TPU: a time taken
    on another backend is not a measurement of this system."""
    if jax.default_backend() != 'tpu':
        sys.exit(f'{script}: no TPU (jax.default_backend() == '
                 f'{jax.default_backend()!r}); refusing to time '
                 'another backend under a device metric')


def flops_floor_ms(kfac, variables, x, y, loss=None, mutable_cols=()):
    """100%-MFU per-iter floor in ms for time_chained's sanity gate.
    Single home for the formula — bench_matrix and benchmarks/ import
    it from here."""
    params = variables['params']
    extra = {k: v for k, v in variables.items() if k != 'params'}
    flops = model_flops_per_step(kfac, params, x, y, extra, loss=loss,
                                 mutable_cols=mutable_cols)
    return flops / detected_tpu_peak() * 1e3


def loss_fn(out, labels):
    return optax.softmax_cross_entropy_with_integer_labels(
        out, labels).mean()


def build_runners(model, x, y, factor_freq, inv_freq, n_iters):
    """(kfac, variables, kfac_run, kfac_carry0, sgd_run, sgd_carry0).

    ``kfac``/``variables`` are returned so callers can count FLOPs
    without a second model construction + device init.
    """
    assert factor_freq == 1, 'tracked config 1 updates factors every iter'
    assert n_iters % inv_freq == 0
    kfac = KFAC(model, factor_update_freq=factor_freq,
                inv_update_freq=inv_freq, damping=0.003, lr=0.1)
    variables, kstate = kfac.init(jax.random.PRNGKey(0), x)
    params = variables['params']
    extra = {k: v for k, v in variables.items() if k != 'params'}
    tx = optax.sgd(0.1, momentum=0.9)
    opt_state = tx.init(params)

    def make_body(inv_update):
        def body(carry, _):
            params, opt_state, kstate, extra = carry
            loss, _, grads, captures, updated = kfac.capture.loss_and_grads(
                lambda out: loss_fn(out, y), params, x,
                extra_vars=extra, mutable_cols=('batch_stats',))
            precond, kstate = kfac.step(kstate, grads, captures,
                                        factor_update=True,
                                        inv_update=inv_update)
            updates, opt_state = tx.update(precond, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state, kstate, {**extra, **updated}), loss
        return body

    inv_body, plain_body = make_body(True), make_body(False)

    def block(carry, _):
        carry, loss0 = inv_body(carry, None)
        carry, losses = jax.lax.scan(plain_body, carry, None,
                                     length=inv_freq - 1)
        return carry, (losses[-1] if inv_freq > 1 else loss0)

    @jax.jit
    def kfac_run(carry):
        carry, losses = jax.lax.scan(block, carry, None,
                                     length=n_iters // inv_freq)
        return carry, losses[-1]

    def sgd_body(carry, _):
        params, opt_state, extra = carry

        def wrapped(params):
            out, updated = model.apply(
                {'params': params, **extra}, x,
                mutable=['batch_stats'])
            return loss_fn(out, y), updated
        (loss, updated), grads = jax.value_and_grad(
            wrapped, has_aux=True)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return (params, opt_state, {**extra, **updated}), loss

    @jax.jit
    def sgd_run(carry):
        carry, losses = jax.lax.scan(sgd_body, carry, None, length=n_iters)
        return carry, losses[-1]

    return (kfac, variables, kfac_run, (params, opt_state, kstate, extra),
            sgd_run, (params, opt_state, extra))


def model_flops_per_step(kfac, params, x, y, extra, loss=None,
                         mutable_cols=('batch_stats',)):
    """Hand-counted model-math FLOPs for one train step (fwd + bwd).

    Counts the matmul/conv FLOPs of every K-FAC-registered layer from
    its capture shapes (``jax.eval_shape`` — no device work):

      conv2d:  fwd = 2 * B*OH*OW * KH*KW*Cin * Cout   (from g's shape)
      linear:  fwd = 2 * rows * Din * Dout

    Backward costs two contractions of the same size as the forward
    (dL/dx and dL/dW), so fwd+bwd = 3x fwd. Elementwise work (BN,
    residual adds, activations) is excluded — a few % on ResNets — so
    MFU computed from this is a slight underestimate. This replaces the
    compiler ``cost_analysis`` numbers, which count scan bodies once
    regardless of trip count (round-2 verdict Weak #4).
    """
    if loss is None:
        loss = lambda out: loss_fn(out, y)
    _, _, _, captures_sh, _ = jax.eval_shape(
        lambda p, e: kfac.capture.loss_and_grads(
            loss, p, x, extra_vars=e, mutable_cols=mutable_cols),
        params, extra)
    total = 0
    for name, spec in kfac.specs.items():
        for a_s, g_s in zip(captures_sh[name]['a'],
                            captures_sh[name]['g']):
            a_sh, g_sh = a_s.shape, g_s.shape
            if spec.kind == 'conv2d':
                kh, kw = spec.kernel_size
                cin, cout = a_sh[-1], g_sh[-1]
                rows = 1
                for d in g_sh[:-1]:
                    rows *= d  # B * OH * OW
                total += 2 * rows * kh * kw * cin * cout
            elif spec.kind == 'linear':
                rows = 1
                for d in a_sh[:-1]:
                    rows *= d
                total += 2 * rows * a_sh[-1] * g_sh[-1]
            # embedding: a gather, no matmul FLOPs
    return 3 * total


def time_chained(run, carry, n_iters, repeats=5, floor_ms=0.0,
                 max_attempts=3, leg=''):
    """Per-iter time: median over ``max_attempts`` batch averages, where
    each batch is ``repeats`` chained calls timed as one window.

    ``floor_ms`` is a physical lower bound (100%-MFU FLOPs floor): a
    batch average below it is evidence of a cached/elided execution
    (the round-2 0.052 ms/iter artifact) and is discarded. Raises
    RuntimeError if every batch is below the floor — a loud failure
    beats a garbage vs_baseline ratio in the recorded artifact.
    """
    def timed_batch(carry):
        """``repeats`` chained calls timed as ONE window, closed by a
        host fetch of the last loss scalar.

        Timing the batch keeps legitimate dispatch/execute pipelining
        inside the window (a real training loop pipelines the same
        way) while the final ``float(loss)`` is a hard data dependency
        on the last scan iteration of the last call — deferred
        execution cannot escape the timed window. One fetch amortized
        over ``repeats * n_iters`` is noise.
        """
        t0 = time.perf_counter()
        for _ in range(repeats):
            carry, loss = run(carry)
        float(loss)  # device -> host: closes the window
        jax.block_until_ready(carry)
        dt = time.perf_counter() - t0
        return carry, dt / (repeats * n_iters) * 1000.0

    carry, loss = jax.block_until_ready(run(carry))  # compile + warm
    float(loss)
    readings = []
    for _ in range(max_attempts):
        carry, per_iter = timed_batch(carry)
        if per_iter >= floor_ms:
            readings.append(per_iter)
    if readings:
        return sorted(readings)[len(readings) // 2]
    raise RuntimeError(
        f'bench leg {leg!r}: every batch reading fell below the '
        f'physical FLOPs floor of {floor_ms:.3f} ms/iter after '
        f'{max_attempts} attempts — cached/elided execution suspected; '
        'refusing to record a garbage measurement')


def main():
    require_tpu('bench.py')
    # Tracked config 1 (BASELINE.md): ResNet-32 / CIFAR-10 K-FAC at the
    # reference CIFAR cadence (factors every iter, inverses every 10 —
    # torch_cifar10_resnet.py:68-71). Global batch 512 keeps the MXU fed
    # on one chip; compile stays in tens of seconds.
    model = cifar_resnet.get_model('resnet32')
    x = jax.random.normal(jax.random.PRNGKey(1), (512, 32, 32, 3))
    y = jax.random.randint(jax.random.PRNGKey(2), (512,), 0, 10)
    n_iters, factor_freq, inv_freq = 150, 1, 10

    kfac, variables, kfac_run, kfac_carry, sgd_run, sgd_carry = (
        build_runners(model, x, y, factor_freq, inv_freq, n_iters))
    flops = model_flops_per_step(
        kfac, variables['params'], x, y,
        {k: v for k, v in variables.items() if k != 'params'})
    # Physical floor: one step cannot beat 100% MFU on the model math
    # alone (K-FAC adds more).
    peak = detected_tpu_peak()
    floor_ms = flops / peak * 1e3

    kfac_ms = time_chained(kfac_run, kfac_carry, n_iters,
                           floor_ms=floor_ms, leg='kfac')
    sgd_ms = time_chained(sgd_run, sgd_carry, n_iters,
                          floor_ms=floor_ms, leg='sgd')

    # Model-math MFU: hand-counted registered-layer fwd+bwd FLOPs (see
    # model_flops_per_step) over measured step time at bf16 peak — how
    # much of the chip the step sustains on model math. K-FAC's
    # factor/decomposition FLOPs are overhead, not model math, so they
    # lower mfu_kfac; that is the point.
    print(json.dumps({
        'metric': 'resnet32_cifar10_kfac_step',
        'value': round(kfac_ms, 3),
        'unit': 'ms/iter',
        'vs_baseline': round(kfac_ms / sgd_ms, 4),
        **device_fields(),
        'model_tflops_per_step': round(flops / 1e12, 4),
        'mfu_kfac': round(flops / (kfac_ms / 1e3) / peak, 4),
        'mfu_sgd': round(flops / (sgd_ms / 1e3) / peak, 4),
    }))


if __name__ == '__main__':
    main()
