#!/usr/bin/env python3
"""Does the K-FAC train step start, step, fire and finish on the chip?

``python3 chip_smoke.py`` drives the program's main path once, in ONE
process that takes every chip the machine has, through the entry points
a user calls, and checks what comes out by the repo's own means:

- **Leg A, the CLI on the conv path**: ``examples/train_cifar10_resnet
  .py:main`` called with argv — ResNet-32 at its published width, batch
  512 a chip, factors every step, inverses every 10, one epoch long
  enough to fire three times, eval, an orbax save.
- **Leg B, the library path the LM CLI runs, at full width**: the
  Transformer-XL-shaped LM (d1024, 16 heads, FFN 4096, seq 1024, batch
  4 a chip, vocab 32768, tied embedding, bf16 compute, bf16 factors and
  inverses) through exactly the calls
  ``examples/train_language_model.py:main`` makes, in its order, with
  random tokens from a seeded generator. Nine steps at f2/i4, so firing,
  factor-only and plain steps all run and the firing program is used
  three times. Depth is cut to ``LM_DEPTH`` of the model's 18 layers:
  each of the three step programs compiles for minutes at full depth,
  and the whole script has twenty (PERF.md, PR 21).
- **Leg C, the kernels**: the Pallas kernel a public knob reaches
  (``inverse_method='newton'``: the VMEM-resident Newton–Schulz inverse)
  is compiled by Mosaic and run once, directly, against a float64
  reference. The attention kernels have their own probe in legs B and D.

A leg fails unless every loss is finite, no factor update was skipped
as non-finite, every step variant was traced once and built once, and
its metrics stream holds no ``retrace`` and no ``pallas_fallback``
event; leg A must also learn (its last losses below its first). The
script runs every leg, prints what it observed — first-call ms per
variant, host-clock step medians per variant class with every step
closed by ``block_until_ready``, peak HBM per device, the compile cache
in effect — and exits non-zero if any leg failed.

It measures the device or nothing: without a TPU it exits non-zero
before building a model, it sets no platform and reads no platform
switch, and it has no small mode. The legs are functions with size
arguments so that ``tests/test_chip_smoke.py`` can hold them to the
same conditions at toy size on the CPU. The last line of standard
output is one JSON object, ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import contextlib
import functools
import gc
import importlib.util
import json
import os
import shutil
import statistics
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

# Layers of the xl LM that leg B runs. Measured on one v5e (PERF.md,
# PR 21): 16 layers fit its HBM and take 540 s, of which 502 s are the
# three compiles; 12 take 345 s. The width — what sizes every factor,
# every inverse bucket and every kernel — is the model's own.
LM_DEPTH = 12

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from distributed_kfac_pytorch_tpu import launch, multislice  # noqa: E402
from distributed_kfac_pytorch_tpu import native  # noqa: E402
from distributed_kfac_pytorch_tpu.models import (  # noqa: E402
    looped_lm,
    mla_moe_lm,
    transformer_lm,
)
from distributed_kfac_pytorch_tpu.observability import (  # noqa: E402
    sink as obs_sink,
)
from distributed_kfac_pytorch_tpu.ops import pallas_kernels  # noqa: E402
from distributed_kfac_pytorch_tpu.parallel import (  # noqa: E402
    distributed as D,
)
from distributed_kfac_pytorch_tpu.training import (  # noqa: E402
    engine,
    optimizers,
)
from distributed_kfac_pytorch_tpu.utils import (  # noqa: E402
    enable_compilation_cache,
)


# ---------------------------------------------------------------------------
# Observation: program builds, step times, device memory
# ---------------------------------------------------------------------------

class _BuildCounter:
    """Counts the executables JAX builds (compiled, or loaded from the
    persistent cache) through its own monitoring events."""

    def __init__(self):
        self.builds = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, _secs, **_kw):
        if event == '/jax/core/compile/backend_compile_duration':
            self.builds += 1

    def _on_event(self, event, **_kw):
        if event == '/jax/compilation_cache/cache_hits':
            self.cache_hits += 1


@functools.lru_cache(maxsize=1)
def _build_counter() -> _BuildCounter:
    return _BuildCounter()  # JAX offers no way to remove one listener


def _variant_class(flags: dict) -> str:
    fired = engine.fired_stage(flags)
    if fired is None:
        return 'plain'
    return 'firing' if fired.startswith(('inverse', 'chunk')) else 'factor'


class StepClock:
    """Host-clock time of every train step, each closed with
    ``block_until_ready``, and the programs JAX built during it."""

    def __init__(self):
        self.rows: list[dict] = []
        self.step_fn = None

    def wrap(self, step_fn):
        self.step_fn = step_fn
        counter = _build_counter()

        # wraps(): train_epoch reads the builder's attributes and the
        # cadence flags in its signature off the function it is given.
        @functools.wraps(step_fn)
        def clocked(*args, **flags):
            traces = sum(step_fn.trace_counts.values())
            builds, hits = counter.builds, counter.cache_hits
            t0 = time.perf_counter()
            try:
                out = jax.block_until_ready(step_fn(*args, **flags))
            except Exception:
                print(f'  step {len(self.rows)} '
                      f'({_variant_class(flags)}) raised; HBM MB '
                      f'{hbm_now()}', flush=True)
                raise
            self.rows.append({
                'class': _variant_class(flags),
                'ms': round((time.perf_counter() - t0) * 1000.0, 2),
                'traced': sum(step_fn.trace_counts.values()) - traces,
                'builds': counter.builds - builds,
                'cache_hits': counter.cache_hits - hits,
                'hbm': hbm_now()})
            print(f'  step {len(self.rows) - 1}: {self.rows[-1]}',
                  flush=True)
            return out

        return clocked

    def medians(self) -> dict:
        """{class: median ms over the calls that built no program}."""
        by: dict[str, list[float]] = {}
        for r in self.rows:
            if not r['traced'] and not r['builds']:
                by.setdefault(r['class'], []).append(r['ms'])
        return {c: round(statistics.median(v), 2) for c, v in by.items()}


@contextlib.contextmanager
def clocked_steps():
    """Every step function ``DistributedKFAC.build_train_step`` returns
    inside the block comes back wrapped by a :class:`StepClock`. The
    step program and its arguments are untouched; the host merely waits
    for each step before it dispatches the next, which is what a time
    per step means. This is how leg A, where the CLI owns the loop, is
    observed without a flag of its own."""
    clock = StepClock()
    build = D.DistributedKFAC.build_train_step

    def clocked_build(self, *args, **kwargs):
        return clock.wrap(build(self, *args, **kwargs))

    D.DistributedKFAC.build_train_step = clocked_build
    try:
        yield clock
    finally:
        D.DistributedKFAC.build_train_step = build


def hbm_now() -> dict:
    """Device 0's allocator counters that move during a run, in MB:
    what arrays and loaded programs hold (``in_use``), what the runtime
    holds besides (``reserved``), and the largest block still free."""
    stats = jax.devices()[0].memory_stats() or {}
    return {short: stats[key] // 2**20
            for short, key in (('in_use', 'bytes_in_use'),
                               ('reserved', 'bytes_reserved'),
                               ('free_block', 'largest_free_block_bytes'))
            if key in stats}


def device_peaks() -> list[int | None]:
    """``peak_bytes_in_use`` of every device (None where the backend
    reports no allocator statistics)."""
    return [(d.memory_stats() or {}).get('peak_bytes_in_use')
            for d in jax.devices()]


def cache_entries(cache_dir: str | None) -> int:
    if not cache_dir or not os.path.isdir(cache_dir):
        return 0
    return sum(len(files) for _, _, files in os.walk(cache_dir))


# ---------------------------------------------------------------------------
# The conditions every training leg is held to
# ---------------------------------------------------------------------------

def check_training(report: dict, stream_path: str, clock: StepClock,
                   *, must_learn: bool) -> None:
    """Fill ``report`` from a leg's metrics stream and step clock and
    append to ``report['failures']`` every condition that does not
    hold."""
    fails = report['failures']
    records = obs_sink.read_jsonl(stream_path)
    steps = [r for r in records if r['kind'] == 'step']
    events = [r for r in records if r['kind'] == 'event']
    losses = [r['metrics']['loss'] for r in steps]
    report['steps'] = len(steps)
    report['losses'] = [round(float(v), 4) for v in losses]
    if not steps:
        fails.append('no step records in the metrics stream')
        return
    if not all(np.isfinite(losses)):
        fails.append(f'non-finite loss: {losses}')
    k = max(1, min(3, len(losses) // 2))
    first, last = float(np.mean(losses[:k])), float(np.mean(losses[-k:]))
    report['loss_first_last'] = [round(first, 4), round(last, 4)]
    if must_learn and not last < first:
        fails.append(f'loss did not fall: mean of last {k} steps '
                     f'{last:.4f} >= mean of first {k} {first:.4f}')
    skips = steps[-1]['metrics'].get('kfac/nonfinite_skips')
    report['nonfinite_skips'] = skips
    if skips != 0:
        fails.append(f'kfac/nonfinite_skips = {skips}, expected 0')

    compiles = [e['data'] for e in events if e['event'] == 'compile']
    report['first_call_ms'] = {
        c['variant']: round(c['first_call_ms'], 1) for c in compiles}
    for name in ('retrace', 'pallas_fallback'):
        hit = [e['data'] for e in events if e['event'] == name]
        if hit:
            fails.append(f'{len(hit)} {name} event(s): {hit}')
    counts = dict(clock.step_fn.trace_counts) if clock.step_fn else {}
    report['trace_counts'] = {str(k): v for k, v in counts.items()}
    if not counts:
        fails.append('no step variant was traced')
    if any(n != 1 for n in counts.values()):
        fails.append(f'a variant was traced more than once: {counts}')
    if len(compiles) != len(counts):
        fails.append(f'{len(compiles)} compile events for '
                     f'{len(counts)} variants')
    # Below the trace cache JAX keys executables by input shardings: a
    # variant can build a second program with no retrace to show for it.
    late = [r for r in clock.rows if r['builds'] and not r['traced']]
    if late:
        fails.append(f'{len(late)} step(s) built a program without '
                     f'tracing a new variant: {late}')
    report['step_ms_median'] = clock.medians()
    report['programs_built'] = sum(r['builds'] for r in clock.rows)
    report['programs_from_cache'] = sum(r['cache_hits']
                                        for r in clock.rows)
    report['hbm_mb_after_step'] = [r['hbm'] for r in clock.rows]
    report['classes_run'] = sorted({r['class'] for r in clock.rows})


def _new_report(name: str) -> dict:
    return {'leg': name, 'failures': []}


# ---------------------------------------------------------------------------
# Leg A: the CIFAR CLI
# ---------------------------------------------------------------------------

def _load_example(name: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, 'examples', f'{name}.py'))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def leg_cifar(out_dir: str, *, name: str = 'A', model: str = 'resnet32',
              batch_size: int = 512, steps: int = 22, inv_freq: int = 10,
              extra_argv=()) -> dict:
    """The conv path through ``train_cifar10_resnet.main(argv)``:
    eigen warm-polish inverses, conv patch factors, BatchNorm, eval, an
    orbax save. ``batch_size`` is global; ``steps`` sizes the synthetic
    set so that one epoch has exactly that many steps."""
    report = _new_report(name)
    stream = os.path.join(out_dir, f'{name}.jsonl')
    argv = ['--model', model, '--batch-size', str(batch_size),
            '--kfac-update-freq', str(inv_freq),
            '--kfac-cov-update-freq', '1', '--epochs', '1', '--no-resume',
            '--kfac-metrics', stream, '--metrics-interval', '1',
            '--checkpoint-dir', os.path.join(out_dir, f'{name}_ckpt'),
            '--log-dir', os.path.join(out_dir, f'{name}_logs'),
            *extra_argv]
    report['argv'] = ' '.join(argv)
    cli = _load_example('train_cifar10_resnet')
    saved = os.environ.get('KFAC_SYNTHETIC_CIFAR')
    os.environ['KFAC_SYNTHETIC_CIFAR'] = str(steps * batch_size)
    try:
        with clocked_steps() as clock:
            rc = cli.main(argv)
    finally:
        if saved is None:
            del os.environ['KFAC_SYNTHETIC_CIFAR']
        else:
            os.environ['KFAC_SYNTHETIC_CIFAR'] = saved
    if rc != 0:
        report['failures'].append(f'CLI returned {rc}')
    check_training(report, stream, clock, must_learn=True)
    if report.get('steps') != steps:
        report['failures'].append(
            f"{report.get('steps')} steps ran, expected {steps}")
    report['augmentation'] = (
        'native C++, built from csrc/augment.cpp'
        if native.get_lib() is not None else 'numpy')
    return report


# ---------------------------------------------------------------------------
# Leg B: the LM through the library calls the LM CLI makes
# ---------------------------------------------------------------------------

def check_placement(report: dict, dkfac, state) -> None:
    """More than one device: is the state spread the way the layout
    says? Code that has only seen virtual devices may put everything on
    the first one."""
    fails = report['failures']
    devices = list(dkfac.mesh.devices.flat)
    for path, leaf in jax.tree_util.tree_leaves_with_path(state.params):
        held = {s.device for s in leaf.addressable_shards
                if s.data.shape == leaf.shape}
        if held != set(devices):
            fails.append(f'params{jax.tree_util.keystr(path)} is whole '
                         f'on {len(held)} of {len(devices)} devices')
            break
    rows = dkfac.total_rows
    row_of = {dev: r
              for r, row in enumerate(dkfac.mesh.devices.reshape(rows, -1))
              for dev in row}
    leaf = jax.tree.leaves(state.kfac_state['inv_stacks'])[0]
    slices: dict[int, set] = {}
    for s in leaf.addressable_shards:
        if s.data.shape[0] * rows != leaf.shape[0]:
            fails.append(
                f'an inv_stacks shard holds {s.data.shape[0]} of '
                f'{leaf.shape[0]} slots on {s.device}; the layout says '
                f'1/{rows}')
            return
        slices.setdefault(row_of[s.device], set()).add(
            (s.index[0].start or 0))
    if any(len(v) != 1 for v in slices.values()) or \
            len({next(iter(v)) for v in slices.values()}) != rows:
        fails.append(f'inv_stacks rows are not one slice per mesh row: '
                     f'{slices}')


def leg_lm(out_dir: str, *, name: str = 'B', size: str = 'xl',
           seq: int = 1024, per_chip_batch: int = 4, vocab: int = 32768,
           steps: int = 9, factor_freq: int = 2, inv_freq: int = 4,
           comm_method: str = 'comm-opt',
           grad_worker_fraction: float = 0.25, dtype=jnp.bfloat16,
           bf16_state: bool = True, seed: int = 0,
           arch: str = 'transformer', **model_overrides) -> dict:
    """``transformer_lm.get_model(size)`` — or, with ``arch='mla_moe'``
    or ``'looped'``, ``mla_moe_lm`` / ``looped_lm.get_model(size)`` with
    the untied head left to SGD (the looped decoder is handed its
    targets and returns its own objective) — through the calls
    ``examples/train_language_model.py:main`` makes, in its order. (The CLI itself cannot select bf16 compute.)"""
    report = _new_report(name)
    stream = os.path.join(out_dir, f'{name}.jsonl')
    n_dev = jax.device_count()
    batch = per_chip_batch * n_dev
    base_lr, grad_clip = 0.1, 0.25

    def build_model():
        if arch == 'mla_moe':
            return mla_moe_lm.get_model(vocab, size, dtype=dtype,
                                        **model_overrides)
        if arch == 'looped':
            return looped_lm.get_model(vocab, size, dtype=dtype,
                                       **model_overrides)
        return transformer_lm.get_model(
            vocab, size, max_len=seq, tie_weights=True, dtype=dtype,
            **model_overrides)

    model = build_model()
    cfg = optimizers.OptimConfig(
        base_lr=base_lr, momentum=0.9, weight_decay=0.0,
        warmup_epochs=1, lr_decay=[20, 30], workers=1,
        kfac_inv_update_freq=inv_freq, kfac_cov_update_freq=factor_freq,
        damping=0.003, factor_decay=0.95, kl_clip=0.001,
        inverse_method='auto',
        skip_layers=['head'] if arch in ('mla_moe', 'looped') else [],
        comm_method=comm_method,
        grad_worker_fraction=grad_worker_fraction,
        bf16_factors=bf16_state, bf16_inverses=bf16_state,
        kfac_metrics=True)
    tx, lr_schedule, kfac, kfac_sched = optimizers.get_optimizer(model,
                                                                 cfg)
    sink = obs_sink.JsonlMetricsSink(
        stream, interval=1, process_index=jax.process_index(),
        meta={'cli': 'chip_smoke.leg_lm', 'size': size, 'bptt': seq,
              'batch_size': batch, 'devices': n_dev})
    tx = optax.chain(optax.clip_by_global_norm(grad_clip), tx)

    ids0 = jnp.zeros((2, seq), jnp.int32)
    params = kfac.init(jax.random.PRNGKey(seed), ids0,
                       train=False)[0]['params']
    report['params_m'] = round(sum(
        x.size for x in jax.tree.leaves(params)) / 1e6, 1)
    report['kfac_layers'] = len(kfac.specs)
    report['depth'] = model.num_layers

    mesh = multislice.make_multislice_mesh(
        num_slices=1, comm_method=optimizers.COMM_METHODS[comm_method],
        grad_worker_fraction=grad_worker_fraction, seq_parallel=1)
    report['mesh'] = dict(mesh.shape)
    params = launch.replicate_on_mesh(mesh, params)
    dkfac = D.DistributedKFAC(kfac, mesh, params)
    kstate = dkfac.init_state(params)
    opt_state = tx.init(params)

    def eval_loss(out, batch):
        return optax.softmax_cross_entropy_with_integer_labels(
            out if arch == 'transformer' else out.astype(jnp.float32),
            batch[1]).mean()

    def loss_fn(out, batch):
        return out.mean() if arch == 'looped' else eval_loss(out, batch)

    data_axes = dkfac.data_axes

    def model_kwargs_fn(batch):
        # Per-device dropout key, folded as the CLI folds it.
        idx = jax.lax.axis_index(data_axes[0])
        for ax in data_axes[1:]:
            idx = idx * jax.lax.psum(1, ax) + jax.lax.axis_index(ax)
        kwargs = {'train': True,
                  'rngs': {'dropout': jax.random.fold_in(batch[2], idx)}}
        if arch == 'looped':
            kwargs['targets'] = batch[1]
        return kwargs

    data_spec = P(multislice.batch_axes(mesh))
    clock = StepClock()
    step_fn = clock.wrap(dkfac.build_train_step(
        loss_fn, tx, model_kwargs_fn=model_kwargs_fn,
        batch_spec=(data_spec, data_spec, P()), loss_scale=None))
    eval_step = engine.make_eval_step(
        build_model(), eval_loss, None, model_args_fn=lambda b: (b[0],),
        model_kwargs={'train': False}, metrics_fn=lambda o, b: {})

    state = engine.TrainState(params=params, opt_state=opt_state,
                              kfac_state=kstate, extra_vars={})
    rng = np.random.default_rng(seed)
    root = jax.random.PRNGKey(seed * 1000)

    def batches():
        for i in range(steps):
            ids = rng.integers(0, vocab, (batch, seq + 1), dtype=np.int32)
            yield ids[:, :-1], ids[:, 1:], jax.random.fold_in(root, i)

    lr = lr_schedule(0)
    state.opt_state = optimizers.set_lr(state.opt_state, lr)
    hyper = {'lr': lr, **kfac_sched.params()}
    engine.train_epoch(
        step_fn, state,
        launch.global_batches(mesh, batches(),
                              batch_spec=(data_spec, data_spec, P())),
        hyper, verbose=True, metrics_sink=sink)
    held_out = rng.integers(0, vocab, (per_chip_batch, seq + 1),
                            dtype=np.int32)
    val = engine.evaluate(
        eval_step, state,
        launch.global_batches(mesh, [(held_out[:, :-1], held_out[:, 1:])],
                              batch_spec=(data_spec, data_spec)),
        verbose=True)
    sink.close()

    report['val_loss'] = round(float(val['loss']), 4)
    if not np.isfinite(val['loss']):
        report['failures'].append(f"non-finite eval loss {val['loss']}")
    check_training(report, stream, clock, must_learn=False)
    want = {'factor', 'firing'} | ({'plain'} if factor_freq > 1 else set())
    if steps >= 2 * inv_freq and set(report.get('classes_run', ())) != want:
        report['failures'].append(
            f"step classes run {report.get('classes_run')}, "
            f'expected {sorted(want)}')
    if n_dev > 1:
        check_placement(report, dkfac, state)
    return report


# ---------------------------------------------------------------------------
# Leg C: the Pallas kernel a public knob reaches, compiled and run
# ---------------------------------------------------------------------------

def _spd_stack(rng, count: int, n: int):
    """``count`` SPD matrices with eigenvalues in [0.5, 2]: conditioned
    like a damped factor."""
    q = np.linalg.qr(rng.normal(size=(count, n, n)))[0]
    d = rng.uniform(0.5, 2.0, (count, n))
    return jnp.asarray(np.einsum('bij,bj,bkj->bik', q, d, q), jnp.float32)


def leg_kernels(*, interpret: bool = False, stack: int = 8,
                inverse_dims=(512, 289), seed: int = 0) -> dict:
    """Build the kernel with ``interpret`` (False: Mosaic compiles
    it), run it once, directly, at its largest eligible dim and at one
    that is no multiple of 128, and hold it to the probes' tolerance
    against its reference. A case the compiler refuses is reported with
    the compiler's words and the next one still runs."""
    report = _new_report('C')
    report['kernels'] = {}
    rng = np.random.default_rng(seed)
    damping = 0.003

    for n in inverse_dims:
        label = f'batched_inverse[{stack}x{n}]'
        mats = _spd_stack(rng, stack, n)
        ref = np.linalg.inv(np.asarray(mats, np.float64)
                            + damping * np.eye(n))
        try:
            rel = pallas_kernels.max_rel_error(
                pallas_kernels.batched_inverse(
                    mats, damping, force_pallas=True, interpret=interpret),
                ref)
        except Exception as e:  # report, then try the next case
            report['kernels'][label] = f'{type(e).__name__}: {e}'
            report['failures'].append(
                f'{label} does not compile or run: '
                f'{type(e).__name__}: {str(e)[:2000]}')
            traceback.print_exc()
            continue
        report['kernels'][label] = f'rel_err {rel:.2e}'
        if not rel < pallas_kernels.PROBE_RTOL:
            report['failures'].append(
                f'{label} disagrees with its reference: relative '
                f'error {rel:.3g} >= {pallas_kernels.PROBE_RTOL}')
    return report


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

def describe_device() -> dict:
    import jaxlib
    try:
        from importlib.metadata import version
        libtpu = version('libtpu')
    except Exception:  # the package name varies with the install
        libtpu = 'unknown'
    dev = jax.devices()[0]
    device = {'platform': dev.platform, 'kind': dev.device_kind,
              'count': len(jax.devices())}
    print(f'device: platform={device["platform"]} '
          f'device_kind={device["kind"]!r} count={device["count"]} | '
          f'jax {jax.__version__} jaxlib {jaxlib.__version__} '
          f'libtpu {libtpu}', flush=True)
    return device


def run_leg(fn, *args, **kwargs) -> dict:
    """One leg, timed; a leg that raises is a failed leg, reported, and
    the remaining legs still run so that one run reports everything.
    Each leg starts with no program of an earlier leg loaded: a loaded
    program holds HBM, and the xl LM needs nearly all of it."""
    jax.clear_caches()
    t0 = time.perf_counter()
    try:
        report = fn(*args, **kwargs)
    except Exception as e:
        traceback.print_exc()
        report = _new_report(kwargs.get('name', fn.__name__))
        report['failures'].append(
            f'raised {type(e).__name__}: {str(e)[:4000]}')
    report['wall_s'] = round(time.perf_counter() - t0, 1)
    report['peak_bytes_in_use'] = device_peaks()
    gc.collect()  # a failed leg's frames must not hold HBM into the next
    return report


def print_report(report: dict, device: dict) -> None:
    tag = f'[{report["leg"]} | {device["kind"]} x{device["count"]}]'
    for key, value in report.items():
        if key not in ('leg', 'failures'):
            print(f'{tag} {key}: {value}')
    for failure in report['failures']:
        print(f'{tag} FAILED: {failure}')
    print(f'{tag} {"ok" if not report["failures"] else "FAILED"}',
          flush=True)


def main() -> int:
    if jax.default_backend() != 'tpu':
        print('chip_smoke: no TPU (jax.default_backend() == '
              f'{jax.default_backend()!r}); this script runs on the '
              'chip or not at all', file=sys.stderr)
        return 2
    device = describe_device()
    cache_dir = enable_compilation_cache()
    entries_before = cache_entries(cache_dir)
    print(f'compile cache: {cache_dir} ({entries_before} entries)')
    out_dir = os.path.join(REPO, 'chiprun_out', 'chip_smoke')
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)

    n_dev = device['count']
    # One chip is the 1x1 mesh. On a host with several, leg A runs
    # COMM_OPT (1 x n) and leg B HYBRID_OPT at fraction 0.5 (2 x n/2);
    # the batch a chip sees is the same either way.
    lm_mesh = ({'comm_method': 'hybrid-opt', 'grad_worker_fraction': 0.5}
               if n_dev > 1 else {})
    reports = [
        run_leg(leg_cifar, out_dir, name='A', batch_size=512 * n_dev),
        run_leg(leg_lm, out_dir, name='B', num_layers=LM_DEPTH,
                **lm_mesh),
        run_leg(leg_kernels),
        # The second decoder's two variants (factors every step) at a
        # small depth: one dense and one MoE layer at published widths.
        run_leg(leg_lm, out_dir, name='D', arch='mla_moe',
                size='kanana2', num_layers=2, vocab=16032, steps=8,
                factor_freq=1, inv_freq=4, bf16_state=False, **lm_mesh),
    ]
    for report in reports:
        peaks = report['peak_bytes_in_use']
        if any(p is None for p in peaks):
            report['failures'].append(
                f'a device reports no peak_bytes_in_use: {peaks}')
        elif max(peaks) > 2 * min(peaks):
            report['failures'].append(
                f'peak HBM differs by more than 2x across devices: '
                f'{peaks}')
        print_report(report, device)
    print(f'compile cache: {cache_dir} ({entries_before} entries before, '
          f'{cache_entries(cache_dir)} after)')
    ok = not any(r['failures'] for r in reports)
    with open(os.path.join(out_dir, 'report.json'), 'w') as f:
        json.dump({'ok': ok, 'device': device, 'legs': reports}, f,
                  indent=1, default=str)
    print(json.dumps({'ok': ok, 'device': device}), flush=True)
    return 0 if ok else 1


if __name__ == '__main__':
    sys.exit(main())
