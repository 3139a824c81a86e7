"""Each step program's bytes by the compiler's count: what
``build_train_step`` records for every variant it loads
(``kfac/program_bytes/*``, the ``kfac/build/*`` span's ``temp_bytes`` /
``code_bytes`` / ``total_bytes``), and the three per-layer metrics of
the benchmark that read it."""

import io
import json
import os
import types

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
import pytest

from distributed_kfac_pytorch_tpu.observability import memory as obs_memory
from distributed_kfac_pytorch_tpu.observability import report as obs_report
from distributed_kfac_pytorch_tpu.observability import sink as obs_sink
from distributed_kfac_pytorch_tpu.observability import tracing
from distributed_kfac_pytorch_tpu.parallel import distributed as D
from distributed_kfac_pytorch_tpu.preconditioner import KFAC, CommMethod
from distributed_kfac_pytorch_tpu.training import engine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BACKEND_COMPILE = '/jax/core/compile/backend_compile_duration'
FIELDS = ('argument', 'output', 'alias', 'temp', 'code', 'total')
BOTH, FACTOR_ONLY = ('factor=True,inv=True,chunk=None',
                     'factor=True,inv=False,chunk=None')


class TinyMLP(nn.Module):
    @nn.compact
    def __call__(self, x):
        x = nn.tanh(nn.Dense(8, name='d0')(x))
        return nn.Dense(4, name='head')(x)


def _build_and_run(devices: int, path: str, record: bool = True) -> dict:
    """Eight steps of a toy model at f1/i4 through the built K-FAC step,
    state donated, with JAX's backend compiles noted beside the span
    open when each came. ``record=False`` builds as the parent did,
    with no executable lowered for its bytes."""
    tracing.clear_trace()
    compiles = []

    def listen(event, seconds, **_kw):
        if event == BACKEND_COMPILE:
            span = tracing.current()
            compiles.append(span.name if span else None)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        with pytest.MonkeyPatch.context() as patch:
            if not record:
                patch.setattr(D, '_record_program_bytes',
                              lambda *a: {})
            kfac = KFAC(TinyMLP(), factor_update_freq=1,
                        inv_update_freq=4, factor_decay=0.5,
                        damping=0.01, lr=0.1, kl_clip=None)
            x = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
            params = kfac.init(jax.random.PRNGKey(0), x)[0]['params']
            mesh = D.make_kfac_mesh(
                jax.devices()[:devices], comm_method=CommMethod.COMM_OPT,
                grad_worker_fraction=1 / devices)
            dkfac = D.DistributedKFAC(kfac, mesh, params)
            kstate = dkfac.init_state(params)
            tx = optax.sgd(0.05)
            step = dkfac.build_train_step(
                lambda out, b: jnp.mean(out ** 2), tx, donate=True)
            sink = obs_sink.JsonlMetricsSink(path)
            state = engine.TrainState(params, tx.init(params), kstate, {})
            batch = (x, jnp.zeros((16,), jnp.int32))
            hyper = {'lr': 0.05, 'damping': 0.01,
                     'factor_update_freq': 1, 'inv_update_freq': 4}
            engine.train_epoch(step, state, iter([batch] * 8), hyper,
                               metrics_sink=sink)
            sink.close()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    out = {'counters': tracing.counters(), 'spans': tracing.spans(),
           'step': step, 'compiles': compiles,
           'records': obs_sink.read_jsonl(path, validate=True)}
    tracing.clear_trace()
    return out


@pytest.fixture(scope='module')
def built(tmp_path_factory):
    return _build_and_run(
        2, str(tmp_path_factory.mktemp('program_bytes') / 'run.jsonl'))


def _variant_gauges(counters, label):
    return {field: counters[f'{D.PROGRAM_BYTES_PREFIX}{label}/{field}']
            for field in FIELDS}


def test_each_variant_has_its_six_gauges(built):
    counters = built['counters']
    named = {k for k in counters if k.startswith(D.PROGRAM_BYTES_PREFIX)}
    assert named == {f'{D.PROGRAM_BYTES_PREFIX}{label}/{field}'
                     for label in (BOTH, FACTOR_ONLY) for field in FIELDS} | {
        D.PROGRAM_BYTES_PREFIX + name
        for name in ('total_max', 'temp_max', 'code_sum')}
    for label in (BOTH, FACTOR_ONLY):
        g = _variant_gauges(counters, label)
        assert g['total'] == (g['argument'] + g['output'] - g['alias']
                              + g['temp'] + g['code'])
        # the state is donated: its buffers come back as outputs
        assert 0 < g['alias'] <= min(g['argument'], g['output'])
        assert g['temp'] > 0


def test_the_gauges_over_the_variants_agree_with_them(built):
    counters = built['counters']
    both = _variant_gauges(counters, BOTH)
    factor = _variant_gauges(counters, FACTOR_ONLY)
    prefix = D.PROGRAM_BYTES_PREFIX
    assert counters[prefix + 'total_max'] == max(both['total'],
                                                 factor['total'])
    assert counters[prefix + 'temp_max'] == max(both['temp'],
                                                factor['temp'])
    assert counters[prefix + 'code_sum'] == both['code'] + factor['code']


@pytest.mark.parametrize('label', [BOTH, FACTOR_ONLY])
def test_a_program_holds_at_least_the_state(built, label):
    counters = built['counters']
    assert (_variant_gauges(counters, label)['total']
            >= counters['kfac/state_bytes/total'] > 0)


def test_the_build_span_and_its_compile_event_carry_the_bytes(built):
    counters = built['counters']
    spans = {s.name[len(D.BUILD_SPAN_PREFIX):]: s for s in built['spans']
             if s.name.startswith(D.BUILD_SPAN_PREFIX)}
    events = {r['data']['variant']: r['data'] for r in built['records']
              if r.get('event') == 'compile'}
    assert set(spans) == set(events) == {BOTH, FACTOR_ONLY}
    for label, span in spans.items():
        g = _variant_gauges(counters, label)
        want = {f'{f}_bytes': g[f] for f in ('temp', 'code', 'total')}
        assert {k: span.attrs[k] for k in want} == want
        assert {k: events[label][k] for k in want} == want


def _in_builds(compiles) -> dict:
    """Backend compiles by the build span they came in."""
    out = {}
    for name in compiles:
        if name and name.startswith(D.BUILD_SPAN_PREFIX):
            out[name] = out.get(name, 0) + 1
    return out


def test_recording_the_bytes_builds_nothing_twice(built):
    step = built['step']
    assert built['counters']['kfac/builds'] == 2
    assert 'kfac/retraces' not in built['counters']
    assert set(step.trace_counts) == {(True, True, None),
                                      (True, False, None)}
    assert set(step.trace_counts.values()) == {1}
    assert set(_in_builds(built['compiles'])) == {
        D.BUILD_SPAN_PREFIX + label for label in (BOTH, FACTOR_ONLY)}


@pytest.mark.parametrize('devices', [1, 2])
def test_a_build_compiles_what_it_compiled_without_the_bytes(
        devices, tmp_path):
    """Against a build that lowers nothing for its bytes (the parent's):
    the same backend compiles in every build span, one a variant on one
    device, and one trace a variant."""
    bare = _build_and_run(devices, str(tmp_path / 'bare.jsonl'),
                          record=False)
    recorded = _build_and_run(devices, str(tmp_path / 'recorded.jsonl'))
    assert not any(k.startswith(D.PROGRAM_BYTES_PREFIX)
                   for k in bare['counters'])
    assert _in_builds(recorded['compiles']) == _in_builds(bare['compiles'])
    if devices == 1:
        assert sorted(_in_builds(recorded['compiles']).values()) == [1, 1]
    assert (recorded['step'].trace_counts == bare['step'].trace_counts
            == {(True, True, None): 1, (True, False, None): 1})


def test_the_report_prints_each_programs_bytes(built):
    summary = obs_report.summarize(built['records'])
    out = io.StringIO()
    obs_report.print_report(summary, out=out)
    rows = [line for line in out.getvalue().splitlines()
            if line.strip().startswith('compile ')]
    assert len(rows) == 2
    for row in rows:
        assert ' total ' in row and ' temp ' in row and ' code ' in row


def test_an_outer_trace_records_no_program(fresh_builder):
    """A first call under someone else's ``jax.jit`` has no program of
    its own to count: the variant builds, and no bytes are gauged."""
    step, args = fresh_builder
    jax.jit(lambda *a: step(*a, factor_update=True, inv_update=False)
            ).lower(*args)
    counters = tracing.counters()
    assert counters['kfac/builds'] == 1
    assert not any(k.startswith(D.PROGRAM_BYTES_PREFIX) for k in counters)


@pytest.fixture
def fresh_builder():
    tracing.clear_trace()
    kfac = KFAC(TinyMLP(), factor_update_freq=1, inv_update_freq=2,
                damping=0.01, lr=0.1, kl_clip=None)
    x = jnp.ones((8, 8))
    params = kfac.init(jax.random.PRNGKey(0), x)[0]['params']
    mesh = D.make_kfac_mesh(jax.devices()[:1],
                            comm_method=CommMethod.COMM_OPT,
                            grad_worker_fraction=1.0)
    dkfac = D.DistributedKFAC(kfac, mesh, params)
    kstate = dkfac.init_state(params)
    tx = optax.sgd(0.05)
    step = dkfac.build_train_step(lambda out, b: jnp.mean(out ** 2), tx,
                                  donate=False)
    hyper = {'lr': 0.05, 'damping': 0.01, 'factor_update_freq': 1,
             'inv_update_freq': 2}
    yield step, (params, tx.init(params), kstate, {},
                 (x, jnp.zeros((8,), jnp.int32)), hyper)
    tracing.clear_trace()


# -- program_footprint ------------------------------------------------------

def test_program_footprint_reads_the_compilers_count():
    f = jax.jit(lambda a, b: (jnp.tanh(a @ b), b * 2), donate_argnums=(1,))
    a = b = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    compiled = f.lower(a, b).compile()
    stats = compiled.memory_analysis()
    got = obs_memory.program_footprint(compiled)
    assert {k: got[k] for k in obs_memory.PROGRAM_FIELDS} == {
        k: getattr(stats, field)
        for k, field in obs_memory.PROGRAM_FIELDS.items()}
    assert got['argument'] == 2 * 64 * 64 * 4
    assert got['alias'] == 64 * 64 * 4       # b's buffer is reused
    assert got['total'] == (got['argument'] + got['output']
                            - got['alias'] + got['temp'] + got['code'])
    if stats.peak_memory_in_bytes:
        assert got['peak'] == stats.peak_memory_in_bytes


def _stats(**kw):
    fields = {field: 0 for field in obs_memory.PROGRAM_FIELDS.values()}
    fields.update(kw)
    return types.SimpleNamespace(**fields)


@pytest.mark.parametrize('analysis, want', [
    (lambda: None, {}),
    (lambda: _stats(argument_size_in_bytes=10, temp_size_in_bytes=5,
                    generated_code_size_in_bytes=2,
                    peak_memory_in_bytes=0),
     {'argument': 10, 'output': 0, 'alias': 0, 'temp': 5, 'code': 2,
      'total': 17}),
    (lambda: _stats(argument_size_in_bytes=10, output_size_in_bytes=4,
                    alias_size_in_bytes=4, peak_memory_in_bytes=12),
     {'argument': 10, 'output': 4, 'alias': 4, 'temp': 0, 'code': 0,
      'total': 10, 'peak': 12}),
], ids=['none', 'no-peak', 'aliased'])
def test_program_footprint_degrades_and_adds_up(analysis, want):
    compiled = types.SimpleNamespace(memory_analysis=analysis)
    assert obs_memory.program_footprint(compiled) == want


# -- the benchmark's three metrics ------------------------------------------

NEW = {'step_program_hbm_gib': ('kfac/program_bytes/total_max', 2 ** -30),
       'step_program_temp_gib': ('kfac/program_bytes/temp_max', 2 ** -30),
       'step_program_code_mib': ('kfac/program_bytes/code_sum', 2 ** -20)}
LAYER = 'step builder (parallel/distributed.py build_train_step)'


def test_the_benchmark_appends_three_metrics_that_read_the_gauges():
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        spec = json.load(f)
    cells = [w['name'] for w in spec['workloads']]
    entries = spec['per_layer'][-3:]
    assert [e['name'] for e in entries] == list(NEW)
    for entry in entries:
        assert entry['source'] == 'program_counter'
        assert entry['layer'] == LAYER
        assert entry['better'] == 'lower'
        assert entry['moves'] == 'peak_hbm_gib'
        assert entry['workloads'] == cells
        with open(os.path.join(ROOT, 'kfac_bench', 'layer_metrics',
                               entry['name'] + '.json')) as f:
            data = json.load(f)
        gauge, scale = NEW[entry['name']]
        assert data == {'reader': 'program_gauge',
                        'parameters': {'gauge': gauge, 'unit_scale': scale},
                        'layer': LAYER}


@pytest.mark.parametrize('name', list(NEW))
def test_each_metric_reads_its_gauge_or_nothing(name):
    from kfac_bench.readers import program_gauge
    gauge, scale = NEW[name]
    tracing.clear_trace()
    try:
        # a program from before this gauge (the parent) reads nothing
        assert program_gauge.read({}, gauge, scale) is None
        tracing.gauge(gauge, 3 * 2 ** 30)
        assert program_gauge.read({}, gauge, scale) == 3 * 2 ** 30 * scale
    finally:
        tracing.clear_trace()
