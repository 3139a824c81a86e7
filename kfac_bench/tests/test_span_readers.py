"""The readers of the program's own spans and gauges: on a span list
written by hand, and on what the toy cell, driven on the CPU, leaves in
the program's recorder. ``toy_benchmark_spans.json`` is
``toy_benchmark.json`` with the metrics these readers serve."""

import json
import os

import pytest

from conftest import TOY
from distributed_kfac_pytorch_tpu.observability import tracing
from kfac_bench import run
from kfac_bench.readers import program_gauge, scope_ms, span_ms

SPEC = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    'toy_benchmark_spans.json')
NEW = ('model_fwd_bwd_ms', 'optimizer_ms', 'data_wait_ms', 'host_loop_ms',
       'step_build_s', 'kfac_state_gib')
MS = 1_000_000


def recorded():
    """Three steps, oldest first as the recorder keeps them (a child
    closes before its parent). Step 1 (ids 1-4): 10 ms, of which 1 ms
    waits for the batch and 6 ms is the call. Step 2 (5-9): 20 ms, 2 ms
    and 12 ms, with a 3 ms flush inside its sink span. Step 3 (10-12):
    40 ms, 4 ms and 28 ms. After them a wait for a batch that belongs
    to no step, and a build under step 1's call."""
    S = tracing.SpanRecord
    return [
        S(2, 1, 'kfac/host/next_batch', 0, 1 * MS, {}),
        S(13, 3, 'kfac/build/factor=True', 2 * MS, 5 * MS, {}),
        S(3, 1, 'kfac/host/step_call', 1 * MS, 7 * MS, {}),
        S(4, 1, 'kfac/host/sink', 7 * MS, 8 * MS, {}),
        S(1, 0, 'kfac/host/step', 0, 10 * MS, {'step': 0}),
        S(6, 5, 'kfac/host/next_batch', 10 * MS, 12 * MS, {}),
        S(7, 5, 'kfac/host/step_call', 12 * MS, 24 * MS, {}),
        S(9, 8, 'kfac/host/sink_flush', 25 * MS, 28 * MS, {}),
        S(8, 5, 'kfac/host/sink', 24 * MS, 29 * MS, {}),
        S(5, 0, 'kfac/host/step', 10 * MS, 30 * MS, {'step': 1}),
        S(11, 10, 'kfac/host/next_batch', 30 * MS, 34 * MS, {}),
        S(12, 10, 'kfac/host/step_call', 34 * MS, 62 * MS, {}),
        S(10, 0, 'kfac/host/step', 30 * MS, 70 * MS, {'step': 2}),
        S(14, 0, 'kfac/host/next_batch', 70 * MS, 170 * MS, {}),
    ]


def test_the_window_is_the_newest_steps_less_their_first():
    spans = recorded()
    wait = 'kfac/host/next_batch'
    assert span_ms.per_step_ms(spans, 1, wait) == 4.0
    # the harness opens its clock inside the first step's wait
    assert span_ms.per_step_ms(spans, 2, wait) == 4.0
    assert span_ms.per_step_ms(spans, 3, wait) == 3.0
    # more steps asked for than the recorder holds: those it holds;
    # the wait that belongs to no step is in none of them
    assert span_ms.per_step_ms(spans, 50, wait) == 3.0
    assert span_ms.per_step_ms(spans, 3, span_ms.STEP) == 30.0


def test_minus_takes_the_named_children_off():
    spans = recorded()
    loop = dict(span=span_ms.STEP, minus=('kfac/host/next_batch',
                                          'kfac/host/step_call'))
    assert span_ms.per_step_ms(spans, 1, **loop) == 8.0
    assert span_ms.per_step_ms(spans, 3, **loop) == 7.0
    # a grandchild is not a child: the flush stays in its sink span
    assert span_ms.per_step_ms(spans, 3, span_ms.STEP,
                               ('kfac/host/sink_flush',)) == 30.0
    assert span_ms.per_step_ms(spans, 3, 'kfac/host/sink',
                               ('kfac/host/sink_flush',)) == 1.0


def test_nothing_where_the_span_is_absent():
    spans = recorded()
    assert span_ms.per_step_ms(spans, 3, 'kfac/host/meters') is None
    assert span_ms.per_step_ms(spans, 1, 'kfac/host/sink') is None
    assert span_ms.per_step_ms([], 3, span_ms.STEP) is None
    assert span_ms.per_step_ms(spans, 0, span_ms.STEP) is None
    assert span_ms.per_run_ms({}, 'kfac/build/') is None
    with pytest.raises(ValueError, match="'step' or 'run'"):
        span_ms.read({'steps': 1}, span_ms.STEP, per='epoch')


def test_per_run_sums_a_name_or_everything_under_a_prefix():
    snapshot = {'kfac/build/factor=True,inv=True': {'total_ms': 2500.0},
                'kfac/build/factor=True,inv=False': {'total_ms': 1500.0},
                'kfac/builder': {'total_ms': 9.0},
                'kfac/host/step': {'total_ms': 70.0}}
    assert span_ms.per_run_ms(snapshot, 'kfac/build/') == 4000.0
    assert span_ms.per_run_ms(snapshot, 'kfac/host/step') == 70.0
    assert span_ms.per_run_ms(snapshot, 'kfac/host') is None


def test_the_readers_read_the_programs_recorder(monkeypatch):
    tracing.clear_trace()
    try:
        for step in range(3):
            with tracing.span(span_ms.STEP, step=step):
                with tracing.span('kfac/host/next_batch'):
                    pass
        tracing.record('kfac/build/a', 2.0)
        tracing.record('kfac/build/b', 1.0)
        tracing.gauge('kfac/state_bytes/total', 3 * 2 ** 30)
        run_ = {'steps': 2}
        assert span_ms.read(run_, 'kfac/host/next_batch') > 0
        assert span_ms.read(run_, 'kfac/host/sink') is None
        assert span_ms.read(run_, 'kfac/build/', per='run',
                            unit_scale=0.001) == pytest.approx(3.0)
        assert program_gauge.read(run_, 'kfac/state_bytes/total',
                                  unit_scale=2.0 ** -30) == 3.0
        assert program_gauge.read(run_, 'kfac/absent') is None
        # a program from before its recorder: nothing, and no error
        monkeypatch.delattr(tracing, 'spans')
        monkeypatch.delattr(tracing, 'counters')
        assert span_ms.read(run_, 'kfac/host/next_batch') is None
        assert span_ms.read(run_, 'kfac/build/', per='run') is None
        assert program_gauge.read(run_, 'kfac/state_bytes/total') is None
    finally:
        tracing.clear_trace()


def test_the_step_scopes_are_read_by_the_scope_reader():
    with open(SPEC) as f:
        cell = run.load_cell_spec(json.load(f), TOY)
    loaded = {'device': {'/device:TPU:0': [
        ['%fusion.1', 0, 700, 'jit(step_impl)/kfac_step/fwd_bwd/dot'],
        ['%fusion.2', 700, 200,
         'jit(step_impl)/transpose(jvp(kfac_step/fwd_bwd))/dot'],
        ['%fusion.3', 900, 50, 'jit(step_impl)/kfac/factors/linear_a'],
        ['%fusion.4', 950, 100, 'jit(step_impl)/kfac_step/optimizer/add'],
        ['%copy.5', 1050, 10, '']]}, 'host': []}
    run_ = {'trace': loaded, 'steps': 2}
    read = {name: scope_ms.read(run_, **cell['per_layer'][name][
        'parameters']) for name in ('model_fwd_bwd_ms', 'optimizer_ms')}
    assert read == {'model_fwd_bwd_ms': pytest.approx(900e-6 / 2),
                    'optimizer_ms': pytest.approx(100e-6 / 2)}


@pytest.fixture(scope='module')
def toy_run():
    tracing.clear_trace()
    code, result = run.run_cell(TOY, 3000000023, 0.5, False,
                                spec_path=SPEC, require_chip=False)
    assert code == 0 and result['correct'] is True
    yield result
    tracing.clear_trace()


def test_the_toy_cell_leaves_every_new_metrics_span_and_gauge(toy_run):
    with open(SPEC) as f:
        spec = json.load(f)
    cell = run.load_cell_spec(spec, TOY)
    assert set(NEW) <= set(cell['per_layer'])
    steps = toy_run['attempted']
    read = run.read_metrics(
        {name: cell['per_layer'][name] for name in NEW},
        {'steps': steps, 'trace': None})
    # no trace on the CPU, so the two device scopes read nothing
    assert set(read) == set(NEW) - {'model_fwd_bwd_ms', 'optimizer_ms'}
    assert all(m['value'] > 0 for m in read.values())
    assert read['host_loop_ms']['unit'] == 'ms/step'
    # the window's steps are the recorder's newest step spans
    roots = tracing.spans(span_ms.STEP)
    assert [s.attrs['step'] for s in roots[-steps:]] == list(
        range(roots[-1].attrs['step'] - steps + 1,
              roots[-1].attrs['step'] + 1))
    # two variants at f1/i4, each built once, in set-up
    builds = [s for s in tracing.spans()
              if s.name.startswith('kfac/build/')]
    assert len(builds) == 2 == tracing.counters()['kfac/builds']
    assert read['step_build_s']['value'] == pytest.approx(
        sum(s.end_ns - s.start_ns for s in builds) / 1e9)
    assert read['step_build_s']['value'] < toy_run['info']['setup_s']
    assert read['kfac_state_gib']['value'] * 2 ** 30 == \
        tracing.counters()['kfac/state_bytes/total']


def test_the_six_entries_of_the_benchmark_match_the_tests_spec():
    with open(os.path.join(run.ROOT, 'BENCHMARK.json')) as f:
        real = {m['name']: m for m in json.load(f)['per_layer']}
    with open(SPEC) as f:
        toy = {m['name']: m for m in json.load(f)['per_layer']}
    for name in NEW:
        entry = dict(real[name])
        entry.pop('workloads', None)
        assert entry == toy[name]
    assert list(real)[-6:] == list(NEW)
    kfac = [m for m in real.values() if m['name'].startswith('kfac_')]
    assert len({tuple(m['workloads']) for m in kfac}) == 1
