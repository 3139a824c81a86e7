"""The program's recorder of spans and counters
(``observability.tracing``), and the spans, counters, gauges and
profiler scopes the epoch loop, the sink and the step builder leave in
it."""

import re
import threading

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
import pytest

from distributed_kfac_pytorch_tpu import utils
from distributed_kfac_pytorch_tpu.observability import memory as obs_memory
from distributed_kfac_pytorch_tpu.observability import sink as obs_sink
from distributed_kfac_pytorch_tpu.observability import tracing
from distributed_kfac_pytorch_tpu.parallel import distributed as D
from distributed_kfac_pytorch_tpu.preconditioner import KFAC, CommMethod
from distributed_kfac_pytorch_tpu.training import engine


class FakeClock:
    """Nanoseconds that move only when a test says so."""

    def __init__(self):
        self.now = 1_000

    def __call__(self):
        return self.now

    def tick(self, ns):
        self.now += ns


@pytest.fixture
def clock():
    return FakeClock()


@pytest.fixture
def recorder(clock):
    return tracing.Recorder(clock=clock)


@pytest.fixture
def fresh():
    tracing.clear_trace()
    yield tracing
    tracing.clear_trace()


# -- the recorder ---------------------------------------------------------

def test_nesting_gives_parent_ids_and_times(recorder, clock):
    with recorder.span('step', step=7) as root:
        clock.tick(10)
        with recorder.span('fetch') as fetch:
            clock.tick(5)
        with recorder.span('call') as call:
            clock.tick(20)
            with recorder.span('build'):
                clock.tick(100)
    by_name = {s.name: s for s in recorder.spans()}
    assert [s.name for s in recorder.spans()] == [
        'fetch', 'build', 'call', 'step']       # in the order they closed
    assert by_name['step'].parent == 0
    assert by_name['fetch'].parent == by_name['call'].parent == root.id
    assert by_name['build'].parent == call.id != fetch.id
    assert by_name['step'].attrs == {'step': 7}
    assert (by_name['fetch'].start_ns, by_name['fetch'].end_ns) == (
        1_010, 1_015)
    assert by_name['step'].end_ns - by_name['step'].start_ns == 135
    assert len({s.id for s in recorder.spans()}) == 4


def test_a_raised_exception_closes_its_spans(recorder, clock):
    with pytest.raises(KeyError):
        with recorder.span('outer') as outer:
            with recorder.span('inner'):
                clock.tick(3)
                raise KeyError('boom')
    assert recorder.current() is None
    inner, closed_outer = recorder.spans()
    assert (inner.name, inner.parent) == ('inner', outer.id)
    assert inner.end_ns - inner.start_ns == 3
    assert closed_outer.parent == 0
    with recorder.span('after'):
        pass
    assert recorder.spans('after')[0].parent == 0


def test_self_time_is_duration_minus_what_children_cover(recorder, clock):
    """A grandchild lies inside its parent's interval: the root gives
    up that time once, to its child, and not a second time."""
    with recorder.span('root'):
        clock.tick(1_000_000)
        with recorder.span('child'):
            clock.tick(1_000_000)
            with recorder.span('grandchild'):
                clock.tick(3_000_000)
            clock.tick(1_000_000)
        with recorder.span('child'):
            clock.tick(3_000_000)
        clock.tick(1_000_000)
    snap = recorder.snapshot()
    assert snap['root'] == {'mean_ms': 10.0, 'total_ms': 10.0, 'count': 1,
                            'self_ms': 2.0, 'max_ms': 10.0}
    assert snap['child'] == {'mean_ms': 4.0, 'total_ms': 8.0, 'count': 2,
                             'self_ms': 5.0, 'max_ms': 5.0}
    assert snap['grandchild']['self_ms'] == 3.0


def test_the_ring_drops_the_oldest_and_keeps_the_aggregates(clock):
    recorder = tracing.Recorder(ring=4, clock=clock)
    for i in range(10):
        with recorder.span('s', i=i):
            clock.tick(1_000_000 * (i + 1))
    assert [s.attrs['i'] for s in recorder.spans()] == [6, 7, 8, 9]
    assert recorder.snapshot()['s'] == {
        'mean_ms': 5.5, 'total_ms': 55.0, 'count': 10, 'self_ms': 55.0,
        'max_ms': 10.0}
    assert tracing.RING_SPANS == 65536


def test_counters_and_gauges_are_plain_numbers_by_name(recorder):
    recorder.count('kfac/builds')
    recorder.count('kfac/builds')
    recorder.count('rows', 40)
    recorder.gauge('kfac/state_bytes/total', 10)
    recorder.gauge('kfac/state_bytes/total', 7)
    assert recorder.counters() == {'kfac/builds': 2, 'rows': 40,
                                   'kfac/state_bytes/total': 7}
    recorder.clear()
    assert recorder.counters() == {} and recorder.spans() == []


def test_attributes_set_while_open_reach_the_record(recorder):
    with recorder.span('flush', records=0) as s:
        s.set(records=64, blocked_ms=1.5)
        assert recorder.current() is s
    assert recorder.spans()[0].attrs == {'records': 64, 'blocked_ms': 1.5}


def test_a_cancelled_span_leaves_no_record(recorder, clock):
    with recorder.span('step') as root:
        with recorder.span('fetch') as fetch:
            clock.tick(9)
            fetch.cancel()
        root.cancel()
    assert recorder.spans() == [] and recorder.snapshot() == {}
    with recorder.span('step'):
        clock.tick(2)
    assert recorder.snapshot()['step']['count'] == 1


def test_each_thread_has_its_own_open_span_and_no_update_is_lost():
    """More workers than cores, a short switch interval: the parent of
    a span is the span open on its own thread, and every span and count
    arrives."""
    import sys
    recorder = tracing.Recorder()
    workers, rounds = 16, 400
    wrong = []

    def work(k):
        for _ in range(rounds):
            with recorder.span(f'outer{k}') as outer:
                with recorder.span(f'inner{k}') as inner:
                    if inner.parent != outer.id:
                        wrong.append(k)
                recorder.count('rounds')

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert wrong == []
    assert recorder.counters()['rounds'] == workers * rounds
    snap = recorder.snapshot()
    assert all(snap[f'{kind}{k}']['count'] == rounds
               for k in range(workers) for kind in ('outer', 'inner'))
    assert len({s.id for s in recorder.spans()}) == 2 * workers * rounds


def test_the_reference_forms_read_and_write_the_recorder(fresh):
    @utils.trace(sync=True, name='work')
    def work(x):
        return x * 2

    for _ in range(3):
        work(jnp.ones(4))
    tracing.record('measured_elsewhere', 0.25)
    assert [s.name for s in tracing.spans()] == ['work'] * 3 + [
        'measured_elsewhere']
    mean, total = utils.get_trace(), utils.get_trace(average=False)
    assert 0 < mean['work'] <= total['work']
    assert mean['measured_elsewhere'] == pytest.approx(0.25)
    snap = tracing.snapshot_trace()
    assert snap['work']['count'] == 3
    assert set(snap['work']) == {'mean_ms', 'total_ms', 'count',
                                 'self_ms', 'max_ms'}
    assert snap['measured_elsewhere']['total_ms'] == pytest.approx(250.0)
    utils.clear_trace()
    assert utils.get_trace() == {} and tracing.spans() == []
    assert not hasattr(tracing, '_FUNC_TRACES')


def test_get_trace_max_history_reads_the_newest_spans(fresh):
    for seconds in (4.0, 1.0, 3.0):
        tracing.record('w', seconds)
    assert tracing.get_trace(max_history=2)['w'] == pytest.approx(2.0)
    assert tracing.get_trace(average=False, max_history=2)['w'] == \
        pytest.approx(4.0)
    assert tracing.get_trace()['w'] == pytest.approx(8.0 / 3)


# -- what the program leaves in it ------------------------------------------

class TinyMLP(nn.Module):
    @nn.compact
    def __call__(self, x):
        x = nn.tanh(nn.Dense(8, name='d0')(x))
        return nn.Dense(4, name='head')(x)


HYPER = {'lr': 0.05, 'damping': 0.01, 'factor_update_freq': 1,
         'inv_update_freq': 2}
BOTH, FACTOR_ONLY = ('factor=True,inv=True,chunk=None',
                     'factor=True,inv=False,chunk=None')
STEPS = 6


@pytest.fixture(scope='module')
def toy_epoch(tmp_path_factory):
    """One ``train_epoch`` of a toy model through the built K-FAC step,
    with a sink that drains every 4 records."""
    tracing.clear_trace()
    kfac = KFAC(TinyMLP(), factor_update_freq=1, inv_update_freq=2,
                factor_decay=0.5, damping=0.01, lr=0.1, kl_clip=None)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    params = kfac.init(jax.random.PRNGKey(0), x)[0]['params']
    mesh = D.make_kfac_mesh(jax.devices()[:2],
                            comm_method=CommMethod.COMM_OPT,
                            grad_worker_fraction=0.5)
    dkfac = D.DistributedKFAC(kfac, mesh, params)
    kstate = dkfac.init_state(params)
    after_init = tracing.counters()
    tx = optax.sgd(0.05)
    step = dkfac.build_train_step(lambda out, b: jnp.mean(out ** 2), tx,
                                  donate=False)
    path = str(tmp_path_factory.mktemp('spans') / 'run.jsonl')
    sink = obs_sink.JsonlMetricsSink(path, drain_every=4)
    state = engine.TrainState(params, tx.init(params), kstate, {})
    batch = (x, jnp.zeros((16,), jnp.int32))
    engine.train_epoch(step, state, iter([batch] * STEPS), HYPER,
                       metrics_sink=sink)
    sink.close()
    out = {'spans': tracing.spans(), 'counters': tracing.counters(),
           'after_init': after_init, 'kstate': kstate, 'step': step,
           'records': obs_sink.read_jsonl(path, validate=True),
           'args': (params, tx.init(params), kstate, {}, batch, HYPER)}
    tracing.clear_trace()
    return out


def test_every_step_has_one_root_span_with_its_children_in_order(
        toy_epoch):
    spans = toy_epoch['spans']
    roots = [s for s in spans if s.name == 'kfac/host/step']
    assert [s.attrs['step'] for s in roots] == list(range(STEPS))
    assert [s.attrs['fired'] for s in roots] == ['inverse', 'factor'] * 3
    assert all(s.parent == 0 for s in roots)
    for root in roots:
        children = sorted((s for s in spans if s.parent == root.id),
                          key=lambda s: s.start_ns)
        assert [s.name for s in children] == [
            'kfac/host/next_batch', 'kfac/host/step_call',
            'kfac/host/sink', 'kfac/host/meters']
        assert root.start_ns <= children[0].start_ns
        assert children[-1].end_ns <= root.end_ns
        assert all(a.end_ns <= b.start_ns
                   for a, b in zip(children, children[1:]))
    # The end of the data is no step and no wait for a batch.
    assert len([s for s in spans
                if s.name == 'kfac/host/next_batch']) == STEPS
    assert not any(s.name.startswith('kfac/host/hook/') for s in spans)


def test_the_sinks_host_step_ms_is_the_step_call_span(toy_epoch):
    calls = [s for s in toy_epoch['spans']
             if s.name == 'kfac/host/step_call']
    steps = [r for r in toy_epoch['records'] if r['kind'] == 'step']
    assert [r['host_step_ms'] for r in steps] == [
        (s.end_ns - s.start_ns) / 1e6 for s in calls]


def test_a_drain_leaves_a_sink_flush_span_inside_its_step(toy_epoch):
    spans = toy_epoch['spans']
    by_id = {s.id: s for s in spans}
    flushes = [s for s in spans if s.name == 'kfac/host/sink_flush']
    full = [s for s in flushes if s.attrs['records'] == 4]
    assert full, [s.attrs for s in flushes]
    for s in flushes:
        assert s.attrs['blocked_ms'] >= 0 and s.attrs['write_ms'] > 0
        assert s.attrs['bytes'] > 0
    # The drain that step_record sets off runs inside that step's sink
    # span; the one at the epoch's end is under no step.
    inside = [s for s in flushes if s.parent
              and by_id[s.parent].name == 'kfac/host/sink']
    assert inside and flushes[-1].parent == 0


def test_a_variant_is_built_once_and_says_where_the_time_went(toy_epoch):
    builds = [s for s in toy_epoch['spans']
              if s.name.startswith(D.BUILD_SPAN_PREFIX)]
    assert sorted(s.name for s in builds) == sorted(
        D.BUILD_SPAN_PREFIX + label for label in (BOTH, FACTOR_ONLY))
    by_id = {s.id: s for s in toy_epoch['spans']}
    for s in builds:
        assert by_id[s.parent].name == 'kfac/host/step_call'
        assert s.attrs['trace_s'] > 0 and s.attrs['lower_s'] > 0
        assert s.attrs['backend_s'] > 0
        # conftest turns the persistent cache off: every build compiles.
        assert s.attrs['cache_hit'] is False
        assert s.attrs['cache_retrieval_s'] == 0
        assert (s.attrs['trace_s'] + s.attrs['lower_s']
                + s.attrs['backend_s']) <= (s.end_ns - s.start_ns) / 1e9
    assert toy_epoch['counters']['kfac/builds'] == 2
    assert 'kfac/retraces' not in toy_epoch['counters']
    events = {r['data']['variant']: r['data']
              for r in toy_epoch['records'] if r.get('event') == 'compile'}
    assert set(events) == {BOTH, FACTOR_ONLY}
    for s in builds:
        event = events[s.name[len(D.BUILD_SPAN_PREFIX):]]
        assert event['first_call_ms'] == (s.end_ns - s.start_ns) / 1e6
        assert event['backend_s'] == s.attrs['backend_s']
        assert event['cache_hit'] is False


def test_the_state_gauges_equal_the_state_footprint(toy_epoch):
    footprint = obs_memory.state_footprint(toy_epoch['kstate'])
    gauges = toy_epoch['after_init']
    assert gauges['kfac/state_bytes/total'] == footprint['total_bytes'] > 0
    assert {k: v for k, v in gauges.items() if k.endswith(
        ('/factors', '/inverses'))} == {
        f'kfac/state_bytes/{g}': footprint['by_group'][g]
        for g in ('factors', 'inverses')}


def test_the_epoch_record_carries_aggregates_and_counters(toy_epoch):
    epoch = [r for r in toy_epoch['records'] if r['kind'] == 'epoch'][-1]
    row = epoch['trace']['kfac/host/step']
    assert row['count'] == STEPS
    assert 0 < row['self_ms'] < row['total_ms']
    assert row['max_ms'] >= row['mean_ms']
    assert epoch['counters']['kfac/builds'] == 2
    assert epoch['counters']['kfac/state_bytes/total'] > 0


def _scopes_in(lowered) -> set:
    text = lowered.compile().as_text()
    return {m for m in re.findall(r'kfac_step/\w+', ' '.join(
        re.findall(r'op_name="([^"]*)"', text)))}


def test_the_steps_hlo_carries_both_step_scopes(toy_epoch):
    step = toy_epoch['step']
    lowered = jax.jit(lambda *a: step(*a, factor_update=True,
                                      inv_update=False)).lower(
        *toy_epoch['args'])
    assert _scopes_in(lowered) == {'kfac_step/fwd_bwd',
                                   'kfac_step/optimizer'}
    # Lowering it here was a second trace of a built variant, and the
    # program counted it.
    assert tracing.counters().get('kfac/retraces') == 1
    tracing.clear_trace()


def test_the_sgd_steps_hlo_carries_them_too():
    model = TinyMLP()
    x = jnp.ones((16, 8))
    params = model.init(jax.random.PRNGKey(0), x)['params']
    tx = optax.sgd(0.05)
    step = engine.build_sgd_train_step(
        model, lambda out, b: jnp.mean(out ** 2), tx, donate=False)
    lowered = step.lower(params, tx.init(params), None, {},
                         (x, jnp.zeros((16,), jnp.int32)), {})
    assert _scopes_in(lowered) == {'kfac_step/fwd_bwd',
                                   'kfac_step/optimizer'}
