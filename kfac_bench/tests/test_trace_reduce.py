"""The reduction from a trace to numbers, on a small recorded trace
(``recorded_trace.json``: the start of a traced step of ``txl12_f1i10``
on the v5e, plus one nested ``while`` written by hand) whose idle share
and per-scope times were worked out by hand."""

import json
import os

import pytest

from kfac_bench import trace_reduce
from kfac_bench.readers import device_idle, scope_ms

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope='module')
def loaded():
    with open(os.path.join(HERE, 'recorded_trace.json')) as f:
        return json.load(f)


def test_busy_is_the_union_of_the_intervals(loaded):
    reduced = trace_reduce.reduce(loaded)
    # twenty events that do not overlap (7339 ns) and a 5000 ns while
    # that holds two operations of its body
    assert reduced['busy_s'] == pytest.approx(12339e-9)
    assert reduced['span_s'] == pytest.approx(4588403e-9)
    assert reduced['events'] == 23
    assert reduced['device_ops'][0][0].startswith('%while.90')
    assert reduced['device_ops'][0][1] == pytest.approx(5000e-9)


def test_idle_gaps_are_named_by_what_the_host_was_doing(loaded):
    gaps = dict(trace_reduce.reduce(loaded)['idle_gaps'])
    # the gaps sum to span - busy; the two before 47476064 ns fall into
    # the host's next_batch span, the rest into its dispatch
    assert gaps['bench/next_batch'] == pytest.approx(3555e-9)
    assert gaps['bench/dispatch'] == pytest.approx(4572509e-9)
    assert sum(gaps.values()) == pytest.approx((4588403 - 12339) * 1e-9)


def test_scope_time_counts_a_nested_operation_once(loaded):
    assert trace_reduce.scope_seconds(loaded, ['kfac/precond']) == \
        pytest.approx(2431e-9)
    # 3 + 320 ns of triangular solves and the 5000 ns while; its body's
    # 2000 + 2500 ns lie inside it
    assert trace_reduce.scope_seconds(
        loaded, ['kfac/inverses', 'kfac/inverse/', 'kfac/eigh/']) == \
        pytest.approx(5323e-9)
    assert trace_reduce.scope_seconds(loaded, ['kfac/factors']) == 0.0


def test_readers_return_nothing_where_there_is_nothing_to_read(loaded):
    run = {'trace': loaded, 'steps': 2, 'window_s': 4588403e-9,
           'reduced': trace_reduce.reduce(loaded)}
    assert scope_ms.read(run, scopes=['kfac/precond']) == \
        pytest.approx(2431e-6 / 2)
    assert scope_ms.read(run, scopes=['kfac/factors']) is None
    assert device_idle.read(run) == pytest.approx(
        100 * (1 - 12339 / 4588403))
    assert scope_ms.read({**run, 'trace': None},
                         scopes=['kfac/precond']) is None
    assert device_idle.read({**run, 'reduced': None}) is None


def test_instruction_names_and_wire_format():
    assert trace_reduce._instruction_of(
        '%fusion.3073 = f32[12,1024,4097]{2,1,0} fusion(...)') == \
        'fusion.3073'
    # field 1 (bytes 'ab'), field 2 (varint 300)
    assert list(trace_reduce._fields(b'\x0a\x02ab\x10\xac\x02')) == [
        (1, 2, b'ab'), (2, 0, 300)]
