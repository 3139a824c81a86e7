"""The comparison that decides ``correct`` has to be able to fail.

At toy size on the CPU: the control (the reference with every
contraction's operands rounded to float8_e4m3) and the planted faults
come out as not correct, and a whole run with the timed path broken
underneath sees ``correct`` come out false. On the chip, at the cells'
own sizes, ``kfac_bench/control.py`` reads the same (PERF.md gives the
readings)."""

import functools

import jax
import jax.numpy as jnp
import pytest

from conftest import TOY, TOY_SPEC
from kfac_bench import control, run


@pytest.fixture(scope='module')
def records():
    return list(control.read(TOY, [7, 8, 9], spec_path=TOY_SPEC))


@pytest.mark.parametrize('mode', list(control.MODES))
def test_control_and_faults_are_not_correct(records, mode):
    mine = [r for r in records if r['mode'] == mode]
    assert len(mine) == 3
    for record in mine:
        assert record['correct'] is False, record


def test_unchanged_state_reads_one(records):
    for record in records:
        if record['mode'] == 'unchanged_state':
            assert record['checks']['dparam_gap']['value'] == \
                pytest.approx(1.0)


@pytest.fixture(scope='module')
def program_records():
    return {dtype: list(control.read_program(TOY, [7, 8, 9], dtype,
                                             spec_path=TOY_SPEC))
            for dtype in (None, control.NARROW_STATE)}


def test_the_program_as_stated_is_correct_on_every_seed(program_records):
    assert [r['correct'] for r in program_records[None]] == [True] * 3


@pytest.mark.parametrize('number', ['factor_gap', 'factor_median_gap'])
def test_narrower_kfac_state_fails_the_state_readings(program_records,
                                                      number):
    """The program's own bfloat16 factors and inverses, where the
    configuration states float32: the control of the stored state."""
    for record in program_records[control.NARROW_STATE]:
        assert record['correct'] is False
        check = record['checks'][number]
        assert check['ok'] is False and check['value'] > 10 * check['limit']


def _unchanged_state(cell):
    real = cell.step_fn

    @functools.wraps(real)
    def broken(params, opt_state, *rest, **flags):
        keep = jax.tree.map(jnp.copy, (params, opt_state))
        out = real(params, opt_state, *rest, **flags)
        return (*keep, *out[2:])

    cell.step_fn = broken


def _half_batch(cell):
    real = cell.step_fn

    @functools.wraps(real)
    def broken(params, opt_state, kstate, extra, batch, hyper, **flags):
        ids, targets, key = batch
        n = ids.shape[0] // 2
        return real(params, opt_state, kstate, extra,
                    (ids[:n], targets[:n], key), hyper, **flags)

    cell.step_fn = broken


@pytest.mark.parametrize('fault', [_unchanged_state, _half_batch])
def test_a_run_with_the_timed_path_broken_is_not_correct(fault):
    code, result = run.run_cell(TOY, 21, 0.1, False, spec_path=TOY_SPEC,
                                require_chip=False, sabotage=fault)
    assert code == 0
    assert result['correct'] is False
    failed = [k for k, c in result['checks'].items() if not c['ok']]
    assert failed, result['checks']
