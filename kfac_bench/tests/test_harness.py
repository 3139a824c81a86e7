"""The harness at toy size on the CPU: the result line's keys, no device
metric without a TPU, and names that find nothing."""

import json

import pytest

from conftest import TOY, TOY_SPEC
from kfac_bench import run


@pytest.fixture(scope='module')
def toy_result():
    code, result = run.run_cell(TOY, 3000000019, 0.5, False,
                                spec_path=TOY_SPEC, require_chip=False)
    assert code == 0
    return result


def test_result_line_has_the_contract_keys(toy_result):
    line = json.loads(json.dumps(toy_result))
    for key in ('correct', 'attempted', 'failed', 'metrics', 'device'):
        assert key in line
    assert list(line)[-1] == 'checks'
    assert line['correct'] is True and line['failed'] == 0
    assert line['attempted'] % 4 == 0 and line['attempted'] >= 4
    assert {'platform', 'kind', 'count'} <= set(line['device'])
    for check in line['checks'].values():
        assert check['limit'] is not None and check['value'] <= check['limit']


def test_no_device_metric_without_a_tpu(toy_result):
    assert toy_result['device']['platform'] != 'tpu'
    assert toy_result['metrics'] == {}
    assert 'not_measured' in toy_result


def test_nothing_compiles_in_the_window(toy_result):
    assert toy_result['info']['builds_in_window'] == 0
    assert toy_result['info']['traces_in_window'] == 0
    assert set(toy_result['info']['trace_counts'].values()) == {1}


def test_a_stall_would_name_itself(toy_result):
    info = toy_result['info']
    index, stage, ms, excess, dispatch = info['slowest_steps'][0]
    assert 0 <= index < toy_result['attempted'] and ms >= excess
    assert stage in info['stage_ms_median'] and dispatch >= 0
    log = info['gc']
    assert log['setup_full_collections_s']   # the one before the window
    assert log['window_seconds'] < 0.05
    assert all(gen < 2 or secs < 0.02
               for gen, secs, _ in log['window_longest'])
    assert info['phases_s']['gc_collect_s'] > 0


def test_the_command_refuses_without_a_tpu(capsys):
    code = run.main(['--workload', 'gpt2s_f1i10', '--seed', '1',
                     '--seconds', '1', '--trace', '0'])
    captured = capsys.readouterr()
    assert code != 0
    assert captured.out == ''
    assert 'TPU' in captured.err


def _spec(tmp_path, name='config.json', **config_changes):
    with open(TOY_SPEC) as f:
        spec = json.load(f)
    with open(run.os.path.join(run.ROOT, spec['configs'][0]['file'])) as f:
        config = json.load(f)
    config.update(config_changes)
    path = tmp_path / name
    path.write_text(json.dumps(config))
    spec['configs'][0]['file'] = str(path)
    return spec


def _run(tmp_path, spec, workload=TOY):
    path = tmp_path / 'spec.json'
    path.write_text(json.dumps(spec))
    return run.run_cell(workload, 1, 0.1, False, spec_path=str(path),
                        require_chip=False)


def test_unknown_names_fail_with_what_was_found(tmp_path, monkeypatch):
    spec = _spec(tmp_path)
    with pytest.raises(run.SpecError, match=r"found \['toy_f1i4'\]"):
        _run(tmp_path, spec, workload='nope')
    broken = json.loads(json.dumps(spec))
    broken['workloads'][0]['config'] = 'nope'
    with pytest.raises(run.SpecError, match=r"found \['toy-lm'\]"):
        _run(tmp_path, broken)
    broken = json.loads(json.dumps(spec))
    broken['workloads'][0]['traffic'] = 'nope'
    with pytest.raises(run.SpecError, match='seq1024_b8_f1i10'):
        _run(tmp_path, broken)
    with pytest.raises(run.SpecError, match=r"family 'nope'; found \['lm'\]"):
        _run(tmp_path, _spec(tmp_path, 'odd_family.json', family='nope'))
    metrics = tmp_path / 'metrics'
    metrics.mkdir()
    (metrics / 'odd.json').write_text(json.dumps({'reader': 'nope'}))
    monkeypatch.setitem(run.METRIC_DIRS, 'per_layer', str(metrics))
    broken = json.loads(json.dumps(spec))
    broken['per_layer'] = [{'name': 'odd', 'unit': 'ms', 'better': 'lower',
                            'source': 'host_clock', 'layer': 'x',
                            'moves': 'tokens_per_s'}]
    with pytest.raises(run.SpecError, match=r"reader 'nope'.*scope_ms"):
        _run(tmp_path, broken)
    broken['per_layer'][0]['name'] = 'missing'
    with pytest.raises(run.SpecError, match=r"'missing'.*\['odd'\]"):
        _run(tmp_path, broken)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(run.SpecError, match='TPU v5 lite'):
        run.chip_peaks('TPU v9 imaginary')
    assert run.chip_peaks('TPU v5 lite')['bf16_flops'] == 197e12
