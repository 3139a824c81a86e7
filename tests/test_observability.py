"""Tests for the r7 observability subsystem.

Covers the ISSUE acceptance surface: metrics-off bit-identity with the
pre-observability step (single-chip AND SPMD), on-device metric
semantics (cadence counts, ν, norms, eigenvalue-floor counts), the
non-finite factor guard, JSONL schema round-trip + rotation + rank
gating, the health-monitor actions, the report CLI over a recorded
file, and the fast-tier CLI smoke (3 CPU steps of the CIFAR entry point
with --kfac-metrics, JSONL validated against the schema).
"""

import os
import warnings

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_kfac_pytorch_tpu.observability import health as obs_health
from distributed_kfac_pytorch_tpu.observability import report as obs_report
from distributed_kfac_pytorch_tpu.observability import sink as obs_sink
from distributed_kfac_pytorch_tpu.preconditioner import KFAC, CommMethod


class MLP(nn.Module):
    @nn.compact
    def __call__(self, x):
        x = nn.tanh(nn.Dense(8, name='d0')(x))
        x = nn.tanh(nn.Dense(8, name='d1')(x))
        return nn.Dense(4, name='head')(x)


def _loss(out):
    return jnp.mean(out ** 2)


def _setup(collect=False, guard=False, **kw):
    kfac = KFAC(MLP(), factor_update_freq=1, inv_update_freq=2,
                factor_decay=0.5, collect_metrics=collect,
                nonfinite_guard=guard, **kw)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 6))
    variables, state = kfac.init(jax.random.PRNGKey(0), x)
    loss, _, grads, captures, _ = kfac.capture.loss_and_grads(
        _loss, variables['params'], x)
    return kfac, variables['params'], state, grads, captures


def _poison(captures, name='d0'):
    """Captures with one NaN in layer ``name``'s output-grad tensor."""
    g0 = captures[name]['g'][0].at[0, 0].set(jnp.nan)
    out = dict(captures)
    out[name] = {'a': captures[name]['a'],
                 'g': (g0,) + tuple(captures[name]['g'][1:])}
    return out


# ---------------------------------------------------------------------------
# Metrics-off bit-identity + on-device metric semantics (single chip)
# ---------------------------------------------------------------------------

def test_metrics_off_state_and_output_unchanged():
    """Off = the pre-observability program: no metrics slot in the
    state, and enabling metrics+guard changes no output bit."""
    k_off, params, s_off, grads, captures = _setup(collect=False)
    k_on, _, s_on, _, _ = _setup(collect=True, guard=True)
    assert 'metrics' not in s_off
    assert 'metrics' in s_on

    step_off = jax.jit(lambda s, g, c: k_off.step(s, g, c))
    step_on = jax.jit(lambda s, g, c: k_on.step(s, g, c))
    for _ in range(3):
        p_off, s_off = step_off(s_off, grads, captures)
        p_on, s_on = step_on(s_on, grads, captures)
        for a, b in zip(jax.tree.leaves(p_off), jax.tree.leaves(p_on)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_metric_counts_and_stats():
    kfac, params, state, grads, captures = _setup(collect=True)
    step = jax.jit(lambda s, g, c: kfac.step(s, g, c))
    for _ in range(3):
        _, state = step(state, grads, captures)
    m = jax.device_get(state['metrics'])
    # freqs: factors every step, inverses every 2nd (steps 0 and 2).
    assert m['factor_updates'] == 3
    assert m['inv_updates'] == 2
    assert m['nonfinite_skips'] == 0
    assert m['damping'] == np.float32(kfac.damping)
    assert 0.0 < m['nu'] <= 1.0
    assert m['grad_norm'] > 0 and m['precond_norm'] > 0
    # bucket keys match the eval_shape-derived state structure: d0/d1
    # share a shape bucket, head has its own.
    assert set(m['bucket_norms']) == set(
        kfac.metric_bucket_keys(params))
    assert all(v > 0 for v in m['bucket_norms'].values())


def test_metric_bucket_keys_match_runtime_grouping():
    kfac, params, state, grads, captures = _setup(collect=True)
    _, stats = kfac.precondition(state, grads, kfac.damping, 0.1,
                                 with_stats=True)
    assert set(stats['bucket_norms']) == set(
        kfac.metric_bucket_keys(params))


def test_nonfinite_guard_skips_factor_update():
    kfac, params, state, grads, captures = _setup(collect=True,
                                                  guard=True)
    bad = _poison(captures)
    step = jax.jit(lambda s, g, c: kfac.step(s, g, c))
    _, new_state = step(state, grads, bad)
    m = jax.device_get(new_state['metrics'])
    assert m['nonfinite_skips'] == 1
    for name in ('d0', 'd1', 'head'):
        for which in ('A', 'G'):
            got = np.asarray(
                jax.device_get(new_state['factors'][name][which]))
            want = np.asarray(jax.device_get(state['factors'][name][which]))
            np.testing.assert_array_equal(got, want)
            assert np.isfinite(got).all()
    # A later finite batch updates factors again (the guard is per-step,
    # not latching).
    _, s2 = step(new_state, grads, captures)
    m2 = jax.device_get(s2['metrics'])
    assert m2['nonfinite_skips'] == 1
    assert m2['factor_updates'] == 2


def test_without_guard_nan_poisons_factors():
    """The counterfactual the guard exists for (reference behavior)."""
    kfac, params, state, grads, captures = _setup()
    _, new_state = jax.jit(lambda s, g, c: kfac.step(s, g, c))(
        state, grads, _poison(captures))
    g_fac = np.asarray(jax.device_get(new_state['factors']['d0']['G']))
    assert not np.isfinite(g_fac).all()


def test_eig_clipped_counts_floored_eigenvalues():
    kfac, params, state, grads, captures = _setup(collect=True)
    # Force a floored spectrum into the stored inverses: dA <= 0 entries
    # are exactly what batched_eigh(clip=0.0) leaves behind.
    state['inverses']['d0']['dA'] = (
        state['inverses']['d0']['dA'].at[0].set(0.0))
    # inv_update=False keeps the doctored inverses in place.
    _, new_state = kfac.step(state, grads, captures,
                             factor_update=False, inv_update=False)
    assert jax.device_get(new_state['metrics'])['eig_clipped'] == 1


# ---------------------------------------------------------------------------
# SPMD path (8-device CPU mesh from conftest)
# ---------------------------------------------------------------------------

class SmallCNN(nn.Module):
    @nn.compact
    def __call__(self, x):
        x = nn.relu(nn.Conv(8, (3, 3))(x))
        x = x.reshape(x.shape[0], -1)
        return nn.Dense(10)(x)


def _run_distributed(collect, n_steps=3):
    from distributed_kfac_pytorch_tpu import launch
    from distributed_kfac_pytorch_tpu.parallel import distributed as D

    kfac = KFAC(SmallCNN(), factor_update_freq=1, inv_update_freq=2,
                damping=0.003, lr=0.1,
                comm_method=CommMethod.HYBRID_OPT,
                grad_worker_fraction=0.5,
                collect_metrics=collect, nonfinite_guard=collect)
    variables, _ = kfac.init(jax.random.PRNGKey(0),
                             jnp.zeros((2, 8, 8, 3)))
    params = variables['params']
    mesh = D.make_kfac_mesh(comm_method=CommMethod.HYBRID_OPT,
                            grad_worker_fraction=0.5)
    dkfac = D.DistributedKFAC(kfac, mesh, params)
    kstate = dkfac.init_state(params)
    tx = optax.sgd(0.05, momentum=0.9)
    opt_state = tx.init(params)

    def loss_fn(out, batch):
        return optax.softmax_cross_entropy_with_integer_labels(
            out, batch[1]).mean()

    # Dynamic cadence (no static flags): ONE compiled program per run —
    # the on-device lax.cond path exercises both gate branches across
    # the 3 steps while keeping this 1-core-CPU test affordable (the
    # static-flag variants are covered by the single-chip tests and the
    # CLI smoke).
    step = dkfac.build_train_step(loss_fn, tx, donate=False)
    rng = np.random.default_rng(0)
    raw = [(rng.normal(size=(32, 8, 8, 3)).astype(np.float32),
            rng.integers(0, 10, 32).astype(np.int32))
           for _ in range(n_steps)]
    extra, metrics = {}, None
    hyper = {'lr': 0.05, 'damping': 0.003,
             'factor_update_freq': 1, 'inv_update_freq': 2}
    for batch in launch.global_batches(mesh, iter(raw)):
        params, opt_state, kstate, extra, metrics = step(
            params, opt_state, kstate, extra, batch, hyper)
    return (jax.device_get(params), jax.device_get(metrics),
            jax.device_get(kstate))


@pytest.mark.slow
def test_distributed_metrics_off_bit_identity_and_values():
    """SPMD analogue of the fast-tier single-chip bit-identity pin.

    slow-marked: two full distributed train-step compiles on the 8-dev
    CPU mesh (~20 s single-core) — the fast tier keeps the single-chip
    identity pin and the CLI smoke; this and the multihost sink test
    run in the default full tier.
    """
    p_off, m_off, ks_off = _run_distributed(False)
    p_on, m_on, ks_on = _run_distributed(True)
    for a, b in zip(jax.tree.leaves(p_off), jax.tree.leaves(p_on)):
        np.testing.assert_array_equal(a, b)
    assert 'metrics' not in ks_off
    assert not any(k.startswith('kfac/') for k in m_off)
    # Step metrics expose the flattened on-device telemetry.
    assert m_on['kfac/factor_updates'] == 3
    assert m_on['kfac/inv_updates'] == 2
    assert m_on['kfac/nonfinite_skips'] == 0
    assert 0.0 < m_on['kfac/nu'] <= 1.0
    assert m_on['kfac/grad_norm'] > 0
    assert any(k.startswith('kfac/bucket_norm/') for k in m_on)
    # ... and the state carries the same values (the drain source).
    assert ks_on['metrics']['factor_updates'] == 3


# ---------------------------------------------------------------------------
# Sink: schema round-trip, atomicity, rotation, rank gating
# ---------------------------------------------------------------------------

def _write_run(path, n=5, monitor=None, interval=1, **sink_kw):
    s = obs_sink.JsonlMetricsSink(str(path), interval=interval,
                                  monitor=monitor,
                                  meta={'run': 'unit'}, **sink_kw)
    for i in range(n):
        s.step_record(i, {'loss': 1.0 / (i + 1),
                          'kfac/damping': 0.003,
                          'kfac/nu': 0.5,
                          'kfac/grad_norm': 2.0,
                          'kfac/precond_norm': 1.0,
                          'kfac/factor_updates': i + 1,
                          'kfac/inv_updates': (i // 2) + 1,
                          'kfac/nonfinite_skips': 0,
                          'kfac/eig_clipped': 0,
                          'kfac/bucket_norm/8x7': 0.4},
                      host_step_ms=1.5)
    s.epoch_record(0, {'loss': 0.5, 'ms_per_iter': 2.0},
                   trace={'train_step': {'mean_ms': 2.0,
                                         'total_ms': 10.0, 'count': n}})
    s.close()
    return s


def test_sink_schema_roundtrip(tmp_path):
    path = tmp_path / 'run.jsonl'
    _write_run(path)
    records = obs_sink.read_jsonl(str(path))  # validates every line
    kinds = [r['kind'] for r in records]
    assert kinds == ['meta'] + ['step'] * 5 + ['epoch']
    assert records[0]['meta'] == {'run': 'unit'}
    assert records[1]['metrics']['kfac/factor_updates'] == 1
    assert records[1]['host_step_ms'] == 1.5
    assert records[-1]['trace']['train_step']['count'] == 5
    # device scalars: a jnp array value must round-trip as a float
    s = obs_sink.JsonlMetricsSink(str(tmp_path / 'dev.jsonl'))
    s.step_record(0, {'loss': jnp.float32(0.25)})
    s.close()
    rec = obs_sink.read_jsonl(str(tmp_path / 'dev.jsonl'))[0]
    assert rec['metrics']['loss'] == 0.25


def test_sink_interval_thins_step_records(tmp_path):
    path = tmp_path / 'run.jsonl'
    _write_run(path, n=10, interval=4)
    steps = [r['step'] for r in obs_sink.read_jsonl(str(path))
             if r['kind'] == 'step']
    assert steps == [0, 4, 8]


def test_sink_nonfinite_values_roundtrip(tmp_path):
    path = tmp_path / 'nan.jsonl'
    s = obs_sink.JsonlMetricsSink(str(path))
    s.step_record(0, {'loss': float('nan'), 'kfac/grad_norm':
                      float('inf')})
    s.close()
    rec = obs_sink.read_jsonl(str(path))[0]  # schema-valid
    assert np.isnan(float(rec['metrics']['loss']))
    assert np.isinf(float(rec['metrics']['kfac/grad_norm']))


def test_sink_fresh_run_clears_previous_segments(tmp_path):
    """A new sink owns its path: a prior run's live file and rotated
    segments are removed so read_jsonl cannot stitch two runs into one
    chimeric stream (the CLIs reuse a default <log-dir> path)."""
    path = tmp_path / 'runA.jsonl'
    s = obs_sink.JsonlMetricsSink(str(path), rotate_bytes=200,
                                  drain_every=2)
    for i in range(12):
        s.step_record(i, {'loss': float(i)})
    s.close()
    assert any('.jsonl.' in f.name for f in tmp_path.iterdir()), \
        'run A should have rotated at least one segment'
    s2 = obs_sink.JsonlMetricsSink(str(path), meta={'run': 'B'})
    s2.step_record(0, {'loss': 5.0})
    s2.close()
    records = obs_sink.read_jsonl(str(path))
    assert [r['kind'] for r in records] == ['meta', 'step']
    assert records[0]['meta'] == {'run': 'B'}


def test_sink_drain_publishes_mid_epoch(tmp_path):
    """Auto-drain persists to disk (crash durability): records are
    readable after drain_every appends with no flush/close call."""
    path = tmp_path / 'crash.jsonl'
    s = obs_sink.JsonlMetricsSink(str(path), drain_every=4)
    for i in range(9):
        s.step_record(i, {'loss': float(i)})
    # two drains (at 4 and 8) have published without any flush()
    steps = [r['step'] for r in obs_sink.read_jsonl(str(path))]
    assert steps == list(range(8))
    del s  # no close: simulates a crashed process


def test_sink_rank_gating(tmp_path):
    path = tmp_path / 'rank1.jsonl'
    s = obs_sink.JsonlMetricsSink(str(path), process_index=1,
                                  meta={'rank': 1})
    s.step_record(0, {'loss': 1.0})
    s.close()
    assert list(tmp_path.iterdir()) == []


def test_sink_rotation_and_atomicity(tmp_path):
    path = tmp_path / 'rot.jsonl'
    s = obs_sink.JsonlMetricsSink(str(path), rotate_bytes=400,
                                  drain_every=2)
    for i in range(30):
        s.step_record(i, {'loss': float(i)})
    s.close()
    # rotated segments exist, no temp files remain, and the reader
    # reassembles the full stream in order.
    names = sorted(f.name for f in tmp_path.iterdir())
    assert 'rot.jsonl' in names and 'rot.jsonl.1' in names
    assert not any('.tmp.' in n for n in names)
    steps = [r['step'] for r in obs_sink.read_jsonl(str(path))]
    assert steps == list(range(30))


# ---------------------------------------------------------------------------
# Health monitor
# ---------------------------------------------------------------------------

def _step_rec(step, **metrics):
    base = {'kfac/factor_updates': step + 1, 'kfac/damping': 0.003}
    base.update(metrics)
    return {'schema': 1, 'kind': 'step', 'step': step,
            'wall_time': 0.0, 'metrics': base}


def test_health_monitor_nonfinite_actions():
    raise_mon = obs_health.HealthMonitor(action='raise')
    raise_mon.observe(_step_rec(0, **{'kfac/nonfinite_skips': 0}))
    with pytest.raises(obs_health.HealthError):
        raise_mon.observe(_step_rec(1, **{'kfac/nonfinite_skips': 1}))

    warn_mon = obs_health.HealthMonitor(action='warn')
    with pytest.warns(RuntimeWarning, match='non-finite'):
        warn_mon.observe(_step_rec(0, **{'kfac/nonfinite_skips': 1}))

    skip_mon = obs_health.HealthMonitor(action='skip')
    with warnings.catch_warnings():
        warnings.simplefilter('error')
        events = skip_mon.observe(_step_rec(0, loss=float('nan')))
    assert len(events) == 1
    assert skip_mon.summary()['events'] == 1


def test_health_monitor_damping_and_staleness():
    mon = obs_health.HealthMonitor(action='skip', stale_after_steps=2,
                                   damping_jump_factor=5.0)
    assert mon.observe(_step_rec(0)) == []
    jump = mon.observe(_step_rec(1, **{'kfac/damping': 0.3}))
    assert any('jumped' in e for e in jump)
    # factor_updates frozen at 1 -> stale after 2 steps.
    for s in range(2, 5):
        rec = _step_rec(s)
        rec['metrics']['kfac/factor_updates'] = 1
        rec['metrics']['kfac/damping'] = 0.3
        events = mon.observe(rec)
    assert any('stale' in e for e in events)


def test_health_invalid_action_rejected():
    with pytest.raises(ValueError):
        obs_health.HealthMonitor(action='explode')


def test_health_eig_clip_fires_on_rising_edge_only():
    mon = obs_health.HealthMonitor(action='skip')
    assert mon.observe(_step_rec(0, **{'kfac/eig_clipped': 2})) != []
    # same persistent count: no re-fire on every record
    assert mon.observe(_step_rec(1, **{'kfac/eig_clipped': 2})) == []
    assert mon.observe(_step_rec(2, **{'kfac/eig_clipped': 5})) != []
    assert len(mon.events) == 2


def test_sink_raise_action_persists_stream_first(tmp_path):
    """action='raise' must leave the full stream (triggering record
    included) on disk, and a subsequent close() must not duplicate
    lines."""
    path = tmp_path / 'raise.jsonl'
    s = obs_sink.JsonlMetricsSink(
        str(path), drain_every=2,
        monitor=obs_health.HealthMonitor(action='raise'))
    s.step_record(0, {'kfac/nonfinite_skips': 0, 'kfac/damping': 0.003})
    with pytest.raises(obs_health.HealthError):
        s.step_record(1, {'kfac/nonfinite_skips': 1,
                          'kfac/damping': 0.003})
    records = obs_sink.read_jsonl(str(path))
    assert [r['step'] for r in records] == [0, 1]
    assert records[1]['metrics']['kfac/nonfinite_skips'] == 1
    s.close()  # no duplicates after the aborted drain
    assert [r['step'] for r in obs_sink.read_jsonl(str(path))] == [0, 1]


# ---------------------------------------------------------------------------
# Report CLI
# ---------------------------------------------------------------------------

def test_report_cli_on_recorded_file(tmp_path, capsys):
    path = tmp_path / 'run.jsonl'
    _write_run(path)
    assert obs_report.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert 'K-FAC run report' in out
    assert 'train_step' in out          # per-stage breakdown row
    assert 'factor updates: 5' in out
    assert 'no health events.' in out
    assert '8x7' in out                 # bucket table


def test_report_step_time_distribution_and_attribution(tmp_path,
                                                       capsys):
    """r9 satellite: p50/p95/p99/max ms/iter plus attribution of the
    outlier steps to the stage that fired them — the pipelined-firing
    acceptance instrument, backend-independent (host dispatch times)."""
    path = tmp_path / 'run.jsonl'
    s = obs_sink.JsonlMetricsSink(str(path))
    # 16 plain ~10ms steps; one 100ms inverse spike; two 30ms chunks.
    for i in range(16):
        s.step_record(i, {'loss': 1.0}, host_step_ms=10.0 + 0.01 * i)
    s.step_record(16, {'loss': 1.0}, host_step_ms=100.0,
                  fired='inverse')
    s.step_record(17, {'loss': 1.0}, host_step_ms=30.0, fired='chunk0')
    s.step_record(18, {'loss': 1.0}, host_step_ms=30.0, fired='chunk1')
    s.close()
    recs = obs_sink.read_jsonl(str(path))  # 'fired' schema-validates
    summary = obs_report.summarize(recs)
    d = summary['step_time']
    assert d['n_steps'] == 19
    assert 10.0 <= d['p50_ms'] < 11.0
    assert d['max_ms'] == 100.0
    assert d['max_over_median'] > 9.0
    assert d['stages']['inverse']['outliers'] == 1
    assert d['stages']['chunk0']['outliers'] == 1
    assert d['stages']['plain']['outliers'] == 0
    assert obs_report.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert 'distribution (19 steps)' in out
    assert 'by fired stage' in out
    assert 'inverse' in out and 'chunk0' in out


def test_report_lists_surviving_incarnations(tmp_path, capsys):
    path = tmp_path / 'run.jsonl'
    for run in range(2):
        s = obs_sink.JsonlMetricsSink(str(path), meta={'run': run})
        s.step_record(0, {'loss': 1.0})
        s.flush()
    assert obs_report.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert '1 surviving prior incarnation(s)' in out
    assert f'{path}.prev.1  (2 records)' in out


def test_report_cli_rejects_invalid_file(tmp_path, capsys):
    bad = tmp_path / 'bad.jsonl'
    bad.write_text('{"schema": 99, "kind": "step"}\n')
    assert obs_report.main([str(bad)]) == 1
    assert 'error' in capsys.readouterr().err


def test_report_surfaces_health_events(tmp_path, capsys):
    path = tmp_path / 'run.jsonl'
    s = obs_sink.JsonlMetricsSink(str(path))
    s.step_record(0, {'kfac/nonfinite_skips': 0, 'kfac/damping': 0.003})
    s.step_record(1, {'kfac/nonfinite_skips': 1, 'kfac/damping': 0.003})
    s.close()
    assert obs_report.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert 'health event' in out
    assert 'non-finite' in out


# ---------------------------------------------------------------------------
# Profiler scopes
# ---------------------------------------------------------------------------

def test_named_stage_scopes_in_compiled_step():
    """The kfac/* named scopes must reach the compiled program's op
    metadata — that op_name path is exactly what a jax.profiler/XProf
    trace attributes device time by, so this pins the acceptance
    criterion without spinning up the profiler service."""
    kfac, params, state, grads, captures = _setup()
    compiled = jax.jit(
        lambda s, g, c: kfac.step(s, g, c)).lower(
            state, grads, captures).compile()
    hlo = compiled.as_text()
    for scope in ('kfac/factors', 'kfac/inverses', 'kfac/eigh/',
                  'kfac/precond'):
        assert scope in hlo, f'missing stage scope {scope}'


def test_profile_trace_capture(tmp_path):
    """--profile-dir path: start/stop produce an on-disk trace dump and
    the guards (idempotence, rank gating) behave.

    Runs in a SUBPROCESS: once ``jax.profiler.start_trace`` has been
    active in a process, the profiler instrumentation keeps a measurable
    per-dispatch overhead after ``stop_trace`` (observed r7: ~20-30%%
    on later tests, ~200 s over the fast tier on the 1-core CI host) —
    exactly the kind of cross-test pollution the observability
    subsystem itself is not allowed to cause.
    """
    import subprocess
    import sys
    script = """
import os, sys
import jax, jax.numpy as jnp
from distributed_kfac_pytorch_tpu.observability import profiling

out = sys.argv[1]
assert profiling.start_trace(out, process_index=1) is False
assert profiling.start_trace(out, process_index=0) is True
# second start while active is a no-op, not an error
assert profiling.start_trace(out, process_index=0) is False
jax.block_until_ready(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
assert profiling.stop_trace() == out
assert profiling.stop_trace() is None
dumped = [os.path.join(r, f) for r, _, fs in os.walk(out) for f in fs]
assert dumped, 'profiler wrote no trace files'
print('PROFILE_CAPTURE_OK')
"""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, 'PYTHONPATH': repo, 'JAX_PLATFORMS': 'cpu',
           'KFAC_COMPILE_CACHE': '0'}
    env['XLA_FLAGS'] = ' '.join(
        f for f in env.get('XLA_FLAGS', '').split()
        if 'xla_force_host_platform_device_count' not in f)
    proc = subprocess.run([sys.executable, '-c', script, str(tmp_path)],
                          env=env, capture_output=True, text=True,
                          timeout=240)
    assert proc.returncode == 0, \
        f'profile capture failed:\n{proc.stdout}\n{proc.stderr[-3000:]}'
    assert 'PROFILE_CAPTURE_OK' in proc.stdout


# ---------------------------------------------------------------------------
# Legacy trace-table re-exports (satellite: utils.py fold-in)
# ---------------------------------------------------------------------------

def test_utils_trace_reexports_share_table():
    from distributed_kfac_pytorch_tpu import utils
    from distributed_kfac_pytorch_tpu.observability import tracing

    utils.clear_trace()

    @utils.trace(name='reexport_probe')
    def work():
        return 1

    work()
    assert 'reexport_probe' in tracing.get_trace()
    assert [s.name for s in tracing.spans()] == ['reexport_probe']
    assert utils.get_trace is tracing.get_trace
    snap = tracing.snapshot_trace()['reexport_probe']
    assert snap['count'] == 1 and snap['total_ms'] >= 0
    tracing.clear_trace()
    assert utils.get_trace() == {}


# ---------------------------------------------------------------------------
# CI fast-tier smoke: 3 CPU steps of the CIFAR CLI with --kfac-metrics
# ---------------------------------------------------------------------------

def test_cifar_cli_metrics_smoke(tmp_path):
    """The satellite CI smoke: run the real entry point for one tiny
    epoch (synthetic data, 3 steps) with --kfac-metrics and validate
    the emitted JSONL against the schema end to end (including the
    report CLI over it).

    The CLI runs as a SUBPROCESS on a fresh single-device CPU backend:
    (a) it is the real command line, env included; (b) the
    8-virtual-device mesh the suite forces is pure overhead for a
    smoke. (Until PR 21 the CLI's TensorBoard writer also imported
    tensorflow into the process; it writes through tensorboardX now.)
    """
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    mpath = tmp_path / 'metrics.jsonl'
    env = {**os.environ,
           'PYTHONPATH': repo,
           'JAX_PLATFORMS': 'cpu',
           'KFAC_COMPILE_CACHE': '0',
           # Bound the data volume (384 train / 96 test synthetic
           # images): 3 steps at batch 128 — the cost is compile.
           'KFAC_SYNTHETIC_CIFAR': '384'}
    # Single-device child: drop the suite's 8-device CPU force.
    env['XLA_FLAGS'] = ' '.join(
        f for f in env.get('XLA_FLAGS', '').split()
        if 'xla_force_host_platform_device_count' not in f)
    # --kfac-update-freq 1: every step fires both cadences, so the
    # static-cadence engine compiles ONE program variant — the smoke
    # stays fast-tier-affordable (the cadence-variant machinery is
    # covered by the cheaper unit tests above).
    proc = subprocess.run(
        [sys.executable,
         os.path.join(repo, 'examples', 'train_cifar10_resnet.py'),
         '--epochs', '1', '--model', 'resnet20',
         '--batch-size', '128', '--val-batch-size', '96',
         '--kfac-update-freq', '1', '--kfac-cov-update-freq', '1',
         '--no-resume',
         '--log-dir', str(tmp_path / 'logs'),
         '--checkpoint-dir', str(tmp_path / 'ckpt'),
         '--kfac-metrics', str(mpath),
         '--metrics-interval', '1',
         '--health-action', 'raise'],
        env=env, capture_output=True, text=True, timeout=480)
    assert proc.returncode == 0, \
        f'CLI smoke failed:\n{proc.stdout[-2000:]}\n{proc.stderr[-3000:]}'
    records = obs_sink.read_jsonl(str(mpath))  # schema-validated
    steps = [r for r in records if r['kind'] == 'step']
    epochs = [r for r in records if r['kind'] == 'epoch']
    assert len(steps) == 3  # 384 synthetic images / batch 128
    assert len(epochs) == 1
    m = steps[-1]['metrics']
    assert m['kfac/factor_updates'] == 3
    assert m['kfac/inv_updates'] == 3
    assert m['kfac/nonfinite_skips'] == 0
    assert 0.0 < float(m['kfac/nu']) <= 1.0
    assert any(k.startswith('kfac/bucket_norm/') for k in m)
    assert 'loss' in m and 'acc' in m
    # the meta record carries the CLI provenance
    meta = next(r for r in records if r['kind'] == 'meta')
    assert meta['meta']['cli'] == 'train_cifar10_resnet'
    # and the report CLI summarizes it
    assert obs_report.main([str(mpath)]) == 0
