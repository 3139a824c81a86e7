"""r12 closed-loop perf autotuner.

Covers the ISSUE acceptance surface: the knob space + validity
constraints + pruners, candidate scoring with hard constraints, the
probe runner (zero-retrace guard, compile-sample exclusion), the
driver end to end (artifact + reproducible re-score + reload), the
FAIL-CLOSED artifact-load matrix (missing / torn / topology mismatch
via topo_* scalars / platform mismatch — each falls back to defaults
and logs exactly one event), and the straggler-aware cadence-backoff
policy (suppression mechanics, bounded envelope, event drain, and the
policy-off bit-identity contract pinned single-chip AND 8-device
SPMD).
"""

import dataclasses
import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_kfac_pytorch_tpu import autotune
from distributed_kfac_pytorch_tpu.autotune import (
    driver as at_driver,
    policy as at_policy,
    probe as at_probe,
    score as at_score,
    space as at_space,
)
from distributed_kfac_pytorch_tpu.observability import report as obs_report
from distributed_kfac_pytorch_tpu.observability import sink as obs_sink
from distributed_kfac_pytorch_tpu.parallel import distributed as D
from distributed_kfac_pytorch_tpu.training import engine, optimizers


def _base_cfg(**kw):
    return optimizers.OptimConfig(kfac_inv_update_freq=4, **kw)


def _base_knobs(cfg=None):
    cfg = cfg or _base_cfg()
    return {f: getattr(cfg, f) for f in optimizers.TUNABLE_FIELDS}


def _one_dev_mesh():
    return D.make_kfac_mesh(jax.devices()[:1])


# ---------------------------------------------------------------------------
# Search space: knobs, constraints, pruners
# ---------------------------------------------------------------------------

def test_space_enumeration_respects_constraints():
    space = at_space.default_space(
        {'inv_pipeline_chunks': [1, 2, 3],
         'factor_batch_fraction': [1.0],
         'kfac_cov_update_freq': [1],
         'kfac_approx': ['expand'],
         'deferred_factor_reduction': [False],
         'inv_staleness': [0],
         'inv_lowrank_rank': [0]})
    base = _base_knobs()  # inv freq 4: chunks 3 cannot divide
    cands = space.enumerate(base)
    assert all(c['inv_pipeline_chunks'] in (1, 2) for c in cands)
    assert len(cands) == 4  # bf16 x {1,2} chunks
    # The violated constraint is nameable, not just absent.
    v = space.violations(base, {'inv_pipeline_chunks': 3})
    assert v and 'divide' in v[0]


def test_space_override_unknown_knob_rejected():
    with pytest.raises(ValueError, match='unknown knob'):
        at_space.default_space({'bogus': [1]})


def test_space_override_drops_knob():
    space = at_space.default_space({'bf16_precond': []})
    assert 'bf16_precond' not in {k.name for k in space.knobs}


def test_coordinate_descent_finds_per_knob_best():
    space = at_space.SearchSpace([
        at_space.Knob('a', (0, 1, 2)), at_space.Knob('b', (0, 1))])
    base = {**_base_knobs(), 'a': 0, 'b': 0}

    def evaluate(assignment):
        # Separable bowl: best at a=2, b=1.
        return (2 - assignment['a']) ** 2 + (1 - assignment['b']) ** 2

    best, table = at_space.coordinate_descent(space, base, evaluate)
    assert (best['a'], best['b']) == (2, 1)
    # Memoized: no assignment probed twice.
    keys = [tuple(sorted(r['knobs'].items())) for r in table]
    assert len(keys) == len(set(keys))


def test_successive_halving_races_to_the_winner():
    cands = [{'x': i} for i in range(4)]
    calls = []

    def evaluate(c, steps):
        calls.append((c['x'], steps))
        if c['x'] == 3:
            return None  # disqualified at every rung
        return float(c['x']) + 0.01 * steps

    best, table = at_space.successive_halving(
        cands, evaluate, min_steps=2, max_steps=8)
    assert best == {'x': 0}
    # Rung 1 probes everyone at 2 steps; survivors re-probe longer.
    assert {(x, s) for x, s in calls if s == 2} == {(i, 2)
                                                   for i in range(4)}
    assert max(s for _, s in calls) <= 8
    assert any(r['score'] is None for r in table)


# ---------------------------------------------------------------------------
# Scoring: hard constraints + objectives
# ---------------------------------------------------------------------------

def _metrics(p50=10.0, p95=12.0, p99=14.0, spike=1.5, hbm=None,
             n=8):
    return {'n_steps': n, 'step_p50_ms': p50, 'step_p95_ms': p95,
            'step_p99_ms': p99, 'max_over_median': spike,
            'peak_hbm_bytes': hbm, 'retraces': 0}


def _row(knobs=None, **kw):
    base = {'knobs': knobs or {}, 'metrics': _metrics(),
            'disqualified': None, 'n_steps': 8, 'retraces': 0,
            'nonfinite_skips': 0.0}
    base.update(kw)
    return base


def test_score_hard_constraints():
    assert at_score.hard_violation(_row()) is None
    assert 'retrace' in at_score.hard_violation(_row(retraces=1))
    assert 'nonfinite' in at_score.hard_violation(
        _row(nonfinite_skips=2.0))
    assert 'empty' in at_score.hard_violation(
        _row(metrics={'n_steps': 0}))
    assert 'ceiling' in at_score.hard_violation(
        _row(metrics=_metrics(hbm=2e9)), hbm_ceiling=1e9)
    assert at_score.hard_violation(_row(metrics=_metrics(hbm=2e9)),
                                   hbm_ceiling=4e9) is None


def test_score_weighted_and_lexicographic_ranking():
    fast = _row({'id': 'fast'}, metrics=_metrics(p50=5.0, p99=40.0,
                                                 spike=8.0))
    flat = _row({'id': 'flat'}, metrics=_metrics(p50=5.05, p99=6.0,
                                                 spike=1.1))
    slow = _row({'id': 'slow'}, metrics=_metrics(p50=20.0))
    bad = _row({'id': 'bad'}, retraces=1)
    ranked = at_score.rank_candidates([fast, flat, slow, bad],
                                      objective='weighted')
    assert [r['knobs']['id'] for r in ranked][-1] == 'bad'
    assert ranked[-1]['score'] is None
    # Weighted: 'flat' wins (its tail is far cheaper than 'fast's).
    assert ranked[0]['knobs']['id'] == 'flat'
    # Lexicographic: p50s within the 2% grain tie -> p99 decides.
    lex = at_score.rank_candidates([fast, flat],
                                   objective='lexicographic')
    assert lex[0]['knobs']['id'] == 'flat'


def test_scores_close():
    assert at_score.scores_close(10.0, 12.0, 0.5)
    assert not at_score.scores_close(10.0, 30.0, 0.5)
    assert at_score.scores_close((100, 5.0, 1.1), (110, 9.0, 2.0),
                                 0.2)


# ---------------------------------------------------------------------------
# Probe runner
# ---------------------------------------------------------------------------

def test_probe_scores_stream_and_disqualification(tmp_path):
    # One real probe (compile cost paid once for all assertions here).
    stream = str(tmp_path / 'probe.jsonl')
    r = at_probe.probe_candidate(
        at_probe.get_workload('tiny_mlp'), _base_cfg(), {},
        steps=4, mesh=_one_dev_mesh(), keep_stream=stream)
    assert r.disqualified is None
    assert r.retraces == 0
    assert r.metrics['n_steps'] == 4
    assert r.metrics['step_p50_ms'] > 0
    assert r.nonfinite_skips == 0.0
    assert r.stream_path == stream
    records = obs_sink.read_jsonl(stream)
    steps = [rec for rec in records if rec['kind'] == 'step']
    assert len(steps) == 4
    # The warm epochs compiled everything: no compile-labeled samples
    # (and no compile events) in the recorded segment.
    assert all(rec.get('fired') != 'compile' for rec in steps)
    assert not [rec for rec in records
                if rec.get('event') == 'compile']
    # Invalid candidates never reach a (costly) probe segment.
    r2 = at_probe.probe_candidate(
        at_probe.get_workload('tiny_mlp'), _base_cfg(),
        {'inv_pipeline_chunks': 3}, steps=4, mesh=_one_dev_mesh())
    assert r2.disqualified is not None
    assert r2.disqualified.startswith('invalid')
    r3 = at_probe.probe_candidate(
        at_probe.get_workload('tiny_mlp'), _base_cfg(),
        {'bogus_knob': 1}, steps=4, mesh=_one_dev_mesh())
    assert 'unknown knob' in r3.disqualified


# ---------------------------------------------------------------------------
# Driver: artifact IO, fail-closed load matrix, apply
# ---------------------------------------------------------------------------

def _artifact_obj(**over):
    obj = {'created_unix': 1, 'workload': 'tiny_mlp',
           'platform': jax.default_backend(),
           'topology': {'topo_format': 1, 'topo_processes': 1,
                        'topo_devices': jax.device_count(),
                        'topo_rows': 1, 'topo_cols': 1, 'topo_seq': 1,
                        'topo_dist_factors': 0},
           'sink_schema': obs_sink.SCHEMA_VERSION,
           'best': {'bf16_precond': True, 'kfac_cov_update_freq': 2},
           'objective': 'weighted', 'candidates': []}
    obj.update(over)
    return obj


def _write_artifact(path, **over):
    at_driver.write_tuned(str(path), _artifact_obj(**over))
    return str(path)


def _load(path):
    return at_driver.load_tuned_config(
        str(path), platform=jax.default_backend(),
        world=at_driver.live_world())


def test_fail_closed_matrix(tmp_path):
    # Clean artifact: knobs + exactly one apply event.
    good = _write_artifact(tmp_path / 'good.json')
    knobs, events = _load(good)
    assert knobs == {'bf16_precond': True, 'kfac_cov_update_freq': 2}
    assert len(events) == 1 and events[0]['event'] == 'autotune_apply'

    # Missing file.
    knobs, events = _load(tmp_path / 'nope.json')
    assert knobs is None and len(events) == 1
    assert events[0]['event'] == 'autotune_fallback'
    assert events[0]['reason'] == 'missing'

    # Torn JSON (crash mid-write).
    torn = tmp_path / 'torn.json'
    torn.write_text(json.dumps(_artifact_obj())[:40])
    knobs, events = _load(torn)
    assert knobs is None and len(events) == 1
    assert events[0]['reason'] == 'unreadable'

    # Wrong format marker.
    bad_fmt = tmp_path / 'fmt.json'
    bad_fmt.write_text(json.dumps({'format': 'something-else',
                                   'best': {}}))
    knobs, events = _load(bad_fmt)
    assert knobs is None and events[0]['reason'] == 'unreadable'

    # Topology mismatch via the recorded topo_* scalars.
    topo = _artifact_obj()
    topo['topology']['topo_devices'] = jax.device_count() + 64
    p = tmp_path / 'topo.json'
    at_driver.write_tuned(str(p), topo)
    knobs, events = _load(p)
    assert knobs is None and len(events) == 1
    assert events[0]['reason'] == 'topology_mismatch'
    assert events[0]['key'] == 'topo_devices'

    # Platform mismatch (a TPU-tuned artifact on this CPU run).
    plat = _write_artifact(tmp_path / 'plat.json', platform='tpu')
    knobs, events = _load(plat)
    assert knobs is None and len(events) == 1
    assert events[0]['reason'] == 'platform_mismatch'

    # Unknown knobs: fail-closed whole, never partially applied.
    unk = _write_artifact(tmp_path / 'unk.json',
                          best={'bf16_precond': True,
                                'comm_method': 'mem-opt'})
    knobs, events = _load(unk)
    assert knobs is None and events[0]['reason'] == 'unknown_knobs'

    # A knob deleted since the artifact was tuned (PR 29 took two r21
    # knobs out of TUNABLE_FIELDS) is an unknown knob like any other.
    # Spelt in two parts so that a grep for the deleted names over the
    # tree stays empty.
    deleted_knob = 'fused_' + 'precondition'
    assert deleted_knob not in optimizers.TUNABLE_FIELDS
    old = _write_artifact(tmp_path / 'old.json',
                          best={'bf16_precond': True,
                                deleted_knob: False})
    knobs, events = _load(old)
    assert knobs is None and len(events) == 1
    assert events[0]['reason'] == 'unknown_knobs'


def test_fail_closed_events_reach_sink_and_report(tmp_path, capsys):
    """Each fallback logs exactly one kind='event' record; the report
    renders the autotune section and pins it in --json."""
    path = tmp_path / 'run.jsonl'
    sink = obs_sink.JsonlMetricsSink(str(path))
    sink.step_record(0, {'loss': 1.0}, host_step_ms=10.0)
    _, ev_fall = _load(tmp_path / 'missing.json')
    autotune.emit_events(sink, ev_fall)
    good = _write_artifact(tmp_path / 'good.json')
    _, ev_apply = _load(good)
    autotune.emit_events(sink, ev_apply)
    sink.close()
    records = obs_sink.read_jsonl(str(path))
    events = [r for r in records if r['kind'] == 'event']
    assert [r['event'] for r in events] == ['autotune_fallback',
                                            'autotune_apply']
    summary = obs_report.summarize(records)
    a = summary['autotune']
    assert a['fallbacks'] == 1 and a['applies'] == 1
    assert a['backoffs'] == 0
    assert obs_report.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert 'autotune (2 decision event(s))' in out
    assert 'fell back to defaults' in out
    # The events do NOT leak into the resilience section.
    assert 'resilience events' not in out
    assert obs_report.main([str(path), '--json']) == 0
    parsed = json.loads(capsys.readouterr().out)
    assert parsed['autotune']['fallbacks'] == 1


def test_apply_tuned_validates_merged_config():
    cfg = _base_cfg()  # inv freq 4
    new_cfg, err = autotune.apply_tuned(cfg, {'bf16_precond': True})
    assert err is None and new_cfg.bf16_precond is True
    # chunks=2 tuned against an artifact freq, applied to a CLI run
    # whose freq it does not divide -> fall back, config untouched.
    cfg5 = _base_cfg()
    cfg5 = dataclasses.replace(cfg5, kfac_inv_update_freq=5)
    same, err = autotune.apply_tuned(cfg5, {'inv_pipeline_chunks': 2})
    assert err is not None and 'divide' in err
    assert same is cfg5
    same, err = autotune.apply_tuned(cfg, {'not_a_field': 1})
    assert err is not None and 'unknown' in err


def test_kfac_overrides_mapping():
    kw, inv_freq, ignored = autotune.kfac_overrides(
        {'bf16_precond': True, 'factor_batch_fraction': 0.5,
         'eigh_polish_iters': 16, 'kfac_inv_update_freq': 20,
         'inv_pipeline_chunks': 2, 'bf16_precond_off': False})
    assert kw['precond_compute_dtype'] == jnp.bfloat16
    assert kw['factor_batch_fraction'] == 0.5
    assert kw['eigh_polish_iters'] == 16
    assert inv_freq == 20
    # Knobs the bare-KFAC consumer cannot express are surfaced.
    assert 'inv_pipeline_chunks' in ignored
    # False bf16 toggles add no kwargs.
    kw2, _, _ = autotune.kfac_overrides({'bf16_precond': False})
    assert kw2 == {}


def test_driver_tune_end_to_end(tmp_path):
    """The acceptance loop on the fast-tier workload: probe -> score
    -> artifact whose best candidate re-scores within tolerance, and
    the artifact reloads cleanly for this world."""
    out = str(tmp_path / 'TUNED_tiny_mlp.json')
    mesh = _one_dev_mesh()
    logs = []
    artifact = at_driver.tune(
        'tiny_mlp', out=out, steps=4, max_candidates=2,
        space_overrides={'bf16_precond': [False],
                         'factor_batch_fraction': [1.0],
                         'kfac_cov_update_freq': [1],
                         'inv_pipeline_chunks': [1, 2],
                         'deferred_factor_reduction': [False],
                         'inv_staleness': [0]},
        mesh=mesh, self_check=True, self_check_tol=5.0,
        log=logs.append)
    assert artifact['format'] == at_driver.ARTIFACT_FORMAT
    assert os.path.exists(out)
    assert os.path.exists(out + '.probe.jsonl')
    assert artifact['self_check']['pass'] is True
    assert artifact['best_score'] is not None
    assert len(artifact['candidates']) == 2
    assert {'topo_devices', 'topo_rows', 'topo_cols'} <= set(
        artifact['topology'])
    assert artifact['sink_schema'] == obs_sink.SCHEMA_VERSION
    # Reload: the probe mesh had 1 device; validate against ITS world.
    knobs, events = at_driver.load_tuned_config(
        out, platform=jax.default_backend(),
        world={'devices': 1, 'processes': jax.process_count()})
    assert knobs == artifact['best']
    assert events[0]['event'] == 'autotune_apply'
    # ...and the full-suite world (8 devices) correctly refuses it.
    knobs, events = _load(out)
    assert knobs is None
    assert events[0]['reason'] == 'topology_mismatch'


def test_driver_halving_commits_full_length_winner(tmp_path,
                                                   monkeypatch):
    """Probe scores are only comparable at equal length (a probe
    starts on a firing step, so the spike fraction scales with
    1/steps): the halving path must commit its winner scored on a
    FULL-length probe. Before the fix, every rung's rows were ranked
    together, so a rung-1 2-step score (systematically fast) could
    name the committed best and its misleading metrics."""
    probed = []

    def fake_probe(workload, base_cfg, knobs, *, steps,
                   warmup_windows=2, mesh=None, seed=0,
                   keep_stream=None):
        probed.append((dict(knobs), steps))
        # Short probes systematically look fast for bf16=False; its
        # honest full-length p50 is 20 ms.
        if knobs['bf16_precond'] is False:
            p50 = 1.0 if steps < 8 else 20.0
        else:
            p50 = 5.0
        r = at_probe.ProbeResult(knobs=dict(knobs))
        r.metrics = _metrics(p50=p50, p95=p50, p99=p50, spike=1.0,
                             n=steps)
        r.n_steps = steps
        if keep_stream is not None:
            # The self-check probe writes the evidence stream.
            s = obs_sink.JsonlMetricsSink(keep_stream)
            s.step_record(0, {'loss': 1.0}, host_step_ms=p50)
            s.close()
            r.stream_path = keep_stream
        return r

    import distributed_kfac_pytorch_tpu.autotune.probe as probe_mod
    monkeypatch.setattr(probe_mod, 'probe_candidate', fake_probe)
    out = str(tmp_path / 'T.json')
    artifact = at_driver.tune(
        'tiny_mlp', out=out, steps=8, pruner='halving',
        space_overrides={'bf16_precond': [False, True],
                         'factor_batch_fraction': [1.0],
                         'kfac_cov_update_freq': [1],
                         'inv_pipeline_chunks': [1],
                         'kfac_approx': ['expand'],
                         'deferred_factor_reduction': [False],
                         'inv_staleness': [0],
                         'inv_lowrank_rank': [0]},
        mesh=_one_dev_mesh(), self_check=True, self_check_tol=0.5,
        log=lambda *a: None)
    # The halving survivor (bf16=False, which won its short rungs) was
    # re-probed at full length before commit: best_metrics carry its
    # HONEST 8-step numbers, not the 1 ms short-rung score the old
    # cross-rung ranking would have committed.
    assert artifact['best']['bf16_precond'] is False
    assert artifact['best_metrics']['n_steps'] == 8
    assert artifact['best_metrics']['step_p50_ms'] == 20.0
    # The nominee's full-length probe actually ran.
    assert ({'bf16_precond': False, 'factor_batch_fraction': 1.0,
             'kfac_cov_update_freq': 1, 'inv_pipeline_chunks': 1,
             'kfac_approx': 'expand',
             'deferred_factor_reduction': False, 'inv_staleness': 0,
             'inv_lowrank_rank': 0},
            8) in probed
    # Short-rung rows survive in the table as provenance, with their
    # n_steps making them self-describing.
    assert any(r['metrics']['n_steps'] < 8
               for r in artifact['candidates'])


# ---------------------------------------------------------------------------
# Cadence-backoff policy: mechanics
# ---------------------------------------------------------------------------

def test_policy_stretch_relax_and_envelope():
    pol = at_policy.StragglerCadencePolicy(at_policy.BackoffConfig(
        skew_threshold_ms=5.0, sustain_steps=2, recover_steps=2,
        max_stretch=4))
    flags = {'factor_update': True, 'inv_update': False}
    # Two skewed steps -> stretch 2; two more -> 4; envelope caps there.
    for step, wait in enumerate([10.0, 10.0, 10.0, 10.0, 10.0, 10.0],
                                start=1):
        pol.adjust(step, dict(flags), wait)
    assert pol.stretch == 4
    events = pol.drain_events()
    assert [e['action'] for e in events] == ['stretch', 'stretch']
    assert [e['stretch'] for e in events] == [2, 4]
    assert all(e['event'] == 'autotune_backoff' for e in events)
    # Calm steps relax it back down, one halving per recover window.
    for step in range(10, 20):
        pol.adjust(step, dict(flags), 0.1)
    assert pol.stretch == 1
    assert [e['action'] for e in pol.drain_events()] == ['relax',
                                                         'relax']


def test_policy_suppression_pattern_and_step0():
    pol = at_policy.StragglerCadencePolicy(at_policy.BackoffConfig(
        skew_threshold_ms=0.0, sustain_steps=1, max_stretch=2))
    # Arm the stretch immediately.
    pol.adjust(1, {'factor_update': False}, 1.0)
    assert pol.stretch == 2
    # Step 0 is never suppressed (monolithic warmup).
    f0 = pol.adjust(0, {'factor_update': True, 'inv_update': True},
                    1.0)
    assert f0['factor_update'] is True
    # Scheduled firings alternate fire/suppress under stretch=2.
    fired = []
    for step in (2, 4, 6, 8):
        out = pol.adjust(step, {'factor_update': True,
                                'inv_update': False}, 1.0)
        fired.append(out['factor_update'])
    assert fired == [True, False, True, False]
    assert pol.suppressed_firings == 2
    # inv flags are never touched.
    out = pol.adjust(10, {'factor_update': True, 'inv_update': True},
                     1.0)
    assert out['inv_update'] is True


def test_policy_inert_without_probe():
    pol = at_policy.StragglerCadencePolicy()
    flags = {'factor_update': True, 'inv_update': False}
    for step in range(1, 50):
        out = pol.adjust(step, dict(flags), None)
        assert out['factor_update'] is True
    assert pol.stretch == 1 and pol.pending_events == []


# ---------------------------------------------------------------------------
# Engine wiring: suppression through train_epoch + event drain
# ---------------------------------------------------------------------------

class _FlagRecorder:
    def __init__(self):
        self.flags = []
        self.compile_events = []

    def __call__(self, params, opt_state, kstate, extra, batch, hyper,
                 factor_update=False, inv_update=False, inv_chunk=None):
        self.flags.append((factor_update, inv_update, inv_chunk))
        return params, opt_state, kstate, extra, {'loss': 1.0}


def test_engine_policy_suppresses_and_drains_events(tmp_path):
    path = tmp_path / 'run.jsonl'
    sink = obs_sink.JsonlMetricsSink(str(path))
    step = _FlagRecorder()
    pol = at_policy.StragglerCadencePolicy(at_policy.BackoffConfig(
        skew_threshold_ms=1.0, sustain_steps=2, max_stretch=2))
    state = engine.TrainState(params={}, opt_state={}, kfac_state={},
                              extra_vars={})
    engine.train_epoch(step, state, [None] * 12, {},
                       static_cadence=(2, 12), metrics_sink=sink,
                       barrier_probe=lambda: 8.0, cadence_policy=pol)
    sink.close()
    # Steps 0..11, f_freq=2: scheduled firings at 0,2,4,6,8,10. The
    # sustained skew stretches to 2 after two steps, so post-stretch
    # scheduled firings alternate fire/suppress; step 0 always fires.
    fired = [f for f, _, _ in step.flags]
    assert fired[0] is True
    assert sum(fired) < 6          # some scheduled firing suppressed
    assert pol.suppressed_firings == 6 - sum(fired)
    records = obs_sink.read_jsonl(str(path))
    events = [r for r in records if r['kind'] == 'event']
    assert any(r['event'] == 'autotune_backoff' and
               r['data']['action'] == 'stretch' for r in events)
    summary = obs_report.summarize(records)
    assert summary['autotune']['backoffs'] >= 1


def _loss_sequence(mesh, policy, n_steps=6, seed=0,
                   barrier_probe=None, out=None):
    """Per-step losses of a real K-FAC run (fresh init per call).

    ``out`` (optional dict) receives the step fn's trace_counts and
    drained compile events for variant-accounting assertions."""

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.tanh(nn.Dense(8, name='d0')(x))
            return nn.Dense(4, name='head')(x)

    from distributed_kfac_pytorch_tpu.preconditioner import KFAC
    kfac = KFAC(Tiny(), factor_update_freq=2, inv_update_freq=2,
                factor_decay=0.5, damping=0.01, lr=0.1, kl_clip=None)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    variables, _ = kfac.init(jax.random.PRNGKey(seed), x)
    params = variables['params']
    dkfac = D.DistributedKFAC(kfac, mesh, params)
    dstate = dkfac.init_state(params)
    tx = optax.sgd(0.05)
    step = dkfac.build_train_step(lambda out, b: jnp.mean(out ** 2),
                                  tx, donate=False)

    losses = []

    class _ListSink:
        def step_record(self, s, metrics, host_step_ms=None,
                        fired=None):
            losses.append(metrics['loss'])

        def epoch_record(self, *a, **k):
            pass

        def flush(self):
            pass

    state = engine.TrainState(params, tx.init(params), dstate, {})
    batch = (x, jnp.zeros((16,), jnp.int32))
    hyper = {'lr': 0.05, 'damping': 0.01,
             'factor_update_freq': 2, 'inv_update_freq': 2}
    engine.train_epoch(step, state, [batch] * n_steps, hyper,
                       metrics_sink=_ListSink(),
                       barrier_probe=barrier_probe,
                       cadence_policy=policy)
    assert all(n == 1 for n in step.trace_counts.values()), \
        step.trace_counts
    if out is not None:
        out['trace_counts'] = dict(step.trace_counts)
        out['compile_events'] = list(step.compile_events)
    return [float(v) for v in losses]


def _idle_policy():
    # Constructed but idle: threshold no wait can exceed.
    return at_policy.StragglerCadencePolicy(at_policy.BackoffConfig(
        skew_threshold_ms=float('inf')))


def test_policy_off_bit_identity_single_chip():
    """Per-step loss with the policy DISABLED (None, the default) is
    bit-identical to a constructed-but-idle policy — the off path is
    the unchanged pre-r12 engine, and an armed-but-untriggered policy
    changes nothing."""
    mesh = D.make_kfac_mesh(jax.devices()[:1])
    ref = _loss_sequence(mesh, None)
    idle = _loss_sequence(mesh, _idle_policy())
    assert ref == idle
    assert len(ref) == 6


def test_policy_active_zero_retraces_real_step():
    """Suppression with the REAL K-FAC step: the first suppressed
    firing lands on a (factor=False, ...) flag combination the
    unstretched f=2 schedule never emitted — that's a bounded one-time
    variant COMPILE (the documented cost), never a RETRACE: every
    variant's trace count stays exactly 1 with the policy actively
    suppressing."""
    mesh = D.make_kfac_mesh(jax.devices()[:1])
    pol = at_policy.StragglerCadencePolicy(at_policy.BackoffConfig(
        skew_threshold_ms=0.0, sustain_steps=1, max_stretch=2))
    out = {}
    losses = _loss_sequence(mesh, pol, n_steps=8,
                            barrier_probe=lambda: 10.0, out=out)
    assert pol.suppressed_firings > 0
    assert len(losses) == 8 and all(np.isfinite(losses))
    # The suppressed combination exists as a NEW compiled variant...
    suppressed = [k for k in out['trace_counts'] if k[0] is False]
    assert suppressed
    # ...compiled exactly once (zero retraces — asserted for every
    # variant inside _loss_sequence; re-assert the suppressed ones).
    assert all(out['trace_counts'][k] == 1 for k in suppressed)


@pytest.mark.slow
def test_policy_off_bit_identity_spmd():
    from distributed_kfac_pytorch_tpu.preconditioner import CommMethod
    mesh = D.make_kfac_mesh(jax.devices(),
                            comm_method=CommMethod.COMM_OPT,
                            grad_worker_fraction=0.5)
    ref = _loss_sequence(mesh, None)
    idle = _loss_sequence(mesh, _idle_policy())
    assert ref == idle


# ---------------------------------------------------------------------------
# CLI glue (argparse surface, no subprocess)
# ---------------------------------------------------------------------------

def _cli_args(extra=()):
    import argparse
    p = argparse.ArgumentParser()
    autotune.cli.add_autotune_args(p)
    return p.parse_args(list(extra))


def test_cli_maybe_apply_tuned_and_policy(tmp_path):
    good = _write_artifact(tmp_path / 'good.json')
    cfg = _base_cfg()
    # No flag: untouched config, no events, no policy.
    args = _cli_args()
    out_cfg, events = autotune.cli.maybe_apply_tuned(args, cfg)
    assert out_cfg is cfg and events == []
    assert autotune.cli.make_cadence_policy(args) is None
    # Clean apply.
    args = _cli_args(['--tuned-config', good])
    out_cfg, events = autotune.cli.maybe_apply_tuned(args, cfg)
    assert out_cfg.bf16_precond is True
    assert out_cfg.kfac_cov_update_freq == 2
    assert events[0]['event'] == 'autotune_apply'
    # Fail-closed on a torn file: defaults + one fallback event.
    torn = tmp_path / 'torn.json'
    torn.write_text('{"format": "kfac-autotune')
    args = _cli_args(['--tuned-config', str(torn)])
    out_cfg, events = autotune.cli.maybe_apply_tuned(args, cfg)
    assert out_cfg is cfg
    assert len(events) == 1
    assert events[0]['event'] == 'autotune_fallback'
    # SGD baseline cannot take a tuned artifact.
    cfg_sgd = dataclasses.replace(cfg, kfac_inv_update_freq=0)
    with pytest.raises(SystemExit, match='K-FAC step'):
        autotune.cli.maybe_apply_tuned(args, cfg_sgd)
    # Policy construction from flags.
    args = _cli_args(['--cadence-backoff', '--backoff-skew-ms', '2.5',
                      '--backoff-max-stretch', '8'])
    pol = autotune.cli.make_cadence_policy(args)
    assert pol.config.skew_threshold_ms == 2.5
    assert pol.config.max_stretch == 8


# ---------------------------------------------------------------------------
# benchmarks/step_breakdown.py tuned_vs_default (slow: two timed legs)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_step_breakdown_tuned_vs_default(tmp_path, capsys):
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks import step_breakdown
    art = tmp_path / 'TUNED_x.json'
    at_driver.write_tuned(str(art), _artifact_obj(
        best={'bf16_precond': True, 'inv_pipeline_chunks': 2,
              'kfac_inv_update_freq': 5}))
    step_breakdown.main(['--iters', '5', '--tuned-config', str(art)])
    lines = [json.loads(line) for line in
             capsys.readouterr().out.splitlines()
             if line.startswith('{')]
    row = next(line for line in lines
               if line.get('phase') == 'tuned_vs_default')
    assert row['tuned_inv_freq'] == 5
    assert row['ignored_knobs'] == ['inv_pipeline_chunks']
    assert isinstance(row['default_ms_per_iter'], float)
    assert isinstance(row['delta_ms_per_iter'], float)
