"""Tests for the r8 resilience subsystem.

Covers the ISSUE acceptance surface: kill-and-resume bit-identity (an
injected preemption at an arbitrary mid-epoch step, auto-resume, same
per-step loss sequence as the uninterrupted run — in-process K-FAC on
CIFAR-shaped data in the fast tier; the real CLI subprocess round-trip
and the SPMD variant in the slow tier), the fault-injection suite
(preemption at step k, NaN batch + ``nonfinite_guard``,
crash-during-save, chaos spec parsing), checkpoint crash durability
(torn orbax writes never surfaced), the step-checkpoint policy and
preemption handler semantics, deterministic data-stream replay
(``skip_batches`` + augmentation RNG consumption), resilience events in
the metrics JSONL + report, and the restore-``like=``/sharding
regression satellites.
"""

import argparse
import os
import signal
import subprocess
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_kfac_pytorch_tpu import KFAC
from distributed_kfac_pytorch_tpu.observability import report as obs_report
from distributed_kfac_pytorch_tpu.observability import sink as obs_sink
from distributed_kfac_pytorch_tpu.parallel import distributed as D
from distributed_kfac_pytorch_tpu.resilience import (
    cli as resil_cli,
    dataiter,
    faults,
    policy as policy_lib,
    preemption,
)
from distributed_kfac_pytorch_tpu.training import (
    checkpoint as ckpt_lib,
    datasets,
    engine,
)


# ---------------------------------------------------------------------------
# CheckpointPolicy
# ---------------------------------------------------------------------------

class TestPolicy:
    def test_step_interval(self):
        pol = policy_lib.CheckpointPolicy(every_steps=3, start_step=0)
        assert not pol.should_save(1)
        assert not pol.should_save(2)
        assert pol.should_save(3)
        pol.note_saved(3)
        assert not pol.should_save(5)
        assert pol.should_save(6)

    def test_wall_clock_interval(self):
        now = [0.0]
        pol = policy_lib.CheckpointPolicy(every_secs=10.0,
                                          clock=lambda: now[0])
        assert not pol.should_save(1)
        now[0] = 10.5
        assert pol.should_save(1)
        pol.note_saved(1)
        assert not pol.should_save(2)

    def test_disabled_and_invalid(self):
        pol = policy_lib.CheckpointPolicy()
        assert not pol.should_save(10 ** 6)
        with pytest.raises(ValueError):
            policy_lib.CheckpointPolicy(every_steps=-1)

    def test_start_step_survives_resume(self):
        # Resumed at global step 100 with every_steps=10: next save at
        # 110, not at the modulo boundary or immediately.
        pol = policy_lib.CheckpointPolicy(every_steps=10, start_step=100)
        assert not pol.should_save(105)
        assert pol.should_save(110)


# ---------------------------------------------------------------------------
# PreemptionHandler
# ---------------------------------------------------------------------------

class TestPreemption:
    def test_sigterm_sets_flag_not_death(self):
        h = preemption.PreemptionHandler(grace_secs=30.0,
                                         signals=(signal.SIGTERM,))
        h.install()
        try:
            os.kill(os.getpid(), signal.SIGTERM)
            assert h.triggered()
            assert 'SIGTERM' in h.reason
            assert 0 < h.remaining_grace() <= 30.0
        finally:
            h.uninstall()

    def test_second_signal_escalates(self, monkeypatch):
        killed = []
        monkeypatch.setattr(preemption.os, 'kill',
                            lambda pid, sig: killed.append(sig))
        h = preemption.PreemptionHandler(signals=(signal.SIGTERM,))
        h.install()
        try:
            h._on_signal(signal.SIGTERM, None)
            assert h.triggered() and not killed
            h._on_signal(signal.SIGTERM, None)  # escalation: re-raise
            assert killed == [signal.SIGTERM]
        finally:
            h.uninstall()

    def test_pluggable_source(self, tmp_path):
        h = preemption.PreemptionHandler(signals=())
        sentinel = tmp_path / 'drain'
        h.add_source(preemption.file_source(str(sentinel)))
        assert not h.triggered()
        sentinel.write_text('')
        assert h.triggered()
        assert 'sentinel' in h.reason


# ---------------------------------------------------------------------------
# Deterministic data-stream replay (dataiter + datasets skip_batches)
# ---------------------------------------------------------------------------

class TestDataReplay:
    def test_epoch_batches_skip_bit_identity_with_augment(self):
        x = np.random.default_rng(0).normal(
            size=(64, 32, 32, 3)).astype(np.float32)
        y = np.arange(64, dtype=np.int32)
        full = list(datasets.epoch_batches(x, y, 16, seed=5, epoch=2,
                                           augment=True))
        tail = list(datasets.epoch_batches(x, y, 16, seed=5, epoch=2,
                                           augment=True, skip_batches=2))
        assert len(tail) == len(full) - 2
        for (xa, ya), (xb, yb) in zip(full[2:], tail):
            np.testing.assert_array_equal(xa, xb)
            np.testing.assert_array_equal(ya, yb)

    def test_consume_augment_rng_matches_augment(self):
        """consume_augment_rng must advance the stream exactly as
        augment_cifar does — pinned by comparing the NEXT draw."""
        x = np.zeros((8, 32, 32, 3), np.float32)
        r1 = np.random.default_rng(3)
        r2 = np.random.default_rng(3)
        datasets.augment_cifar(x, r1)
        datasets.consume_augment_rng(r2, 8)
        assert r1.integers(0, 1 << 30) == r2.integers(0, 1 << 30)

    def test_bptt_batches_skip(self):
        ids = np.arange(1000, dtype=np.int32)
        full = list(datasets.bptt_batches(ids, 4, 10, shuffle_offset=True,
                                          seed=1, epoch=3))
        tail = list(datasets.bptt_batches(ids, 4, 10, shuffle_offset=True,
                                          seed=1, epoch=3,
                                          skip_batches=3))
        assert len(tail) == len(full) - 3
        for (xa, ta), (xb, tb) in zip(full[3:], tail):
            np.testing.assert_array_equal(xa, xb)
            np.testing.assert_array_equal(ta, tb)

    def test_data_stream_state_scalars_roundtrip(self):
        st = dataiter.DataStreamState(seed=42, epoch=3, step_in_epoch=7)
        sc = st.scalars()
        assert sc == {'data_seed': 42, 'epoch': 3, 'step_in_epoch': 7}
        back = dataiter.DataStreamState.from_scalars(
            {k: jnp.asarray(v) for k, v in sc.items()})
        assert back == st
        assert dataiter.resume_offset(st, 3) == 7
        assert dataiter.resume_offset(st, 4) == 0
        assert dataiter.resume_offset(None, 3) == 0


# ---------------------------------------------------------------------------
# Fault injectors
# ---------------------------------------------------------------------------

class TestFaults:
    def test_parse_spec(self):
        plan = faults.parse_spec('preempt@3,nan-batch@1')
        assert plan.preempt_at == 3 and plan.nan_batch_at == 1
        assert plan.crash_at is None and plan.crash_in_save_at is None
        assert faults.parse_spec('') is None
        assert faults.parse_spec(None) is None
        with pytest.raises(ValueError, match='fault spec'):
            faults.parse_spec('explode@3')
        with pytest.raises(ValueError, match='fault spec'):
            faults.parse_spec('preempt=3')

    def test_plan_from_env(self, monkeypatch):
        monkeypatch.setenv(faults.ENV_VAR, 'crash@7')
        assert faults.plan_from_env().crash_at == 7
        monkeypatch.delenv(faults.ENV_VAR)
        assert faults.plan_from_env() is None

    def test_poison_at(self):
        batches = [(np.zeros((4, 2), np.float32),
                    np.zeros(4, np.int32)) for _ in range(3)]
        out = list(faults.poison_at(iter(batches),
                                    faults.FaultPlan(nan_batch_at=4),
                                    first_step=3))
        assert not np.isfinite(out[1][0]).all()   # step 4 poisoned
        assert np.isfinite(out[0][0]).all()
        assert np.isfinite(out[2][0]).all()
        # passthrough without a plan
        clean = list(faults.poison_at(iter(batches), None))
        assert all(np.isfinite(b[0]).all() for b in clean)

    def test_nan_batch_exercises_nonfinite_guard(self):
        """The acceptance fault: a NaN batch under the armed guard
        leaves factor statistics untouched and counts the skip; the
        unguarded counterfactual poisons them (r7 semantics driven
        through the r8 injector)."""

        class MLP(nn.Module):
            @nn.compact
            def __call__(self, x):
                return nn.Dense(4)(nn.tanh(nn.Dense(8)(x)))

        kfac = KFAC(MLP(), factor_update_freq=1, inv_update_freq=1,
                    factor_decay=0.5, collect_metrics=True,
                    nonfinite_guard=True)
        clean = (np.random.default_rng(0).normal(
            size=(16, 6)).astype(np.float32),
            np.zeros(16, np.int32))
        bad, = list(faults.poison_at(
            iter([clean]), faults.FaultPlan(nan_batch_at=0)))
        variables, state = kfac.init(jax.random.PRNGKey(0), clean[0])
        params = variables['params']

        def loss(out):
            return jnp.mean(out ** 2)

        step = jax.jit(lambda s, g, c: kfac.step(s, g, c))
        _, _, grads, captures, _ = kfac.capture.loss_and_grads(
            loss, params, clean[0])
        _, state = step(state, grads, captures)
        before = jax.device_get(state['factors'])
        _, _, grads_b, captures_b, _ = kfac.capture.loss_and_grads(
            loss, params, bad[0])
        _, state2 = step(state, grads_b, captures_b)
        m = jax.device_get(state2['metrics'])
        assert m['nonfinite_skips'] == 1
        for name, fac in jax.device_get(state2['factors']).items():
            for which in ('A', 'G'):
                np.testing.assert_array_equal(fac[which],
                                              before[name][which])
                assert np.isfinite(fac[which]).all()

    def test_crash_faults_fire_via_hard_crash(self, monkeypatch,
                                              tmp_path):
        """crash@K and crash-in-save@K both route through
        faults.hard_crash at the right moment (monkeypatched here —
        the real os._exit path is exercised by the subprocess
        durability test)."""
        crashed = []
        monkeypatch.setattr(faults, 'hard_crash',
                            lambda code=137: crashed.append(code) or
                            (_ for _ in ()).throw(SystemExit(code)))
        mgr = ckpt_lib.CheckpointManager(str(tmp_path / 'ck'))
        state = engine.TrainState(params={'w': jnp.zeros(2)},
                                  opt_state=(), kfac_state=None,
                                  extra_vars={}, step=2)
        ck = policy_lib.StepCheckpointer(
            mgr, None, lambda st, k: {'params': st.params,
                                      'scalars': {'step': st.step}},
            plan=faults.FaultPlan(crash_at=2))
        with pytest.raises(SystemExit):
            ck.after_step(state, 1)
        assert crashed == [137]
        assert mgr.latest_epoch() is None  # crash = no save
        ck2 = policy_lib.StepCheckpointer(
            mgr, policy_lib.CheckpointPolicy(every_steps=1),
            lambda st, k: {'params': st.params,
                           'scalars': {'step': st.step}},
            plan=faults.FaultPlan(crash_in_save_at=2))
        with pytest.raises(SystemExit):
            ck2.after_step(state, 1)
        mgr.close()


# ---------------------------------------------------------------------------
# StepCheckpointer: intervals, forced preemption save, events
# ---------------------------------------------------------------------------

def _tiny_bundle_fn(st, step_in_epoch):
    return ckpt_lib.bundle_state(
        st.params, st.opt_state, {}, st.extra_vars,
        step=st.step, epoch=st.epoch, step_in_epoch=step_in_epoch,
        data_seed=0)


class TestStepCheckpointer:
    def test_interval_saves_and_events(self, tmp_path):
        mgr = ckpt_lib.CheckpointManager(str(tmp_path / 'steps'))
        sink = obs_sink.JsonlMetricsSink(str(tmp_path / 'm.jsonl'))
        ck = policy_lib.StepCheckpointer(
            mgr, policy_lib.CheckpointPolicy(every_steps=2),
            _tiny_bundle_fn, sink=sink)
        state = engine.TrainState(params={'w': jnp.arange(4.0)},
                                  opt_state=(), kfac_state=None,
                                  extra_vars={})
        for _ in range(5):
            state.step += 1
            ck.after_step(state, state.step)
        mgr.wait_until_finished()
        assert mgr.latest_epoch() == 4       # saves at steps 2 and 4
        sink.close()
        recs = obs_sink.read_jsonl(str(tmp_path / 'm.jsonl'))
        saves = [r for r in recs if r.get('event') == 'checkpoint_save']
        assert [s['data']['global_step'] for s in saves] == [2, 4]
        assert all(s['data']['latency_ms'] >= 0 for s in saves)
        assert not any(s['data']['forced'] for s in saves)
        ck.close()

    def test_preemption_forces_blocking_save_and_raises(self, tmp_path):
        mgr = ckpt_lib.CheckpointManager(str(tmp_path / 'steps'))
        sink = obs_sink.JsonlMetricsSink(str(tmp_path / 'm.jsonl'))
        handler = preemption.PreemptionHandler(signals=())
        ck = policy_lib.StepCheckpointer(
            mgr, policy_lib.CheckpointPolicy(), _tiny_bundle_fn,
            preemption=handler, sink=sink,
            plan=faults.FaultPlan(preempt_at=3))
        state = engine.TrainState(params={'w': jnp.arange(4.0)},
                                  opt_state=(), kfac_state=None,
                                  extra_vars={})
        for _ in range(2):
            state.step += 1
            ck.after_step(state, state.step)
        state.step += 1
        with pytest.raises(preemption.Preempted) as ei:
            ck.after_step(state, state.step)
        assert ei.value.global_step == 3
        # Blocking save: durable NOW, with the resume point recorded.
        restored = ckpt_lib.CheckpointManager(
            str(tmp_path / 'steps')).restore(3)
        assert int(restored['scalars']['step']) == 3
        assert int(restored['scalars']['step_in_epoch']) == 3
        sink.close()
        recs = obs_sink.read_jsonl(str(tmp_path / 'm.jsonl'))
        kinds = [r.get('event') for r in recs if r['kind'] == 'event']
        assert kinds == ['checkpoint_save', 'preemption']
        save = next(r for r in recs
                    if r.get('event') == 'checkpoint_save')
        assert save['data']['forced'] and save['data']['blocking']
        ck.close()


# ---------------------------------------------------------------------------
# Events in the JSONL schema + report
# ---------------------------------------------------------------------------

class TestEventRecords:
    def test_event_schema_roundtrip_and_immediate_flush(self, tmp_path):
        path = tmp_path / 'ev.jsonl'
        s = obs_sink.JsonlMetricsSink(str(path), drain_every=1000)
        s.step_record(0, {'loss': 1.0})
        s.event_record('preemption', global_step=5, reason='signal')
        # events flush immediately — readable with NO close() (the
        # preempted process may never get to close cleanly)
        recs = obs_sink.read_jsonl(str(path))
        assert [r['kind'] for r in recs] == ['step', 'event']
        assert recs[1]['event'] == 'preemption'
        assert recs[1]['data']['global_step'] == 5
        s.close()

    def test_relaunch_preserves_previous_incarnation(self, tmp_path):
        """A relaunch reuses the same metrics path; the dead
        incarnation's live segment — holding its preemption/forced-save
        events — must survive as <path>.prev.1 instead of being
        unlinked (and must NOT be stitched into the new run's
        stream)."""
        path = tmp_path / 'm.jsonl'
        s1 = obs_sink.JsonlMetricsSink(str(path))
        s1.step_record(0, {'loss': 1.0})
        s1.event_record('preemption', global_step=1, reason='SIGTERM')
        # no close(): the preempted process died after the event flush
        s2 = obs_sink.JsonlMetricsSink(str(path), meta={'run': 2})
        s2.step_record(1, {'loss': 0.5})
        s2.close()
        live = obs_sink.read_jsonl(str(path))
        assert [r['kind'] for r in live] == ['meta', 'step']
        assert obs_sink.incarnation_paths(str(path)) == [
            str(path) + '.prev.1']
        prev = obs_sink.read_jsonl(str(path) + '.prev.1')
        assert [r.get('event') for r in prev
                if r['kind'] == 'event'] == ['preemption']

    def test_second_relaunch_chains_incarnations(self, tmp_path):
        """r9 satellite: the r8 single-slot layout let a SECOND
        relaunch silently overwrite the first dead incarnation's tail.
        The chain keeps each one — newest at .prev.1 — bounded, oldest
        pruned; legacy .prev files fold into the chain."""
        path = tmp_path / 'm.jsonl'
        for run in range(3):
            s = obs_sink.JsonlMetricsSink(str(path), meta={'run': run})
            s.event_record('preemption', global_step=run)
        chain = obs_sink.incarnation_paths(str(path))
        assert chain == [f'{path}.prev.1', f'{path}.prev.2']
        # Newest-first: .prev.1 is run 1's stream, .prev.2 run 0's.
        for p, want in zip(chain, (1, 0)):
            recs = obs_sink.read_jsonl(p)
            assert recs[0]['meta'] == {'run': want}
            assert recs[-1]['data']['global_step'] == want
        # Legacy pre-r9 slot folds into the chain instead of being
        # clobbered by the next relaunch.
        import os
        os.replace(str(path), f'{path}.prev')
        s = obs_sink.JsonlMetricsSink(str(path), meta={'run': 3})
        s.flush()
        assert obs_sink.incarnation_paths(str(path)) == [
            f'{path}.prev.1', f'{path}.prev.2', f'{path}.prev.3']
        # Bound: the chain prunes past PREV_INCARNATIONS_KEPT.
        for run in range(4, 4 + obs_sink.PREV_INCARNATIONS_KEPT):
            s = obs_sink.JsonlMetricsSink(str(path), meta={'run': run})
            s.flush()
        chain = obs_sink.incarnation_paths(str(path))
        assert len(chain) == obs_sink.PREV_INCARNATIONS_KEPT

    def test_orphaned_rotated_segments_are_chained(self, tmp_path):
        """Crash window: flush() renames the live segment to <path>.1
        before republishing a fresh live file — a crash in between
        leaves rotated segments with NO live file. They are the dead
        incarnation and must chain on relaunch; the r9.0 early-return
        left them in place, where the new run's read_jsonl stitched
        them into a chimeric two-run stream."""
        path = tmp_path / 'm.jsonl'
        s1 = obs_sink.JsonlMetricsSink(str(path))
        s1.event_record('preemption', global_step=0)  # flushed now
        os.replace(str(path), f'{path}.1')  # crash mid-rotation
        s2 = obs_sink.JsonlMetricsSink(str(path), meta={'run': 1})
        s2.step_record(0, {'loss': 1.0})
        s2.flush()
        live = obs_sink.read_jsonl(str(path))
        assert [r['kind'] for r in live] == ['meta', 'step']
        assert obs_sink.incarnation_paths(str(path)) == [
            f'{path}.prev.1']
        prev = obs_sink.read_incarnation(f'{path}.prev.1')
        assert [r.get('event') for r in prev
                if r['kind'] == 'event'] == ['preemption']

    def test_legacy_prev_reads_exact_file_only(self, tmp_path):
        """A legacy '<path>.prev' coexisting with chain entries (e.g.
        an r8-era binary wrote the slot after an r9 run): its
        '.prev.<n>' NEIGHBORS are chain entries — other runs — not
        rotated segments; read_incarnation must not stitch them."""
        import json as _json
        path = tmp_path / 'm.jsonl'
        rec = {'schema': 2, 'kind': 'meta', 'wall_time': 0.0,
               'meta': {}}
        (tmp_path / 'm.jsonl.prev').write_text(_json.dumps(rec) + '\n')
        (tmp_path / 'm.jsonl.prev.2').write_text(
            (_json.dumps(rec) + '\n') * 3)
        assert len(obs_sink.read_incarnation(f'{path}.prev')) == 1
        assert len(obs_sink.read_incarnation(f'{path}.prev.2')) == 3

    def test_v1_records_still_validate(self):
        obs_sink.validate_record(
            {'schema': 1, 'kind': 'step', 'step': 0, 'wall_time': 0.0,
             'metrics': {'loss': 1.0}})
        with pytest.raises(ValueError, match='event name'):
            obs_sink.validate_record(
                {'schema': 2, 'kind': 'event', 'wall_time': 0.0})

    def test_report_summarizes_resilience_events(self, tmp_path,
                                                 capsys):
        path = tmp_path / 'ev.jsonl'
        s = obs_sink.JsonlMetricsSink(str(path))
        s.step_record(0, {'loss': 1.0})
        s.event_record('checkpoint_save', global_step=1,
                       latency_ms=12.0, blocking=True, forced=True)
        s.event_record('preemption', global_step=1, reason='SIGTERM')
        s.event_record('restore', source='step', global_step=1,
                       epoch=0, step_in_epoch=1)
        s.close()
        assert obs_report.main([str(path)]) == 0
        out = capsys.readouterr().out
        assert 'resilience events' in out
        assert 'checkpoint_save' in out and 'x1' in out
        assert 'save latency' in out
        assert 'preemption' in out and 'restore' in out


# ---------------------------------------------------------------------------
# Checkpoint crash durability (torn writes never surfaced)
# ---------------------------------------------------------------------------

class TestCrashDurability:
    def test_torn_write_never_surfaced(self, tmp_path):
        """The state a writer killed between snapshot and finalize
        leaves behind (an uncommitted orbax tmp dir) must be invisible
        to latest_epoch()/restore()."""
        d = str(tmp_path / 'ck')
        mgr = ckpt_lib.CheckpointManager(d)
        mgr.save(0, {'w': jnp.arange(4.0)}, blocking=True)
        mgr.close()
        faults.torn_step_dir(d, 1)
        mgr2 = ckpt_lib.CheckpointManager(d)
        assert mgr2.latest_epoch() == 0
        restored = mgr2.restore()
        np.testing.assert_array_equal(restored['w'], np.arange(4.0))
        mgr2.close()

    def test_killed_writer_subprocess(self, tmp_path):
        """Kill a real writer mid-async-save (the r7 JSONL-sink crash
        pattern applied to orbax): whatever latest_epoch() reports
        afterwards must restore cleanly — a torn step may exist on
        disk but never surfaces."""
        d = str(tmp_path / 'ck')
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        script = """
import os, sys
import numpy as np
from distributed_kfac_pytorch_tpu.training import checkpoint as ckpt_lib
d = sys.argv[1]
mgr = ckpt_lib.CheckpointManager(d, max_to_keep=None)
tree = {'params': {'w': np.arange(1 << 21, dtype=np.float32)}}
mgr.save(0, tree, blocking=True)
tree2 = {'params': {'w': np.arange(1 << 21, dtype=np.float32) * 2}}
mgr.save(1, tree2)   # async: snapshot taken, write in flight
os._exit(137)        # killed between snapshot and finalize
"""
        env = {**os.environ, 'PYTHONPATH': repo, 'JAX_PLATFORMS': 'cpu',
               'KFAC_COMPILE_CACHE': '0'}
        env['XLA_FLAGS'] = ' '.join(
            f for f in env.get('XLA_FLAGS', '').split()
            if 'xla_force_host_platform_device_count' not in f)
        proc = subprocess.run([sys.executable, '-c', script, d],
                              env=env, capture_output=True, text=True,
                              timeout=240)
        assert proc.returncode == 137, proc.stderr[-2000:]
        mgr = ckpt_lib.CheckpointManager(d, max_to_keep=None)
        latest = mgr.latest_epoch()
        assert latest in (0, 1)
        like = {'params': {'w': np.zeros(1 << 21, np.float32)}}
        restored = mgr.restore(latest, like=like)
        w = np.asarray(restored['params']['w'])
        scale = 2.0 if latest == 1 else 1.0
        np.testing.assert_array_equal(
            w, np.arange(1 << 21, dtype=np.float32) * scale)
        mgr.close()


# ---------------------------------------------------------------------------
# restore() sharding semantics (satellite regression)
# ---------------------------------------------------------------------------

class TestRestoreShardings:
    def test_like_is_authoritative_for_shardings(self, tmp_path):
        """restore(like=) must adopt the LIVE state's placements, not
        the checkpoint's recorded save-world layout: a row-sharded
        save restores replicated when the like tree is replicated and
        row-sharded when it is row-sharded. (Without like, orbax falls
        back to the save-world metadata — same-topology only, which is
        why every resume path passes like; see
        CheckpointManager.restore.)"""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        mesh = D.make_kfac_mesh()
        row = NamedSharding(mesh, P(D.KFAC_AXES))
        repl = NamedSharding(mesh, P())
        sharded = jax.device_put(jnp.arange(16.0).reshape(8, 2), row)
        mgr = ckpt_lib.CheckpointManager(str(tmp_path / 'ck'))
        mgr.save(0, {'stack': sharded}, blocking=True)
        same = mgr.restore(0, like={'stack': sharded})
        assert same['stack'].sharding == sharded.sharding
        np.testing.assert_array_equal(np.asarray(same['stack']),
                                      np.asarray(sharded))
        relaid = mgr.restore(
            0, like={'stack': jax.device_put(jnp.zeros((8, 2)), repl)})
        assert relaid['stack'].sharding.is_equivalent_to(repl, 2)
        np.testing.assert_array_equal(np.asarray(relaid['stack']),
                                      np.asarray(sharded))
        # bare restore still round-trips VALUES on the same topology
        bare = mgr.restore(0)
        np.testing.assert_array_equal(np.asarray(bare['stack']),
                                      np.asarray(sharded))
        mgr.close()


# ---------------------------------------------------------------------------
# Kill-and-resume bit-identity (the acceptance pin)
# ---------------------------------------------------------------------------

class _CifarNet(nn.Module):
    """Small conv net over CIFAR-shaped input (the fast-tier stand-in
    for resnet20 — the CLI-subprocess test drives the real model)."""

    @nn.compact
    def __call__(self, x):
        x = nn.relu(nn.Conv(8, (3, 3), strides=(2, 2))(x))
        x = nn.relu(nn.Conv(8, (3, 3), strides=(2, 2))(x))
        x = x.reshape(x.shape[0], -1)
        return nn.Dense(10)(x)


class _LossSink:
    """Minimal metrics sink capturing the per-step loss sequence."""

    def __init__(self):
        self.losses = []

    def step_record(self, step, metrics, host_step_ms=None,
                    fired=None):
        self.losses.append(metrics['loss'])

    def epoch_record(self, epoch, metrics, trace=None, counters=None):
        pass

    def flush(self):
        pass

    def floats(self):
        return [float(jax.device_get(v)) for v in self.losses]


def _run_cifar(mesh_devices, *, tmp_path=None, preempt_at=None,
               resume=False, n_devices_batch=32):
    """Build the K-FAC CIFAR setup on a mesh over ``mesh_devices`` and
    run one epoch (optionally interrupted / resumed), returning the
    per-step losses. The jitted step is cached per device count via
    ``_run_cifar.steps`` so all phases share ONE compile."""
    from distributed_kfac_pytorch_tpu import launch
    from distributed_kfac_pytorch_tpu.training import utils

    key = len(mesh_devices)
    if key not in _run_cifar.cache:
        model = _CifarNet()
        kfac = KFAC(model, factor_update_freq=1, inv_update_freq=1,
                    damping=0.003, lr=0.1)
        variables, _ = kfac.init(jax.random.PRNGKey(0),
                                 jnp.zeros((2, 32, 32, 3)))
        params0 = variables['params']
        mesh = D.make_kfac_mesh(mesh_devices)
        dkfac = D.DistributedKFAC(kfac, mesh, params0)
        tx = optax.sgd(0.05, momentum=0.9)

        def loss_fn(out, b):
            return utils.label_smooth_loss(out, b[1], 0.0)

        step_fn = dkfac.build_train_step(loss_fn, tx, donate=False)
        _run_cifar.cache[key] = (mesh, dkfac, tx, step_fn, params0)
    mesh, dkfac, tx, step_fn, params0 = _run_cifar.cache[key]
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    def fresh_params():
        # Commit replicated onto the run's mesh so every phase starts
        # with identical, consistently-placed state.
        return jax.device_put(params0, NamedSharding(mesh, P()))

    (train_x, train_y), _ = datasets.get_cifar(None, synthetic_size=192)
    hyper = {'lr': 0.05, 'damping': 0.003,
             'factor_update_freq': 1, 'inv_update_freq': 1}

    def bundle_fn(st, step_in_epoch):
        return ckpt_lib.bundle_state(
            st.params, st.opt_state, dkfac.state_dict(st.kfac_state),
            st.extra_vars, step=st.step, epoch=st.epoch,
            step_in_epoch=step_in_epoch, data_seed=7)

    sink = _LossSink()
    skip = 0
    if resume:
        step_mgr = ckpt_lib.CheckpointManager(
            str(tmp_path / 'steps'), max_to_keep=2)
        params = fresh_params()
        state = engine.TrainState(
            params=params, opt_state=tx.init(params),
            kfac_state=dkfac.init_state(params), extra_vars={})
        args = argparse.Namespace(no_resume=False, resume_step=None,
                                  checkpoint_dir=str(tmp_path))
        epoch_mgr = ckpt_lib.CheckpointManager(str(tmp_path / 'epochs'))
        restored, start_epoch, skip, source = resil_cli.resume(
            args, epoch_mgr, step_mgr, bundle_fn(state, 0))
        assert source == 'step'
        state.params = restored['params']
        state.opt_state = restored['opt_state']
        state.kfac_state = dkfac.load_state_dict(restored['kfac'],
                                                 state.params)
        state.extra_vars = restored['extra_vars']
        state.epoch = start_epoch
        state.step = int(restored['scalars']['step'])
        # Satellite regression: the like= path must hand back the
        # row-sharded inverse stacks with their committed shardings.
        live = dkfac.init_state(state.params)
        for k, entry in restored['kfac']['inv_stacks'].items():
            for name, leaf in entry.items():
                assert isinstance(leaf, jax.Array)
                assert leaf.sharding == live['inv_stacks'][k][name]\
                    .sharding, (k, name)
        ckpt = None
        epoch_mgr.close()
    else:
        params = fresh_params()
        state = engine.TrainState(
            params=params, opt_state=tx.init(params),
            kfac_state=dkfac.init_state(params), extra_vars={})
        ckpt = None
        if preempt_at is not None:
            step_mgr = ckpt_lib.CheckpointManager(
                str(tmp_path / 'steps'), max_to_keep=2)
            ckpt = policy_lib.StepCheckpointer(
                step_mgr, policy_lib.CheckpointPolicy(), bundle_fn,
                preemption=preemption.PreemptionHandler(signals=()),
                plan=faults.FaultPlan(preempt_at=preempt_at))
    batches = launch.global_batches(mesh, datasets.epoch_batches(
        train_x, train_y, n_devices_batch, seed=7, epoch=0,
        augment=True, skip_batches=skip))
    try:
        engine.train_epoch(step_fn, state, batches, hyper,
                           metrics_sink=sink, checkpointer=ckpt,
                           start_step_in_epoch=skip)
    except preemption.Preempted:
        assert preempt_at is not None
    if ckpt is not None:
        ckpt.close()
    elif resume:
        step_mgr.close()
    return sink.floats(), state


_run_cifar.cache = {}


def _kill_and_resume(devices, tmp_path):
    full, _ = _run_cifar(devices)
    assert len(full) == 6  # 192 images / batch 32
    part, _ = _run_cifar(devices, tmp_path=tmp_path, preempt_at=2)
    assert len(part) == 2
    rest, state = _run_cifar(devices, tmp_path=tmp_path, resume=True)
    assert len(rest) == 4
    # Bit-identity: the interrupted+resumed per-step loss sequence
    # equals the uninterrupted run's, elementwise and exactly.
    np.testing.assert_array_equal(np.asarray(part + rest),
                                  np.asarray(full))
    assert state.step == 6


class TestKillAndResume:
    def test_single_chip_bit_identity(self, tmp_path):
        """Injected preemption at a mid-epoch step + auto-resume ==
        uninterrupted run, per-step-loss-exact (fast tier; single
        device mesh = the single-chip path)."""
        _kill_and_resume(jax.devices()[:1], tmp_path)

    @pytest.mark.slow
    def test_spmd_bit_identity(self, tmp_path):
        """SPMD variant on the 8-device mesh (slow tier): same
        bit-identity through dkfac.state_dict/load_state_dict with
        row-sharded inverse stacks restored via like=."""
        _kill_and_resume(jax.devices(), tmp_path)


# ---------------------------------------------------------------------------
# resume(): newest-of-step-or-epoch selection
# ---------------------------------------------------------------------------

class TestResumeSelection:
    def _save(self, mgr, label, step, epoch, offset):
        mgr.save(label, ckpt_lib.bundle_state(
            {'w': jnp.full(2, float(step))}, (), {}, {},
            step=step, epoch=epoch, step_in_epoch=offset, data_seed=0),
            blocking=True)

    def _args(self, tmp_path, **kw):
        return argparse.Namespace(no_resume=False, resume_step=None,
                                  checkpoint_dir=str(tmp_path), **kw)

    def test_step_newer_than_epoch_wins(self, tmp_path):
        em = ckpt_lib.CheckpointManager(str(tmp_path / 'e'))
        sm = ckpt_lib.CheckpointManager(str(tmp_path / 's'))
        self._save(em, 1, step=20, epoch=2, offset=0)  # epoch 1 done
        self._save(sm, 27, step=27, epoch=2, offset=7)  # mid-epoch 2
        like = ckpt_lib.bundle_state({'w': jnp.zeros(2)}, (), {}, {},
                                     step=0, epoch=0, step_in_epoch=0,
                                     data_seed=0)
        tree, start_epoch, offset, src = resil_cli.resume(
            self._args(tmp_path), em, sm, like)
        assert (src, start_epoch, offset) == ('step', 2, 7)
        assert int(tree['scalars']['step']) == 27
        em.close(), sm.close()

    def test_stale_step_loses_to_epoch(self, tmp_path):
        em = ckpt_lib.CheckpointManager(str(tmp_path / 'e'))
        sm = ckpt_lib.CheckpointManager(str(tmp_path / 's'))
        self._save(sm, 13, step=13, epoch=1, offset=3)  # old preemption
        self._save(em, 4, step=50, epoch=5, offset=0)   # epoch 4 done
        like = ckpt_lib.bundle_state({'w': jnp.zeros(2)}, (), {}, {},
                                     step=0, epoch=0, step_in_epoch=0,
                                     data_seed=0)
        tree, start_epoch, offset, src = resil_cli.resume(
            self._args(tmp_path), em, sm, like)
        assert (src, start_epoch, offset) == ('epoch', 5, 0)
        em.close(), sm.close()

    def test_adopts_checkpoint_data_seed(self, tmp_path):
        """A relaunch that forgot --seed must not replay a different
        permutation: resume() adopts the bundle's data_seed."""
        em = ckpt_lib.CheckpointManager(str(tmp_path / 'e'))
        sm = ckpt_lib.CheckpointManager(str(tmp_path / 's'))
        sm.save(5, ckpt_lib.bundle_state(
            {'w': jnp.zeros(2)}, (), {}, {},
            step=5, epoch=0, step_in_epoch=5, data_seed=7),
            blocking=True)
        like = ckpt_lib.bundle_state({'w': jnp.zeros(2)}, (), {}, {},
                                     step=0, epoch=0, step_in_epoch=0,
                                     data_seed=0)
        args = self._args(tmp_path, seed=42)
        resil_cli.resume(args, em, sm, like)
        assert args.seed == 7
        em.close(), sm.close()

    def test_no_resume_and_empty(self, tmp_path):
        em = ckpt_lib.CheckpointManager(str(tmp_path / 'e'))
        sm = ckpt_lib.CheckpointManager(str(tmp_path / 's'))
        assert resil_cli.resume(self._args(tmp_path), em, sm, {}) is None
        args = self._args(tmp_path)
        args.no_resume = True
        assert resil_cli.resume(args, em, sm, {}) is None
        em.close(), sm.close()


# ---------------------------------------------------------------------------
# Chaos harness
# ---------------------------------------------------------------------------

class TestChaos:
    def test_relaunch_loop(self, tmp_path):
        """The chaos CLI relaunches while the child exits with the
        relaunch code, clearing the fault spec after launch 1."""
        from distributed_kfac_pytorch_tpu.resilience import chaos

        marker = tmp_path / 'launched_once'
        script = (
            "import os, sys\n"
            f"m = {str(marker)!r}\n"
            "if not os.path.exists(m):\n"
            "    open(m, 'w').write(os.environ.get('KFAC_CHAOS', ''))\n"
            f"    sys.exit({preemption.RELAUNCH_EXIT_CODE})\n"
            "assert 'KFAC_CHAOS' not in os.environ  # cleared\n"
            "sys.exit(0)\n")
        rc = chaos.main(['preempt@1', '--relaunch', '3', '--',
                         sys.executable, '-c', script])
        assert rc == 0
        assert marker.read_text() == 'preempt@1'

    def test_bad_spec_rejected_before_launch(self):
        from distributed_kfac_pytorch_tpu.resilience import chaos

        with pytest.raises(ValueError):
            chaos.main(['frobnicate@1', '--', 'true'])


# ---------------------------------------------------------------------------
# CLI-level round trips (slow tier: full entry-point subprocesses)
# ---------------------------------------------------------------------------

def _cli_env(repo, cache_dir):
    env = {**os.environ, 'PYTHONPATH': repo, 'JAX_PLATFORMS': 'cpu',
           'PYTHONUNBUFFERED': '1',
           # Share one compile cache across the runs of a test: the
           # relaunch recompiles the identical program (single-device
           # CPU warm reads are fine; only the multi-device CPU
           # backend has the known warm-cache issue — see conftest).
           'JAX_COMPILATION_CACHE_DIR': cache_dir,
           'KFAC_SYNTHETIC_CIFAR': '384'}
    env['XLA_FLAGS'] = ' '.join(
        f for f in env.get('XLA_FLAGS', '').split()
        if 'xla_force_host_platform_device_count' not in f)
    return env


def _cifar_cli_cmd(repo, tmp_path, metrics_name):
    return [sys.executable,
            os.path.join(repo, 'examples', 'train_cifar10_resnet.py'),
            '--epochs', '1', '--model', 'resnet20',
            '--batch-size', '128', '--val-batch-size', '96',
            '--kfac-update-freq', '1', '--kfac-cov-update-freq', '1',
            '--log-dir', str(tmp_path / 'logs'),
            '--checkpoint-dir', str(tmp_path / 'ckpt'),
            '--checkpoint-steps', '1',
            '--kfac-metrics', str(tmp_path / metrics_name),
            '--metrics-interval', '1']


def _losses(path):
    return [(r['step'], r['metrics']['loss'])
            for r in obs_sink.read_jsonl(str(path))
            if r['kind'] == 'step']


@pytest.mark.slow
class TestCLIKillAndResume:
    def test_cifar_cli_chaos_preempt_resume_bit_identity(self,
                                                         tmp_path):
        """The acceptance smoke through the REAL entry point: an
        injected preemption at step 1 exits with the relaunch code
        after a forced blocking save; the relaunch resumes mid-epoch
        and the combined per-step loss sequence equals an
        uninterrupted run's bit-for-bit. (scripts/resilience_smoke.sh
        is the standalone form of this test.)"""
        repo = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        env = _cli_env(repo, str(tmp_path / 'cache'))

        ref = subprocess.run(
            _cifar_cli_cmd(repo, tmp_path, 'ref.jsonl')
            + ['--no-resume', '--checkpoint-dir',
               str(tmp_path / 'ckpt-ref')],
            env=env, capture_output=True, text=True, timeout=600)
        assert ref.returncode == 0, \
            f'{ref.stdout[-2000:]}\n{ref.stderr[-3000:]}'

        env_chaos = {**env, 'KFAC_CHAOS': 'preempt@1'}
        run1 = subprocess.run(
            _cifar_cli_cmd(repo, tmp_path, 'run1.jsonl'),
            env=env_chaos, capture_output=True, text=True, timeout=600)
        assert run1.returncode == preemption.RELAUNCH_EXIT_CODE, \
            f'{run1.stdout[-2000:]}\n{run1.stderr[-3000:]}'
        assert 'preempted' in run1.stdout

        run2 = subprocess.run(
            _cifar_cli_cmd(repo, tmp_path, 'run2.jsonl'),
            env=env, capture_output=True, text=True, timeout=600)
        assert run2.returncode == 0, \
            f'{run2.stdout[-2000:]}\n{run2.stderr[-3000:]}'
        assert 'resumed from step checkpoint' in run2.stdout

        ref_losses = _losses(tmp_path / 'ref.jsonl')
        got = _losses(tmp_path / 'run1.jsonl') + \
            _losses(tmp_path / 'run2.jsonl')
        assert len(ref_losses) == 3  # 384 images / batch 128
        assert got == ref_losses     # steps AND loss floats identical
        # restore + preemption events made it into the streams
        ev1 = [r['event'] for r in
               obs_sink.read_jsonl(str(tmp_path / 'run1.jsonl'))
               if r['kind'] == 'event']
        assert 'preemption' in ev1 and 'checkpoint_save' in ev1
        ev2 = [r['event'] for r in
               obs_sink.read_jsonl(str(tmp_path / 'run2.jsonl'))
               if r['kind'] == 'event']
        assert 'restore' in ev2

    def test_cifar_cli_real_sigterm(self, tmp_path):
        """A real SIGTERM mid-run drains gracefully: forced blocking
        save, relaunch exit code, and a resumable step checkpoint."""
        repo = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        env = _cli_env(repo, str(tmp_path / 'cache'))
        proc = subprocess.Popen(
            _cifar_cli_cmd(repo, tmp_path, 'sig.jsonl'),
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        # Wait until the handler is installed (the 'devices:' banner
        # prints after install), then deliver the preemption notice.
        for line in proc.stdout:
            if line.startswith('devices:'):
                proc.send_signal(signal.SIGTERM)
                break
        out = proc.stdout.read()
        rc = proc.wait(timeout=600)
        assert rc == preemption.RELAUNCH_EXIT_CODE, out[-3000:]
        assert 'preempted (signal SIGTERM)' in out
        steps = ckpt_lib.CheckpointManager(
            str(tmp_path / 'ckpt' / 'steps'))
        assert steps.latest_epoch() is not None
        steps.close()


@pytest.mark.slow
def test_lm_cli_sgd_baseline_trains(tmp_path, capsys):
    """--kfac-update-freq 0 on the LM CLI: the SGD fallback (satellite)
    trains end to end and suffixes the default checkpoint dir with
    -sgd so a later K-FAC run cannot trip over the SGD state tree."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        'train_language_model',
        os.path.join(os.path.dirname(__file__), '..', 'examples',
                     'train_language_model.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rng = np.random.default_rng(0)
    data = tmp_path / 'data'
    data.mkdir()
    for split, n in (('train', 3000), ('valid', 600)):
        toks = rng.integers(0, 50, size=n).astype(str)
        (data / f'{split}.txt').write_text(' '.join(toks))
    argv = ['--arch', 'transformer', '--emsize', '32',
            '--nhid', '32', '--nlayers', '1', '--nheads', '2',
            '--bptt', '8', '--batch-size', '16', '--epochs', '1',
            '--dropout', '0.0', '--no-resume',
            '--kfac-update-freq', '0',
            '--data-dir', str(data),
            '--log-dir', str(tmp_path / 'logs')]
    import shutil
    try:
        assert mod.main(argv) == 0
        out = capsys.readouterr().out
        assert 'val ppl' in out
        # the -sgd suffix is applied inside main() (the parse-time
        # default is the bare ./checkpoints/lm): the SGD run's tree
        # must land under the suffixed path so a later K-FAC resume
        # cannot pick it up.
        assert os.path.isdir('./checkpoints/lm-sgd')
    finally:
        shutil.rmtree('./checkpoints/lm-sgd', ignore_errors=True)
