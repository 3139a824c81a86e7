"""Tests for the r16 self-healing subsystem.

Covers the ISSUE acceptance surface: the chaos ladder proofs
(``corrupt-factor@K`` recovers in-process via quarantine -> re-admit
with final loss within tolerance of the fault-free run; ``diverge@K``
escalates damping then decays back; rung-4 rollback restores the
newest VERIFIED bundle in-process), ladder-off per-step-loss
bit-identity with the ladder armed + the zero-retrace guard, the
checkpoint-integrity machinery (content checksums, verified resume
walk, ``ckpt_quarantine`` events, crash-in-save + corrupt bundles,
pre-r16 unverified restores), controller-unit ladder transitions, and
the observability satellites (health summary per-kind counts, report
self-healing section, gate ``selfheal_rollbacks`` metric). The 8-dev
SPMD variants of the heavy legs ride in the slow tier.
"""

import argparse
import json
import warnings

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_kfac_pytorch_tpu import KFAC, launch
from distributed_kfac_pytorch_tpu.observability import (
    gate as obs_gate,
    health as obs_health,
    report as obs_report,
    sink as obs_sink,
)
from distributed_kfac_pytorch_tpu.parallel import distributed as D
from distributed_kfac_pytorch_tpu.resilience import (
    cli as resil_cli,
    faults,
    integrity,
    policy as policy_lib,
    preemption,
    selfheal,
)
from distributed_kfac_pytorch_tpu.training import (
    checkpoint as ckpt_lib,
    engine,
)


class _Net(nn.Module):
    @nn.compact
    def __call__(self, x):
        x = nn.tanh(nn.Dense(8)(x))
        x = nn.tanh(nn.Dense(8)(x))
        return nn.Dense(4)(x)


class _EventSink:
    """Duck-typed sink capturing per-step losses and events."""

    def __init__(self):
        self.losses = []
        self.events = []

    def step_record(self, step, metrics, host_step_ms=None, fired=None):
        self.losses.append(metrics['loss'])

    def epoch_record(self, epoch, metrics, trace=None, counters=None):
        pass

    def event_record(self, name, **data):
        self.events.append((name, data))

    def flush(self):
        pass

    def floats(self):
        return [float(jax.device_get(v)) for v in self.losses]

    def kinds(self):
        return [name for name, _ in self.events]


def _data(n=64, bs=8, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 6).astype(np.float32)
    y = rng.randn(n, 4).astype(np.float32)
    return [(x[i:i + bs], y[i:i + bs]) for i in range(0, n, bs)]


def _build(n_devices: int, tag: str = ''):
    """One compiled K-FAC setup per (device count, tag) (f=1, i=4
    cadence) — cached so ladder tests share program variants. A
    builder must only ever see ONE hyper structure (armed gates add a
    ``bucket_gate`` entry), so the bit-identity tests use dedicated
    tags for their unarmed runs instead of mixing structures in one
    trace cache."""
    key = (n_devices, tag)
    if key not in _build.cache:
        model = _Net()
        kfac = KFAC(model, factor_update_freq=1, inv_update_freq=4,
                    damping=0.003, lr=0.1, collect_metrics=True,
                    nonfinite_guard=True)
        variables, _ = kfac.init(jax.random.PRNGKey(0),
                                 jnp.zeros((2, 6)))
        params0 = variables['params']
        mesh = D.make_kfac_mesh(jax.devices()[:n_devices])
        dkfac = D.DistributedKFAC(kfac, mesh, params0)
        tx = optax.sgd(0.05, momentum=0.9)
        step_fn = dkfac.build_train_step(
            lambda out, b: jnp.mean((out - b[1]) ** 2), tx,
            donate=False)
        _build.cache[key] = (kfac, mesh, dkfac, tx, step_fn, params0)
    return _build.cache[key]


_build.cache = {}

_HYPER = {'lr': 0.05, 'damping': 0.003,
          'factor_update_freq': 1, 'inv_update_freq': 4}


def _fresh_state(mesh, dkfac, tx, params0):
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    params = jax.device_put(params0, NamedSharding(mesh, P()))
    return engine.TrainState(params=params, opt_state=tx.init(params),
                             kfac_state=dkfac.init_state(params),
                             extra_vars={})


def _controller(kfac, params, *, quarantine=True, rollback_after=20,
                max_rollbacks=1):
    cfg = selfheal.SelfHealConfig(
        check_every=1, escalate_after=1, quarantine_after=1,
        readmit_windows=2, quarantine=quarantine,
        rollback_after=rollback_after, max_rollbacks=max_rollbacks)
    # bucket_layers ALWAYS rides (inert when quarantine=False) so every
    # ladder shape shares the cached step builder's traced hyper
    # structure — the zero-retrace pin below depends on it.
    return selfheal.SelfHealController(
        cfg, bucket_layers=selfheal.bucket_layer_map(kfac, params))


def _run_ladder(n_devices, *, chaos=None, ctl=None, tmp_path=None,
                ckpt_steps=0, epochs=2, data_seed=0, tag=''):
    """Train `epochs` epochs; returns (sink, controller, state,
    step_mgr). Chaos faults are injected via the real StepCheckpointer
    poll point; Rollback propagates to the caller."""
    kfac, mesh, dkfac, tx, step_fn, params0 = _build(n_devices, tag)
    state = _fresh_state(mesh, dkfac, tx, params0)
    sink = _EventSink()
    step_mgr = None
    ckpt = None
    if tmp_path is not None:
        step_mgr = ckpt_lib.CheckpointManager(str(tmp_path / 'steps'),
                                              max_to_keep=20)

        def bundle_fn(st, sie):
            return ckpt_lib.bundle_state(
                st.params, st.opt_state,
                dkfac.state_dict(st.kfac_state), st.extra_vars,
                step=st.step, epoch=st.epoch, step_in_epoch=sie,
                data_seed=7)
        _run_ladder.bundle_fn = bundle_fn
        ckpt = policy_lib.StepCheckpointer(
            step_mgr, policy_lib.CheckpointPolicy(every_steps=ckpt_steps),
            bundle_fn,
            preemption=preemption.PreemptionHandler(signals=()),
            plan=faults.parse_spec(chaos), sink=sink, always_block=True)
    elif chaos is not None:
        ckpt = policy_lib.StepCheckpointer(
            None, None, None,
            preemption=preemption.PreemptionHandler(signals=()),
            plan=faults.parse_spec(chaos), sink=sink)
    for _ep in range(epochs):
        batches = launch.global_batches(mesh, iter(_data(seed=data_seed)))
        engine.train_epoch(step_fn, state, batches, _HYPER,
                           metrics_sink=sink, checkpointer=ckpt,
                           selfheal=ctl)
    return sink, ctl, state, step_mgr


# ---------------------------------------------------------------------------
# SelfHealConfig / controller units
# ---------------------------------------------------------------------------

class TestConfig:
    def test_validation(self):
        with pytest.raises(ValueError):
            selfheal.SelfHealConfig(check_every=0)
        with pytest.raises(ValueError):
            selfheal.SelfHealConfig(damping_factor=1.0)
        with pytest.raises(ValueError):
            selfheal.SelfHealConfig(quarantine_after=3,
                                    rollback_after=3)
        # Without the quarantine rung the ordering constraint lifts.
        selfheal.SelfHealConfig(quarantine=False, quarantine_after=3,
                                rollback_after=3)


class _StubState:
    def __init__(self, step, factors=None):
        self.step = step
        self.kfac_state = {'factors': factors or {}}


class TestControllerUnits:
    def _ctl(self, **kw):
        kw.setdefault('check_every', 1)
        kw.setdefault('escalate_after', 1)
        kw.setdefault('rollback_after', 4)
        cfg = selfheal.SelfHealConfig(**kw)
        return selfheal.SelfHealController(cfg)

    def test_escalate_on_nonfinite_then_decay(self):
        ctl = self._ctl()
        ctl.observe(_StubState(0), {'loss': 1.0,
                                    'kfac/nonfinite_skips': 1.0})
        assert ctl.damping_mult == 10.0
        assert [e['event'] for e in ctl.pending_events] == \
            ['selfheal_escalate']
        # hyper adjustment is a pure value change
        assert ctl.adjust_hyper({'damping': 0.01})['damping'] == \
            pytest.approx(0.1)
        ctl.observe(_StubState(1), {'loss': 1.0,
                                    'kfac/nonfinite_skips': 1.0})
        assert ctl.damping_mult == 1.0
        assert ctl.pending_events[-1]['event'] == 'selfheal_deescalate'

    def test_escalation_bounded_at_max(self):
        ctl = self._ctl(damping_max_mult=100.0, rollback_after=50)
        for step in range(6):
            ctl.observe(_StubState(step),
                        {'loss': 1.0,
                         'kfac/nonfinite_skips': float(step + 1)})
        assert ctl.damping_mult == 100.0
        ups = [e for e in ctl.pending_events
               if e['event'] == 'selfheal_escalate']
        assert len(ups) == 2  # 10 -> 100, then capped silently

    def test_divergence_window(self):
        ctl = self._ctl(diverge_ratio=5.0)
        ctl.observe(_StubState(0), {'loss': 1.0})   # establishes EMA
        ctl.observe(_StubState(1), {'loss': 50.0})  # 50x the reference
        assert ctl.damping_mult == 10.0
        assert ctl.pending_events[-1]['kind'] == 'diverge'

    def test_sustained_divergence_reaches_rollback(self):
        """Review regression: a diverged window must NOT feed the loss
        EMA at full alpha (the spike would vouch for itself within one
        window); a sustained plateau keeps flagging and climbs to the
        rollback rung."""
        ctl = self._ctl(diverge_ratio=10.0, rollback_after=4)
        ctl.observe(_StubState(0), {'loss': 1.0})  # reference
        with pytest.raises(selfheal.Rollback):
            for step in range(1, 10):
                ctl.observe(_StubState(step), {'loss': 100.0})
        # The reference re-legitimized by at most x1.2 per window —
        # nowhere near absorbing a 100x plateau before rollback.
        assert ctl._loss_ema < 3.0

    def test_moderate_transient_escalates_then_decays(self):
        """The flip side: a shallow transient IS re-accepted within a
        few windows (the reference creeps x diverge_adapt), so the
        ladder escalates then decays instead of rolling back."""
        ctl = self._ctl(diverge_ratio=1.3, rollback_after=6)
        ctl.observe(_StubState(0), {'loss': 6.9})
        for step in range(1, 5):
            ctl.observe(_StubState(step), {'loss': 11.0})
        kinds = [e['event'] for e in ctl.pending_events]
        assert 'selfheal_escalate' in kinds
        assert 'selfheal_deescalate' in kinds
        assert ctl.rollbacks == 0

    def test_nan_loss_is_nonfinite_window(self):
        ctl = self._ctl()
        ctl.observe(_StubState(0), {'loss': float('nan')})
        assert ctl.damping_mult == 10.0
        assert ctl.pending_events[-1]['kind'] == 'nonfinite'

    def test_quarantine_attribution_and_reset(self):
        factors = {
            'bad': {'A': jnp.full((3, 3), jnp.inf),
                    'G': jnp.eye(2)},
            'good': {'A': jnp.eye(3), 'G': jnp.eye(2)},
        }
        cfg = selfheal.SelfHealConfig(check_every=1, escalate_after=1,
                                      quarantine_after=1,
                                      rollback_after=9)
        ctl = selfheal.SelfHealController(
            cfg, bucket_layers={'b0': ['bad'], 'b1': ['good']})
        st = _StubState(0, factors)
        ctl.observe(st, {'loss': 1.0, 'kfac/nonfinite_skips': 1.0})
        assert ctl.gates == {'b0': 0.0, 'b1': 1.0}
        # The quarantined layer's EWMA reset to the identity seeds;
        # the healthy layer untouched.
        reset = st.kfac_state['factors']['bad']
        np.testing.assert_array_equal(np.asarray(reset['A']),
                                      np.eye(3, dtype=np.float32))
        assert np.isfinite(np.asarray(reset['A'])).all()
        kinds = [e['event'] for e in ctl.pending_events]
        assert 'selfheal_quarantine' in kinds
        # gate rides in hyper for every step
        assert ctl.adjust_hyper({'damping': 1.0})['bucket_gate'] == \
            {'b0': 0.0, 'b1': 1.0}

    def test_readmit_needs_probe_and_refire(self):
        cfg = selfheal.SelfHealConfig(check_every=1, escalate_after=1,
                                      quarantine_after=1,
                                      readmit_windows=2,
                                      rollback_after=9)
        ctl = selfheal.SelfHealController(
            cfg, bucket_layers={'b0': ['l']})
        st = _StubState(0, {'l': {'A': jnp.full((2, 2), jnp.nan)}})
        ctl.observe(st, {'loss': 1.0, 'kfac/nonfinite_skips': 1.0,
                         'kfac/inv_updates': 1.0})
        assert ctl.gates['b0'] == 0.0
        # Clean windows but NO inverse refresh yet: stays gated.
        ctl.observe(st, {'loss': 1.0, 'kfac/nonfinite_skips': 1.0,
                         'kfac/inv_updates': 1.0})
        ctl.observe(st, {'loss': 1.0, 'kfac/nonfinite_skips': 1.0,
                         'kfac/inv_updates': 1.0})
        assert ctl.gates['b0'] == 0.0
        # Inverse refreshed + factors finite (reset did that) -> lift.
        ctl.observe(st, {'loss': 1.0, 'kfac/nonfinite_skips': 1.0,
                         'kfac/inv_updates': 2.0})
        assert ctl.gates['b0'] == 1.0
        assert ctl.pending_events[-1]['event'] == 'selfheal_readmit'

    def test_rollback_after_persistent_badness(self):
        ctl = self._ctl(rollback_after=3)
        with pytest.raises(selfheal.Rollback) as ei:
            for step in range(5):
                ctl.observe(_StubState(step),
                            {'loss': float('nan')})
        assert ei.value.onset_step == 0  # step 0 window, minus window
        assert ctl.rollbacks == 1
        # Budget spent: the next request exhausts the ladder.
        ctl.after_rollback(0)
        with pytest.raises(selfheal.SelfHealExhausted):
            for step in range(5):
                ctl.observe(_StubState(step),
                            {'loss': float('nan')})

    def test_unarmed_hyper_untouched(self):
        ctl = self._ctl()
        h = {'damping': 0.01, 'lr': 0.1}
        assert ctl.adjust_hyper(h) == h  # mult 1, no bucket_layers


# ---------------------------------------------------------------------------
# The ladder end-to-end (in-process, real K-FAC step)
# ---------------------------------------------------------------------------

class TestLadderEndToEnd:
    def test_corrupt_factor_heals_in_process(self, tmp_path):
        """ISSUE acceptance: corrupt-factor@K -> quarantine of exactly
        the poisoned bucket -> factor re-accumulation -> re-admit;
        loss stays finite throughout and the final loss matches the
        fault-free run within tolerance. Zero retraces with the
        ladder armed (trace_counts guard)."""
        kfac, mesh, dkfac, tx, step_fn, params0 = _build(1)
        clean_sink, _, _, _ = _run_ladder(
            1, ctl=_controller(kfac, params0))
        sink, ctl, _, _ = _run_ladder(
            1, chaos='corrupt-factor@5', ctl=_controller(kfac, params0))
        kinds = sink.kinds()
        assert 'selfheal_escalate' in kinds
        assert 'selfheal_quarantine' in kinds
        assert 'selfheal_readmit' in kinds
        # Event ORDER: escalate before quarantine before readmit.
        assert kinds.index('selfheal_escalate') < \
            kinds.index('selfheal_quarantine') < \
            kinds.index('selfheal_readmit')
        q = dict(sink.events[kinds.index('selfheal_quarantine')][1])
        # Attribution: the first layer (lexicographic — what
        # poison_factors hits) lives in the 8x7 bucket (Dense(8) over
        # 6 features + bias).
        assert q['bucket'] == '8x7'
        losses = sink.floats()
        assert np.isfinite(losses).all()
        clean = clean_sink.floats()
        assert abs(losses[-1] - clean[-1]) < 0.1 * abs(clean[-1]) + 0.05
        # Healed: gates lifted, damping decayed back.
        assert all(v == 1.0 for v in ctl.gates.values())
        assert ctl.damping_mult == 1.0
        assert all(v == 1 for v in step_fn.trace_counts.values()), \
            step_fn.trace_counts

    def test_diverge_escalates_then_decays(self):
        kfac, mesh, dkfac, tx, step_fn, params0 = _build(1)
        sink, ctl, _, _ = _run_ladder(
            1, chaos='diverge@5', ctl=_controller(kfac, params0))
        kinds = sink.kinds()
        assert 'selfheal_escalate' in kinds
        assert 'selfheal_deescalate' in kinds
        assert kinds.index('selfheal_escalate') < \
            kinds.index('selfheal_deescalate')
        # The injected spike is finite: never a quarantine, and the
        # multiplier is fully decayed by the end.
        assert 'selfheal_quarantine' not in kinds
        assert ctl.damping_mult == 1.0
        assert np.isfinite(sink.floats()).all()

    def test_armed_ladder_bit_identity_and_zero_retrace(self):
        """ISSUE acceptance: ladder-off per-step losses == armed
        (fault-free) per-step losses, bitwise; armed run retraces
        nothing."""
        # Dedicated builders: a trace cache must only ever see ONE
        # hyper structure (armed adds bucket_gate), so off/on each get
        # their own — the zero-retrace pin then applies to both.
        kfac_off, _, _, _, step_off, _ = _build(1, 'bit_off')
        kfac_on, _, _, _, step_on, params_on = _build(1, 'bit_on')
        off_sink, _, _, _ = _run_ladder(1, ctl=None, tag='bit_off')
        on_sink, ctl, _, _ = _run_ladder(
            1, ctl=_controller(kfac_on, params_on), tag='bit_on')
        np.testing.assert_array_equal(np.asarray(off_sink.floats()),
                                      np.asarray(on_sink.floats()))
        assert ctl.damping_mult == 1.0
        # No ladder events on a clean run (compile telemetry from the
        # fresh builders is expected and fine).
        assert not [k for k in on_sink.kinds()
                    if k.startswith('selfheal')]
        assert all(v == 1 for v in step_off.trace_counts.values())
        assert all(v == 1 for v in step_on.trace_counts.values())

    def test_rollback_restores_verified_and_continues(self, tmp_path):
        """Rung 4 end-to-end: quarantine disabled (inert gates), the
        persistent corruption escalates to Rollback; the in-process
        restore lands on a verified pre-fault bundle and training
        continues to a finite loss in the same process."""
        kfac, mesh, dkfac, tx, step_fn, params0 = _build(1)
        ctl = _controller(kfac, params0, quarantine=False,
                          rollback_after=3)
        sink = None
        with pytest.raises(selfheal.Rollback) as ei:
            sink, _, state, step_mgr = _run_ladder(
                1, chaos='corrupt-factor@5', ctl=ctl,
                tmp_path=tmp_path, ckpt_steps=2)
        rb = ei.value
        assert rb.onset_step < rb.global_step
        # The CLI half: restore + re-arm + keep training.
        kfac2, mesh2, dkfac2, tx2, step_fn2, params02 = _build(1)
        state = _fresh_state(mesh2, dkfac2, tx2, params02)
        step_mgr = ckpt_lib.CheckpointManager(str(tmp_path / 'steps'),
                                              max_to_keep=20)
        sink = _EventSink()

        def bundle_fn(st, sie):
            return ckpt_lib.bundle_state(
                st.params, st.opt_state,
                dkfac2.state_dict(st.kfac_state), st.extra_vars,
                step=st.step, epoch=st.epoch, step_in_epoch=sie,
                data_seed=7)
        args = argparse.Namespace(checkpoint_dir=str(tmp_path))
        start_epoch, start_offset = selfheal.handle_rollback(
            rb, args=args, step_mgr=step_mgr, like=bundle_fn(state, 0),
            state=state, dkfac=dkfac2, sink=sink, controller=ctl)
        assert 'selfheal_rollback' in sink.kinds()
        rb_data = dict(sink.events[
            sink.kinds().index('selfheal_rollback')][1])
        assert rb_data['to_step'] <= rb.onset_step
        assert state.step == rb_data['to_step']
        # Restored state is clean and the ladder re-armed.
        assert integrity.finite_ok(state.kfac_state['factors'])
        assert ctl.damping_mult == 1.0
        # Continue training IN-PROCESS from the restored position:
        # finite to the end (the chaos latch in StepCheckpointer is
        # one-shot, so the replay is fault-free).
        batches = launch.global_batches(
            mesh2, iter(_data()[start_offset:]))
        m = engine.train_epoch(step_fn2, state, batches, _HYPER,
                               metrics_sink=sink, selfheal=ctl)
        assert np.isfinite(m['loss'])
        step_mgr.close()

    @pytest.mark.slow
    def test_spmd_corrupt_factor_heals(self):
        """8-dev SPMD variant of the quarantine -> re-admit proof."""
        kfac, mesh, dkfac, tx, step_fn, params0 = _build(8)
        clean_sink, _, _, _ = _run_ladder(
            8, ctl=_controller(kfac, params0))
        sink, ctl, _, _ = _run_ladder(
            8, chaos='corrupt-factor@5', ctl=_controller(kfac, params0))
        kinds = sink.kinds()
        assert 'selfheal_quarantine' in kinds
        assert 'selfheal_readmit' in kinds
        losses = sink.floats()
        assert np.isfinite(losses).all()
        clean = clean_sink.floats()
        assert abs(losses[-1] - clean[-1]) < 0.1 * abs(clean[-1]) + 0.05
        assert all(v == 1 for v in step_fn.trace_counts.values())

    @pytest.mark.slow
    def test_spmd_armed_bit_identity(self):
        kfac_on, _, _, _, step_on, params_on = _build(8, 'bit_on')
        _build(8, 'bit_off')
        off_sink, _, _, _ = _run_ladder(8, ctl=None, tag='bit_off')
        on_sink, _, _, _ = _run_ladder(
            8, ctl=_controller(kfac_on, params_on), tag='bit_on')
        np.testing.assert_array_equal(np.asarray(off_sink.floats()),
                                      np.asarray(on_sink.floats()))
        assert all(v == 1 for v in step_on.trace_counts.values())


class TestQuarantineGateSemantics:
    def test_gated_bucket_serves_raw_gradient(self):
        """KFAC.precondition(gates=...): a gated-off bucket's layers
        get exactly the (nu-scaled) RAW gradient — the plain SGD
        direction — even when their stored inverses are pure NaN; an
        all-ones gate is bit-identical to no gate."""
        from distributed_kfac_pytorch_tpu.observability import (
            metrics as obs_metrics,
        )
        model = _Net()
        kfac = KFAC(model, kl_clip=None, damping=0.003, lr=0.1)
        variables, _ = kfac.init(jax.random.PRNGKey(0),
                                 jnp.zeros((2, 6)))
        params = variables['params']
        state = kfac.init_state(params)
        grads = jax.tree.map(jnp.ones_like, params)
        # Poison one layer's stored inverses wholesale.
        name = sorted(state['inverses'])[0]
        state['inverses'][name] = jax.tree.map(
            lambda x: jnp.full_like(x, jnp.nan),
            state['inverses'][name])
        spec = kfac.specs[name]
        from distributed_kfac_pytorch_tpu import layers as L

        def subgrads(tree):
            sub = tree
            for part in spec.path:
                sub = sub[part]
            return sub
        gm_shape = jax.eval_shape(
            lambda p: L.grads_to_matrix(spec, p),
            subgrads(params)).shape
        key = obs_metrics.shape_key(gm_shape)
        gates = {k: 1.0 for k in kfac.metric_bucket_keys(params)}
        gates[key] = 0.0
        out = kfac.precondition(state, grads, 0.003, 0.1, gates=gates)
        # Gated layer: finite and exactly the raw gradient (nu == 1
        # with kl_clip=None).
        for leaf in jax.tree_util.tree_leaves(subgrads(out)):
            np.testing.assert_array_equal(np.asarray(leaf),
                                          np.ones_like(leaf))
        # Everything else is finite too: the NaN branch was a select.
        assert integrity.finite_ok(out)
        # All-ones gates == ungated, bitwise.
        clean = kfac.init_state(params)
        ones = {k: 1.0 for k in gates}
        a = kfac.precondition(clean, grads, 0.003, 0.1)
        b = kfac.precondition(clean, grads, 0.003, 0.1, gates=ones)
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)):
            np.testing.assert_array_equal(np.asarray(x),
                                          np.asarray(y))


# ---------------------------------------------------------------------------
# Checkpoint integrity (resilience.integrity + the verified resume walk)
# ---------------------------------------------------------------------------

def _bundle(w, step, **kw):
    return ckpt_lib.bundle_state(
        {'w': jnp.asarray(w, jnp.float32)}, (), {}, {},
        step=step, epoch=kw.pop('epoch', 0),
        step_in_epoch=kw.pop('offset', step), data_seed=0, **kw)


def _args(tmp_path, **kw):
    kw.setdefault('no_resume', False)
    kw.setdefault('resume_step', None)
    return argparse.Namespace(checkpoint_dir=str(tmp_path), **kw)


class TestIntegrity:
    def test_checksum_roundtrip_and_flip(self):
        t = _bundle([1.0, 2.0], 3)
        assert t['scalars'][integrity.CHECKSUM_KEY] != \
            integrity.UNVERIFIED
        ok, rec, act = integrity.verify_tree(t)
        assert ok is True and rec == act
        bad = {**t, 'params': {'w': t['params']['w'].at[0].set(9.0)}}
        ok, rec, act = integrity.verify_tree(bad)
        assert ok is False and rec != act
        assert 'mismatch' in integrity.describe_mismatch(rec, act)

    def test_checksum_excludes_itself_and_is_stable(self):
        t = _bundle([1.0, 2.0], 3)
        # Recomputing over the stamped tree matches the stamp: the
        # digest excludes its own field.
        assert integrity.tree_checksum(t) == \
            t['scalars'][integrity.CHECKSUM_KEY]

    def test_template_stamp_skips_hash(self):
        """integrity='template' carries the checksum FIELD (orbax
        restore structures are exact) with the unverified sentinel —
        no host fetch/hash for a digest nobody reads."""
        t = ckpt_lib.bundle_state({'w': jnp.ones(2)}, (), {}, {},
                                  integrity='template', step=1,
                                  epoch=0, step_in_epoch=0,
                                  data_seed=0)
        assert t['scalars'][integrity.CHECKSUM_KEY] == \
            integrity.UNVERIFIED
        ok, rec, _ = integrity.verify_tree(t)
        assert ok is None and rec == integrity.UNVERIFIED
        # Structure matches the real r16 bundle (template-compatible).
        real = _bundle([1.0, 1.0], 1)
        assert set(t['scalars']) == set(real['scalars'])

    def test_opt_out_and_pre_r16_detection(self):
        old = ckpt_lib.bundle_state({'w': jnp.zeros(2)}, (), {}, {},
                                    integrity=False, step=1, epoch=0,
                                    step_in_epoch=0, data_seed=0)
        assert integrity.CHECKSUM_KEY not in old['scalars']
        ok, rec, _ = integrity.verify_tree(old)
        assert ok is None and rec is None
        stripped = integrity.strip_checksum(_bundle([0.0], 0))
        assert integrity.CHECKSUM_KEY not in stripped['scalars']

    def test_finite_ok(self):
        assert integrity.finite_ok({'a': jnp.ones(3)})
        assert not integrity.finite_ok(
            {'a': jnp.array([1.0, jnp.nan])})
        assert integrity.finite_ok({'i': jnp.arange(3)})  # ints pass

    def test_scalar_representation_stable_across_restore(self, tmp_path):
        """Save/restore round-trip must verify: scalar leaves hash by
        value, so python-int vs 0-d-array representation drift between
        save and restore cannot fake a corruption."""
        mgr = ckpt_lib.CheckpointManager(str(tmp_path / 's'))
        t = _bundle([1.0, 2.0, 3.0], 5)
        mgr.save(5, t, blocking=True)
        restored = mgr.restore(5, like=_bundle([0.0, 0.0, 0.0], 0))
        ok, _, _ = integrity.verify_tree(restored)
        assert ok is True
        mgr.close()


class TestVerifiedResumeWalk:
    def test_corrupt_newest_walks_back_with_quarantine_event(
            self, tmp_path):
        sm = ckpt_lib.CheckpointManager(str(tmp_path / 's'),
                                        max_to_keep=10)
        em = ckpt_lib.CheckpointManager(str(tmp_path / 'e'))
        sm.save(2, _bundle([2.0], 2), blocking=True)
        sm.save(4, _bundle([4.0], 4), blocking=True)
        faults.corrupt_bundle_file(sm.directory, 4)
        sink = _EventSink()
        with warnings.catch_warnings():
            warnings.simplefilter('ignore')
            out = resil_cli.resume(_args(tmp_path), em, sm,
                                   _bundle([0.0], 0), sink=sink)
        tree, _, _, src = out
        assert src == 'step' and int(tree['scalars']['step']) == 2
        kinds = sink.kinds()
        assert kinds.count('ckpt_quarantine') == 1
        q = dict(sink.events[kinds.index('ckpt_quarantine')][1])
        assert q['label'] == 4 and q['source'] == 'step'
        sm.close(), em.close()

    def test_crash_in_save_torn_dir_then_verified_restore(
            self, tmp_path):
        """Satellite: crash-during-save leaves a torn orbax tmp dir;
        the resume walk never surfaces it and lands on the newest
        VERIFIED bundle — with the newest finalized bundle ALSO
        corrupt, that means quarantining it and walking back."""
        sm = ckpt_lib.CheckpointManager(str(tmp_path / 's'),
                                        max_to_keep=10)
        em = ckpt_lib.CheckpointManager(str(tmp_path / 'e'))
        sm.save(2, _bundle([2.0], 2), blocking=True)
        sm.save(4, _bundle([4.0], 4), blocking=True)
        faults.torn_step_dir(sm.directory, 6)   # killed writer @6
        faults.corrupt_bundle_file(sm.directory, 4)  # bit rot @4
        sink = _EventSink()
        with warnings.catch_warnings():
            warnings.simplefilter('ignore')
            out = resil_cli.resume(_args(tmp_path), em, sm,
                                   _bundle([0.0], 0), sink=sink)
        tree, _, _, _ = out
        assert int(tree['scalars']['step']) == 2
        np.testing.assert_array_equal(
            np.asarray(tree['params']['w']), [2.0])
        assert sink.kinds().count('ckpt_quarantine') == 1
        sm.close(), em.close()

    def test_all_corrupt_fails_closed(self, tmp_path):
        sm = ckpt_lib.CheckpointManager(str(tmp_path / 's'))
        em = ckpt_lib.CheckpointManager(str(tmp_path / 'e'))
        sm.save(2, _bundle([2.0], 2), blocking=True)
        faults.corrupt_bundle_file(sm.directory, 2)
        with warnings.catch_warnings():
            warnings.simplefilter('ignore')
            with pytest.raises(SystemExit, match='failed restore'):
                resil_cli.resume(_args(tmp_path), em, sm,
                                 _bundle([0.0], 0))
        sm.close(), em.close()

    def test_explicit_resume_step_corrupt_is_fatal(self, tmp_path):
        sm = ckpt_lib.CheckpointManager(str(tmp_path / 's'))
        em = ckpt_lib.CheckpointManager(str(tmp_path / 'e'))
        sm.save(2, _bundle([2.0], 2), blocking=True)
        sm.save(4, _bundle([4.0], 4), blocking=True)
        faults.corrupt_bundle_file(sm.directory, 4)
        with warnings.catch_warnings():
            warnings.simplefilter('ignore')
            with pytest.raises(SystemExit):
                resil_cli.resume(_args(tmp_path, resume_step=4), em,
                                 sm, _bundle([0.0], 0))
        sm.close(), em.close()

    def test_pre_r16_bundle_restores_unverified_with_warning(
            self, tmp_path):
        sm = ckpt_lib.CheckpointManager(str(tmp_path / 's'))
        em = ckpt_lib.CheckpointManager(str(tmp_path / 'e'))
        old = ckpt_lib.bundle_state({'w': jnp.ones(2)}, (), {}, {},
                                    integrity=False, step=5, epoch=0,
                                    step_in_epoch=5, data_seed=0)
        sm.save(5, old, blocking=True)
        with pytest.warns(RuntimeWarning, match='UNVERIFIED'):
            out = resil_cli.resume(_args(tmp_path), em, sm,
                                   _bundle([0.0, 0.0], 0))
        assert int(out[0]['scalars']['step']) == 5
        sm.close(), em.close()

    def test_rollback_restore_skips_nonfinite_bundle(self, tmp_path):
        """A bundle saved AFTER the state was poisoned checksums
        perfectly — the rollback walk must still refuse it."""
        sm = ckpt_lib.CheckpointManager(str(tmp_path / 's'),
                                        max_to_keep=10)
        clean = ckpt_lib.bundle_state(
            {'w': jnp.ones(1)}, (), {'f': jnp.array([1.0])}, {},
            step=2, epoch=0, step_in_epoch=2, data_seed=0)
        sm.save(2, clean, blocking=True)
        poisoned = ckpt_lib.bundle_state(
            {'w': jnp.ones(1)}, (), {'f': jnp.array([jnp.nan])}, {},
            step=4, epoch=0, step_in_epoch=4, data_seed=0)
        sm.save(4, poisoned, blocking=True)
        sink = _EventSink()
        like = ckpt_lib.bundle_state(
            {'w': jnp.zeros(1)}, (), {'f': jnp.zeros(1)}, {},
            step=0, epoch=0, step_in_epoch=0, data_seed=0)
        with warnings.catch_warnings():
            warnings.simplefilter('ignore')
            label, tree = selfheal.rollback_restore(
                sm, like, from_step=9, onset_step=5, sink=sink)
        assert label == 2
        kinds = sink.kinds()
        assert 'ckpt_quarantine' in kinds
        assert 'selfheal_rollback' in kinds
        sm.close()

    @pytest.mark.slow
    def test_spmd_crash_in_save_then_verified_resume(self, tmp_path):
        """Satellite (slow tier): real 8-dev SPMD K-FAC bundles — a
        torn step dir (crash-in-save debris) plus a bit-rotted newest
        bundle; resume quarantines the corrupt one and restores the
        older verified bundle with its row-sharded stacks intact."""
        kfac, mesh, dkfac, tx, step_fn, params0 = _build(8)
        state = _fresh_state(mesh, dkfac, tx, params0)
        sm = ckpt_lib.CheckpointManager(str(tmp_path / 'steps'),
                                        max_to_keep=10)
        em = ckpt_lib.CheckpointManager(str(tmp_path / 'epochs'))

        def bundle_fn(st, sie):
            return ckpt_lib.bundle_state(
                st.params, st.opt_state,
                dkfac.state_dict(st.kfac_state), st.extra_vars,
                step=st.step, epoch=st.epoch, step_in_epoch=sie,
                data_seed=7)
        # Two steps of real training between saves so the bundles
        # differ in content.
        batches = iter(_data(n=16, bs=8))
        engine.train_epoch(step_fn, state,
                           launch.global_batches(mesh, batches),
                           _HYPER)
        sm.save(2, bundle_fn(state, 2), blocking=True)
        batches = iter(_data(n=16, bs=8, seed=1))
        engine.train_epoch(step_fn, state,
                           launch.global_batches(mesh, batches),
                           _HYPER)
        sm.save(4, bundle_fn(state, 4), blocking=True)
        faults.torn_step_dir(sm.directory, 6)
        faults.corrupt_bundle_file(sm.directory, 4)
        sink = _EventSink()
        with warnings.catch_warnings():
            warnings.simplefilter('ignore')
            out = resil_cli.resume(
                _args(tmp_path), em, sm, bundle_fn(state, 0),
                sink=sink)
        tree, _, offset, src = out
        assert src == 'step' and int(tree['scalars']['step']) == 2
        assert offset == 2
        assert sink.kinds().count('ckpt_quarantine') == 1
        # The restored K-FAC state loads back onto the live mesh.
        restored = dkfac.load_state_dict(tree['kfac'], tree['params'])
        assert int(jax.device_get(restored['step'])) == 2
        sm.close(), em.close()

    def test_force_save_replaces_existing_label(self, tmp_path):
        """Review regression: an in-process rollback rewinds the
        epoch/step counters, so the replay re-saves labels whose
        pre-rollback bundles still exist — force=True must replace
        them (orbax's own force only bypasses the interval policy and
        still raises StepAlreadyExistsError)."""
        mgr = ckpt_lib.CheckpointManager(str(tmp_path / 'e'))
        mgr.save(3, _bundle([1.0], 3), blocking=True)
        mgr.save(3, _bundle([9.0], 3), force=True, blocking=True)
        r = mgr.restore(3, like=_bundle([0.0], 0))
        np.testing.assert_array_equal(np.asarray(r['params']['w']),
                                      [9.0])
        assert integrity.verify_tree(r)[0] is True
        mgr.close()

    def test_rollback_restore_quarantines_nonfinite_on_disk(
            self, tmp_path):
        """Review regression: a checksum-clean but poisoned bundle is
        MOVED aside when the rollback walk refuses it — otherwise the
        r8 relaunch resume (checksum-only) restores the poison right
        back after the ladder exhausts."""
        sm = ckpt_lib.CheckpointManager(str(tmp_path / 's'),
                                        max_to_keep=10)
        clean = ckpt_lib.bundle_state(
            {'w': jnp.ones(1)}, (), {'f': jnp.array([1.0])}, {},
            step=2, epoch=0, step_in_epoch=2, data_seed=0)
        sm.save(2, clean, blocking=True)
        poisoned = ckpt_lib.bundle_state(
            {'w': jnp.ones(1)}, (), {'f': jnp.array([jnp.nan])}, {},
            step=4, epoch=0, step_in_epoch=4, data_seed=0)
        sm.save(4, poisoned, blocking=True)
        like = ckpt_lib.bundle_state(
            {'w': jnp.zeros(1)}, (), {'f': jnp.zeros(1)}, {},
            step=0, epoch=0, step_in_epoch=0, data_seed=0)
        with warnings.catch_warnings():
            warnings.simplefilter('ignore')
            label, _ = selfheal.rollback_restore(
                sm, like, from_step=9, onset_step=5)
        assert label == 2
        # The poisoned bundle is no longer restorable by a plain
        # resume — its dir moved aside, kept for forensics.
        assert sm.all_steps() == [2]
        assert (tmp_path / 's' / '4.quarantined').exists()
        sm.close()

    def test_rollback_restore_respects_onset(self, tmp_path):
        sm = ckpt_lib.CheckpointManager(str(tmp_path / 's'),
                                        max_to_keep=10)
        sm.save(2, _bundle([2.0], 2), blocking=True)
        sm.save(6, _bundle([6.0], 6), blocking=True)
        label, _ = selfheal.rollback_restore(
            sm, _bundle([0.0], 0), from_step=9, onset_step=4)
        assert label == 2  # 6 is newer but past the fault onset
        with pytest.raises(selfheal.SelfHealExhausted):
            selfheal.rollback_restore(sm, _bundle([0.0], 0),
                                      from_step=9, onset_step=1)
        sm.close()


# ---------------------------------------------------------------------------
# Fault-spec parsing (satellite: messages + fail-closed)
# ---------------------------------------------------------------------------

class TestFaultSpecs:
    def test_new_kinds_parse(self):
        plan = faults.parse_spec(
            'corrupt-factor@3,corrupt-ckpt@5,diverge@7')
        assert plan.corrupt_factor_at == 3
        assert plan.corrupt_ckpt_at == 5
        assert plan.diverge_at == 7

    def test_unknown_kind_names_the_menu(self):
        with pytest.raises(ValueError) as ei:
            faults.parse_spec('explode@3')
        msg = str(ei.value)
        assert 'explode' in msg
        # The message enumerates EVERY valid kind with its grammar,
        # not just the bad token (satellite bugfix).
        for kind in ('preempt@K', 'corrupt-factor@K', 'corrupt-ckpt@K',
                     'diverge@K', 'resize@K->N'):
            assert kind in msg

    def test_bad_step_names_the_menu(self):
        with pytest.raises(ValueError) as ei:
            faults.parse_spec('preempt@x')
        assert 'integer step' in str(ei.value)
        assert 'resize@K->N' in str(ei.value)

    def test_duplicate_kind_fails_closed_at_parse(self):
        with pytest.raises(ValueError, match='more than once'):
            faults.parse_spec('preempt@2,preempt@5')

    def test_poison_factors_targets_first_layer(self):
        state = {'factors': {'b': {'A': jnp.eye(2)},
                             'a': {'A': jnp.eye(2), 'G': jnp.eye(3)}}}
        out = faults.poison_factors(state)
        assert not np.isfinite(np.asarray(out['factors']['a']['A'])).all()
        assert np.isfinite(np.asarray(out['factors']['b']['A'])).all()
        # input untouched (functional edit)
        assert np.isfinite(np.asarray(state['factors']['a']['A'])).all()

    def test_poison_params_scales_floats_only(self):
        params = {'w': jnp.ones(2), 'i': jnp.arange(2)}
        out = faults.poison_params(params, scale=4.0)
        np.testing.assert_array_equal(np.asarray(out['w']),
                                      [4.0, 4.0])
        np.testing.assert_array_equal(np.asarray(out['i']), [0, 1])

    def test_injections_fire_once_per_process(self, tmp_path):
        ckpt = policy_lib.StepCheckpointer(
            None, None, None,
            preemption=preemption.PreemptionHandler(signals=()),
            plan=faults.parse_spec('diverge@3'))
        state = engine.TrainState(params={'w': jnp.ones(2)},
                                  opt_state=(), kfac_state=None,
                                  extra_vars={}, step=3)
        ckpt.after_step(state, 3)
        first = np.asarray(state.params['w']).copy()
        assert (first != 1.0).all()
        # A rollback rewound past the fault step: the latch holds.
        ckpt.after_step(state, 3)
        np.testing.assert_array_equal(np.asarray(state.params['w']),
                                      first)


# ---------------------------------------------------------------------------
# Observability satellites: health by-kind, report section, gate metric
# ---------------------------------------------------------------------------

class TestHealthSummaryByKind:
    def test_summary_counts_per_kind(self):
        mon = obs_health.HealthMonitor(action='skip')
        mon.observe({'kind': 'step', 'step': 1,
                     'metrics': {'loss': float('nan'),
                                 'kfac/damping': -1.0}})
        mon.observe({'kind': 'step', 'step': 2,
                     'metrics': {'kfac/nonfinite_skips': 1.0}})
        s = mon.summary()
        assert s['events'] == 3
        assert s['by_kind'] == {'nonfinite': 2, 'damping': 1}
        assert s['nonfinite_skips'] == 1

    def test_kinds_parallel_events(self):
        mon = obs_health.HealthMonitor(action='skip')
        mon.observe({'kind': 'step', 'step': 1,
                     'metrics': {'loss': float('inf')}})
        assert len(mon.events) == len(mon.event_kinds) == 1
        assert mon.event_kinds == ['nonfinite']


def _selfheal_stream(path):
    s = obs_sink.JsonlMetricsSink(str(path), interval=1)
    for i in range(6):
        s.step_record(i, {'loss': 1.0}, host_step_ms=10.0)
    s.event_record('selfheal_escalate', global_step=2, kind='nonfinite',
                   damping_mult=10.0, bad_windows=1)
    s.event_record('selfheal_quarantine', global_step=3, bucket='8x7',
                   layers='Dense_0', nonfinite_layers='Dense_0')
    s.event_record('selfheal_readmit', global_step=5, bucket='8x7',
                   windows=2)
    s.event_record('selfheal_deescalate', global_step=5,
                   damping_mult=1.0)
    s.event_record('ckpt_quarantine', source='step', label=4,
                   reason='digest mismatch')
    s.event_record('selfheal_rollback', from_step=9, to_step=2,
                   label=2, reason='persistent badness')
    s.close()


class TestReportAndGate:
    def test_report_selfheal_section_and_json(self, tmp_path, capsys):
        path = tmp_path / 'run.jsonl'
        _selfheal_stream(path)
        assert obs_report.main([str(path)]) == 0
        text = capsys.readouterr().out
        assert '-- self-healing (6 ladder event(s)) --' in text
        assert 'rollbacks: 1 in-process' in text
        assert obs_report.main([str(path), '--json']) == 0
        js = json.loads(capsys.readouterr().out)
        sh = js['selfheal']
        assert sh['escalations'] == 1
        assert sh['quarantines'] == 1
        assert sh['readmits'] == 1
        assert sh['rollbacks'] == 1
        assert sh['ckpt_quarantines'] == 1
        assert 'health_event_counts' in js

    def test_every_selfheal_event_kind_registered(self):
        for kind in ('selfheal_escalate', 'selfheal_deescalate',
                     'selfheal_quarantine', 'selfheal_readmit',
                     'selfheal_rollback', 'ckpt_quarantine'):
            assert kind in obs_sink.EVENT_KINDS

    def test_gate_counts_rollbacks(self, tmp_path, capsys):
        path = tmp_path / 'run.jsonl'
        _selfheal_stream(path)
        records, _ = obs_sink.read_jsonl_tolerant(str(path))
        m = obs_gate.gate_metrics(records)
        assert m['selfheal_rollbacks'] == 1
        # Baseline with zero rollbacks breaches on this run.
        breaches, _ = obs_gate.compare(m, {'selfheal_rollbacks': 0})
        assert any(b['metric'] == 'selfheal_rollbacks'
                   for b in breaches)
        # A pre-r16 baseline without the metric skips it.
        breaches, skipped = obs_gate.compare(m, {'retraces': 0})
        assert not any(b['metric'] == 'selfheal_rollbacks'
                       for b in breaches)
        assert any('selfheal_rollbacks' in s for s in skipped)


class TestEngineBitIdentityPolicyOff:
    def test_selfheal_none_is_default_path(self):
        """train_epoch(selfheal=None) must be byte-for-byte the
        historical engine: same signature default, no hyper copy."""
        import inspect
        sig = inspect.signature(engine.train_epoch)
        assert sig.parameters['selfheal'].default is None
