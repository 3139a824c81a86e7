"""Training applications layer: utils, datasets, engine, checkpointing.

Covers the reference L4 machinery (SURVEY.md §2 C13-C19): metric
averaging, label smoothing, LR schedule shape, data pipelines, the full
train/eval epoch loop, and checkpoint save/auto-resume round-trips.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_kfac_pytorch_tpu import KFAC, CommMethod
from distributed_kfac_pytorch_tpu.models import cifar_resnet
from distributed_kfac_pytorch_tpu.observability import tracing
from distributed_kfac_pytorch_tpu.parallel import distributed as D
from distributed_kfac_pytorch_tpu.training import (
    checkpoint as ckpt_lib,
    datasets,
    engine,
    optimizers,
    utils,
)


class TestUtils:
    def test_metric_weighted_average(self):
        m = utils.Metric('loss')
        m.update(1.0, n=1)
        m.update(3.0, n=3)
        assert m.avg == pytest.approx(2.5)

    def test_accuracy(self):
        logits = jnp.array([[0.1, 0.9], [0.8, 0.2]])
        assert float(utils.accuracy(logits, jnp.array([1, 1]))) == 0.5

    def test_label_smoothing_matches_plain_at_zero(self):
        logits = jax.random.normal(jax.random.PRNGKey(0), (4, 7))
        labels = jnp.array([0, 1, 2, 3])
        plain = utils.label_smooth_loss(logits, labels, 0.0)
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()
        assert float(plain) == pytest.approx(float(ce), rel=1e-6)

    def test_label_smoothing_penalizes_confidence(self):
        logits = jnp.array([[10.0, -10.0]])
        labels = jnp.array([0])
        assert float(utils.label_smooth_loss(logits, labels, 0.1)) > \
            float(utils.label_smooth_loss(logits, labels, 0.0))

    def test_lr_schedule_warmup_and_decay(self):
        # Reference semantics (examples/utils.py:50-61): factor 1 at epoch
        # 0, `workers` after warmup, x alpha at each decay epoch.
        f = utils.create_lr_schedule(workers=8, warmup_epochs=5,
                                     decay_schedule=[35, 75], alpha=0.1)
        assert f(0) == pytest.approx(1.0)
        assert f(5) == pytest.approx(8.0)
        assert f(34) == pytest.approx(8.0)
        assert f(35) == pytest.approx(0.8)
        assert f(75) == pytest.approx(0.08)


class TestDatasets:
    def test_synthetic_cifar_shapes(self):
        (tx, ty), (vx, vy) = datasets.get_cifar(None, synthetic_size=256)
        assert tx.shape == (256, 32, 32, 3) and ty.shape == (256,)
        assert vx.shape == (64, 32, 32, 3)
        assert tx.dtype == np.float32 and ty.dtype == np.int32

    def test_synthetic_splits_share_prototypes(self):
        # Same class -> correlated images across splits (learnable val).
        (tx, ty), (vx, vy) = datasets.get_cifar(None, synthetic_size=512)
        c = 3
        t_mean = tx[ty == c].mean(axis=0).ravel()
        v_mean = vx[vy == c].mean(axis=0).ravel()
        corr = np.corrcoef(t_mean, v_mean)[0, 1]
        assert corr > 0.5

    def test_epoch_batches_deterministic_and_complete(self):
        x = np.arange(40, dtype=np.float32).reshape(10, 2, 2, 1)
        y = np.arange(10, dtype=np.int32)
        b1 = list(datasets.epoch_batches(x, y, 4, seed=7, epoch=3))
        b2 = list(datasets.epoch_batches(x, y, 4, seed=7, epoch=3))
        assert len(b1) == 2  # drop_last
        for (xa, ya), (xb, yb) in zip(b1, b2):
            np.testing.assert_array_equal(xa, xb)
            np.testing.assert_array_equal(ya, yb)
        b3 = list(datasets.epoch_batches(x, y, 4, seed=7, epoch=4))
        assert not all(np.array_equal(a[1], b[1]) for a, b in zip(b1, b3))

    def test_augment_preserves_shape_and_stats(self):
        rng = np.random.default_rng(0)
        x = rng.normal(size=(8, 32, 32, 3)).astype(np.float32)
        out = datasets.augment_cifar(x, rng)
        assert out.shape == x.shape
        assert np.isfinite(out).all()

    def test_imagenet_tfdata_real_tree(self, tmp_path):
        """Exercise the real-data ImageFolder pipeline (round-2 VERDICT
        #10) against a tiny generated JPEG tree, so its first execution
        is not on a pod: class-table order, decode, augmentation shapes,
        normalization, and eval determinism."""
        tf = pytest.importorskip('tensorflow')
        rng = np.random.default_rng(0)
        # Deliberately create class_b FIRST with MORE images: if the
        # class table ever follows creation order instead of sorted
        # order, the per-label counts below flip and the test fails.
        for split, counts in (('train', {'class_b': 4, 'class_a': 2}),
                              ('val', {'class_b': 2, 'class_a': 2})):
            for cls, n_per in counts.items():
                d = tmp_path / split / cls
                d.mkdir(parents=True)
                for i in range(n_per):
                    img = rng.integers(0, 255, (40, 52, 3),
                                       dtype=np.uint8)
                    enc = tf.io.encode_jpeg(tf.constant(img))
                    (d / f'{i}.jpg').write_bytes(enc.numpy())

        train_ds, val_ds = datasets.imagenet_tfdata(str(tmp_path),
                                                    image_size=32)
        xs, ys = next(iter(train_ds.batch(6)))
        assert xs.shape == (6, 32, 32, 3)
        assert xs.dtype == tf.float32
        # Sorted class order: class_a (2 images) -> 0, class_b (4) -> 1.
        labels = ys.numpy().tolist()
        assert labels.count(0) == 2 and labels.count(1) == 4, labels
        # Normalized values are centered-ish, not raw [0, 255].
        assert float(tf.reduce_max(tf.abs(xs))) < 10.0

        v1 = next(iter(val_ds.batch(4)))[0].numpy()
        v2 = next(iter(val_ds.batch(4)))[0].numpy()
        np.testing.assert_array_equal(v1, v2)  # eval path deterministic
        assert v1.shape == (4, 32, 32, 3)


class TestOptimizers:
    def test_sgd_matches_torch_semantics(self):
        """wd folded before momentum: p -= lr*(m*buf + g + wd*p)."""
        cfg = optimizers.OptimConfig(base_lr=0.1, momentum=0.9,
                                     weight_decay=0.01,
                                     kfac_inv_update_freq=0)
        tx = optimizers.make_sgd(cfg)
        p = {'w': jnp.array([1.0])}
        g = {'w': jnp.array([0.5])}
        s = tx.init(p)
        u1, s = tx.update(g, s, p)
        # step 1: buf = g + wd*p = 0.51; update = -lr*buf
        np.testing.assert_allclose(u1['w'], -0.1 * 0.51, rtol=1e-6)
        p2 = optax.apply_updates(p, u1)
        u2, s = tx.update(g, s, p2)
        buf2 = 0.9 * 0.51 + (0.5 + 0.01 * float(p2['w'][0]))
        np.testing.assert_allclose(u2['w'], -0.1 * buf2, rtol=1e-6)

    def test_get_optimizer_wires_kfac(self):
        model = cifar_resnet.get_model('resnet20')
        cfg = optimizers.OptimConfig(kfac_inv_update_freq=10,
                                     kfac_cov_update_freq=2,
                                     comm_method='hybrid-opt')
        tx, lr_sched, kfac, sched = optimizers.get_optimizer(model, cfg)
        assert kfac is not None and sched is not None
        assert kfac.inv_update_freq == 10
        assert kfac.factor_update_freq == 2
        assert kfac.comm_method is CommMethod.HYBRID_OPT
        assert lr_sched(0) == pytest.approx(cfg.base_lr)

    def test_bf16_inverses_wired(self):
        import jax.numpy as jnp
        model = cifar_resnet.get_model('resnet20')
        cfg = optimizers.OptimConfig(kfac_inv_update_freq=10,
                                     bf16_inverses=True)
        _, _, kfac, _ = optimizers.get_optimizer(model, cfg)
        assert kfac.inv_dtype == jnp.bfloat16
        cfg = optimizers.OptimConfig(kfac_inv_update_freq=10)
        _, _, kfac, _ = optimizers.get_optimizer(model, cfg)
        assert kfac.inv_dtype == jnp.float32

    def test_kfac_disabled_when_freq_zero(self):
        model = cifar_resnet.get_model('resnet20')
        cfg = optimizers.OptimConfig(kfac_inv_update_freq=0)
        _, _, kfac, sched = optimizers.get_optimizer(model, cfg)
        assert kfac is None and sched is None

    def test_set_lr(self):
        cfg = optimizers.OptimConfig(kfac_inv_update_freq=0)
        tx = optimizers.make_sgd(cfg)
        p = {'w': jnp.zeros(1)}
        s = tx.init(p)
        s = optimizers.set_lr(s, 0.42)
        g = {'w': jnp.array([1.0])}
        u, _ = tx.update(g, s, p)
        np.testing.assert_allclose(u['w'], -0.42, rtol=1e-6)


def _small_setup(n_epoch_batches=2, batch=32):
    model = cifar_resnet.get_model('resnet20')
    kfac = KFAC(model, factor_update_freq=1, inv_update_freq=2,
                damping=0.003, lr=0.1)
    x0 = jnp.zeros((2, 16, 16, 3))
    variables, _ = kfac.init(jax.random.PRNGKey(0), x0)
    params = variables['params']
    extra = {'batch_stats': variables['batch_stats']}
    mesh = D.make_kfac_mesh(comm_method=CommMethod.HYBRID_OPT,
                            grad_worker_fraction=0.5)
    dkfac = D.DistributedKFAC(kfac, mesh, params)
    kstate = dkfac.init_state(params)
    tx = optax.sgd(0.05, momentum=0.9)
    opt_state = tx.init(params)

    def loss_fn(out, b):
        return utils.label_smooth_loss(out, b[1], 0.0)

    step_fn = dkfac.build_train_step(
        loss_fn, tx, mutable_cols=('batch_stats',),
        metrics_fn=lambda out, b: {'acc': utils.accuracy(out, b[1])},
        donate=False)
    rng = np.random.default_rng(0)
    data = [(rng.normal(size=(batch, 16, 16, 3)).astype(np.float32),
             rng.integers(0, 10, batch).astype(np.int32))
            for _ in range(n_epoch_batches)]
    state = engine.TrainState(params=params, opt_state=opt_state,
                              kfac_state=kstate, extra_vars=extra)
    return model, dkfac, tx, step_fn, state, data, mesh, loss_fn


class TestEngine:
    @pytest.mark.slow
    def test_train_epoch_and_eval(self):
        (model, dkfac, tx, step_fn, state, data, mesh,
         loss_fn) = _small_setup()
        hyper = {'lr': 0.05, 'damping': 0.003,
                 'factor_update_freq': 1, 'inv_update_freq': 2}
        m = engine.train_epoch(step_fn, state, data, hyper)
        assert set(m) >= {'loss', 'acc', 'time_s', 'ms_per_iter'}
        assert np.isfinite(m['loss'])
        assert state.step == len(data)
        assert state.epoch == 1

        eval_step = engine.make_eval_step(
            model, loss_fn, mesh, model_args_fn=lambda b: (b[0], False))
        em = engine.evaluate(eval_step, state, data)
        assert np.isfinite(em['loss']) and 0.0 <= em['acc'] <= 1.0

    def test_static_cadence_phase_mismatch_raises(self):
        """A host step counter out of phase with the on-device K-FAC
        counter silently shifts the factor/inverse schedule — the epoch
        loop asserts the invariant at epoch boundaries (ADVICE r1)."""
        (model, dkfac, tx, step_fn, state, data, mesh,
         loss_fn) = _small_setup()
        hyper = {'lr': 0.05, 'damping': 0.003,
                 'factor_update_freq': 1, 'inv_update_freq': 2}
        state.step = 7  # e.g. TrainState rebuilt without restoring step
        with pytest.raises(RuntimeError, match='phase error'):
            engine.train_epoch(step_fn, state, data, hyper)

    def test_precise_bn_recalibrate_exact(self):
        """The recalibrated stats must equal the plain average of each
        batch's population statistics (the precise-BN definition) —
        pinned against a hand-computed numpy oracle, with two BN layers
        at DIFFERENT momenta to prove the momentum extraction is
        per-leaf, not a global assumption."""
        import flax.linen as nn

        class Net(nn.Module):
            @nn.compact
            def __call__(self, x, train=True):
                x = nn.Dense(6, name='d1')(x)
                x = nn.BatchNorm(use_running_average=not train,
                                 momentum=0.9, name='bn1')(x)
                x = nn.relu(x)
                x = nn.Dense(4, name='d2')(x)
                x = nn.BatchNorm(use_running_average=not train,
                                 momentum=0.6, name='bn2')(x)
                return x

        model = Net()
        rng = np.random.default_rng(3)
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.zeros((4, 5), jnp.float32))
        params = variables['params']
        extra = {'batch_stats': variables['batch_stats']}
        batches = [(rng.normal(size=(16, 5)).astype(np.float32),)
                   for _ in range(3)]

        new = engine.precise_bn_recalibrate(model, params, extra, batches)
        # Oracle: per-batch population stats of each BN layer's INPUT,
        # averaged over batches.
        d1k = np.asarray(params['d1']['kernel'])
        d1b = np.asarray(params['d1']['bias'])
        means1, vars1 = [], []
        for (xb,) in batches:
            h = xb @ d1k + d1b
            means1.append(h.mean(0))
            vars1.append(h.var(0))
        got = new['batch_stats']['bn1']
        np.testing.assert_allclose(got['mean'],
                                   np.mean(means1, axis=0), rtol=1e-4)
        np.testing.assert_allclose(got['var'],
                                   np.mean(vars1, axis=0), rtol=1e-4)
        # bn2's input depends on bn1's TRAIN-mode output (batch stats,
        # not running stats), so recompute it the same way.
        b1 = params['bn1']
        d2k = np.asarray(params['d2']['kernel'])
        d2b = np.asarray(params['d2']['bias'])
        means2, vars2 = [], []
        for i, (xb,) in enumerate(batches):
            h = xb @ d1k + d1b
            hn = (h - means1[i]) / np.sqrt(vars1[i] + 1e-5)
            hn = hn * np.asarray(b1['scale']) + np.asarray(b1['bias'])
            h2 = np.maximum(hn, 0.0) @ d2k + d2b
            means2.append(h2.mean(0))
            vars2.append(h2.var(0))
        got2 = new['batch_stats']['bn2']
        np.testing.assert_allclose(got2['mean'],
                                   np.mean(means2, axis=0), rtol=1e-4)
        np.testing.assert_allclose(got2['var'],
                                   np.mean(vars2, axis=0),
                                   rtol=1e-3, atol=1e-5)
        # Other collections and params untouched; stateless models
        # pass through unchanged.
        assert engine.precise_bn_recalibrate(
            model, params, {}, batches) == {}

    def test_precise_bn_recalibrate_mesh(self):
        """Mesh path: per-shard statistics pmean'd — must match the
        single-device result on the same global batch."""
        model = cifar_resnet.get_model('resnet20')
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.zeros((2, 16, 16, 3)))
        params = variables['params']
        extra = {'batch_stats': variables['batch_stats']}
        rng = np.random.default_rng(0)
        batches = [(rng.normal(size=(16, 16, 16, 3)).astype(np.float32),
                    rng.integers(0, 10, 16).astype(np.int32))
                   for _ in range(2)]
        mesh = D.make_kfac_mesh()
        got = engine.precise_bn_recalibrate(
            model, params, extra, batches, mesh,
            model_args_fn=lambda b: (b[0],))
        ref = engine.precise_bn_recalibrate(
            model, params, extra, batches, None,
            model_args_fn=lambda b: (b[0],))
        # The stem BN's input is BN-free, so mean-of-shard-means equals
        # the global mean exactly there. Deeper layers see per-shard
        # train-mode normalization upstream (local-BN semantics — the
        # reference's per-GPU torch BN behaves identically), so they
        # only agree approximately at shard batch 8; var leaves
        # additionally lack the between-shard component.
        np.testing.assert_allclose(got['batch_stats']['bn1']['mean'],
                                   ref['batch_stats']['bn1']['mean'],
                                   rtol=1e-4, atol=1e-5)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=0.5,
                                                    atol=0.06),
            got['batch_stats'], ref['batch_stats'])

    def test_eval_step_single_device(self):
        model = cifar_resnet.get_model('resnet20')
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.zeros((2, 16, 16, 3)), train=False)
        eval_step = engine.make_eval_step(
            model, lambda out, b: utils.label_smooth_loss(out, b[1]),
            mesh=None, model_args_fn=lambda b: (b[0], False))
        x = np.zeros((4, 16, 16, 3), np.float32)
        y = np.zeros((4,), np.int32)
        m = eval_step(variables['params'],
                      {'batch_stats': variables['batch_stats']}, (x, y))
        assert np.isfinite(float(m['loss']))


# -- the epoch loop's running means (PR 28) --------------------------------

PERIOD, PLAIN_KEYS, CAPTURE_KEYS = 4, 4, 40


@pytest.fixture(scope='module')
def builds():
    """The benchmark's own counter of the executables JAX builds, which
    is what its ``compiles_in_window`` reads."""
    from kfac_bench.run import BuildCounter
    return BuildCounter(jax)


def _wide_step_fn(devices):
    """A step function of ``build_train_step``'s shape whose first step
    of every ``PERIOD`` captures: 40 metrics where a plain step returns
    4, floats and an int32 among both, every value its own function of
    the step's batch. Over ``devices`` > 1 the metrics come back
    committed to a mesh, as the built step's do."""
    trace_counts = {}
    sharding = None
    if devices > 1:
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:devices]), ('d',))
        sharding = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec())

    def program(x, capture):
        trace_counts[capture] = trace_counts.get(capture, 0) + 1
        metrics = {'loss': x * 0.37 + 2.0, 'acc': 0.5 + 0.4 * jnp.cos(x),
                   'grad_norm': 1e3 + x,
                   'kfac/nonfinite_skips': (x * 3).astype(jnp.int32)}
        if capture:
            metrics['moe/rows_here'] = (380 + x).astype(jnp.int32)
            metrics.update({f'kfac/bucket_norm/{i}': (1.1 + jnp.sin(x + i)) * (i + 1)
                            for i in range(CAPTURE_KEYS - PLAIN_KEYS - 1)})
        return metrics

    program = jax.jit(program, static_argnums=1, out_shardings=sharding)
    calls = []

    def step_fn(params, opt_state, kfac_state, extra_vars, batch, hyper):
        capture = len(calls) % PERIOD == 0
        calls.append(capture)
        return (params, opt_state, kfac_state, extra_vars,
                program(batch, capture))

    step_fn.trace_counts = trace_counts
    step_fn.calls = calls
    return step_fn


def _parents_loop(step_fn, periods):
    """The steps of ``periods`` periods through the parent's loop: a
    ``Metric`` a key, updated on the steps that carried the key. Returns
    the meters and every value in float64, key by key."""
    meters, per_key = {}, {}
    for i in range(periods * PERIOD):
        out = step_fn(None, None, None, None, jnp.float32(i), {})[4]
        for k, v in out.items():
            meters.setdefault(k, utils.Metric(k)).update(v)
            per_key.setdefault(k, []).append(float(np.float64(v)))
    del step_fn.calls[:]
    return meters, per_key


class _ListSink:
    """A duck-typed sink, as the tests around this one pass."""

    def __init__(self):
        self.steps, self.epochs = [], []

    def step_record(self, step, metrics, **kw):
        self.steps.append((step, metrics))

    def epoch_record(self, epoch, metrics, **kw):
        self.epochs.append((metrics, kw))

    def flush(self):
        pass


def _run_epoch(step_fn, periods, sink):
    state = engine.TrainState(params={}, opt_state={}, kfac_state={},
                              extra_vars={})
    batches = [jnp.float32(i) for i in range(periods * PERIOD)]
    before = tracing.counters().get('kfac/host/meter_dispatches', 0)
    out = engine.train_epoch(step_fn, state, batches, {}, verbose=False,
                             metrics_sink=sink)
    issued = tracing.counters()['kfac/host/meter_dispatches'] - before
    return out, issued


class TestEpochMeters:
    @pytest.mark.parametrize('devices', [1, 2])
    @pytest.mark.parametrize('with_sink', [False, True])
    def test_one_dispatch_a_step_and_the_parents_means(self, devices,
                                                       with_sink):
        step_fn = _wide_step_fn(devices)
        meters, per_key = _parents_loop(step_fn, 3)
        assert sorted({len(v) for v in per_key.values()}) == [3, 12]
        sink = _ListSink() if with_sink else None
        out, issued = _run_epoch(step_fn, 3, sink)
        # At most one device execution a step, 4 keys or 40.
        assert issued <= 3 * PERIOD
        assert list(out)[:PLAIN_KEYS] == list(per_key)[:PLAIN_KEYS]
        assert set(out) == set(per_key) | {'time_s', 'ms_per_iter'}
        assert len(per_key) == CAPTURE_KEYS
        # A capturing step's keys are averaged over the capturing steps
        # alone, as the parent's loop does.
        for k, values in per_key.items():
            assert out[k] == pytest.approx(np.mean(values), rel=1e-6), k
            assert out[k] == pytest.approx(meters[k].avg, rel=1e-6), k
        assert out['moe/rows_here'] == pytest.approx(384.0)
        if with_sink:
            assert [s for s, _ in sink.steps] == list(range(3 * PERIOD))
            assert [len(m) for _, m in sink.steps] == [
                CAPTURE_KEYS, *[PLAIN_KEYS] * 3] * 3
            epoch, kw = sink.epochs[-1]
            assert epoch == out
            assert kw['counters']['kfac/host/meter_dispatches'] >= issued

    @pytest.mark.parametrize('devices', [1, 2])
    def test_the_accumulate_is_built_once_a_key_set(self, devices, builds):
        step_fn = _wide_step_fn(devices)
        _run_epoch(step_fn, 1, None)      # the first period: warm-up
        traced = dict(step_fn.trace_counts)
        assert traced == {True: 1, False: 1}
        built = builds.builds
        # An epoch of its own, as the benchmark's window is after its
        # warm-up: new sums, no new program.
        out, issued = _run_epoch(step_fn, 2, _ListSink())
        assert builds.builds == built
        assert step_fn.trace_counts == traced
        assert issued == 2 * PERIOD
        assert out['moe/rows_here'] == pytest.approx(380.0 + 2.0)

    def test_an_epoch_with_no_metrics_returns_its_times(self):
        def step_fn(params, opt_state, kfac_state, extra_vars, batch,
                    hyper):
            return params, opt_state, kfac_state, extra_vars, {}

        out, issued = _run_epoch(step_fn, 1, None)
        assert set(out) == {'time_s', 'ms_per_iter'} and issued == 0

    def test_running_means_keeps_metrics_dtype_rules(self):
        """An int or a bool is averaged as a float, a python number is
        taken as it comes, and nothing is read before ``averages``."""
        means = utils.RunningMeans()
        means.update({'n': jnp.int32(3), 'flag': jnp.bool_(True), 'x': 0.5})
        means.update({'n': jnp.int32(4), 'flag': jnp.bool_(False),
                      'x': 1.5})
        means.update({'x': 4.0})
        assert means.averages() == {'n': 3.5, 'flag': 0.5, 'x': 2.0}


class TestCheckpoint:
    @pytest.mark.slow
    def test_roundtrip_and_auto_resume(self, tmp_path):
        (model, dkfac, tx, step_fn, state, data, mesh,
         loss_fn) = _small_setup()
        hyper = {'lr': 0.05, 'damping': 0.003,
                 'factor_update_freq': 1, 'inv_update_freq': 2}
        engine.train_epoch(step_fn, state, data, hyper)

        mgr = ckpt_lib.CheckpointManager(str(tmp_path / 'ckpt'))
        tree = ckpt_lib.bundle_state(
            state.params, state.opt_state,
            dkfac.state_dict(state.kfac_state), state.extra_vars,
            step=state.step)
        mgr.save(0, tree)
        assert mgr.latest_epoch() == 0

        restored = mgr.restore(like=tree)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6),
            restored['params'], state.params)
        kstate2 = dkfac.load_state_dict(restored['kfac'], state.params)
        np.testing.assert_allclose(
            int(kstate2['step']), int(state.kfac_state['step']))
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6),
            kstate2['factors'], state.kfac_state['factors'])
        mgr.close()

    @pytest.mark.slow
    def test_factor_only_checkpoint_recomputes_inverses(self, tmp_path):
        (model, dkfac, tx, step_fn, state, data, mesh,
         loss_fn) = _small_setup()
        hyper = {'lr': 0.05, 'damping': 0.003,
                 'factor_update_freq': 1, 'inv_update_freq': 2}
        engine.train_epoch(step_fn, state, data, hyper)
        sd = dkfac.state_dict(state.kfac_state, include_inverses=False)
        assert 'inv_stacks' not in sd
        kstate2 = dkfac.load_state_dict(sd, state.params)
        # Inverses recomputed from factors: nonzero and finite.
        leaves = jax.tree.leaves(kstate2['inv_stacks'])
        assert all(np.isfinite(np.asarray(x)).all() for x in leaves)
        assert any(np.abs(np.asarray(x)).sum() > 0 for x in leaves)

    def test_layer_mismatch_rejected(self):
        (model, dkfac, tx, step_fn, state, data, mesh,
         loss_fn) = _small_setup()
        sd = dkfac.state_dict(state.kfac_state)
        sd = {**sd, 'factors': {'bogus': sd['factors'][
            list(sd['factors'])[0]]}}
        with pytest.raises(ValueError, match='do not match'):
            dkfac.load_state_dict(sd, state.params)


class TestBundleStateRoundtrip:
    def test_roundtrip_with_schedulers_and_scalars(self, tmp_path):
        """bundle_state incl. schedulers + the r8 resume-point scalars
        round-trips exactly through save/restore (previously only
        exercised implicitly via CLI smokes)."""
        from distributed_kfac_pytorch_tpu.scheduler import (
            KFACParamScheduler,
        )

        class _KFACStub:
            damping = 0.003
            factor_update_freq = 1
            inv_update_freq = 10

        def make_sched():
            return KFACParamScheduler(
                _KFACStub(), damping_alpha=0.5,
                damping_schedule=[2, 4], update_freq_alpha=2.0,
                update_freq_schedule=[3])

        sched = make_sched()
        sched.step(3)  # advance past schedule points -> nontrivial state
        params = {'w': jnp.arange(6.0)}
        tree = ckpt_lib.bundle_state(
            params, {'momentum': jnp.ones(6)}, {}, {'extra': jnp.ones(2)},
            schedulers={'kfac': sched},
            step=37, epoch=3, step_in_epoch=5, data_seed=42)
        assert tree['schedulers']['kfac'] == sched.state_dict()
        mgr = ckpt_lib.CheckpointManager(str(tmp_path / 'ck'))
        mgr.save(0, tree, blocking=True)
        restored = mgr.restore(0, like=tree)
        sc = restored['scalars']
        # r16: bundles additionally carry the content checksum scalar
        # (resilience.integrity; verified by the resume walk).
        from distributed_kfac_pytorch_tpu.resilience import integrity
        assert {k: int(v) for k, v in sc.items()
                if k != integrity.CHECKSUM_KEY} == {
            'step': 37, 'epoch': 3, 'step_in_epoch': 5, 'data_seed': 42}
        assert integrity.verify_tree(restored)[0] is True
        np.testing.assert_array_equal(restored['params']['w'],
                                      np.arange(6.0))
        np.testing.assert_array_equal(restored['opt_state']['momentum'],
                                      np.ones(6))
        np.testing.assert_array_equal(restored['extra_vars']['extra'],
                                      np.ones(2))
        # scheduler state restores into a fresh scheduler and the
        # derived params match the saved scheduler's exactly
        fresh = make_sched()
        fresh.load_state_dict(jax.tree.map(
            lambda x: x.item() if hasattr(x, 'item') else x,
            restored['schedulers']['kfac']))
        assert fresh.params() == sched.params()
        mgr.close()


class TestAsyncCheckpoint:
    def test_async_save_then_restore_roundtrip(self, tmp_path):
        """save() is async by default (round-2 VERDICT #8): it returns
        before durability, later manager calls join the write, and the
        restored tree is exact."""
        mgr = ckpt_lib.CheckpointManager(str(tmp_path / 'ck'))
        tree = {'params': {'w': jnp.arange(8.0)},
                'scalars': {'step': 7}}
        mgr.save(0, tree)              # non-blocking
        # Training-loop work proceeds here while orbax writes...
        _ = jnp.sum(jnp.ones((64, 64)) @ jnp.ones((64, 64)))
        mgr.wait_until_finished()
        restored = mgr.restore(like=tree)
        np.testing.assert_array_equal(restored['params']['w'],
                                      np.arange(8.0))
        assert int(restored['scalars']['step']) == 7
        # A second async save joins implicitly through restore().
        tree2 = {'params': {'w': jnp.arange(8.0) * 2},
                 'scalars': {'step': 9}}
        mgr.save(1, tree2)
        restored2 = mgr.restore(like=tree2)
        np.testing.assert_array_equal(restored2['params']['w'],
                                      np.arange(8.0) * 2)
        mgr.close()


class TestDynamicLossScale:
    """loss_scale='dynamic' GradScaler parity through the distributed
    step (reference engine.py:38-41,75-80): overflow steps are skipped
    collectively, the scale backs off, factor statistics still advance
    (sanitized captures), and finite steps train normally."""

    def _build(self):
        from distributed_kfac_pytorch_tpu import fp16

        model = cifar_resnet.get_model('resnet20')
        kfac = KFAC(model, factor_update_freq=1, inv_update_freq=1,
                    damping=0.01, lr=0.05)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 16, 3))
        y = jax.random.randint(jax.random.PRNGKey(2), (8,), 0, 10)
        variables, _ = kfac.init(jax.random.PRNGKey(0), x)
        params = variables['params']
        extra = {'batch_stats': variables['batch_stats'],
                 'loss_scale': fp16.init_loss_scale(2.0 ** 10)}
        mesh = D.make_kfac_mesh(jax.devices()[:4])
        dkfac = D.DistributedKFAC(kfac, mesh, params)
        kstate = dkfac.init_state(params)
        tx = optax.sgd(0.05)
        opt_state = tx.init(params)

        def loss(out, batch):
            return optax.softmax_cross_entropy_with_integer_labels(
                out, batch[1]).mean()

        step = dkfac.build_train_step(loss, tx,
                                      mutable_cols=('batch_stats',),
                                      donate=False,
                                      loss_scale='dynamic')
        hyper = {'lr': 0.05, 'damping': 0.01,
                 'factor_update_freq': 1, 'inv_update_freq': 1}
        return step, params, opt_state, kstate, extra, (x, y), hyper

    @pytest.mark.slow
    def test_finite_step_trains_and_tracks_scale(self):
        step, params, opt_state, kstate, extra, batch, hyper = (
            self._build())
        p2, o2, k2, e2, m = step(params, opt_state, kstate, extra,
                                 batch, hyper,
                                 factor_update=True, inv_update=True)
        assert float(m['overflow']) == 0.0
        assert float(m['loss_scale']) == 2.0 ** 10
        # Params moved; scale unchanged (growth_interval not reached).
        moved = jax.tree.leaves(jax.tree.map(
            lambda a, b: float(jnp.abs(a - b).max()), params, p2))
        assert max(moved) > 0
        assert float(e2['loss_scale']['scale']) == 2.0 ** 10
        assert int(e2['loss_scale']['growth_count']) == 1

    @pytest.mark.slow
    def test_overflow_skips_update_and_backs_off(self):
        step, params, opt_state, kstate, extra, (x, y), hyper = (
            self._build())
        bad_x = x.at[0, 0, 0, 0].set(jnp.nan)
        p2, o2, k2, e2, m = step(params, opt_state, kstate, extra,
                                 (bad_x, y), hyper,
                                 factor_update=True, inv_update=True)
        assert float(m['overflow']) == 1.0
        # Collective skip: params and optimizer state are bit-identical.
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), params, p2)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), opt_state, o2)
        # Scale halved, growth counter reset, K-FAC step still advanced
        # (static-cadence phase stays aligned with the host counter).
        assert float(e2['loss_scale']['scale']) == 2.0 ** 9
        assert int(e2['loss_scale']['growth_count']) == 0
        assert int(k2['step']) == int(kstate['step']) + 1
        # Factor/inverse CONTENT did not advance (a zeroed-capture EWMA
        # would shrink factors at full weight), and BN running stats
        # were not poisoned by the non-finite forward pass.
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)),
            kstate['factors'], k2['factors'])
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)),
            extra['batch_stats'], e2['batch_stats'])
        for leaf in jax.tree.leaves(e2['batch_stats']):
            assert bool(jnp.isfinite(leaf).all())

    def test_missing_state_raises(self):
        step, params, opt_state, kstate, extra, batch, hyper = (
            self._build())
        extra.pop('loss_scale')
        with pytest.raises(ValueError, match='init_loss_scale'):
            step(params, opt_state, kstate, extra, batch, hyper,
                 factor_update=True, inv_update=True)

    @pytest.mark.slow
    def test_dynamic_scale_with_grad_accum(self):
        """The live scale threads through the micro-batch scan
        (accum_fwd_bwd's scale parameter) and overflow-skip still works
        when contributions come from accumulated micro-batches."""
        from distributed_kfac_pytorch_tpu import fp16

        model = cifar_resnet.get_model('resnet20')
        kfac = KFAC(model, factor_update_freq=1, inv_update_freq=1,
                    damping=0.01, lr=0.05)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 16, 3))
        y = jax.random.randint(jax.random.PRNGKey(2), (8,), 0, 10)
        variables, _ = kfac.init(jax.random.PRNGKey(0), x)
        params = variables['params']
        extra = {'batch_stats': variables['batch_stats'],
                 'loss_scale': fp16.init_loss_scale(2.0 ** 10)}
        mesh = D.make_kfac_mesh(jax.devices()[:4])
        dkfac = D.DistributedKFAC(kfac, mesh, params)
        kstate = dkfac.init_state(params)
        tx = optax.sgd(0.05)
        opt_state = tx.init(params)

        def loss(out, batch):
            return optax.softmax_cross_entropy_with_integer_labels(
                out, batch[1]).mean()

        step = dkfac.build_train_step(loss, tx,
                                      mutable_cols=('batch_stats',),
                                      donate=False, grad_accum_steps=2,
                                      loss_scale='dynamic')
        hyper = {'lr': 0.05, 'damping': 0.01,
                 'factor_update_freq': 1, 'inv_update_freq': 1}
        p2, o2, k2, e2, m = step(params, opt_state, kstate, extra,
                                 (x, y), hyper,
                                 factor_update=True, inv_update=True)
        assert float(m['overflow']) == 0.0
        moved = jax.tree.leaves(jax.tree.map(
            lambda a, b: float(jnp.abs(a - b).max()), params, p2))
        assert max(moved) > 0
        # Overflow micro-batch poisons the summed grads -> whole step
        # skipped collectively, scale backs off.
        bad_x = x.at[0, 0, 0, 0].set(jnp.nan)
        p3, o3, k3, e3, m3 = step(params, opt_state, kstate, extra,
                                  (bad_x, y), hyper,
                                  factor_update=True, inv_update=True)
        assert float(m3['overflow']) == 1.0
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), params, p3)
        assert float(e3['loss_scale']['scale']) == 2.0 ** 9


class TestFP16NonCifarEntryPoints:
    """--fp16 wiring beyond the CIFAR CLI (round 4; VERDICT r3 ask #5):
    the reference exposes fp16/AMP in all four of its CNN entry points
    and its production ImageNet launch passes --fp16
    (launch_node_torch_imagenet.sh:73-87); here the ImageNet-model
    overflow-skip runs through the same dynamic-loss-scale builder the
    ImageNet CLI wires, and the LM CLI trains end to end under --fp16.
    """

    @pytest.mark.slow
    def test_imagenet_model_fp16_overflow_skip(self):
        from distributed_kfac_pytorch_tpu import fp16
        from distributed_kfac_pytorch_tpu.models import imagenet_resnet

        # fp16 compute dtype exactly as train_imagenet_resnet.py builds
        # it under --fp16 (32px input: the skip semantics don't depend
        # on spatial size). Batch 32 -> 8 rows per device: fp16
        # BatchNorm backward over a 2-row shard overflows regardless of
        # scale (1/sigma^2 terms), which is the scaler's job to survive
        # but makes a deterministic finite first step impossible.
        model = imagenet_resnet.get_model('resnet18', dtype=jnp.float16)
        kfac = KFAC(model, factor_update_freq=1, inv_update_freq=1,
                    damping=0.01, lr=0.05)
        x = jax.random.normal(jax.random.PRNGKey(1), (32, 32, 32, 3))
        y = jax.random.randint(jax.random.PRNGKey(2), (32,), 0, 1000)
        variables, _ = kfac.init(jax.random.PRNGKey(0), x)
        params = variables['params']
        extra = {'batch_stats': variables['batch_stats'],
                 'loss_scale': fp16.init_loss_scale(2.0 ** 10)}
        mesh = D.make_kfac_mesh(jax.devices()[:4])
        dkfac = D.DistributedKFAC(kfac, mesh, params)
        kstate = dkfac.init_state(params)
        tx = optax.sgd(0.05)
        opt_state = tx.init(params)

        def loss(out, batch):
            return optax.softmax_cross_entropy_with_integer_labels(
                out, batch[1]).mean()

        step = dkfac.build_train_step(loss, tx,
                                      mutable_cols=('batch_stats',),
                                      donate=False, loss_scale='dynamic')
        hyper = {'lr': 0.05, 'damping': 0.01,
                 'factor_update_freq': 1, 'inv_update_freq': 1}
        p2, o2, k2, e2, m = step(params, opt_state, kstate, extra,
                                 (x, y), hyper,
                                 factor_update=True, inv_update=True)
        assert float(m['overflow']) == 0.0
        moved = jax.tree.leaves(jax.tree.map(
            lambda a, b: float(jnp.abs(a - b).max()), params, p2))
        assert max(moved) > 0
        bad_x = x.at[0, 0, 0, 0].set(jnp.nan)
        p3, _, k3, e3, m3 = step(params, opt_state, kstate, extra,
                                 (bad_x, y), hyper,
                                 factor_update=True, inv_update=True)
        assert float(m3['overflow']) == 1.0
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), params, p3)
        assert float(e3['loss_scale']['scale']) == 2.0 ** 9
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)),
            kstate['factors'], k3['factors'])

    @pytest.mark.slow
    def test_lm_cli_fp16_trains(self, tmp_path, capsys):
        """train_language_model.py --fp16: the full CLI path (dynamic
        loss scale seeded in extra_vars, fp16 transformer compute)
        trains one tiny epoch to a finite perplexity."""
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            'train_language_model',
            os.path.join(os.path.dirname(__file__), '..', 'examples',
                         'train_language_model.py'))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        # Tiny on-disk corpus: the synthetic fallback is 200k tokens
        # (~1.5k steps/epoch), far too slow for the CPU test tier.
        rng = np.random.default_rng(0)
        data = tmp_path / 'data'
        data.mkdir()
        for split, n in (('train', 3000), ('valid', 600)):
            toks = rng.integers(0, 50, size=n).astype(str)
            (data / f'{split}.txt').write_text(' '.join(toks))
        mod.main(['--arch', 'transformer', '--emsize', '32',
                  '--nhid', '32', '--nlayers', '1', '--nheads', '2',
                  '--bptt', '8', '--batch-size', '16', '--epochs', '1',
                  '--dropout', '0.0', '--fp16', '--no-resume',
                  '--kfac-update-freq', '2',
                  '--data-dir', str(data),
                  '--checkpoint-dir', str(tmp_path / 'ckpt'),
                  '--log-dir', str(tmp_path / 'logs')])
        out = capsys.readouterr().out
        assert 'val ppl' in out
        ppl = float(out.split('val ppl')[-1].strip().split()[0])
        assert np.isfinite(ppl) and ppl > 0
