"""Tracing utility, optax adapter, comm benchmark, and launch helpers."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_kfac_pytorch_tpu import KFAC, utils
from distributed_kfac_pytorch_tpu.observability import tracing
from distributed_kfac_pytorch_tpu.optim import kfac_transform
import flax.linen as nn


class TestTrace:
    def test_trace_records_and_clears(self):
        utils.clear_trace()

        @utils.trace(sync=True)
        def work(x):
            return x * 2

        for _ in range(3):
            work(jnp.ones(4))
        t = utils.get_trace()
        assert 'work' in t and t['work'] > 0
        total = utils.get_trace(average=False)['work']
        assert total >= t['work']
        # Reference bug fixed: clear_trace actually clears (utils.py:11-12)
        utils.clear_trace()
        assert utils.get_trace() == {}

    def test_trace_history_window(self):
        utils.clear_trace()

        @utils.trace(name='w')
        def work():
            return None

        for _ in range(5):
            work()
        assert len(tracing.spans('w')) == 5
        assert utils.get_trace(max_history=2)['w'] > 0
        utils.clear_trace()

    def test_tree_bytes(self):
        tree = {'a': jnp.zeros((4, 4), jnp.float32),
                'b': jnp.zeros((2,), jnp.bfloat16)}
        assert utils.tree_bytes(tree) == 4 * 4 * 4 + 2 * 2


class MLP(nn.Module):
    @nn.compact
    def __call__(self, x):
        x = nn.Dense(8, name='fc1')(x)
        x = nn.relu(x)
        return nn.Dense(4, name='fc2')(x)


class TestOptaxAdapter:
    def test_chained_with_sgd_matches_manual(self):
        model = MLP()
        kfac = KFAC(model, factor_update_freq=1, inv_update_freq=1,
                    damping=0.01, lr=0.1)
        x = jax.random.normal(jax.random.PRNGKey(0), (16, 6))
        y = jax.random.randint(jax.random.PRNGKey(1), (16,), 0, 4)
        variables, kstate0 = kfac.init(jax.random.PRNGKey(2), x)
        params = variables['params']

        def loss_fn(out):
            return optax.softmax_cross_entropy_with_integer_labels(
                out, y).mean()

        loss, _, grads, captures, _ = kfac.capture.loss_and_grads(
            loss_fn, params, x)

        # Manual: KFAC.step then SGD scale.
        precond, _ = kfac.step(kstate0, grads, captures, lr=0.1)
        manual = jax.tree.map(lambda p, g: p - 0.1 * g, params, precond)

        # optax chain path.
        tx = optax.chain(kfac_transform(kfac), optax.sgd(0.1))
        state = tx.init(params)
        updates, state = tx.update(grads, state, params,
                                   captures=captures, lr=0.1)
        chained = optax.apply_updates(params, updates)

        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5,
                                                    atol=1e-7),
            manual, chained)

    def test_state_advances(self):
        model = MLP()
        kfac = KFAC(model, factor_update_freq=1, inv_update_freq=1)
        x = jnp.ones((4, 6))
        variables, _ = kfac.init(jax.random.PRNGKey(0), x)
        params = variables['params']
        tx = kfac_transform(kfac)
        state = tx.init(params)
        _, _, grads, captures, _ = kfac.capture.loss_and_grads(
            lambda out: out.sum(), params, x)
        _, state = tx.update(grads, state, params, captures=captures)
        assert int(state.kfac_state['step']) == 1


class TestCommBenchmark:
    def test_runs_on_virtual_mesh(self, capsys):
        from benchmarks import communication
        communication.main(['--size', '16', '--iters', '2'])
        out = capsys.readouterr().out
        assert 'allreduce_world[gw=8]' in out
        assert 'gather_inv_group[gw=2]' in out
        assert 'bcast_grad_group[gw=1]' in out


class TestLaunch:
    def test_single_host_initialize(self):
        from distributed_kfac_pytorch_tpu import launch
        info = launch.initialize_multihost()
        assert info['process_count'] == 1
        assert info['global_devices'] == 8

    def test_process_local_slice(self):
        from distributed_kfac_pytorch_tpu import launch
        sl = launch.process_local_slice(64)
        assert sl == slice(0, 64)

    def test_host_local_batch_to_global(self):
        from distributed_kfac_pytorch_tpu import launch
        from distributed_kfac_pytorch_tpu.parallel import distributed as D
        from jax.sharding import PartitionSpec as P
        mesh = D.make_kfac_mesh()
        batch = {'x': np.ones((16, 3), np.float32)}
        out = launch.host_local_batch_to_global(
            mesh, batch, P(D.KFAC_AXES))
        assert out['x'].shape == (16, 3)
        assert len(out['x'].sharding.device_set) == 8


def test_enable_compilation_cache(tmp_path, monkeypatch):
    """The cache directory comes from outside the program
    (JAX_COMPILATION_CACHE_DIR) or is the fixed <checkout>/.jax_cache;
    KFAC_COMPILE_CACHE is an off switch, not a second path knob."""
    import os

    import jax

    from distributed_kfac_pytorch_tpu import utils as U

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    prev_dir = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
    monkeypatch.delenv('KFAC_COMPILE_CACHE', raising=False)
    try:
        # This test process IS a multi-device CPU configuration (the
        # conftest mesh), i.e. the warm-read segfault surface: no
        # default directory, nothing set.
        assert U._multi_device_cpu_configured()
        assert U.enable_compilation_cache() is None
        assert jax.config.jax_compilation_cache_dir is None
        # JAX's own variable set: returned untouched, nothing set in
        # code — JAX reads the variable itself — even here.
        monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', '/shared/warm')
        assert U.enable_compilation_cache() == '/shared/warm'
        assert os.environ['JAX_COMPILATION_CACHE_DIR'] == '/shared/warm'
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR')
        # What an accelerator entry point sees (guard stubbed out):
        # the fixed path under the checkout.
        monkeypatch.setattr(U, '_multi_device_cpu_configured',
                            lambda: False)
        fixed = os.path.join(repo, '.jax_cache')
        assert U.enable_compilation_cache() == fixed
        assert os.path.isdir(fixed)
        assert jax.config.jax_compilation_cache_dir == fixed
        jax.config.update('jax_compilation_cache_dir', None)
        # A path in KFAC_COMPILE_CACHE is no longer honoured ...
        monkeypatch.setenv('KFAC_COMPILE_CACHE', str(tmp_path / 'mine'))
        assert U.enable_compilation_cache() == fixed
        assert not (tmp_path / 'mine').exists()
        jax.config.update('jax_compilation_cache_dir', None)
        # ... and its off spellings switch the helper off.
        for off in ('0', 'false', 'OFF', 'no'):
            monkeypatch.setenv('KFAC_COMPILE_CACHE', off)
            assert U.enable_compilation_cache() is None
            assert jax.config.jax_compilation_cache_dir is None
    finally:
        jax.config.update('jax_compilation_cache_dir', prev_dir)
