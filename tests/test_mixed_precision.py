"""Mixed-precision policies: bf16 factors, fp32 decompositions, fp16
loss scaling.

Pins the reference's dtype policy (README.md:150-160, SURVEY.md §2.2):
factors may be stored in the low-precision compute dtype, inverses are
always *computed* in fp32, and loss-scaled backward passes unscale the
captured output-grads before factor statistics (BASELINE config 5 is
bf16 factors + fp32 eigendecomp).
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_kfac_pytorch_tpu import KFAC
from distributed_kfac_pytorch_tpu import layers as L
from distributed_kfac_pytorch_tpu.capture import EMBEDDING
from distributed_kfac_pytorch_tpu.preconditioner import _get


class MLP(nn.Module):
    @nn.compact
    def __call__(self, x):
        return nn.Dense(4)(nn.relu(nn.Dense(12)(x)))


class StraddleEmbedNet(nn.Module):
    """Embedding + four Denses hitting every precondition dispatch
    branch under ``auto_eigen_max_dim=16``: both-eigen, A-eigen/G-inv,
    both-inv, A-inv/G-eigen, plus the diagonal-A embedding path."""

    @nn.compact
    def __call__(self, ids):
        x = nn.Embed(24, 8, name='emb')(ids).mean(axis=1)
        x = nn.relu(nn.Dense(8, name='l_ee')(x))
        x = nn.relu(nn.Dense(24, name='l_ei')(x))
        x = nn.relu(nn.Dense(24, name='l_ii')(x))
        return nn.Dense(6, name='l_ie')(x)


def _embed_batch():
    ids = jax.random.randint(jax.random.PRNGKey(1), (32, 5), 0, 24)
    y = jax.random.randint(jax.random.PRNGKey(2), (32,), 0, 6)
    return ids, y


def _stepped(precond_compute_dtype, kl_clip=None, inv_dtype=jnp.float32,
             inverse_method=None):
    """One full factor+inverse+precondition step on StraddleEmbedNet."""
    ids, y = _embed_batch()
    kfac = KFAC(StraddleEmbedNet(), factor_update_freq=1,
                inv_update_freq=1, damping=0.01, lr=0.1,
                auto_eigen_max_dim=16, kl_clip=kl_clip,
                eigh_method='xla', inv_dtype=inv_dtype,
                inverse_method=inverse_method,
                precond_compute_dtype=precond_compute_dtype)
    variables, state = kfac.init(jax.random.PRNGKey(0), ids)
    params = variables['params']
    _, _, grads, captures, _ = kfac.capture.loss_and_grads(
        lambda out: optax.softmax_cross_entropy_with_integer_labels(
            out, y).mean(), params, ids)
    precond, new_state = jax.jit(
        lambda s, g, c: kfac.step(s, g, c, factor_update=True,
                                  inv_update=True))(state, grads, captures)
    return kfac, grads, precond, new_state


def _data():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(16, 8), jnp.float32)
    y = jnp.asarray(rng.randint(0, 4, 16))
    return x, y


def test_bf16_factor_storage_fp32_decomposition():
    x, y = _data()
    model = MLP()
    kfac = KFAC(model, factor_update_freq=1, inv_update_freq=1,
                damping=0.01, factor_dtype=jnp.bfloat16)
    variables, state = kfac.init(jax.random.PRNGKey(0), x)
    for f in jax.tree.leaves(state['factors']):
        assert f.dtype == jnp.bfloat16

    def loss_fn(out):
        return optax.softmax_cross_entropy_with_integer_labels(
            out, y).mean()

    _, _, grads, captures, _ = kfac.capture.loss_and_grads(
        loss_fn, variables['params'], x)
    precond, state = kfac.step(state, grads, captures)
    for f in jax.tree.leaves(state['factors']):
        assert f.dtype == jnp.bfloat16          # stored/communicated bf16
    for f in jax.tree.leaves(state['inverses']):
        assert f.dtype == jnp.float32           # computed + stored fp32
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree.leaves(precond))


def test_loss_scale_is_identity_in_fp32():
    x, y = _data()
    model = MLP()
    kfac = KFAC(model, factor_update_freq=1, inv_update_freq=1,
                damping=0.01)
    variables, state = kfac.init(jax.random.PRNGKey(0), x)
    params = variables['params']

    def loss_fn(out):
        return optax.softmax_cross_entropy_with_integer_labels(
            out, y).mean()

    loss_a, _, grads_a, caps_a, _ = kfac.capture.loss_and_grads(
        loss_fn, params, x)
    loss_b, _, grads_b, caps_b, _ = kfac.capture.loss_and_grads(
        loss_fn, params, x, loss_scale=2.0 ** 14)
    np.testing.assert_allclose(float(loss_a), float(loss_b), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(grads_a), jax.tree.leaves(grads_b)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)
    # Captured output-grads are unscaled too (factor stats unaffected).
    for name in caps_a:
        for ga, gb in zip(caps_a[name]['g'], caps_b[name]['g']):
            np.testing.assert_allclose(np.asarray(ga), np.asarray(gb),
                                       rtol=1e-5, atol=1e-7)


def test_repr_lists_hyperparams():
    kfac = KFAC(MLP(), damping=0.02, inverse_method='newton')
    text = repr(kfac)
    assert 'damping: 0.02' in text
    assert "inverse_method: 'newton'" in text
    assert 'registered_layers' in text


def test_bf16_factor_compute_close_to_fp32():
    """bf16 covariance-matmul inputs (fp32 accumulation) track the fp32
    factor statistics to bf16 input precision — the MXU fast path behind
    OptimConfig.bf16_factors (see PERF.md)."""
    x, y = _data()
    model = MLP()

    def factors_for(compute_dtype):
        kfac = KFAC(model, factor_update_freq=1, inv_update_freq=1,
                    damping=0.01, factor_compute_dtype=compute_dtype)
        variables, state = kfac.init(jax.random.PRNGKey(0), x)
        _, _, grads, captures, _ = kfac.capture.loss_and_grads(
            lambda out: optax.softmax_cross_entropy_with_integer_labels(
                out, y).mean(), variables['params'], x)
        _, new_state = kfac.step(state, grads, captures)
        return new_state['factors']

    f32 = factors_for(None)
    bf16 = factors_for(jnp.bfloat16)
    for a, b in zip(jax.tree.leaves(f32), jax.tree.leaves(bf16)):
        assert b.dtype == jnp.float32  # accumulation/storage stay fp32
        np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-2)
    # And bf16 inputs genuinely change the bits (the cast really ran).
    assert any(not np.allclose(a, b, rtol=1e-6, atol=1e-7)
               for a, b in zip(jax.tree.leaves(f32),
                               jax.tree.leaves(bf16)))


class TestFp16Robustness:
    """fp16 parity hardening (round-2 VERDICT #7): the jit-friendly
    analogues of the reference's hook-time inf/NaN capture drop
    (kfac/layers/base.py:397-407) and GradScaler dynamic scaling."""

    def test_sanitize_captures_zeroes_and_counts(self):
        from distributed_kfac_pytorch_tpu import fp16
        captures = {
            'L1': {'a': (jnp.ones((4, 3)),),
                   'g': (jnp.array([[1.0, jnp.inf], [0.0, 1.0]]),)},
            'L2': {'a': (jnp.full((2, 2), jnp.nan),),
                   'g': (jnp.ones((2, 2)),)},
        }
        clean, count = jax.jit(fp16.sanitize_captures)(captures)
        assert int(count) == 2
        np.testing.assert_array_equal(clean['L1']['g'][0],
                                      np.zeros((2, 2)))
        np.testing.assert_array_equal(clean['L2']['a'][0],
                                      np.zeros((2, 2)))
        # Finite tensors pass through untouched.
        np.testing.assert_array_equal(clean['L1']['a'][0], np.ones((4, 3)))
        np.testing.assert_array_equal(clean['L2']['g'][0], np.ones((2, 2)))

    def test_dynamic_loss_scale_schedule(self):
        from distributed_kfac_pytorch_tpu import fp16
        state = fp16.init_loss_scale(initial=2.0 ** 10)
        # Overflow halves and resets growth.
        state = fp16.update_loss_scale(state, False)
        assert float(state['scale']) == 2.0 ** 9
        assert int(state['growth_count']) == 0
        # growth_interval consecutive finite steps double the scale.
        for _ in range(3):
            state = fp16.update_loss_scale(state, True,
                                           growth_interval=3)
        assert float(state['scale']) == 2.0 ** 10
        assert int(state['growth_count']) == 0

    def test_apply_if_finite_skips_update(self):
        from distributed_kfac_pytorch_tpu import fp16
        old = {'w': jnp.zeros(3)}
        new = {'w': jnp.ones(3)}
        kept = fp16.apply_if_finite(False, new, old)
        np.testing.assert_array_equal(kept['w'], np.zeros(3))
        applied = fp16.apply_if_finite(True, new, old)
        np.testing.assert_array_equal(applied['w'], np.ones(3))

    def test_factor_update_unpoisoned_by_injected_inf(self):
        """End-to-end: an inf in one layer's output-grad capture leaves
        that factor at its EWMA-of-zero-contribution value instead of
        poisoning the whole state with NaNs."""
        from distributed_kfac_pytorch_tpu import fp16
        model = MLP()
        kfac = KFAC(model, factor_update_freq=1, inv_update_freq=1,
                    damping=0.01)
        x = jax.random.normal(jax.random.PRNGKey(0), (16, 6))
        variables, state = kfac.init(jax.random.PRNGKey(1), x)
        _, _, grads, captures, _ = kfac.capture.loss_and_grads(
            lambda out: jnp.mean(out ** 2), variables['params'], x)
        # Poison one capture as an overflowed fp16 backward would.
        name = sorted(captures)[0]
        g0 = captures[name]['g'][0]
        captures[name]['g'] = (g0.at[0, 0].set(jnp.inf),)
        clean, count = fp16.sanitize_captures(captures)
        assert int(count) == 1
        _, new_state = kfac.step(state, grads, clean)
        for leaf in jax.tree.leaves(new_state['factors']):
            assert np.isfinite(np.asarray(leaf)).all()


# ---------------------------------------------------------------------------
# precond_compute_dtype: the bf16 precondition pipeline (r6 tentpole)
# ---------------------------------------------------------------------------

def _oracle_mats(kfac, state, grads, damping):
    """fp64 dense-oracle preconditioned matrices per layer (the
    reference operators, from the post-step factors)."""
    want = {}
    for name, spec in kfac.specs.items():
        grad_mat = np.asarray(
            L.grads_to_matrix(spec, _get(grads, spec.path)), np.float64)
        a = np.asarray(state['factors'][name]['A'], np.float64)
        g = np.asarray(state['factors'][name]['G'], np.float64)
        g_inv = np.linalg.inv(g + damping * np.eye(g.shape[0]))
        if spec.kind == EMBEDDING:
            want[name] = (1.0 / (a + damping))[:, None] * (
                grad_mat @ g_inv)
            continue
        a_dim, g_dim = a.shape[0], g.shape[0]
        both_eigen = (kfac.method_for_dim(a_dim) == 'eigen'
                      and kfac.method_for_dim(g_dim) == 'eigen')
        if both_eigen:
            da_, qa = np.linalg.eigh(a)
            dg_, qg = np.linalg.eigh(g)
            v1 = qg.T @ grad_mat @ qa
            v2 = v1 / (dg_[:, None] * da_[None, :] + damping)
            want[name] = qg @ v2 @ qa.T
        else:
            a_inv = np.linalg.inv(a + damping * np.eye(a_dim))
            want[name] = g_inv @ grad_mat @ a_inv
    return want


class TestPrecondComputeDtype:
    """r6 tentpole: low-precision, bucketed precondition pipeline."""

    @pytest.mark.parametrize('method', ['auto', 'cholesky'])
    def test_dtype_ladder_vs_dense_oracle(self, method):
        """fp32-strict and bf16 preconditioned grads vs the fp64 dense
        oracle, across every dispatch branch (both-eigen, mixed x2,
        both-inverse via 'auto'; all-baked + diag/G_inv via 'cholesky';
        diag/eigen-G embedding via 'auto')."""
        damping = 0.01
        outs = {}
        for cdt in (None, jnp.float32, jnp.bfloat16):
            kfac, grads, precond, state = _stepped(
                cdt, inverse_method=method)
            outs[cdt] = precond
        tols = {None: 1e-4, jnp.float32: 1e-4, jnp.bfloat16: 5e-2}
        want = _oracle_mats(kfac, state, grads, damping)
        for cdt, precond in outs.items():
            for name, spec in kfac.specs.items():
                v = np.asarray(L.grads_to_matrix(
                    spec, _get(precond, spec.path)), np.float64)
                scale = np.abs(want[name]).max()
                np.testing.assert_allclose(
                    v, want[name], rtol=tols[cdt],
                    atol=tols[cdt] * scale,
                    err_msg=f'{name} @ {cdt}')
        # bf16 genuinely changed the operand bits (the cast really ran).
        leaves0 = jax.tree.leaves(outs[None])
        leaves16 = jax.tree.leaves(outs[jnp.bfloat16])
        assert any(not np.array_equal(a, b)
                   for a, b in zip(leaves0, leaves16))

    def test_bf16_resident_inverses_consumed_without_upcast(self):
        """inv_dtype=bf16 + precond_compute_dtype=bf16 (the
        bandwidth-lever config: stored inverses consumed resident)
        tracks the fp32-read path to bf16 tolerance."""
        base_kfac, grads, base, state = _stepped(
            None, inv_dtype=jnp.bfloat16)
        _, _, resident, _ = _stepped(jnp.bfloat16,
                                     inv_dtype=jnp.bfloat16)
        for a, b in zip(jax.tree.leaves(base), jax.tree.leaves(resident)):
            a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
            assert np.isfinite(b).all()
            scale = max(np.abs(a).max(), 1e-30)
            np.testing.assert_allclose(a, b, rtol=5e-2,
                                       atol=5e-2 * scale)

    def test_repr_lists_precond_dtype(self):
        kfac = KFAC(MLP(), precond_compute_dtype=jnp.bfloat16)
        assert 'precond_compute_dtype' in repr(kfac)
