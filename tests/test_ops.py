"""Numerics tests for factor statistics and linear algebra ops.

Goes beyond the reference (which had no numerics unit tests — SURVEY.md §4):
covariance/eigh/inverse identities are checked against numpy oracles, and
the conv im2col path is checked against a brute-force patch extraction.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_kfac_pytorch_tpu.observability import tracing
from distributed_kfac_pytorch_tpu.ops import factors, linalg, pallas_kernels


def rand(*shape, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


class TestCov:
    def test_matches_definition(self):
        a = rand(32, 5)
        got = factors.get_cov(a)
        want = np.asarray(a).T @ np.asarray(a) / 32
        np.testing.assert_allclose(got, want, rtol=1e-5)
        np.testing.assert_allclose(got, got.T, rtol=0, atol=0)  # exact sym

    def test_two_tensor_form(self):
        a, b = rand(16, 4, seed=1), rand(16, 4, seed=2)
        got = factors.get_cov(a, b)
        want = np.asarray(a).T @ np.asarray(b) / 16
        np.testing.assert_allclose(got, want, rtol=1e-5)

    def test_scale_override(self):
        a = rand(8, 3)
        np.testing.assert_allclose(
            factors.get_cov(a, scale=2.0),
            np.asarray(a).T @ np.asarray(a) / 2.0, rtol=1e-5)

    def test_rejects_non_2d(self):
        with pytest.raises(ValueError):
            factors.get_cov(rand(2, 3, 4))


def _count_primitives(jaxpr, found=None):
    """``{primitive name: count}`` over ``jaxpr`` and its sub-jaxprs."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        found[eqn.primitive.name] = found.get(eqn.primitive.name, 0) + 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _count_primitives(sub, found)
    return found


def _single_contraction(a, scale=None, compute_dtype=None):
    """``get_cov``'s self-covariance as it was before the blocked
    route: one contraction, symmetrized (the oracle of the same
    arithmetic, whatever the shape)."""
    precision = None
    if compute_dtype is not None:
        a = a.astype(compute_dtype)
        if jnp.dtype(compute_dtype) == jnp.float32:
            precision = jax.lax.Precision.HIGHEST
    cov = jnp.matmul(a.T, a, preferred_element_type=jnp.float32,
                     precision=precision)
    return (cov + cov.T) * (0.5 / (a.shape[0] if scale is None else scale))


class TestBlockedCov:
    """``get_cov``'s self-covariance contracts only the upper block
    triangle where the static ``(rows, d)`` makes the contraction
    compute-bound (``factors.cov_block_side``), and is the program it
    was everywhere else."""

    # (rows, d, column blocks a side; None below the gate)
    SHAPES = [(3072, 3072, 3), (3100, 3072, 3), (4096, 4096, 4),
              (3584, 3584, None), (4608, 4608, 4),
              (1024, 768, None), (3071, 3072, None), (64, 4096, None),
              (4096, 3071, None), (2048, 2048, None), (3400, 3328, None)]
    IDS = [f'{r}x{d}-{"blocked" if k else "full"}' for r, d, k in SHAPES]

    @pytest.mark.parametrize('rows,d,k', SHAPES, ids=IDS)
    def test_the_gate_is_a_function_of_the_shape(self, rows, d, k):
        assert factors.cov_block_side(rows, d) == k
        if k is not None:
            assert k in range(factors.COV_BLOCK_MIN_SIDE,
                              factors.COV_BLOCK_MAX_SIDE + 1)
            assert d % (k * factors.COV_BLOCK_ALIGN) == 0
            assert d // k >= factors.COV_BLOCK_MIN_WIDTH
            # ... and no larger k within the limit would do.
            assert all(d % (m * factors.COV_BLOCK_ALIGN) or
                       d // m < factors.COV_BLOCK_MIN_WIDTH
                       for m in range(k + 1,
                                      factors.COV_BLOCK_MAX_SIDE + 1))

    @pytest.mark.parametrize('d,k', [
        (3072, 3), (6144, 4), (2048, None), (1536, None), (768, None),
        (769, None)])
    def test_the_cells_dims_at_8192_rows(self, d, k):
        assert factors.cov_block_side(8192, d) == k

    @pytest.mark.parametrize('compute_dtype', [
        None, jnp.float32, jnp.bfloat16],
        ids=['default', 'float32', 'bfloat16'])
    @pytest.mark.parametrize('rows,d,k', SHAPES[:2] + SHAPES[5:6],
                             ids=IDS[:2] + IDS[5:6])
    def test_matches_the_single_contraction(self, rows, d, k,
                                            compute_dtype):
        a = rand(rows, d, seed=d)
        got = factors.get_cov(a, compute_dtype=compute_dtype)
        want = _single_contraction(a, compute_dtype=compute_dtype)
        assert got.dtype == jnp.float32 and got.shape == (d, d)
        # Same operands (rounded once to compute_dtype, so bf16's own
        # rounding is on both sides), float32 accumulation: only the
        # summation order differs: 1e-6 of float32 sums over <= 3100
        # rows of O(1) products, relative on the O(1) diagonal.
        np.testing.assert_allclose(got, want, rtol=2e-6, atol=1e-6)
        np.testing.assert_array_equal(got, got.T)
        if compute_dtype == jnp.bfloat16:
            exact = np.asarray(a, np.float64)
            exact = exact.T @ exact / rows
            # ... and the bf16 result is within the operands' rounding
            # (2^-8 relative each) of the float64 one.
            np.testing.assert_allclose(got, exact, rtol=0, atol=2e-2)

    @pytest.mark.parametrize('rows,d,k', SHAPES, ids=IDS)
    def test_the_jaxpr_holds_the_upper_triangle_only(self, rows, d, k):
        a = jax.ShapeDtypeStruct((rows, d), jnp.bfloat16)
        tracing.clear_trace()
        jaxpr = jax.make_jaxpr(
            lambda x: factors.get_cov(x, scale=7.0))(a).jaxpr
        counters = tracing.counters()
        found = _count_primitives(jaxpr)
        if k is None:
            want = jax.make_jaxpr(
                lambda x: _single_contraction(x, scale=7.0))(a).jaxpr
            assert str(jaxpr) == str(want)
            assert found['dot_general'] == 1
            assert counters == {'kfac/factors/cov_full': 1}
            return
        # One loop over the k(k+1)/2 block pairs with one block-sized
        # contraction in its body, and nothing else that contracts.
        loops = [e for e in jaxpr.eqns if e.primitive.name == 'scan']
        assert len(loops) == 1 and found['dot_general'] == 1
        assert loops[0].params['length'] == k * (k + 1) // 2
        body = loops[0].params['jaxpr'].jaxpr
        dots = [e for e in body.eqns if e.primitive.name == 'dot_general']
        assert [e.outvars[0].aval.shape for e in dots] == [(d // k, d // k)]
        assert {v.aval.dtype for v in dots[0].invars} == {
            jnp.dtype(jnp.bfloat16)}
        # scale meets block-sized float32 values only: nothing
        # elementwise ever has the operand's (rows, .) shape.
        for eqn in body.eqns:
            if eqn.primitive.name in ('mul', 'div', 'add'):
                aval = eqn.outvars[0].aval
                assert aval.shape in ((), (d // k, d // k)), eqn
        assert counters == {'kfac/factors/cov_blocked': 1}

    def test_strict_float32_keeps_highest_in_every_block(self):
        a = jax.ShapeDtypeStruct((3072, 3072), jnp.bfloat16)
        jaxpr = jax.make_jaxpr(lambda x: factors.get_cov(
            x, compute_dtype=jnp.float32))(a).jaxpr
        (loop,) = [e for e in jaxpr.eqns if e.primitive.name == 'scan']
        (dot,) = [e for e in loop.params['jaxpr'].jaxpr.eqns
                  if e.primitive.name == 'dot_general']
        assert dot.params['preferred_element_type'] == jnp.float32
        assert dot.invars[0].aval.dtype == jnp.float32
        assert set(dot.params['precision']) == {jax.lax.Precision.HIGHEST}

    def test_the_two_tensor_form_is_not_blocked(self):
        a = jax.ShapeDtypeStruct((3072, 3072), jnp.float32)
        tracing.clear_trace()
        found = _count_primitives(
            jax.make_jaxpr(lambda x, y: factors.get_cov(x, y))(a, a).jaxpr)
        assert found['dot_general'] == 1 and 'scan' not in found
        assert not tracing.counters()

    @pytest.mark.parametrize('has_bias', [True, False],
                             ids=['bias', 'no-bias'])
    def test_linear_factors_through_the_blocked_route(self, has_bias):
        rows, d = 3080, 3072
        a = rand(4, rows // 4, d, seed=11)
        tracing.clear_trace()
        got_a = factors.linear_a_factor(a, has_bias,
                                        compute_dtype=jnp.float32)
        got_g = factors.linear_g_factor(a, compute_dtype=jnp.float32)
        assert tracing.counters() == {'kfac/factors/cov_blocked': 2}
        flat = np.asarray(a, np.float64).reshape(rows, d)
        want_g = flat.T @ flat / rows
        if has_bias:
            flat = np.concatenate([flat, np.ones((rows, 1))], axis=1)
        want_a = flat.T @ flat / rows
        assert got_a.shape == (d + has_bias,) * 2
        np.testing.assert_allclose(got_a, want_a, rtol=0, atol=1e-5)
        np.testing.assert_allclose(got_g, want_g, rtol=0, atol=1e-5)
        np.testing.assert_array_equal(got_a, got_a.T)
        np.testing.assert_array_equal(got_g, got_g.T)


class TestRunningAvg:
    def test_ewma(self):
        new, cur = rand(4, 4, seed=3), rand(4, 4, seed=4)
        got = factors.update_running_avg(new, cur, alpha=0.95)
        np.testing.assert_allclose(
            got, 0.95 * np.asarray(cur) + 0.05 * np.asarray(new), rtol=1e-6)


class TestLinearFactors:
    def test_a_with_bias(self):
        a = rand(10, 6)
        got = factors.linear_a_factor(a, has_bias=True)
        aug = np.concatenate([np.asarray(a), np.ones((10, 1))], axis=1)
        np.testing.assert_allclose(got, aug.T @ aug / 10, rtol=1e-5)
        assert got.shape == (7, 7)

    def test_a_collapses_time_dim(self):
        a = rand(4, 5, 6)  # (batch, time, dim)
        got = factors.linear_a_factor(a, has_bias=False)
        flat = np.asarray(a).reshape(20, 6)
        np.testing.assert_allclose(got, flat.T @ flat / 20, rtol=1e-5,
                                   atol=1e-6)

    def test_g(self):
        g = rand(10, 3)
        np.testing.assert_allclose(
            factors.linear_g_factor(g),
            np.asarray(g).T @ np.asarray(g) / 10, rtol=1e-5)


def _patches_bruteforce(x, kh, kw, sh, sw, pad):
    """Reference im2col in numpy, feature order (kh, kw, c)."""
    x = np.pad(np.asarray(x), ((0, 0), (pad[0], pad[0]), (pad[1], pad[1]),
                               (0, 0)))
    b, h, w, c = x.shape
    oh = (h - kh) // sh + 1
    ow = (w - kw) // sw + 1
    out = np.zeros((b, oh, ow, kh * kw * c), np.float32)
    for i in range(oh):
        for j in range(ow):
            patch = x[:, i * sh:i * sh + kh, j * sw:j * sw + kw, :]
            out[:, i, j, :] = patch.reshape(b, -1)
    return out


class TestConvFactors:
    @pytest.mark.parametrize('pad_mode,pad', [('VALID', (0, 0)),
                                              ('SAME', (1, 1))])
    def test_patches_match_bruteforce(self, pad_mode, pad):
        x = rand(2, 5, 5, 3, seed=5)
        got = factors.extract_conv2d_patches(x, (3, 3), (1, 1), pad_mode)
        want = _patches_bruteforce(x, 3, 3, 1, 1, pad)
        np.testing.assert_allclose(got, want, rtol=1e-5)

    def test_patch_order_matches_flax_kernel_flatten(self):
        # conv(x) == patches @ kernel.reshape(-1, cout): the basis contract
        # that makes A consistent with the flattened gradient.
        x = rand(2, 6, 6, 3, seed=6)
        k = rand(3, 3, 3, 4, seed=7)  # HWIO
        y = jax.lax.conv_general_dilated(
            x, k, (1, 1), 'SAME', dimension_numbers=('NHWC', 'HWIO', 'NHWC'))
        patches = factors.extract_conv2d_patches(x, (3, 3), (1, 1), 'SAME')
        y2 = patches @ np.asarray(k).reshape(-1, 4)
        np.testing.assert_allclose(y, y2, rtol=1e-4, atol=1e-5)

    def test_a_factor_scaling(self):
        x = rand(2, 4, 4, 3, seed=8)
        got = factors.conv2d_a_factor(x, (3, 3), (1, 1), 'SAME',
                                      has_bias=True)
        p = _patches_bruteforce(x, 3, 3, 1, 1, (1, 1)).reshape(-1, 27)
        p = np.concatenate([p, np.ones((p.shape[0], 1), np.float32)], 1)
        s = 16  # 4*4 spatial
        want = (p / s).T @ (p / s) / p.shape[0]
        want = (want + want.T) / 2
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)

    def test_g_factor_scaling(self):
        g = rand(2, 4, 4, 5, seed=9)
        got = factors.conv2d_g_factor(g)
        g2 = np.asarray(g).reshape(-1, 5) / 16
        want = g2.T @ g2 / g2.shape[0]
        want = (want + want.T) / 2
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


class TestEmbeddingFactor:
    def test_frequency_diagonal(self):
        ids = jnp.array([[0, 1, 1], [3, 1, 0]])
        got = factors.embedding_a_factor(ids, vocab_size=5)
        np.testing.assert_allclose(got, [2 / 6, 3 / 6, 0, 1 / 6, 0],
                                   rtol=1e-6)


class TestTriu:
    def test_roundtrip(self):
        x = rand(6, 6, seed=10)
        x = (x + x.T) / 2
        flat = factors.get_triu(x)
        assert flat.shape == (21,)
        back = factors.fill_triu((6, 6), flat)
        np.testing.assert_allclose(back, x, rtol=1e-6)

    def test_rectangular_roundtrip(self):
        # rows < cols is supported (reference fill_triu handles it);
        # the lower triangle of the square block is mirrored.
        x = np.zeros((2, 4), np.float32)
        x[np.triu_indices(2, m=4)] = np.arange(1, 8)
        x[1, 0] = x[0, 1]  # symmetric square block
        flat = factors.get_triu(jnp.asarray(x))
        back = factors.fill_triu((2, 4), flat)
        np.testing.assert_allclose(back, x, rtol=1e-6)

    def test_more_rows_than_cols_rejected(self):
        with pytest.raises(ValueError):
            factors.get_triu(jnp.zeros((4, 2)))
        with pytest.raises(ValueError):
            factors.fill_triu((4, 2), jnp.zeros(5))


def spd(n, seed=0):
    m = np.asarray(rand(n, n, seed=seed))
    return jnp.asarray(m @ m.T + n * np.eye(n, dtype=np.float32))


class TestLinalg:
    def test_eigh_reconstructs(self):
        x = spd(8, seed=11)
        q, d = linalg.get_eigendecomp(x)
        np.testing.assert_allclose(np.asarray(q) * d @ np.asarray(q).T, x,
                                   rtol=1e-3, atol=1e-3)

    def test_eigh_clip(self):
        x = jnp.diag(jnp.array([-1.0, 2.0]))
        _, d = linalg.get_eigendecomp(x, clip=0.0)
        assert float(d.min()) >= 0.0

    def test_damped_cholesky_inverse(self):
        x = spd(10, seed=12)
        inv = linalg.get_inverse(x, damping=0.5)
        want = np.linalg.inv(np.asarray(x) + 0.5 * np.eye(10))
        np.testing.assert_allclose(inv, want, rtol=1e-3, atol=1e-4)

    def test_elementwise_inverse_keeps_zeros(self):
        v = jnp.array([2.0, 0.0, 4.0])
        np.testing.assert_allclose(linalg.get_elementwise_inverse(v),
                                   [0.5, 0.0, 0.25])

    def test_precondition_eigen_equals_damped_natural_grad(self):
        # With running-average factors A, G the eigen path must equal
        # (G + sqrt(λ))^-1 grad (A + sqrt(λ))^-1 when λ is split evenly —
        # here checked in the exact form used by the reference: eigenbasis
        # division by (dG dA^T + λ).
        a, g = spd(5, seed=13), spd(4, seed=14)
        grad = rand(4, 5, seed=15)
        qa, da = linalg.get_eigendecomp(a)
        qg, dg = linalg.get_eigendecomp(g)
        lam = 0.1
        got = linalg.precondition_eigen(grad, qa, qg, da, dg, lam)
        # Oracle: full Kronecker solve (G⊗A + λI)^-1 vec(grad)
        kron = np.kron(np.asarray(g), np.asarray(a))
        vec = np.asarray(grad).reshape(-1)  # row-major: (out, in)
        want = np.linalg.solve(kron + lam * np.eye(20), vec).reshape(4, 5)
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4)

    def test_precondition_inv(self):
        a, g = spd(3, seed=16), spd(3, seed=17)
        grad = rand(3, 3, seed=18)
        a_inv = linalg.get_inverse(a, damping=0.2)
        g_inv = linalg.get_inverse(g, damping=0.2)
        got = linalg.precondition_inv(grad, a_inv, g_inv)
        want = (np.linalg.inv(np.asarray(g) + 0.2 * np.eye(3))
                @ np.asarray(grad)
                @ np.linalg.inv(np.asarray(a) + 0.2 * np.eye(3)))
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4)

    def test_batched_via_vmap(self):
        xs = jnp.stack([spd(6, seed=s) for s in range(4)])
        qs, ds = jax.vmap(linalg.get_eigendecomp)(xs)
        for i in range(4):
            np.testing.assert_allclose(
                np.asarray(qs[i]) * ds[i] @ np.asarray(qs[i]).T, xs[i],
                rtol=1e-3, atol=1e-3)


def _solver_stack_sizes(jaxpr, n, found):
    """Matrices per call of every factorization, triangular solve and
    matmul on (..., n, n) values anywhere in ``jaxpr``, by primitive."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ('cholesky', 'triangular_solve',
                                  'dot_general'):
            shape = eqn.outvars[0].aval.shape
            assert shape[-2:] == (n, n), (eqn.primitive.name, shape)
            found.setdefault(eqn.primitive.name, []).append(
                int(np.prod(shape[:-2], dtype=np.int64)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _solver_stack_sizes(sub, n, found)
    return found


class TestDampedInverseStack:
    """``linalg.damped_inverse_stack``: the dispatch every firing runs.
    A stack over ``INVERSE_SUBSTACK_BYTES`` must give what the whole
    stack gives, from a loop whose solver never holds more than the
    budget's matrices (the 16 GB fix of PR 21 rests on that)."""

    N, PER_CHUNK = 8, 3

    @pytest.mark.parametrize('out_dtype', [None, jnp.bfloat16],
                             ids=['f32', 'bf16'])
    @pytest.mark.parametrize('method,count', [
        ('cholesky', 2), ('cholesky', 7), ('cholesky', 6),
        ('newton', 2), ('newton', 7)],
        ids=['cholesky-under', 'cholesky-over-remainder',
             'cholesky-over-multiple', 'newton-under',
             'newton-over-remainder'])
    def test_matches_the_whole_stack(self, monkeypatch, method, count,
                                     out_dtype):
        n, per_chunk = self.N, self.PER_CHUNK
        monkeypatch.setattr(linalg, 'INVERSE_SUBSTACK_BYTES',
                            per_chunk * n * n * 4)
        stack = jnp.stack([spd(n, seed=40 + i) for i in range(count)])
        damping = 0.3

        def run(x):
            return linalg.damped_inverse_stack(
                x, damping, method, out_dtype=out_dtype)

        got = run(stack)
        want = jax.vmap(
            lambda m: linalg.get_inverse(m, damping=damping))(stack)
        assert got.shape == (count, n, n)
        assert got.dtype == (out_dtype or jnp.float32)
        # Cholesky sub-stacks run the same per-matrix arithmetic;
        # Newton-Schulz stops at a 1e-5 residual; bf16 keeps 8 bits.
        rtol = (1e-2 if out_dtype is not None
                else 1e-5 if method == 'cholesky' else 1e-3)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want), rtol=rtol,
            atol=rtol * float(jnp.abs(want).max()))

        jaxpr = jax.make_jaxpr(run)(stack).jaxpr
        loops = [e for e in jaxpr.eqns if e.primitive.name == 'scan']
        sizes = _solver_stack_sizes(jaxpr, n, {})
        assert sizes, 'no solver primitive found in the jaxpr'
        if count <= per_chunk:
            assert not loops
            assert {max(v) for v in sizes.values()} == {count}
        else:
            # lax.map: equal sub-stacks, every one under the one scan
            # (7 of budget 3 run as 3 x 3 with two identities of
            # padding), so no solver stands outside the loop and
            # nowhere holds more than the budget.
            chunks = -(-count // per_chunk)
            size = -(-count // chunks)
            assert len(loops) == 1
            assert loops[0].params['length'] == chunks >= 2
            in_loop = _solver_stack_sizes(
                loops[0].params['jaxpr'].jaxpr, n, {})
            assert {max(v) for v in in_loop.values()} == {size}
            assert size <= per_chunk
            assert sizes == in_loop


def _whole_route(x, damping):
    """``get_inverse`` as it was before the halved route (PR 32): the
    oracle of the leaf's arithmetic and of the jaxpr a dim under the
    gate must still trace."""
    x = x.astype(jnp.float32)
    if damping is not None:
        x = x + damping * jnp.eye(x.shape[-1], dtype=x.dtype)
    chol = jnp.linalg.cholesky(x)
    eye = jnp.eye(x.shape[-1], dtype=x.dtype)
    inv_l = jax.scipy.linalg.solve_triangular(chol, eye, lower=True)
    return inv_l.T @ inv_l


def _graded(n, seed, low=-4.0):
    """A float64 SPD matrix with eigenvalues graded from 1 down to
    ``10**low`` in a random basis: damping 0.003 then sets the
    condition, as it does for a K-FAC factor."""
    rng = np.random.default_rng(seed)
    q, _ = np.linalg.qr(rng.standard_normal((n, n)))
    return (q * np.logspace(0.0, low, n)) @ q.T


def _fro_gap(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


class TestHalvedInverse:
    """``linalg.get_inverse`` above ``INVERSE_HALVE_MIN_DIM``: the
    Cholesky factor's inverse by recursive halving. The toy cases lower
    the gate, the leaf and the split's alignment together (gate 32,
    leaf 16, splits at multiples of 8), so 49 = 6 * 8 + 1 splits as
    3073 = 24 * 128 + 1 does: 24 + 25, and the 25 again as 8 + 17."""

    DAMPING = 0.003

    @pytest.fixture
    def lowered(self, monkeypatch):
        monkeypatch.setattr(linalg, 'INVERSE_HALVE_MIN_DIM', 32)
        monkeypatch.setattr(linalg, 'INVERSE_HALVE_LEAF', 16)
        monkeypatch.setattr(linalg, 'INVERSE_HALVE_ALIGN', 8)

    def test_the_constants_are_consistent(self):
        assert linalg.INVERSE_HALVE_ALIGN == 128
        assert linalg.INVERSE_HALVE_LEAF % linalg.INVERSE_HALVE_ALIGN == 0
        # Every dim the gate lets through is split at least once, at a
        # lane-aligned point that leaves two non-empty halves.
        assert (linalg.INVERSE_HALVE_MIN_DIM > linalg.INVERSE_HALVE_LEAF
                >= 2 * linalg.INVERSE_HALVE_ALIGN)
        assert not linalg.inverse_is_halved(linalg.INVERSE_HALVE_MIN_DIM - 1)
        assert linalg.inverse_is_halved(linalg.INVERSE_HALVE_MIN_DIM)

    def _check_against_whole_and_float64(self, n):
        a = _graded(n, seed=n)
        want = np.linalg.inv(a + self.DAMPING * np.eye(n))
        x = jnp.asarray(a, jnp.float32)
        assert linalg.inverse_is_halved(n)
        halved = jax.jit(
            lambda m: linalg.get_inverse(m, self.DAMPING))(x)
        whole = jax.jit(lambda m: _whole_route(m, self.DAMPING))(x)
        assert halved.dtype == jnp.float32 and halved.shape == (n, n)
        err_whole = _fro_gap(whole, want)
        # The tolerance is what the whole route itself reads against
        # the float64 inverse: the same factorization in another order
        # of summation may not be worse than twice that, nor farther
        # from the whole route than both errors together.
        assert 0 < err_whole < 1e-4
        assert _fro_gap(halved, want) <= 2 * err_whole
        assert _fro_gap(halved, whole) <= 3 * err_whole
        np.testing.assert_allclose(halved, halved.T, rtol=0,
                                   atol=1e-5 * float(jnp.abs(whole).max()))

    @pytest.mark.parametrize('n', [32, 64, 128, 49, 97, 33],
                             ids=lambda n: f'dim{n}')
    def test_agrees_with_the_whole_route_and_float64(self, lowered, n):
        self._check_against_whole_and_float64(n)

    def test_at_the_real_gate_an_odd_dim(self):
        """No constant lowered: the first odd dim over the gate (the
        3073 kind: an aligned half and a half one wider)."""
        self._check_against_whole_and_float64(
            linalg.INVERSE_HALVE_MIN_DIM + 1)

    @pytest.mark.parametrize('count,budget', [(5, 8), (7, 3)],
                             ids=['one-batch', 'sub-stacked'])
    def test_a_batched_stack_and_its_counter(self, lowered, monkeypatch,
                                             count, budget):
        n = 49
        monkeypatch.setattr(linalg, 'INVERSE_SUBSTACK_BYTES',
                            budget * n * n * 4)
        mats = [_graded(n, seed=70 + i) for i in range(count)]
        stack = jnp.asarray(np.stack(mats), jnp.float32)
        tracing.clear_trace()
        got = linalg.damped_inverse_stack(stack, self.DAMPING, 'cholesky')
        # Matrices, not calls and not the identities a sub-stacked
        # bucket is padded with.
        assert tracing.counters() == {'kfac/inverse/halved': count}
        whole = jax.vmap(lambda m: _whole_route(m, self.DAMPING))(stack)
        for i, a in enumerate(mats):
            want = np.linalg.inv(a + self.DAMPING * np.eye(n))
            err_whole = _fro_gap(whole[i], want)
            assert _fro_gap(got[i], want) <= 2 * err_whole

    @pytest.mark.parametrize('method,dims,want', [
        ('cholesky', (8, 31), {'kfac/inverse/whole': 6}),
        ('cholesky', (31, 32, 49),
         {'kfac/inverse/whole': 3, 'kfac/inverse/halved': 6}),
        ('newton', (8, 49), {})],
        ids=['under-the-gate', 'both-routes', 'newton-counts-nothing'])
    def test_the_counters_count_matrices_by_route(self, lowered, method,
                                                  dims, want):
        tracing.clear_trace()
        jax.make_jaxpr(lambda *stacks: [
            linalg.damped_inverse_stack(s, 0.1, method, iters=2)
            for s in stacks])(*[
                jax.ShapeDtypeStruct((3, d, d), jnp.float32) for d in dims])
        assert tracing.counters() == want

    @pytest.mark.parametrize('route', ['halved', 'whole'])
    @pytest.mark.parametrize('where', ['first-half', 'schur-complement'])
    def test_not_positive_definite_is_non_finite_on_both_routes(
            self, lowered, where, route):
        n = 64
        a = _graded(n, seed=5) + self.DAMPING * np.eye(n)
        # One strongly negative direction: inside the leading block, or
        # on the last coordinate, where only the last Schur complement
        # (the last leaf's input) is indefinite.
        at = 3 if where == 'first-half' else n - 1
        a[at, at] -= 10.0
        assert np.linalg.eigvalsh(a).min() < -1.0
        fn = linalg.get_inverse if route == 'halved' else _whole_route
        got = np.asarray(jax.jit(lambda m: fn(m, None))(
            jnp.asarray(a, jnp.float32)))
        assert not np.isfinite(got).any()

    @pytest.mark.parametrize('batched', [False, True],
                             ids=['matrix', 'vmapped'])
    def test_under_the_gate_the_jaxpr_is_the_one_it_was(self, batched):
        n = linalg.INVERSE_HALVE_MIN_DIM - 1
        assert n > 640      # over every eigen-path dim 'auto' keeps
        new, old = (lambda m: linalg.get_inverse(m, damping=0.003),
                    lambda m: _whole_route(m, 0.003))
        shape = (n, n)
        if batched:
            new, old, shape = jax.vmap(new), jax.vmap(old), (3, n, n)
        spec = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        assert str(jax.make_jaxpr(new)(spec)) == str(
            jax.make_jaxpr(old)(spec))

    def test_the_halved_jaxpr_holds_leaves_and_square_products(
            self, lowered):
        n, leaf = 97, linalg.INVERSE_HALVE_LEAF
        jaxpr = jax.make_jaxpr(
            lambda m: linalg.get_inverse(m, 0.003))(
                jax.ShapeDtypeStruct((n, n), jnp.float32)).jaxpr
        by_name = {}

        def walk(j):
            for eqn in j.eqns:
                by_name.setdefault(eqn.primitive.name, []).append(eqn)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)
        walk(jaxpr)
        # 97 -> 48 + 49 -> (24, 24) + (24, 25) -> (8, 16) ... (8, 17)
        # -> (8, 9): 9 leaves of 8..16, and XLA's own factorization and
        # solve only ever see those.
        leaves = [e.outvars[0].aval.shape[-1] for e in by_name['cholesky']]
        merges = len(leaves) - 1
        assert sorted(leaves) == [8] * 5 + [9] + [16] * 3
        assert sum(leaves) == n and max(leaves) <= leaf
        assert len(by_name['triangular_solve']) == len(leaves)
        # Four products a merge at HIGHEST in float32 (where XLA's
        # expanders ran theirs). At the default precision, as the whole
        # route's X^T X is (ROADMAP D10): a leaf's own X^T X and two
        # block products a merge, none of them as large as the matrix:
        # the zero block of X is never multiplied.
        dots = by_name['dot_general']
        pinned = [e for e in dots if e.params['precision'] is not None
                  and set(e.params['precision']) == {
                      jax.lax.Precision.HIGHEST}]
        assert len(pinned) == 4 * merges
        assert all(e.params['preferred_element_type'] == jnp.float32
                   for e in pinned)
        default = [e for e in dots if e not in pinned]
        assert len(default) == len(leaves) + 2 * merges
        assert all(e.params['precision'] is None for e in default)
        assert max(max(e.outvars[0].aval.shape) for e in dots) <= 49


class TestFusedPatchCov:
    """Fused im2col+covariance Pallas kernel (interpret mode on CPU):
    must equal ops.factors.conv2d_a_factor exactly in structure — same
    (kh, kw, c) basis, bias assembly, and scaling — for every conv
    configuration the ResNets use (round-2: removes the HBM-materialized
    patch blowup that dominated factor-update cost on v5e)."""

    @pytest.mark.parametrize('cfg', [
        dict(h=8, w=8, c=3, k=(3, 3), s=(1, 1), pad='SAME', bias=True),
        dict(h=8, w=8, c=4, k=(3, 3), s=(2, 2), pad='SAME', bias=True),
        dict(h=9, w=7, c=2, k=(3, 3), s=(1, 1), pad='VALID', bias=False),
        dict(h=8, w=8, c=3, k=(1, 1), s=(1, 1), pad='SAME', bias=True),
        dict(h=10, w=10, c=2, k=(5, 3), s=(1, 2), pad='SAME', bias=True),
    ], ids=['same', 'stride2', 'valid', 'k1', 'rect'])
    def test_matches_xla_path(self, cfg):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(8, cfg['h'], cfg['w'],
                                         cfg['c'])), jnp.float32)
        ref = factors.conv2d_a_factor(x, cfg['k'], cfg['s'], cfg['pad'],
                                      cfg['bias'])
        got = pallas_kernels.conv_a_factor_fused(
            x, cfg['k'], cfg['s'], cfg['pad'], cfg['bias'],
            mult_bf16=False, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)

    def test_block_batch_accumulation(self):
        """Multiple grid steps accumulate into one output block."""
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(6, 8, 8, 3)), jnp.float32)
        ref = factors.conv2d_a_factor(x, (3, 3), (1, 1), 'SAME', True)
        got = pallas_kernels.conv_a_factor_fused(
            x, (3, 3), (1, 1), 'SAME', True, mult_bf16=False,
            block_batch=2, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)


class TestBlockBatchFloor:
    def test_prime_batch_degrades_to_zero(self):
        # Budget fits 2 images; 17 is prime so the only divisors are
        # 17 (too big) and 1 (degenerate) -> refuse, don't degrade.
        assert pallas_kernels._fused_block_batch(
            17, 10 ** 6, 2 * 10 ** 6) == 0

    def test_small_batch_exempt_from_floor(self):
        # b=4 < MIN_FUSED_BLOCK_BATCH: the whole batch is one block,
        # nothing was degraded.
        assert pallas_kernels._fused_block_batch(
            4, 10, 10 ** 6) == 4

    def test_divisor_within_budget(self):
        assert pallas_kernels._fused_block_batch(512, 1, 32) == 32

    def test_degenerate_dispatch_records_fallback(self):
        # A prime batch at a shape whose VMEM budget forces a thin
        # block: the dispatcher warns, records the event, and raises
        # (the factors.py caller catches and runs XLA).
        pallas_kernels.drain_pallas_events()
        x = jnp.zeros((13, 32, 32, 16), jnp.float32)
        with pytest.warns(RuntimeWarning, match='falling back'):
            with pytest.raises(ValueError, match='block_batch'):
                pallas_kernels.conv_a_factor_fused(
                    x, (3, 3), (1, 1), 'SAME', True, interpret=True)
        events = pallas_kernels.drain_pallas_events()
        assert [e['kernel'] for e in events] == ['patch_cov']
        assert 'no divisor' in events[0]['reason']


class TestForcedFallbackProbes:
    """The once-per-process gates of the kernels that have one."""

    PROBES = (pallas_kernels.fused_patch_cov_supported,
              pallas_kernels._fused_attention_probed)

    @pytest.fixture(autouse=True)
    def _fresh_probe_caches(self):
        for probe in self.PROBES:
            probe.cache_clear()
        pallas_kernels.drain_pallas_events()
        yield
        for probe in self.PROBES:
            probe.cache_clear()
        pallas_kernels.drain_pallas_events()

    def test_forced_fallback_records_named_events(self, monkeypatch):
        monkeypatch.setenv('KFAC_PALLAS_FALLBACK', '1')
        with pytest.warns(RuntimeWarning, match='falling back'):
            for probe in self.PROBES:
                assert not probe()
        events = pallas_kernels.drain_pallas_events()
        assert {e['kernel'] for e in events} == {'patch_cov',
                                                'attention'}
        assert all(e['event'] == 'pallas_fallback' for e in events)
        assert all('KFAC_PALLAS_FALLBACK' in e['reason']
                   for e in events)

    def test_no_event_without_the_kill_switch(self, monkeypatch):
        # Off the TPU the patch-covariance kernel is closed (TPU-only)
        # and the attention gate is left to the interpret-mode check;
        # neither is a fallback, so neither records one.
        monkeypatch.delenv('KFAC_PALLAS_FALLBACK', raising=False)
        assert not pallas_kernels.fused_patch_cov_supported()
        assert pallas_kernels._fused_attention_probed()
        assert pallas_kernels.drain_pallas_events() == []


class TestConvPatchImplDispatch:
    """KFAC_CONV_PATCH_IMPL dispatch: every named impl computes the same
    A factor (slices is the measured-fastest default after the round-2
    crosscov regression — VERDICT r2 / PERF.md rounds 1-5), and unknown
    values are rejected loudly instead of silently hitting a legacy
    path."""

    @pytest.mark.parametrize('impl', ['slices', 'crosscov', 'dilated',
                                      'pairs'])
    @pytest.mark.parametrize('cfg', [
        dict(h=8, w=8, c=3, k=(3, 3), s=(1, 1), pad='SAME', bias=True),
        dict(h=9, w=7, c=2, k=(3, 3), s=(2, 2), pad='VALID', bias=False),
        dict(h=16, w=16, c=3, k=(7, 7), s=(2, 2), pad='SAME', bias=True),
    ], ids=['same', 'valid-stride2', 'stem-7x7-s2'])
    def test_impls_agree(self, impl, cfg, monkeypatch):
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.normal(size=(4, cfg['h'], cfg['w'],
                                         cfg['c'])), jnp.float32)
        monkeypatch.delenv('KFAC_CONV_PATCH_IMPL', raising=False)
        ref = factors.conv2d_a_factor(x, cfg['k'], cfg['s'], cfg['pad'],
                                      cfg['bias'],
                                      compute_dtype=jnp.float32)
        monkeypatch.setenv('KFAC_CONV_PATCH_IMPL', impl)
        got = factors.conv2d_a_factor(x, cfg['k'], cfg['s'], cfg['pad'],
                                      cfg['bias'],
                                      compute_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)

    def test_crosscov_symmetric(self, monkeypatch):
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.normal(size=(4, 8, 8, 3)), jnp.float32)
        monkeypatch.setenv('KFAC_CONV_PATCH_IMPL', 'crosscov')
        got = np.asarray(factors.conv2d_a_factor(
            x, (3, 3), (1, 1), 'SAME', False, compute_dtype=jnp.float32))
        np.testing.assert_array_equal(got, got.T)

    def test_unknown_impl_rejected(self, monkeypatch):
        x = jnp.zeros((2, 4, 4, 3), jnp.float32)
        monkeypatch.setenv('KFAC_CONV_PATCH_IMPL', 'bogus')
        with pytest.raises(ValueError, match='KFAC_CONV_PATCH_IMPL'):
            factors.conv2d_a_factor(x, (3, 3), (1, 1), 'SAME', True)
