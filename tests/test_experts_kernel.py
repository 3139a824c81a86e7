"""The stacked experts' grouped-matmul route
(``ops.pallas_kernels.grouped_matmul``, JAX's shipped ``gmm``/``tgmm``)
on Pallas's TPU interpreter, against ``jax.lax.ragged_dot``'s dense
expansion: the output and the input gradient on the routed rows, the
weight gradient whole, whatever the rows past the last group hold.

And the per-expert covariances of ``ops.factors`` on the same ``tgmm``
(``ops.pallas_kernels.experts_cov``) against
``jax.lax.ragged_dot_general``: every expert's block, empty experts,
NaN past the groups, the running average, the gate and the counters a
toy MoE step leaves."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from distributed_kfac_pytorch_tpu.capture import EXPERTS
from distributed_kfac_pytorch_tpu.models import mla_moe_lm
from distributed_kfac_pytorch_tpu.modules import experts
from distributed_kfac_pytorch_tpu.observability import tracing
from distributed_kfac_pytorch_tpu.ops import factors as F
from distributed_kfac_pytorch_tpu.ops import pallas_kernels as P
from distributed_kfac_pytorch_tpu.preconditioner import KFAC

TILE = P.GROUPED_ROW_TILE
ROWS, K, N, E = 3 * TILE, 256, 128, 4

#: Rows an expert, over a buffer of three row tiles.
FILLS = {
    # every group but the first starts inside a tile; a tail past them
    'expected': [150, 150, 150, 150],
    # empty experts, one group over three tiles, one starting inside one
    'skewed': [0, 2 * TILE + 18, 0, 70],
    'full': [ROWS // E] * E,
    'none': [0] * E,
}


def _operands(fill, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(ROWS, K)), dtype)
    w = jnp.asarray(rng.normal(size=(E, K, N)) / K ** 0.5, dtype)
    dy = jnp.asarray(rng.normal(size=(ROWS, N)), dtype)
    return x, w, dy, jnp.asarray(fill, jnp.int32)


def _with_tail(a, here, value):
    return jnp.where((jnp.arange(a.shape[0]) < here)[:, None], a, value)


def _pallas(x, w, gs, dy):
    with pltpu.force_tpu_interpret_mode():
        y, vjp = jax.vjp(lambda x, w: P.grouped_matmul(x, w, gs), x, w)
        return (y, *vjp(dy))


def _xla(x, w, gs, dy):
    y, vjp = jax.vjp(lambda x, w: jax.lax.ragged_dot(x, w, gs), x, w)
    return (y, *vjp(dy))


def _f32(a):
    return np.asarray(a.astype(jnp.float32))


@pytest.mark.parametrize('fill', FILLS.values(), ids=FILLS.keys())
def test_the_pallas_route_equals_ragged_dot_on_the_routed_rows(fill):
    x, w, dy, gs = _operands(fill)
    here = sum(fill)
    # The reference reads zeros past the groups; the route reads NaN
    # there, as a buffer's tail on the chip may hold.
    x0, dy0 = _with_tail(x, here, 0.0), _with_tail(dy, here, 0.0)
    want = _xla(x0, w, gs, dy0)
    got = _pallas(_with_tail(x, here, jnp.nan), w, gs,
                  _with_tail(dy, here, jnp.nan))
    for name, a, b in zip(('y', 'dx'), got[:2], want[:2]):
        np.testing.assert_allclose(_f32(a)[:here], _f32(b)[:here],
                                   rtol=1e-5, atol=1e-5, err_msg=name)
    dw = _f32(got[2])
    assert np.isfinite(dw).all()
    np.testing.assert_allclose(dw, _f32(want[2]), rtol=1e-5, atol=1e-4)
    for e in np.flatnonzero(np.asarray(fill) == 0):
        assert not dw[e].any()


def test_bf16_operands_accumulate_in_float32_like_ragged_dot():
    fill = FILLS['expected']
    x, w, dy, gs = _operands(fill, jnp.bfloat16, seed=1)
    here = sum(fill)
    got, want = _pallas(x, w, gs, dy), _xla(x, w, gs, dy)
    assert all(a.dtype == jnp.bfloat16 for a in got)
    for a, b, rows in zip(got, want, (here, here, None)):
        a, b = _f32(a)[:rows], _f32(b)[:rows]
        assert np.abs(a - b).max() <= 2e-2 * np.abs(b).max()


def test_tgmm_and_gmm_take_a_tiling_of_their_own_shape():
    """Whole ``k`` and ``n`` where the tiles fit the fast-memory budget,
    else the wider one cut to a lane-multiple divisor, at both MoE
    cells' expert widths and a narrow one."""
    for k, n in ((2304, 896), (896, 2304), (2048, 768), (768, 2048),
                 (256, 128)):
        for weight_grad in (False, True):
            tm, tk, tn = P.grouped_tiling(k, n, weight_grad=weight_grad)
            assert tm == TILE and k % tk == 0 and n % tn == 0
            assert tk % 128 == 0 and tn % 128 == 0
            assert P._grouped_tile_bytes(tm, tk, tn, 2, weight_grad) \
                <= P.GROUPED_TILE_BUDGET
    assert P.grouped_tiling(256, 128) == (TILE, 256, 128)
    assert P.grouped_tiling(2304, 896) == (TILE, 2304, 896)


#: ``(k, n, weight_grad)``: the products' tiles at both MoE cells'
#: widths as they were measured and shipped (PERF.md §6): mellum's
#: gate/up forward, down's forward (= gate/up's input gradient), the
#: two weight gradients; down's input gradient is gate's forward; then
#: kanana's.
PRODUCT_TILES = {
    (2304, 896, False): (TILE, 2304, 896),
    (896, 2304, False): (TILE, 896, 1152),
    (2304, 896, True): (TILE, 1152, 896),
    (896, 2304, True): (TILE, 896, 1152),
    (2048, 768, False): (TILE, 2048, 768),
    (768, 2048, False): (TILE, 768, 2048),
    (2048, 768, True): (TILE, 1024, 768),
    (768, 2048, True): (TILE, 768, 1024),
}


@pytest.mark.parametrize('shape', PRODUCT_TILES, ids=lambda s: '%dx%d%s' % (
    s[0], s[1], '_dw' if s[2] else ''))
def test_the_products_keep_their_measured_tiles(shape):
    """The output's itemsize defaults to the operands': no product is
    retiled by the count that the covariances' float32 output needs."""
    k, n, weight_grad = shape
    assert P.grouped_tiling(k, n, weight_grad=weight_grad) == \
        PRODUCT_TILES[shape]


#: ``d``: the covariance's tiles at each MoE width. At 2304 the cut goes
#: to 2304's next divisor, 768, where 1152's own would be 384 (on a
#: v5e the first ran 12-14 % faster); 2048's pick was the fastest of the
#: 14 tilings swept; 896 and 768 fit whole.
COV_TILES = {2304: (TILE, 768, 1152), 2048: (TILE, 512, 1024),
             896: (TILE, 896, 896), 768: (TILE, 768, 768)}


@pytest.mark.parametrize('d', COV_TILES)
def test_the_covariance_tiles_fit_the_budget_with_a_float32_output(d):
    """A ``(tk, tn)`` float32 output tile, double-buffered, with its
    accumulator, at each MoE width: the tiles divide ``d`` in lane
    multiples and fit the budget counted at 4 bytes an output element,
    which is more than the operands' 2 would count."""
    tm, tk, tn = P.grouped_tiling(d, d, weight_grad=True, out_itemsize=4)
    assert (tm, tk, tn) == COV_TILES[d]
    assert d % tk == 0 and d % tn == 0 and tk % 128 == 0 and tn % 128 == 0
    counted = P._grouped_tile_bytes(tm, tk, tn, 2, True, 4)
    assert counted <= P.GROUPED_TILE_BUDGET
    assert counted > P._grouped_tile_bytes(tm, tk, tn, 2, True)


def _shape(rows, k, n, dtype=jnp.float32, w_dtype=None):
    return (jax.ShapeDtypeStruct((rows, k), dtype),
            jax.ShapeDtypeStruct((E, k, n), w_dtype or dtype))


@pytest.mark.parametrize('rows,k,n,dtype,w_dtype,opens', [
    (ROWS, K, N, jnp.float32, None, True),
    (ROWS, K, N, jnp.bfloat16, None, True),
    (ROWS, 200, N, jnp.float32, None, False),
    (ROWS, K, 96, jnp.float32, None, False),
    (ROWS + TILE // 2, K, N, jnp.float32, None, False),
    (ROWS, K, N, jnp.bfloat16, jnp.float32, False),
    (ROWS, K, N, jnp.float16, None, False),
], ids=['f32', 'bf16', 'in_200', 'out_96', 'rows_half_tile',
        'mixed_dtypes', 'f16'])
def test_the_gate_opens_only_where_the_kernels_tile(rows, k, n, dtype,
                                                    w_dtype, opens):
    x, w = _shape(rows, k, n, dtype, w_dtype)
    assert not P.grouped_matmul_applies(x, w)   # the CPU: ragged_dot
    with pltpu.force_tpu_interpret_mode():
        assert P.grouped_matmul_applies(x, w) == opens


def test_the_kill_switch_closes_the_gate(monkeypatch):
    monkeypatch.setenv('KFAC_PALLAS_FALLBACK', '1')
    P._grouped_kernel_allowed.cache_clear()
    P.drain_pallas_events()
    try:
        with pltpu.force_tpu_interpret_mode(), pytest.warns(RuntimeWarning):
            assert not P.grouped_matmul_applies(*_shape(ROWS, K, N))
        assert [e['kernel'] for e in P.drain_pallas_events()] == [
            'grouped_matmul']
    finally:
        P._grouped_kernel_allowed.cache_clear()


def _count(name):
    return tracing.counters().get(name, 0)


@pytest.mark.parametrize('features,route', [(N, 'grouped_pallas'),
                                            (96, 'grouped_xla')])
def test_experts_dense_counts_the_route_it_traced(features, route):
    """One count a traced call, on the route the gate chose, and the
    module's output and gradients are ragged_dot's on the routed rows."""
    fill = FILLS['skewed']
    x, _, _, gs = _operands(fill)
    layer = experts.ExpertsDense(E, features)
    params = layer.init(jax.random.PRNGKey(0), x, gs)
    here = sum(fill)

    def loss(params, x):
        y = layer.apply(params, x, gs)
        return jnp.sum(jnp.where((jnp.arange(ROWS) < here)[:, None],
                                 y, 0.0) ** 2)

    names = ('kfac/moe/grouped_pallas', 'kfac/moe/grouped_xla')
    before = [_count(n) for n in names]
    with pltpu.force_tpu_interpret_mode():
        got = jax.value_and_grad(loss, (0, 1))(params, x)
    counted = [_count(n) - b for n, b in zip(names, before)]
    assert counted == ([1, 0] if route == 'grouped_pallas' else [0, 1])
    want = jax.value_and_grad(loss, (0, 1))(params, x)   # the CPU's route
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    np.testing.assert_allclose(
        got[1][0]['params']['kernel'], want[1][0]['params']['kernel'],
        rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(_f32(got[1][1])[:here],
                               _f32(want[1][1])[:here], rtol=1e-5,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# The per-expert covariances
# ---------------------------------------------------------------------------

COV_PALLAS = 'kfac/factors/experts_cov_pallas'
COV_XLA = 'kfac/factors/experts_cov_xla'


def _rows(d, fill, dtype=jnp.bfloat16, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(ROWS, d)), dtype),
            jnp.asarray(fill, jnp.int32))


def _ragged_cov(x, gs):
    return jax.lax.ragged_dot_general(
        x, x, gs, F._RAGGED_ROWS, preferred_element_type=jnp.float32)


def _pallas_cov(x, gs):
    with pltpu.force_tpu_interpret_mode():
        return P.experts_cov(x, gs)


@pytest.mark.parametrize('d', [256, 1280], ids=['one_tile', 'cut'])
@pytest.mark.parametrize('fill', FILLS.values(), ids=FILLS.keys())
def test_the_pallas_covariance_equals_ragged_dot_general_per_expert(fill,
                                                                    d):
    """Every expert's block, at the expected fill, a skewed one with
    empty experts and groups starting inside a tile, the buffer full
    and no row at all; NaN past the groups reaches no block, an empty
    expert's block is zero, every block is symmetric bit for bit. At
    1280 the output is cut into tiles."""
    x, gs = _rows(d, fill)
    here = sum(fill)
    want = _ragged_cov(_with_tail(x, here, 0.0), gs)
    got = _pallas_cov(_with_tail(x, here, jnp.nan), gs)
    assert got.dtype == jnp.float32 and got.shape == (E, d, d)
    got = np.asarray(got)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, got.transpose(0, 2, 1))
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5, atol=1e-3)
    for e in np.flatnonzero(np.asarray(fill) == 0):
        assert not got[e].any()


def test_bf16_rows_accumulate_in_float32():
    """bf16 products are exact in float32, so a float32 sum meets the
    float64 sum of the same bf16 values to float32's rounding; a bf16
    accumulator would miss it by about 1e-2."""
    fill = FILLS['expected']
    x, gs = _rows(K, fill, seed=2)
    got = np.asarray(_pallas_cov(x, gs))
    rows = np.asarray(x.astype(jnp.float32), np.float64)
    start = 0
    for e, n in enumerate(fill):
        block = rows[start:start + n]
        want = block.T @ block
        start += n
        assert np.abs(got[e] - want).max() <= 1e-5 * np.abs(want).max()


@pytest.mark.parametrize('fill', [FILLS['skewed'], FILLS['none']],
                         ids=['skewed', 'none'])
def test_the_running_average_keeps_an_empty_experts_factors(fill):
    """Through the statistics on the Pallas route and
    ``experts_running_avg``: an expert that got no row keeps its A and G
    bit for bit, every other one moves."""
    x, gs = _rows(K, fill, seed=3)
    g, _ = _rows(K, fill, seed=4)
    here = sum(fill)
    x, g = _with_tail(x, here, jnp.nan), _with_tail(g, here, jnp.nan)
    rng = np.random.default_rng(5)
    old = {k: jnp.asarray(rng.normal(size=(E, K, K)), jnp.float32)
           for k in ('A', 'G')}
    with pltpu.force_tpu_interpret_mode():
        new = F.experts_running_avg(
            old, F.experts_a_factor(x, gs, 2), F.experts_g_factor(g, gs, 2),
            F.experts_row_share(gs, ROWS, 2), 0.95)
    for name in ('A', 'G'):
        got = np.asarray(new[name])
        assert np.isfinite(got).all()
        for e, n in enumerate(fill):
            same = np.array_equal(got[e], np.asarray(old[name][e]))
            assert same == (n == 0), (name, e)


@pytest.mark.parametrize('rows,d,dtype,opens', [
    (ROWS, K, jnp.bfloat16, True),
    (ROWS, 1280, jnp.bfloat16, True),
    (ROWS, 200, jnp.bfloat16, False),
    (ROWS + TILE // 2, K, jnp.bfloat16, False),
    (ROWS, K, jnp.float32, False),
    (ROWS, K, jnp.float16, False),
], ids=['bf16', 'bf16_1280', 'd_200', 'rows_half_tile', 'f32', 'f16'])
def test_the_covariance_gate_opens_only_for_bf16_rows_in_whole_tiles(
        rows, d, dtype, opens):
    x = jax.ShapeDtypeStruct((rows, d), dtype)
    assert not P.experts_cov_applies(x)   # the CPU: ragged_dot_general
    with pltpu.force_tpu_interpret_mode():
        assert P.experts_cov_applies(x) == opens


def test_the_kill_switch_closes_the_covariance_gate(monkeypatch):
    monkeypatch.setenv('KFAC_PALLAS_FALLBACK', '1')
    P._grouped_kernel_allowed.cache_clear()
    P.drain_pallas_events()
    try:
        with pltpu.force_tpu_interpret_mode(), pytest.warns(RuntimeWarning):
            assert not P.experts_cov_applies(
                jax.ShapeDtypeStruct((ROWS, K), jnp.bfloat16))
        assert [e['kernel'] for e in P.drain_pallas_events()] == [
            'experts_cov']
    finally:
        P._grouped_kernel_allowed.cache_clear()


@pytest.mark.parametrize('compute_dtype', [None, jnp.float32],
                         ids=['rows_dtype', 'f32_highest'])
def test_the_statistic_is_symmetric_on_either_route(compute_dtype):
    """``_experts_cov`` whole: the Pallas route (bf16 rows; the kernel's
    blocks as they come) or XLA's (float32 under ``Precision.HIGHEST``,
    symmetrized), NaN past the groups: symmetric bit for bit and equal
    to the reference of its own precision."""
    fill = FILLS['skewed']
    x, gs = _rows(K, fill, seed=6)
    here = sum(fill)
    names = (COV_PALLAS, COV_XLA)
    before = [_count(n) for n in names]
    with pltpu.force_tpu_interpret_mode():
        got = np.asarray(F._experts_cov(_with_tail(x, here, jnp.nan), gs,
                                        compute_dtype))
    counted = [_count(n) - b for n, b in zip(names, before)]
    assert counted == ([1, 0] if compute_dtype is None else [0, 1])
    np.testing.assert_array_equal(got, got.transpose(0, 2, 1))
    x0 = _with_tail(x, here, 0.0)
    if compute_dtype is not None:
        x0 = x0.astype(compute_dtype)
    np.testing.assert_allclose(got, np.asarray(_ragged_cov(x0, gs)),
                               rtol=1e-5, atol=1e-3)


def _toy_moe_step_counts(interpret):
    """Traces one capturing K-FAC step of a toy decoder with four MoE
    layers (widths 128, 64 tokens x 4 choices = 256 rows a buffer) and
    returns what the two covariance counters gained."""
    model = mla_moe_lm.get_model(
        64, 'tiny', d_model=128, num_layers=5, moe_intermediate_size=128,
        num_experts_per_tok=4, dtype=jnp.bfloat16)
    kfac = KFAC(model, skip_layers=['head'], factor_update_freq=1,
                inv_update_freq=2, damping=0.003)
    ids = jax.random.randint(jax.random.PRNGKey(1), (4, 17), 0, 64)
    variables, state = kfac.init(jax.random.PRNGKey(0), ids[:, :-1])
    moe = [s for s in kfac.specs.values() if s.kind == EXPERTS]
    assert len(moe) == 12   # gate, up, down in each of 4 layers

    def step(params, state):
        out, _, grads, captures, _ = kfac.capture.loss_and_grads(
            lambda out: jnp.mean(out.astype(jnp.float32) ** 2), params,
            ids[:, :-1])
        return kfac.step(state, grads, captures)

    names = (COV_PALLAS, COV_XLA)
    before = [_count(n) for n in names]
    with (pltpu.force_tpu_interpret_mode() if interpret
          else contextlib.nullcontext()):
        jax.jit(step).lower(variables['params'], state)
    return [_count(n) - b for n, b in zip(names, before)]


@pytest.mark.parametrize('interpret,want', [(True, [20, 0]),
                                            (False, [0, 20])],
                         ids=['pallas', 'xla'])
def test_a_toy_moe_step_counts_twenty_statistics_on_the_route_it_took(
        interpret, want):
    """Four MoE layers x (gate's A, down's A, and the three G's; up
    reads gate's A): 20 statistics a traced capturing step, on the
    Pallas route under the TPU interpreter, on XLA's on the CPU."""
    assert _toy_moe_step_counts(interpret) == want
