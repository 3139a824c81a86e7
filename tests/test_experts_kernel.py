"""The stacked experts' grouped-matmul route
(``ops.pallas_kernels.grouped_matmul``, JAX's shipped ``gmm``/``tgmm``)
on Pallas's TPU interpreter, against ``jax.lax.ragged_dot``'s dense
expansion: the output and the input gradient on the routed rows, the
weight gradient whole, whatever the rows past the last group hold."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from distributed_kfac_pytorch_tpu.modules import experts
from distributed_kfac_pytorch_tpu.observability import tracing
from distributed_kfac_pytorch_tpu.ops import pallas_kernels as P

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
    P._grouped_matmul_allowed.cache_clear()
    P.drain_pallas_events()
    try:
        with pltpu.force_tpu_interpret_mode(), pytest.warns(RuntimeWarning):
            assert not P.grouped_matmul_applies(*_shape(ROWS, K, N))
        assert [e['kernel'] for e in P.drain_pallas_events()] == [
            'grouped_matmul']
    finally:
        P._grouped_matmul_allowed.cache_clear()


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
