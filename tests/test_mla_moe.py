"""The latent-attention, routed-expert decoder and the stacked-expert
K-FAC kind, at toy size on the CPU.

The program is held to the plain reference ``kfac_bench/references/
mla_moe_lm.py`` (written from the equations, dense per-expert masks, its
own K-FAC step) through the benchmark's family: losses, gradients, every
factor and the change after three steps through one inverse firing.
Beside that: the shares add up to the uncut layer, an expert without a
token keeps its factors, the router's equations, the attention gate,
what is left to SGD, and the rows past the routed ones, which the chip
leaves undefined.
"""

import json
import os
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from distributed_kfac_pytorch_tpu.capture import EXPERTS  # noqa: E402
from distributed_kfac_pytorch_tpu.models import mla_moe_lm  # noqa: E402
from distributed_kfac_pytorch_tpu.modules import experts  # noqa: E402
from distributed_kfac_pytorch_tpu.observability import tracing  # noqa: E402
from distributed_kfac_pytorch_tpu.ops import factors as F  # noqa: E402
from distributed_kfac_pytorch_tpu.ops import pallas_kernels  # noqa: E402
from distributed_kfac_pytorch_tpu.parallel import sequence  # noqa: E402
from distributed_kfac_pytorch_tpu.preconditioner import KFAC  # noqa: E402
from kfac_bench import control, reference, run  # noqa: E402
from kfac_bench.families import mla_moe_lm as family  # noqa: E402
from kfac_bench.references import mla_moe_lm as plain  # noqa: E402

SEED = 3000000019
CHECKS = ('loss1_gap', 'loss2_gap', 'loss3_gap', 'grad1_gap',
          'grad1_median_gap', 'dparam_gap', 'dparam_median_gap',
          'factor_gap', 'factor_median_gap')


def _bench_json(kind, name):
    with open(os.path.join(ROOT, 'kfac_bench', kind, f'{name}.json')) as f:
        return json.load(f)


@pytest.fixture(scope='module')
def toy():
    """The toy configuration's program through its first three steps
    (one inverse firing at step 0), and the reference over the same
    batches: ``(checks, counters, observed, expected)``."""
    config = _bench_json('configs', 'toy-mla-moe')
    traffic = _bench_json('traffic', 'toy_seq32_b4_f1i4')
    # The family spreads its batch over every device there is: on the
    # tests' 8 virtual ones the program's statistics are averaged over
    # a 1 x 8 mesh, which the reference (one batch, no mesh) never sees.
    chips = jax.device_count()
    cell = family.build(config, traffic, SEED, chips, tempfile.mkdtemp())
    got = control.first_steps(cell)
    want = family.reference_observe(config, traffic, SEED, chips,
                                    list(cell.checked))
    checks = reference.compare(got, want,
                               _bench_json('limits', 'toy_moe_f1i4'))
    return checks, cell.counters(), got, want


@pytest.mark.parametrize('name', CHECKS)
def test_program_agrees_with_the_reference_at_round_off(toy, name):
    check = toy[0][name]
    assert check['limit'] is not None
    assert check['value'] <= min(check['limit'], 5e-6), check


def test_every_factor_is_compared_expert_stacks_among_them(toy):
    _, _, got, want = toy
    assert set(got['factors']) == set(want['factors'])
    stacks = [k for k in want['factors'] if '/experts/' in k]
    # 2 MoE layers x 3 matrices x (A, G) x 4 held experts
    assert len(stacks) == 2 * 3 * 2 * 4
    gaps = reference.sketch_gaps(got['factors'], want['factors'])
    assert max(gaps[k] for k in stacks) < 5e-6


def test_the_counters_say_where_the_rows_fell(toy):
    moe = toy[1]['moe']
    # tokens x top 2 over 8 experts, 4 held: one row a token expected
    tokens = 4 * jax.device_count() * 32
    assert moe['experts_held'] == 4 and moe['steps'] == 3
    assert 0.5 * tokens < moe['rows_here'] < 1.5 * tokens
    assert moe['rows_here'] / 4 <= moe['rows_max_expert'] <= tokens
    assert moe['empty_experts'] == 0


def test_approx_summary_names_what_is_left_to_sgd(toy):
    left = toy[1]['left_to_sgd']
    assert 'head' in left and 'norm' in left
    assert 'layer1/self_attn/kv_a_layernorm' in left
    assert 'layer1/mlp' in left            # the correction bias's owner
    assert not any('proj' in name or 'router' in name for name in left)
    model = mla_moe_lm.get_model(50, 'tiny')
    kfac = KFAC(model, skip_layers=['head'])
    kfac.init(jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
    summary = kfac.approx_summary(left_to_sgd=True)
    assert summary['head'] == 'sgd: skip_layers match'
    assert summary['layer0/input_layernorm'].startswith('sgd: ')
    assert summary['layer1/mlp/experts/up_proj'] == 'expand'
    assert kfac.approx_summary(shared_a=True)[
        'layer1/mlp/experts/up_proj'] == (
            'expand+A of layer1/mlp/experts/gate_proj')
    assert set(kfac.approx_summary().values()) == {'expand'}
    kinds = {spec.kind for spec in kfac.specs.values()}
    assert kinds == {'embedding', 'linear', EXPERTS}
    assert not any(spec.has_bias for spec in kfac.specs.values())


# ---------------------------------------------------------------------------
# The shares add up to the uncut layer
# ---------------------------------------------------------------------------

SIZES = dict(plain.sizes_of(_bench_json('configs', 'toy-mla-moe')),
             experts_held=(0, 8), heads_held=4)


def _uncut_params():
    params = family.init_params(7, SIZES)['layer1']
    return jax.tree.map(lambda x: 5.0 * x, params)   # away from linear


def _probes(b, t):
    return jax.tree.map(lambda s: jnp.zeros(s, jnp.float32),
                        plain._probe_shapes(SIZES, b, t)['layer1'],
                        is_leaf=lambda s: isinstance(s, tuple))


def test_expert_shares_add_up_to_the_uncut_reference_layer():
    params = _uncut_params()['mlp']
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    want = plain._moe(h, params, _probes(2, 16), SIZES,
                      reference.Rounding(), {})
    bias = {'e_score_correction_bias': jnp.zeros((8,))}
    common = dict(n_routed_experts=8, num_experts_per_tok=2,
                  moe_intermediate_size=16, n_shared_experts=2,
                  routed_scaling_factor=2.448)
    shared = mla_moe_lm.GatedMLP(32).apply(
        {'params': params['shared_experts']}, h)
    total = shared                       # the replicated part, once
    for lo, hi in ((0, 3), (3, 4), (4, 8)):
        held = jax.tree.map(lambda x: x[lo:hi], params['experts'])
        out = mla_moe_lm.MoE(experts_held=(lo, hi), **common).apply(
            {'params': {**params, **bias, 'experts': held}}, h)
        total = total + (out - shared)
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-6)


def test_head_shares_add_up_to_the_uncut_reference_attention():
    params = _uncut_params()['self_attn']
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 32))
    want = plain._mla(h, params, _probes(2, 16), SIZES,
                      reference.Rounding(), {})
    dims = dict(qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
                kv_lora_rank=12, rope_theta=10000.0)
    total = 0.0
    for lo, hi in ((0, 1), (1, 4)):
        def heads(kernel, width, axis, lo=lo, hi=hi):
            return jnp.take(kernel, jnp.arange(lo * width, hi * width),
                            axis=axis)
        share = {**params,
                 'q_proj': {'kernel': heads(
                     params['q_proj']['kernel'], 12, 1)},
                 'kv_b_proj': {'kernel': heads(
                     params['kv_b_proj']['kernel'], 16, 1)},
                 'o_proj': {'kernel': heads(
                     params['o_proj']['kernel'], 8, 0)}}
        total = total + mla_moe_lm.MLA(hi - lo, **dims).apply(
            {'params': share}, h, jnp.arange(16))
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# The router's equations
# ---------------------------------------------------------------------------

def test_router_sigmoid_bias_in_the_choice_only_normalised_and_scaled():
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(5, 16)).astype(np.float32)
    bias = np.zeros(16, np.float32)
    bias[3] = 10.0                       # expert 3 is always chosen
    chosen, weights = mla_moe_lm.route(jnp.asarray(logits),
                                       jnp.asarray(bias), 6, 2.448)
    scores = 1.0 / (1.0 + np.exp(-logits))
    for t in range(5):
        order = np.argsort(-(scores[t] + bias))[:6]
        assert set(np.asarray(chosen[t])) == set(order)
        assert 3 in order
        picked = scores[t][np.asarray(chosen[t])]    # no bias in here
        np.testing.assert_allclose(
            weights[t], picked / (picked.sum() + 1e-20) * 2.448,
            rtol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 2.448, rtol=1e-6)
    grad = jax.grad(lambda b: mla_moe_lm.route(
        jnp.asarray(logits), b, 6, 2.448)[1].sum())(jnp.asarray(bias))
    assert not np.any(np.asarray(grad))


# ---------------------------------------------------------------------------
# The attention gate
# ---------------------------------------------------------------------------

def test_wider_qk_than_v_heads_take_the_plain_path_and_count_it():
    from jax.experimental.pallas import tpu as pltpu
    q = jnp.ones((1, 256, 2, 192), jnp.bfloat16)
    v = jnp.ones((1, 256, 2, 128), jnp.bfloat16)
    before = tracing.counters().get('kfac/attention/plain', 0)
    fused = tracing.counters().get('kfac/attention/fused', 0)
    with pltpu.force_tpu_interpret_mode():   # the gate as a TPU sees it
        assert not pallas_kernels.fused_attention_applies(q, q, v)
        out = sequence.local_causal_attention(q, q, v)
    assert out.shape == (1, 256, 2, 128) and out.dtype == jnp.float32
    np.testing.assert_allclose(out, 1.0, rtol=1e-6)
    counters = tracing.counters()
    assert counters['kfac/attention/plain'] == before + 1
    assert counters.get('kfac/attention/fused', 0) == fused


# ---------------------------------------------------------------------------
# The stacked-expert statistics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('fill', [(3, 0, 5, 2), (0, 0, 0, 9), (4, 4, 4, 4)])
def test_expert_factors_are_contracted_over_each_experts_own_rows(fill):
    rng = np.random.default_rng(1)
    rows, d, top_k = 24, 6, 2            # 12 tokens, a tail past the fill
    x = rng.normal(size=(rows, d)).astype(np.float32)
    x[sum(fill):] = np.nan               # what a chip leaves there
    gs = jnp.asarray(fill, jnp.int32)
    a = F.experts_a_factor(jnp.asarray(x), gs, top_k)
    g = F.experts_g_factor(jnp.asarray(x), gs, top_k)
    share = F.experts_row_share(gs, rows, top_k)
    tokens, lo = rows // top_k, 0
    for e, n in enumerate(fill):
        mine = x[lo:lo + n]
        np.testing.assert_allclose(a[e], mine.T @ mine / tokens,
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(g[e], mine.T @ mine / tokens,
                                   rtol=1e-5, atol=1e-6)
        assert float(share[e]) == pytest.approx(n / tokens)
        lo += n
    old = {'A': jnp.broadcast_to(jnp.eye(d), (4, d, d)),
           'G': jnp.broadcast_to(jnp.eye(d), (4, d, d))}
    new = F.experts_running_avg(old, a, g, share, 0.9)
    lo = 0
    for e, n in enumerate(fill):
        mine = x[lo:lo + n]
        if n == 0:                       # untouched, both sides
            np.testing.assert_array_equal(new['A'][e], np.eye(d))
            np.testing.assert_array_equal(new['G'][e], np.eye(d))
        else:
            np.testing.assert_allclose(
                new['A'][e], 0.9 * np.eye(d) + 0.1 * mine.T @ mine / n,
                rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(
                new['G'][e],
                0.9 * np.eye(d) + 0.1 * mine.T @ mine / tokens,
                rtol=1e-5, atol=1e-6)
        lo += n


def test_an_expert_without_a_token_keeps_its_factors_and_inverts():
    """Through KFAC itself: a bias that keeps every token off expert 0
    of the held range leaves its stacks at their seed, step after step,
    and its damped inverse finite."""
    model = mla_moe_lm.get_model(50, 'tiny', num_layers=2)
    kfac = KFAC(model, skip_layers=['head'], factor_update_freq=1,
                inv_update_freq=1, damping=0.01)
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, 16), 0, 50)
    variables, state = kfac.init(jax.random.PRNGKey(0), ids)
    params = variables['params']
    bias = jnp.zeros((8,)).at[0].set(-10.0)
    params['layer1']['mlp']['e_score_correction_bias'] = bias

    @jax.jit
    def step(state):
        loss, _, grads, captures, _ = kfac.capture.loss_and_grads(
            lambda out: out.astype(jnp.float32).mean(), params, ids)
        rows = captures['layer1/mlp/experts/up_proj']['rows'][0]
        return kfac.step(state, grads, captures) + (rows,)

    for _ in range(2):
        precond, state, rows = step(state)
    assert int(rows[0]) == 0 and int(rows[1:].min()) > 0
    for name in ('gate_proj', 'up_proj', 'down_proj'):
        layer = f'layer1/mlp/experts/{name}'
        f, inv = state['factors'][layer], state['inverses'][layer]
        # up_proj reads gate_proj's rows: its A inverse is gate_proj's.
        inv = {**inv, 'A_inv': state['inverses'][
            kfac.specs[layer].a_owner or layer]['A_inv']}
        assert ('A_inv' in state['inverses'][layer]) == (name != 'up_proj')
        for side in ('A', 'G'):
            d = f[side].shape[-1]
            np.testing.assert_array_equal(f[side][0], np.eye(d))
            assert not np.allclose(f[side][1], np.eye(d))
            np.testing.assert_allclose(
                inv[f'{side}_inv'][0], np.eye(d) / 1.01, rtol=1e-5,
                atol=1e-7)
            assert np.isfinite(inv[f'{side}_inv']).all()
    assert all(np.isfinite(x).all() for x in jax.tree.leaves(precond))


# ---------------------------------------------------------------------------
# The rows past the routed ones
# ---------------------------------------------------------------------------

def test_dispatch_and_combine_never_read_the_tail(monkeypatch):
    monkeypatch.setattr(experts, 'ROW_CHUNK', 8)
    rng = np.random.default_rng(2)
    tokens, d, rows, here = 6, 4, 24, 11
    h = jnp.asarray(rng.normal(size=(tokens, d)), jnp.float32)
    token_of_row = jnp.asarray(rng.integers(0, tokens, rows), jnp.int32)
    w = jnp.asarray(rng.uniform(0.5, 1.0, rows), jnp.float32)
    tail = jnp.arange(rows)[:, None] >= here

    def layer(h, w):
        x = experts.dispatch_rows(h, token_of_row, jnp.asarray(here))
        y = jnp.where(tail, jnp.nan, 2.0 * x)    # a chip's tail
        return experts.combine_rows(y, w, token_of_row,
                                    jnp.asarray(here), tokens)

    out, (dh, dw) = jax.value_and_grad(
        lambda h, w: layer(h, w).sum(), argnums=(0, 1))(h, w)
    want = np.zeros((tokens, d))
    for r in range(here):
        want[int(token_of_row[r])] += 2.0 * float(w[r]) * np.asarray(
            h[int(token_of_row[r])])
    np.testing.assert_allclose(layer(h, w), want, rtol=1e-6)
    assert np.isfinite(out) and np.isfinite(dh).all()
    assert np.isfinite(dw).all() and not np.any(np.asarray(dw[here:]))


# ---------------------------------------------------------------------------
# Through the benchmark's harness, and over a mesh
# ---------------------------------------------------------------------------

def test_the_harness_runs_the_family_at_toy_size(tmp_path):
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        spec = json.load(f)
    spec['configs'] = [{'name': 'toy-mla-moe', 'source': 'none',
                        'file': 'kfac_bench/configs/toy-mla-moe.json',
                        'reduced': [], 'why': 'tests'}]
    spec['workloads'] = [{'name': 'toy_moe_f1i4', 'config': 'toy-mla-moe',
                          'traffic': 'toy_seq32_b4_f1i4',
                          'chips': jax.device_count(), 'why': 'tests'}]
    for metric in spec['per_layer']:
        if 'kanana2_d5_f1i10' in metric.get('workloads', ()):
            metric['workloads'] = ['toy_moe_f1i4']
    path = tmp_path / 'toy_moe_benchmark.json'
    path.write_text(json.dumps(spec))
    code, result = run.run_cell('toy_moe_f1i4', SEED, 0.5, False,
                                spec_path=str(path), require_chip=False)
    assert code == 0 and result['correct'] is True
    assert result['failed'] == 0 and result['attempted'] >= 4
    assert result['info']['builds_in_window'] == 0
    assert set(result['info']['trace_counts'].values()) == {1}
    assert result['metrics'] == {}       # no device metric off the chip
    assert all(c['ok'] for c in result['checks'].values())


def test_the_new_readers_read_nothing_where_there_is_nothing():
    from kfac_bench.readers import moe_rows, program_gauge
    assert moe_rows.read({'counters': {}}) is None
    assert moe_rows.read({'counters': {'moe': {'rows_here': None,
                                               'experts_held': 8}}}) is None
    assert moe_rows.read({'counters': {'moe': {
        'rows_here': 3072.0, 'experts_held': 8}}}) == 384.0
    assert program_gauge.read({}, 'kfac/state_bytes/no_such_group') is None
