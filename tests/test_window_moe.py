"""The windowed/full-attention, grouped-head, softmax-routed expert
decoder, at toy size on the CPU.

The program is held to the plain reference ``kfac_bench/references/
window_moe_lm.py`` (written from the equations, attention in query
blocks, dense per-expert masks, its own K-FAC step) through the
benchmark's family: losses, gradients, every factor and the change
after three steps through one inverse firing. Beside that: the expert
shares add up to the uncut layer, YaRN's frequencies at the published
numbers, the splash route against the plain path on Pallas's TPU
interpreter, the counters a traced pass leaves, the sigmoid router as it
was, and the other decoders' programs as they were.
"""

import json
import os
import re
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from distributed_kfac_pytorch_tpu.models import mla_moe_lm  # noqa: E402
from distributed_kfac_pytorch_tpu.models import window_moe_lm  # noqa: E402
from distributed_kfac_pytorch_tpu.observability import tracing  # noqa: E402
from distributed_kfac_pytorch_tpu.ops import pallas_kernels  # noqa: E402
from distributed_kfac_pytorch_tpu.parallel import sequence  # noqa: E402
from kfac_bench import control, reference, run  # noqa: E402
from kfac_bench.families import window_moe_lm as family  # noqa: E402
from kfac_bench.references import window_moe_lm as plain  # noqa: E402

SEED = 3000000019
CHECKS = ('loss1_gap', 'loss2_gap', 'loss3_gap', 'grad1_gap',
          'grad1_median_gap', 'dparam_gap', 'dparam_median_gap',
          'factor_gap', 'factor_median_gap')
FIXTURES = os.path.join(ROOT, 'tests', 'fixtures')


def _bench_json(kind, name):
    with open(os.path.join(ROOT, 'kfac_bench', kind, f'{name}.json')) as f:
        return json.load(f)


@pytest.fixture(scope='module')
def toy():
    """The toy configuration's program through its first three steps
    (one inverse firing at step 0), and the reference over the same
    batches: ``(checks, counters, observed, expected)``."""
    config = _bench_json('configs', 'toy-window-moe')
    traffic = _bench_json('traffic', 'toy_seq32_b4_f1i4')
    chips = jax.device_count()
    cell = family.build(config, traffic, SEED, chips, tempfile.mkdtemp())
    got = control.first_steps(cell)
    want = family.reference_observe(config, traffic, SEED, chips,
                                    list(cell.checked))
    checks = reference.compare(got, want,
                               _bench_json('limits', 'toy_window_moe_f1i4'))
    return checks, cell.counters(), got, want


@pytest.mark.parametrize('name', CHECKS)
def test_program_agrees_with_the_reference_at_round_off(toy, name):
    """float32 on both sides, so every reading is round-off: the
    program's summation orders (a fused softmax, the grouped expert
    contractions, a batch spread over the mesh) against the reference's
    dense ones. 5e-6 is ten times the largest seen."""
    check = toy[0][name]
    assert check['limit'] is not None
    assert check['value'] <= min(check['limit'], 5e-6), check


def test_every_factor_is_compared_expert_stacks_and_followers_among_them(
        toy):
    _, counters, got, want = toy
    assert set(got['factors']) == set(want['factors'])
    stacks = [k for k in want['factors'] if '/experts/' in k]
    # 4 MoE layers x 3 matrices x (A, G) x 4 held experts
    assert len(stacks) == 4 * 3 * 2 * 4
    # k and v read q's A, up reads gate's, in every layer
    followers = counters['a_followers']
    assert len(followers) == 4 * 3
    assert followers['layer3/self_attn/v_proj'] == 'layer3/self_attn/q_proj'
    assert followers['layer0/mlp/experts/up_proj'] == (
        'layer0/mlp/experts/gate_proj')
    assert 'head' in counters['left_to_sgd']
    assert not any('proj' in n or 'router' in n
                   for n in counters['left_to_sgd'])


def test_the_harness_runs_the_family_at_toy_size(tmp_path):
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        spec = json.load(f)
    spec['configs'] = [{'name': 'toy-window-moe', 'source': 'none',
                        'file': 'kfac_bench/configs/toy-window-moe.json',
                        'reduced': [], 'why': 'tests'}]
    spec['workloads'] = [{'name': 'toy_window_moe_f1i4',
                          'config': 'toy-window-moe',
                          'traffic': 'toy_seq32_b4_f1i4',
                          'chips': jax.device_count(), 'why': 'tests'}]
    for metric in spec['per_layer']:
        if 'mellum2_d4_f1i10' in metric.get('workloads', ()):
            metric['workloads'] = ['toy_window_moe_f1i4']
    path = tmp_path / 'toy_window_moe_benchmark.json'
    path.write_text(json.dumps(spec))
    code, result = run.run_cell('toy_window_moe_f1i4', SEED, 0.5, False,
                                spec_path=str(path), require_chip=False)
    assert code == 0 and result['correct'] is True
    assert result['failed'] == 0 and result['attempted'] >= 4
    assert result['info']['builds_in_window'] == 0
    assert set(result['info']['trace_counts'].values()) == {1}
    assert result['metrics'] == {}       # no device metric off the chip


def test_the_cells_data_files_name_what_the_harness_finds():
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        spec = json.load(f)
    cell = run.load_cell_spec(spec, 'mellum2_d4_f1i10')
    assert cell['config']['family'] == 'window_moe_lm'
    assert (cell['traffic']['seq'], cell['traffic']['per_chip_batch']) == (
        4096, 1)
    for name in ('sliding_attention_ms', 'attention_roofline_pct',
                 'attention_ms', 'moe_ms', 'moe_rows_per_expert',
                 'kfac_expert_state_gib', 'step_program_hbm_gib',
                 'step_mfu_pct'):
        assert name in cell['per_layer'], name
    sizes = plain.sizes_of(cell['config'])
    assert (sizes['hidden_size'], sizes['moe_intermediate_size'],
            sizes['num_attention_heads'], sizes['num_key_value_heads'],
            sizes['head_dim'], sizes['num_experts'],
            sizes['num_experts_per_tok'], sizes['sliding_window'],
            sizes['vocab_size'], sizes['experts_held']) == (
        2304, 896, 32, 4, 128, 64, 8, 1024, 12288, (0, 8))
    assert sizes['layer_types'] == (plain.SLIDING,) * 3 + (plain.FULL,)
    # Attention's work a step: 953.7 GFLOP over 620.9 M kept pairs; a
    # sliding layer keeps 43.7 % of a full one's.
    work = family.attention_work(sizes, 1, 4096)
    assert work['flops'] == 12 * 128 * 620_871_680
    assert 0.6e9 < work['bytes'] < 0.62e9
    one_sliding = dict(sizes, layer_types=(plain.SLIDING,))
    one_full = dict(sizes, layer_types=(plain.FULL,))
    assert family.mask_pairs(one_sliding, 4096) / family.mask_pairs(
        one_full, 4096) == pytest.approx(0.4375, abs=5e-4)
    # the attention is 22 % of the model's FLOPs a step
    attention = 3 * 4 * 128 * family.mask_pairs(sizes, 4096)
    assert attention / family.flops_per_step(sizes, 1, 4096) == (
        pytest.approx(0.219, abs=0.002))


# ---------------------------------------------------------------------------
# The shares add up to the uncut layer
# ---------------------------------------------------------------------------

def test_expert_shares_add_up_to_the_uncut_reference_layer():
    """Four shares of 8 experts, each chip's block output less the part
    every chip computes alike (the residual stream and attention),
    summed, and that part added once: the uncut reference block, on a
    full (YaRN) layer."""
    config = _bench_json('configs', 'toy-window-moe')
    sizes = dict(plain.sizes_of(config), experts_held=(0, 8))
    params = jax.tree.map(lambda x: 5.0 * x,    # away from linear
                          family.init_params(7, sizes)['layer3'])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    probes = jax.tree.map(lambda s: jnp.zeros(s, jnp.float32),
                          plain._probe_shapes(sizes, 2, 16)['layer3'],
                          is_leaf=lambda s: isinstance(s, tuple))

    def uncut(x):
        h = plain._rms_norm(x, params['input_layernorm']['scale'])
        x = x + plain._attention(h, params['self_attn'], probes, sizes,
                                 reference.Rounding(), {}, plain.FULL)
        h = plain._rms_norm(x, params['post_attention_layernorm']['scale'])
        return x, x + plain._moe(h, params['mlp'], probes, sizes,
                                 reference.Rounding(), {})

    replicated, want = uncut(x)
    yarn = dict(zip(('factor', 'original_max_position_embeddings',
                     'beta_fast', 'beta_slow', 'attention_factor'),
                    sizes['full_rope'][1:]))
    total = replicated
    for lo, hi in ((0, 2), (2, 4), (4, 7), (7, 8)):
        held = jax.tree.map(lambda w: w[lo:hi], params['mlp']['experts'])
        block = window_moe_lm.Block(
            dict(num_heads=4, num_kv_heads=2, head_dim=16, window=None,
                 rope_theta=10000.0, yarn=yarn),
            dict(n_routed_experts=8, experts_held=(lo, hi),
                 num_experts_per_tok=2, moe_intermediate_size=16,
                 n_shared_experts=0, scoring_func='softmax'))
        out = block.apply({'params': {**params, 'mlp': {
            **params['mlp'], 'experts': held}}}, x, jnp.arange(16))
        total = total + (out - replicated)
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# YaRN at the published numbers
# ---------------------------------------------------------------------------

def test_yarn_frequencies_at_the_published_numbers():
    """Mellum2's full layers: d 128, theta 5e5, factor 16, original
    8192, beta_fast 32, beta_slow 1: the ramp runs from index 18 to 35;
    below it the plain frequencies, past it a sixteenth of them."""
    yarn = window_moe_lm.MELLUM2_YARN
    assert window_moe_lm.yarn_range(128, 5e5, 8192, 32.0, 1.0) == (18, 35)
    got = np.asarray(window_moe_lm.yarn_inv_freq(
        128, 5e5, yarn['factor'], yarn['original_max_position_embeddings'],
        yarn['beta_fast'], yarn['beta_slow']), np.float64)
    i = np.arange(64)
    base = 5e5 ** (-2.0 * i / 128)
    ramp = np.clip((i - 18) / 17, 0, 1)
    hand = base / 16 * ramp + base * (1 - ramp)
    np.testing.assert_allclose(got, hand, rtol=2e-6)
    np.testing.assert_allclose(got[:19], base[:19], rtol=2e-6)
    np.testing.assert_allclose(got[35:], base[35:] / 16, rtol=2e-6)
    assert got[26] == pytest.approx(base[26] * (1 - 8 / 17 * 15 / 16),
                                    rel=2e-6)
    # the reference's own table, from the configuration's keys
    sizes = plain.sizes_of(_bench_json('configs', 'mellum2-12b-a2.5b-d4'))
    inv_freq, scale = plain.rope_frequencies(sizes, plain.FULL)
    np.testing.assert_allclose(inv_freq, hand, rtol=2e-6)
    assert scale == yarn['attention_factor']


# ---------------------------------------------------------------------------
# The splash route
# ---------------------------------------------------------------------------

WINDOWED, GROUPED = 'kfac/attention/windowed', 'kfac/attention/grouped'
FUSED, PLAIN = 'kfac/attention/fused', 'kfac/attention/plain'


@pytest.fixture
def fresh(monkeypatch):
    monkeypatch.delenv('KFAC_PALLAS_FALLBACK', raising=False)
    pallas_kernels._splash_attention_probed.cache_clear()
    pallas_kernels.drain_pallas_events()
    tracing.clear_trace()
    yield
    pallas_kernels._splash_attention_probed.cache_clear()
    pallas_kernels.drain_pallas_events()
    tracing.clear_trace()


def _grouped_qkv(t, heads, kv_heads, dtype, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(1, t, heads, 128)), dtype),
            *(jnp.asarray(rng.normal(size=(1, t, kv_heads, 128)), dtype)
              for _ in range(2)))


def _out_and_grads(attend, qkv, **kw):
    w = jnp.asarray(np.random.default_rng(7).normal(size=qkv[0].shape),
                    jnp.float32)

    def loss(q, k, v):
        out = attend(q, k, v, **kw)
        return jnp.sum(out * w), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, (0, 1, 2), has_aux=True))(*qkv)
    return out, grads


@pytest.mark.parametrize('t,heads,kv_heads,window', [
    (512, 4, 2, None), (512, 4, 2, 200), (768, 2, 2, 300),
    (512, 4, 1, 512)], ids=['grouped', 'grouped_window', 'window_256_tiles',
                            'one_kv_head_window_one_tile'])
@pytest.mark.parametrize('dtype,tol', [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=['float32', 'bfloat16'])
def test_splash_matches_plain(fresh, t, heads, kv_heads, window, dtype, tol):
    """Output and dq/dk/dv of the splash route on Pallas's TPU
    interpreter against the plain path, which repeats the kv heads and
    masks the band. float32: summation order alone; bfloat16: the
    kernel's rounding of q/sqrt(d), of P for P.V and of its outputs."""
    qkv = _grouped_qkv(t, heads, kv_heads, dtype)
    with pltpu.force_tpu_interpret_mode():
        out, grads = _out_and_grads(sequence.local_causal_attention, qkv,
                                    window=window)
    want = {FUSED: 1, WINDOWED: int(window is not None),
            GROUPED: int(kv_heads != heads)}
    assert tracing.counters() == {k: v for k, v in want.items() if v}
    ref_out, ref_grads = _out_and_grads(sequence.plain_attention, qkv,
                                        window=window)
    assert out.dtype == ref_out.dtype == jnp.float32
    assert pallas_kernels.max_rel_error(out, ref_out) < tol
    for got, ref in zip(grads, ref_grads):
        assert got.dtype == ref.dtype == dtype
        assert pallas_kernels.max_rel_error(got, ref) < tol


@pytest.mark.parametrize('probed', ['_fused_attention_probed',
                                    '_splash_attention_probed'])
def test_each_routes_probe_reads_well_under_its_tolerance(fresh, probed,
                                                          monkeypatch):
    """A route's once-a-process probe as a TPU runs it, on the CPU's
    interpreter: output and dq/dk/dv within a fifth of the tolerance
    (the scalar the gradients come from is left out: at the splash
    probe's shapes its cancellation alone reads 0.12)."""
    readings = []

    def probe_here(kernel, rel_error):
        with pltpu.force_tpu_interpret_mode():
            readings.append(rel_error())
        return True

    monkeypatch.setattr(pallas_kernels, '_probe_on_tpu', probe_here)
    monkeypatch.setattr(pallas_kernels.jax, 'default_backend',
                        lambda: 'tpu')
    getattr(pallas_kernels, probed).cache_clear()
    try:
        assert getattr(pallas_kernels, probed)()
    finally:
        getattr(pallas_kernels, probed).cache_clear()
    assert len(readings) == 1
    assert readings[0] < 0.2 * pallas_kernels.PROBE_RTOL


def test_the_plain_path_reads_the_band_and_the_groups():
    """The plain path against attention written out query by query."""
    q, k, v = _grouped_qkv(16, 4, 2, jnp.float32)
    q, k, v = q[..., :8], k[..., :8], v[..., :8]
    out = np.asarray(sequence.plain_attention(q, k, v, window=5))
    for i in range(16):
        for j in range(4):
            keys = np.arange(max(0, i - 4), i + 1)
            s = np.asarray(k[0, keys, j // 2]) @ np.asarray(q[0, i, j])
            p = np.exp(s / np.sqrt(8) - np.max(s / np.sqrt(8)))
            want = (p / p.sum()) @ np.asarray(v[0, keys, j // 2])
            np.testing.assert_allclose(out[0, i, j], want, rtol=1e-5,
                                       atol=1e-6)


@pytest.mark.parametrize('case', ['kvalid', 'head_dim_32', 't_200',
                                  'ragged_groups'])
def test_the_splash_gate_is_closed_by_shape(fresh, case):
    q, k, v = _grouped_qkv(256, 4, 2, jnp.float32)
    kw = {'window': 64}
    if case == 'kvalid':
        kw = {'kvalid': jnp.arange(256) < 200}
    elif case == 'head_dim_32':
        q, k, v = q[..., :32], k[..., :32], v[..., :32]
    elif case == 't_200':
        q, k, v = q[:, :200], k[:, :200], v[:, :200]
    else:
        q = jnp.concatenate([q, q[:, :, :1]], axis=2)     # 5 over 2
    with pltpu.force_tpu_interpret_mode():
        assert not pallas_kernels.splash_attention_applies(
            q, k, v, kw.get('kvalid'))
        if case != 'ragged_groups':
            out = sequence.local_causal_attention(q, k, v, **kw)
    if case != 'ragged_groups':
        assert tracing.counters()[PLAIN] == 1 and FUSED not in (
            tracing.counters())
        np.testing.assert_array_equal(
            out, sequence.plain_attention(q, k, v, **kw))


def test_the_splash_route_leaves_jaxs_kernel_module_as_it_is(fresh):
    """The route's kernels come from a private copy of JAX's splash
    module; the module every other caller imports still hands
    ``pallas_call`` its metadata."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as shipped,
    )
    qkv = _grouped_qkv(512, 4, 2, jnp.float32)
    with pltpu.force_tpu_interpret_mode():
        sequence.local_causal_attention(*qkv, window=200)
    copy = pallas_kernels._splash_kernels()
    assert shipped.pl is pl
    assert copy is not shipped
    assert copy.pl.pallas_call is not pl.pallas_call


def test_a_splash_module_that_no_longer_reaches_pallas_by_name_raises(
        monkeypatch):
    monkeypatch.setattr(pallas_kernels, 'SPLASH_KERNEL_MODULE',
                        'jax.experimental.pallas.ops.tpu.splash_attention.'
                        'splash_attention_mask')
    pallas_kernels._splash_kernels.cache_clear()
    try:
        with pytest.raises(RuntimeError, match='no longer reaches Pallas'):
            pallas_kernels._splash_kernels()
    finally:
        pallas_kernels._splash_kernels.cache_clear()


def test_a_call_with_neither_window_nor_groups_never_asks_the_splash_gate(
        fresh, monkeypatch):
    def refuse(*a, **k):
        raise AssertionError('the splash gate was asked')
    monkeypatch.setattr(pallas_kernels, 'splash_attention_applies', refuse)
    q, k, v = _grouped_qkv(256, 2, 2, jnp.float32)
    sequence.local_causal_attention(q[..., :64], k[..., :64], v[..., :64])
    assert tracing.counters() == {PLAIN: 1}


def test_the_kill_switch_closes_the_splash_route_and_says_so(fresh,
                                                             monkeypatch):
    monkeypatch.setenv('KFAC_PALLAS_FALLBACK', '1')
    q, k, v = _grouped_qkv(256, 4, 2, jnp.float32)
    with pltpu.force_tpu_interpret_mode():
        with pytest.warns(RuntimeWarning, match='falling back'):
            sequence.local_causal_attention(q, k, v, window=64)
    assert tracing.counters() == {PLAIN: 1, WINDOWED: 1, GROUPED: 1}
    events = pallas_kernels.drain_pallas_events()
    assert [(e['event'], e['kernel']) for e in events] == [
        ('pallas_fallback', 'splash_attention')]


def test_a_model_pass_counts_three_windowed_and_four_grouped_calls(fresh):
    """Mellum2's period at toy size: S, S, S, F over 4 q / 2 kv heads;
    each traced pass counts its calls once, under two scopes."""
    model = window_moe_lm.get_model(64, 'tiny')
    ids = jnp.zeros((1, 32), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                               ids))
    tracing.clear_trace()
    text = jax.jit(model.apply).lower(params, ids).as_text(
        debug_info=True)
    attention = {k: v for k, v in tracing.counters().items()
                 if k.startswith('kfac/attention/')}
    assert attention == {WINDOWED: 3, GROUPED: 4, PLAIN: 4}
    assert 'kfac_model/attention/sliding' in text
    assert 'kfac_model/attention/full' in text


# ---------------------------------------------------------------------------
# What the shared code does for the other decoders
# ---------------------------------------------------------------------------

def test_route_with_sigmoid_is_the_routers_formula_bit_for_bit():
    """``route``'s sigmoid branch is the DeepSeek-V3 router as it was
    before the softmax branch came: the same operations, so the same
    bits."""
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(64, 16)), jnp.bfloat16)
    bias = jnp.asarray(rng.normal(size=16) * 0.1, jnp.float32)
    chosen, weights = mla_moe_lm.route(logits, bias, 6, 2.448)
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, want_chosen = jax.lax.top_k(scores + bias, 6)
    picked = jnp.take_along_axis(scores, want_chosen, axis=-1)
    want = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    np.testing.assert_array_equal(chosen, want_chosen)
    np.testing.assert_array_equal(weights, want * 2.448)


def test_route_with_softmax_scores_all_experts_and_renormalises():
    rng = np.random.default_rng(1)
    logits = rng.normal(size=(5, 8)).astype(np.float32)
    chosen, weights = mla_moe_lm.route(jnp.asarray(logits), None, 3, 1.0,
                                       'softmax')
    p = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    for t in range(5):
        top = np.argsort(-p[t])[:3]
        assert set(np.asarray(chosen[t])) == set(top)
        np.testing.assert_allclose(
            weights[t], p[t][np.asarray(chosen[t])] / p[t][top].sum(),
            rtol=1e-6)
    with pytest.raises(ValueError, match='scoring'):
        mla_moe_lm.route(jnp.asarray(logits), None, 3, 1.0, 'tanh')


# The digests below are made by this function on the commit before the
# windowed decoder came (``tests/fixtures/decoder_jaxprs.json``).
# --- decoder programs: begin ---
def decoder_programs() -> dict:
    """sha256 of the jaxpr of the loss and its gradient of each decoder
    the benchmark ran before this one, at toy size, with object
    addresses blanked; the first two traced as a TPU traces them
    (Pallas's interpreter forced: the fused gate open where the shape
    allows; the looped decoder's remat cannot take the interpreter's
    callbacks)."""
    import hashlib

    import optax

    from distributed_kfac_pytorch_tpu.models import (
        looped_lm,
        mla_moe_lm,
        transformer_lm,
    )

    ids = jnp.zeros((2, 256), jnp.int32)
    models = {
        'transformer_lm': (transformer_lm.TransformerLM(
            vocab_size=64, d_model=128, num_layers=2, num_heads=2,
            max_len=256, dropout=0.0, tie_weights=True), {}, True),
        'mla_moe_lm': (mla_moe_lm.get_model(64, 'tiny'), {}, True),
        'looped_lm': (looped_lm.get_model(64, 'tiny', num_heads=2,
                                          head_dim=64), {'targets': ids},
                      False),
    }
    out = {}
    for name, (model, kw, interpret) in models.items():
        params = model.init(jax.random.PRNGKey(0), ids,
                            **kw)['params']

        def loss(p, model=model, kw=kw):
            y = model.apply({'params': p}, ids, **kw)
            if kw:
                return y.mean()
            return optax.softmax_cross_entropy_with_integer_labels(
                y.astype(jnp.float32), ids).mean()

        trace = jax.make_jaxpr(jax.value_and_grad(loss))
        if interpret:
            with pltpu.force_tpu_interpret_mode():
                text = str(trace(params))
        else:
            text = str(trace(params))
        text = re.sub(r'0x[0-9a-f]+', '0x', text)
        out[name] = hashlib.sha256(text.encode()).hexdigest()
    return out
# --- decoder programs: end ---


def test_the_other_decoders_trace_the_programs_they_traced_before():
    """The attention gate, ``route``/``MoE`` and ``rope_half`` are
    shared with the decoders already in the benchmark: their jaxprs,
    fused path included, are digest for digest the ones the commit
    before this change traced."""
    with open(os.path.join(FIXTURES, 'decoder_jaxprs.json')) as f:
        golden = json.load(f)
    if golden['made_with'] != jax.__version__:
        pytest.skip(f'digests are jax {golden["made_with"]}\'s')
    tracing.clear_trace()
    assert decoder_programs() == golden['digests']
    counters = tracing.counters()
    assert WINDOWED not in counters and GROUPED not in counters
