"""The softmax-routed expert decoder trained by diffusion over blocks,
at toy size on the CPU.

The program is held to the plain reference ``kfac_bench/references/
block_diffusion_moe_lm.py`` (written from the equations, the mask as a
rule on (i, j), attention in query blocks, dense per-expert masks, the
windowed decoder's K-FAC step) through the benchmark's family: the
noised stream's logits, the objective, gradients, every factor and the
change after three steps through one inverse firing. Beside that: the
mask against a brute-force matrix, the splash route and the plain path
against that matrix's attention, the clean stream against the causal
decoder, the expert shares against the uncut layer, the feed, the CLI,
and the windowed decoder's programs as they were.
"""

import hashlib
import json
import os
import re
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.experimental.pallas import tpu as pltpu

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, 'examples'))

from distributed_kfac_pytorch_tpu.models import block_diffusion_lm  # noqa: E402
from distributed_kfac_pytorch_tpu.models import window_moe_lm  # noqa: E402
from distributed_kfac_pytorch_tpu.observability import tracing  # noqa: E402
from distributed_kfac_pytorch_tpu.ops import pallas_kernels  # noqa: E402
from distributed_kfac_pytorch_tpu.parallel import sequence  # noqa: E402
from kfac_bench import control, reference, run  # noqa: E402
from kfac_bench.families import block_diffusion_moe_lm as family  # noqa: E402
from kfac_bench.references import block_diffusion_moe_lm as plain  # noqa: E402

SEED = 3000000019
CHECKS = ('loss1_gap', 'loss2_gap', 'loss3_gap', 'grad1_gap',
          'grad1_median_gap', 'dparam_gap', 'dparam_median_gap',
          'factor_gap', 'factor_median_gap')
FIXTURES = os.path.join(ROOT, 'tests', 'fixtures')
DIFFUSION = 'kfac/attention/block_diffusion'
SHIFTED = 'kfac/attention/block_diffusion/mask_shifted'
FUSED, PLAIN = 'kfac/attention/fused', 'kfac/attention/plain'
KEPT = 'kfac/attention/tile_fill/kept_pairs'
VISITED = 'kfac/attention/tile_fill/visited_pairs'


def _bench_json(kind, name):
    with open(os.path.join(ROOT, 'kfac_bench', kind, f'{name}.json')) as f:
        return json.load(f)


def brute_mask(length: int, block: int) -> np.ndarray:
    """The mask written out pair by pair from its definition."""
    t = 2 * length
    out = np.zeros((t, t), bool)
    for i in range(t):
        for j in range(t):
            clean_i, clean_j = i >= length, j >= length
            bi, bj = (i % length) // block, (j % length) // block
            if not clean_i and not clean_j:
                out[i, j] = bi == bj
            elif not clean_i:
                out[i, j] = bj < bi
            elif clean_j:
                out[i, j] = bj <= bi
    return out


# ---------------------------------------------------------------------------
# The mask
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('length,block', [(8, 4), (12, 3), (20, 4),
                                          (24, 1)])
def test_the_mask_rule_is_the_brute_force_matrix(length, block):
    want = brute_mask(length, block)
    i, j = np.arange(2 * length)[:, None], np.arange(2 * length)[None, :]
    np.testing.assert_array_equal(
        pallas_kernels.block_diffusion_keep(i, j, block, length), want)
    np.testing.assert_array_equal(
        np.asarray(plain.keep(jnp.asarray(i), jnp.asarray(j), block,
                              length)), want)
    # a noised query reads B keys of its block and B b clean ones; a
    # clean query B (b + 1)
    assert want.sum() == family.mask_pairs(length, block)


@pytest.mark.parametrize('length,block,route', [
    (8, 4, 'shifted'), (16, 8, 'shifted'), (32, 16, 'shifted'),
    (24, 1, 'shifted'), (20, 2, 'shifted'), (12, 3, 'divided')])
def test_the_kernels_mask_is_the_brute_force_matrix(length, block, route):
    """What the splash kernels evaluate on a tile: the division-free
    rule where the block is a power of two, pair by pair, and the
    rule's own ``//`` form where it is not."""
    want = brute_mask(length, block)
    i, j = np.arange(2 * length)[:, None], np.arange(2 * length)[None, :]
    assert pallas_kernels.block_diffusion_route(block) == route
    if route == 'shifted':
        np.testing.assert_array_equal(
            pallas_kernels.block_diffusion_keep_shifted(
                jnp.asarray(i), jnp.asarray(j), block, length), want)
    mask = pallas_kernels._block_diffusion_mask(2 * length, block)
    np.testing.assert_array_equal(
        mask.mask_function(jnp.asarray(i), jnp.asarray(j)), want)


def test_the_splash_mask_reads_the_rule_tile_by_tile_past_a_ragged_end():
    """The kernel's computable mask, read in tiles of 7 over T = 40 (no
    whole number of tiles: the last row and column of tiles are cut
    short), is the brute-force matrix; the tile counts match."""
    length, block, tile = 20, 4, 7
    want = brute_mask(length, block)
    mask = pallas_kernels._block_diffusion_mask(2 * length, block)
    got = np.zeros_like(want)
    t = 2 * length
    for lo in range(0, t, tile):
        for col in range(0, t, tile):
            rows = slice(lo, min(lo + tile, t))
            cols = slice(col, min(col + tile, t))
            got[rows, cols] = mask[rows, cols]
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize('length', [256, 2048])
def test_the_tile_fill_is_the_brute_force_count_of_tiles(length):
    """At the kernel's tile (256 at T = 512, 512 at the cell's 4096):
    the pairs kept, and every pair of each tile that keeps one."""
    t = 2 * length
    want = pallas_kernels.block_diffusion_keep(
        np.arange(t)[:, None], np.arange(t)[None, :], 4, length)
    s = 512 if t % 512 == 0 else 256
    tiles = want.reshape(t // s, s, t // s, s).any(axis=(1, 3))
    kept, visited = pallas_kernels.splash_tile_fill(t, 4)
    assert kept == want.sum() == family.mask_pairs(length, 4)
    assert visited == tiles.sum() * s * s
    if length == 2048:
        # 24 of 64 tiles; two thirds of their pairs are kept
        assert (tiles.sum(), round(kept / visited, 4)) == (24, 0.668)


# ---------------------------------------------------------------------------
# The attention routes against the matrix's attention
# ---------------------------------------------------------------------------

@pytest.fixture
def fresh(monkeypatch):
    monkeypatch.delenv('KFAC_PALLAS_FALLBACK', raising=False)
    pallas_kernels._splash_block_diffusion_probed.cache_clear()
    pallas_kernels.drain_pallas_events()
    tracing.clear_trace()
    yield
    pallas_kernels._splash_block_diffusion_probed.cache_clear()
    pallas_kernels.drain_pallas_events()
    tracing.clear_trace()


def _qkv(t, heads, kv_heads, dtype, d=128, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(1, t, heads, d)), dtype),
            *(jnp.asarray(rng.normal(size=(1, t, kv_heads, d)), dtype)
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


def _matrix_attention(length, block):
    """Attention under the brute-force matrix, in float32 at HIGHEST."""
    mask = jnp.asarray(brute_mask(length, block))

    def attend(q, k, v, **_):
        h, hk = q.shape[2], k.shape[2]
        k, v = (jnp.repeat(x.astype(jnp.float32), h // hk, axis=2)
                for x in (k, v))
        s = jnp.einsum('bqhd,bkhd->bhqk', q.astype(jnp.float32), k,
                       precision='highest') / np.sqrt(q.shape[-1])
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum('bhqk,bkhd->bqhd', p, v, precision='highest')
    return attend


@pytest.mark.parametrize('dtype,tol,block', [
    pytest.param(jnp.float32, 2e-5, 4, id='float32'),
    pytest.param(jnp.bfloat16, 2e-2, 4, id='bfloat16'),
    pytest.param(jnp.float32, 2e-5, 16, id='float32-block16'),
    pytest.param(jnp.bfloat16, 2e-2, 16, id='bfloat16-block16')])
def test_splash_and_plain_are_the_matrix_attention(fresh, dtype, tol, block):
    """T = 512 (two 256-tiles a side, one skipped), 4 q heads over 2
    kv heads, blocks of 4 and of 16: output and dq/dk/dv of the splash
    route on Pallas's TPU interpreter and of the plain path against
    attention under the brute-force matrix. bfloat16: the kernel's
    rounding of q/sqrt(d), of P for P.V and of its outputs."""
    length = 256
    qkv = _qkv(2 * length, 4, 2, dtype)
    want = _out_and_grads(_matrix_attention(length, block), qkv)
    with pltpu.force_tpu_interpret_mode():
        splash = _out_and_grads(sequence.local_causal_attention, qkv,
                                diffusion_block=block)
    counters = tracing.counters()
    kept, visited = pallas_kernels.splash_tile_fill(512, block)
    assert counters == {DIFFUSION: 1, SHIFTED: 1, FUSED: 1,
                        'kfac/attention/grouped': 1,
                        KEPT: 4 * kept, VISITED: 4 * visited}
    tracing.clear_trace()
    plain_path = _out_and_grads(sequence.local_causal_attention, qkv,
                                diffusion_block=block)
    assert tracing.counters()[PLAIN] == 1
    for got in (splash, plain_path):
        assert got[0].dtype == jnp.float32
        assert pallas_kernels.max_rel_error(got[0], want[0]) < tol
        for g, w in zip(got[1], want[1]):
            assert g.dtype == dtype
            assert pallas_kernels.max_rel_error(g, w.astype(dtype)) < tol


def test_the_block_diffusion_probe_reads_well_under_its_tolerance(
        fresh, monkeypatch):
    readings = []

    def probe_here(kernel, rel_error):
        with pltpu.force_tpu_interpret_mode():
            readings.append((kernel, rel_error()))
        return True

    monkeypatch.setattr(pallas_kernels, '_probe_on_tpu', probe_here)
    monkeypatch.setattr(pallas_kernels.jax, 'default_backend',
                        lambda: 'tpu')
    assert pallas_kernels._splash_block_diffusion_probed()
    assert [k for k, _ in readings] == ['splash_block_diffusion']
    assert readings[0][1] < 0.2 * pallas_kernels.PROBE_RTOL


def test_a_mask_the_route_does_not_know_is_refused():
    q, k, v = _qkv(16, 2, 2, jnp.float32, d=8)
    with pytest.raises(ValueError, match='no window'):
        sequence.local_causal_attention(q, k, v, window=4,
                                        diffusion_block=4)
    with pytest.raises(ValueError, match='whole blocks'):
        sequence.local_causal_attention(q, k, v, diffusion_block=3)


def test_a_model_pass_counts_its_calls_under_the_new_scopes(fresh):
    model = block_diffusion_lm.get_model(64, 'tiny')
    ids = jnp.zeros((1, 16), jnp.int32)
    w = jnp.ones((1, 16), jnp.float32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids,
                                               ids, w))
    tracing.clear_trace()
    text = jax.jit(model.apply).lower(params, ids, ids, w).as_text(
        debug_info=True)
    attention = {k: v for k, v in tracing.counters().items()
                 if k.startswith('kfac/attention/')}
    assert attention == {DIFFUSION: 2, 'kfac/attention/grouped': 2,
                         PLAIN: 2}
    assert 'kfac_model/attention/block_diffusion' in text
    assert 'kfac_model/diffusion/objective' in text


# ---------------------------------------------------------------------------
# The program against the reference
# ---------------------------------------------------------------------------

@pytest.fixture(scope='module')
def toy():
    """The toy configuration's program through its first three steps
    (one inverse firing at step 0), and the reference over the same
    batches: ``(checks, counters, observed, expected)``."""
    config = _bench_json('configs', 'toy-block-diffusion-moe')
    traffic = _bench_json('traffic', 'toy_bd16_b4_blk4_f1i4')
    chips = jax.device_count()
    cell = family.build(config, traffic, SEED, chips, tempfile.mkdtemp())
    got = control.first_steps(cell)
    want = family.reference_observe(config, traffic, SEED, chips,
                                    list(cell.checked))
    checks = reference.compare(
        got, want, _bench_json('limits', 'toy_block_diffusion_moe_f1i4'))
    return checks, cell.counters(), got, want


@pytest.mark.parametrize('name', CHECKS)
def test_program_agrees_with_the_reference_at_round_off(toy, name):
    """float32 on both sides, so every reading is round-off: the
    program's summation orders against the reference's dense ones.
    5e-6 is three times the largest seen."""
    check = toy[0][name]
    assert check['limit'] is not None
    assert check['value'] <= min(check['limit'], 5e-6), check


def test_every_factor_is_compared_over_both_streams(toy):
    _, counters, got, want = toy
    assert set(got['factors']) == set(want['factors'])
    stacks = [k for k in want['factors'] if '/experts/' in k]
    # 2 MoE layers x 3 matrices x (A, G) x 4 held experts
    assert len(stacks) == 2 * 3 * 2 * 4
    followers = counters['a_followers']
    assert followers['layer1/self_attn/k_proj'] == 'layer1/self_attn/q_proj'
    assert followers['layer0/mlp/experts/up_proj'] == (
        'layer0/mlp/experts/gate_proj')
    assert 'head' in counters['left_to_sgd']
    assert any('q_norm' in n for n in counters['left_to_sgd'])
    # the feed masks about half of each chip's 4 x 16 noised positions
    masked = counters['diffusion']['masked_tokens']
    assert 0.3 * 64 < masked < 0.8 * 64


def _toy_sizes(**overrides):
    sizes = plain.sizes_of(_bench_json('configs', 'toy-block-diffusion-moe'))
    return {**sizes, **overrides}


def _toy_model(sizes, block=4, **overrides):
    return family.build_model({'program_size': 'tiny',
                               'compute_dtype': 'float32'}, sizes, block)


def _toy_batch(length=16, rows=2, seed=0):
    feed = family.diffusion_batches(seed, 64, rows, length, 4, (0.3, 0.8))
    return next(feed)


def test_the_noised_streams_logits_are_the_references():
    sizes = _toy_sizes()
    params = family.init_params(5, sizes)
    x_t, x_0, weights = _toy_batch()
    got = _toy_model(sizes).apply({'params': params}, x_t, x_0)
    probes = jax.tree.map(lambda s: jnp.zeros(s, jnp.float32),
                          plain.probe_shapes(sizes, 2, 32),
                          is_leaf=lambda s: isinstance(s, tuple))
    with jax.default_matmul_precision('highest'):
        want, _ = plain.forward(params, probes, jnp.asarray(x_t),
                                jnp.asarray(x_0), sizes=sizes, block=4,
                                rounding=reference.Rounding())
        objective = _toy_model(sizes).apply({'params': params}, x_t, x_0,
                                            weights)
    assert got.shape == (2, 16, 64)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    nll = optax.softmax_cross_entropy_with_integer_labels(want, x_0)
    np.testing.assert_allclose(objective, weights * nll, rtol=2e-5,
                               atol=2e-6)


def test_at_block_one_the_clean_stream_is_the_causal_decoder():
    """Blocks of 1, every noised position masked: each clean position's
    stream, layer by layer, is the windowed decoder's full-attention
    layer with q/k norm run over ``x_0`` alone, causally."""
    sizes = _toy_sizes()
    params = family.init_params(9, sizes)
    _, x_0, _ = _toy_batch()
    x_t = np.full_like(x_0, 63)
    model = _toy_model(sizes, block=1)
    _, seen = model.apply({'params': params}, x_t, x_0,
                          capture_intermediates=True)
    x = params['embed']['embedding'][x_0]
    pos = jnp.arange(16)
    for i in range(sizes['num_hidden_layers']):
        causal = window_moe_lm.Block(
            dict(num_heads=4, num_kv_heads=2, head_dim=16, window=None,
                 rope_theta=1e4, qk_norm=True),
            dict(n_routed_experts=8, experts_held=(2, 6),
                 num_experts_per_tok=2, moe_intermediate_size=16,
                 n_shared_experts=0, scoring_func='softmax'))
        x = causal.apply({'params': params[f'layer{i}']}, x, pos)
        clean = seen['intermediates'][f'layer{i}']['__call__'][0][:, 16:]
        np.testing.assert_allclose(clean, x, rtol=2e-5, atol=2e-5)


def test_expert_shares_add_up_to_the_uncut_reference_layer():
    """Four shares of 8 experts, each chip's block output less the part
    every chip computes alike (the residual stream and attention),
    summed, and that part added once: the uncut reference block over
    both streams."""
    sizes = _toy_sizes(experts_held=(0, 8))
    params = jax.tree.map(lambda x: 5.0 * x,    # away from linear
                          family.init_params(7, sizes)['layer1'])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32))
    probes = jax.tree.map(lambda s: jnp.zeros(s, jnp.float32),
                          plain.probe_shapes(sizes, 2, 32)['layer1'],
                          is_leaf=lambda s: isinstance(s, tuple))
    rounding = reference.Rounding()
    with jax.default_matmul_precision('highest'):
        h = plain._rms_norm(x, params['input_layernorm']['scale'])
        replicated = x + plain._attention(h, params['self_attn'], probes,
                                          sizes, 4, rounding, {})
        h = plain._rms_norm(replicated,
                            params['post_attention_layernorm']['scale'])
        want = replicated + plain._moe(h, params['mlp'], probes, sizes,
                                       rounding, {})
        total = replicated
        for lo, hi in ((0, 2), (2, 4), (4, 7), (7, 8)):
            held = jax.tree.map(lambda w: w[lo:hi],
                                params['mlp']['experts'])
            block = window_moe_lm.Block(
                dict(num_heads=4, num_kv_heads=2, head_dim=16, window=None,
                     rope_theta=1e4, qk_norm=True,
                     diffusion_block=4),
                dict(n_routed_experts=8, experts_held=(lo, hi),
                     num_experts_per_tok=2, moe_intermediate_size=16,
                     n_shared_experts=0, scoring_func='softmax'))
            out = block.apply({'params': {**params, 'mlp': {
                **params['mlp'], 'experts': held}}}, x,
                jnp.tile(jnp.arange(16), 2))
            total = total + (out - replicated)
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# The feed, the configuration and the harness
# ---------------------------------------------------------------------------

def test_the_feed_draws_its_noise_from_the_seed_a_level_a_block():
    one = family.diffusion_batches(11, 64, 3, 32, 4, (0.3, 0.8))
    two = family.diffusion_batches(11, 64, 3, 32, 4, (0.3, 0.8))
    for _ in range(3):
        x_t, x_0, w = next(one)
        for a, b in zip((x_t, x_0, w), next(two)):
            np.testing.assert_array_equal(a, b)
        masked = w > 0
        assert (x_0 < 63).all()
        np.testing.assert_array_equal(x_t == 63, masked)
        np.testing.assert_array_equal(x_t[~masked], x_0[~masked])
        assert ((w[masked] >= 1 / 0.8) & (w[masked] <= 1 / 0.3)).all()
        # one level a (row, block): the masked weights of a block agree
        blocks = w.reshape(3, 8, 4)
        for row in blocks:
            for b in row:
                assert len(set(b[b > 0].tolist())) <= 1
    # the CLI's noise draws the same way from the same generator
    rng_a, rng_b = (np.random.default_rng(3) for _ in range(2))
    x_0 = rng_a.integers(0, 63, (3, 32), dtype=np.int32)
    rng_b.integers(0, 63, (3, 32), dtype=np.int32)
    got = block_diffusion_lm.noise(rng_a, x_0, 4, 63)
    want = next(family.diffusion_batches(3, 64, 3, 32, 4, (0.3, 0.8)))
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[2])


def test_the_cells_data_files_name_what_the_harness_finds():
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        spec = json.load(f)
    cell = run.load_cell_spec(spec, 'sdar_d4_bd4_f1i10')
    assert cell['config']['family'] == 'block_diffusion_moe_lm'
    traffic = cell['traffic']
    assert (traffic['seq'], traffic['per_chip_batch'], traffic['block'],
            traffic['noise_levels']) == (2048, 1, 4, [0.3, 0.8])
    for name in ('block_diffusion_attention_ms', 'attention_tile_fill_pct',
                 'attention_roofline_pct', 'attention_ms', 'moe_ms',
                 'moe_rows_per_expert', 'kfac_expert_state_gib',
                 'step_program_hbm_gib', 'step_mfu_pct'):
        assert name in cell['per_layer'], name
    sizes = plain.sizes_of(cell['config'])
    assert (sizes['hidden_size'], sizes['moe_intermediate_size'],
            sizes['num_attention_heads'], sizes['num_key_value_heads'],
            sizes['head_dim'], sizes['num_experts'],
            sizes['num_experts_per_tok'], sizes['vocab_size'],
            sizes['rope_theta'], sizes['experts_held']) == (
        2048, 768, 32, 4, 128, 128, 8, 18992, 1000000, (0, 8))
    # attention is a quarter of the model's FLOPs a step
    attention = (3 * 4 * 128 * 32 * 4 * family.mask_pairs(2048, 4))
    assert attention / family.flops_per_step(sizes, 1, 2048, 4) == (
        pytest.approx(0.245, abs=0.005))
    work = family.attention_work(sizes, 1, 2048, 4)
    assert work['flops'] == 12 * 128 * 32 * 4 * (2048 ** 2 + 2048 * 4)


def test_the_configuration_keeps_every_published_number_it_does_not_list():
    config = _bench_json('configs', 'sdar-30b-a3b-d4')
    published = {'hidden_size': 2048, 'intermediate_size': 6144,
                  'moe_intermediate_size': 768, 'num_attention_heads': 32,
                  'num_key_value_heads': 4, 'head_dim': 128,
                  'num_experts': 128, 'num_experts_per_tok': 8,
                  'rope_theta': 1000000, 'rms_norm_eps': 1e-6,
                  'max_position_embeddings': 32768,
                  'decoder_sparse_step': 1, 'max_window_layers': 48}
    for key, value in published.items():
        assert config[key] == value, key
    assert (config['num_hidden_layers'], config['vocab_size']) == (4, 18992)
    assert set(config['reduced']) == {'num_hidden_layers', 'num_experts',
                                      'vocab_size'}
    for key in ('block_length', 'noise_schedule', 'qk_norm', 'mask_token'):
        assert key in config['assumed']


def test_the_harness_runs_the_family_at_toy_size(tmp_path):
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        spec = json.load(f)
    spec['configs'] = [{'name': 'toy-block-diffusion-moe', 'source': 'none',
                        'file': 'kfac_bench/configs/'
                                'toy-block-diffusion-moe.json',
                        'reduced': [], 'why': 'tests'}]
    spec['workloads'] = [{'name': 'toy_block_diffusion_moe_f1i4',
                          'config': 'toy-block-diffusion-moe',
                          'traffic': 'toy_bd16_b4_blk4_f1i4',
                          'chips': jax.device_count(), 'why': 'tests'}]
    for metric in spec['per_layer']:
        if 'sdar_d4_bd4_f1i10' in metric.get('workloads', ()):
            metric['workloads'] = ['toy_block_diffusion_moe_f1i4']
    path = tmp_path / 'toy_block_diffusion_benchmark.json'
    path.write_text(json.dumps(spec))
    code, result = run.run_cell('toy_block_diffusion_moe_f1i4', SEED, 0.5,
                                False, spec_path=str(path),
                                require_chip=False)
    assert code == 0 and result['correct'] is True
    assert result['failed'] == 0 and result['attempted'] >= 4
    assert result['info']['builds_in_window'] == 0
    assert set(result['info']['trace_counts'].values()) == {1}
    assert result['metrics'] == {}       # no device metric off the chip


# ---------------------------------------------------------------------------
# The CLI
# ---------------------------------------------------------------------------

def test_the_cli_trains_two_steps(tmp_path, capsys):
    import train_language_model as cli

    rng = np.random.default_rng(0)
    words = [f'w{i}' for i in range(40)]
    for name, n in (('train.txt', 280), ('valid.txt', 140)):
        (tmp_path / name).write_text(' '.join(rng.choice(words, n)))
    rc = cli.main(['--arch', 'block_diffusion_moe', '--bd-size', 'tiny',
                   '--data-dir', str(tmp_path), '--epochs', '1',
                   '--bptt', '16', '--batch-size', '8',
                   '--kfac-update-freq', '2', '--no-resume',
                   '--log-dir', str(tmp_path / 'logs'),
                   '--checkpoint-dir', str(tmp_path / 'ckpt')])
    assert rc == 0
    out = capsys.readouterr().out
    loss = re.search(r"epoch 0: train \{'loss': ([0-9.]+)", out)
    assert loss and 0 < float(loss.group(1)) < 20, out


@pytest.mark.parametrize('argv,message', [
    (['--seq-parallel', '2'], 'no block-diffusion mask'),
    (['--bptt', '18'], 'whole number of --bd-block'),
])
def test_the_cli_refuses_what_the_objective_cannot_run(argv, message):
    import train_language_model as cli

    with pytest.raises(SystemExit, match=message):
        cli.main(['--arch', 'block_diffusion_moe', *argv])


# ---------------------------------------------------------------------------
# The windowed decoder's programs
# ---------------------------------------------------------------------------

def window_programs() -> dict:
    """sha256 of the jaxpr of the loss and its gradient of the windowed
    decoder at toy size, with object addresses blanked: as it is (the
    plain path) and at head dim 64 with Pallas's interpreter forced
    (the splash route). The fixture was made on the commit before the
    block-diffusion mask came."""
    ids = jnp.zeros((2, 256), jnp.int32)
    out = {}
    for name, kw, interpret in (('plain', {}, False),
                                ('splash', {'head_dim': 64}, True)):
        model = window_moe_lm.get_model(64, 'tiny', **kw)
        params = model.init(jax.random.PRNGKey(0), ids)['params']

        def loss(p, model=model):
            y = model.apply({'params': p}, ids)
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


def test_the_windowed_decoder_traces_the_programs_it_traced_before():
    """``Attention``, the splash route and the plain path gained the
    q/k norm and the block-diffusion mask; the windowed decoder's
    jaxprs, splash route included, are digest for digest the ones the
    commit before traced."""
    with open(os.path.join(FIXTURES, 'window_decoder_jaxprs.json')) as f:
        golden = json.load(f)
    if golden['made_with'] != jax.__version__:
        pytest.skip(f'digests are jax {golden["made_with"]}\'s')
    tracing.clear_trace()
    assert window_programs() == golden['digests']
    assert DIFFUSION not in tracing.counters()
