"""The looped decoder (one stack of layers run several times a forward,
an exit after every pass) and the multi-call K-FAC path it drives, at
toy size on the CPU.

The program is held to the plain reference ``kfac_bench/references/
looped_lm.py`` (written from the equations, its own K-FAC step): the
model alone (loss and every gradient leaf) and, through the benchmark's
family, losses, gradients, every factor and the change after three
steps through one inverse firing. Beside that: what registration sees
(four calls a matrix, the shared A's, what is left to SGD), the exit
distribution, a planted fault (one pass's statistics dropped), and that
a model in which no module is called twice still traces the program it
traced before.
"""

import json
import os
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from distributed_kfac_pytorch_tpu.models import looped_lm  # noqa: E402
from distributed_kfac_pytorch_tpu.observability import tracing  # noqa: E402
from distributed_kfac_pytorch_tpu.preconditioner import KFAC  # noqa: E402
from kfac_bench import control, reference, run  # noqa: E402
from kfac_bench.families import looped_lm as family  # noqa: E402
from kfac_bench.references import looped_lm as plain  # noqa: E402

SEED = 3000000019
CHECKS = ('loss1_gap', 'loss2_gap', 'loss3_gap', 'grad1_gap',
          'grad1_median_gap', 'dparam_gap', 'dparam_median_gap',
          'factor_gap', 'factor_median_gap')
PASSES = 4                       # toy-looped's total_ut_steps


def _bench_json(kind, name):
    with open(os.path.join(ROOT, 'kfac_bench', kind, f'{name}.json')) as f:
        return json.load(f)


CONFIG = _bench_json('configs', 'toy-looped')
TRAFFIC = _bench_json('traffic', 'toy_seq32_b4_f1i4')
LIMITS = _bench_json('limits', 'toy_looped_f1i4')


@pytest.fixture(scope='module')
def toy():
    """The toy configuration's program through its first three steps
    (one inverse firing at step 0), and the reference over the same
    batches: ``(checks, counters, observed, expected, batches)``."""
    # The family spreads its batch over every device there is: on the
    # tests' 8 virtual ones the program's statistics are averaged over
    # a 1 x 8 mesh, which the reference (one batch, no mesh) never sees.
    chips = jax.device_count()
    tracing.clear_trace()
    cell = family.build(CONFIG, TRAFFIC, SEED, chips, tempfile.mkdtemp())
    gauges = dict(tracing.counters())
    got = control.first_steps(cell)
    batches = list(cell.checked)
    want = family.reference_observe(CONFIG, TRAFFIC, SEED, chips, batches)
    checks = reference.compare(got, want, LIMITS)
    return checks, {**cell.counters(), 'gauges': gauges}, got, want, batches


# ---------------------------------------------------------------------------
# The model against the reference
# ---------------------------------------------------------------------------

def test_model_loss_and_every_gradient_leaf_match_the_reference():
    sizes = plain.sizes_of(CONFIG)
    params = family.init_params(SEED, sizes)
    rng = np.random.default_rng(7)
    ids = rng.integers(0, sizes['vocab_size'], (3, 17), dtype=np.int32)
    ids, targets = ids[:, :-1], ids[:, 1:]
    model = family.build_model(CONFIG, sizes)

    def program_loss(p):
        return model.apply({'params': p}, ids, targets=targets).mean()

    got_loss, got = jax.value_and_grad(program_loss)(params)
    with jax.default_matmul_precision('highest'):
        want_loss, want, _, tokens = plain.model_step(sizes, 2)(
            params, (ids, targets))
    assert tokens == 3 * 16
    # float32 on the CPU on both sides: what differs is summation order
    # (the reference goes through its rows in blocks of 2 and 1) and
    # the exit distribution, worked in logs here and as products there:
    # a few float32 ulps of the largest entry a leaf.
    assert abs(float(got_loss) - float(want_loss)) < 2e-6 * float(want_loss)
    flat_got = reference.leaf_arrays(got)
    flat_want = reference.leaf_arrays(want)
    assert set(flat_got) == set(flat_want)
    for name, ref in flat_want.items():
        scale = np.abs(ref).max()
        assert scale > 0, name
        np.testing.assert_allclose(flat_got[name], ref, rtol=0,
                                   atol=2e-5 * scale, err_msg=name)


def test_without_targets_the_model_returns_the_last_pass_logits():
    sizes = plain.sizes_of(CONFIG)
    params = family.init_params(SEED, sizes)
    model = family.build_model(CONFIG, sizes)
    ids = jnp.arange(12, dtype=jnp.int32).reshape(2, 6)
    logits = model.apply({'params': params}, ids, train=False)
    assert logits.shape == (2, 6, sizes['vocab_size'])
    # One pass alone is a plain decoder: its objective is the exit's
    # cross entropy (p_1 = 1, H = 0).
    once = family.build_model({**CONFIG, 'total_ut_steps': 1},
                              {**sizes, 'total_ut_steps': 1})
    targets = (ids + 1) % sizes['vocab_size']
    nll = optax.softmax_cross_entropy_with_integer_labels(
        once.apply({'params': params}, ids), targets)
    np.testing.assert_allclose(
        once.apply({'params': params}, ids, targets=targets), nll,
        rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize('passes', [1, 2, 4])
def test_exit_distribution_sums_to_one(passes):
    logits = jnp.asarray(np.random.default_rng(passes).normal(
        0, 3, (passes, 5, 7)), jnp.float32)
    p, entropy = looped_lm.exit_distribution(logits)
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
    np.testing.assert_allclose(p, plain.exit_weights(logits), atol=1e-6)
    lam = jax.nn.sigmoid(logits)
    if passes > 1:
        np.testing.assert_allclose(p[0], lam[0], atol=1e-6)
        np.testing.assert_allclose(p[1], (lam[1] if passes > 2 else 1.0)
                                   * (1 - lam[0]), atol=1e-6)
    else:
        np.testing.assert_allclose(entropy, 0.0, atol=1e-7)
    assert bool(jnp.all(entropy >= 0))
    # a saturated gate gives no NaN
    p, entropy = looped_lm.exit_distribution(
        jnp.full((passes, 2), 200.0, jnp.float32))
    assert bool(jnp.all(jnp.isfinite(p)) & jnp.all(jnp.isfinite(entropy)))


def test_rope_is_the_half_split_layout():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 5, 2, 8)),
                    jnp.float32)
    pos = jnp.arange(5)
    got = looped_lm.rope_half(x, pos, 1e4)
    angle = np.arange(5)[:, None] * 1e4 ** (-np.arange(0, 8, 2) / 8)
    cos = np.concatenate([np.cos(angle)] * 2, -1)[None, :, None, :]
    sin = np.concatenate([np.sin(angle)] * 2, -1)[None, :, None, :]
    rotated = np.concatenate([-x[..., 4:], x[..., :4]], -1)  # rotate_half
    np.testing.assert_allclose(got, x * cos + rotated * sin, atol=1e-6)


# ---------------------------------------------------------------------------
# Three K-FAC steps against the reference's follow
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('name', CHECKS)
def test_program_agrees_with_the_reference_at_round_off(toy, name):
    check = toy[0][name]
    assert check['limit'] is not None
    # float32 on both sides; the worst reading is a factor leaf at
    # 1.4e-7 and a norm scale's change at 5e-7 (summation order over
    # the 8 virtual devices' shards against the reference's row blocks).
    assert check['value'] <= min(check['limit'], 5e-6), check


def test_every_factor_is_compared_the_gates_pair_among_them(toy):
    _, _, got, want, _ = toy
    assert set(got['factors']) == set(want['factors'])
    # embed + 2 layers x 7 matrices + the gate, an A and a G each
    assert len(want['factors']) == 2 * (1 + 2 * 7 + 1)
    gate_a = want['factors']["['exit/early_exit_gate']['A']"]
    assert gate_a.shape == (CONFIG['hidden_size'] + 1,
                            reference.PROBE_COLUMNS)


def test_registration_sees_four_calls_a_matrix_and_the_shared_inputs(toy):
    counters = toy[1]
    calls = counters['calls']
    assert calls.pop('embed') == 1
    assert len(calls) == 2 * 7 + 1 and set(calls.values()) == {PASSES}
    assert counters['a_followers'] == {
        f'layer{i}/{follower}': f'layer{i}/{owner}'
        for i in range(2) for follower, owner in (
            ('self_attn/k_proj', 'self_attn/q_proj'),
            ('self_attn/v_proj', 'self_attn/q_proj'),
            ('mlp/up_proj', 'mlp/gate_proj'))}
    assert counters['gauges']['kfac/capture/calls'] == PASSES * 15 + 1
    assert counters['gauges']['kfac/capture/calls_max'] == PASSES
    # one A and one G a layer, less the six A's that follow another's
    assert counters['gauges']['kfac/inverses/per_firing'] == 2 * 16 - 1 - 6


def test_approx_summary_names_the_head_and_the_norm_scales_only(toy):
    left = toy[1]['left_to_sgd']
    norms = [f'layer{i}/{n}' for i in range(2) for n in (
        'input_layernorm', 'input_layernorm_2',
        'post_attention_layernorm', 'post_attention_layernorm_2')]
    assert left == sorted(['exit/head', 'exit/norm', *norms])
    model = looped_lm.get_model(50, 'tiny')
    kfac = KFAC(model, skip_layers=['head'])
    kfac.init(jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
    summary = kfac.approx_summary(left_to_sgd=True)
    assert summary['exit/head'] == 'sgd: skip_layers match'
    assert summary['exit/norm'].startswith('sgd: ')
    assert summary['exit/early_exit_gate'] == 'expand'
    assert kfac.specs['exit/early_exit_gate'].has_bias
    assert kfac.approx_summary(shared_a=True)['layer1/mlp/up_proj'] == (
        'expand+A of layer1/mlp/gate_proj')
    assert {s.num_calls for n, s in kfac.specs.items() if n != 'embed'} \
        == {3}                   # the tiny preset runs its stack 3 times


def test_a_dropped_pass_fails_the_factor_comparison(toy):
    """The planted fault: the statistics of one pass go missing (every
    factor's sum runs over three calls of four); loss and gradients are
    whole. The comparison has to fail, by the factors."""
    _, _, got, _, batches = toy
    faulty = family.reference_observe(CONFIG, TRAFFIC, SEED,
                                      jax.device_count(), batches,
                                      dropped_pass=2)
    checks = reference.compare(got, faulty, LIMITS)
    # Reads 0.20 at the worst leaf (a factor that lost a quarter of its
    # sum); the median stored factor is a G near its identity seed and
    # sees nothing, as in the other cells.
    assert not checks['factor_gap']['ok']
    assert checks['factor_gap']['value'] > 0.1
    # The first loss is computed before any statistic is used.
    assert checks['loss1_gap']['ok']


# ---------------------------------------------------------------------------
# Through the benchmark's harness
# ---------------------------------------------------------------------------

def test_the_harness_runs_the_family_at_toy_size(tmp_path):
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        spec = json.load(f)
    spec['configs'] = [{'name': 'toy-looped', 'source': 'none',
                        'file': 'kfac_bench/configs/toy-looped.json',
                        'reduced': [], 'why': 'tests'}]
    spec['workloads'] = [{'name': 'toy_looped_f1i4', 'config': 'toy-looped',
                          'traffic': 'toy_seq32_b4_f1i4',
                          'chips': jax.device_count(), 'why': 'tests'}]
    for metric in spec['per_layer']:
        if 'ouro_d4_f1i10' in metric.get('workloads', ()):
            metric['workloads'] = ['toy_looped_f1i4']
    path = tmp_path / 'toy_looped_benchmark.json'
    path.write_text(json.dumps(spec))
    code, result = run.run_cell('toy_looped_f1i4', SEED, 0.5, False,
                                spec_path=str(path), require_chip=False)
    assert code == 0 and result['correct'] is True
    assert result['failed'] == 0 and result['attempted'] >= 4
    assert result['info']['builds_in_window'] == 0
    assert set(result['info']['trace_counts'].values()) == {1}
    assert result['metrics'] == {}       # no device metric off the chip
    assert all(c['ok'] for c in result['checks'].values())


def test_the_cells_data_files_name_what_the_harness_finds():
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        spec = json.load(f)
    cell = run.load_cell_spec(spec, 'ouro_d4_f1i10')
    assert cell['config']['family'] == 'looped_lm'
    assert cell['traffic']['per_chip_batch'] == 4
    eight = _bench_json('traffic', 'seq1024_b8_f1i10')
    assert {k: v for k, v in cell['traffic'].items()
            if k not in ('per_chip_batch', 'who')} == {
        k: v for k, v in eight.items() if k not in ('per_chip_batch', 'who')}
    for name in ('ut_blocks_ms', 'ut_exits_ms', 'kfac_capture_calls',
                 'kfac_factors_ms', 'kfac_inverses_ms', 'kfac_precond_ms',
                 'kfac_state_gib', 'attention_ms', 'step_mfu_pct'):
        assert name in cell['per_layer'], name
    sizes = plain.sizes_of(cell['config'])
    assert (sizes['hidden_size'], sizes['intermediate_size'],
            sizes['num_attention_heads'], sizes['head_dim'],
            sizes['vocab_size'], sizes['total_ut_steps'],
            sizes['num_hidden_layers']) == (2048, 5632, 16, 128, 49152,
                                            4, 4)
    # 4 passes of (4 layers + the head + the gate), forward and
    # backward: 31.75 TFLOP a step at 4 rows x 1024 (blocks 21.85, of
    # it causal attention counted whole 1.65; heads 9.90).
    flops = family.flops_per_step(sizes, 4, 1024)
    assert 31.7e12 < flops < 31.8e12


# ---------------------------------------------------------------------------
# What the multi-call path adds to the step, and to which programs
# ---------------------------------------------------------------------------

def _load_shared_a_tests():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        '_shared_a_tests', os.path.join(ROOT, 'tests', 'test_shared_a.py'))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_a_model_with_no_repeated_module_traces_the_jaxpr_it_traced_before():
    """The one thing this path adds to the step (the new factors tied
    to the gradients before the precondition, where a layer has several
    calls) is in the looped decoder's factor program and in no program
    of a model whose modules are called once: those are, digest for
    digest, ``tests/fixtures/shared_a_plain_jaxprs.json``'s (made
    before this change, by ``tests/test_shared_a.py``'s own method)."""
    shared_a = _load_shared_a_tests()
    with open(os.path.join(shared_a.FIXTURES,
                           'shared_a_plain_jaxprs.json')) as f:
        golden = json.load(f)
    if golden['made_with'] != jax.__version__:
        pytest.skip(f'digests are jax {golden["made_with"]}\'s')
    assert shared_a._plain_programs() == golden['digests']

    def factor_program(model, batch, loss_fn, kwargs_fn, **kw):
        from distributed_kfac_pytorch_tpu.parallel import distributed as D
        kfac = KFAC(model, factor_update_freq=1, inv_update_freq=2, **kw)
        params = kfac.init(jax.random.PRNGKey(0), batch[0])[0]['params']
        dkfac = D.DistributedKFAC(
            kfac, D.make_kfac_mesh(jax.devices()[:1]), params)
        tx = optax.sgd(0.1)
        step = dkfac.build_train_step(loss_fn, tx, donate=False,
                                      model_kwargs_fn=kwargs_fn)
        return str(jax.make_jaxpr(
            lambda *a: step(*a, factor_update=True, inv_update=False))(
            params, tx.init(params), dkfac.init_state(params), {}, batch,
            {'lr': 0.1, 'damping': 0.01}))

    ids = jnp.zeros((2, 9), jnp.int32)
    looped = factor_program(
        looped_lm.get_model(50, 'tiny'), (ids[:, :-1], ids[:, 1:]),
        lambda out, batch: out.mean(),
        lambda batch: {'targets': batch[1]}, skip_layers=['head'])
    assert 'optimization_barrier' in looped
    x = jnp.ones((8, 4, 4, 3), jnp.float32)
    plain_model = factor_program(
        shared_a.Plain(), (x, jnp.zeros((8,), jnp.int32)), shared_a._xent,
        None)
    assert 'optimization_barrier' not in plain_model
