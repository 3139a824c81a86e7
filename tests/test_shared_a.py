"""Layers that read one input share one A statistic and one A inverse.

Registration (``capture.py``) finds the layers that were handed the
same traced value and records the first of them as the owner of the
others' A (``LayerSpec.a_owner``). The owner's A is contracted once and
inverted once; each follower keeps its own factor slot (the running
average of the same statistic, so the same bits) and reads the owner's
inverse. Held here at toy size on the CPU: who owns what in the two toy
decoders of the benchmark, what is *not* grouped, that nothing moves for
a model in which no two layers read one input, that the followers'
slots and every preconditioned gradient are what they are with grouping
off, how many matrices a firing inverts, the placement on a mesh, and
what an old checkpoint does on load.
"""

import dataclasses
import hashlib
import json
import os
import re
import sys
import tempfile

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from distributed_kfac_pytorch_tpu.capture import (  # noqa: E402
    EXPERTS,
    KFACCapture,
)
from distributed_kfac_pytorch_tpu.elastic import reshard  # noqa: E402
from distributed_kfac_pytorch_tpu.elastic import topology  # noqa: E402
from distributed_kfac_pytorch_tpu.models import mla_moe_lm  # noqa: E402
from distributed_kfac_pytorch_tpu.observability import tracing  # noqa: E402
from distributed_kfac_pytorch_tpu.parallel import distributed as D  # noqa: E402
from distributed_kfac_pytorch_tpu.preconditioner import (  # noqa: E402
    A_SIDE_KEYS,
    KFAC,
    CommMethod,
)
from kfac_bench.families import lm as lm_family  # noqa: E402
from kfac_bench.families import mla_moe_lm as moe_family  # noqa: E402

SEED = 3000000019
FIXTURES = os.path.join(ROOT, 'tests', 'fixtures')


def _bench_json(kind, name):
    with open(os.path.join(ROOT, 'kfac_bench', kind, f'{name}.json')) as f:
        return json.load(f)


def _groups(kfac) -> dict[str, list[str]]:
    """``{owner: [followers]}``, in registration order."""
    out: dict[str, list[str]] = {}
    for name, owner in kfac.a_followers().items():
        out.setdefault(owner, []).append(name)
    return out


# ---------------------------------------------------------------------------
# Who owns what
# ---------------------------------------------------------------------------

@pytest.fixture(scope='module')
def toy_lm_cell():
    return lm_family.build(_bench_json('configs', 'toy-lm'),
                           _bench_json('traffic', 'toy_seq32_b4_f1i4'),
                           SEED, jax.device_count(), tempfile.mkdtemp())


@pytest.fixture(scope='module')
def toy_moe_cell():
    return moe_family.build(_bench_json('configs', 'toy-mla-moe'),
                            _bench_json('traffic', 'toy_seq32_b4_f1i4'),
                            SEED, jax.device_count(), tempfile.mkdtemp())


def test_toy_lm_q_k_v_are_one_group_a_layer(toy_lm_cell):
    kfac = toy_lm_cell.dkfac.kfac
    assert _groups(kfac) == {
        f'block{i}/attn/q_proj': [f'block{i}/attn/k_proj',
                                  f'block{i}/attn/v_proj']
        for i in range(2)}
    # out_proj, the MLP and the tied embedding read values of their own.
    owners = [n for n, s in kfac.specs.items() if s.a_owner is None]
    assert len(owners) == len(kfac.specs) - 4
    assert kfac.specs['embed'].a_owner is None


def _moe_groups(dtype) -> dict[str, list[str]]:
    model = mla_moe_lm.get_model(64, 'tiny', num_layers=3, dtype=dtype)
    kfac = KFAC(model, skip_layers=['head'], inverse_method='cholesky')
    kfac.init(jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
    return _groups(kfac)


def _expected_moe_groups(router_joins: bool) -> dict[str, list[str]]:
    want = {'layer0/mlp/gate_proj': ['layer0/mlp/up_proj']}
    for i in range(3):
        want[f'layer{i}/self_attn/q_proj'] = [
            f'layer{i}/self_attn/kv_a_proj_with_mqa']
    for i in (1, 2):
        mlp = f'layer{i}/mlp'
        want[f'{mlp}/experts/gate_proj'] = [f'{mlp}/experts/up_proj']
        if router_joins:
            want[f'{mlp}/router'] = [f'{mlp}/shared_experts/gate_proj',
                                     f'{mlp}/shared_experts/up_proj']
        else:
            want[f'{mlp}/shared_experts/gate_proj'] = [
                f'{mlp}/shared_experts/up_proj']
    return want


def test_toy_mla_moe_in_bfloat16_the_router_is_alone():
    """As the cell runs it: q + kv_a, the dense layer's gate + up, the
    shared experts' gate + up, the expert stacks' gate + up. The router
    reads ``h.astype(float32)``, a value of its own."""
    assert _moe_groups(jnp.bfloat16) == _expected_moe_groups(False)


def test_toy_mla_moe_in_float32_the_routers_cast_is_no_cast(toy_moe_cell):
    """The toy configuration computes in float32, where
    ``h.astype(float32)`` hands back ``h`` itself: the router then reads
    the very value the shared experts read, and owns their A (it is
    called first). Through the benchmark's own builder."""
    assert _groups(toy_moe_cell.dkfac.kfac) == _expected_moe_groups(True)
    assert _moe_groups(jnp.float32) == _expected_moe_groups(True)


def test_expert_stacks_share_only_with_the_same_group_sizes():
    class TwoRoutings(nn.Module):
        @nn.compact
        def __call__(self, x):
            from distributed_kfac_pytorch_tpu.modules.experts import (
                ExpertsDense,
            )
            sizes = jnp.asarray([3, 5], jnp.int32)
            other = jnp.asarray([3, 5], jnp.int32)
            a = ExpertsDense(2, 4, name='a')(x, sizes)
            b = ExpertsDense(2, 4, name='b')(x, sizes)
            c = ExpertsDense(2, 4, name='c')(x, other)
            return a + b + c

    kfac = KFAC(TwoRoutings())
    kfac.init(jax.random.PRNGKey(0), jnp.ones((8, 6)))
    assert kfac.specs['a'].kind == EXPERTS
    assert kfac.a_followers() == {'b': 'a'}


class Readers(nn.Module):
    """Three Denses on ``x`` itself, then one each on a copy, a cast
    and a biased twin."""
    @nn.compact
    def __call__(self, x):
        dense = lambda name, **kw: nn.Dense(5, name=name, **kw)  # noqa: E731
        return (dense('first')(x) + dense('second')(x)
                + dense('copy')(x + 0) + dense('array')(jnp.array(x))
                + dense('cast')(x.astype(jnp.bfloat16)).astype(x.dtype)
                + dense('no_bias', use_bias=False)(x)
                + dense('third')(x))


@pytest.mark.parametrize('name', ['copy', 'array', 'cast', 'no_bias'])
def test_a_copy_a_cast_or_another_statistic_is_not_grouped(name):
    kfac = KFAC(Readers())
    kfac.init(jax.random.PRNGKey(0), jnp.ones((4, 6)))
    assert kfac.a_followers() == {'second': 'first', 'third': 'first'}
    assert kfac.specs[name].a_owner is None


def test_registration_under_jit_groups_the_same_layers():
    kfac = KFAC(Readers())
    jax.eval_shape(lambda: kfac.init(jax.random.PRNGKey(0),
                                     jnp.ones((4, 6)))[0])
    assert kfac.a_followers() == {'second': 'first', 'third': 'first'}


def test_a_follower_whose_approximation_differs_leaves_the_group():
    class Shared(nn.Module):
        @nn.compact
        def __call__(self, x):
            return sum(nn.Dense(5, name=n)(x) for n in 'qkvw')

    x = jnp.ones((2, 3, 6))
    kfac = KFAC(Shared(), kfac_approx={'k': 'reduce', 'v': 'reduce'})
    kfac.init(jax.random.PRNGKey(0), x)
    # k makes another statistic of x than q, and owns v's; w stays q's.
    assert kfac.a_followers() == {'v': 'k', 'w': 'q'}
    assert KFACCapture(Shared()).init(jax.random.PRNGKey(0), x)[1][
        'v'].a_owner == 'q'


class Plain(nn.Module):
    """No two layers read one input (the conv pair reads one value
    with two geometries: two statistics)."""
    @nn.compact
    def __call__(self, x):
        y = nn.Conv(4, (3, 3), name='conv3')(x) + nn.Conv(
            4, (1, 1), name='conv1')(x)
        y = nn.relu(y).reshape(y.shape[0], -1)
        y = nn.relu(nn.Dense(12, name='fc1')(y))
        return nn.Dense(5, name='fc2')(y)


def _plain_setup(**kw):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 4, 4, 3)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 5, 8).astype(np.int32))
    kfac = KFAC(Plain(), factor_update_freq=1, inv_update_freq=2, **kw)
    variables, state = kfac.init(jax.random.PRNGKey(0), x)
    return kfac, variables['params'], state, x, y


def _xent(out, batch):
    return optax.softmax_cross_entropy_with_integer_labels(
        out, batch[1]).mean()


def _jaxpr_digest(fn, *args) -> str:
    """sha256 of a traced function's jaxpr text with object addresses
    blanked and each ``frozenset``'s members sorted (their order follows
    the process's hash seed; PERF.md, PR 29: the method of a "nothing
    moved" claim)."""
    text = re.sub(r'0x[0-9a-f]+', '0x', str(jax.make_jaxpr(fn)(*args)))
    text = re.sub(
        r'frozenset\(\{([^}]*)\}\)',
        lambda m: 'frozenset({%s})' % ', '.join(
            sorted(x.strip() for x in m.group(1).split(','))), text)
    return hashlib.sha256(text.encode()).hexdigest()


def _plain_programs() -> dict[str, str]:
    """Digests of the programs of :class:`Plain`: the single-chip step
    with a firing, and the distributed firing and factor variants on a
    one-device mesh, under the default dispatch and under Cholesky."""
    out = {}
    for label, kw in (('auto', {}), ('cholesky',
                                     {'inverse_method': 'cholesky'})):
        kfac, params, state, x, y = _plain_setup(**kw)

        def single(params, state):
            _, _, grads, captures, _ = kfac.capture.loss_and_grads(
                lambda out: _xent(out, (x, y)), params, x)
            return kfac.step(state, grads, captures, factor_update=True,
                             inv_update=True)

        out[f'single/{label}'] = _jaxpr_digest(single, params, state)
        mesh = D.make_kfac_mesh(jax.devices()[:1])
        dkfac = D.DistributedKFAC(kfac, mesh, params)
        tx = optax.sgd(0.1)
        step = dkfac.build_train_step(_xent, tx, donate=False)
        args = (params, tx.init(params), dkfac.init_state(params), {},
                (x, y), {'lr': 0.1, 'damping': 0.01})
        for fire in (True, False):
            out[f'mesh/{label}/inv={fire}'] = _jaxpr_digest(
                lambda *a, fire=fire: step(*a, factor_update=True,
                                           inv_update=fire), *args)
    return out


def test_a_model_that_shares_nothing_traces_the_program_it_traced_before():
    """Every layer its own owner, and the traced programs are, as jaxpr
    text, the ones the commit before this change traced (digests made
    there by this very function: ``tests/fixtures/
    shared_a_plain_jaxprs.json``, whose ``made_with`` names the jax that
    wrote them; under another jax the comparison says nothing and is
    skipped)."""
    kfac = _plain_setup()[0]
    assert kfac.a_followers() == {}
    assert all(s.a_owner is None for s in kfac.specs.values())
    with open(os.path.join(FIXTURES, 'shared_a_plain_jaxprs.json')) as f:
        golden = json.load(f)
    if golden['made_with'] != jax.__version__:
        pytest.skip(f'digests are jax {golden["made_with"]}\'s')
    assert _plain_programs() == golden['digests']


# ---------------------------------------------------------------------------
# Same slots, same gradients, fewer inverses
# ---------------------------------------------------------------------------

def _moe_setup(share: bool, **kw):
    """The toy second decoder (float32) with its K-FAC state; ``share``
    False strikes every ``a_owner`` after registration: each layer then
    contracts and inverts its own A, as before this change."""
    model = mla_moe_lm.get_model(64, 'tiny', dtype=jnp.float32)
    kfac = KFAC(model, skip_layers=['head'], factor_update_freq=1,
                inv_update_freq=2, damping=0.003, **kw)
    ids = jax.random.randint(jax.random.PRNGKey(1), (4, 17), 0, 64)
    variables, state = kfac.init(jax.random.PRNGKey(0), ids[:, :-1])
    if not share:
        kfac._specs = {n: dataclasses.replace(s, a_owner=None)
                       for n, s in kfac.specs.items()}
        state = kfac.init_state(variables['params'])
    return kfac, variables['params'], state, ids


def _single_chip_run(share: bool, steps: int = 3, **kw):
    kfac, params, state, ids = _moe_setup(share, **kw)
    batch = (ids[:, :-1], ids[:, 1:])

    @jax.jit
    def step(params, state):
        _, _, grads, captures, _ = kfac.capture.loss_and_grads(
            lambda out: _xent(out, batch), params, batch[0])
        precond, state = kfac.step(state, grads, captures)
        return jax.tree.map(lambda p, g: p - 0.1 * g, params,
                            precond), state, precond

    for _ in range(steps):
        params, state, precond = step(params, state)
    return kfac, params, state, precond


@pytest.fixture(scope='module', params=['cholesky', 'auto', 'eigen'])
def single_chip_pair(request):
    kw = {'inverse_method': request.param}
    return _single_chip_run(True, **kw), _single_chip_run(False, **kw)


def test_followers_factor_slots_are_bit_equal_to_the_owners(
        single_chip_pair):
    (kfac, _, state, _), (_, _, state_off, _) = single_chip_pair
    assert kfac.a_followers()
    for name, owner in kfac.a_followers().items():
        np.testing.assert_array_equal(state['factors'][name]['A'],
                                      state['factors'][owner]['A'])
    # ... and every slot holds what it holds with grouping off.
    assert jax.tree.structure(state['factors']) == jax.tree.structure(
        state_off['factors'])
    jax.tree.map(np.testing.assert_array_equal, state['factors'],
                 state_off['factors'])


def test_followers_hold_no_a_inverse(single_chip_pair):
    (kfac, _, state, _), (_, _, state_off, _) = single_chip_pair
    for name in kfac.specs:
        held = set(state['inverses'][name])
        if name in kfac.a_followers():
            assert not held & set(A_SIDE_KEYS), (name, held)
            assert held == set(state_off['inverses'][name]) - set(
                A_SIDE_KEYS)
        else:
            assert held >= set(state_off['inverses'][name])


def test_preconditioned_gradients_equal_grouping_off(single_chip_pair):
    (_, params, _, precond), (_, params_off, _, precond_off) = (
        single_chip_pair)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-5,
                                                atol=1e-7),
        (precond, params), (precond_off, params_off))


class MixedReaders(nn.Module):
    """Three Denses on one input whose G sides fall on both sides of
    the eigen cutoff (10 here): the A they share (dim 7) is eigen, so a
    layer with a wide G is *mixed* and reads the A's baked inverse,
    beside layers that read its eigenpair."""
    widths: tuple

    @nn.compact
    def __call__(self, x):
        y = jnp.concatenate([nn.Dense(w, name=f'r{i}')(x)
                             for i, w in enumerate(self.widths)], -1)
        return nn.Dense(3, name='out')(nn.relu(y))


@pytest.mark.parametrize('mesh', [False, True],
                         ids=['single-chip', 'one-device-mesh'])
@pytest.mark.parametrize('widths', [(5, 40, 5), (40, 5, 40)],
                         ids=['owner-eigen', 'owner-mixed'])
def test_a_group_with_mixed_and_eigen_layers_equals_grouping_off(
        widths, mesh):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(16, 6)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 3, 16).astype(np.int32))
    got = []
    for share in (True, False):
        kfac = KFAC(MixedReaders(widths), factor_update_freq=1,
                    inv_update_freq=2, damping=0.01,
                    auto_eigen_max_dim=10, eigh_method='xla')
        variables, state = kfac.init(jax.random.PRNGKey(0), x)
        params = variables['params']
        if share:
            assert kfac.a_followers() == {'r1': 'r0', 'r2': 'r0'}
            baked = 'A_inv' in state['inverses']['r0']
            assert baked and {'QA', 'dA'} <= set(state['inverses']['r0'])
        else:
            kfac._specs = {n: dataclasses.replace(s, a_owner=None)
                           for n, s in kfac.specs.items()}
            state = kfac.init_state(params)
        if mesh:
            dkfac = D.DistributedKFAC(
                kfac, D.make_kfac_mesh(jax.devices()[:1]), params)
            tx = optax.sgd(0.1)
            step = dkfac.build_train_step(_xent, tx, donate=False)
            state, opt_state, extra = (dkfac.init_state(params),
                                       tx.init(params), {})
            for _ in range(3):
                params, opt_state, state, extra, _ = step(
                    params, opt_state, state, extra, (x, y),
                    {'lr': 0.1, 'damping': 0.01})
        else:
            for _ in range(3):
                _, _, grads, captures, _ = kfac.capture.loss_and_grads(
                    lambda out: _xent(out, (x, y)), params, x)
                precond, state = kfac.step(state, grads, captures)
                params = jax.tree.map(lambda p, g: p - 0.1 * g, params,
                                      precond)
        got.append(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4,
                                                atol=1e-6), *got)


def _matrices_inverted(jaxpr) -> int:
    """Matrices the Cholesky calls of a jaxpr hold (batch dims
    multiplied out), sub-jaxprs included."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == 'cholesky':
            total += int(np.prod(eqn.invars[0].aval.shape[:-2]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            total += _matrices_inverted(sub)
    return total


def _firing_jaxpr(share: bool, mesh: bool):
    kfac, params, state, ids = _moe_setup(share,
                                          inverse_method='cholesky')
    batch = (ids[:, :-1], ids[:, 1:])
    if not mesh:
        def fire(params, state):
            _, _, grads, captures, _ = kfac.capture.loss_and_grads(
                lambda out: _xent(out, batch), params, batch[0])
            return kfac.step(state, grads, captures, factor_update=True,
                             inv_update=True)
        return kfac, jax.make_jaxpr(fire)(params, state).jaxpr
    dkfac = D.DistributedKFAC(kfac, D.make_kfac_mesh(jax.devices()[:1]),
                              params)
    tx = optax.sgd(0.1)
    step = dkfac.build_train_step(_xent, tx, donate=False)
    return kfac, jax.make_jaxpr(
        lambda *a: step(*a, factor_update=True, inv_update=True))(
            params, tx.init(params), dkfac.init_state(params), {}, batch,
            {'lr': 0.1, 'damping': 0.003}).jaxpr


@pytest.mark.parametrize('mesh', [False, True],
                         ids=['single-chip', 'one-device-mesh'])
def test_a_firing_inverts_the_parents_count_less_the_followers(mesh):
    kfac, shared = _firing_jaxpr(True, mesh)
    _, apart = _firing_jaxpr(False, mesh)
    followers = sum(max(kfac.specs[n].num_blocks, 1)
                    for n in kfac.a_followers())
    # tiny: 2 layers; q + kv_a twice, the dense gate + up, the router
    # with the shared gate and up, and 4 experts' gate + up.
    assert followers == 2 + 1 + 2 + 4
    # Every dense layer two matrices and every stack two a block, less
    # the embedding's diagonal A.
    every = sum(2 * max(s.num_blocks, 1) for s in kfac.specs.values()) - 1
    assert _matrices_inverted(apart) == every
    assert _matrices_inverted(shared) == every - followers


def test_counters_and_gauges_say_what_is_shared():
    kfac, params, state, ids = _moe_setup(True, inverse_method='cholesky')
    tracing.clear_trace()
    dkfac = D.DistributedKFAC(kfac, D.make_kfac_mesh(jax.devices()[:1]),
                              params)
    dstate = dkfac.init_state(params)
    numbers = tracing.counters()
    followers = kfac.a_followers()
    every = sum(2 * max(s.num_blocks, 1) for s in kfac.specs.values()) - 1
    assert numbers['kfac/inverses/per_firing'] == every - 9
    d = 32  # tiny's d_model: every shared A is (32, 32) float32
    assert numbers['kfac/state_bytes/shared_saved'] == 9 * d * d * 4
    # The state is smaller by just that (the factors keep every slot).
    kfac_off, params_off, _, _ = _moe_setup(False,
                                            inverse_method='cholesky')
    off = D.DistributedKFAC(
        kfac_off, D.make_kfac_mesh(jax.devices()[:1]),
        params_off).init_state(params_off)
    nbytes = lambda t: sum(x.size * x.dtype.itemsize  # noqa: E731
                           for x in jax.tree.leaves(t))
    assert nbytes(off) - nbytes(dstate) == 9 * d * d * 4
    assert jax.tree.structure(off['factors']) == jax.tree.structure(
        dstate['factors'])
    # One count a follower a traced contribution pass.
    tracing.clear_trace()
    batch = (ids[:, :-1], ids[:, 1:])
    _, _, _, captures, _ = kfac.capture.loss_and_grads(
        lambda out: _xent(out, batch), params, batch[0])
    kfac.factor_contribs(captures)
    assert tracing.counters()['kfac/factors/shared_a'] == len(followers)
    assert f'layers_sharing_an_a: {len(followers)}' in repr(kfac)


def test_chunk_items_and_bucket_slots_shrink_with_the_followers():
    kfac, params, state, _ = _moe_setup(True, inverse_method='cholesky')
    off, params_off, state_off, _ = _moe_setup(False,
                                               inverse_method='cholesky')
    items = dict(kfac.inverse_chunk_items(state['factors']))
    items_off = dict(off.inverse_chunk_items(state_off['factors']))
    dense = [n for n in kfac.a_followers()
             if kfac.specs[n].kind != EXPERTS]
    assert set(items_off) - set(items) == {('mat', n, 'A') for n in dense}
    stack = 'layer1/mlp/experts/up_proj'
    assert items[('grouped', stack)] < items_off[('grouped', stack)]
    plan = D.assign_work(kfac, params, 1, 1)
    plan_off = D.assign_work(off, params_off, 1, 1)
    # d_model 32: q, kv_a, the dense gate and up, router, shared gate and
    # up, twice q and kv_a... every A of width 32 sits in bucket 32.
    assert (plan_off.buckets[32].slots_per_col
            - plan.buckets[32].slots_per_col) == len(dense)
    for name, owner in kfac.a_followers().items():
        if name in dense:
            assert plan.buckets[32].slot[(name, 'A')] == plan.buckets[
                32].slot[(owner, 'A')]


# ---------------------------------------------------------------------------
# On a mesh
# ---------------------------------------------------------------------------

def _mesh_run(kfac, params, ids, comm_method, frac, devices, steps=3):
    mesh = D.make_kfac_mesh(devices, comm_method=comm_method,
                            grad_worker_fraction=frac)
    dkfac = D.DistributedKFAC(kfac, mesh, params)
    tx = optax.sgd(0.1)
    step = dkfac.build_train_step(_xent, tx, donate=False)
    state, opt_state, extra = dkfac.init_state(params), tx.init(params), {}
    batch = (ids[:, :-1], ids[:, 1:])
    for _ in range(steps):
        params, opt_state, state, extra, _ = step(
            params, opt_state, state, extra, batch,
            {'lr': 0.1, 'damping': 0.003})
    return dkfac, params, state


@pytest.fixture(scope='module')
def hybrid_2x4():
    """Three steps on 8 virtual devices, HYBRID 2 x 4, and on one."""
    model = mla_moe_lm.get_model(64, 'tiny', dtype=jnp.float32)
    kfac = KFAC(model, skip_layers=['head'], factor_update_freq=1,
                inv_update_freq=2, damping=0.003,
                inverse_method='cholesky',
                comm_method=CommMethod.HYBRID_OPT,
                grad_worker_fraction=0.5)
    ids = jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0, 64)
    variables, _ = kfac.init(jax.random.PRNGKey(0), ids[:, :-1])
    params = variables['params']
    many = _mesh_run(kfac, params, ids, CommMethod.HYBRID_OPT, 0.5,
                     jax.devices())
    one = _mesh_run(kfac, params, ids, CommMethod.COMM_OPT, 0.25,
                    jax.devices()[:1])
    return kfac, many, one


def test_a_group_lands_on_one_row(hybrid_2x4):
    kfac, (dkfac, _, _), _ = hybrid_2x4
    assert (dkfac.n_rows, dkfac.n_cols) == (2, 4)
    rows = dkfac.assignment.layer_row
    assert set(rows.values()) == {0, 1}
    for name, owner in kfac.a_followers().items():
        assert rows[name] == rows[owner], (name, owner)
    assert set(kfac.a_followers()) & set(
        dkfac.assignment.grouped_layers) == {'layer1/mlp/experts/up_proj'}


def test_the_mesh_agrees_with_one_device(hybrid_2x4):
    _, (_, params, state), (_, params_one, state_one) = hybrid_2x4
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=5e-4,
                                                atol=5e-6),
        (params, state['factors']), (params_one, state_one['factors']))


def test_followers_slots_stay_bit_equal_on_the_mesh(hybrid_2x4):
    kfac, (_, _, state), _ = hybrid_2x4
    for name, owner in kfac.a_followers().items():
        np.testing.assert_array_equal(state['factors'][name]['A'],
                                      state['factors'][owner]['A'])
        assert 'A_inv' not in state['grouped_inv'].get(name, {})


# ---------------------------------------------------------------------------
# Checkpoints
# ---------------------------------------------------------------------------

def test_single_chip_state_dict_round_trip(single_chip_pair):
    (kfac, params, state, _), _ = single_chip_pair
    sd = jax.tree.map(np.asarray,
                      kfac.state_dict(state, include_inverses=True))
    back = kfac.load_state_dict(sd, params)
    assert jax.tree.structure(back['inverses']) == jax.tree.structure(
        state['inverses'])
    jax.tree.map(np.testing.assert_array_equal, back['inverses'],
                 state['inverses'])
    # Factors only: the inverses are rebuilt into the same layout.
    rebuilt = kfac.load_state_dict(
        jax.tree.map(np.asarray, kfac.state_dict(state)), params)
    assert jax.tree.structure(rebuilt['inverses']) == jax.tree.structure(
        state['inverses'])


def test_single_chip_loads_a_checkpoint_that_carries_followers_inverses(
        single_chip_pair):
    """The parent's format: an A side for every layer. The followers'
    are dropped and the rest is taken as saved, not recomputed."""
    (kfac, params, state, _), (off, _, state_off, _) = single_chip_pair
    old = jax.tree.map(np.asarray,
                       off.state_dict(state_off, include_inverses=True))
    assert any(k in old['inverses'][n] for n in kfac.a_followers()
               for k in A_SIDE_KEYS)
    back = kfac.load_state_dict(old, params)
    assert jax.tree.structure(back['inverses']) == jax.tree.structure(
        state['inverses'])
    for name, entry in back['inverses'].items():
        for key, value in entry.items():
            np.testing.assert_array_equal(
                value, old['inverses'][name][key])


def test_mesh_state_dict_round_trip_and_a_parents_checkpoint(hybrid_2x4):
    kfac, (dkfac, params, state), _ = hybrid_2x4
    sd = jax.tree.map(np.asarray, dkfac.state_dict(state))
    back = dkfac.load_state_dict(sd, params)
    assert jax.tree.structure(back) == jax.tree.structure(state)
    jax.tree.map(np.testing.assert_array_equal,
                 (back['inv_stacks'], back['grouped_inv']),
                 (state['inv_stacks'], state['grouped_inv']))
    # The parent's format: every stack carries an A_inv, and the dense
    # buckets a slot a layer (other shapes). Neither is an error: the
    # stacks are rebuilt from the factors, the followers' A_inv dropped.
    follower = 'layer1/mlp/experts/up_proj'
    owner = kfac.specs[follower].a_owner
    old = {**sd, 'grouped_inv': {
        **sd['grouped_inv'],
        follower: {**sd['grouped_inv'][follower],
                   'A_inv': sd['grouped_inv'][owner]['A_inv']}}}
    kept = dkfac.load_state_dict(old, params)
    assert set(kept['grouped_inv'][follower]) == {'G_inv'}
    wider = {**old, 'inv_stacks': {
        dim: {k: np.concatenate([v, v[:1]]) for k, v in entry.items()}
        for dim, entry in old['inv_stacks'].items()}}
    rebuilt = dkfac.load_state_dict(wider, params)
    assert jax.tree.structure(rebuilt) == jax.tree.structure(state)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4,
                                                atol=1e-6),
        rebuilt['inv_stacks'], state['inv_stacks'])


def test_a_parents_bundle_from_another_mesh_is_rebuilt_not_resharded(
        hybrid_2x4):
    """Elastic resume, 2 x 4 -> one device: a bundle in today's layout
    is moved slot by slot; one with a slot a layer (the parent's) has
    its inverse groups dropped, and ``load_state_dict`` rebuilds them."""
    _, (dkfac, params, state), (one, _, _) = hybrid_2x4
    saved = topology.TopologySpec.of_mesh(
        dkfac.mesh,
        distribute_layer_factors=dkfac.distribute_layer_factors)
    sd = jax.tree.map(np.asarray, dkfac.state_dict(state))
    moved = reshard.reshard_state_dict(sd, saved, one, params)
    fresh = one.init_state(params)
    assert {d: v['inv'].shape for d, v in moved['inv_stacks'].items()} == {
        d: v['inv'].shape for d, v in fresh['inv_stacks'].items()}
    old = {**sd, 'inv_stacks': {
        dim: {k: np.concatenate([v, v[:2]]) for k, v in entry.items()}
        for dim, entry in sd['inv_stacks'].items()}}
    dropped = reshard.reshard_state_dict(old, saved, one, params)
    assert not {'inv_stacks', 'diag_inv', 'grouped_inv'} & set(dropped)
    back = one.load_state_dict(dropped, params)
    assert jax.tree.structure(back) == jax.tree.structure(fresh)
