"""Ring-attention sequence parallelism and Transformer LM tests.

The reference has no long-context machinery (SURVEY.md §5); these tests
pin the new capability: ring attention over the 8-device CPU mesh must be
*exact* (same math as single-device attention, only blockwise), and the
Transformer LM must register all its projection Denses with K-FAC.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from distributed_kfac_pytorch_tpu.parallel import sequence as seq
from distributed_kfac_pytorch_tpu.models import transformer_lm


def _qkv(rng, b, t, h, d):
    return (jnp.asarray(rng.randn(b, t, h, d), jnp.float32),
            jnp.asarray(rng.randn(b, t, h, d), jnp.float32),
            jnp.asarray(rng.randn(b, t, h, d), jnp.float32))


@pytest.mark.parametrize('causal', [True, False])
@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16])
def test_ring_attention_matches_local(causal, dtype):
    """Ring == local at BOTH operand dtypes: each logit is one q.k dot
    product of the same operand rows in either path (blocking does not
    change a dot product), so the bf16-operand MXU contract preserves
    mutual exactness — only fold-order fp32 rounding differs."""
    rng = np.random.RandomState(0)
    b, t, h, d = 2, 32, 2, 8       # t sharded 8-way -> 4 tokens/device
    q, k, v = (x.astype(dtype) for x in _qkv(rng, b, t, h, d))
    ref = seq.local_causal_attention(q, k, v, causal=causal)

    mesh = Mesh(np.asarray(jax.devices()), (seq.SEQ_AXIS,))
    ring = jax.jit(jax.shard_map(
        lambda q, k, v: seq.ring_self_attention(q, k, v, causal=causal),
        mesh=mesh,
        in_specs=(P(None, seq.SEQ_AXIS), P(None, seq.SEQ_AXIS),
                  P(None, seq.SEQ_AXIS)),
        out_specs=P(None, seq.SEQ_AXIS), check_vma=False))(q, k, v)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_local_attention_is_softmax_attention():
    """Oracle: plain softmax attention computed directly."""
    rng = np.random.RandomState(1)
    b, t, h, d = 1, 8, 1, 4
    q, k, v = _qkv(rng, b, t, h, d)
    logits = np.einsum('bqhd,bkhd->bhqk', q, k) / np.sqrt(d)
    mask = np.tril(np.ones((t, t), bool))
    logits = np.where(mask[None, None], logits, -np.inf)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ref = np.einsum('bhqk,bkhd->bqhd', p, v)
    out = seq.local_causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize('causal', [True, False])
@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16])
def test_ring_bench_schedule_matches_monolithic(causal, dtype):
    """Pin the perf bench's per-device emulation to the real algorithm:
    ``ring_device_schedule`` at device ``i`` must equal rows
    ``[i*T_local, (i+1)*T_local)`` of monolithic attention — so the
    bench's on-chip numbers time the exact compute one
    ring device performs, not an approximation of it."""
    from benchmarks.ring_attention_bench import ring_device_schedule

    rng = np.random.RandomState(3)
    b, t, h, d, s = 2, 32, 2, 8, 4
    q, k, v = (x.astype(dtype) for x in _qkv(rng, b, t, h, d))
    ref = np.asarray(seq.local_causal_attention(q, k, v, causal=causal))
    t_local = t // s
    k_stack = jnp.stack([k[:, i * t_local:(i + 1) * t_local]
                         for i in range(s)])
    v_stack = jnp.stack([v[:, i * t_local:(i + 1) * t_local]
                         for i in range(s)])
    for idx in range(s):
        out = ring_device_schedule(
            q[:, idx * t_local:(idx + 1) * t_local], k_stack, v_stack,
            device_idx=idx, ring_size=s, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out),
            ref[:, idx * t_local:(idx + 1) * t_local],
            rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize('causal', [True, False])
@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16])
def test_chunked_attention_matches_local(causal, dtype):
    """Chunked (memory-efficient) attention is exact: same fold code as
    the ring, only scanned within one device."""
    rng = np.random.RandomState(4)
    b, t, h, d = 2, 32, 2, 8
    q, k, v = (x.astype(dtype) for x in _qkv(rng, b, t, h, d))
    ref = seq.local_causal_attention(q, k, v, causal=causal)
    # Blocks 5 and 7 don't divide t=32: the fold pads to a block
    # multiple with masked keys and slices pad queries off — exact at
    # any length (a ViT's num_patches + 1 cls token is the product
    # case, models/vit.py).
    for block in (4, 5, 7, 16, 32):
        out = seq.chunked_causal_attention(q, k, v, block_size=block,
                                           causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
    # block >= t degenerates to exact monolithic attention (short-seq
    # eval / factor-shaping passes under a long-context config).
    out = seq.chunked_causal_attention(q, k, v, block_size=4 * t,
                                       causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_chunked_attention_gradients_match_local():
    """The checkpointed scan backward equals monolithic attention's
    gradients — the training path, not just inference."""
    rng = np.random.RandomState(5)
    b, t, h, d = 2, 16, 2, 4
    q, k, v = _qkv(rng, b, t, h, d)
    w = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)  # loss weights

    def loss(attn):
        def f(q, k, v):
            return jnp.sum(attn(q, k, v) * w)
        return f

    ref_grads = jax.grad(loss(seq.local_causal_attention),
                         argnums=(0, 1, 2))(q, k, v)
    for block in (4, 5):            # 5: the ragged masked-padding path
        chk_grads = jax.grad(
            loss(lambda q, k, v: seq.chunked_causal_attention(
                q, k, v, block_size=block)), argnums=(0, 1, 2))(q, k, v)
        for g_ref, g_chk in zip(ref_grads, chk_grads):
            np.testing.assert_allclose(np.asarray(g_chk),
                                       np.asarray(g_ref),
                                       rtol=1e-4, atol=1e-5)


def test_transformer_lm_chunked_attention_same_logits():
    """attn_block_size is a pure memory/layout knob: same params, same
    logits as the monolithic path."""
    kw = dict(vocab_size=61, size='tiny', max_len=16, dropout=0.0)
    mono = transformer_lm.get_model(**kw)
    chunked = transformer_lm.get_model(attn_block_size=4, **kw)
    ids = jnp.asarray(np.random.RandomState(6).randint(0, 61, (2, 16)),
                      jnp.int32)
    variables = mono.init(jax.random.PRNGKey(0), ids, train=False)
    ref = mono.apply(variables, ids, train=False)
    out = chunked.apply(variables, ids, train=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_transformer_lm_seq_axis_excludes_attn_block():
    """Ring + chunked is a caller confusion (the ring already folds
    blockwise per device) — rejected loudly, not silently preferred."""
    model = transformer_lm.get_model(
        vocab_size=31, size='tiny', max_len=16, dropout=0.0,
        seq_axis='kfac_sp', attn_block_size=4)
    ids = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match='mutually exclusive'):
        model.init(jax.random.PRNGKey(0), ids, train=False)


def test_transformer_lm_kfac_registration():
    model = transformer_lm.get_model(vocab_size=50, size='tiny',
                                     max_len=16, dropout=0.0)
    from distributed_kfac_pytorch_tpu import KFAC
    kfac = KFAC(model, factor_update_freq=1, inv_update_freq=1,
                damping=0.01)
    ids = jnp.zeros((2, 8), jnp.int32)
    variables, state = kfac.init(jax.random.PRNGKey(0), ids, train=False)
    kinds = {name: s.kind for name, s in kfac.specs.items()}
    # 2 blocks x (q/k/v/out + mlp_in/mlp_out) Denses + the embedding.
    assert sum(1 for k in kinds.values() if k == 'linear') == 12
    assert sum(1 for k in kinds.values() if k == 'embedding') == 1
    assert any('q_proj' in n for n in kinds)
    assert any('mlp_out' in n for n in kinds)


def test_transformer_lm_kfac_step_runs_and_descends():
    model = transformer_lm.get_model(vocab_size=37, size='tiny',
                                     max_len=16, dropout=0.0,
                                     num_layers=1)
    from distributed_kfac_pytorch_tpu import KFAC
    kfac = KFAC(model, factor_update_freq=1, inv_update_freq=1,
                damping=0.01, lr=0.1)
    rng = np.random.RandomState(2)
    ids = jnp.asarray(rng.randint(0, 37, (4, 8)), jnp.int32)
    targets = jnp.asarray(rng.randint(0, 37, (4, 8)), jnp.int32)
    variables, state = kfac.init(jax.random.PRNGKey(0), ids, train=False)
    params = variables['params']
    tx = optax.sgd(0.2, momentum=0.9)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, state):
        loss, _, grads, captures, _ = kfac.capture.loss_and_grads(
            lambda out: optax.softmax_cross_entropy_with_integer_labels(
                out, targets).mean(),
            params, ids, train=False)
        precond, state = kfac.step(state, grads, captures)
        updates, opt_state = tx.update(precond, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, state, loss

    losses = []
    for _ in range(5):
        params, opt_state, state, loss = step(params, opt_state, state)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


@pytest.mark.parametrize('comm_method', ['COMM_OPT', 'MEM_OPT'])
@pytest.mark.slow
def test_distributed_kfac_train_step_with_seq_parallel(comm_method):
    """Full K-FAC train step on an (ig, gw, sp) mesh: batch sharded over
    the K-FAC axes, sequence sharded 4-way, ring attention inside."""
    from distributed_kfac_pytorch_tpu import KFAC, CommMethod
    from distributed_kfac_pytorch_tpu.parallel import distributed as D

    vocab, b, t = 23, 4, 16
    sp = 4
    t_local = t // sp
    rng = np.random.RandomState(5)
    ids = jnp.asarray(rng.randint(0, vocab, (b, t)), jnp.int32)
    targets = jnp.asarray(rng.randint(0, vocab, (b, t)), jnp.int32)

    mesh = D.make_kfac_mesh(comm_method=CommMethod[comm_method],
                            seq_parallel=sp)
    model = transformer_lm.get_model(vocab_size=vocab, size='tiny',
                                     max_len=t, dropout=0.0, num_layers=1,
                                     seq_axis=seq.SEQ_AXIS)
    kfac = KFAC(model, factor_update_freq=1, inv_update_freq=1,
                damping=0.01, lr=0.1)
    # Registration traces the structurally-identical non-ring twin (ring
    # collectives cannot trace outside the mesh).
    twin = transformer_lm.get_model(vocab_size=vocab, size='tiny',
                                    max_len=t, dropout=0.0, num_layers=1)
    variables, _ = kfac.init(jax.random.PRNGKey(0), ids, train=False,
                             init_model=twin)
    params = variables['params']

    dkfac = D.DistributedKFAC(kfac, mesh, params)
    dstate = dkfac.init_state(params)
    tx = optax.sgd(0.1, momentum=0.9)
    opt_state = tx.init(params)

    def loss_fn(out, batch):
        return optax.softmax_cross_entropy_with_integer_labels(
            out, batch[1]).mean()

    # COMM_OPT additionally exercises gradient accumulation with a
    # replicated per-step PRNG-key leaf in the batch (broadcast, not
    # sliced, across micro-batches).
    accum = 2 if comm_method == 'COMM_OPT' else 1
    data_spec = P(D.KFAC_AXES, seq.SEQ_AXIS)
    step = dkfac.build_train_step(
        loss_fn, tx,
        model_kwargs_fn=lambda batch: {
            'train': False,
            'pos_offset': jax.lax.axis_index(seq.SEQ_AXIS) * t_local},
        batch_spec=(data_spec, data_spec, P()),
        grad_accum_steps=accum,
        donate=False)

    losses = []
    hyper = {'lr': 0.1, 'damping': 0.01}
    key = jax.random.PRNGKey(0)
    for i in range(3):
        params, opt_state, dstate, _, metrics = step(
            params, opt_state, dstate, {},
            (ids, targets, jax.random.fold_in(key, i)), hyper)
        losses.append(float(metrics['loss']))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_transformer_ring_matches_single_device():
    """Full model, sequence sharded 8-way == unsharded, same params."""
    vocab, b, t = 29, 2, 16
    rng = np.random.RandomState(3)
    ids = jnp.asarray(rng.randint(0, vocab, (b, t)), jnp.int32)

    local = transformer_lm.get_model(vocab_size=vocab, size='tiny',
                                     max_len=t, dropout=0.0)
    params = local.init(jax.random.PRNGKey(0), ids, train=False)['params']
    ref = local.apply({'params': params}, ids, train=False)

    ringm = transformer_lm.get_model(vocab_size=vocab, size='tiny',
                                     max_len=t, dropout=0.0,
                                     seq_axis=seq.SEQ_AXIS)
    mesh = Mesh(np.asarray(jax.devices()), (seq.SEQ_AXIS,))
    t_local = t // 8

    def fwd(params, ids):
        off = jax.lax.axis_index(seq.SEQ_AXIS) * t_local
        return ringm.apply({'params': params}, ids, train=False,
                           pos_offset=off)

    out = jax.jit(jax.shard_map(
        fwd, mesh=mesh,
        in_specs=(P(), P(None, seq.SEQ_AXIS)),
        out_specs=P(None, seq.SEQ_AXIS), check_vma=False))(params, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=5e-4, atol=5e-4)
