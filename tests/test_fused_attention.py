"""PR 26: the fused attention path of ``local_causal_attention``.

On the CPU the gate is closed, so every other test sees the plain path;
here ``pltpu.force_tpu_interpret_mode()`` opens it and runs the shipped
flash-attention kernels (forward, dK/dV, dQ) on Pallas's TPU
interpreter at toy shapes. Pinned:

- **parity**: output and dq/dk/dv of the fused path against
  ``plain_attention``, causal and bidirectional, bfloat16 and float32;
- **the gate**: T = 197, a ``kvalid``, a head dim the kernel does not
  take, operands of two dtypes, a T of one 128-tile and the CPU by
  default each take the plain path with nothing recorded;
  ``KFAC_PALLAS_FALLBACK=1`` takes it with one ``pallas_fallback``
  event;
- **K-FAC capture passes through the custom_vjp**: a toy
  ``TransformerLM`` gives the same loss, gradients and captured
  ``a``/``g`` on either path, and the counters and a built variant's
  span say how many traced calls took which (a capturing pass traces
  the model twice: the probes' shape pass, then the differentiated one).
"""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.experimental.pallas import tpu as pltpu

from distributed_kfac_pytorch_tpu import KFAC, CommMethod
from distributed_kfac_pytorch_tpu.models import transformer_lm
from distributed_kfac_pytorch_tpu.observability import tracing
from distributed_kfac_pytorch_tpu.ops import pallas_kernels
from distributed_kfac_pytorch_tpu.parallel import distributed as D
from distributed_kfac_pytorch_tpu.parallel import sequence

FUSED, PLAIN = 'kfac/attention/fused', 'kfac/attention/plain'


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    monkeypatch.delenv('KFAC_PALLAS_FALLBACK', raising=False)
    pallas_kernels._fused_attention_probed.cache_clear()
    pallas_kernels.drain_pallas_events()
    tracing.clear_trace()
    yield
    pallas_kernels._fused_attention_probed.cache_clear()
    pallas_kernels.drain_pallas_events()
    tracing.clear_trace()


def _qkv(shape=(2, 256, 2, 64), dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=shape), dtype)
                 for _ in range(3))


def _out_and_grads(attend, qkv, causal):
    w = jnp.asarray(np.random.default_rng(7).normal(size=qkv[0].shape),
                    jnp.float32)

    def loss(q, k, v):
        out = attend(q, k, v, causal=causal)
        return jnp.sum(out * w), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, (0, 1, 2), has_aux=True))(*qkv)
    return out, grads


@pytest.mark.parametrize('causal', [True, False],
                         ids=['causal', 'bidirectional'])
@pytest.mark.parametrize('dtype,tol', [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=['float32', 'bfloat16'])
def test_fused_matches_plain(causal, dtype, tol):
    qkv = _qkv(dtype=dtype)
    with pltpu.force_tpu_interpret_mode():
        out, grads = _out_and_grads(sequence.local_causal_attention, qkv,
                                    causal)
    assert tracing.counters() == {FUSED: 1}
    ref_out, ref_grads = _out_and_grads(sequence.plain_attention, qkv,
                                        causal)
    assert out.dtype == ref_out.dtype == jnp.float32
    assert pallas_kernels.max_rel_error(out, ref_out) < tol
    for got, ref in zip(grads, ref_grads):
        assert got.dtype == ref.dtype == dtype
        assert pallas_kernels.max_rel_error(got, ref) < tol


def _takes_plain(q, k, v, **kw):
    """The call's path by its counter, and that it equals the plain
    path's result bit for bit (it is the same code)."""
    out = sequence.local_causal_attention(q, k, v, **kw)
    assert tracing.counters() == {PLAIN: 1}
    np.testing.assert_array_equal(
        out, sequence.plain_attention(q, k, v, **kw))
    assert pallas_kernels.drain_pallas_events() == []


@pytest.mark.parametrize('case', ['vit_t197', 'kvalid', 'head_dim_32',
                                  'two_dtypes', 'one_128_tile'])
def test_the_gate_is_closed_by_shape(case):
    q, k, v = _qkv({'vit_t197': (1, 197, 2, 64),
                    'head_dim_32': (1, 256, 2, 32),
                    'one_128_tile': (1, 128, 2, 64)}.get(
                        case, (1, 256, 2, 64)))
    kw = {}
    if case == 'kvalid':
        kw['kvalid'] = jnp.arange(256) < 200
    if case == 'two_dtypes':
        k = k.astype(jnp.bfloat16)
    with pltpu.force_tpu_interpret_mode():
        _takes_plain(q, k, v, **kw)


def test_the_gate_is_closed_on_the_cpu_by_default():
    _takes_plain(*_qkv())


def test_the_kill_switch_takes_the_plain_path_and_says_so(monkeypatch):
    monkeypatch.setenv('KFAC_PALLAS_FALLBACK', '1')
    q, k, v = _qkv()
    with pltpu.force_tpu_interpret_mode():
        with pytest.warns(RuntimeWarning, match='falling back'):
            out = sequence.local_causal_attention(q, k, v)
            sequence.local_causal_attention(q, k, v)
    assert tracing.counters() == {PLAIN: 2}
    np.testing.assert_array_equal(out, sequence.plain_attention(q, k, v))
    events = pallas_kernels.drain_pallas_events()   # once a process
    assert [(e['event'], e['kernel']) for e in events] == [
        ('pallas_fallback', 'attention')]
    assert 'KFAC_PALLAS_FALLBACK' in events[0]['reason']


def test_kvalid_masks_padding_keys():
    q, k, v = _qkv((1, 8, 1, 4))
    out = sequence.local_causal_attention(q, k, v, causal=False,
                                          kvalid=jnp.arange(8) < 5)
    ref = sequence.plain_attention(q[:, :, :, :], k[:, :5], v[:, :5],
                                   causal=False)
    np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize('t,tile,rows', [(2048, 512, 1024),
                                         (1024, 512, 1024),
                                         (512, 512, 512),
                                         (768, 256, 256),
                                         (256, 256, 256)])
def test_tiles_follow_the_measured_table(t, tile, rows):
    sizes = pallas_kernels._attention_block_sizes(t)
    assert {sizes.block_q, sizes.block_k_major, sizes.block_k,
            sizes.block_k_major_dkv, sizes.block_k_dkv,
            sizes.block_k_major_dq, sizes.block_k_dq} == {tile}
    assert {sizes.block_q_major_dkv, sizes.block_q_dkv,
            sizes.block_q_dq} == {rows}
    assert sizes.has_backward_blocks


# -- through the model and K-FAC --------------------------------------------

VOCAB, LAYERS, SEQ = 64, 2, 256
CALLS = 2 * LAYERS      # zero_probes' eval_shape pass + value_and_grad


def _lm():
    return transformer_lm.TransformerLM(
        vocab_size=VOCAB, d_model=128, num_layers=LAYERS, num_heads=2,
        max_len=SEQ, dropout=0.0, tie_weights=True)


def _tokens(b=2):
    x = jax.random.randint(jax.random.PRNGKey(1), (b, SEQ), 0, VOCAB)
    y = jax.random.randint(jax.random.PRNGKey(2), (b, SEQ), 0, VOCAB)
    return x, y


def _capture(kfac, params, x, y):
    def loss_of(out):
        return optax.softmax_cross_entropy_with_integer_labels(
            out, y).mean()

    loss, _, grads, caps, _ = jax.jit(
        lambda p: kfac.capture.loss_and_grads(loss_of, p, x,
                                              train=False))(params)
    return loss, grads, caps


def test_kfac_capture_is_the_same_on_either_path():
    x, y = _tokens()
    kfac = KFAC(_lm(), factor_update_freq=1, inv_update_freq=1,
                damping=0.003, lr=0.1)
    params = kfac.init(jax.random.PRNGKey(0), x, train=False)[0]['params']
    tracing.clear_trace()
    with pltpu.force_tpu_interpret_mode():
        fused = _capture(kfac, params, x, y)
    assert tracing.counters() == {FUSED: CALLS}
    tracing.clear_trace()
    plain = _capture(kfac, params, x, y)
    assert tracing.counters() == {PLAIN: CALLS}
    assert set(fused[2]) == set(plain[2]) and 'block0/attn/out_proj' in {
        '/'.join(k) if isinstance(k, tuple) else k for k in fused[2]}
    np.testing.assert_allclose(fused[0], plain[0], rtol=1e-6)
    for got, ref in zip(jax.tree.leaves(fused[1:]),
                        jax.tree.leaves(plain[1:])):
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-6)


@pytest.mark.parametrize('path', ['fused', 'plain'])
def test_forward_and_backward_carry_the_attention_scope(path):
    """``attention_ms`` reads the operations whose ``op_name`` holds
    ``kfac_model/attention``; the backward's are its transpose."""
    x, y = _tokens()
    model = _lm()
    params = model.init(jax.random.PRNGKey(0), x, train=False)['params']

    def loss(p):
        out = model.apply({'params': p}, x, train=False)
        return optax.softmax_cross_entropy_with_integer_labels(
            out, y).mean()

    tracing.clear_trace()
    with (pltpu.force_tpu_interpret_mode() if path == 'fused'
          else contextlib.nullcontext()):
        text = jax.jit(jax.grad(loss)).lower(params).compile().as_text()
    assert set(tracing.counters()) == {f'kfac/attention/{path}'}
    names = [n for n in re.findall(r'op_name="([^"]*)"', text)
             if 'kfac_model/attention' in n]
    forward = [n for n in names if 'transpose(' not in n]
    backward = [n for n in names
                if 'transpose(jvp(' in n.replace('TransformerLM/', '')]
    assert forward and backward
    for block in range(LAYERS):
        assert any(f'block{block}/attn/kfac_model/attention' in n
                   for n in forward + backward)


def test_a_built_variant_says_which_path_its_attention_calls_took():
    x, y = _tokens()
    kfac = KFAC(_lm(), factor_update_freq=1, inv_update_freq=1,
                damping=0.003, lr=0.1)
    params = kfac.init(jax.random.PRNGKey(0), x, train=False)[0]['params']
    mesh = D.make_kfac_mesh(jax.devices()[:1],
                            comm_method=CommMethod.COMM_OPT)
    dkfac = D.DistributedKFAC(kfac, mesh, params)
    tx = optax.sgd(0.05)
    step = dkfac.build_train_step(
        lambda out, b: optax.softmax_cross_entropy_with_integer_labels(
            out, b[1]).mean(), tx, donate=False,
        model_kwargs_fn=lambda b: {'train': False})
    hyper = {'lr': 0.05, 'damping': 0.003, 'factor_update_freq': 1,
             'inv_update_freq': 1}
    args = (params, tx.init(params), dkfac.init_state(params), {},
            (x, y), hyper)
    with pltpu.force_tpu_interpret_mode():
        out = step(*args, factor_update=True, inv_update=True)
    assert np.isfinite(float(out[-1]['loss']))
    build, = [s for s in tracing.spans()
              if s.name.startswith(D.BUILD_SPAN_PREFIX)]
    assert (build.attrs['attention_fused'],
            build.attrs['attention_plain']) == (CALLS, 0)
    event, = [e for e in step.compile_events if e['event'] == 'compile']
    assert (event['attention_fused'], event['attention_plain']) == (
        CALLS, 0)
    assert not any(e['event'] == 'pallas_fallback'
                   for e in step.compile_events)
