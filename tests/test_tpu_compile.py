"""Compiles for a described TPU v5e, no chip attached: what only the
TPU's own compiler can say. Nothing runs and nothing is timed.

The topology is described inside a fixture, never at import (one
process at a time may load the TPU's library; every xdist worker
imports every test file), and all such tests live in this one file.
"""

import os
import re
import types

import jax
import jax.numpy as jnp
import pytest

from distributed_kfac_pytorch_tpu.observability import memory as obs_memory
from distributed_kfac_pytorch_tpu.ops import factors
from distributed_kfac_pytorch_tpu.ops import pallas_kernels
from distributed_kfac_pytorch_tpu.parallel import distributed as D


@pytest.fixture(scope='module')
def one_chip():
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')
    return SingleDeviceSharding(topo.devices[0])


CALLS = 8


def _compiled(fn, options, *shapes):
    return jax.jit(fn, compiler_options=options).lower(*shapes).compile()


def _code_bytes(fn, options, *shapes):
    return _compiled(fn, options, *shapes).memory_analysis(
    ).generated_code_size_in_bytes


def _multi_call_loss(w, x):
    """One layer applied CALLS times."""
    for _ in range(CALLS):
        x = jax.nn.gelu(x @ w) + x
        x = x * jax.lax.rsqrt(jnp.mean(
            x.astype(jnp.float32) ** 2, -1, keepdims=True) + 1e-6
        ).astype(x.dtype)
    return jnp.mean(x.astype(jnp.float32) ** 2)


def _multi_call_options():
    specs = {'shared': types.SimpleNamespace(num_calls=CALLS)}
    return D.step_compiler_options('tpu', specs)


def _square(one_chip):
    return jax.ShapeDtypeStruct((512, 512), jnp.bfloat16, sharding=one_chip)


def test_a_multi_call_models_option_shares_equal_fusions(one_chip):
    """``step_compiler_options`` names an option the installed compiler
    takes, and a program that applies one layer eight times is less
    than half the code with it: every call's kernels are emitted once."""
    options = _multi_call_options()
    assert options
    w = x = _square(one_chip)
    plain = _code_bytes(jax.grad(_multi_call_loss), None, w, x)
    shared = _code_bytes(jax.grad(_multi_call_loss), options, w, x)
    assert shared < 0.5 * plain


def test_program_footprint_reads_a_tpu_programs_code(one_chip):
    """What ``build_train_step`` gauges for a variant
    (``observability.memory.program_footprint``) counts the loaded code
    on a TPU, where the CPU's compiler reports none, and reads the
    multi-call option's saving."""
    w = x = _square(one_chip)
    plain = obs_memory.program_footprint(
        _compiled(jax.grad(_multi_call_loss), None, w, x))
    shared = obs_memory.program_footprint(
        _compiled(jax.grad(_multi_call_loss), _multi_call_options(), w, x))
    assert plain['code'] > 0 and shared['code'] > 0
    assert shared['code'] < plain['code']
    assert plain['argument'] == 2 * 512 * 512 * 2
    for footprint in (plain, shared):
        assert footprint['total'] == (
            footprint['argument'] + footprint['output']
            - footprint['alias'] + footprint['temp'] + footprint['code'])


@pytest.mark.parametrize('window', [1024, None], ids=['sliding', 'full'])
def test_the_splash_route_compiles_at_mellum2s_widths(one_chip, window):
    """The windowed/grouped attention route at the cell's shapes (4096
    positions, 32 q heads over 4 kv heads of 128, bf16), forward and
    backward: Mosaic takes its three kernels, each carries the caller's
    ``jax.named_scope`` in its ``op_name`` (what a device trace ties it
    to a scope by), and nothing of (T, T) size is left to XLA's
    temporaries."""
    q = jax.ShapeDtypeStruct((1, 4096, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4096, 4, 128), jnp.bfloat16,
                              sharding=one_chip)

    def loss(*qkv):
        with jax.named_scope('kfac_model/attention/sliding'):
            out = pallas_kernels.splash_attention(*qkv, window=window)
        return jnp.sum(out ** 2)

    compiled = _compiled(jax.value_and_grad(loss, (0, 1, 2)), None, q, kv,
                         kv)
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 3
    for line in calls:
        assert re.search(r'op_name="[^"]*kfac_model/attention/sliding',
                         line), line[:200]
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


@pytest.mark.parametrize('t,block', [(4096, 4), (4096, 16), (3072, 3)],
                         ids=['shifted-4', 'shifted-16', 'divided-3'])
def test_the_block_diffusion_route_compiles_at_sdars_widths(one_chip, t,
                                                            block):
    """The splash route under the block-diffusion mask at the cell's
    heads (32 q over 4 kv heads of 128, bf16), forward and backward:
    Mosaic takes the division-free rule at blocks of 4 and 16 and the
    ``//`` form at a block that is not a power of two, in all three
    kernels, and nothing of (T, T) size is left to XLA's temporaries."""
    assert pallas_kernels.block_diffusion_route(block) == (
        'divided' if block == 3 else 'shifted')
    q = jax.ShapeDtypeStruct((1, t, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, t, 4, 128), jnp.bfloat16,
                              sharding=one_chip)

    def loss(*qkv):
        out = pallas_kernels.splash_attention(*qkv, diffusion_block=block)
        return jnp.sum(out ** 2)

    compiled = _compiled(jax.value_and_grad(loss, (0, 1, 2)), None, q, kv,
                         kv)
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 3
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


@pytest.mark.parametrize('rows,d,f', [(32768, 2304, 896), (49152, 2048, 768)],
                         ids=['mellum2', 'kanana2'])
@pytest.mark.parametrize('matrix', ['gate', 'down'])
def test_the_grouped_expert_kernels_compile_at_the_moe_cells_widths(
        one_chip, rows, d, f, matrix):
    """A stacked-expert product at a MoE cell's buffer and widths (8
    experts held, bf16), forward, input gradient and weight gradient:
    Mosaic takes the three grouped kernels at the tiles
    ``grouped_tiling`` picks, and each carries the caller's
    ``jax.named_scope`` in its ``op_name`` (``moe_experts_ms`` reads them
    by it)."""
    k, n = (d, f) if matrix == 'gate' else (f, d)
    x = jax.ShapeDtypeStruct((rows, k), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((8, k, n), jnp.bfloat16, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)

    def loss(x, w, sizes):
        with jax.named_scope('kfac_model/moe/experts'):
            y = pallas_kernels.grouped_matmul(x, w, sizes)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    compiled = _compiled(jax.value_and_grad(loss, (0, 1)), None, x, w,
                         sizes)
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 3
    for line in calls:
        assert re.search(r'op_name="[^"]*kfac_model/moe/experts',
                         line), line[:200]


@pytest.mark.parametrize('rows,d', [(32768, 2304), (32768, 896),
                                    (49152, 2048), (49152, 768)],
                         ids=['mellum2_d', 'mellum2_f', 'kanana2_d',
                              'kanana2_f'])
def test_the_experts_covariance_compiles_at_the_moe_cells_widths(
        one_chip, monkeypatch, rows, d):
    """A per-expert statistic at a MoE cell's buffer and width (8
    experts held, bf16 rows), the gate held open as a TPU backend would
    open it: Mosaic takes ``tgmm`` at the float32 output's tiles, the
    kernel carries the statistic's scope in its ``op_name``
    (``kfac_expert_factors_ms`` reads it by ``kfac/factors/experts_``),
    and neither a ``ragged-dot`` nor a transposed copy of the stack (the
    symmetrization the kernel's symmetric blocks do not need) is left."""
    monkeypatch.setattr(pallas_kernels, 'experts_cov_applies',
                        lambda x: True)
    x = jax.ShapeDtypeStruct((rows, d), jnp.bfloat16, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)
    compiled = _compiled(lambda x, sizes: factors.experts_a_factor(
        x, sizes, 8), None, x, sizes)
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1
    assert re.search(r'op_name="[^"]*kfac/factors/experts_a', calls[0]), \
        calls[0][:200]
    assert 'ragged' not in text
    assert not re.search(r'f32\[8,%d,%d\]\{1,2,0' % (d, d), text)
