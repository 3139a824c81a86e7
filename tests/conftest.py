"""Test harness: run everything on 8 virtual CPU devices.

The reference could only test its distributed logic on real multi-GPU
allocations (SURVEY.md §4); here the whole mesh path runs on a simulated
8-device CPU topology, so `pytest -q tests/` validates single-device
numerics AND multi-chip sharding with no TPU pod.
"""

import os

# pytest plugins pre-import jax, so env-var config is too late; the backend
# itself is not initialized until first use, so jax.config still works here.
# Overrides any inherited platform choice: unit tests always run on the
# virtual CPU mesh.
os.environ['JAX_PLATFORMS'] = 'cpu'
xla_flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in xla_flags:
    os.environ['XLA_FLAGS'] = (
        xla_flags + ' --xla_force_host_platform_device_count=8').strip()

# 8 device threads on a 1-core host starve past XLA's default 40 s
# collective rendezvous termination under compile load (fatal check in
# rendezvous.cc) — raise the timeouts before backend init.
from distributed_kfac_pytorch_tpu.utils import (  # noqa: E402
    raise_cpu_collective_timeouts,
)

raise_cpu_collective_timeouts()

import jax  # noqa: E402

jax.config.update('jax_platforms', 'cpu')
jax.config.update('jax_num_cpu_devices', 8)
jax.config.update('jax_enable_x64', False)

assert jax.default_backend() == 'cpu', (
    'tests must run on the virtual CPU mesh, got ' + jax.default_backend())
assert jax.device_count() == 8, (
    f'expected 8 virtual CPU devices, got {jax.device_count()}')

# The persistent compilation cache is deliberately DISABLED here —
# including any cache inherited from the environment (JAX's own
# JAX_COMPILATION_CACHE_DIR): warm cache reads segfault reproducibly on
# this multi-device CPU backend (trace-time crash inside a shard_map
# trace on the second suite run; cold runs are green both times). The
# on-chip entry points keep the cache — their warm paths are validated.
from distributed_kfac_pytorch_tpu.utils import (  # noqa: E402
    disable_compilation_cache,
)

disable_compilation_cache()


def pytest_configure(config):
    # Compile-heavy tests (the flagship ResNet-50 distributed step, the
    # 2-process multihost rendezvous, the distributed static-cadence
    # equivalence runs) carry @pytest.mark.slow. They RUN by default so
    # the plain `pytest tests/` invocation covers everything (what the
    # driver runs; ~25 min single-core); the FAST TIER for dev loops is
    # `pytest tests/ -m 'not slow'` or KFAC_SKIP_SLOW=1 (~2 min on a
    # multi-core host; the compile-bound tests scale with cores).
    config.addinivalue_line('markers', 'slow: compile-heavy (~minutes)')


def pytest_collection_modifyitems(config, items):
    import pytest as _pytest
    if os.environ.get('KFAC_SKIP_SLOW') != '1':
        return
    skip = _pytest.mark.skip(reason='KFAC_SKIP_SLOW=1 fast tier')
    for item in items:
        if 'slow' in item.keywords:
            item.add_marker(skip)
