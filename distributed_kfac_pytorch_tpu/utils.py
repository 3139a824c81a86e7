"""Tracing / timing utilities (reference kfac/utils.py:8-56).

The wall-clock trace table is ``observability.tracing``'s recorder of
spans and counters; the ``trace`` / ``get_trace`` / ``print_trace`` /
``clear_trace`` names stay importable from here so reference-parity
callers and existing tests keep working unchanged.
"""

from __future__ import annotations

from typing import Any

import jax

# Re-exports (same objects — decorating through either path feeds the
# one recorder).
from distributed_kfac_pytorch_tpu.observability.tracing import (  # noqa: F401
    clear_trace,
    get_trace,
    print_trace,
    trace,
)


def tree_bytes(tree: Any) -> int:
    """Total bytes of all array leaves (for memory accounting)."""
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves(tree) if hasattr(x, 'size'))


_CACHE_OFF = ('0', 'false', 'off', 'no', '')


def enable_compilation_cache() -> str | None:
    """Turn on JAX's persistent compilation cache for an entry point;
    returns the directory in effect, or None when the cache is off.

    A repeat process then loads its programs instead of compiling them,
    which on the chip is most of a cold run (PERF.md). No reference
    analogue (torch eager has no compile step). The directory comes
    from outside the program or is fixed, never chosen in code, because
    the path is part of the cache key — a directory that moves never
    hits:

    - ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself. This
      helper returns it and sets nothing.
    - unset: ``<checkout>/.jax_cache`` (the package's parent directory;
      ``.gitignore`` lists it). If it cannot be created — an installed
      package under a read-only site-packages — the cache stays off.
    - ``KFAC_COMPILE_CACHE=0`` (or false/off/no): this helper does
      nothing. The CPU smokes use it to measure cold compiles.

    Deliberately a per-entry-point call, NOT a library import side
    effect: the library never mutates global JAX config by being
    imported. Safe for timing benches: the cache changes compile time
    only, never the compiled program.

    Known issue (observed on jax 0.8 in this tree): WARM cache reads
    segfault on the multi-device CPU backend — the second full test
    suite run crashes at trace time inside a shard_map trace, while
    cold runs and single-device warm reads are clean. A process
    configured for several CPU devices therefore gets no default
    directory; one that exports ``JAX_COMPILATION_CACHE_DIR`` itself
    keeps it (the test harness clears it with
    :func:`disable_compilation_cache`).
    """
    import os

    if os.environ.get('KFAC_COMPILE_CACHE', '1').strip().lower() \
            in _CACHE_OFF:
        return None
    from_env = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    if from_env:
        return from_env
    if _multi_device_cpu_configured():
        return None
    cache_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        '.jax_cache')
    try:
        os.makedirs(cache_dir, exist_ok=True)
    except OSError:
        return None
    # JAX's default min-compile-time threshold (~1 s) stays: it caches
    # exactly the expensive programs (train steps, big test programs)
    # while skipping the thousands of tiny helper jits. An earlier
    # min_compile_time=0.0 override was reverted after a reproducible
    # segfault in warm full-suite runs (tiny-entry churn from
    # overlapping processes is the prime suspect).
    jax.config.update('jax_compilation_cache_dir', cache_dir)
    return cache_dir


def disable_compilation_cache() -> None:
    """Turn the persistent compilation cache off for this process —
    including a cache inherited through JAX's own
    ``JAX_COMPILATION_CACHE_DIR`` env var. The single point of truth
    for the multi-device-CPU segfault workaround (see
    :func:`enable_compilation_cache`); used by the CPU-mesh test
    harness and the multichip dryrun.
    """
    import os

    os.environ.pop('JAX_COMPILATION_CACHE_DIR', None)
    jax.config.update('jax_compilation_cache_dir', None)


def raise_cpu_collective_timeouts(terminate_s: int = 600,
                                  warn_s: int = 120) -> None:
    """Raise XLA-CPU collective rendezvous timeouts via XLA_FLAGS.

    The virtual multi-device CPU mesh runs one thread per device on
    however few cores the host has; under compile load a device thread
    can be starved past XLA's default 40 s rendezvous termination
    timeout, which kills the process with a Fatal check ("Expected N
    threads to join the rendezvous...") — observed on the 1-core CI
    host between epoch-boundary program variants. Must run BEFORE the
    CPU backend initializes (XLA_FLAGS is read at backend init);
    existing user-provided values for these flags win.
    """
    import os

    flags = os.environ.get('XLA_FLAGS', '')
    add = []
    if '--xla_cpu_collective_call_terminate_timeout_seconds' not in flags:
        add.append('--xla_cpu_collective_call_terminate_timeout_seconds'
                   f'={terminate_s}')
    if '--xla_cpu_collective_call_warn_stuck_timeout_seconds' not in flags:
        add.append('--xla_cpu_collective_call_warn_stuck_timeout_seconds'
                   f'={warn_s}')
    if add:
        os.environ['XLA_FLAGS'] = (flags + ' ' + ' '.join(add)).strip()


def _multi_device_cpu_configured() -> bool:
    """Is this process set up for a multi-device CPU backend (the
    configuration whose warm cache reads segfault)? Decided from
    config/env only, WITHOUT initializing the backend (entry points
    still need jax.config.update('jax_platforms', ...) to work after
    this check). True both when ``jax_platforms`` names cpu first and
    when it is unset — the process may then still resolve to an
    accelerator, but a default cache directory is not worth the
    segfault if it does not.
    """
    import os
    import re

    plats = jax.config.jax_platforms
    first = plats.split(',')[0] if plats else None
    m = re.search(r'xla_force_host_platform_device_count=(\d+)',
                  os.environ.get('XLA_FLAGS', ''))
    forced = bool(m and int(m.group(1)) > 1) or (
        jax.config.jax_num_cpu_devices or 0) > 1
    return forced and first in ('cpu', None)
