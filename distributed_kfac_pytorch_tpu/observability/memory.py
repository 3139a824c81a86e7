"""Memory telemetry: device HBM watermarks, K-FAC state footprint, and
each step program's own bytes.

Three complementary sources, all host-side and sync-free (r10):

  - :func:`device_memory_stats` — the live allocator watermarks
    (``bytes_in_use`` / ``peak_bytes_in_use``) from
    ``jax.Device.memory_stats()``. On TPU/GPU this is the HBM truth the
    paper's memory/communication trade-off (KAISA, arXiv:2107.01739)
    is argued over; the CPU backend reports nothing and the function
    degrades to ``{}`` instead of raising, so callers can emit records
    unconditionally.
  - :func:`state_footprint` — a shape/dtype walk over the resident
    K-FAC state pytree (factors / inverses / bucket stacks, by dtype).
    No device transfer happens: ``jax.Array`` carries shape and dtype
    on the host, so the breakdown is exact and free. This is what
    finally makes the r6 bf16-resident-inverse and KAISA
    grad-worker-fraction memory claims auditable from a run's JSONL
    alone — the ``kind='memory'`` records carry both sources
    (``observability.sink.JsonlMetricsSink.memory_record``).
  - :func:`program_footprint` — a compiled program's arguments,
    temporaries and code by the compiler's count. The allocator's peak
    leaves out a running program's temporaries and counts every loaded
    program's code (PERF.md, PR 34/35); ``build_train_step`` records
    this for each variant it loads (``kfac/program_bytes/*``).

The engine samples every ``memory_interval`` steps
(``train_epoch(memory_interval=)``, ``--memory-interval`` in the CLIs);
``observability.report`` prints the last/peak watermarks and the
footprint table, and ``observability.gate`` regresses peak HBM against
a committed baseline.
"""

from __future__ import annotations

from typing import Any

import jax

# state_footprint groups. Top-level K-FAC state keys outside this map
# fold into 'other' (scalars like step / inv_chunk_phase). The SPMD
# bucket stacks ('inv_stacks') and the replicated single-chip
# 'inverses' both count as inverse storage, so the same report reads
# on either path.
STATE_GROUPS = {
    'factors': 'factors',
    'inverses': 'inverses',
    'inv_stacks': 'inverses',
    'diag_inv': 'inverses',
    'grouped_inv': 'inverses',
    'metrics': 'metrics',
    # r14 overlap state: the deferred-reduction accumulator is a full
    # factor-sized copy per device, and the staleness snapshot another
    # replicated factor copy — worth their own rows in the footprint
    # (they are the knobs' HBM price).
    'factor_accum': 'factor_accum',
    'frozen_factors': 'frozen_factors',
}


def device_memory_stats(device=None) -> dict:
    """Allocator watermarks of one device (``{}`` when unavailable).

    Keys are backend-defined; TPU/GPU expose at least ``bytes_in_use``
    and ``peak_bytes_in_use``. Only int/float values pass through (the
    JSONL record must stay scalar-valued). ``device`` defaults to the
    first local device — with the replicated/SPMD layouts this
    framework builds, every local device holds the same resident state,
    so one device's watermark is the per-chip number the gate compares.
    """
    if device is None:
        devs = jax.local_devices()
        if not devs:
            return {}
        device = devs[0]
    try:
        stats = device.memory_stats()
    except Exception:
        return {}
    if not stats:
        return {}
    return {k: v for k, v in stats.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def _leaf_bytes(x) -> int:
    """Per-device resident bytes of one leaf.

    Sharded leaves (the row-sharded SPMD inverse stacks) count their
    per-device shard, not the global logical size — the footprint must
    line up with the per-chip allocator watermarks it is reported next
    to (this is exactly the KAISA axis: grad_worker_fraction trades
    per-chip inverse residency against communication).
    """
    sharding = getattr(x, 'sharding', None)
    shape = getattr(x, 'shape', None)
    dtype = getattr(x, 'dtype', None)
    if sharding is not None and shape is not None and dtype is not None:
        try:
            shard_shape = sharding.shard_shape(tuple(shape))
            n = 1
            for s in shard_shape:
                n *= int(s)
            return n * dtype.itemsize
        except Exception:
            pass
    nbytes = getattr(x, 'nbytes', None)
    if isinstance(nbytes, int):
        return nbytes
    return 0


def _leaf_dtype(x) -> str:
    dt = getattr(x, 'dtype', None)
    return str(dt) if dt is not None else type(x).__name__


def state_footprint(state: Any) -> dict:
    """Byte breakdown of a (K-FAC) state pytree, by group and dtype.

    Pure host arithmetic over shapes/dtypes — no device sync, no
    transfer. Returns::

      {'total_bytes': int,
       'by_group': {'factors': int, 'inverses': int, ...},
       'by_dtype': {'float32': int, 'bfloat16': int, ...},
       'by_group_dtype': {'inverses/bfloat16': int, ...}}

    Grouping keys on the state's top-level entries per
    :data:`STATE_GROUPS` (single-chip ``inverses`` and the SPMD
    ``inv_stacks``/``diag_inv``/``grouped_inv`` all fold into
    'inverses', so the same report reads on either path); non-dict
    states (the SGD baseline threads ``None`` through the kfac slot)
    return an all-zero breakdown.
    """
    out = {'total_bytes': 0, 'by_group': {}, 'by_dtype': {},
           'by_group_dtype': {}}
    if not isinstance(state, dict):
        return out
    for key, sub in state.items():
        group = STATE_GROUPS.get(key, 'other')
        for leaf in jax.tree.leaves(sub):
            n = _leaf_bytes(leaf)
            if not n:
                continue
            dt = _leaf_dtype(leaf)
            out['total_bytes'] += n
            out['by_group'][group] = out['by_group'].get(group, 0) + n
            out['by_dtype'][dt] = out['by_dtype'].get(dt, 0) + n
            gk = f'{group}/{dt}'
            out['by_group_dtype'][gk] = (
                out['by_group_dtype'].get(gk, 0) + n)
    return out


# program_footprint keys -> the compiler's CompiledMemoryStats fields.
PROGRAM_FIELDS = {
    'argument': 'argument_size_in_bytes',
    'output': 'output_size_in_bytes',
    'alias': 'alias_size_in_bytes',
    'temp': 'temp_size_in_bytes',
    'code': 'generated_code_size_in_bytes',
}


def program_footprint(compiled) -> dict:
    """One device's bytes of a compiled program, by the compiler's
    count (``compiled.memory_analysis()``; ``{}`` where the backend
    gives none, as :func:`device_memory_stats` degrades).

    Keys: :data:`PROGRAM_FIELDS`'s, ``total`` = argument + output −
    alias + temp + code (what the device holds while the program runs:
    the runtime's ``peak_bytes_in_use`` counts its arguments and code
    but not its temporaries), and ``peak``, the compiler's own
    ``peak_memory_in_bytes``, where it is given and non-zero.
    """
    stats = compiled.memory_analysis()
    if stats is None:
        return {}
    out = {key: int(getattr(stats, field))
           for key, field in PROGRAM_FIELDS.items()}
    out['total'] = (out['argument'] + out['output'] - out['alias']
                    + out['temp'] + out['code'])
    peak = getattr(stats, 'peak_memory_in_bytes', 0)
    if peak:
        out['peak'] = int(peak)
    return out


def format_bytes(n: float) -> str:
    """Human-readable byte count for the report tables."""
    try:
        n = float(n)
    except (TypeError, ValueError):
        return '-'
    for unit in ('B', 'KiB', 'MiB', 'GiB', 'TiB'):
        if abs(n) < 1024.0 or unit == 'TiB':
            return (f'{n:.0f} {unit}' if unit == 'B'
                    else f'{n:.2f} {unit}')
        n /= 1024.0
    return f'{n:.2f} TiB'
