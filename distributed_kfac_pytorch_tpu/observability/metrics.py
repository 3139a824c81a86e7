"""On-device K-FAC step metrics (pure jnp; built *inside* the jitted step).

The metrics pytree rides in the K-FAC state (``state['metrics']``), so
enabling it changes no call signatures and adds NO host transfers to the
step: every entry is an on-device scalar updated by traced ops, and the
host drains the tree asynchronously whenever it likes (the engine's
JSONL sink enqueues the device arrays and converts to floats lazily —
:mod:`observability.sink`).

Tracked (schema in :data:`METRIC_KEYS`):

  - ``damping`` / ``nu``: the resolved dynamic damping and KL-clip
    scale this step (reference preconditioner.py:661-682's ν).
  - ``grad_norm`` / ``precond_norm``: global l2 norms of the registered
    layers' gradient matrices and of the ν-scaled preconditioned
    result — their ratio is the "how hard is K-FAC steering" health
    signal (KAISA tunes against exactly this kind of per-step evidence).
  - ``factor_updates`` / ``inv_updates``: cumulative firing counts of
    the two periodic stages (host-side staleness tracking derives from
    these without any extra device work).
  - ``nonfinite_skips``: cumulative count of factor updates whose
    candidate factors were non-finite (see the guard in
    ``KFAC.update_factors``).
  - ``eig_clipped``: number of eigenvalues currently sitting at the
    0.0 floor across all stored eigen slots (post-``clip``: a clipped
    eigenvalue is exactly 0, so the stored spectra are countable
    without touching the decomposition path).
  - ``bucket_norms/<shape>``: per precondition shape-bucket l2 norms of
    the preconditioned matrices (the unit ``KFAC._bucketed_precond_mats``
    and the KAISA row-sharded path batch over).

With ``collect_metrics=False`` (the default) none of this exists in the
state or the trace — the step is bit-identical to the pre-observability
program (pinned by tests/test_observability.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distributed_kfac_pytorch_tpu.capture import EXPERTS

# Scalar metric slots (beyond the per-model 'bucket_norms' subtree).
# 'inv_chunk_firings' counts pipelined chunk firings (r9: a chunk
# firing covers 1/k of the factor set, so it is tallied separately
# from the monolithic 'inv_updates' — k chunk firings = one window's
# worth of inverse work).
METRIC_KEYS = ('damping', 'nu', 'grad_norm', 'precond_norm',
               'factor_updates', 'inv_updates', 'inv_chunk_firings',
               'nonfinite_skips', 'eig_clipped')
_INT_KEYS = ('factor_updates', 'inv_updates', 'inv_chunk_firings',
             'nonfinite_skips', 'eig_clipped')


def shape_key(shape) -> str:
    """Stable string key for a gradient-matrix shape bucket."""
    return 'x'.join(str(int(s)) for s in shape)


def init_metrics(bucket_keys) -> dict:
    """Fresh metrics subtree for ``state['metrics']`` (all on-device)."""
    m = {k: (jnp.zeros((), jnp.int32) if k in _INT_KEYS
             else jnp.zeros((), jnp.float32))
         for k in METRIC_KEYS}
    m['nu'] = jnp.ones((), jnp.float32)
    m['bucket_norms'] = {k: jnp.zeros((), jnp.float32)
                         for k in bucket_keys}
    return m


def update_metrics(prev: dict, *, damping, stats: dict, did_factor,
                   did_inv, factor_finite, eig_clipped,
                   did_chunk=0) -> dict:
    """One traced metrics-state transition (call inside the step).

    ``stats`` comes from the preconditioner's ``with_stats`` pass
    (``nu`` / ``grad_norm`` / ``precond_norm`` / ``bucket_norms``);
    ``did_factor`` / ``did_inv`` / ``did_chunk`` are 0/1 cadence
    indicators (``did_chunk``: a pipelined chunk firing, r9) and
    ``factor_finite`` the 0/1 finiteness of this step's candidate
    factors (1 on non-factor steps).
    """
    return {
        'damping': jnp.asarray(damping, jnp.float32),
        'nu': stats['nu'].astype(jnp.float32),
        'grad_norm': stats['grad_norm'].astype(jnp.float32),
        'precond_norm': stats['precond_norm'].astype(jnp.float32),
        'factor_updates': prev['factor_updates'] + did_factor,
        'inv_updates': prev['inv_updates'] + did_inv,
        'inv_chunk_firings': (prev.get('inv_chunk_firings',
                                       jnp.zeros((), jnp.int32))
                              + did_chunk),
        'nonfinite_skips': (prev['nonfinite_skips']
                            + did_factor * (1 - factor_finite)),
        'eig_clipped': jnp.asarray(eig_clipped, jnp.int32),
        'bucket_norms': {k: v.astype(jnp.float32)
                         for k, v in stats['bucket_norms'].items()},
    }


def flatten_metrics(m: dict, prefix: str = 'kfac') -> dict:
    """Flatten a metrics subtree into scalar entries for a metrics dict
    (``'kfac/grad_norm'``, ``'kfac/bucket_norm/128x65'``, ...)."""
    out = {f'{prefix}/{k}': m[k] for k in METRIC_KEYS if k in m}
    for k, v in m.get('bucket_norms', {}).items():
        out[f'{prefix}/bucket_norm/{k}'] = v
    return out


def moe_row_metrics(specs: dict, captures: dict, axes) -> dict:
    """How this step's tokens fell on the experts held here, from the
    stacked-expert layers' captured ``group_sizes`` (one stream a MoE
    layer: its matrices share their routing), summed over the mesh's
    data ``axes``: ``moe/rows_here`` (routed rows a layer, mean over
    the layers), ``moe/rows_max_expert`` (the fullest expert's rows)
    and ``moe/empty_experts`` (experts, over all layers, that got no
    row and so kept their factors). Traced scalars that ride in the
    step's metrics dict like every other; ``{}`` for a model without
    such layers, and on steps that capture nothing."""
    per_layer = {}
    for name, spec in specs.items():
        if spec.kind == EXPERTS and 'rows' in captures.get(name, {}):
            per_layer.setdefault(spec.path[:-1],
                                 sum(captures[name]['rows']))
    if not per_layer:
        return {}
    rows = jax.lax.psum(jnp.stack(list(per_layer.values())), axes)
    return {'moe/rows_here': jnp.mean(
                jnp.sum(rows, axis=-1).astype(jnp.float32)),
            'moe/rows_max_expert': jnp.max(rows).astype(jnp.float32),
            'moe/empty_experts': jnp.sum(rows == 0).astype(jnp.float32)}


def count_clipped_eigvals(inverses: dict) -> jax.Array:
    """Eigenvalues at the 0.0 clip floor in a per-layer inverse dict.

    Post-clip spectra: ``batched_eigh(clip=0.0)`` floors with
    ``max(d, 0)``, so a clipped eigenvalue is stored as exactly 0 and
    ``d <= 0`` counts precisely the floored set (values above the floor
    are untouched and stay positive).
    """
    total = jnp.zeros((), jnp.int32)
    for entry in inverses.values():
        for k in ('dA', 'dG'):
            if k in entry:
                total += jnp.sum(
                    (entry[k].astype(jnp.float32) <= 0.0)
                    .astype(jnp.int32))
    return total


def count_clipped_eigvals_stacks(inv_stacks: dict) -> jax.Array:
    """Row-local clipped-eigenvalue count over distributed inverse
    stacks (sum the caller psums over the inverse-group axis; identity
    padding slots hold d=1 and contribute nothing)."""
    total = jnp.zeros((), jnp.int32)
    for entry in inv_stacks.values():
        if 'd' in entry:
            total += jnp.sum(
                (entry['d'].astype(jnp.float32) <= 0.0)
                .astype(jnp.int32))
    return total


def precond_stats(grad_mats: dict, precond_mats: dict, nu) -> dict:
    """Norm statistics over one step's precondition pass.

    ``grad_mats`` / ``precond_mats`` map layer name -> matrix (any
    shapes); buckets group by matrix shape — the same grouping the
    bucketed precondition paths batch over, derived from static shapes
    so the metric keys are trace-constant.
    """
    gsq = jnp.zeros((), jnp.float32)
    bucket_sq: dict[str, jax.Array] = {}
    psq = jnp.zeros((), jnp.float32)
    nu32 = jnp.asarray(nu, jnp.float32)
    for name, gm in grad_mats.items():
        gsq += jnp.sum(jnp.square(gm.astype(jnp.float32)))
        vm = precond_mats[name].astype(jnp.float32)
        vsq = jnp.sum(jnp.square(vm)) * nu32 * nu32
        psq += vsq
        key = shape_key(gm.shape)
        bucket_sq[key] = bucket_sq.get(key, jnp.zeros((),
                                                      jnp.float32)) + vsq
    return {'nu': nu32,
            'grad_norm': jnp.sqrt(gsq),
            'precond_norm': jnp.sqrt(psq),
            'bucket_norms': {k: jnp.sqrt(v)
                             for k, v in bucket_sq.items()}}
