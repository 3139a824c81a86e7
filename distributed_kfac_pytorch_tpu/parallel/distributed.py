"""Distributed K-FAC execution on a TPU mesh (SPMD, shard_map).

This is the TPU-native replacement for the reference's three communication
strategies (reference kfac/preconditioner.py:19-36, kfac/utils.py:59-147)
and its NCCL/Horovod broadcast groups (kfac/comm.py). The world is a 2-D
``jax.sharding.Mesh`` of shape ``(n_inv_groups, grad_workers)``:

  - axis ``kfac_ig`` indexes the *inverse groups* (KAISA's contiguous
    inverse-broadcast groups, reference kfac/utils.py:156-159);
  - axis ``kfac_gw`` indexes position *within* a group (the strided
    gradient-broadcast groups, reference kfac/utils.py:150-153, are the
    columns of this view).

Data parallelism shards the batch over *both* axes flattened; gradient
averaging is one ``pmean`` over ``(kfac_ig, kfac_gw)``.

The reference's rank-selective work and broadcasts become SPMD-friendly
masked collectives (the "zero the non-assigned buffer and sum" trick the
reference itself uses for tensor gathers, kfac/layers/base.py:202-206):

  - **factor allreduce** (reference preconditioner.py:525-533) — ``pmean``
    of per-device covariance contributions over both axes;
  - **inverse compute + broadcast** (reference preconditioner.py:555-564,
    base.py:129-171) — same-size factors are stacked per *bucket*, every
    device eigendecomposes its slice of its row's stack (one batched
    ``eigh`` on the MXU instead of ~100 sequential kernels), and one
    ``all_gather`` over ``kfac_gw`` leaves each inverse group holding
    exactly its own layers' inverses — COMM_OPT (1 group) replicates all
    inverses everywhere, MEM_OPT (group size 1) keeps each inverse on a
    single device, HYBRID in between;
  - **gradient broadcast** (reference preconditioner.py:545-553,
    base.py:173-196) — each row preconditions its own layers (the value is
    masked to zero on other rows), and a single ``psum`` over ``kfac_ig``
    delivers every layer's preconditioned gradient to all devices.

All placement is decided host-side at trace time (``WorkAssignment``),
exactly like the reference's one-time deferred assignment
(preconditioner.py:616-659): greedy LPT of layers onto inverse groups,
then of factors onto group members.
"""

from __future__ import annotations

import collections
import dataclasses
import operator
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from distributed_kfac_pytorch_tpu import fp16 as fp16_ops
from distributed_kfac_pytorch_tpu import layers as L
from distributed_kfac_pytorch_tpu.capture import (BLOCK_STACK_KINDS,
                                                  EMBEDDING, EXPERTS,
                                                  subsample_captures)
from distributed_kfac_pytorch_tpu.observability import (
    memory as obs_memory,
    metrics as obs_metrics,
    profiling,
    tracing,
)
from distributed_kfac_pytorch_tpu.ops import factors as F
from distributed_kfac_pytorch_tpu.ops import linalg
from distributed_kfac_pytorch_tpu.ops import pallas_kernels
from distributed_kfac_pytorch_tpu.parallel.placement import load_balance
from distributed_kfac_pytorch_tpu.parallel.sequence import SEQ_AXIS
from distributed_kfac_pytorch_tpu.preconditioner import (
    KFAC,
    CommMethod,
    cadence_gate,
    eigen_family,
    grouped_block_inverses,
    guard_nonfinite_factors,
    q_stack_degenerate,
    resolve_eigh_method,
)

# Mesh axis names. Batch/data parallelism shards over both axes jointly;
# an optional third SEQ_AXIS ('kfac_sp') shards the sequence dimension for
# ring-attention context parallelism (parallel.sequence). Multi-slice
# pods (r20) prepend an OUTER slice axis: devices within a slice share
# fast ICI, slices are joined by slow DCN, and the collective topology
# is two-level — inverse groups never span slices
# (multislice.make_multislice_mesh builds the nested mesh).
SLICE_AXIS = 'kfac_slice'
INV_GROUP_AXIS = 'kfac_ig'
GRAD_WORKER_AXIS = 'kfac_gw'
KFAC_AXES = (INV_GROUP_AXIS, GRAD_WORKER_AXIS)


# A step variant's first call runs under a ``kfac/build/<variant>`` span
# (observability.tracing). JAX's monitoring events that arrive while it
# is open say where that call's time went: each goes to the span's
# attribute named here. A jit traced inside another reports a trace
# time of its own that the outer one's includes, so the longest stands;
# the other events add up. The cache's retrieval event comes only with
# a hit, and the backend's time includes it.
BUILD_SPAN_PREFIX = 'kfac/build/'
_BUILD_EVENT_ATTRS = {
    '/jax/core/compile/jaxpr_trace_duration': ('trace_s', max),
    '/jax/core/compile/jaxpr_to_mlir_module_duration':
        ('lower_s', operator.add),
    '/jax/core/compile/backend_compile_duration':
        ('backend_s', operator.add),
    '/jax/compilation_cache/cache_retrieval_time_sec':
        ('cache_retrieval_s', operator.add),
}
_build_listener_registered = False


def _on_jax_duration(event: str, seconds: float, **_kw) -> None:
    if event not in _BUILD_EVENT_ATTRS:
        return
    build = tracing.current()
    if build is not None and build.name.startswith(BUILD_SPAN_PREFIX):
        attr, combine = _BUILD_EVENT_ATTRS[event]
        build.set(**{attr: combine(build.attrs[attr], seconds)})


def _open_build_span(label: str) -> tracing.Span:
    """The span round one variant's first call. JAX keeps a listener
    for the life of the process, so the one that fills these spans is
    registered once, by the first build."""
    global _build_listener_registered
    if not _build_listener_registered:
        jax.monitoring.register_event_duration_secs_listener(
            _on_jax_duration)
        _build_listener_registered = True
    return tracing.span(
        BUILD_SPAN_PREFIX + label,
        **{attr: 0.0 for attr, _ in _BUILD_EVENT_ATTRS.values()})


def _attention_paths_since(before: dict) -> dict:
    """How many attention calls this build traced onto the fused kernel
    and onto the plain path: what the ``kfac/attention/*`` counters
    (parallel.sequence) gained since ``before``. A capturing variant
    traces the model twice (the probes' shape pass, then the
    differentiated pass), so it reads twice its attention layers."""
    now = tracing.counters()
    return {f'attention_{path}': int(
        now.get(f'kfac/attention/{path}', 0)
        - before.get(f'kfac/attention/{path}', 0))
        for path in ('fused', 'plain')}


def resolve_grad_workers(size: int, comm_method: CommMethod,
                         grad_worker_fraction: float) -> int:
    """Number of devices per inverse group for a strategy.

    Reference parity: preconditioner.py:235-259 (COMM_OPT -> world,
    MEM_OPT -> 1, HYBRID_OPT -> validated ``grad_worker_fraction``).
    """
    if comm_method is CommMethod.COMM_OPT:
        return size
    if comm_method is CommMethod.MEM_OPT:
        return 1
    gw = max(1, round(size * grad_worker_fraction))
    if size % gw != 0:
        raise ValueError(
            f'grad_worker_fraction {grad_worker_fraction} gives '
            f'{gw} grad workers, which does not divide world size {size}')
    return gw


def make_kfac_mesh(devices: Sequence[jax.Device] | None = None, *,
                   comm_method: CommMethod = CommMethod.COMM_OPT,
                   grad_worker_fraction: float = 0.25,
                   seq_parallel: int = 1) -> Mesh:
    """Build the ``(n_inv_groups, grad_workers[, seq])`` mesh.

    Contiguous device runs form inverse groups (rows), matching the
    reference's contiguous ``partition_inv_ranks`` (kfac/utils.py:156-159)
    — on a TPU slice, contiguous devices are ICI neighbors, so the
    latency-critical inverse all_gather rides the fastest links.

    ``seq_parallel > 1`` appends a third ``SEQ_AXIS`` of that size as the
    *innermost* (fastest-varying) axis, so the ring-attention ppermute
    hops between physically adjacent chips.

    The device grid is *derived from* the golden KAISA topology spec
    (``placement.WorkerAllocator``, reference kfac/utils.py:59-159,
    pinned by tests/test_placement.py): mesh rows are the allocator's
    contiguous inverse-broadcast groups, and the columns across rows are
    exactly its strided gradient-broadcast groups — one source of truth
    for the topology, consumed here rather than re-derived by reshape.
    """
    from distributed_kfac_pytorch_tpu.parallel.placement import (
        WorkerAllocator,
    )
    if devices is None:
        devices = jax.devices()
    devices = np.asarray(devices)
    if devices.size % seq_parallel:
        raise ValueError(f'{seq_parallel=} does not divide '
                         f'{devices.size} devices')
    dp = devices.size // seq_parallel
    gw = resolve_grad_workers(dp, comm_method, grad_worker_fraction)
    alloc = WorkerAllocator(dp, gw / dp)
    assert alloc.grad_workers == gw
    # (n_inv_groups, grad_workers) grid of K-FAC ranks per the spec.
    grid = alloc.grid
    if seq_parallel > 1:
        # Rank r owns the contiguous run of seq_parallel devices.
        devs = devices.reshape(dp, seq_parallel)[grid]
        return Mesh(devs, KFAC_AXES + (SEQ_AXIS,))
    return Mesh(devices[grid], KFAC_AXES)


def replicated_specs(tree):
    """P() for every leaf (None leaves included) — shard_map boilerplate."""
    return jax.tree.map(lambda _: P(), tree, is_leaf=lambda x: x is None)


def normalize_batch_specs(batch_spec, batch):
    """Per-leaf PartitionSpec tree for a batch pytree.

    A single ``PartitionSpec`` (or None) is broadcast over every leaf; a
    pytree of specs matching ``batch`` passes through unchanged. Single
    point of truth for every train-step builder that accepts
    ``batch_spec``.
    """
    if batch_spec is None or isinstance(batch_spec, P):
        return jax.tree.map(lambda _: batch_spec, batch)
    return batch_spec


# ---------------------------------------------------------------------------
# Host-side static work assignment
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """Layout of all same-size factors as one stacked eigh workload.

    The global stack has shape ``(n_rows * slots_per_row, dim, dim)``,
    sharded over ``kfac_ig`` (each row of the mesh owns the contiguous
    slice of ``slots_per_row`` slots holding its layers' factors). Device
    ``(i, j)`` eigendecomposes local slots
    ``[j * slots_per_col, (j+1) * slots_per_col)``; unassigned slots hold
    identity padding.
    """
    dim: int
    slots_per_col: int          # eigh workload per device for this bucket
    n_cols: int
    # (layer_name, 'A'|'G') -> slot index within the owning row's slice.
    # A layer that follows another's A (``LayerSpec.a_owner``) has its
    # ``(name, 'A')`` at its owner's slot: one matrix is stacked and
    # inverted there, the owner's, and both layers read the result.
    slot: dict[tuple[str, str], int]

    @property
    def slots_per_row(self) -> int:
        return self.slots_per_col * self.n_cols


@dataclasses.dataclass(frozen=True)
class WorkAssignment:
    """Static placement of K-FAC second-order work onto the mesh.

    ``layer_row[name]`` is the inverse group that computes, stores, and
    preconditions with layer ``name``'s inverses — the analogue of the
    reference's per-layer inverse worker + its broadcast group
    (preconditioner.py:616-659). ``buckets`` lay out the eigh work;
    ``diag_layers`` (embedding A factors) are diagonal and handled
    replicated (their inverse is an elementwise reciprocal).
    """
    n_rows: int
    n_cols: int
    layer_row: dict[str, int]
    buckets: dict[int, BucketPlan]
    diag_layers: tuple[str, ...]
    # Grouped/depthwise convs and stacked experts: per-block stacks,
    # computed replicated and preconditioned by their owning row.
    grouped_layers: tuple[str, ...] = ()


def assign_work(kfac: KFAC, params, n_rows: int, n_cols: int, *,
                distribute_layer_factors: bool | None = None
                ) -> WorkAssignment:
    """LPT-place layers onto inverse groups and factors onto members.

    Two-level greedy longest-processing-time balance, mirroring the
    reference cost model (n^3 'compute' / n^2 'memory',
    preconditioner.py:625-628): layers across rows (each layer's A and G
    stay in one inverse group, as required by the KAISA topology), then
    factors across the row's columns. ``distribute_layer_factors`` places A
    and G on different columns when possible (reference
    preconditioner.py:638-645); it defaults to True when each group has
    more than one member.

    Layers that share an A (``KFAC.a_followers``) are placed on one row,
    as one unit of the balance, and a follower's A is no work item: it
    takes its owner's slot, so the one inverse is where the gradient of
    each of them is preconditioned. Where no layers share, units are
    layers and the placement is what it always was.
    """
    if distribute_layer_factors is None:
        distribute_layer_factors = n_cols > 1
    exp = 3 if kfac.assignment_strategy == 'compute' else 2
    names = list(kfac.specs)
    shapes = {}
    diag_layers = []
    grouped_layers = []
    for name in names:
        spec = kfac.specs[name]
        a_dim, g_dim = L.factor_shapes(spec, _get(params, spec.path))
        shapes[name] = (a_dim, g_dim)
        if spec.kind == EMBEDDING:
            diag_layers.append(name)
        elif spec.kind in BLOCK_STACK_KINDS:
            grouped_layers.append(name)

    follows = kfac.a_followers()

    def factor_entries(name):
        """[(key, dim, cost)] for the dense (eigh-requiring) factors.

        Grouped convs contribute none: their per-group block stacks run
        replicated (outside the bucket layout) — they still get a row
        for precondition ownership via ``layer_cost`` below.
        """
        if name in grouped_layers:
            return []
        a_dim, g_dim = shapes[name]
        out = []
        if name not in diag_layers and name not in follows:
            out.append(((name, 'A'), a_dim, a_dim ** exp))
        out.append(((name, 'G'), g_dim, g_dim ** exp))
        return out

    layer_cost = {n: sum(c for _, _, c in factor_entries(n)) for n in names}
    for n in grouped_layers:
        ng = kfac.specs[n].num_blocks
        a_dim, g_dim = shapes[n]
        layer_cost[n] = ng * (g_dim ** exp
                              + (0 if n in follows else a_dim ** exp))
    unit_cost: dict[str, int] = {}
    for n in names:
        unit = follows.get(n, n)
        unit_cost[unit] = unit_cost.get(unit, 0) + layer_cost[n]
    unit_row = dict(zip(unit_cost, load_balance(
        n_rows, list(unit_cost.values()))))
    row_of = {n: unit_row[follows.get(n, n)] for n in names}

    # Per row: LPT factors -> columns (or whole layers -> columns when not
    # distributing A/G, reference preconditioner.py:638-645).
    cell: dict[tuple[int, int, int], list] = collections.defaultdict(list)
    for r in range(n_rows):
        row_names = [n for n in names if row_of[n] == r]
        if not row_names:
            continue
        if distribute_layer_factors:
            items = [e for n in row_names for e in factor_entries(n)]
        else:
            items = [((n, '*'), 0, layer_cost[n])
                     for n in row_names if factor_entries(n)]
        if not items:
            continue  # row holds only grouped/diag layers (no buckets)
        cols = load_balance(n_cols, [c for _, _, c in items])
        for (key, dim, _), col in zip(items, cols):
            if key[1] == '*':
                for sub_key, sub_dim, _ in factor_entries(key[0]):
                    cell[(r, col, sub_dim)].append(sub_key)
            else:
                cell[(r, col, dim)].append(key)

    dims = sorted({d for (_, _, d) in cell})
    buckets = {}
    for dim in dims:
        s = max(len(cell[(r, c, dim)])
                for r in range(n_rows) for c in range(n_cols))
        slot = {}
        for r in range(n_rows):
            for c in range(n_cols):
                for k, key in enumerate(cell[(r, c, dim)]):
                    slot[key] = c * s + k
        for n, owner in follows.items():
            if (owner, 'A') in slot:
                slot[(n, 'A')] = slot[(owner, 'A')]
        buckets[dim] = BucketPlan(dim=dim, slots_per_col=s, n_cols=n_cols,
                                  slot=slot)
    return WorkAssignment(n_rows=n_rows, n_cols=n_cols, layer_row=row_of,
                          buckets=buckets, diag_layers=tuple(diag_layers),
                          grouped_layers=tuple(grouped_layers))


# ---------------------------------------------------------------------------
# The distributed preconditioner
# ---------------------------------------------------------------------------

class DistributedKFAC:
    """K-FAC with second-order work sharded over a ``make_kfac_mesh`` mesh.

    Wraps a :class:`KFAC` (which must have been ``init()``-ed so layer
    specs exist) and re-implements its inverse and preconditioning stages
    as SPMD collectives; factor statistics and hyperparameter semantics are
    inherited. ``spmd_step`` is the in-``shard_map`` analogue of
    ``KFAC.step``; ``build_train_step`` assembles the full jitted
    data-parallel training step around it.
    """

    def __init__(self, kfac: KFAC, mesh: Mesh, params, *,
                 distribute_layer_factors: bool | None = None,
                 shard_precond_compute: bool = True):
        if set(KFAC_AXES) - set(mesh.axis_names):
            raise ValueError(
                f'mesh must have axes {KFAC_AXES}, got {mesh.axis_names}')
        self.kfac = kfac
        self.mesh = mesh
        # KAISA grad-worker compute saving (reference
        # preconditioner.py:577-585: only compute_grad_ranks compute the
        # preconditioned gradients). True (default) stacks same-shape
        # dense layers per inverse group and dynamic-slices per device,
        # so MEM/HYBRID rows compute only their OWN layers' precondition
        # matmuls (1/n_rows of the FLOPs) instead of computing every
        # layer and masking; at n_rows == 1 (COMM_OPT) the same plan is
        # a pure same-shape batching — one vmapped matmul per shape
        # group on the replicated path too (r6). False keeps the
        # per-layer replicate-and-mask form (the round-1..3 path; also
        # the parity oracle in tests).
        self.shard_precond_compute = shard_precond_compute
        self.n_rows = mesh.shape[INV_GROUP_AXIS]
        self.n_cols = mesh.shape[GRAD_WORKER_AXIS]
        # Multi-slice (r20): an outer SLICE_AXIS makes the inverse-row
        # space two-level — each slice holds ``n_rows`` contiguous
        # global rows, so inverse state and decompositions stay
        # slice-confined (the in-group all_gather rides ICI only);
        # only preconditioned gradients cross the DCN (the delivery
        # psum widens to both row axes).
        self.sliced = SLICE_AXIS in mesh.axis_names
        self.n_slices = (mesh.shape[SLICE_AXIS] if self.sliced else 1)
        self.total_rows = self.n_slices * self.n_rows
        # Axis spec of the global inverse-row dimension: stacks are
        # sharded (and row-space collectives reduce) over the slice
        # axis jointly with the inverse-group axis when sliced.
        self._row_axes = ((SLICE_AXIS, INV_GROUP_AXIS) if self.sliced
                          else INV_GROUP_AXIS)
        if kfac.hierarchical_reduce and not self.sliced:
            raise ValueError(
                'hierarchical_reduce=True requires a multi-slice mesh '
                f'(an outer {SLICE_AXIS!r} axis — '
                'multislice.make_multislice_mesh with num_slices > 1); '
                'on a flat mesh there is no DCN boundary to defer over')
        # The EFFECTIVE A/G-across-columns flag (assign_work resolves
        # None to n_cols > 1). Recorded in every checkpoint's topology
        # scalars (elastic.topology) so the elastic resume path can
        # reconstruct this exact placement on a different mesh.
        self.distribute_layer_factors = (
            self.n_cols > 1 if distribute_layer_factors is None
            else bool(distribute_layer_factors))
        # Gradient/factor averaging spans every data-bearing axis: the two
        # K-FAC axes plus the sequence axis when context parallelism is on
        # (each device then holds a (batch shard, sequence block) tile),
        # plus the outer slice axis on a multi-slice mesh.
        self.data_axes = (
            ((SLICE_AXIS,) if self.sliced else ())
            + KFAC_AXES
            + ((SEQ_AXIS,) if SEQ_AXIS in mesh.axis_names else ()))
        # Batch-dim sharding axes (data_axes minus SEQ_AXIS, which
        # shards the sequence dim, not the batch dim).
        self.batch_axes = (((SLICE_AXIS,) if self.sliced else ())
                           + KFAC_AXES)
        self.data_size = int(np.prod([mesh.shape[a]
                                      for a in self.data_axes]))
        # Work placement spans the GLOBAL row space (slices x in-slice
        # inverse groups): assign_work is a pure function of
        # (specs/shapes, total rows, cols, flag), so a flat
        # ``total_rows``-row mesh and a sliced one produce the same
        # layer/bucket layout — the property the elastic reshard path's
        # slice-count changes rely on (elastic.topology.layout_key).
        self.assignment = assign_work(
            kfac, params, self.total_rows, self.n_cols,
            distribute_layer_factors=self.distribute_layer_factors)
        self._factor_dims = {
            name: L.factor_shapes(spec, _get(params, spec.path))
            for name, spec in kfac.specs.items()}
        self._precond_groups = self._plan_precond_groups()
        # Eigen-family dim buckets (exact eigen AND r19 low-rank) that
        # hold at least one *mixed* layer's side additionally carry a
        # firing-time-baked dense inverse stack (see
        # _spmd_update_inverses / KFAC.update_inverses for the
        # timing-semantics rationale).
        self._bucket_mixed = {
            dim: any(self._layer_is_mixed(name)
                     for (name, _w) in plan.slot)
            for dim, plan in self.assignment.buckets.items()
            if eigen_family(kfac.method_for_dim(dim))}
        # Pipelined inverse firing (inv_pipeline_chunks > 1): static
        # chunk plan over within-slice slot offsets; None at k == 1.
        self._chunk_plan = self._plan_firing_chunks()

    def _plan_firing_chunks(self) -> dict | None:
        """Static SPMD chunk plan for pipelined inverse firing.

        The SPMD work unit is one *within-slice slot offset* ``m`` of a
        dim bucket: every device decomposes the slot at its own
        ``col * slots_per_col + m`` position, so firing offset ``m``
        costs each device exactly one dim^3 decomposition and the
        in-group all_gather moves exactly the fired slots — per-device
        load (the spike the pipelining smears) splits in these units.
        Greedy LPT (``preconditioner.plan_inverse_chunks``, the same
        balancer as the single-chip per-matrix plan) packs the offsets
        plus the grouped/diagonal items into ``k`` chunks. Returns
        ``{'offsets': {dim: {chunk: (m, ...)}}, 'diag': {name: chunk},
        'grouped': {name: chunk}}``; ``None`` when the chunk-firing
        machinery is off (``k == 1`` without ``inv_staleness`` — at
        staleness=1 even ``k == 1`` builds a one-chunk plan so the
        whole firing can run mid-window from the frozen snapshot).
        """
        kfac = self.kfac
        k = kfac.inv_pipeline_chunks
        if not kfac.pipelined_firing:
            return None
        from distributed_kfac_pytorch_tpu.ops.linalg import (
            decomposition_cost,
        )
        from distributed_kfac_pytorch_tpu.preconditioner import (
            measured_unit_scale,
            plan_inverse_chunks,
        )
        measured = kfac.inv_pipeline_costs or {}
        # Same unit discipline as KFAC.inverse_chunk_items (shared
        # helper): a measurement dict must cover every bucket dim, and
        # the tiny grouped/diagonal proxy costs rescale into the
        # measured unit. The SPMD work unit is a slot offset, so the
        # per-dim unit count is slots_per_col.
        proxy_scale = measured_unit_scale(
            measured,
            {dim: plan.slots_per_col
             for dim, plan in self.assignment.buckets.items()},
            'inverse bucket dim of this mesh layout')
        items: list[tuple[tuple, float]] = []
        for dim in sorted(self.assignment.buckets):
            plan = self.assignment.buckets[dim]
            # r19: low-rank buckets fire at r·dim^2, not dim^3 (same
            # rank-aware model as the single-chip planner).
            unit = (float(measured[dim]) / plan.slots_per_col
                    if dim in measured
                    else decomposition_cost(
                        dim, rank=kfac.lowrank_rank_for(dim)))
            for m in range(plan.slots_per_col):
                items.append((('slot', dim, m), unit))
        for name in self.assignment.diag_layers:
            items.append((('diag', name),
                          proxy_scale
                          * float(self._factor_dims[name][0])))
        for name in self.assignment.grouped_layers:
            spec = kfac.specs[name]
            a_dim, g_dim = self._factor_dims[name]
            items.append((('grouped', name),
                          proxy_scale * sum(
                              spec.num_blocks * decomposition_cost(d)
                              for d in ((g_dim,) if spec.a_owner
                                        else (a_dim, g_dim)))))
        if k > len(items):
            raise ValueError(
                f'inv_pipeline_chunks={k} exceeds the {len(items)} '
                'inverse work items of this mesh layout (bucket slot '
                'offsets + grouped/diagonal layers); lower it to at '
                f'most {len(items)}')
        assignment = plan_inverse_chunks(items, k)
        offsets: dict[int, dict[int, tuple[int, ...]]] = {
            dim: {} for dim in self.assignment.buckets}
        diag: dict[str, int] = {}
        grouped: dict[str, int] = {}
        for key, j in assignment.items():
            if key[0] == 'slot':
                offsets[key[1]].setdefault(j, [])
                offsets[key[1]][j].append(key[2])
            elif key[0] == 'diag':
                diag[key[1]] = j
            else:
                grouped[key[1]] = j
        offsets = {dim: {j: tuple(sorted(ms))
                         for j, ms in per.items()}
                   for dim, per in offsets.items()}
        return {'offsets': offsets, 'diag': diag, 'grouped': grouped}

    def _layer_is_mixed(self, name: str) -> bool:
        """Dense layer with exactly one eigen-family side (an 'auto'
        straddle, or a low-rank side paired with a baked one)."""
        spec = self.kfac.specs[name]
        if spec.kind in (EMBEDDING, *BLOCK_STACK_KINDS):
            return False
        a_dim, g_dim = self._factor_dims[name]
        return (eigen_family(self.kfac.method_for_dim(a_dim))
                != eigen_family(self.kfac.method_for_dim(g_dim)))

    def _plan_precond_groups(self):
        """Static plan for the row-sharded precondition compute.

        Dense layers are grouped by gradient-matrix shape ``(g_dim,
        a_dim)`` (a vmap-able unit, like the factor buckets); within a
        group each inverse group's layers occupy contiguous slots
        ``row * S + k``, and a ``lax.switch`` over the static rows
        stacks exactly this device's own row's ``S`` grad matrices —
        the SPMD form of "only the grad workers compute" (reference
        preconditioner.py:577-585). ``a_idx`` / ``g_idx`` map each
        global slot to the layer's in-row slot inside the factor-dim
        bucket stacks, so inverse operands are one traced-index gather
        from this row's (local) inverse shard. Padding slots point at
        slot 0 (computed then never read back).
        """
        by_shape: dict[tuple[int, int], dict[int, list[str]]] = {}
        for name, spec in self.kfac.specs.items():
            if spec.kind in (EMBEDDING, *BLOCK_STACK_KINDS):
                continue  # diagonal A / block stacks: per-layer path
            a_dim, g_dim = self._factor_dims[name]
            rows = by_shape.setdefault((g_dim, a_dim), {})
            rows.setdefault(self.assignment.layer_row[name],
                            []).append(name)
        groups = []
        for (g_dim, a_dim), rows in by_shape.items():
            s = max(len(v) for v in rows.values())
            slot_of = {}
            a_idx = np.zeros(self.total_rows * s, np.int32)
            g_idx = np.zeros(self.total_rows * s, np.int32)
            for r, names in rows.items():
                for k, name in enumerate(names):
                    gslot = r * s + k
                    slot_of[name] = gslot
                    a_idx[gslot] = self.assignment.buckets[
                        a_dim].slot[(name, 'A')]
                    g_idx[gslot] = self.assignment.buckets[
                        g_dim].slot[(name, 'G')]
            groups.append({'shape': (g_dim, a_dim), 'S': s,
                           'slot_of': slot_of,
                           'a_idx': a_idx, 'g_idx': g_idx})
        return groups

    # -- state ---------------------------------------------------------

    def init_state(self, params) -> dict:
        """Fresh distributed K-FAC state pytree (global shapes).

        ``factors`` are replicated like the reference's post-allreduce
        factors; ``inv_stacks`` hold per-bucket eigendecompositions (or
        Cholesky inverses) sharded over inverse groups; ``diag_inv`` holds
        replicated diagonal inverses for embedding A factors.

        The state is built ON ``self.mesh`` under :meth:`state_pspecs`
        (one jitted constant program with ``out_shardings``), so the
        first call of every step variant sees the same input types as
        every later call — a state built off the mesh comes back from
        the ``shard_map`` step typed with the mesh and retraces each
        variant once. Building under jit also means the single-chip
        per-layer inverse slots ``KFAC.init_state`` makes, which this
        layout never reads, are never materialized.
        """
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
        build = lambda: self._init_state_values(shapes)
        shardings = jax.tree.map(
            lambda spec: NamedSharding(self.mesh, spec),
            self.state_pspecs(jax.eval_shape(build)))
        state = jax.jit(build, out_shardings=shardings)()
        # What the state holds of one device's memory, by group: from
        # the shards' shapes, so nothing waits for the device.
        footprint = obs_memory.state_footprint(state)
        for group, nbytes in footprint['by_group'].items():
            tracing.gauge(f'kfac/state_bytes/{group}', nbytes)
        tracing.gauge('kfac/state_bytes/total', footprint['total_bytes'])
        experts = [n for n, s in self.kfac.specs.items()
                   if s.kind == EXPERTS]
        if experts:
            # The expert stacks' share of the groups above (factors and
            # inverses both), not a group beside them.
            tracing.gauge('kfac/state_bytes/experts', sum(
                obs_memory.state_footprint(
                    {k: {n: state[k][n] for n in experts}
                     for k in ('factors', 'grouped_inv')})
                ['by_group'].values()))
        inverted, saved = self._inverse_counts(state)
        tracing.gauge('kfac/inverses/per_firing', inverted)
        tracing.gauge('kfac/state_bytes/shared_saved', saved)
        # Capture pairs a step hands the factor stage: one a call of
        # every registered layer (a looped decoder's matrices are called
        # once a pass), and the most calls any one layer has.
        calls = [spec.num_calls for spec in self.kfac.specs.values()]
        tracing.gauge('kfac/capture/calls', sum(calls))
        tracing.gauge('kfac/capture/calls_max', max(calls))
        return state

    def _inverse_counts(self, state: dict) -> tuple[int, int]:
        """``(matrices a firing inverts, bytes of inverse state that
        layers sharing an A do not hold)``, from the placement and the
        state's shapes: every dense factor with a slot of its own and
        every block of a stack (an embedding's diagonal A is no
        matrix); and for each layer that follows another's A
        (``KFAC.a_followers``) what one more slot of its dim's bucket,
        or one more ``A_inv`` stack, would hold."""
        follows = self.kfac.a_followers()
        inverted = sum(
            len({(self.assignment.layer_row[name], at)
                 for (name, _which), at in plan.slot.items()})
            for plan in self.assignment.buckets.values())
        saved = 0
        for name in self.assignment.grouped_layers:
            blocks = self.kfac.specs[name].num_blocks
            inverted += blocks * (1 if name in follows else 2)
        nbytes = lambda x: x.size * x.dtype.itemsize  # noqa: E731
        for name, owner in follows.items():
            if name in self.assignment.grouped_layers:
                saved += nbytes(state['grouped_inv'][owner]['A_inv'])
            else:
                stack = state['inv_stacks'][str(self._factor_dims[name][0])]
                saved += sum(nbytes(x) // x.shape[0]
                             for x in stack.values())
        return inverted, saved

    def _init_state_values(self, params) -> dict:
        """The values of :meth:`init_state` (``params``: shapes only)."""
        base = self.kfac.init_state(params)
        idt = self.kfac.inv_dtype
        stacks = {}
        for dim, plan in self.assignment.buckets.items():
            n_slots = self.total_rows * plan.slots_per_row
            # Buckets are dim-homogeneous, so the per-dim dispatch
            # ('auto': eigen below the cutoff, damped inverse above,
            # r19 low-rank at/above the engaged threshold —
            # KFAC.method_for_dim) picks each bucket's representation
            # wholesale; global modes make every bucket the same.
            method = self.kfac.method_for_dim(dim)
            if eigen_family(method):
                # Identity bases / unit eigenvalues: the exact
                # eigendecomposition of the identity-seeded factors, and
                # a valid warm start for the eigh_method='auto' polish
                # from step 0 (see KFAC.init_state). Low-rank buckets
                # carry a RECTANGULAR (dim, r) identity-column basis —
                # orthonormal columns, valid for the subspace-refresh
                # + polish from step 0.
                r = (self.kfac.inv_lowrank_rank if method == 'lowrank'
                     else dim)
                stacks[str(dim)] = {
                    'Q': jnp.broadcast_to(jnp.eye(dim, r, dtype=idt),
                                          (n_slots, dim, r)),
                    'd': jnp.ones((n_slots, r), idt)}
                if self._bucket_mixed.get(dim):
                    # Baked per-side damped inverses for mixed layers'
                    # eigen-family sides (zero-seeded; step 0 fires
                    # first).
                    stacks[str(dim)]['inv'] = jnp.zeros(
                        (n_slots, dim, dim), idt)
            else:
                stacks[str(dim)] = {
                    'inv': jnp.zeros((n_slots, dim, dim), idt)}
        diag_inv = {}
        for name in self.assignment.diag_layers:
            a_dim = base['factors'][name]['A'].shape[0]
            diag_inv[name] = jnp.zeros((a_dim,), idt)
        # Grouped convs: replicated per-group block-inverse stacks (the
        # single-chip init already builds the right zero shapes).
        grouped_inv = {name: base['inverses'][name]
                       for name in self.assignment.grouped_layers}
        state = {'step': base['step'], 'factors': base['factors'],
                 'inv_stacks': stacks, 'diag_inv': diag_inv,
                 'grouped_inv': grouped_inv,
                 # Pipelined-firing position (next chunk due; constant 0
                 # under inv_pipeline_chunks=1) — see KFAC.init_state.
                 'inv_chunk_phase': base['inv_chunk_phase']}
        if self.kfac.deferred_factor_reduction \
                or self.kfac.hierarchical_reduce:
            # Per-DEVICE local accumulators (deferred reduce, r14):
            # each device folds its own un-reduced contributions, so
            # the leaves carry a leading device dim sharded over the
            # data axes (state_pspecs) — a replicated spec would
            # silently collapse device-varying values. The decay
            # product is identical on every device (replicated).
            # Hierarchical reduce (r20) accumulates SLICE-mean
            # contributions (post intra-slice pmean), identical within
            # a slice: the leading dim is the slice count, sharded
            # over the slice axis only.
            lead = (self.n_slices if self.kfac.hierarchical_reduce
                    else self.data_size)
            state['factor_accum'] = jax.tree.map(
                lambda x: jnp.zeros((lead,) + x.shape, x.dtype),
                base['factors'])
            state['accum_decay'] = jnp.ones((), jnp.float32)
        if self.kfac.inv_staleness:
            # Replicated window-head factor snapshot (post-reduce
            # factors are replicated like the factors themselves).
            state['frozen_factors'] = jax.tree.map(lambda x: x,
                                                   base['factors'])
        if self.kfac.collect_metrics:
            # Replicated on-device metrics scalars (the single-chip
            # slot; state_pspecs' default P() covers them).
            state['metrics'] = obs_metrics.init_metrics(
                self.kfac.metric_bucket_keys(params))
        return state

    def state_pspecs(self, state: dict) -> dict:
        """PartitionSpecs for a state pytree: stacks row-sharded, rest
        replicated."""
        specs = jax.tree.map(lambda _: P(), state)
        specs['inv_stacks'] = jax.tree.map(
            lambda _: P(self._row_axes), state['inv_stacks'])
        if 'factor_accum' in state:
            # Leading device dim sharded over every data-bearing axis:
            # each device owns exactly its own accumulator slice.
            # Hierarchical reduce: slice-mean accumulators, sharded
            # over the slice axis only (replicated within a slice).
            acc_axes = ((SLICE_AXIS,)
                        if self.kfac.hierarchical_reduce
                        else self.data_axes)
            specs['factor_accum'] = jax.tree.map(
                lambda _: P(acc_axes), state['factor_accum'])
        return specs

    def shard_state(self, state: dict) -> dict:
        """Device-put a host state pytree with its proper shardings."""
        return self._commit(state, self.state_pspecs(state))

    # -- SPMD pipeline stages (call inside shard_map over self.mesh) ----

    def local_factor_contribs(self, captures) -> dict:
        """Per-layer local covariance contributions {name: {'A', 'G'}}.

        The device-local half of the factor update (reference
        compute_factors, preconditioner.py:566-575), split out so gradient
        accumulation can average contributions over micro-batches before
        the mesh ``pmean``. A layer that follows another's A
        (``KFAC.a_followers``) is handed its owner's A statistic, as in
        ``KFAC.factor_contribs``.
        """
        cdt = self.kfac.factor_compute_dtype
        captures = subsample_captures(captures,
                                      self.kfac.factor_batch_fraction)
        out = {}
        for name, spec in self.kfac.specs.items():
            owner = out.get(spec.a_owner)
            if owner is not None:
                tracing.count('kfac/factors/shared_a')
            if spec.kind == EXPERTS:
                out[name] = L.experts_contrib(spec, captures[name], cdt,
                                              a_of=owner)
                continue
            contrib = {
                'A': (owner['A'] if owner is not None
                      else L.compute_a_factor(spec, captures[name]['a'],
                                              compute_dtype=cdt)),
                'G': L.compute_g_factor(spec, captures[name]['g'],
                                        compute_dtype=cdt)}
            extras = L.compute_tied_factor_extras(spec, captures[name],
                                                  compute_dtype=cdt)
            if extras is not None:
                # Tied embedding (attend site): kept as SEPARATE parts
                # through accumulation/pmean because their world/accum
                # rescale differs — 'A_g2' is quadratic in the (local-
                # mean-loss) output grads like 'G'; 'G_a' is
                # activation-derived like 'A' (L.GRAD_QUADRATIC_KEYS).
                # _spmd_update_factors folds them in post-scale.
                contrib.update(extras)
            out[name] = contrib
        return out

    @profiling.scope('kfac/factors')
    def _spmd_update_factors(self, state, contribs, factor_decay):
        """Local covariance contributions, ``pmean``ed over the mesh.

        The analogue of compute_factors + allreduce_factors (reference
        preconditioner.py:566-575,525-533): each device contracts its batch
        shard, one pmean over both axes averages — equal local batch sizes
        make the mean exact.

        G normalization: local captures ``g`` come from the *local*-mean
        loss, so they are ``world_size`` times larger than global-mean-loss
        gradients; G is quadratic in g, hence the ``1 / world_size**2``.
        The reference skips this, making its G scale (and effective
        damping) depend on per-rank batch size — here factors are
        world-size-invariant, so single-device and distributed runs agree
        and hyperparameters transfer across world sizes.
        """
        kfac = self.kfac
        alpha = kfac.factor_decay if factor_decay is None else factor_decay
        g_scale = 1.0 / self.data_size ** 2

        def factor_pmean(m):
            """pmean of a symmetric factor; triangular-packed if enabled.

            Reference symmetry_aware_comm (kfac/layers/base.py:120-125):
            halves the bytes on the wire at the cost of the gather-free
            mask/concat pack+unpack (ops.factors.pack_symmetric).
            Embedding A factors are 1-D (already minimal).
            """
            with profiling.annotate('kfac/comm/factor_allreduce'):
                if kfac.symmetry_aware_comm and m.ndim == 2:
                    packed = jax.lax.pmean(F.pack_symmetric(m),
                                           self.data_axes)
                    return F.unpack_symmetric(packed, m.shape[-1])
                return jax.lax.pmean(m, self.data_axes)

        new_factors = {}
        a_means: dict[str, Any] = {}
        for name, spec in kfac.specs.items():
            # One reduction of an A that several layers share: a
            # follower's contribution is its owner's value.
            a_new = a_means[name] = (
                a_means[spec.a_owner] if spec.a_owner is not None
                else factor_pmean(contribs[name]['A']))
            g_new = g_scale * factor_pmean(contribs[name]['G'])
            if 'A_g2' in contribs[name]:
                # Tied-embedding attend parts: the vocab-side diagonal
                # is grad-quadratic (g_scale corrects the local-mean-
                # loss blowup exactly like 'G'); the d-side input
                # covariance is activation-derived (no rescale, like
                # 'A'). See L.GRAD_QUADRATIC_KEYS.
                a_new = a_new + g_scale * factor_pmean(
                    contribs[name]['A_g2'])
                g_new = g_new + factor_pmean(contribs[name]['G_a'])
            old = state['factors'][name]
            if 'rows' in contribs[name]:
                new_factors[name] = F.experts_running_avg(
                    old, a_new, g_new,
                    jax.lax.pmean(contribs[name]['rows'], self.data_axes),
                    alpha)
                continue
            new_factors[name] = {
                'A': F.update_running_avg(a_new.astype(old['A'].dtype),
                                          old['A'], alpha),
                'G': F.update_running_avg(g_new.astype(old['G'].dtype),
                                          old['G'], alpha)}
        return new_factors

    def _local_combined_contribs(self, contribs) -> dict:
        """World-scale one batch's local contributions into combined
        per-layer ``{'A', 'G'}`` parts.

        The scaling half of :meth:`_spmd_update_factors`, applied
        LOCALLY (every scale is a constant, so scaling before or after
        the mean is the same linear map): grad-quadratic parts ('G',
        tied 'A_g2' — ``L.GRAD_QUADRATIC_KEYS``) get the
        ``1/world**2`` local-mean-loss correction, activation parts
        ('A', 'G_a') none, and the tied extras fold into their sides.
        Feeds the deferred-reduction accumulator, whose boundary pmean
        then needs no per-key bookkeeping.
        """
        g_scale = 1.0 / self.data_size ** 2
        out = {}
        for name in self.kfac.specs:
            c = contribs[name]
            a_new = c['A']
            g_new = g_scale * c['G']
            if 'A_g2' in c:
                a_new = a_new + g_scale * c['A_g2']
                g_new = g_new + c['G_a']
            out[name] = {'A': a_new, 'G': g_new}
        return out

    @profiling.scope('kfac/factors')
    def _spmd_accumulate_factors(self, state, contribs, factor_decay
                                 ) -> tuple[dict, jax.Array]:
        """Deferred-reduction factor step: fold this device's batch
        contribution into ITS slice of the accumulator — NO collective.

        The per-step factor ``pmean`` of the eager path
        (:meth:`_spmd_update_factors`) is exactly what this defers:
        ``acc ← α·acc + (1-α)·c_local`` and ``decay ← α·decay``
        per device; :meth:`_spmd_reduce_factors` pmeans the
        accumulators once per window. By linearity
        ``pmean(Σ w_i c_i) = Σ w_i pmean(c_i)``, so the boundary value
        matches the per-step recursion up to fp associativity
        (test-pinned). Returns ``(new_accum, new_decay)``; inside
        shard_map the accumulator leaves are this device's ``(1, ...)``
        slice of the sharded stack.
        """
        kfac = self.kfac
        alpha = kfac.factor_decay if factor_decay is None else factor_decay
        combined = self._local_combined_contribs(contribs)
        if kfac.hierarchical_reduce:
            # Hierarchical reduce (r20): the intra-slice half of the
            # factor reduction runs EVERY factor step on ICI — after
            # this pmean every device in a slice holds the slice-mean
            # contribution; the inter-slice half (slow DCN) is the
            # deferred window-boundary pmean over SLICE_AXIS only
            # (_spmd_reduce_factors). pmean_slices(pmean_intra(c)) ==
            # pmean_all(c) for uniform shard counts, so the boundary
            # value matches the flat reduce by the same EMA linearity.
            intra = tuple(a for a in self.data_axes if a != SLICE_AXIS)
            with profiling.annotate(
                    'kfac/comm/factor_allreduce_intra'):
                if kfac.symmetry_aware_comm:
                    combined = {
                        name: {k: (F.unpack_symmetric(
                                       jax.lax.pmean(
                                           F.pack_symmetric(v), intra),
                                       v.shape[-1])
                                   if v.ndim == 2
                                   else jax.lax.pmean(v, intra))
                               for k, v in entry.items()}
                        for name, entry in combined.items()}
                else:
                    combined = jax.lax.pmean(combined, intra)
        acc = state['factor_accum']
        new_acc = {}
        for name in kfac.specs:
            old = acc[name]
            new_acc[name] = {
                which: F.update_running_avg(
                    combined[name][which].astype(
                        old[which].dtype)[None],
                    old[which], alpha)
                for which in ('A', 'G')}
        return new_acc, alpha * state['accum_decay']

    @profiling.scope('kfac/factors')
    def _spmd_reduce_factors(self, state, acc, decay) -> dict:
        """Window-boundary deferred reduction: ONE bucketed pmean of
        the whole accumulator tree, then the EMA boundary update.

        This is the single collective that replaces the eager path's
        per-factor-step ``pmean`` (``kfac/comm/factor_reduce`` — the
        r14 overlap win's comm attribution scope). The tree is reduced
        in one ``lax.pmean`` call so XLA buckets the transfers;
        ``symmetry_aware_comm`` packs 2-D matrices before the wire
        exactly like the eager path's ``factor_pmean``.
        """
        kfac = self.kfac

        def pack(m):
            m = m[0]  # this device's slice of the sharded stack
            if kfac.symmetry_aware_comm and m.ndim == 2:
                return F.pack_symmetric(m)
            return m

        packed = {name: {k: pack(v) for k, v in entry.items()}
                  for name, entry in acc.items()}
        if kfac.hierarchical_reduce:
            # r20: the accumulators already hold slice means (the
            # intra-slice ICI pmean ran per factor step), so the
            # boundary collective crosses ONLY the slice axis — this
            # is the one DCN transfer of the whole factor pipeline,
            # attributed separately for the straggler wait buckets.
            with profiling.annotate('kfac/comm/factor_reduce_dcn'):
                reduced = jax.lax.pmean(packed, (SLICE_AXIS,))
        else:
            with profiling.annotate('kfac/comm/factor_reduce'):
                reduced = jax.lax.pmean(packed, self.data_axes)
        new_factors = {}
        for name in kfac.specs:
            old = state['factors'][name]
            entry = {}
            for which in ('A', 'G'):
                r = reduced[name][which]
                if kfac.symmetry_aware_comm and old[which].ndim == 2:
                    r = F.unpack_symmetric(r, old[which].shape[-1])
                entry[which] = (decay * old[which]
                                + r).astype(old[which].dtype)
            new_factors[name] = entry
        return new_factors

    def _owned_slots(self, plan: BucketPlan):
        """The bucket's ``((layer, side), slot)`` pairs whose factor is
        the one stacked and inverted there: all but the A of a layer
        that follows another's (it reads its owner's slot)."""
        follows = self.kfac.a_followers()
        return [(key, at) for key, at in plan.slot.items()
                if not (key[1] == 'A' and key[0] in follows)]

    def _build_bucket_stack(self, factors, plan: BucketPlan) -> jax.Array:
        """Replicated ``(n_rows * slots_per_row, dim, dim)`` factor stack.

        Unassigned (padding) slots hold the identity so the batched
        decomposition stays well-conditioned. The stack keeps the
        factors' storage dtype: the decompositions upcast the slice
        they work on, so a bf16 factor set never has a whole-bucket
        fp32 copy.
        """
        S = plan.slots_per_row
        mats: list[Any] = [None] * (self.total_rows * S)
        for (name, which), slot_idx in self._owned_slots(plan):
            g = self.assignment.layer_row[name] * S + slot_idx
            mats[g] = factors[name][which]
        eye = jnp.eye(plan.dim,
                      dtype=self.kfac.factor_dtype or jnp.float32)
        return jnp.stack([eye if m is None else m for m in mats])

    def _build_bucket_substack(self, factors, plan: BucketPlan,
                               offs) -> jax.Array:
        """Fired-offsets-only factor stack for a partial chunk firing.

        A chunk that fires ``offs`` ⊂ [0, slots_per_col) of a bucket
        needs only those slots' matrices; stacking the whole bucket
        (``_build_bucket_stack``) would pay the full O(n_slots · dim²)
        assembly on every chunk phase — k× the monolithic build cost
        per window (measured as the dominant share of the pipelined
        legs' per-firing overhead on the CPU bench). Layout is
        ``[(row, col, m ∈ offs)]`` so a device's fired slots are the
        contiguous ``(row · n_cols + col) · len(offs)`` slice — the
        same dynamic_slice program shape as the whole-slice path.
        Across one window every slot is built exactly once, matching
        the monolithic firing's total assembly work.
        """
        S = plan.slots_per_row
        s = plan.slots_per_col
        by_global = {}
        for (name, which), slot_idx in self._owned_slots(plan):
            g = self.assignment.layer_row[name] * S + slot_idx
            by_global[g] = factors[name][which]
        eye = jnp.eye(plan.dim,
                      dtype=self.kfac.factor_dtype or jnp.float32)
        mats = []
        for r in range(self.total_rows):
            for c in range(self.n_cols):
                for m in offs:
                    mat = by_global.get(r * S + c * s + int(m))
                    mats.append(eye if mat is None else mat)
        return jnp.stack(mats)

    @profiling.scope('kfac/inverses')
    def _spmd_update_inverses(self, factors, damping, prev_stacks=None,
                              chunk: int | None = None,
                              prev_diag=None, prev_grouped=None):
        """Sharded batched inverse computation + in-group all_gather.

        Each device decomposes its ``slots_per_col`` slice of its row's
        stack (``lax.dynamic_slice`` at a device-dependent offset — the
        SPMD form of "only the assigned rank computes",
        reference kfac/layers/base.py:249,294), then an ``all_gather``
        over ``kfac_gw`` reassembles the row's full inverse stack.

        ``prev_stacks``: the state's previous inverse stacks. On the
        eigen path they hold each slot's previous eigenbasis — this
        device slices *its own slots'* bases (the stacks are
        ``kfac_ig``-sharded and slot layout is static, so the slice
        aligns with the factors being decomposed) and runs the
        warm-start polish instead of a cold eigh (eigh_method 'auto').

        ``chunk``: pipelined firing — decompose only the slot offsets /
        diag / grouped items ``_plan_firing_chunks`` assigns to this
        chunk, passing everything else through from ``prev_stacks`` /
        ``prev_diag`` / ``prev_grouped`` unchanged (local row shards
        in, local row shards out). A bucket whose offsets are split
        across chunks fires partially: each device decomposes only its
        fired slots (a static-offset gather), the in-group all_gather
        moves only those slots, and the results scatter into the
        stored stack at static indices — no collective ever touches a
        non-fired slot, so the amortized COMM_OPT gather shrinks by
        exactly the chunk fraction.

        Scope of the per-chunk-group program shape: it applies to the
        in-run firing path (``prev_stacks`` present), where it makes a
        frozen-factor pipelined window bit-identical to a monolithic
        firing WITHIN this SPMD path. The eager rebuild
        (``prev_stacks=None`` — ``recompute_inverses`` after a
        factor-only/layout-mismatch restore) keeps the historical
        whole-slice program even at ``inv_pipeline_chunks > 1``: there
        are no stored shards to merge into, and no bitwise contract
        spans a rebuild — a rebuilt basis differs from the in-run one
        by the same slice-instability ulps regardless (single-chip vs
        SPMD were never bitwise-comparable either; their stacks batch
        different layer sets by construction). Each slot is simply
        overwritten next time its chunk fires.
        """
        kfac = self.kfac
        chunk_plan = self._chunk_plan
        row = self._global_row()
        col = jax.lax.axis_index(GRAD_WORKER_AXIS)
        eigh_method = resolve_eigh_method(kfac.eigh_method)
        stacks = {}
        for dim, plan in self.assignment.buckets.items():
            s = plan.slots_per_col
            # Offset groups to fire this call. Pipelined mode (k > 1)
            # ALWAYS decomposes per chunk group — a monolithic firing
            # runs every group, a chunk firing exactly one — so the
            # per-slot computation is the same trace fragment either
            # way and the frozen-window bit-identity contract is
            # structural (the backend's batched kernels are NOT
            # slice-stable across batch sizes: a different vmap width
            # rotates Q by O(1) within near-degenerate clusters,
            # observed on CPU). The eager rebuild path (no prev stacks
            # to merge into, ``recompute_inverses``) and k == 1 keep
            # the historical whole-slice program.
            if chunk_plan is None or prev_stacks is None:
                groups = [None] if chunk is None else None
            else:
                per = chunk_plan['offsets'][dim]
                if chunk is not None:
                    fired = per.get(chunk, ())
                    groups = [fired] if fired else []
                else:
                    groups = [per[j] for j in sorted(per)]
            if groups is None:
                raise ValueError(
                    'inv_chunk requires inv_pipeline_chunks > 1 and '
                    'stored inverse stacks')
            if not groups:
                # Not this chunk's work: the stored (row-local) stack
                # passes through untouched — no decomposition, no
                # in-group all_gather.
                stacks[str(dim)] = prev_stacks[str(dim)]
                continue
            # The whole-bucket stack is built ONLY for whole-slice
            # groups (the historical program shape); partial groups
            # assemble just their fired slots (_build_bucket_substack),
            # so a window's k chunk firings pay the monolithic firing's
            # total assembly cost, not k times it.
            full = (self._build_bucket_stack(factors, plan)
                    if any(g is None or len(g) == s for g in groups)
                    else None)
            bucket_method = kfac.method_for_dim(dim)
            prev_entry = (prev_stacks[str(dim)]
                          if prev_stacks is not None else None)
            # A group of all s offsets is the whole contiguous slice —
            # encode as offs=None (dynamic_slice + full replace, the
            # historical program shape).
            cur = dict(prev_entry) if prev_entry is not None else {}
            for group in groups:
                offs = (None if group is None or len(group) == s
                        else np.asarray(group, np.int32))

                def fired_factors(offs=offs):
                    """This device's fired factor matrices (contiguous
                    dynamic_slice of the whole-bucket stack for a
                    whole-slice group, or of the fired-only substack
                    when partial)."""
                    if offs is None:
                        return jax.lax.dynamic_slice(
                            full,
                            (row * plan.slots_per_row + col * s, 0, 0),
                            (s, plan.dim, plan.dim))
                    sub = self._build_bucket_substack(
                        factors, plan, offs)
                    u = len(offs)
                    return jax.lax.dynamic_slice(
                        sub, ((row * self.n_cols + col) * u, 0, 0),
                        (u, plan.dim, plan.dim))

                def local_slots(src, offs=offs):
                    """This device's fired slots of a ROW-LOCAL stored
                    stack (contiguous slice for a whole-slice group;
                    static-offset gather when partial)."""
                    base = col * s
                    if offs is None:
                        start = (base,) + (0,) * (src.ndim - 1)
                        return jax.lax.dynamic_slice(
                            src, start, (s,) + src.shape[1:])
                    return jnp.take(src, base + jnp.asarray(offs),
                                    axis=0)

                def merge(computed, key, offs=offs):
                    """all_gather this group's slots over the grad
                    workers and merge into the stored row stack (full
                    replace for a whole-slice group; static-index
                    scatter when partial)."""
                    with profiling.annotate(
                            'kfac/comm/inverse_allgather'):
                        g = jax.lax.all_gather(
                            computed, GRAD_WORKER_AXIS, tiled=True)
                    g = g.astype(kfac.inv_dtype)
                    if offs is None:
                        cur[key] = g
                        return
                    # Gathered layout: col c's fired slots sit at
                    # g[c*u:(c+1)*u] — their in-row slot indices
                    # c*s + offs are static, so the merge is one
                    # static scatter into the stored shard.
                    idx = np.concatenate(
                        [c * s + offs for c in range(self.n_cols)])
                    cur[key] = cur[key].at[idx].set(g)

                local = fired_factors()
                if eigen_family(bucket_method):
                    local = local.astype(jnp.float32)
                    q_prev = None
                    if prev_entry is not None and (
                            bucket_method == 'lowrank'
                            or eigh_method == 'auto'):
                        # Inside shard_map the stored stack is the
                        # *local* row shard (slots_per_row, dim, dim):
                        # index by the in-row column offset only
                        # (local_slots does). Low-rank warm starts are
                        # NOT gated on eigh_method — the carried
                        # truncated basis IS the low-rank state.
                        q_prev = local_slots(
                            prev_entry['Q'].astype(jnp.float32))
                    if bucket_method == 'lowrank':
                        q, d = linalg.batched_lowrank_eigh(
                            local, kfac.inv_lowrank_rank,
                            q_prev=q_prev,
                            polish_iters=kfac.eigh_polish_iters)
                    else:
                        q, d = linalg.batched_eigh(
                            local, eigh_method, clip=0.0,
                            q_prev=q_prev,
                            polish_iters=kfac.eigh_polish_iters)
                    if self._bucket_mixed.get(dim):
                        # Bake this firing's damping into the mixed
                        # layers' eigen sides (whole group for vmap
                        # uniformity — the extra d^3 per pure-eigen
                        # slot is noise next to the polish). Same λ as
                        # the baked big-side inverses: the split
                        # operator stays symmetric under damping
                        # schedules.
                        inv = jax.vmap(
                            lambda qi, di: linalg.eigen_side_inverse(
                                qi, di, damping))(q, d)
                        merge(inv, 'inv')
                    merge(q, 'Q')
                    merge(d, 'd')
                else:
                    inv = linalg.damped_inverse_stack(
                        local, damping, bucket_method,
                        iters=kfac.newton_iters,
                        out_dtype=kfac.inv_dtype)
                    merge(inv, 'inv')
            stacks[str(dim)] = cur
        diag_inv = {}
        for name in self.assignment.diag_layers:
            if chunk is not None and \
                    chunk_plan['diag'][name] != chunk:
                diag_inv[name] = prev_diag[name]
                continue
            diag_inv[name] = linalg.get_elementwise_inverse(
                factors[name]['A'].astype(jnp.float32),
                damping=damping).astype(kfac.inv_dtype)
        # Replicated per-group block inverses (tiny blocks — replicating
        # beats any sharding bookkeeping); shared helper with the
        # single-chip path so the two cannot drift.
        grouped_inv = {
            name: (prev_grouped[name]
                   if chunk is not None
                   and chunk_plan['grouped'][name] != chunk
                   else grouped_block_inverses(
                       factors[name], damping, kfac.inv_dtype,
                       sides='G' if kfac.specs[name].a_owner else 'AG'))
            for name in self.assignment.grouped_layers}
        return stacks, diag_inv, grouped_inv

    def _global_row(self):
        """This device's GLOBAL inverse-row index (traced scalar).

        Flat mesh: the inverse-group axis index. Multi-slice: slices
        hold contiguous runs of ``n_rows`` rows, matching the
        ``P((SLICE_AXIS, INV_GROUP_AXIS))`` sharding of the stacks —
        no inverse-bearing collective ever crosses the slice axis, so
        the index arithmetic is the only place slices appear in the
        inverse pipeline.
        """
        row = jax.lax.axis_index(INV_GROUP_AXIS)
        if self.sliced:
            row = jax.lax.axis_index(SLICE_AXIS) * self.n_rows + row
        return row

    def _layer_inverses(self, inv_stacks, name: str) -> dict:
        """This device's (row-local) inverse views for one layer.

        Static slot indices are identical across devices (SPMD); rows that
        do not own the layer read a different layer's slot — their result
        is masked to zero before the ``psum`` in ``_spmd_precondition``.
        """
        kfac = self.kfac
        spec = kfac.specs[name]
        a_dim, g_dim = self._shape_of(name)
        # Mixed layers read their eigen side's firing-time-baked dense
        # inverse (same λ as the baked big side); pure-eigen layers
        # read Q/d for the joint-damping formula.
        mixed = self._layer_is_mixed(name)
        out = {}
        if spec.kind != EMBEDDING:
            plan = self.assignment.buckets[a_dim]
            sl = plan.slot[(name, 'A')]
            if eigen_family(kfac.method_for_dim(a_dim)) and not mixed:
                out['QA'] = inv_stacks[str(a_dim)]['Q'][sl]
                out['dA'] = inv_stacks[str(a_dim)]['d'][sl]
            else:
                out['A_inv'] = inv_stacks[str(a_dim)]['inv'][sl]
        plan = self.assignment.buckets[g_dim]
        sl = plan.slot[(name, 'G')]
        if eigen_family(kfac.method_for_dim(g_dim)) and not mixed:
            out['QG'] = inv_stacks[str(g_dim)]['Q'][sl]
            out['dG'] = inv_stacks[str(g_dim)]['d'][sl]
        else:
            out['G_inv'] = inv_stacks[str(g_dim)]['inv'][sl]
        return out

    def _shape_of(self, name):
        return self._factor_dims[name]

    def _rowsharded_precond_mats(self, inv_stacks, grad_mats, damping,
                                 row) -> dict:
        """Row-masked preconditioned mats, computing only this row's
        layers (KAISA grad-worker compute semantics, reference
        preconditioner.py:577-585).

        Per shape group (see :meth:`_plan_precond_groups`): a
        ``lax.switch`` over the static rows stacks exactly this row's
        ``S`` grad matrices, gathers the matching inverse operands from
        the row-local factor stacks by traced slot index, and runs ONE
        vmapped :func:`linalg.precondition_dispatch` over the slice —
        1/n_rows of the replicate-and-mask path's matmul FLOPs. The
        output assembly reuses the same aliased-read + ownership-mask
        trick as :meth:`_layer_inverses`: position ``k`` of the local
        result holds a *different* layer on every row, and the mask
        keeps exactly the owner's value for the delivery ``psum``.
        """
        kfac = self.kfac
        out = {}
        for grp in self._precond_groups:
            g_dim, a_dim = grp['shape']
            s = grp['S']
            slot_name = {gslot: name
                         for name, gslot in grp['slot_of'].items()}

            # lax.switch over the (static) rows: each branch stacks only
            # ITS row's S grad matrices (+ zero padding) and carries the
            # row's inverse slot indices as constants — the full
            # (n_rows*S, g, a) stack is never written, so the stack
            # traffic is 1/n_rows of the dynamic-slice-of-everything
            # form (round-4 review finding). XLA compiles all branches,
            # executes one.
            def make_branch(r):
                def branch():
                    mats = [
                        (grad_mats[slot_name[r * s + k]]
                         .astype(jnp.float32)
                         if (r * s + k) in slot_name
                         else jnp.zeros((g_dim, a_dim), jnp.float32))
                        for k in range(s)]
                    return (jnp.stack(mats),
                            jnp.asarray(grp['a_idx'][r * s:(r + 1) * s]),
                            jnp.asarray(grp['g_idx'][r * s:(r + 1) * s]))
                return branch

            local, my_a, my_g = jax.lax.switch(
                row, [make_branch(r) for r in range(self.total_rows)])
            # Mixed-ness is uniform per group (a function of the dim
            # pair): split groups gather baked inverses for both sides.
            # Eigen-family covers the r19 low-rank buckets too — their
            # rectangular Q/d gather exactly the same way (the group's
            # rank is uniform because its dims are).
            a_eig = eigen_family(kfac.method_for_dim(a_dim))
            g_eig = eigen_family(kfac.method_for_dim(g_dim))
            entry = {}
            if a_eig and g_eig:
                entry['QA'] = inv_stacks[str(a_dim)]['Q'][my_a]
                entry['dA'] = inv_stacks[str(a_dim)]['d'][my_a]
                entry['QG'] = inv_stacks[str(g_dim)]['Q'][my_g]
                entry['dG'] = inv_stacks[str(g_dim)]['d'][my_g]
            else:
                entry['A_inv'] = inv_stacks[str(a_dim)]['inv'][my_a]
                entry['G_inv'] = inv_stacks[str(g_dim)]['inv'][my_g]
            vs = jax.vmap(
                lambda gm, e: linalg.precondition_dispatch(
                    gm, e, damping,
                    compute_dtype=kfac.precond_compute_dtype))(
                local, entry)
            for name, gslot in grp['slot_of'].items():
                mask = (row == self.assignment.layer_row[name]).astype(
                    vs.dtype)
                out[name] = vs[gslot % s] * mask
        return out

    @profiling.scope('kfac/precond')
    def _spmd_precondition(self, inv_stacks, diag_inv, grouped_inv,
                           grads, damping, lr, with_stats: bool = False,
                           gates: dict | None = None):
        """Row-masked preconditioning + one ``psum`` gradient broadcast.

        Every member of a layer's inverse group computes its preconditioned
        gradient redundantly (KAISA's compute/comm tradeoff — the
        reference's grad workers, preconditioner.py:577-585); other rows
        produce zeros, and ``psum`` over ``kfac_ig`` is exactly the
        strided-group gradient broadcast (reference base.py:173-196).
        The KL-clip factor is assembled the same way: row-partial ``v·g``
        sums, ``psum``ed, so the scale matches the single-device path
        bit-for-bit in structure (reference preconditioner.py:661-682).

        ``gates`` (r16 self-healing quarantine): per-shape-bucket 0/1
        traced scalars — a gated-off bucket's layers serve the RAW
        gradient (plain SGD direction). The blend happens on the
        row-masked per-layer mats BEFORE the KL-clip and delivery
        ``psum`` (the SGD fallback carries the same owner-row mask, so
        the psum still sums exactly one contribution and the clip sees
        the blended ``v·g``); replicated scalar gates keep the select
        identical on every device. ``None`` = the bit-identical
        historical path (see ``KFAC.precondition``).
        """
        kfac = self.kfac
        row = self._global_row()
        grad_mats = {
            name: L.grads_to_matrix(spec, _get(grads, spec.path))
            for name, spec in kfac.specs.items()}
        # Bucketed batched precondition matmuls on every mesh shape:
        # with n_rows > 1 each row computes only its own layers (KAISA
        # compute sharding); at n_rows == 1 (COMM_OPT) the same path
        # degenerates to a pure same-shape batching — one vmapped
        # matmul per shape group instead of a per-layer dispatch, the
        # replicated-path analogue of the single-chip
        # KFAC._bucketed_precond_mats.
        precond_mats = (
            self._rowsharded_precond_mats(inv_stacks, grad_mats, damping,
                                          row)
            if self.shard_precond_compute else {})
        for name, spec in kfac.specs.items():
            if name in precond_mats:
                continue  # computed by the row-sharded path
            if spec.kind in BLOCK_STACK_KINDS:
                # Replicated block-stack inverses; batched
                # G_inv @ grad @ A_inv broadcasts over the group dim.
                # Masked to the owning row like every per-layer path so
                # the delivery psum stays a sum of one contribution.
                # (A stack that follows another's A reads its owner's,
                # which its own row holds.)
                inv = grouped_inv[name]
                if spec.a_owner is not None:
                    inv = {**inv,
                           'A_inv': grouped_inv[spec.a_owner]['A_inv']}
            else:
                inv = self._layer_inverses(inv_stacks, name)
            # Same four-way per-side dispatch as the single-chip path
            # (linalg.precondition_dispatch) so 'auto' mixed-method
            # layers cannot drift between the two.
            v = linalg.precondition_dispatch(
                grad_mats[name], inv, damping,
                diag_a=(diag_inv[name] if spec.kind == EMBEDDING
                        else None),
                compute_dtype=kfac.precond_compute_dtype)
            mask = (row == self.assignment.layer_row[name]).astype(v.dtype)
            precond_mats[name] = v * mask

        if gates is not None:
            # Quarantine blend (r16): row-masked SGD fallback so the
            # delivery psum still sums one owner contribution; where is
            # a select, so a poisoned (NaN) preconditioned branch does
            # not propagate into the blended output.
            for name in precond_mats:
                g = gates.get(obs_metrics.shape_key(
                    grad_mats[name].shape))
                if g is None:
                    continue
                pm = precond_mats[name]
                own = (row == self.assignment.layer_row[name]).astype(
                    pm.dtype)
                precond_mats[name] = jnp.where(
                    jnp.asarray(g, jnp.float32) >= 0.5, pm,
                    grad_mats[name].astype(pm.dtype) * own)

        if kfac.kl_clip is not None:
            vg_sum = jnp.zeros((), jnp.float32)
            for name in precond_mats:
                vg_sum += jnp.sum(precond_mats[name] *
                                  grad_mats[name].astype(jnp.float32)
                                  * lr ** 2)
            with profiling.annotate('kfac/comm/klclip_psum'):
                vg_sum = jax.lax.psum(vg_sum, self._row_axes)
            nu = jnp.minimum(
                1.0, jnp.sqrt(kfac.kl_clip / (jnp.abs(vg_sum) + 1e-30)))
        else:
            nu = jnp.ones((), jnp.float32)

        with profiling.annotate('kfac/comm/grad_psum'):
            # The delivery broadcast spans the whole row space — on a
            # multi-slice mesh this is the ONE collective of the
            # inverse/precondition pipeline that crosses the DCN
            # (gradients, not factors or inverses, ride the slow
            # interconnect — arXiv:2206.15143's placement rule).
            precond_mats = jax.lax.psum(precond_mats, self._row_axes)

        # Stats AFTER the delivery psum: every device sees the full
        # preconditioned matrices, so the norms are replicated scalars.
        stats = (obs_metrics.precond_stats(grad_mats, precond_mats, nu)
                 if with_stats else None)
        out = jax.tree.map(lambda x: x, grads)
        for name, spec in kfac.specs.items():
            sub = _get(grads, spec.path)
            new_sub = L.matrix_to_grads(
                spec, (nu * precond_mats[name]).astype(jnp.float32), sub)
            out = _set(out, spec.path, jax.tree.map(
                lambda n, o: n.astype(o.dtype), new_sub, sub))
        return (out, stats) if with_stats else out

    # -- the step -------------------------------------------------------

    def spmd_step(self, state: dict, grads: dict, captures: dict = None, *,
                  contribs: dict = None,
                  damping=None, lr=None, factor_decay=None,
                  factor_update_freq=None, inv_update_freq=None,
                  factor_update: bool | None = None,
                  inv_update: bool | None = None,
                  inv_chunk: int | None = None,
                  factor_reduce: bool = False,
                  factor_snapshot: bool = False,
                  gates: dict | None = None) -> tuple[dict, dict]:
        """One distributed K-FAC update; call inside ``shard_map``.

        Same contract and cadence semantics as :meth:`KFAC.step`
        (reference preconditioner.py:472-523): ``grads`` must be the
        already-averaged global gradients (reference's DDP contract,
        preconditioner.py:479-482); ``captures`` are this device's *local*
        batch shard captures — factor statistics are averaged globally
        inside (the subtle pre-psum/post-psum contract from SURVEY §7).

        ``contribs`` may be passed instead of ``captures``: precomputed
        local factor contributions (from :meth:`local_factor_contribs`),
        e.g. averaged over gradient-accumulation micro-batches (the
        analogue of the reference's ``accumulate_data`` path,
        kfac/layers/base.py:364-379).

        ``factor_update`` / ``inv_update``: static cadence gating — see
        :meth:`KFAC.step`. ``None`` keeps the dynamic ``lax.cond`` form;
        Python bools bake the schedule into the trace (the fast path on
        TPU — a cond whose branch holds the decompositions costs 10-18x
        in XLA layout/copy pathologies around it, measured on v5e).

        ``inv_chunk``: pipelined inverse firing (static, mutually
        exclusive with ``inv_update=True``): recompute only chunk
        ``j``'s buckets this step, pass the rest of the (row-sharded)
        stacks through untouched — see :meth:`KFAC.step` and
        :meth:`_spmd_update_inverses`.

        ``factor_reduce`` / ``factor_snapshot``: the r14 overlap flags
        (deferred window-boundary factor reduction / frozen-snapshot
        refresh) — static-cadence only, same contract as
        :meth:`KFAC.step`.

        ``gates``: per-shape-bucket quarantine mask (r16 self-healing,
        traced scalar values) — see :meth:`_spmd_precondition`;
        ``None`` (default) keeps the historical program bit-identical.
        """
        kfac = self.kfac
        damping = kfac.damping if damping is None else damping
        lr = kfac.lr if lr is None else lr
        f_freq = (kfac.factor_update_freq if factor_update_freq is None
                  else factor_update_freq)
        i_freq = (kfac.inv_update_freq if inv_update_freq is None
                  else inv_update_freq)
        step = state['step']
        if contribs is None and captures is None:
            raise ValueError('pass captures or contribs')

        def do_factors():
            # Contraction stays inside the gated path: covariance work
            # only runs on factor-update steps.
            return self._spmd_update_factors(
                state,
                (contribs if contribs is not None
                 else self.local_factor_contribs(captures)),
                factor_decay)

        track = kfac.collect_metrics or kfac.nonfinite_guard
        overlap_state = {}
        if kfac.deferred_factor_reduction or kfac.hierarchical_reduce:
            # Deferred reduce (r14): factor steps fold into this
            # device's local accumulator slice — no collective; the
            # window-boundary reduce step pays ONE bucketed pmean.
            # Hierarchical reduce (r20) shares the window machinery:
            # factor steps additionally pmean intra-slice on ICI, and
            # the boundary pmean crosses only the slice axis (DCN).
            # Static cadence only (the reduce is program structure).
            if factor_update is None:
                raise ValueError(
                    'deferred_factor_reduction / hierarchical_reduce '
                    'require static cadence '
                    'flags (Python-bool factor_update/factor_reduce) — '
                    'the window-boundary reduce is static program '
                    'structure, like inv_chunk')
            acc, decay = state['factor_accum'], state['accum_decay']
            if factor_update:
                acc, decay = self._spmd_accumulate_factors(
                    state,
                    (contribs if contribs is not None
                     else self.local_factor_contribs(captures)),
                    factor_decay)
            if factor_reduce:
                candidate = self._spmd_reduce_factors(state, acc, decay)
                # Post-pmean candidate check: collective-safe (every
                # device sees the same averaged values), exactly like
                # the eager path's guard — moved to the reduce point.
                factors, finite_f = guard_nonfinite_factors(
                    candidate, state['factors'], kfac.nonfinite_guard)
                acc = jax.tree.map(jnp.zeros_like, acc)
                decay = jnp.ones((), jnp.float32)
            else:
                factors = state['factors']
                finite_f = jnp.ones((), jnp.int32)
            overlap_state['factor_accum'] = acc
            overlap_state['accum_decay'] = decay
        else:
            if factor_reduce:
                raise ValueError(
                    'factor_reduce requires '
                    'deferred_factor_reduction=True or '
                    'hierarchical_reduce=True')
            if track:
                # Tracked form: finiteness of the candidate factors
                # rides out of the gate (guard skip + metrics count);
                # semantics shared with the single-chip step via
                # preconditioner.guard_nonfinite_factors.
                def do_factors_tracked():
                    return guard_nonfinite_factors(
                        do_factors(), state['factors'],
                        kfac.nonfinite_guard)

                factors, finite_f = cadence_gate(
                    factor_update, step, f_freq, do_factors_tracked,
                    lambda: (state['factors'], jnp.ones((), jnp.int32)))
            else:
                # Metrics/guard off: the historical program, untouched.
                factors = cadence_gate(factor_update, step, f_freq,
                                       do_factors,
                                       lambda: state['factors'])
        if kfac.inv_staleness:
            if inv_update is None:
                raise ValueError(
                    'inv_staleness=1 requires static cadence flags '
                    '(the frozen-snapshot firing schedule is static '
                    'program structure, like inv_chunk)')
            # Window heads (and monolithic firings — the step-0
            # warmup) refresh the snapshot from this step's
            # post-update factors; in-window chunk firings decompose
            # the carried one, breaking the data dependency on this
            # step's forward/backward/factor work.
            frozen = (factors if factor_snapshot or inv_update
                      else state['frozen_factors'])
            overlap_state['frozen_factors'] = frozen
            fire_factors = frozen
        else:
            if factor_snapshot:
                raise ValueError(
                    'factor_snapshot requires inv_staleness=1')
            fire_factors = factors
        if inv_chunk is not None:
            k = kfac.inv_pipeline_chunks
            if inv_update:
                raise ValueError(
                    'inv_chunk is mutually exclusive with '
                    'inv_update=True (a monolithic firing already '
                    'covers every chunk)')
            if not 0 <= inv_chunk < k:
                raise ValueError(
                    f'{inv_chunk=} out of range for '
                    f'inv_pipeline_chunks={k}')
            with profiling.annotate(f'kfac/inverse/chunk{inv_chunk}'):
                inv_stacks, diag_inv, grouped_inv = (
                    self._spmd_update_inverses(
                        fire_factors, damping,
                        prev_stacks=state['inv_stacks'],
                        chunk=inv_chunk,
                        prev_diag=state['diag_inv'],
                        prev_grouped=state.get('grouped_inv', {})))
            chunk_phase = jnp.asarray((inv_chunk + 1) % k, jnp.int32)
        else:
            inv_stacks, diag_inv, grouped_inv = cadence_gate(
                inv_update, step, i_freq,
                lambda: self._spmd_update_inverses(
                    fire_factors, damping,
                    prev_stacks=state['inv_stacks']),
                lambda: (state['inv_stacks'], state['diag_inv'],
                         state.get('grouped_inv', {})))
            chunk_phase = (jnp.zeros((), jnp.int32) if inv_update
                           else state['inv_chunk_phase'])

        if factor_update is True and any(
                spec.num_calls > 1 for spec in kfac.specs.values()):
            # A layer applied several times a step (a looped decoder's
            # every matrix) hands the factor stage one capture pair a
            # CALL. Where no inverse fires, nothing downstream reads the
            # new factors, and XLA schedules their contractions last:
            # every call's captures then outlive the precondition and
            # the optimizer (15.35 GiB against 14.76 at 4 layers x 4
            # passes, compiled for a v5e's 15.75; PERF.md section 6,
            # PR 34). Tie the gradients to
            # the new factors so that the captures are dead before the
            # precondition starts. A model in which no module is called
            # twice traces the program it traced before.
            factors, grads = jax.lax.optimization_barrier((factors, grads))
        if not kfac.collect_metrics:
            precond = self._spmd_precondition(
                inv_stacks, diag_inv, grouped_inv, grads, damping, lr,
                gates=gates)
            new_state = {'step': step + 1, 'factors': factors,
                         'inv_stacks': inv_stacks, 'diag_inv': diag_inv,
                         'grouped_inv': grouped_inv,
                         'inv_chunk_phase': chunk_phase,
                         **overlap_state}
            return precond, new_state

        precond, stats = self._spmd_precondition(
            inv_stacks, diag_inv, grouped_inv, grads, damping, lr,
            with_stats=True, gates=gates)
        one = lambda: jnp.ones((), jnp.int32)
        zero = lambda: jnp.zeros((), jnp.int32)
        did_f = cadence_gate(factor_update, step, f_freq, one, zero)
        did_i = (zero() if inv_chunk is not None
                 else cadence_gate(inv_update, step, i_freq, one, zero))
        did_c = one() if inv_chunk is not None else zero()
        # Row-local clip counts summed over inverse groups: each row's
        # stacks hold only its own layers' spectra (columns agree after
        # the in-group all_gather), so one psum yields the global count.
        eig_clipped = jax.lax.psum(
            obs_metrics.count_clipped_eigvals_stacks(inv_stacks),
            self._row_axes)
        new_state = {'step': step + 1, 'factors': factors,
                     'inv_stacks': inv_stacks, 'diag_inv': diag_inv,
                     'grouped_inv': grouped_inv,
                     'inv_chunk_phase': chunk_phase,
                     **overlap_state,
                     'metrics': obs_metrics.update_metrics(
                         state['metrics'], damping=damping, stats=stats,
                         did_factor=did_f, did_inv=did_i,
                         did_chunk=did_c,
                         factor_finite=finite_f,
                         eig_clipped=eig_clipped)}
        return precond, new_state

    # -- checkpointing --------------------------------------------------

    def state_dict(self, state: dict, include_inverses: bool = True
                   ) -> dict:
        """Checkpointable state: step + factors (+ inverse stacks).

        Unlike the reference (which recomputes inverses on load and
        refuses to save them under MEM_OPT, preconditioner.py:294-353),
        inverse stacks default to *included*: orbax writes each device's
        shard, so no rank pays for the whole stack and resume needs no
        recompute. Pass ``include_inverses=False`` for reference-style
        factor-only checkpoints, then call :meth:`recompute_inverses`
        after restoring.
        """
        out = {'step': state['step'], 'factors': state['factors'],
               'inv_chunk_phase': state.get(
                   'inv_chunk_phase', jnp.zeros((), jnp.int32))}
        # r14 overlap state (deferred accumulators are device-sharded;
        # orbax writes each device's slice): present only when the
        # knobs are on — default checkpoints keep the historical
        # layout (MIGRATION.md).
        for key in ('factor_accum', 'accum_decay', 'frozen_factors'):
            if key in state:
                out[key] = state[key]
        if include_inverses:
            out['inv_stacks'] = state['inv_stacks']
            out['diag_inv'] = state['diag_inv']
            out['grouped_inv'] = state.get('grouped_inv', {})
        return out

    def load_state_dict(self, sd: dict, params, *,
                        damping: float | None = None) -> dict:
        """Rebuild full distributed state from a checkpoint tree.

        Validates layer congruence (reference preconditioner.py:334-336)
        and recomputes inverses from factors when they were not saved.
        """
        state = self.init_state(params)
        if set(sd['factors']) != set(state['factors']):
            raise ValueError(
                'checkpoint layers do not match registered layers: '
                f'{sorted(sd["factors"])} vs {sorted(state["factors"])}')
        state = {**state, 'step': jnp.asarray(sd['step'], jnp.int32),
                 'factors': sd['factors'],
                 # Pre-r9 checkpoints: default the pipeline position to
                 # 0 — always safe, the engine re-derives the chunk
                 # schedule from the step counter (MIGRATION.md).
                 'inv_chunk_phase': jnp.asarray(
                     sd.get('inv_chunk_phase', 0), jnp.int32)}
        from distributed_kfac_pytorch_tpu.preconditioner import (
            _overlay_overlap_state,
        )
        state = _overlay_overlap_state(state, sd)
        # Layout compatibility: a checkpoint written under a different
        # inverse dispatch (e.g. 'eigen' stacks loaded into an 'auto'
        # config whose large buckets are 'inv'-typed) — or under a
        # DIFFERENT mesh topology, whose slot stacks have other shapes
        # (the elastic resume path reshards them BEFORE calling here;
        # anything that reaches this check mismatched is rebuilt) — is
        # recomputed from the replicated factors rather than spliced in
        # structurally mismatched. Shapes matter as much as key sets: a
        # 4-device stack spliced into an 8-device program would feed
        # out-of-range (silently clamped) dynamic-slice offsets.
        compatible = 'inv_stacks' in sd and all(
            set(sd['inv_stacks'].get(k, ())) == set(state['inv_stacks'][k])
            and all(tuple(np.shape(sd['inv_stacks'][k][n]))
                    == tuple(state['inv_stacks'][k][n].shape)
                    for n in state['inv_stacks'][k])
            for k in state['inv_stacks'])
        if compatible and not self._degenerate_stacks(sd['inv_stacks']):
            # (A checkpoint from before layers shared an A carries an
            # ``A_inv`` for every stack: a follower's is dropped.)
            grouped = sd.get('grouped_inv', state['grouped_inv'])
            state = {**state, 'inv_stacks': sd['inv_stacks'],
                     'diag_inv': sd['diag_inv'],
                     'grouped_inv': {
                         name: {k: grouped[name][k] for k in entry}
                         for name, entry in state['grouped_inv'].items()}}
        else:
            state = self.recompute_inverses(state, damping=damping)
        return self._commit(state, self.state_pspecs(state))

    def _commit(self, tree, specs):
        """Commit host or mis-placed leaves of ``tree`` to ``self.mesh``
        under ``specs`` (row-sharded stacks included).

        A checkpoint restored WITHOUT ``like=`` (or against a template
        whose leaves were uncommitted init arrays) hands back host or
        single-device arrays with the proper shardings lost (see
        ``CheckpointManager.restore``); spliced into the state
        uncommitted they would be re-sharded lazily on first jitted
        use — and row-sharded inverse stacks would transit as full
        replicated arrays first, which on multi-host is an outright
        placement error. Leaves already carrying their target sharding
        ON THIS MESH pass through untouched, so a fully-placed like=
        restore costs nothing. The mesh is part of the test even where
        the layout is the same (one device): jit caches a program under
        its inputs' types, an array's type names the mesh it is
        committed to, and the step's outputs are committed to this one.
        Single-process: a plain ``device_put`` per mis-placed leaf.
        Multi-host: a mis-placed-but-addressable leaf is a full
        per-process copy (the restore template carried global shapes),
        so the global array is rebuilt from it per device shard via
        ``make_array_from_callback`` — ``device_put`` cannot target
        non-addressable shardings; a NON-addressable leaf with a
        merely different layout is left for the step to reshard.
        """
        multiprocess = jax.process_count() > 1

        def place(x, spec):
            if x is None:
                return x
            target = NamedSharding(self.mesh, spec)
            have = getattr(x, 'sharding', None)
            if (isinstance(have, NamedSharding)
                    and have.mesh == self.mesh
                    and have.is_equivalent_to(target, x.ndim)):
                return x
            if multiprocess:
                if not getattr(x, 'is_fully_addressable', True):
                    return x
                arr = np.asarray(x)
                return jax.make_array_from_callback(
                    arr.shape, target, lambda idx: arr[idx])
            return jax.device_put(jnp.asarray(x), target)

        return jax.tree.map(place, tree, specs,
                            is_leaf=lambda x: x is None)

    def _degenerate_stacks(self, inv_stacks: dict) -> bool:
        """True if any stored eigenbasis stack is unusable (all-zero).

        Pre-warm-eigh checkpoints stored zero-initialized Q stacks;
        Q=0 is a fixed point of the warm polish, so such checkpoints
        must be rebuilt from factors instead of warm-started. Shares
        :func:`preconditioner.q_stack_degenerate` (multi-host safe:
        inspects addressable shards only). Under 'auto' dispatch only
        the eigen buckets carry Q stacks — only those are checked.
        """
        return any(q_stack_degenerate(entry['Q'])
                   for entry in inv_stacks.values() if 'Q' in entry)

    def recompute_inverses(self, state: dict,
                           damping: float | None = None) -> dict:
        """Eagerly recompute all inverse stacks from current factors.

        The distributed analogue of the reference's post-load
        ``compute_inverses`` + broadcast (preconditioner.py:347-353).
        """
        damping = self.kfac.damping if damping is None else damping
        kspecs = self.state_pspecs(state)

        def compute(factors):
            return self._spmd_update_inverses(factors, damping)

        stacks, diag, grouped = jax.jit(jax.shard_map(
            compute, mesh=self.mesh,
            in_specs=(jax.tree.map(lambda _: P(), state['factors']),),
            out_specs=(kspecs['inv_stacks'],
                       jax.tree.map(lambda _: P(), state['diag_inv']),
                       jax.tree.map(lambda _: P(),
                                    state.get('grouped_inv', {}))),
            check_vma=False))(state['factors'])
        return {**state, 'inv_stacks': stacks, 'diag_inv': diag,
                'grouped_inv': grouped}

    # -- straggler probe (r10 observability) ---------------------------

    def build_barrier_probe(self):
        """Host-side pre-collective barrier-wait probe for this mesh.

        Returns ``probe() -> wait_ms``: a minimal psum over the same
        data axes every K-FAC collective in this module reduces over
        (the factor ``pmean``, the in-group inverse ``all_gather``,
        the gradient/KL ``psum`` — COMM_OPT and KAISA alike), blocked
        on from the host. Since the device stream is in-order, the
        measured wall time is own-queue drain plus the wait for the
        slowest participant — the wait this host's next collective
        would pay. Compiled+warmed here; see
        ``observability.stragglers`` for semantics and cost (the probe
        serializes host dispatch with device completion — opt-in via
        ``--straggler-shards``).
        """
        from distributed_kfac_pytorch_tpu.observability import (
            stragglers,
        )
        return stragglers.build_barrier_probe(self.mesh,
                                              self.data_axes)

    # -- full train step builder ---------------------------------------

    def build_train_step(self, loss_fn, tx, *, model_args_fn=None,
                         model_kwargs_fn=None,
                         metrics_fn=None,
                         mutable_cols: Sequence[str] = (),
                         batch_spec: P | None = None,
                         donate: bool = True,
                         grad_accum_steps: int = 1,
                         loss_scale=None):
        """Jitted data-parallel train step with distributed K-FAC.

        The functional analogue of the reference training engine step
        (examples/cnn_utils/engine.py:29-83): forward/backward with
        capture, gradient pmean, K-FAC preconditioning, then the wrapped
        optax transformation (the reference applies SGD after KFAC.step,
        engine.py:74-82).

        Args:
          loss_fn: ``loss_fn(model_out, batch) -> scalar`` mean loss over
            the (local) batch.
          tx: optax GradientTransformation applied to the preconditioned
            gradients.
          model_args_fn: maps a batch pytree to the model's positional
            args; default ``batch[0],`` (i.e. ``(x, y)`` batches).
          model_kwargs_fn: optional ``batch -> kwargs dict`` evaluated
            *inside* the shard_map, so it may use ``jax.lax.axis_index``
            — e.g. a sequence-parallel LM's ``pos_offset`` (the global
            start of this device's sequence block).
          metrics_fn: optional ``metrics_fn(model_out, batch) -> dict`` of
            scalars, globally averaged and merged into the returned
            metrics (e.g. train accuracy, reference engine.py:81-83).
          mutable_cols: flax variable collections updated in the forward
            pass (e.g. ``('batch_stats',)``); their updates are
            ``pmean``ed (synchronized batch statistics).
          batch_spec: PartitionSpec of every batch leaf (or a pytree of
            specs matching the batch, e.g. to keep a per-step dropout key
            replicated while data is sharded); defaults to batch-dim
            sharding over both K-FAC mesh axes.
          grad_accum_steps: micro-batch count per step. The per-device
            batch shard is split into this many micro-batches processed
            sequentially under ``lax.scan``, averaging gradients and
            factor contributions — the analogue of the reference's
            ``batches_per_allreduce`` sub-batch loop with ``no_sync`` and
            hook-data accumulation (engine.py:33-65, base.py:364-379).
            Peak activation memory drops by ~the accumulation factor;
            numerics match the single-pass step up to fp associativity
            (G contributions carry the exact ``1/accum**2`` loss-scale
            correction).
          loss_scale: fp16 loss scaling. A float is a FIXED scale
            forwarded to ``KFACCapture.loss_and_grads`` (grads and
            output-grad captures are unscaled before any factor
            statistics). The string ``'dynamic'`` enables the full
            GradScaler-parity schedule (reference engine.py:38-41,
            75-80): the live scale is read from
            ``extra_vars['loss_scale']`` (seed with
            ``fp16.init_loss_scale()``), non-finite captures are zeroed
            before factor statistics, the parameter/optimizer update is
            skipped collectively on any non-finite gradient, and the
            scale state backs off / grows per ``fp16.update_loss_scale``.
            Metrics gain ``loss_scale`` and ``overflow``.

        Returns a function
        ``step(params, opt_state, kfac_state, extra_vars, batch, hyper)
        -> (params, opt_state, kfac_state, extra_vars, metrics)`` where
        ``hyper`` is a dict with 'lr', 'damping', 'factor_update_freq',
        'inv_update_freq', 'factor_decay' scalars (all dynamic).
        """
        if model_args_fn is None:
            model_args_fn = lambda batch: (batch[0],)
        if batch_spec is None:
            batch_spec = P(self.batch_axes)
        if grad_accum_steps < 1:
            raise ValueError(f'{grad_accum_steps=} must be >= 1')
        capture = self.kfac.capture
        mutable_cols = tuple(mutable_cols)

        dynamic_ls = loss_scale == 'dynamic'
        static_ls = None if dynamic_ls else loss_scale
        if dynamic_ls and any(s.kind == EXPERTS
                              for s in self.kfac.specs.values()):
            # fp16.sanitize_captures drops a capture that holds any
            # non-finite element, and a stacked-expert layer's captures
            # are undefined past their routed rows (modules.experts).
            raise ValueError(
                "loss_scale='dynamic' does not support stacked-expert "
                'layers: their captures are undefined past the routed '
                'rows, which the overflow hygiene would read as overflow')

        def fwd_bwd(params, extra_vars, batch, scale=None,
                    do_capture=True):
            """One micro/full-batch pass -> (loss, metrics, grads,
            contribs, updated_vars).

            ``do_capture=False`` is the static-cadence non-factor-step
            fast path: plain autodiff, no interception (the reference
            gates its hooks off on those steps the same way —
            _periodic_hook, kfac/preconditioner.py:684-699)."""
            def wrapped_loss(out):
                extra = metrics_fn(out, batch) if metrics_fn else {}
                return loss_fn(out, batch), extra

            kwargs = model_kwargs_fn(batch) if model_kwargs_fn else {}
            with jax.named_scope('kfac_step/fwd_bwd'):
                loss, extra_metrics, grads, captures, updated = (
                    capture.loss_and_grads(
                        wrapped_loss, params, *model_args_fn(batch),
                        extra_vars=extra_vars, mutable_cols=mutable_cols,
                        has_aux=True,
                        loss_scale=static_ls if scale is None else scale,
                        intercept=do_capture,
                        **kwargs))
            if dynamic_ls and captures:
                # Reference hook behavior under GradScaler: non-finite
                # grad-output tensors are dropped before factor
                # statistics (kfac/layers/base.py:397-407); the SPMD
                # form zeroes them (fp16.sanitize_captures). Steps whose
                # *gradients* overflow are skipped wholesale in
                # local_step — this sanitize covers the residual case of
                # a non-finite per-call capture inside an otherwise
                # finite step (e.g. one timestep of a multi-call layer),
                # keeping the factor math NaN-free without poisoning the
                # EWMA.
                captures, _ = fp16_ops.sanitize_captures(captures)
            return loss, extra_metrics, grads, captures, updated

        def accum_fwd_bwd(params, extra_vars, batch, do_factors,
                          scale=None):
            """Scan over micro-batches, averaging grads/contribs/metrics.

            Captures are reduced to factor contributions inside the scan
            so memory stays flat in the accumulation count (unlike the
            reference, whose hook buffers grow linearly, README.md:144-148);
            the contraction itself is gated on ``do_factors`` so
            non-factor-update steps skip the covariance work, like the
            single-pass path's in-cond contraction.
            """
            specs = normalize_batch_specs(batch_spec, batch)

            def split(x, spec):
                if spec == P():
                    # Fully-replicated per-step leaf (e.g. a dropout PRNG
                    # key): identical for every micro-batch, not sliced.
                    return jnp.broadcast_to(x[None],
                                            (grad_accum_steps,) + x.shape)
                if x.shape[0] % grad_accum_steps:
                    raise ValueError(
                        f'per-device batch shard of {x.shape[0]} is not '
                        f'divisible by {grad_accum_steps=}')
                return x.reshape((grad_accum_steps,
                                  x.shape[0] // grad_accum_steps)
                                 + x.shape[1:])

            micro = jax.tree.map(split, batch, specs)
            first = jax.tree.map(lambda x: x[0], micro)
            loss_sh, extras_sh, grads_sh, captures_sh, _ = jax.eval_shape(
                fwd_bwd, params, extra_vars, first, scale)
            contribs_sh = jax.eval_shape(self.local_factor_contribs,
                                         captures_sh)
            zeros = lambda sh: jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), sh)

            # Running sums live in the carry so peak memory stays at one
            # micro-batch (a stacked scan output would materialize
            # accum x every grad/contrib leaf before the reduction).
            def body(carry, mb):
                extra_c, sums = carry
                loss, extra_metrics, grads, captures, updated = fwd_bwd(
                    params, extra_c, mb, scale,
                    do_capture=do_factors is not False)
                if isinstance(do_factors, bool):
                    # Static cadence: the contraction is simply present or
                    # absent from this program variant.
                    contribs = (self.local_factor_contribs(captures)
                                if do_factors else zeros(contribs_sh))
                else:
                    contribs = jax.lax.cond(
                        do_factors,
                        lambda: self.local_factor_contribs(captures),
                        lambda: zeros(contribs_sh))
                new_sums = jax.tree.map(
                    jnp.add, sums, (loss, extra_metrics, grads, contribs))
                new_extra = ({**extra_c, **updated} if updated
                             else extra_c)
                return (new_extra, new_sums), None

            init = (extra_vars, (zeros(loss_sh), zeros(extras_sh),
                                 zeros(grads_sh), zeros(contribs_sh)))
            (extra_out, sums), _ = jax.lax.scan(body, init, micro)
            loss_sum, extras_sum, grads_sum, contribs_sum = sums
            inv_n = 1.0 / grad_accum_steps
            mean = lambda t: jax.tree.map(lambda x: x * inv_n, t)
            # g captures come from the micro-mean loss: accum x larger
            # than the local-batch-mean-loss g; grad-QUADRATIC contrib
            # parts ('G', and a tied embedding's 'A_g2' — see
            # L.GRAD_QUADRATIC_KEYS) get the 1/accum**2 correction;
            # activation-derived parts ('A', 'G_a') only the mean.
            g_fix = 1.0 / grad_accum_steps ** 2
            contribs = {
                name: {k: (g_fix if k in L.GRAD_QUADRATIC_KEYS
                           else 1.0) * v * inv_n
                       for k, v in c.items()}
                for name, c in contribs_sum.items()}
            updated = ({c: extra_out[c] for c in mutable_cols
                        if c in extra_out} if mutable_cols else {})
            return (mean(loss_sum), mean(extras_sum), mean(grads_sum),
                    contribs, updated)

        def make_local_step(factor_update, inv_update, inv_chunk,
                            factor_reduce=False, factor_snapshot=False):
            def local_step(params, opt_state, kstate, extra_vars, batch,
                           hyper):
                if dynamic_ls:
                    if 'loss_scale' not in extra_vars:
                        raise ValueError(
                            "loss_scale='dynamic' requires a loss-scale "
                            "state in extra_vars['loss_scale'] — seed it "
                            'with fp16.init_loss_scale()')
                    ls_state = extra_vars['loss_scale']
                    scale = ls_state['scale']
                else:
                    scale = None
                if grad_accum_steps == 1:
                    # Static factor_update=False: skip the capture
                    # machinery entirely — its cost is NOT dead-code-
                    # eliminated by XLA when captures go unused
                    # (measured +2.7 ms/iter, ResNet-50 @224 b64).
                    loss, extra_metrics, grads, captures, updated = fwd_bwd(
                        params, extra_vars, batch, scale,
                        do_capture=factor_update is not False)
                    contribs = None
                else:
                    if factor_update is not None:
                        do_factors = factor_update
                    else:
                        f_freq = hyper.get('factor_update_freq')
                        if f_freq is None:
                            f_freq = self.kfac.factor_update_freq
                        do_factors = kstate['step'] % f_freq == 0
                    loss, extra_metrics, grads, contribs, updated = (
                        accum_fwd_bwd(params, extra_vars, batch, do_factors,
                                      scale))
                    captures = None
                grads = jax.lax.pmean(grads, self.data_axes)
                loss = jax.lax.pmean(loss, self.data_axes)
                metrics = {'loss': loss,
                           **jax.lax.pmean(extra_metrics, self.data_axes)}
                if captures:
                    metrics.update(obs_metrics.moe_row_metrics(
                        self.kfac.specs, captures, self.data_axes))
                precond, new_kstate = self.spmd_step(
                    kstate, grads, captures, contribs=contribs,
                    damping=hyper['damping'], lr=hyper['lr'],
                    factor_decay=hyper.get('factor_decay'),
                    factor_update_freq=hyper.get('factor_update_freq'),
                    inv_update_freq=hyper.get('inv_update_freq'),
                    factor_update=factor_update, inv_update=inv_update,
                    inv_chunk=inv_chunk, factor_reduce=factor_reduce,
                    factor_snapshot=factor_snapshot,
                    # r16 self-healing quarantine gates ride in hyper
                    # (replicated traced scalars) — present exactly
                    # when the ladder is armed; the dict-structure
                    # check is static, so the unarmed program is
                    # byte-for-byte the historical one.
                    gates=hyper.get('bucket_gate'))
                with jax.named_scope('kfac_step/optimizer'):
                    updates, new_opt_state = tx.update(precond, opt_state,
                                                       params)
                    new_params = jax.tree.map(
                        lambda p, u: (p + u).astype(p.dtype), params,
                        updates)
                if dynamic_ls:
                    # GradScaler semantics (reference engine.py:75-80):
                    # on non-finite gradients skip the entire state
                    # advance — params, optimizer, K-FAC factor/inverse
                    # content (a zeroed-capture EWMA update would shrink
                    # factors toward zero at full weight), and the
                    # mutable collections (BN running stats computed
                    # from a non-finite forward would be poisoned
                    # forever: momentum*NaN stays NaN). Only the K-FAC
                    # step counter and the loss-scale state advance, so
                    # the static-cadence phase stays aligned with the
                    # host counter. The pmean above propagates any
                    # device's non-finite values to all devices, so the
                    # skip is collective.
                    finite = fp16_ops.tree_all_finite(grads)
                    new_params, new_opt_state = fp16_ops.apply_if_finite(
                        finite, (new_params, new_opt_state),
                        (params, opt_state))
                    new_kstate = {
                        **fp16_ops.apply_if_finite(finite, new_kstate,
                                                   kstate),
                        'step': new_kstate['step']}
                    if updated:
                        # A collection first *created* during apply has
                        # no incoming value to fall back to on an
                        # overflow-skipped step, and jit's static output
                        # structure forbids dropping it conditionally —
                        # keeping the new value would let a non-finite
                        # first step poison e.g. BN running stats
                        # forever. Demand the seed loudly (ADVICE r3
                        # flagged the former bare KeyError here).
                        missing = [c for c in updated
                                   if c not in extra_vars]
                        if missing:
                            raise ValueError(
                                f'mutable collections {missing} are '
                                'created inside the step but absent '
                                "from extra_vars; with loss_scale="
                                "'dynamic' the overflow-skip needs "
                                'their incoming values — seed them '
                                'from model.init() (e.g. '
                                "extra_vars['batch_stats'] = "
                                "variables['batch_stats'])")
                        updated = fp16_ops.apply_if_finite(
                            finite, updated,
                            {c: extra_vars[c] for c in updated})
                    extra_vars = {
                        **extra_vars,
                        'loss_scale': fp16_ops.update_loss_scale(
                            ls_state, finite)}
                    metrics = {**metrics, 'loss_scale': scale,
                               'overflow': 1.0
                               - finite.astype(jnp.float32)}
                if updated:
                    extra_vars = {**extra_vars,
                                  **jax.lax.pmean(updated, self.data_axes)}
                if self.kfac.collect_metrics:
                    # Expose the on-device K-FAC metrics in the step's
                    # metrics dict (replicated scalars — flows through
                    # the P() out-spec): the engine's sink drains these
                    # asynchronously, and the epoch meters average them
                    # like any other metric.
                    metrics = {**metrics, **obs_metrics.flatten_metrics(
                        new_kstate['metrics'])}
                return (new_params, new_opt_state, new_kstate, extra_vars,
                        metrics)
            return local_step

        def make_step_impl(factor_update, inv_update, inv_chunk,
                           factor_reduce=False, factor_snapshot=False):
            key = _variant_key(factor_update, inv_update, inv_chunk,
                               factor_reduce, factor_snapshot)

            def step_impl(params, opt_state, kstate, extra_vars, batch,
                          hyper):
                # Host-side trace tally: this body re-executes exactly
                # when jax retraces the variant, so the count pins
                # PERF.md pitfall 3 (one compile per flag combination,
                # ever) — asserted by the retrace-guard test. A count
                # above 1 additionally queues a 'retrace' telemetry
                # event (drained into the metrics stream by the
                # engine): the offline echo of the same contract, so a
                # recorded run can be audited for mid-run recompiles
                # (observability.gate regresses the count against 0).
                n = trace_counts.get(key, 0) + 1
                trace_counts[key] = n
                if n > 1:
                    tracing.count('kfac/retraces')
                    compile_events.append(
                        {'event': 'retrace',
                         'variant': _variant_label(key),
                         'trace_count': n})
                kspecs = self.state_pspecs(kstate)
                batch_specs = normalize_batch_specs(batch_spec, batch)
                state_specs = (replicated_specs(params),
                               replicated_specs(opt_state), kspecs,
                               replicated_specs(extra_vars))
                in_specs = (*state_specs, batch_specs,
                            replicated_specs(hyper))
                # metrics dict: a P() prefix covers any keys
                out_specs = (*state_specs, P())
                fn = jax.shard_map(
                    make_local_step(factor_update, inv_update,
                                    inv_chunk, factor_reduce,
                                    factor_snapshot),
                    mesh=self.mesh, in_specs=in_specs,
                    out_specs=out_specs, check_vma=False)
                return fn(params, opt_state, kstate, extra_vars, batch,
                          hyper)
            return step_impl

        # One separately-jitted callable per cadence-flag combination
        # (factor_update, inv_update, inv_chunk), built lazily and kept
        # for the builder's lifetime. Passing the flags through one jit
        # via static_argnums retraced + recompiled on EVERY flag flip
        # (observed on jax 0.8: the tracing cache kept only the most
        # recent static-arg variant — ~15-45 s per flip on TPU);
        # distinct jit callables have independent caches, so each
        # variant compiles exactly once. With pipelined firing each
        # chunk phase is one more variant (k-1 extra compiles per run,
        # zero retraces — pinned by the trace_counts guard test).
        donate_argnums = (0, 1, 2, 3) if donate else ()
        variants: dict[tuple, Any] = {}
        trace_counts: dict[tuple, int] = {}
        compile_events: list[dict] = []

        # hierarchical_reduce (r20) reuses the r14 window machinery:
        # the engine schedules its boundary reduce off the same
        # `deferred_factor_reduction` step attribute, and the variant
        # key gains the reduce flag identically.
        deferred = (self.kfac.deferred_factor_reduction
                    or self.kfac.hierarchical_reduce)
        staleness = self.kfac.inv_staleness

        def _variant_key(f, i, c, r=False, s=False):
            """Variant-cache key. Both knobs off keeps the historical
            3-tuple (the trace_counts guard tests pin that shape); each
            engaged knob appends its flag — per-builder the key length
            is constant, so lookups stay unambiguous."""
            key = (f, i, c)
            if deferred:
                key += (bool(r),)
            if staleness:
                key += (bool(s),)
            return key

        def _variant_label(key) -> str:
            f, i, c = key[:3]
            label = f'factor={f},inv={i},chunk={c}'
            extra = key[3:]
            if deferred:
                label += f',reduce={extra[0]}'
                extra = extra[1:]
            if staleness:
                label += f',snapshot={extra[0]}'
            return label

        def step(params, opt_state, kstate, extra_vars, batch, hyper,
                 factor_update: bool | None = None,
                 inv_update: bool | None = None,
                 inv_chunk: int | None = None,
                 factor_reduce: bool = False,
                 factor_snapshot: bool = False):
            """``factor_update`` / ``inv_update``: static cadence flags
            (see :meth:`KFAC.step`). ``None`` = dynamic on-device conds;
            host-driven bools select one of the statically-compiled
            program variants (the TPU fast path). ``inv_chunk``: fire
            only pipelined chunk ``j`` of the inverse work (static int;
            requires ``inv_update`` falsy — see ``KFAC.step``).
            ``factor_reduce`` / ``factor_snapshot``: the r14 overlap
            flags (see :meth:`spmd_step`) — each engaged knob's flag is
            part of the variant key."""
            key = _variant_key(factor_update, inv_update, inv_chunk,
                               factor_reduce, factor_snapshot)
            first = key not in variants
            if first:
                # keep_unused: a firing that warm-starts nothing (the
                # damped-inverse buckets) replaces the stored inverse
                # stacks without reading them. Pruned from the program's
                # arguments they would not be donated either: the new
                # stacks would be a second allocation next to the old,
                # and any other name for the initial state would keep
                # its zero-seeded stacks — 1.5 GB at xl LM scale,
                # measured on v5e (PERF.md, PR 21) — alive for the run.
                variants[key] = jax.jit(
                    make_step_impl(factor_update, inv_update, inv_chunk,
                                   factor_reduce, factor_snapshot),
                    donate_argnums=donate_argnums, keep_unused=True)
                # The first call fixes the input types this variant's
                # program is cached under, and the step returns its
                # state committed to the mesh. State made off the mesh
                # (a plain model/optimizer init) would therefore come
                # back differently typed and compile the variant a
                # second time, so it is committed here, once; state
                # already on the mesh is not touched.
                params, opt_state, kstate, extra_vars = self._commit(
                    (params, opt_state, kstate, extra_vars),
                    (replicated_specs(params),
                     replicated_specs(opt_state),
                     self.state_pspecs(kstate),
                     replicated_specs(extra_vars)))
                tracing.count('kfac/builds')
                label = _variant_label(key)
                counted = tracing.counters()
                with _open_build_span(label) as build:
                    out = variants[key](params, opt_state, kstate,
                                        extra_vars, batch, hyper)
                    build.set(
                        cache_hit=build.attrs['cache_retrieval_s'] > 0,
                        **_attention_paths_since(counted))
                # First-call wall = trace + lowering + XLA compile (or
                # the load from the persistent cache) + dispatch; the
                # execution itself is async. The span's attributes
                # split it. Queued, not written: the engine drains
                # compile_events into the metrics sink off the step
                # path; a sink-less caller just accumulates a short
                # list (one entry per variant, ever).
                compile_events.append(
                    {'event': 'compile', 'variant': label,
                     'first_call_ms': build.duration_ms,
                     **build.attrs})
                # A first call is where the attention kernel's probe
                # runs (trace time); surface any recorded fallback
                # through the same engine-drained queue so a fleet run
                # can tell "fused" from "fell back to XLA".
                compile_events.extend(
                    pallas_kernels.drain_pallas_events())
                return out
            return variants[key](params, opt_state, kstate, extra_vars,
                                 batch, hyper)

        # Introspection for the engine's chunk scheduler and the
        # retrace-guard test (host-side, no runtime cost);
        # compile_events additionally feeds the r10 compile/retrace
        # telemetry (drained by engine.train_epoch).
        step.inv_pipeline_chunks = self.kfac.inv_pipeline_chunks
        step.deferred_factor_reduction = deferred
        step.hierarchical_reduce = self.kfac.hierarchical_reduce
        step.inv_staleness = staleness
        step.trace_counts = trace_counts
        step.compile_events = compile_events
        return step


def _get(tree, path):
    for part in path:
        tree = tree[part]
    return tree


def _set(tree, path, value):
    if not path:
        return value
    out = dict(tree)
    out[path[0]] = _set(tree[path[0]], path[1:], value)
    return out
