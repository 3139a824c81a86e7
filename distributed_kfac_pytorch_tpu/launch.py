"""Multi-host launch: process-group init and host-local data feeding.

Reference L5 parity (scripts/launch_node_torch_imagenet.sh,
scripts/slurm/*.slurm): where the reference bridges mpiexec/SLURM rank
env-vars into ``torch.distributed.launch`` per node
(launch_node_torch_imagenet.sh:45-48), the JAX runtime replaces the whole
MPI machinery with ``jax.distributed.initialize`` — on TPU pods the
coordinator and process ranks come from the TPU metadata, on SLURM from
the SLURM env (both auto-detected), or explicitly from arguments.
"""

from __future__ import annotations

import os

import jax
import numpy as np


def initialize_multihost(coordinator_address: str | None = None,
                         num_processes: int | None = None,
                         process_id: int | None = None) -> dict:
    """Initialize the JAX multi-host runtime (idempotent, single-host safe).

    Auto-detects TPU pod / SLURM / Open MPI environments like
    ``jax.distributed.initialize`` does; explicit arguments override.
    Returns a summary dict (process_index, process_count, device counts).
    """
    # Manual launch support: JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES
    # / JAX_PROCESS_ID env vars (jax.distributed.initialize itself only
    # auto-detects SLURM / Open MPI / TPU-pod environments) — the
    # generic analogue of the reference's MASTER_ADDR / RANK env chain
    # (launch_node_torch_imagenet.sh:45-68).
    if coordinator_address is None:
        coordinator_address = os.environ.get('JAX_COORDINATOR_ADDRESS')
    if num_processes is None and \
            os.environ.get('JAX_NUM_PROCESSES', '').isdigit():
        num_processes = int(os.environ['JAX_NUM_PROCESSES'])
    if process_id is None and \
            os.environ.get('JAX_PROCESS_ID', '').isdigit():
        process_id = int(os.environ['JAX_PROCESS_ID'])
    explicit = (coordinator_address or num_processes
                or process_id is not None)
    # Initialize only when explicitly configured OR the environment
    # actually declares >1 process. Presence of a cluster-ish env var
    # alone is NOT enough: single-host environments export lookalikes
    # (observed live: a runtime that sets
    # TPU_WORKER_HOSTNAMES=localhost in every interpreter, where
    # jax.distributed.initialize then dies with 'coordinator_address
    # should be defined' — which broke every CLI while the 'skip when
    # single' path was gated on the env var's absence).
    if explicit or _detected_world_size() > 1:
        # Cross-process collectives on the CPU backend need an
        # implementation selected before the backend initializes;
        # harmless on TPU (ICI/DCN collectives are native). This is
        # what lets the multi-host path run on plain hosts (and the
        # 2-process integration test, tests/test_multihost.py).
        jax.config.update('jax_cpu_collectives_implementation', 'gloo')
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes, process_id=process_id)
        except RuntimeError as e:
            # Double-init is benign. A job whose env declares >1 process
            # must fail loudly, or every host would silently train alone
            # on its own shard.
            if 'should only be called once' not in str(e).lower():
                raise
    # Cross-check the env-scan against the live runtime: the scan
    # silently returns 1 when no known variable matches, and a launch
    # chain that half-exports its env (e.g. SLURM_NTASKS set on some
    # hosts only, or a typo'd JAX_NUM_PROCESSES) would otherwise split
    # the world without a trace. Explicit arguments opt out — they
    # override the env by design, so a disagreement there is intended.
    if not explicit:
        _check_world_size(_detected_world_size(), jax.process_count())
    return {'process_index': jax.process_index(),
            'process_count': jax.process_count(),
            'local_devices': jax.local_device_count(),
            'global_devices': jax.device_count()}


def _check_world_size(detected: int, actual: int) -> None:
    """Warn when the env-declared world size disagrees with the
    initialized runtime's ``jax.process_count()`` (split out for
    testability — the runtime value is authoritative, so this is a
    diagnostic, not a failure)."""
    if detected == actual:
        return
    import warnings

    warnings.warn(
        f'launch environment declares {detected} process(es) '
        f'(_detected_world_size: SLURM/OMPI/JAX_NUM_PROCESSES/'
        f'TPU_WORKER_HOSTNAMES scan) but the initialized JAX runtime '
        f'reports {actual} — the runtime value wins, but check the '
        'launch chain: a half-exported env var here usually means '
        'some hosts are about to train alone on their own shard.')


def host_metadata() -> dict:
    """Identity of THIS host for per-rank telemetry (r10).

    The straggler shards (``observability.stragglers``) stamp this into
    each shard's meta record so a skewed rank in a merged report can be
    mapped back to a machine — 'rank 13 is slow' is actionable only
    once rank 13 has a hostname.
    """
    import platform

    return {'process_index': jax.process_index(),
            'process_count': jax.process_count(),
            'hostname': platform.node(),
            'backend': jax.default_backend(),
            'local_devices': jax.local_device_count()}


def _detected_world_size() -> int:
    """Process count declared by the launch environment (1 if unknown)."""
    for var in ('SLURM_NTASKS', 'OMPI_COMM_WORLD_SIZE',
                'JAX_NUM_PROCESSES'):
        if os.environ.get(var, '').isdigit():
            return int(os.environ[var])
    hosts = os.environ.get('TPU_WORKER_HOSTNAMES', '')
    if hosts:
        return len([h for h in hosts.split(',') if h.strip()])
    return 1


def replicate_on_mesh(mesh, tree):
    """Commit a pytree REPLICATED over the mesh (multi-host safe).

    Model/optimizer init leaves arrive uncommitted on one device; the
    jitted step would replicate them lazily, but the r8 resume path
    builds its orbax restore template (``like=``) from the live state
    *before* any step runs — an uncommitted template makes a pod
    checkpoint restore single-device (caught by the multihost kill
    test). Single-process: a plain ``device_put``. Multi-process: a
    global replicated array assembled from each host's (identical)
    copy — ``device_put`` cannot target non-addressable shardings.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    sharding = NamedSharding(mesh, PartitionSpec())

    def put(x):
        if jax.process_count() > 1:
            return jax.make_array_from_process_local_data(
                sharding, np.asarray(x))
        return jax.device_put(x, sharding)

    return jax.tree.map(put, tree)


def host_local_batch_to_global(mesh, batch, pspec):
    """Assemble a global sharded batch from per-host local arrays.

    Multi-host analogue of the reference's DistributedSampler sharding
    (each rank loads its slice, examples/cnn_utils/datasets.py:57-63):
    each host feeds its local shard; the result is one global jax.Array
    laid out per ``pspec`` over the mesh.
    """
    from jax.sharding import NamedSharding

    def make(x, spec):
        sharding = NamedSharding(mesh, spec)
        return jax.make_array_from_process_local_data(sharding,
                                                      np.asarray(x))

    return jax.tree.map(lambda x: make(x, pspec), batch)


def process_local_slice(n_global: int) -> slice:
    """Index range of this host's share of a globally-indexed dataset."""
    per = n_global // jax.process_count()
    start = jax.process_index() * per
    return slice(start, start + per)


def global_batches(mesh, batches, batch_spec=None, *,
                   already_sharded: bool = False):
    """Adapt an iterator of host-identical global batches for multi-host.

    The multi-host feeding glue between a dataset iterator and a jitted
    ``shard_map`` train step — the analogue of the reference's
    ``DistributedSampler`` + per-rank loader chain
    (examples/cnn_utils/datasets.py:53-68, launch chain
    launch_node_torch_imagenet.sh:45-68 -> torch_imagenet_resnet.py:113):

      - single-process: yields batches unchanged (jit shards them onto
        the local mesh per its in_specs — no wrapping needed);
      - multi-process: every host generates the *same* global batch
        (same seed/epoch => same permutation, like DistributedSampler's
        shared-seed shuffle); each host keeps only its
        :func:`process_local_slice` of every batch-sharded leaf and
        assembles one global ``jax.Array`` per leaf spec, so the jitted
        step sees a fully-addressable global batch.

    ``batch_spec``: a single PartitionSpec (broadcast over leaves) or a
    pytree of specs matching the batch — same convention as
    ``DistributedKFAC.build_train_step``. ``None`` defaults to sharding
    the leading dim over the K-FAC mesh axes. Leaves with a
    fully-replicated spec (``P()``) are passed whole from every host.
    Supported specs shard the *leading* dim across processes; later
    spec dims may only map to mesh axes contained within one process
    (e.g. single-host sequence parallelism) — anything else raises.

    ``already_sharded=True``: the iterator yields *per-process local*
    batches (e.g. a tf.data pipeline sharded with
    ``ds.shard(process_count, process_index)``) — no slicing, each
    host's data is used as its local shard directly. Prefer this at
    scale: the default shared-global-batch mode costs every host the
    full global input pipeline (simple and exact for in-memory
    datasets, wasteful for a 32-host ImageNet job).
    """
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    if jax.process_count() == 1:
        yield from batches
        return
    from distributed_kfac_pytorch_tpu.parallel.distributed import (
        KFAC_AXES,
        SLICE_AXIS,
        normalize_batch_specs,
    )
    if batch_spec is None:
        # Default: leading dim over the K-FAC data axes — including
        # the outer slice axis on a multi-slice mesh (r20), mirroring
        # DistributedKFAC.batch_axes.
        axes = (((SLICE_AXIS,) if SLICE_AXIS in mesh.axis_names else ())
                + KFAC_AXES)
        batch_spec = P(axes)
    nproc = jax.process_count()

    def axis_spans_processes(name) -> bool:
        """Does moving along mesh axis ``name`` cross a process?"""
        idx = mesh.axis_names.index(name)
        rows = np.moveaxis(mesh.devices, idx, -1)
        rows = rows.reshape(-1, rows.shape[-1])
        return any(len({d.process_index for d in row}) > 1
                   for row in rows)

    def _axes(entry):
        if entry is None:
            return ()
        return entry if isinstance(entry, tuple) else (entry,)

    def check_spec(spec):
        for entry in tuple(spec)[1:]:
            for ax in _axes(entry):
                if axis_spans_processes(ax):
                    raise NotImplementedError(
                        f'global_batches only shards the leading batch '
                        f'dim across processes; spec {spec} shards a '
                        f'later dim over mesh axis {ax!r} which spans '
                        'multiple processes — assemble such leaves '
                        'yourself with host_local_batch_to_global')

    def assemble(x, spec):
        sharding = NamedSharding(mesh, spec)
        if spec == P():
            return jax.make_array_from_process_local_data(
                sharding, np.asarray(x))
        check_spec(spec)
        if already_sharded:
            return jax.make_array_from_process_local_data(
                sharding, np.asarray(x))
        n = x.shape[0]
        if n % nproc:
            raise ValueError(
                f'global batch of {n} does not divide evenly over '
                f'{nproc} processes')
        local = np.asarray(x)[process_local_slice(n)]
        return jax.make_array_from_process_local_data(sharding, local)

    for batch in batches:
        specs = normalize_batch_specs(batch_spec, batch)
        yield jax.tree.map(assemble, batch, specs)
