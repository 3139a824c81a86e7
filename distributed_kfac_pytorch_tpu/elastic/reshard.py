"""Re-shard K-FAC state between pod topologies at resume time.

The stateless-shard framing of *Distributed Preconditioning*
(arXiv:2206.15143): replicated factors let ANY world reconstruct its
preconditioning slice, so moving a run from an N-device mesh to an
M-device one is a pure resume-time transform — no cold restart.

Concretely, the topology-dependent part of ``DistributedKFAC`` state is
the row-sharded bucket stacks: each same-dim factor group lives as one
``(n_rows * slots_per_row, dim, dim)`` stack whose slot positions come
from the deterministic two-level LPT placement (``assign_work``). The
reshard is therefore a *permutation*, not a recomputation:

  1. **gather** — using the SAVED topology's ``WorkAssignment``
     (reconstructed host-side from the ``topo_*`` scalars the bundle
     recorded, :mod:`elastic.topology`), pull each ``(layer, 'A'|'G')``
     factor's inverse entries (``Q``/``d``/``inv``) out of the saved
     global stacks into a canonical per-factor layout;
  2. **repack** — place them at the NEW mesh's slot positions,
     identity/ones/zeros padding for unassigned slots exactly as
     ``init_state`` seeds them, and hand the result to the existing
     re-commit machinery (``DistributedKFAC.load_state_dict`` commits
     the stacks row-sharded; ``launch.replicate_on_mesh`` re-commits
     the replicated groups).

Because gather∘repack copies bytes, an N→M→N round trip is LOSSLESS:
resuming back on the original topology continues bit-identically to an
uninterrupted N-run (pinned by tests/test_elastic.py). Replicated
groups (factors, diagonal/grouped inverses, params, optimizer state)
pass through untouched; ``inv_chunk_phase`` rides along while the
chunk plan itself is re-planned implicitly — constructing
``DistributedKFAC`` on the new mesh reruns the greedy-LPT chunk
balance for the new device count, and the engine re-derives the firing
schedule from the step counter, so the zero-retrace guard holds on the
new world too.

Factor-only checkpoints (``include_inverses=False``) need none of
this: ``load_state_dict`` already rebuilds all inverse stacks from the
replicated factors — the purest form of the stateless-shard design.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

from distributed_kfac_pytorch_tpu.elastic.topology import TopologySpec


def saved_assignment(kfac, params, topo: TopologySpec):
    """The SAVED world's WorkAssignment, reconstructed host-side.

    ``assign_work`` is deterministic in ``(layer specs, params shapes,
    n_rows, n_cols, distribute_layer_factors)`` — all available on the
    restoring side — so the saving world's exact slot map can be
    rebuilt without ever having run there.
    """
    from distributed_kfac_pytorch_tpu.parallel.distributed import (
        assign_work,
    )
    from distributed_kfac_pytorch_tpu.parallel.placement import (
        WorkerAllocator,
    )
    # Validate the recorded grid as a legal KAISA partition first: the
    # allocator is the golden topology spec (reference kfac/utils.py),
    # and a bundle whose rows x cols cannot form one must fail here,
    # not deep inside the slot math. On a multi-slice world the
    # per-slice grid is the allocator unit; placement then runs over
    # the GLOBAL row space (slices * rows — each slice owns a
    # contiguous run of rows, exactly like the live DistributedKFAC).
    alloc = WorkerAllocator.from_grid(topo.rows, topo.cols)
    assert (alloc.inv_groups, alloc.grad_workers) == (topo.rows,
                                                      topo.cols)
    return assign_work(
        kfac, params, topo.slices * topo.rows, topo.cols,
        distribute_layer_factors=topo.distribute_layer_factors)


def _to_host(x) -> np.ndarray:
    """Host view of a (fully-addressable) array leaf."""
    return np.asarray(x)


def gather_canonical(inv_stacks: dict, assignment) -> dict:
    """Saved slot stacks -> canonical ``{(name, 'A'|'G'): {key: mat}}``.

    ``assignment`` must be the SAVED topology's (``saved_assignment``);
    shapes are validated against it so a bundle whose stacks do not
    match its recorded topology fails loudly instead of scattering
    garbage.
    """
    canon: dict[tuple, dict] = {}
    for dim, plan in assignment.buckets.items():
        entry = inv_stacks[str(dim)]
        S = plan.slots_per_row
        n_slots = assignment.n_rows * S
        arrs = {}
        for key, stack in entry.items():
            host = _to_host(stack)
            if host.shape[0] != n_slots:
                raise ValueError(
                    f'checkpoint inv_stacks[{dim}][{key!r}] has '
                    f'{host.shape[0]} slots but the recorded topology '
                    f'implies {n_slots} — the bundle does not match '
                    'its own topo_* scalars (corrupt or hand-edited '
                    'checkpoint)')
            arrs[key] = host
        for (name, which), slot in plan.slot.items():
            g = assignment.layer_row[name] * S + slot
            canon[(name, which)] = {k: v[g] for k, v in arrs.items()}
    return canon


def _pad_stack(key: str, n_slots: int, shape: tuple, dtype) -> np.ndarray:
    """Padding slots seeded exactly like ``init_state``: identity
    eigenbases / unit eigenvalues (a valid warm start for the polish),
    zero dense inverses. A truncated (dim, r) basis (r19 low-rank)
    pads with the rectangular identity-column seed — assigned slots
    carry their saved bases across the reshard unchanged."""
    if key == 'Q':
        # np.eye(N, M): rectangular for truncated bases, square else.
        return np.broadcast_to(np.eye(shape[-2], shape[-1],
                                      dtype=dtype),
                               (n_slots,) + shape).copy()
    if key == 'd':
        return np.ones((n_slots,) + shape, dtype)
    return np.zeros((n_slots,) + shape, dtype)


def repack_canonical(canon: dict, assignment) -> dict:
    """Canonical per-factor entries -> the NEW topology's slot stacks."""
    stacks: dict[str, dict] = {}
    for dim, plan in assignment.buckets.items():
        S = plan.slots_per_row
        n_slots = assignment.n_rows * S
        sample_key = next(iter(plan.slot))
        if sample_key not in canon:
            raise ValueError(
                f'factor {sample_key} missing from the gathered '
                'checkpoint state — saved and live layer registries '
                'disagree (layer congruence should have caught this)')
        arrs = {k: _pad_stack(k, n_slots, v.shape, v.dtype)
                for k, v in canon[sample_key].items()}
        for (name, which), slot in plan.slot.items():
            g = assignment.layer_row[name] * S + slot
            for k, mat in canon[(name, which)].items():
                arrs[k][g] = mat
        stacks[str(dim)] = arrs
    return stacks


def reshard_state_dict(sd: dict, saved_topo: TopologySpec, dkfac,
                       params) -> dict:
    """A ``DistributedKFAC.state_dict`` tree, re-sharded for ``dkfac``'s
    live mesh.

    ``sd`` leaves must be host or fully-addressable (e.g. replicated)
    arrays — the elastic restore path guarantees this
    (``CheckpointManager.restore_replicated``). Replicated groups
    (step, factors, diag/grouped inverses, ``inv_chunk_phase``) pass
    through; only ``inv_stacks`` is gathered and repacked. The result
    feeds straight into ``DistributedKFAC.load_state_dict``, whose
    ``_commit_host_leaves`` commits the new stacks row-sharded.
    """
    kfac = dkfac.kfac
    if set(sd.get('factors', {})) != set(kfac.specs):
        raise ValueError(
            'cannot reshard: checkpoint layers do not match registered '
            f'layers: {sorted(sd.get("factors", {}))} vs '
            f'{sorted(kfac.specs)}')
    live = TopologySpec.of_mesh(
        dkfac.mesh,
        distribute_layer_factors=dkfac.distribute_layer_factors)
    if not saved_topo.needs_reshard(live):
        return sd
    if 'inv_stacks' not in sd:
        # Factor-only checkpoint: nothing topology-shaped to move;
        # load_state_dict recomputes the inverses from the replicated
        # factors on the new mesh (the stateless-shard fast path).
        return sd

    def drop_inverses():
        return {k: v for k, v in sd.items()
                if k not in ('inv_stacks', 'diag_inv', 'grouped_inv')}

    if not _stacks_match_config(sd['inv_stacks'], dkfac):
        # The saved inverse REPRESENTATION does not match the live
        # config (e.g. eigen stacks saved, 'inv' dispatch resumed) —
        # the same cross-config case load_state_dict already degrades
        # on: drop the inverse groups so it rebuilds everything from
        # the (topology-independent) replicated factors.
        return drop_inverses()
    assn = saved_assignment(kfac, params, saved_topo)
    if kfac.a_followers() and any(
            np.shape(stack)[0] != assn.n_rows * plan.slots_per_row
            for dim, plan in assn.buckets.items()
            for stack in sd['inv_stacks'][str(dim)].values()):
        # A bundle from before layers that read one input shared an A
        # slot (MIGRATION.md, PR 31) holds a slot a layer, which is not
        # what today's placement gives the saved topology: nothing to
        # move slot by slot, so the inverses are rebuilt from the
        # factors. (Where no layers share, such a mismatch is a corrupt
        # bundle, and gather_canonical says so.)
        return drop_inverses()
    canon = gather_canonical(sd['inv_stacks'], assn)
    return {**sd,
            'inv_stacks': repack_canonical(canon, dkfac.assignment)}


def _stacks_match_config(inv_stacks: dict, dkfac) -> bool:
    """Do the saved stacks carry exactly the entry keys the live
    config's dispatch produces? Bucket dims and per-dim Q/d/inv key
    sets are functions of (model, K-FAC config) — NOT of topology —
    so a mismatch here means the run configuration changed, which is
    rebuild-from-factors territory, not reshard territory."""
    from distributed_kfac_pytorch_tpu.preconditioner import eigen_family
    kfac = dkfac.kfac
    expected = {}
    for dim in dkfac.assignment.buckets:
        if eigen_family(kfac.method_for_dim(dim)):
            keys = {'Q', 'd'}
            if dkfac._bucket_mixed.get(dim):
                keys.add('inv')
        else:
            keys = {'inv'}
        expected[str(dim)] = keys
    # r19: a low-rank basis saved at a DIFFERENT rank shares the Q/d
    # key names; the per-slot column count must also line up or the
    # repacked stacks feed wrong-shape operands to the firing —
    # rebuild from factors instead (reseed, not carry).
    for dim in dkfac.assignment.buckets:
        entry = inv_stacks.get(str(dim))
        if not entry or 'Q' not in entry:
            continue
        rank = kfac.lowrank_rank_for(dim) or dim
        if tuple(np.shape(entry['Q']))[-2:] != (dim, rank):
            return False
    return {k: set(v) for k, v in inv_stacks.items()} == expected


# ---------------------------------------------------------------------------
# Resume-time context (consumed by resilience.cli.resume)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ElasticResume:
    """Everything the elastic resume path needs about the LIVE world.

    ``dkfac`` may be None (SGD baseline runs): there is no K-FAC state
    to reshard, but restored replicated groups are still re-committed
    onto the new mesh. ``params`` is the live parameter template
    (needed to reconstruct the saved WorkAssignment).
    """
    mesh: Any
    dkfac: Any = None
    params: Any = None

    @property
    def topology(self) -> TopologySpec:
        return TopologySpec.of_mesh(
            self.mesh,
            distribute_layer_factors=(
                self.dkfac.distribute_layer_factors
                if self.dkfac is not None else None))

    def reshard_tree(self, tree: dict,
                     saved_topo: TopologySpec | None) -> dict:
        """Re-shard a restored bundle for the live world.

        The kfac subtree goes through :func:`reshard_state_dict` (when
        a reshard is needed and possible); the replicated groups are
        re-committed onto the live mesh via
        ``launch.replicate_on_mesh`` — the restore handed them back
        replicated-but-host-staged, and an uncommitted splice would
        re-shard lazily inside the first jitted step (or worse, break
        the next ``bundle_fn`` template on a pod).
        """
        from distributed_kfac_pytorch_tpu import launch

        out = dict(tree)
        if (self.dkfac is not None and out.get('kfac')
                and saved_topo is not None):
            out['kfac'] = reshard_state_dict(
                out['kfac'], saved_topo, self.dkfac, self.params)
        for key in ('params', 'opt_state', 'extra_vars'):
            if key in out:
                out[key] = launch.replicate_on_mesh(self.mesh, out[key])
        return out


def like_matches_metadata(metadata, like) -> bool:
    """Do the saved leaves' shapes line up with the live template's?

    A conservative positional comparison (leaf count + per-leaf
    shapes): metadata trees come back from orbax in plain containers,
    so treedefs cannot be compared directly against a live template
    holding custom nodes (optax states). A false positive is caught by
    the caller's try/except around the ``like=`` restore; a false
    negative just routes through the (always-correct) replicated
    restore.
    """
    import jax

    try:
        m_leaves = jax.tree.leaves(metadata)
        l_leaves = jax.tree.leaves(like)
    except Exception:
        return False
    if len(m_leaves) != len(l_leaves):
        return False
    return all(
        tuple(getattr(m, 'shape', ()) or ()) == tuple(np.shape(l))
        for m, l in zip(m_leaves, l_leaves))
