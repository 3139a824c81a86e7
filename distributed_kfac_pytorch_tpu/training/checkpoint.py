"""Checkpoint save / auto-resume via orbax.

Reference parity: examples/utils.py:10-19 (save_checkpoint bundling
model + optimizer + preconditioner + scheduler states) and the
auto-resume scan in torch_cifar10_resnet.py:147-151 (find the newest
epoch checkpoint and restore). K-FAC factors are saved but inverses are
recomputed on load (reference preconditioner.py:294-353, README.md:222-223)
— the caller passes ``kfac_state_dict`` already filtered by
``KFAC.state_dict``.

Orbax handles sharded arrays natively: distributed inverse stacks save
and restore with their shardings, so resume works across pod restarts.
"""

from __future__ import annotations

import os
from typing import Any

import jax
import numpy as np
import orbax.checkpoint as ocp

#: Filename recording WHY a bundle was moved to ``<label>.quarantined``
#: (written by :meth:`CheckpointManager.quarantine`, read back by
#: ``quarantine_info`` for the --resume-step refusal message).
QUARANTINE_REASON_FILE = 'QUARANTINE_REASON'


class CheckpointManager:
    """Epoch-indexed checkpoints with auto-resume.

    Stores one composite pytree per epoch under ``directory/<epoch>/``;
    ``latest_epoch()``/``restore()`` implement the reference's
    scan-downward resume (torch_cifar10_resnet.py:147-151) via orbax's
    step tracking.
    """

    def __init__(self, directory: str, max_to_keep: int | None = 2):
        self.directory = os.path.abspath(directory)
        self._mgr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep, create=True),
            # Explicit handler so ``item_metadata`` works on a FRESH
            # manager (a resumed process that has not saved yet has no
            # lazily-registered handler; without this, orbax returns
            # None and the elastic restore path cannot inspect saved
            # shapes before reading data). Same handler save/restore
            # already use via args=Standard{Save,Restore}.
            item_handlers=ocp.StandardCheckpointHandler())

    def save(self, epoch: int, tree: dict, *, force: bool = False,
             blocking: bool = False) -> None:
        """Save a checkpoint tree.

        Async by default: orbax snapshots the (device) arrays and writes
        in a background thread, so a multi-GB ImageNet-scale save does
        not stall the training loop (the step right after a save
        proceeds while bytes hit disk). Pending writes are joined by the
        next ``save``/``restore``/``latest_epoch``/``close`` call —
        orbax serializes them internally — or explicitly via
        :meth:`wait_until_finished`. Pass ``blocking=True`` (or call
        ``wait_until_finished``) where durability must be certain before
        proceeding, e.g. right before process exit.

        ``force=True`` additionally REPLACES an existing bundle at the
        same label (orbax's own ``force`` only bypasses the
        save-interval policy and still raises StepAlreadyExistsError):
        an in-process self-heal rollback (r16) rewinds the step/epoch
        counters, and the replay's saves land on labels whose
        pre-rollback bundles are stale garbage from an abandoned
        timeline — they must be overwritten, not fatal.
        """
        try:
            self._mgr.save(epoch, args=ocp.args.StandardSave(tree),
                           force=force)
        except Exception as e:
            if not force or \
                    type(e).__name__ != 'StepAlreadyExistsError':
                raise
            self._mgr.wait_until_finished()
            self._mgr.delete(epoch)
            self._mgr.save(epoch, args=ocp.args.StandardSave(tree),
                           force=True)
        if blocking:
            self._mgr.wait_until_finished()

    def wait_until_finished(self) -> None:
        """Block until all pending async saves are durable on disk."""
        self._mgr.wait_until_finished()

    def latest_epoch(self) -> int | None:
        self._mgr.wait_until_finished()  # join any pending async save
        return self._mgr.latest_step()

    def all_steps(self) -> list[int]:
        """Every finalized checkpoint label on disk, ascending. The
        verified-resume walk (``resilience.cli.resume`` /
        ``resilience.selfheal.rollback_restore``) iterates these
        newest-first, quarantining corrupt/torn bundles until one
        verifies (r16)."""
        self._mgr.wait_until_finished()
        return sorted(self._mgr.all_steps())

    def quarantine(self, label: int,
                   reason: str | None = None) -> str | None:
        """Move a corrupt bundle's directory aside
        (``<label>.quarantined[.N]`` — kept for forensics, invisible
        to orbax's integer-step scan) and resync the manager.

        Without the move, a run that resumed PAST the corrupt bundle
        re-reaches its step and orbax refuses the re-save
        (StepAlreadyExistsError) — the quarantined garbage would brick
        the very replay the verified walk just enabled. On shared
        multihost storage the first mover wins; losers see the dir
        gone and only resync. Returns the new path (None if another
        rank already moved it).

        ``reason`` is recorded as ``QUARANTINE_REASON`` inside the
        moved directory (best effort) so a later explicit
        ``--resume-step`` at this label can tell the operator WHY the
        bundle was moved, not just that it is gone (r17;
        :meth:`quarantine_info`)."""
        self._mgr.wait_until_finished()
        src = os.path.join(self.directory, str(label))
        dst = f'{src}.quarantined'
        n = 0
        while os.path.exists(dst):
            n += 1
            dst = f'{src}.quarantined.{n}'
        moved = None
        try:
            os.replace(src, dst)
            moved = dst
        except FileNotFoundError:
            pass  # raced with another rank (or already gone)
        if moved is not None and reason:
            try:
                with open(os.path.join(moved,
                                       QUARANTINE_REASON_FILE),
                          'w') as f:
                    f.write(str(reason) + '\n')
            except OSError:
                pass  # forensics metadata must never fail the walk
        reload = getattr(self._mgr, 'reload', None)
        if reload is not None:
            reload()
        return moved

    def quarantined_paths(self, label: int) -> list[str]:
        """Quarantined copies of ``label`` on disk, oldest first
        (``<label>.quarantined``, ``.quarantined.1``, ...)."""
        src = os.path.join(self.directory, str(label))
        out = []
        dst = f'{src}.quarantined'
        n = 0
        while os.path.exists(dst):
            out.append(dst)
            n += 1
            dst = f'{src}.quarantined.{n}'
        return out

    def quarantine_info(self, label: int) -> tuple[str, str] | None:
        """``(path, reason)`` of the NEWEST quarantined copy of
        ``label`` — but only when no live bundle exists at that label
        (a live bundle supersedes its quarantined history: the replay
        re-saved it). None otherwise. The resume walk uses this to
        refuse an explicit ``--resume-step`` at a quarantined label
        with the real story instead of a bare not-found."""
        if os.path.exists(os.path.join(self.directory, str(label))):
            return None
        paths = self.quarantined_paths(label)
        if not paths:
            return None
        newest = paths[-1]
        reason = 'no recorded reason (pre-r17 quarantine)'
        try:
            with open(os.path.join(newest,
                                   QUARANTINE_REASON_FILE)) as f:
                reason = f.read().strip() or reason
        except OSError:
            pass
        return newest, reason

    def restore(self, epoch: int | None = None,
                like: dict | None = None) -> dict:
        """Restore a checkpoint (the latest when ``epoch`` is None).

        ``like`` provides the target pytree structure/shardings; restored
        arrays adopt its placements (replicated vs row-sharded state).

        WITHOUT ``like``, orbax falls back to the checkpoint's own
        recorded metadata: host-staged arrays laid out for the
        topology that SAVED them (orbax itself warns this is UNSAFE).
        That only works when the restoring world exactly matches the
        saving world — resuming a pod checkpoint at a different
        process/device count, or an SPMD checkpoint on one chip, gets
        wrong or failing placements. Engine/CLI resume paths therefore
        ALWAYS pass ``like`` (a live-state bundle of the same
        structure — ``resilience.cli.resume`` enforces this): restored
        arrays adopt the LIVE state's committed shardings regardless
        of what wrote the checkpoint, and
        ``DistributedKFAC.load_state_dict`` re-commits stray host
        leaves as a second line of defense. Regression-tested in
        tests/test_resilience.py (like= adopts the live placements;
        sharded SPMD kill-and-resume).

        Restoring onto a DIFFERENT topology is supported through the
        elastic path, not through this method's bare form: bundles
        record their saving world in ``topo_*`` scalars
        (``elastic.topology``), ``restore_replicated`` brings the
        bundle up replicated on any live mesh, and
        ``elastic.reshard`` repacks the K-FAC slot stacks for the new
        world — ``resilience.cli.resume(elastic=...)`` wires it all
        (README "Elastic training").
        """
        self._mgr.wait_until_finished()  # join any pending async save
        if epoch is None:
            epoch = self.latest_epoch()
        if epoch is None:
            raise FileNotFoundError(
                f'no checkpoints found under {self.directory}')
        steps = self._mgr.all_steps()
        if epoch not in steps:
            # Orbax's own failure for a missing step is an opaque
            # directory error; name the request and what IS on disk.
            raise FileNotFoundError(
                f'no checkpoint for step {epoch} under '
                f'{self.directory}; steps on disk: '
                f'{sorted(steps) if steps else "none"}')
        if like is not None:
            abstract = jax.tree.map(ocp.utils.to_shape_dtype_struct, like)
            return self._mgr.restore(
                epoch, args=ocp.args.StandardRestore(abstract))
        # Explicit StandardRestore: a manager that has not saved in this
        # process has no handler registered for the step yet (a resumed
        # fresh process always starts this way).
        return self._mgr.restore(epoch, args=ocp.args.StandardRestore())

    def metadata_tree(self, epoch: int) -> dict:
        """Saved tree structure + per-leaf shape/dtype, WITHOUT reading
        array data (orbax ``item_metadata``). The elastic resume path
        uses this to decide between a same-topology ``like=`` restore
        and a cross-topology replicated restore, and to build the
        latter's template.

        Always the plain nested dict: newer orbax wraps it in a
        ``TreeMetadata`` object (the dict is its ``.tree``), which is
        neither a ``dict`` nor a pytree of its leaves — callers that
        looked for a key or counted leaves saw nothing there."""
        self._mgr.wait_until_finished()
        md = self._mgr.item_metadata(epoch)
        return getattr(md, 'tree', md)

    def restore_replicated(self, epoch: int, mesh,
                           like: dict | None = None) -> dict:
        """Restore a bundle fully REPLICATED on ``mesh`` — the
        topology-independent layout any world can load.

        The template is built from the checkpoint's own metadata
        (saved shapes/dtypes, replicated shardings on the LIVE mesh),
        so it works regardless of what world wrote the bundle —
        multi-host safe, unlike the bare no-``like`` restore. Scalars
        (0-d leaves) come back as host scalars.

        ``like``: the live bundle template. Its ``opt_state`` subtree,
        when present, is used for that group's restore template
        instead of the metadata's — orbax metadata comes back in plain
        containers, and the optimizer state is the one bundle group
        holding custom pytree nodes (optax states) whose structure the
        caller needs preserved; its shapes are topology-independent,
        so the live template's are correct.
        """
        from jax.sharding import NamedSharding, PartitionSpec

        md = self.metadata_tree(epoch)
        rep = NamedSharding(mesh, PartitionSpec())

        def of_meta(m):
            shape = tuple(getattr(m, 'shape', ()) or ())
            # True scalars (python ints/floats in the bundle) restore
            # as host scalars; ARRAY leaves — 0-d included (the K-FAC
            # step / inv_chunk_phase counters) — must carry the live
            # replicated sharding: without one, orbax falls back to
            # the sharding FILE, which references the SAVING world's
            # devices and cannot materialize on a different topology.
            if isinstance(m, ocp.metadata.ScalarMetadata):
                return jax.ShapeDtypeStruct((), m.dtype)
            return jax.ShapeDtypeStruct(shape, m.dtype, sharding=rep)

        def of_live(x):
            # Mirror the save-side typing: array leaves (0-d optax
            # step counters included) were written as arrays and need
            # the live replicated sharding to deserialize; plain
            # python scalars were written as scalars and restore bare.
            if isinstance(x, (jax.Array, np.ndarray)):
                return jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                            sharding=rep)
            return jax.ShapeDtypeStruct((), np.asarray(x).dtype)

        template = {k: jax.tree.map(of_meta, v) for k, v in md.items()}
        if like is not None and 'opt_state' in like \
                and 'opt_state' in template:
            template['opt_state'] = jax.tree.map(of_live,
                                                 like['opt_state'])
        return self._mgr.restore(
            epoch, args=ocp.args.StandardRestore(template))

    def close(self):
        self._mgr.wait_until_finished()
        self._mgr.close()


def bundle_state(params, opt_state, kfac_state_dict, extra_vars,
                 schedulers: dict[str, Any] | None = None,
                 topology=None, integrity: bool | str = True,
                 **scalars) -> dict:
    """Assemble the composite checkpoint tree.

    Mirrors the reference's checkpoint dict {model, optimizer,
    preconditioner, schedulers} (examples/utils.py:10-19).

    ``scalars`` carries the resume point (r8 resilience format, see
    MIGRATION.md "Checkpoint format"): ``step`` (global optimizer
    step), ``epoch`` (the epoch to (re)enter on resume), and
    ``step_in_epoch`` + ``data_seed`` (the data-stream position,
    ``resilience.dataiter.DataStreamState``) — epoch-boundary bundles
    record ``step_in_epoch=0``.

    ``topology``: an ``elastic.topology.TopologySpec`` of the saving
    world; its ``topo_*`` int scalars are merged into ``scalars`` so
    the bundle can be resumed on a DIFFERENT topology (the r11
    elastic format — bundles without it are same-topology-only; see
    MIGRATION.md).

    ``integrity=True`` (default, the r16 format) additionally stamps a
    content checksum of the assembled tree into
    ``scalars['integrity_checksum']`` (``resilience.integrity``); the
    unified resume path verifies it and walks back past bundles that
    fail. ``integrity='template'`` carries the field with the
    unverified sentinel and SKIPS the host fetch + hash — for
    restore-template bundles (``resume(like=)``), whose digest nobody
    reads. ``False`` omits the field entirely — the pre-r16 format,
    only where unverified restores are acceptable (MIGRATION.md
    "Checkpoint integrity").
    """
    scalars = dict(scalars)
    if topology is not None:
        scalars.update(topology.scalars())
    tree = {'params': params,
            'opt_state': opt_state,
            'kfac': kfac_state_dict,
            'extra_vars': extra_vars,
            'scalars': scalars}
    if schedulers:
        tree['schedulers'] = {k: v.state_dict()
                              for k, v in schedulers.items()}
    if integrity:
        from distributed_kfac_pytorch_tpu.resilience import (
            integrity as integrity_lib,
        )
        # The digest is computed SYNCHRONOUSLY at assembly, not
        # deferred behind the async orbax write: the train step
        # donates its state buffers (donate_argnums), so the arrays
        # referenced here are invalidated by the very next dispatch —
        # a deferred hash would read freed buffers. The cost is one
        # host fetch + sha256 per SAVE (not per step); opt out with
        # integrity=False / 'template' where that gates cadence
        # (PERF.md r16).
        integrity_lib.stamp(tree, compute=integrity != 'template')
    return tree
