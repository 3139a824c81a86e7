"""Worker/shared harness for the 2-process multi-host integration test.

Run as a subprocess (one per simulated host) by tests/test_multihost.py:

    python tests/multihost_worker.py PORT PROCESS_ID NUM_PROCESSES OUT.npz

Each process gets 4 virtual CPU devices; ``launch.initialize_multihost``
joins them into one 8-device global runtime (gloo cross-process
collectives), exactly the path a TPU pod worker takes through the
example CLIs (the analogue of the reference's
``init_process_group`` + env-var launch chain,
launch_node_torch_imagenet.sh:45-68 -> torch_imagenet_resnet.py:113).

``run_training`` is also imported by the test and executed in-process on
the single-process 8-device mesh: identical math, so the multi-process
result must match it (same seeds => same data; factor pmeans/grad psums
span the same 8 devices either way).
"""

from __future__ import annotations

import sys


def _configure(n_local_devices=4):
    import jax
    jax.config.update('jax_platforms', 'cpu')
    jax.config.update('jax_num_cpu_devices', n_local_devices)
    return jax


def run_training(n_steps=3, metrics_path=None, process_index=0,
                 checkpoint_dir=None, kill_at=None, resume=False,
                 rank_shards=False, devices=None, elastic=False):
    """Build a small conv net + DistributedKFAC on the global mesh and
    train ``n_steps`` deterministic steps through ``global_batches``.

    Returns (params, metrics_history) — identical across processes
    (all outputs are replicated) and across 1-vs-2-process runs.

    ``metrics_path`` switches on the r7 observability path: the K-FAC
    step collects on-device metrics and every process constructs a
    ``JsonlMetricsSink`` on the SAME path — the sink's rank-0 gating
    (plus atomic write-then-rename) is what keeps a multi-process run
    from interleaving or tearing lines, and that is exactly what
    test_multihost asserts on the result.

    The r8 resilience path: with ``checkpoint_dir`` every process joins
    a collective, *blocking* per-step checkpoint save (orbax
    coordinates the shard writes across hosts — the restore-with-
    committed-shardings contract under test). ``kill_at=k`` hard-kills
    process 1 (``os._exit``) right after the step-``k`` save is
    durable — the killed-multihost-worker fault; the surviving worker
    must then fail its next collective rather than hang forever.
    ``resume=True`` restores the newest step checkpoint (``like=`` the
    live sharded state) and replays only the remaining global batches,
    so a relaunched world must reproduce the uninterrupted run.

    ``rank_shards=True`` (r10, requires ``metrics_path``): EVERY
    process additionally writes its own straggler shard
    ``<metrics_path>.rank<r>`` with per-step dispatch wall time and
    the pre-collective barrier wait from
    ``DistributedKFAC.build_barrier_probe`` — the 2-process
    write->merge path ``observability.report``'s straggler section
    rests on (asserted by test_multihost mode='stragglers').

    The r11 elastic path: checkpoints are full ``bundle_state``
    bundles carrying the saving world's ``topo_*`` scalars;
    ``devices=`` builds the mesh over a SUBSET of the local devices
    (a shrunk world), and ``elastic=True`` routes the resume through
    ``resilience.cli.resume(elastic=...)`` so a checkpoint written by
    a 2-process 8-device pod restores — resharded — onto a 1-process
    4-device mesh (the pod-shrink contract test_multihost pins).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from flax import linen as nn

    from distributed_kfac_pytorch_tpu import launch
    from distributed_kfac_pytorch_tpu.parallel import distributed as D
    from distributed_kfac_pytorch_tpu.preconditioner import (
        CommMethod,
        KFAC,
    )

    class SmallCNN(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.Conv(8, (3, 3))(x)
            x = nn.relu(x)
            x = x.reshape(x.shape[0], -1)
            x = nn.Dense(16)(x)
            x = nn.relu(x)
            return nn.Dense(10)(x)

    model = SmallCNN()
    kfac = KFAC(model, factor_update_freq=1, inv_update_freq=2,
                damping=0.003, lr=0.1,
                comm_method=CommMethod.HYBRID_OPT,
                grad_worker_fraction=0.5,
                collect_metrics=metrics_path is not None,
                nonfinite_guard=metrics_path is not None)
    x0 = jnp.zeros((2, 8, 8, 3))
    variables, _ = kfac.init(jax.random.PRNGKey(0), x0)
    params = variables['params']
    mesh = D.make_kfac_mesh(devices,
                            comm_method=CommMethod.HYBRID_OPT,
                            grad_worker_fraction=0.5)
    # Commit params replicated on the global mesh: the r8 resume path
    # builds its restore template from live state, and an uncommitted
    # single-device init would restore the checkpoint onto one device.
    params = launch.replicate_on_mesh(mesh, params)
    dkfac = D.DistributedKFAC(kfac, mesh, params)
    kstate = dkfac.init_state(params)
    tx = optax.sgd(0.05, momentum=0.9)
    opt_state = tx.init(params)

    def loss_fn(out, batch):
        return optax.softmax_cross_entropy_with_integer_labels(
            out, batch[1]).mean()

    step = dkfac.build_train_step(loss_fn, tx, donate=False)
    hyper = {'lr': 0.05, 'damping': 0.003}

    sink = None
    if metrics_path is not None:
        from distributed_kfac_pytorch_tpu.observability import (
            sink as obs_sink,
        )
        sink = obs_sink.JsonlMetricsSink(
            metrics_path, interval=1, process_index=process_index,
            meta={'mode': 'multihost-metrics',
                  'process_index': process_index})
    rank_sink, probe = None, None
    if rank_shards:
        import time

        from distributed_kfac_pytorch_tpu.observability import (
            stragglers as obs_stragglers,
        )
        rank_sink = obs_stragglers.make_rank_shard_sink(
            metrics_path, process_index, meta=launch.host_metadata())
        probe = dkfac.build_barrier_probe()

    mgr, start = None, 0
    if checkpoint_dir is not None:
        from distributed_kfac_pytorch_tpu import elastic as elastic_lib
        from distributed_kfac_pytorch_tpu.training import (
            checkpoint as ckpt_lib,
        )
        topo = elastic_lib.TopologySpec.of_mesh(
            mesh,
            distribute_layer_factors=dkfac.distribute_layer_factors)

        def bundle(params, opt_state, kstate, step):
            return ckpt_lib.bundle_state(
                params, opt_state, dkfac.state_dict(kstate), {},
                topology=topo, step=step, epoch=0,
                step_in_epoch=step, data_seed=0)

        mgr = ckpt_lib.CheckpointManager(checkpoint_dir,
                                         max_to_keep=None)
        if resume and elastic:
            # The r11 pod-shrink path: restore the newest bundle via
            # the elastic resume flow (replicated restore + reshard
            # onto THIS mesh, which may be a different world than the
            # one that saved).
            import argparse
            import os as _os

            from distributed_kfac_pytorch_tpu.resilience import (
                cli as resil_cli,
            )
            args = argparse.Namespace(no_resume=False,
                                      resume_step=None,
                                      checkpoint_dir=checkpoint_dir)
            epoch_mgr = ckpt_lib.CheckpointManager(
                _os.path.join(checkpoint_dir, 'elastic-epochs'))
            restored, _e0, _off, _src = resil_cli.resume(
                args, epoch_mgr, mgr,
                bundle(params, opt_state, kstate, 0),
                elastic=elastic_lib.ElasticResume(
                    mesh=mesh, dkfac=dkfac, params=params))
            epoch_mgr.close()
            params = restored['params']
            opt_state = restored['opt_state']
            kstate = dkfac.load_state_dict(restored['kfac'], params)
            start = int(restored['scalars']['step'])
        elif resume:
            restored = mgr.restore(
                like=bundle(params, opt_state, kstate, 0))
            params = restored['params']
            opt_state = restored['opt_state']
            kstate = dkfac.load_state_dict(restored['kfac'], params)
            start = int(restored['scalars']['step'])

    rng = np.random.default_rng(0)
    raw = [(rng.normal(size=(32, 8, 8, 3)).astype(np.float32),
            rng.integers(0, 10, 32).astype(np.int32))
           for _ in range(n_steps)]

    losses = []
    extra = {}
    for i, batch in enumerate(
            launch.global_batches(mesh, iter(raw[start:])), start=start):
        wait_ms = probe() if probe is not None else None
        t_it = time.perf_counter() if rank_sink is not None else None
        params, opt_state, kstate, extra, metrics = step(
            params, opt_state, kstate, extra, batch, hyper,
            factor_update=True, inv_update=(i % 2 == 0))
        if sink is not None:
            sink.step_record(i, metrics)
        if rank_sink is not None:
            rank_sink.step_record(
                i, {obs_stragglers.BARRIER_WAIT_KEY: wait_ms},
                host_step_ms=(time.perf_counter() - t_it) * 1000.0,
                fired='inverse' if i % 2 == 0 else 'factor')
        losses.append(float(jax.device_get(metrics['loss'])))
        if mgr is not None:
            # Collective blocking save: every process participates;
            # durable before the kill fault below can fire. Full
            # bundle_state bundles (topo_* scalars included) so the
            # elastic shrink test can resume them on another world.
            mgr.save(i + 1, bundle(params, opt_state, kstate, i + 1),
                     force=True, blocking=True)
            if kill_at == i + 1 and process_index == 1:
                import os
                os._exit(1)  # the killed worker: no cleanup, no goodbye
    if sink is not None:
        sink.close()
    if rank_sink is not None:
        rank_sink.close()
    if mgr is not None:
        mgr.close()
    params_host = jax.tree.map(
        lambda a: np.asarray(jax.device_get(a)), params)
    return params_host, losses


def run_replicate_check(out_path: str, process_index: int) -> None:
    """Exercise ``launch.replicate_on_mesh``'s MULTI-PROCESS branch
    (``make_array_from_process_local_data`` — the branch the
    single-process fast tier can never reach) and assert its contract:
    every leaf comes back a committed, fully-replicated global
    ``jax.Array`` whose every addressable shard holds the full value.
    Writes a per-process OK marker the test asserts on."""
    import jax
    import numpy as np

    from distributed_kfac_pytorch_tpu import launch
    from distributed_kfac_pytorch_tpu.parallel import distributed as D

    assert jax.process_count() > 1, \
        'replicate check must run the multi-process branch'
    mesh = D.make_kfac_mesh()
    tree = {'w': np.arange(24.0, dtype=np.float32).reshape(4, 6),
            'nested': {'b': np.float32(3.5)}}
    out = launch.replicate_on_mesh(mesh, tree)
    for leaf in jax.tree.leaves(out):
        assert isinstance(leaf, jax.Array), type(leaf)
        assert leaf.sharding.is_fully_replicated, leaf.sharding
        assert len(leaf.sharding.device_set) == jax.device_count()
    w = out['w']
    assert w.shape == (4, 6)
    for shard in w.addressable_shards:
        np.testing.assert_array_equal(np.asarray(shard.data),
                                      tree['w'])
    np.testing.assert_array_equal(np.asarray(jax.device_get(w)),
                                  tree['w'])
    assert float(jax.device_get(out['nested']['b'])) == 3.5
    with open(f'{out_path}.p{process_index}', 'w') as f:
        f.write('ok')


def run_comm_bench(iters: int = 10, size: int = 256) -> dict:
    """Grouped-collective timings with the KAISA group axes laid out
    WITHIN vs ACROSS the process boundary (VERDICT r2 #10).

    The MEM/HYBRID tradeoff question is whether inverse/grad broadcast
    groups should be confined to the fast intra-host fabric (ICI on a
    pod; shared memory here) or may span the slow inter-host one (DCN;
    gloo-over-TCP here). The two mesh orientations below put the
    grad-worker axis on each side of the 2-process boundary and time
    the collectives the K-FAC pipeline actually issues. Absolute
    numbers are CPU/gloo, not TPU/DCN — the *ratio* between
    orientations is the recorded evidence (same caveat class as
    bench_matrix config 3).
    """
    import jax
    import jax.numpy as jnp

    from distributed_kfac_pytorch_tpu.parallel.distributed import (
        GRAD_WORKER_AXIS,
        INV_GROUP_AXIS,
        KFAC_AXES,
    )

    n = len(jax.devices())
    x = jnp.ones((size, size), jnp.float32)
    cases = {
        'allreduce_world': (x, lambda v: jax.lax.psum(v, KFAC_AXES) / n),
        'gather_gw_axis': (x, lambda v: jax.lax.all_gather(
            v, GRAD_WORKER_AXIS, tiled=True)),
        'psum_ig_axis': (x, lambda v: jax.lax.psum(v, INV_GROUP_AXIS)),
    }
    return _time_grouped_collectives(cases, iters)


def _time_grouped_collectives(cases, iters):
    """Time {name: (tensor, op)} under both KAISA mesh orientations.

    Single home for the layout construction (the process-boundary
    invariant both comm benches rest on): rows = inverse groups, cols =
    grad workers (Mesh axes order KFAC_AXES = (ig, gw)). Both layouts
    are (n/2, 2) — identical group sizes — so the recorded
    intra-vs-cross ratio isolates the fabric boundary, not collective
    size: 'intra' pairs grad workers within one process (C-order
    reshape keeps process-contiguous device pairs), 'cross' pairs
    device i of process 0 with device i of process 1.
    """
    import time

    import jax
    import numpy as np
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from distributed_kfac_pytorch_tpu.parallel.distributed import (
        KFAC_AXES,
    )

    devs = jax.devices()
    half = len(devs) // 2
    layouts = {
        'gw_intra_process': np.asarray(devs).reshape(half, 2),
        'gw_cross_process': np.stack([np.asarray(devs[:half]),
                                      np.asarray(devs[half:])], axis=1),
    }
    out = {}
    for name, arr in layouts.items():
        mesh = Mesh(arr, KFAC_AXES)
        out[name] = {}
        for op_name, (x, op) in cases.items():
            # kfaclint: waive[retrace-jit-in-loop] per-(layout,op) comm microbench: one program each, compile excluded by the warm call
            fn = jax.jit(jax.shard_map(op, mesh=mesh, in_specs=P(),
                                       out_specs=P(), check_vma=False))
            jax.block_until_ready(fn(x))  # compile + warm
            t0 = time.perf_counter()
            for _ in range(iters):
                jax.block_until_ready(fn(x))
            out[name][op_name] = round(
                (time.perf_counter() - t0) / iters * 1000.0, 3)
    return out


def run_comm_bench_flagship(iters: int = 3) -> dict:
    """Grouped-collective timings at FLAGSHIP factor dims (round 4;
    VERDICT r3 stretch #9): the actual per-phase collectives the K-FAC
    pipeline issues for a ResNet-50-class factor set, with the
    grad-worker axis laid out within vs across the process boundary.

    Tensor set (fp32): the flagship's largest A factor (4609^2, 85 MB),
    a mid-size bucket stack (4 x 1153^2, the unit the inverse
    all_gather moves), and a stage-4 gradient matrix (2048 x 2049, what
    the precondition psum delivers). Absolute numbers are CPU/gloo; the
    intra-vs-cross *ratio* is the recorded ICI-vs-DCN tradeoff shape
    ("replicated eigh may beat comm; measure before committing",
    SURVEY §7).
    """
    import jax.numpy as jnp

    from distributed_kfac_pytorch_tpu.parallel.distributed import (
        GRAD_WORKER_AXIS,
        INV_GROUP_AXIS,
        KFAC_AXES,
    )

    import jax

    cases = {
        'factor_pmean_4609sq': (
            jnp.ones((4609, 4609), jnp.float32),
            lambda v: jax.lax.pmean(v, KFAC_AXES)),
        'inv_gather_gw_4x1153sq': (
            jnp.ones((4, 1153, 1153), jnp.float32),
            lambda v: jax.lax.all_gather(v, GRAD_WORKER_AXIS,
                                         tiled=True)),
        'grad_psum_ig_2048x2049': (
            jnp.ones((2048, 2049), jnp.float32),
            lambda v: jax.lax.psum(v, INV_GROUP_AXIS)),
    }
    return _time_grouped_collectives(cases, iters)


def run_comm_bench_hier(iters: int = 10, size: int = 256) -> dict:
    """Flat vs hierarchical factor-reduction collectives on a 2-slice
    nested mesh whose slice boundary IS the process boundary (r20):
    slice 0 = process 0's devices, slice 1 = process 1's — the
    cross-slice leg is the gloo/DCN stand-in, the on-slice leg stays
    shared-memory/ICI.

    Three rows, one per collective the r20 reduce modes issue:
    ``factor_pmean_flat`` (one global pmean over slice+kfac axes —
    what every factor step pays without hierarchy), ``factor_pmean
    _intra_slice`` (kfac axes only — the hierarchical per-step cost)
    and ``factor_pmean_dcn_boundary`` (slice axis only — the
    hierarchical once-per-window cost). PERF.md's r20 decision rule
    combines them: hierarchical wins a window of W factor steps when
    ``W*intra + dcn < W*flat``.
    """
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from distributed_kfac_pytorch_tpu.parallel.distributed import (
        KFAC_AXES,
        SLICE_AXIS,
    )

    devs = jax.devices()
    half = len(devs) // 2
    # (slice, ig, gw): each slice is one process's devices, laid out
    # as a (half//2, 2) KAISA grid within the slice.
    arr = np.stack([np.asarray(devs[:half]).reshape(half // 2, 2),
                    np.asarray(devs[half:]).reshape(half // 2, 2)])
    mesh = Mesh(arr, (SLICE_AXIS,) + KFAC_AXES)
    x = jnp.ones((size, size), jnp.float32)
    cases = {
        'factor_pmean_flat':
            lambda v: jax.lax.pmean(v, (SLICE_AXIS,) + KFAC_AXES),
        'factor_pmean_intra_slice':
            lambda v: jax.lax.pmean(v, KFAC_AXES),
        'factor_pmean_dcn_boundary':
            lambda v: jax.lax.pmean(v, (SLICE_AXIS,)),
    }
    out = {'slice_per_process': {}}
    for op_name, op in cases.items():
        # kfaclint: waive[retrace-jit-in-loop] per-op comm microbench: one program each, compile excluded by the warm call
        fn = jax.jit(jax.shard_map(op, mesh=mesh, in_specs=P(),
                                   out_specs=P(), check_vma=False))
        jax.block_until_ready(fn(x))  # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            jax.block_until_ready(fn(x))
        out['slice_per_process'][op_name] = round(
            (time.perf_counter() - t0) / iters * 1000.0, 3)
    return out


def main():
    port, pid, nproc, out_path = sys.argv[1:5]
    mode = sys.argv[5] if len(sys.argv) > 5 else 'train'
    _configure()
    from distributed_kfac_pytorch_tpu import launch
    info = launch.initialize_multihost(
        coordinator_address=f'localhost:{port}',
        num_processes=int(nproc), process_id=int(pid))
    assert info['process_count'] == int(nproc), info
    assert info['global_devices'] == 4 * int(nproc), info
    if mode == 'metrics':
        # r7 observability: every process constructs the sink on the
        # same path; only rank 0 writes (the gating under test).
        run_training(metrics_path=out_path,
                     process_index=info['process_index'])
        print(f'worker {pid} done', flush=True)
        return
    if mode == 'stragglers':
        # r10: rank-0 stream PLUS one straggler shard per process
        # (out_path.rank0 / .rank1), each carrying per-step wall +
        # barrier-wait — the write half of the shard merge path.
        run_training(metrics_path=out_path,
                     process_index=info['process_index'],
                     rank_shards=True)
        print(f'worker {pid} done', flush=True)
        return
    if mode == 'replicate':
        # r11 satellite: the multi-process replicate_on_mesh branch.
        run_replicate_check(out_path, info['process_index'])
        print(f'worker {pid} done', flush=True)
        return
    if mode == 'resilience':
        # r8: collective per-step checkpoints; optionally kill worker 1
        # after step KILL_AT's save, or resume from the newest step.
        # argv: ... OUT.npz resilience CKPT_DIR KILL_AT RESUME(0|1)
        ckpt_dir, kill_at, resume = sys.argv[6:9]
        n_steps = int(sys.argv[9]) if len(sys.argv) > 9 else 4
        params, losses = run_training(
            n_steps=n_steps, process_index=info['process_index'],
            checkpoint_dir=ckpt_dir,
            kill_at=None if kill_at == '-' else int(kill_at),
            resume=resume == '1')
        if info['process_index'] == 0:
            import numpy as np

            import jax
            flat = {'/'.join(map(str, path)): leaf
                    for path, leaf in
                    jax.tree_util.tree_flatten_with_path(params)[0]}
            np.savez(out_path, losses=np.asarray(losses),
                     **{k: v for k, v in flat.items()})
        print(f'worker {pid} done', flush=True)
        return
    if mode in ('comm', 'comm_flagship', 'comm_hier'):
        result = (run_comm_bench_flagship() if mode == 'comm_flagship'
                  else run_comm_bench_hier() if mode == 'comm_hier'
                  else run_comm_bench())
        if info['process_index'] == 0:
            import json
            with open(out_path, 'w') as f:
                json.dump({'processes': int(nproc),
                           'devices_per_process': 4,
                           'transport': 'gloo (DCN stand-in) + '
                                        'shared-memory (ICI stand-in)',
                           'unit': 'ms/op', **result}, f, indent=1)
        print(f'worker {pid} done', flush=True)
        return
    params, losses = run_training()
    if info['process_index'] == 0:
        import numpy as np

        import jax
        flat = {'/'.join(map(str, path)): leaf
                for path, leaf in
                jax.tree_util.tree_flatten_with_path(params)[0]}
        np.savez(out_path, losses=np.asarray(losses),
                 **{k: v for k, v in flat.items()})
    print(f'worker {pid} done', flush=True)


if __name__ == '__main__':
    main()
