"""The K-FAC distributed gradient preconditioner (TPU-native core).

Functional redesign of the reference orchestrator
(kfac/preconditioner.py:39-735). The reference is a torch Optimizer that
mutates per-layer state through hooks; here the preconditioner is a pure
state transition

    precond_grads, new_state = kfac.step(state, grads, captures, ...)

with all per-layer state (running-average factors, eigendecompositions,
step counter) carried in one pytree. The whole pipeline — factor EWMA,
inverse/eigendecomposition, preconditioning, KL clipping — traces into a
single XLA program:

  - periodic work (``factor_update_freq`` / ``inv_update_freq`` gating,
    reference preconditioner.py:494-510) is ``lax.cond`` on the on-device
    step counter, so cadences are runtime-schedulable without recompiles;
  - the O(n^3) eigendecompositions are *bucketed by factor size* and run as
    one vmapped ``eigh`` per bucket — large batched MXU-friendly kernels
    instead of ~100 tiny sequential ones (and the natural unit for
    sharding inverse work across the mesh);
  - the KL-clip scale (reference preconditioner.py:661-682) is an on-device
    scalar — no per-layer ``.item()`` device->host syncs.

Distribution: factor *statistics* need no explicit collectives — captures
are batch-sharded over the mesh and XLA turns the covariance contraction
into a psum (the allreduce of reference preconditioner.py:525-533).
COMM_OPT / MEM_OPT / HYBRID_OPT placement of inverse and preconditioning
work lives in ``parallel.distributed``.
"""

from __future__ import annotations

import enum
import os
import warnings
from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_kfac_pytorch_tpu import fp16 as fp16_ops
from distributed_kfac_pytorch_tpu import layers as L
from distributed_kfac_pytorch_tpu.observability import (
    metrics as obs_metrics,
)
from distributed_kfac_pytorch_tpu.observability import profiling
from distributed_kfac_pytorch_tpu.observability import tracing
from distributed_kfac_pytorch_tpu.capture import (BLOCK_STACK_KINDS,
                                                  EMBEDDING, EXPERTS,
                                                  KFACCapture,
                                                  share_a_owners,
                                                  subsample_captures)
from distributed_kfac_pytorch_tpu.ops import factors as F
from distributed_kfac_pytorch_tpu.ops import linalg
from distributed_kfac_pytorch_tpu.ops import pallas_kernels


class CommMethod(enum.Enum):
    """Communication strategy (reference preconditioner.py:19-36).

    - COMM_OPT: every device holds all inverses and preconditions its own
      gradients; inverses are all-gathered after computation ('KFAC_opt').
    - MEM_OPT: each layer's inverses live on one device, which computes the
      preconditioned gradient and broadcasts it ('KFAC_lw').
    - HYBRID_OPT: a ``grad_worker_fraction`` of devices per layer hold
      inverses and precondition; the rest receive the result (KAISA).
    """
    COMM_OPT = 1
    MEM_OPT = 2
    HYBRID_OPT = 3


def cadence_gate(flag: bool | None, step, freq, do, keep):
    """Shared static/dynamic gating for periodic pipeline stages.

    ``flag=None`` gates dynamically — ``lax.cond(step % freq == 0)`` on
    the on-device counter; a Python bool is static — the stage is simply
    present or absent from the trace (the TPU fast path, see
    :meth:`KFAC.step`). Single point of truth so the single-chip and
    SPMD pipelines cannot drift.
    """
    if flag is None:
        return jax.lax.cond(step % freq == 0, do, keep)
    return do() if flag else keep()


def _tree_size_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves(tree) if hasattr(x, 'size'))


class KFAC:
    """K-FAC gradient preconditioner over a flax model.

    Hyperparameter surface mirrors the reference constructor
    (kfac/preconditioner.py:135-214); torch-specific knobs (grad_scaler —
    bf16 needs no loss scaling; compute_factor_in_hook — capture is fused
    into the step by construction) are intentionally absent.

    Args:
      model: flax module to precondition (registration walks its Dense /
        Conv / Embed submodules, minus ``skip_layers``).
      damping: Tikhonov damping (default 0.001).
      factor_decay: running-average coefficient for factors (default 0.95).
      factor_update_freq: steps between factor statistic updates (def. 10).
      inv_update_freq: steps between eigendecompositions (default 100).
      kl_clip: KL clipping parameter; None disables scaling (default 0.001).
      lr: learning rate used in the KL-clip scale (default 0.1).
      use_eigen_decomp: eigendecomposition method if True, else damped
        inverses (default None -> per-dim 'auto' dispatch; mutually
        consistent with ``inverse_method`` — contradictory combinations
        raise).
      inverse_method: 'auto' (the default — per-factor-dim dispatch:
        the eigen path with the warm-start polish where it wins, dims
        <= ``auto_eigen_max_dim``; ``auto_large_method`` damped inverses
        above, where the fp32 polish matmuls blow up — measured 41x at
        flagship 4609-dim factors, PERF.md round 3/4. One default that
        is fast at every scale, the analogue of the reference's one
        eigen default serving all dims, kfac/layers/base.py:432-441),
        'eigen' (same as ``use_eigen_decomp=True`` — every factor),
        'cholesky' (XLA Cholesky + triangular solves, the reference's
        non-eigen method) or 'newton' (matmul-only Newton–Schulz, Pallas
        VMEM-resident on TPU — see ops.pallas_kernels).
      auto_eigen_max_dim: largest factor dim the 'auto' dispatch keeps
        on the eigen path (default 640 — the measured v5e crossover
        region: warm polish wins 3-5x over cold eigh at CIFAR-class
        dims <= 577 and costs seconds per firing at 2305+; PERF.md).
        Layers with one side above and one below mix representations;
        any such *split* layer preconditions as the reference's
        non-eigen operator ``(G+λI)^{-1} ⊗ (A+λI)^{-1}`` (damping
        semantics note: PARITY.md; dispatch: linalg.precondition_dispatch).
      auto_large_method: 'cholesky' (default) or 'newton' — the damped
        inverse used above the cutoff in 'auto' mode.
      inv_lowrank_rank: rank of the randomized truncated
        eigendecomposition path (r19, *Randomized K-FACs*
        arXiv:2206.15397). 0 (default) = off — the exact per-dim
        dispatch above, bit-identical. With ``r > 0``, dense factor
        dims ``>= inv_lowrank_dim_threshold`` decompose as a rank-r
        truncated eigenpair instead of a full O(d^3) factorization:
        a Gaussian range-finder sketch seeds the basis once, and each
        firing refreshes it with one subspace iteration plus the
        warm-start polish (``ops.linalg.lowrank_eigh`` — r·d^2 matmul
        work, carried basis converges across windows). Preconditioning
        consumes the truncated (Q, d) plus the damping-only complement
        (``I/λ`` on the discarded tail — full-rank correct, tail
        curvature regularized to the damping floor), so the per-step
        eigen contractions are r-thin too. The truncated slots replace
        the engaged sides' dense representation (a KAISA-style
        memory/compute trade-off knob, arXiv:2107.01739 — state for an
        engaged side is r·d instead of d^2); the exact path stays the
        default and the parity oracle. ``r`` must be < every engaged
        dim (validated at registration — rank >= dim is a hard error,
        never a silent fallback). Composes with ``inv_pipeline_chunks``
        (the LPT chunk planner switches the engaged buckets' cost
        model to r·dim^2), ``inv_staleness`` and the bf16 pipeline.
      inv_lowrank_dim_threshold: smallest dense factor dim the
        low-rank path engages (default 2048 — transformer-scale
        factors, where the exact decomposition is the measured
        fired-step wall; BENCH_r09/r14). Ignored at
        ``inv_lowrank_rank=0``.
      eigh_method: backend for the eigen path's decompositions:
        'auto' (default — the warm-start matmul-only basis polish,
        ops.linalg.eigh_polish, seeded from the previous firing's
        eigenbasis carried in the state; falls back to 'xla' where no
        previous basis exists, e.g. factor-only checkpoint restore),
        'warm' (always polish), 'xla' (the backend eigh every firing)
        or 'jacobi' (vectorized parallel cyclic Jacobi,
        ops.linalg.jacobi_eigh). On TPU 'auto' is both faster and
        data-independent in runtime: the backend eigh's iterative
        while-loops run ~5x longer on trained covariance factors than
        on identity-seeded ones (PERF.md §6).
      eigh_polish_iters: fixed iteration count for the warm polish
        (default 8 — ~1e-3 worst-case preconditioner error at EWMA
        drift rates, measured indistinguishable from 16 iters on the
        workload-level convergence study while saving ~1.5 ms/iter on
        the tracked config at inv_freq=10; pass 16 for the ~1e-5
        tracking regime. Sweep data: PERF.md round 3; see
        ops.linalg.eigh_polish).
      newton_iters: iteration cap for 'newton' (the loop exits early on
        a 1e-5 residual; ~log2(cond)+6 iterations are used in practice).
      factor_dtype: dtype for factor running averages (default fp32; pass
        ``jnp.bfloat16`` for bf16 factor storage/comm — the analogue of the
        reference's keep-autocast-dtype policy, README.md:150-160).
      factor_compute_dtype: input dtype/precision for the covariance
        matmuls (accumulation is always fp32). Default None uses the
        backend's native matmul precision — on TPU that is bf16 inputs
        with fp32 accumulation (~4e-3 relative covariance error), the
        production fast path. ``jnp.float32`` requests *strict* fp32
        (inputs cast + ``Precision.HIGHEST``; numerics parity with the
        reference's fp32 factors at ~2x covariance cost on TPU).
        ``jnp.bfloat16`` makes the bf16 fast path explicit — the
        analogue of the reference's fp16 factor mode (``--fp16``,
        launch_node_torch_imagenet.sh:73-87) with better accumulation.
        See ops.factors.get_cov for the measured numbers.
      factor_batch_fraction: fraction of the (per-device) batch used for
        the A/G covariance statistics (default 1.0 = reference parity:
        the whole batch). Values < 1 keep ``ceil(B * f)`` evenly-strided
        rows of every capture before the factor contraction — an
        estimator of the same expectations (every covariance here
        normalizes by its own row count; strided, not a head slice, so
        ordered batches still contribute across the batch), thinning
        *within* the batch exactly as the reference's production cadence
        thins
        *across* steps (factors from one batch in 50,
        launch_node_torch_imagenet.sh:73-87). The factor phase's cost
        (patch materialization + contraction, the dominant K-FAC
        overhead at CIFAR scale — PERF.md roofline) scales with f.
        Gradients and preconditioning always see the full batch.
      capture_dtype: dtype for captured activations ('a'). Default
        'auto' = bf16 on TPU (what the covariance matmul keeps anyway;
        halves capture + im2col patch traffic — see KFACCapture), fp32
        passthrough elsewhere and under strict
        ``factor_compute_dtype=float32`` parity. ``None`` = always
        passthrough; explicit dtype forces the cast. Reference parity:
        hooks capture the autocast dtype under AMP
        (kfac/layers/base.py:385).
      inv_dtype: dtype for stored inverses (default fp32; decompositions
        always *computed* in fp32, reference base.py:432-441).
      precond_compute_dtype: input dtype for the per-step precondition
        contractions (``inverse · grad``), mirroring
        ``factor_compute_dtype``'s contract — accumulation is always
        fp32, and the damping quotient on the eigen path stays fp32.
        Default None is the legacy path (operands upcast to fp32,
        backend-native matmul precision) and is bit-identical to the
        pre-knob behavior. ``jnp.bfloat16`` runs bf16 operands with
        fp32 accumulation — the MXU fast path for the every-step
        ``G_inv @ grad @ A_inv`` matmuls that dominate the LM
        flagship's non-factor overhead (PERF.md r6); combined with
        ``inv_dtype=jnp.bfloat16`` the stored inverses are consumed
        *resident* (no fp32 upcast-on-read copy — the bandwidth lever
        when the step is HBM-bound on inverse reads). ``jnp.float32``
        requests strict fp32 (``Precision.HIGHEST``). Threaded through
        ``linalg.precondition_dispatch`` for every branch
        (eigen / baked-inverse / diagonal / mixed), single-chip and
        SPMD alike.
      kfac_approx: weight-sharing Kronecker approximation policy
        (arXiv:2311.00636; see ``sharing.approx``). ``'expand'``
        (default) flattens every layer's shared sequence/patch axis
        into covariance rows — bit-identical to the pre-sharing code
        path (test-pinned). ``'reduce'`` engages the automatic
        by-module-kind policy: sequence/patch-shared Denses (attention
        q/k/v/o, MLP in/out) and patch-embedding convs reduce over the
        shared axis BEFORE the covariance (activations averaged,
        output-grads summed — Eq. 22's bias-column-exactly-1
        convention), a factor-T cheaper factor update with matching
        quality on transformer/ViT workloads (PERF.md r13); everything
        else stays expand. A ``{pattern: 'expand'|'reduce'}`` dict
        gives explicit per-layer control (loud validation). Factor
        DIMS are approximation-invariant, so the state layout, KAISA
        buckets and chunk plans are identical under any setting — the
        choice is static program structure (zero retraces,
        test-pinned).
      tied_embeddings: capture ``Embed.attend`` call sites (the tied
        in/out decoder, flax's form of the reference
        register_shared_module pair, preconditioner.py:404-470) so
        both uses of a tied embedding weight contribute statistics to
        ONE factor pair with ONE inverse entry: A gains the attend
        output-grads' diagonal vocab covariance, G the attend inputs'
        covariance. ``None`` (default) follows the sharing subsystem —
        on with any non-expand ``kfac_approx``, off under the pure
        default (which keeps the all-default path bit-identical:
        lookup-only statistics, the historical behavior where the
        attend site contributed gradient but no statistics). State
        layout is unchanged either way (additive statistics only —
        MIGRATION.md).
      skip_layers: module names/classes to skip (case-insensitive, prunes
        subtrees).
      trainable: optional predicate ``trainable(module_path) -> bool``
        marking which layers actually train — frozen layers (e.g. an
        optax.masked fine-tune) get plain gradients and NO factor/
        inverse work (reference module_requires_grad,
        kfac/layers/__init__.py:38-40).
      symmetry_aware_comm: communicate only ~half of each (symmetric)
        factor matrix — a gather-free rectangular triangular packing
        (ops.factors.pack_symmetric) before the allreduce (reference
        kfac/layers/base.py:120-125). Worth it when factor averaging
        crosses hosts (DCN-bound); on-chip the pack/unpack mask-and-
        concat work usually costs more than the halved bytes.
      assignment_strategy: 'compute' (n^3 cost) or 'memory' (n^2) for the
        LPT work balancer (reference preconditioner.py:625-628).
      comm_method / grad_worker_fraction: see CommMethod; consumed by the
        distributed step builder in ``parallel.distributed``.
      collect_metrics: carry an on-device metrics pytree in the state
        (``state['metrics']``, see observability.metrics) updated by
        the step — damping, KL-clip ν, grad/preconditioned-grad norms,
        per-bucket precondition norms, factor/inverse firing counts,
        eigenvalue-floor clips, non-finite events. All traced scalar
        updates: no host syncs; the host drains asynchronously (the
        engine's JSONL sink). Default False is bit-identical to the
        pre-observability step — the same discipline as
        ``precond_compute_dtype=None`` (test-pinned).
      inv_pipeline_chunks: pipeline the per-firing inverse work across
        the cadence window (default 1 = reference parity, bit-identical:
        the whole factor set decomposes in one firing step). With
        ``k > 1`` the inverse work items (the same-shape bucket stacks
        the precondition/linalg paths already form, plus the grouped/
        diagonal layers) are greedy-bin-packed into ``k`` cost-balanced
        chunks on a dim^3 proxy (:meth:`inverse_chunk_plan`), and the
        engine fires chunk ``j`` on step ``t = j * inv_update_freq/k``
        of each window instead of firing everything at the window head —
        smearing the decomposition spike (measured 4x the non-factor
        step on the xl LM flagship, PERF.md r5) into ``k`` smaller ones.
        Each chunk phase is its own statically-compiled program variant
        (``KFAC.step(inv_chunk=j)`` /
        ``DistributedKFAC.build_train_step``'s variant cache) — cadence
        stays static program structure, no retraces (PERF.md pitfalls
        2-3). Semantics: every factor still refires every
        ``inv_update_freq`` steps; chunks fired mid-window see factors
        up to ``inv_update_freq * (k-1)/k`` steps FRESHER than the
        window head (strictly less stale than the reference), but
        layer inverses are no longer simultaneous across chunks — with
        factors frozen across a window, one full pipelined window is
        bit-identical to a monolithic firing (test-pinned). The eigen
        warm-start carry is unaffected: each factor's previous basis is
        per-factor state updated only when its own chunk fires, so
        chunking is NOT rejected under ``inverse_method='eigen'`` /
        warm polish (documented decision, ISSUE r9). Constraints:
        ``k >= 1``, ``k`` must divide ``inv_update_freq``, and ``k``
        may not exceed the model's inverse work-item count (validated
        at registration).
      inv_pipeline_costs: optional ``{factor_dim: measured_ms}``
        refinement for the chunk bin-packing — the per-bucket
        ``bucket_parts`` ms of a flagship firing leg
        (FLAGSHIP_LM_*.jsonl) in place of the default
        ``count * dim^3`` proxy. Must cover EVERY dense factor dim of
        the model (validated at plan time): ms and the dim^3 proxy are
        different units and a partial dict would silently un-balance
        the packing.
      deferred_factor_reduction: accumulate factor-statistic
        contributions LOCALLY on factor steps and apply them to the
        running averages only at the cadence-window boundary where the
        inverses consume them (default False = reference parity: the
        EWMA advances — and, under SPMD, the cross-replica factor
        ``pmean`` fires — on every factor step). The decayed EMA is
        linear, so the deferred form is mathematically exact at every
        consumption point: with per-step decay ``α_i`` the boundary
        update ``F ← (Π α_i) · F + Σ_i (Π_{j>i} α_j)(1-α_i) · c_i``
        equals the per-step recursion, and (under SPMD)
        ``pmean(Σ w_i c_i) = Σ w_i pmean(c_i)`` — equal up to fp
        associativity (the summation order differs). The win is on the
        mesh: the per-factor-step collective on the critical path
        collapses to ONE bucketed reduction per cadence window
        (``kfac/comm/factor_reduce``; arXiv:2107.06533's smart-overlap
        framing, ROADMAP item 2). Static-cadence only — the reduce is
        static program structure like ``inv_chunk`` (the engine passes
        ``factor_reduce=True`` on window-head steps). Scope notes:
        mid-window chunk firings (``inv_pipeline_chunks > 1``) see the
        factors as of the last window-head reduction (the staleness
        profile of ``inv_staleness=1`` rather than r9's
        fresher-mid-window factors); with ``nonfinite_guard`` the
        finiteness check moves to the reduce point's post-average
        candidate (collective-safe, unchanged), so a poisoned window
        is skipped WHOLE — the accumulator resets either way.
      hierarchical_reduce: two-level factor reduction for multi-slice
        meshes (r20, SPMD-only; mutually exclusive with
        ``deferred_factor_reduction``). Factor contributions are
        ``pmean``-ed WITHIN each slice (over ICI) on every factor step
        and folded into a per-slice accumulator; the inter-slice
        (DCN) half of the mean is deferred to ONE bucketed reduce per
        cadence window (``kfac/comm/factor_reduce_dcn``) — exact by
        the same EMA-linearity argument as the deferred form, since
        ``pmean_slices(pmean_intra(c)) = pmean_all(c)``. Requires a
        ``multislice.make_multislice_mesh`` mesh with > 1 slice;
        :class:`KFAC` itself (single-chip, no mesh) raises on step.
      inv_staleness: 0 (default) or 1. At 1, the decompositions
        consumed during cadence window ``w+1`` are computed from
        factors FROZEN at the end of window ``w`` (a snapshot carried
        in ``state['frozen_factors']``, refreshed on window-head
        steps) and fired across the window's plain steps: chunk ``j``
        fires at phase ``j * inv_update_freq/k + 1`` instead of r9's
        ``j * stride`` (with ``inv_pipeline_chunks == 1`` the whole
        firing runs as one chunk at phase 1). Because the firing reads
        the snapshot, it has NO data dependency on the firing step's
        forward/backward or factor update — XLA can overlap the eigh
        with the step's compute and collectives instead of serializing
        behind them (arXiv:2206.15143's off-critical-path inverses),
        and the +1 phase offset keeps the spike off the window-head
        step that pays the factor reduction. Preconditioning applies a
        one-window-stale inverse (the monolithic k=1 staleness
        profile; strictly staler than r9's mid-window chunks) — gate
        promotion on a convergence A/B exactly like r9's (PERF.md
        r14). Step 0 still fires monolithically from the fresh
        snapshot (slots are zero-seeded). Static-cadence only.
        Requires ``inv_update_freq / inv_pipeline_chunks >= 2`` so the
        shifted phases stay inside the window.
      nonfinite_guard: skip the factor EWMA update when the candidate
        factors are non-finite (a NaN/Inf gradient/capture batch would
        otherwise poison the running averages forever — EWMA keeps
        NaN). The skip is on-device (``where`` on a finiteness flag,
        collective-safe: it checks the post-average candidates) and
        counted in ``metrics['nonfinite_skips']`` when metrics are on.
        Scope: this protects the FACTOR STATISTICS only — the same
        step's gradients still flow through precondition and whatever
        optimizer update the caller applies. For a whole-step skip of
        params/optimizer on non-finite gradients, use the dynamic
        loss-scale path (``build_train_step(loss_scale='dynamic')`` —
        GradScaler parity), which composes with this guard.
        Default False = reference behavior (no guard).
    """

    def __init__(self, model: nn.Module, *,
                 damping: float = 0.001,
                 factor_decay: float = 0.95,
                 factor_update_freq: int = 10,
                 inv_update_freq: int = 100,
                 kl_clip: float | None = 0.001,
                 lr: float = 0.1,
                 use_eigen_decomp: bool | None = None,
                 inverse_method: str | None = None,
                 auto_eigen_max_dim: int = 640,
                 auto_large_method: str = 'cholesky',
                 inv_lowrank_rank: int = 0,
                 inv_lowrank_dim_threshold: int = 2048,
                 eigh_method: str = 'auto',
                 eigh_polish_iters: int = 8,
                 newton_iters: int = 100,
                 factor_dtype: Any = None,
                 factor_compute_dtype: Any = None,
                 factor_batch_fraction: float = 1.0,
                 capture_dtype: Any = 'auto',
                 inv_dtype: Any = jnp.float32,
                 precond_compute_dtype: Any = None,
                 inv_pipeline_chunks: int = 1,
                 inv_pipeline_costs: dict | None = None,
                 deferred_factor_reduction: bool = False,
                 hierarchical_reduce: bool = False,
                 inv_staleness: int = 0,
                 kfac_approx: Any = 'expand',
                 tied_embeddings: bool | None = None,
                 skip_layers: str | Sequence[str] | None = None,
                 trainable: Any = None,
                 symmetry_aware_comm: bool = False,
                 assignment_strategy: str = 'compute',
                 comm_method: CommMethod = CommMethod.COMM_OPT,
                 grad_worker_fraction: float = 0.25,
                 collect_metrics: bool = False,
                 nonfinite_guard: bool = False,
                 verbose: bool = False):
        if factor_update_freq < 1 or inv_update_freq < 1:
            raise ValueError('update frequencies must be >= 1')
        if inv_update_freq % factor_update_freq != 0:
            warnings.warn(
                'inv_update_freq is not a multiple of factor_update_freq: '
                'some inverse updates will reuse stale factors '
                f'({inv_update_freq=} {factor_update_freq=})')
        if inv_pipeline_chunks < 1:
            raise ValueError(
                f'{inv_pipeline_chunks=} must be >= 1')
        if inv_pipeline_chunks > 1:
            if inv_update_freq % inv_pipeline_chunks != 0:
                raise ValueError(
                    'inv_pipeline_chunks must divide inv_update_freq '
                    'so chunk phases land on whole steps '
                    f'({inv_pipeline_chunks=} {inv_update_freq=})')
            stride = inv_update_freq // inv_pipeline_chunks
            if stride % factor_update_freq != 0:
                warnings.warn(
                    'inv_update_freq/inv_pipeline_chunks is not a '
                    'multiple of factor_update_freq: some chunk '
                    'firings will reuse stale factors '
                    f'({inv_update_freq=} {inv_pipeline_chunks=} '
                    f'{factor_update_freq=})')
        if inv_staleness not in (0, 1):
            raise ValueError(
                f'{inv_staleness=} must be 0 or 1 (one-window-stale '
                'off-critical-path inverses; deeper staleness is not '
                'supported)')
        if inv_staleness == 1:
            k = max(1, inv_pipeline_chunks)
            if inv_update_freq % k != 0 or inv_update_freq // k < 2:
                raise ValueError(
                    'inv_staleness=1 fires chunk j at phase '
                    'j*(inv_update_freq/inv_pipeline_chunks)+1 of each '
                    'window, which needs inv_update_freq/'
                    'inv_pipeline_chunks >= 2 so the shifted phases '
                    f'stay inside the window ({inv_update_freq=} '
                    f'{inv_pipeline_chunks=})')
        if assignment_strategy not in ('compute', 'memory'):
            raise ValueError("assignment_strategy must be 'compute' or "
                             "'memory'")
        if (capture_dtype == 'auto' and factor_compute_dtype is not None
                and jnp.dtype(factor_compute_dtype).itemsize
                > jnp.dtype(jnp.bfloat16).itemsize):
            # A strict high-precision factor request (fp32, fp64, ...)
            # implies captures at least that wide: a bf16 capture would
            # discard the precision the high-precision covariance
            # contraction exists to keep (ADVICE r3: the old gate only
            # matched fp32, leaking bf16 captures under fp64).
            capture_dtype = None
        # Weight-sharing approximation policy (sharing.approx,
        # arXiv:2311.00636): 'expand' (default, bit-identical to the
        # pre-sharing code path), 'reduce' (automatic by-module-kind:
        # sequence/patch-shared Denses + patch-embed convs reduce over
        # the shared axis before the covariance — a factor-T cheaper
        # factor update), or a {pattern: approx} dict for explicit
        # per-layer control. Resolved per layer at init() and carried
        # in the LayerSpec registry (static program structure).
        from distributed_kfac_pytorch_tpu.sharing import approx as _approx
        if isinstance(kfac_approx, str) or kfac_approx is None:
            if kfac_approx not in (None, 'expand', 'reduce'):
                raise ValueError(
                    f"kfac_approx must be 'expand', 'reduce' or a "
                    f'{{pattern: approx}} dict, got {kfac_approx!r}')
        elif not isinstance(kfac_approx, dict):
            raise ValueError(
                f"kfac_approx must be 'expand', 'reduce' or a dict, "
                f'got {type(kfac_approx).__name__}')
        self.kfac_approx = kfac_approx if kfac_approx is not None \
            else 'expand'
        self._approx_mod = _approx
        # Tied-embedding handling (one factor pair + one inverse for an
        # in/out-tied Embed; the attend call site's statistics join the
        # lookup's). None follows the sharing subsystem: engaged when
        # the setting actually names 'reduce' anywhere, off otherwise —
        # so 'expand', AND an all-expand dict like {'embed': 'expand'},
        # keep the bit-identical pre-sharing path (an explicit
        # per-layer pin must not silently change the capture program).
        if tied_embeddings is None:
            if isinstance(self.kfac_approx, dict):
                tied_embeddings = any(v == 'reduce'
                                      for v in self.kfac_approx.values())
            else:
                tied_embeddings = self.kfac_approx == 'reduce'
        self.tied_embeddings = bool(tied_embeddings)
        self.capture = KFACCapture(model, skip_layers=skip_layers,
                                   capture_dtype=capture_dtype,
                                   trainable=trainable,
                                   tied_embeddings=self.tied_embeddings)
        self.model = model
        self.damping = damping
        self.factor_decay = factor_decay
        self.factor_update_freq = factor_update_freq
        self.inv_update_freq = inv_update_freq
        self.kl_clip = kl_clip
        self.lr = lr
        if inverse_method is None:
            if use_eigen_decomp is None:
                inverse_method = 'auto'
            else:
                inverse_method = ('eigen' if use_eigen_decomp
                                  else 'cholesky')
        if inverse_method not in ('auto', 'eigen', 'cholesky', 'newton'):
            raise ValueError(
                "inverse_method must be 'auto', 'eigen', 'cholesky' or "
                f"'newton', got {inverse_method!r}")
        if use_eigen_decomp is not None and (
                inverse_method == 'auto'
                or use_eigen_decomp != (inverse_method == 'eigen')):
            raise ValueError(
                f'{use_eigen_decomp=} contradicts {inverse_method=}; '
                'set one or the other')
        if auto_large_method not in ('cholesky', 'newton'):
            raise ValueError(
                "auto_large_method must be 'cholesky' or 'newton', "
                f'got {auto_large_method!r}')
        if eigh_method not in ('auto', 'xla', 'jacobi', 'warm'):
            raise ValueError(
                "eigh_method must be 'auto', 'xla', 'jacobi' or 'warm', "
                f'got {eigh_method!r}')
        self.inverse_method = inverse_method
        self.use_eigen_decomp = inverse_method == 'eigen'
        self.auto_eigen_max_dim = auto_eigen_max_dim
        self.auto_large_method = auto_large_method
        inv_lowrank_rank = int(inv_lowrank_rank)
        inv_lowrank_dim_threshold = int(inv_lowrank_dim_threshold)
        if inv_lowrank_rank < 0:
            raise ValueError(
                f'{inv_lowrank_rank=} must be >= 0 (0 disables the '
                'randomized low-rank inverse path)')
        if inv_lowrank_rank > 0 and inv_lowrank_dim_threshold < 2:
            raise ValueError(
                f'{inv_lowrank_dim_threshold=} must be >= 2 with '
                'inv_lowrank_rank > 0 (a rank-r truncation of a '
                'dim < 2 factor cannot satisfy rank < dim)')
        self.inv_lowrank_rank = inv_lowrank_rank
        self.inv_lowrank_dim_threshold = inv_lowrank_dim_threshold
        self.eigh_method = eigh_method
        self.eigh_polish_iters = eigh_polish_iters
        self.newton_iters = newton_iters
        if not 0.0 < factor_batch_fraction <= 1.0:
            raise ValueError(
                f'{factor_batch_fraction=} must be in (0, 1]')
        self.factor_batch_fraction = factor_batch_fraction
        self.factor_dtype = factor_dtype
        self.factor_compute_dtype = factor_compute_dtype
        self.inv_dtype = inv_dtype
        self.precond_compute_dtype = precond_compute_dtype
        self.inv_pipeline_chunks = inv_pipeline_chunks
        self.inv_pipeline_costs = (dict(inv_pipeline_costs)
                                   if inv_pipeline_costs else None)
        if hierarchical_reduce and deferred_factor_reduction:
            raise ValueError(
                'hierarchical_reduce and deferred_factor_reduction are '
                'mutually exclusive: hierarchical reduce already '
                'defers the (inter-slice DCN) half of the factor '
                'reduction to the window boundary, and its intra-slice '
                'ICI pmean must fire every factor step')
        self.deferred_factor_reduction = bool(deferred_factor_reduction)
        self.hierarchical_reduce = bool(hierarchical_reduce)
        self.inv_staleness = int(inv_staleness)
        self.symmetry_aware_comm = symmetry_aware_comm
        self.assignment_strategy = assignment_strategy
        self.comm_method = comm_method
        self.grad_worker_fraction = grad_worker_fraction
        self.collect_metrics = collect_metrics
        self.nonfinite_guard = nonfinite_guard
        if os.environ.get('KFAC_FUSED_PATCH_COV', '') == '1':
            # The opt-in study kernel ops.factors.conv2d_a_factor
            # dispatches to from inside the trace.
            pallas_kernels.fused_patch_cov_supported()
        self.verbose = verbose
        self._specs: dict[str, Any] | None = None

    def __repr__(self) -> str:
        """Hyperparameter dump (reference KFAC.__repr__,
        preconditioner.py:265-292)."""
        fields = ('damping', 'factor_decay', 'factor_update_freq',
                  'inv_update_freq', 'kl_clip', 'lr', 'inverse_method',
                  'auto_eigen_max_dim', 'auto_large_method',
                  'inv_lowrank_rank', 'inv_lowrank_dim_threshold',
                  'eigh_method', 'eigh_polish_iters', 'newton_iters',
                  'factor_batch_fraction', 'factor_dtype',
                  'factor_compute_dtype', 'inv_dtype',
                  'precond_compute_dtype', 'inv_pipeline_chunks',
                  'deferred_factor_reduction', 'hierarchical_reduce',
                  'inv_staleness',
                  'kfac_approx', 'tied_embeddings',
                  'symmetry_aware_comm',
                  'assignment_strategy', 'comm_method',
                  'grad_worker_fraction', 'collect_metrics',
                  'nonfinite_guard')
        lines = [f'  {name}: {getattr(self, name)!r}' for name in fields]
        n_layers = (len(self._specs) if self._specs is not None
                    else '<uninitialized>')
        lines.append(f'  registered_layers: {n_layers}')
        if self._specs is not None:
            lines.append(f'  layers_sharing_an_a: {len(self.a_followers())}')
        return 'KFAC(\n' + '\n'.join(lines) + '\n)'

    # ------------------------------------------------------------------
    # Per-dim inverse dispatch
    # ------------------------------------------------------------------

    def method_for_dim(self, dim: int) -> str:
        """Decomposition method for a dense factor of this dimension.

        'auto' dispatches per dim (eigen below ``auto_eigen_max_dim``,
        ``auto_large_method`` above — the measured v5e crossover,
        PERF.md); global modes return themselves. Host-side, static:
        the dispatch is baked into the trace, so it costs nothing at
        runtime and the single-chip and SPMD paths share it (VERDICT r3
        asks #1/#7).

        The r19 low-rank knob sits in FRONT of the base dispatch:
        with ``inv_lowrank_rank > 0``, any dense dim at or above
        ``inv_lowrank_dim_threshold`` resolves to ``'lowrank'`` — the
        randomized truncated eigendecomposition — regardless of the
        base method (the knob exists to replace whatever the large-dim
        path was; at rank 0 the dispatch is byte-identical to r18).
        """
        if (self.inv_lowrank_rank > 0
                and dim >= self.inv_lowrank_dim_threshold):
            return 'lowrank'
        if self.inverse_method == 'auto':
            return ('eigen' if dim <= self.auto_eigen_max_dim
                    else self.auto_large_method)
        return self.inverse_method

    def lowrank_rank_for(self, dim: int) -> int | None:
        """The truncation rank for a dim, or None where the exact path
        runs — the cost-model hook the r9/r14 chunk planners feed to
        ``linalg.decomposition_cost(dim, rank=...)``."""
        return (self.inv_lowrank_rank
                if self.method_for_dim(dim) == 'lowrank' else None)

    def _side_methods(self, spec, a_dim: int, g_dim: int
                      ) -> tuple[str | None, str | None]:
        """(A-side, G-side) methods for one layer; diagonal A -> None;
        grouped convs and stacked experts -> (None, None) (their block
        stacks run a batched damped Cholesky, outside the dense per-dim
        dispatch — a grouped conv's blocks are tiny, so eigen warm-start
        bookkeeping would cost more than it saves, and an expert's are
        above the 'auto' eigen cutoff at any width worth routing)."""
        if spec.kind in BLOCK_STACK_KINDS:
            return None, None
        ma = (None if spec.kind == EMBEDDING
              else self.method_for_dim(a_dim))
        return ma, self.method_for_dim(g_dim)

    # ------------------------------------------------------------------
    # Pipelined inverse firing: chunk planning
    # ------------------------------------------------------------------

    @property
    def pipelined_firing(self) -> bool:
        """True when the in-window chunk-firing machinery is engaged:
        ``inv_pipeline_chunks > 1`` (r9), or ``inv_staleness == 1`` —
        which chunk-fires even a single chunk mid-window from the
        frozen snapshot (at ``k == 1`` the plan is one chunk holding
        every work item, so the per-firing program keeps the
        monolithic shape)."""
        return self.inv_pipeline_chunks > 1 or self.inv_staleness == 1

    def inverse_chunk_items(self, factors: dict
                            ) -> list[tuple[tuple, float]]:
        """Cost-weighted inverse work items for pipelined firing.

        One item per dense factor matrix that is inverted (``('mat',
        layer, 'A'|'G')``; a layer that follows another's A has no A
        item, :meth:`a_followers`)
        — the finest unit the bucketed eigh/inverse paths can regroup:
        within a chunk, same-dim fired matrices still stack into one
        vmapped kernel via ``_size_buckets``, so chunking never changes
        a matrix's decomposition, only when it runs), one per
        grouped-conv layer (its per-group block stacks), one per
        diagonal-A embedding layer. Matrix granularity — rather than
        whole same-dim buckets — is what lets the bin-packer hit the
        <= 1.5x-of-ideal balance bound on factor sets whose largest
        bucket alone exceeds ``total/k`` (the xl LM's 18x4096^2 bucket
        is 1.9x the k=4 ideal; test-pinned in
        tests/test_inv_pipeline.py). Costs use the ``linalg``
        decomposition proxy (``dim^3``), or — when
        ``inv_pipeline_costs`` is given — measured per-bucket
        ``bucket_parts`` ms split evenly over each bucket's matrices.
        Measured ms and the dim^3 proxy are DIFFERENT UNITS, so a
        measurement dict must cover **every dense factor dim** (a
        partial one raises: mixing a measured 531.8 ms next to a
        proxied 1024^3 would weight the genuinely heaviest bucket
        ~1e7x too cheap and silently un-balance the plan); the tiny
        grouped/diagonal proxy costs are rescaled into the measured
        unit by the fitted ms-per-dim^3 factor.
        """
        from distributed_kfac_pytorch_tpu.ops.linalg import (
            decomposition_cost,
        )
        dense_count: dict[int, int] = {}
        for name, spec in self.specs.items():
            if spec.kind in BLOCK_STACK_KINDS:
                continue
            f = factors[name]
            if spec.kind != EMBEDDING and spec.a_owner is None:
                a = int(f['A'].shape[-1])
                dense_count[a] = dense_count.get(a, 0) + 1
            g = int(f['G'].shape[-1])
            dense_count[g] = dense_count.get(g, 0) + 1
        measured = self.inv_pipeline_costs or {}
        # One global cost unit: proxy dim^3, or measured ms when a
        # complete measurement is supplied. proxy_scale converts the
        # non-dense proxy costs into the measured unit.
        proxy_scale = measured_unit_scale(measured, dense_count,
                                          'dense factor dim')

        def unit_cost(dim: int) -> float:
            if dim in measured:
                return float(measured[dim]) / dense_count[dim]
            # r19: low-rank buckets fire at r·dim^2, not dim^3 — the
            # plan must weigh them accordingly or every mixed window
            # un-balances by dim/r.
            return decomposition_cost(dim,
                                      rank=self.lowrank_rank_for(dim))

        items: list[tuple[tuple, float]] = []
        for name, spec in self.specs.items():
            f = factors[name]
            a_dim = int(f['A'].shape[-1])
            g_dim = int(f['G'].shape[-1])
            if spec.kind in BLOCK_STACK_KINDS:
                ng = int(f['A'].shape[0])
                items.append((('grouped', name),
                              proxy_scale * sum(
                                  ng * decomposition_cost(d)
                                  for d in ((g_dim,) if spec.a_owner
                                            else (a_dim, g_dim)))))
                continue
            if spec.kind == EMBEDDING:
                # Elementwise reciprocal: O(dim), negligible next to any
                # dense decomposition but still a schedulable item.
                items.append((('diag', name), proxy_scale * a_dim))
            elif spec.a_owner is None:
                # (A follower's A is its owner's item.)
                items.append((('mat', name, 'A'), unit_cost(a_dim)))
            items.append((('mat', name, 'G'), unit_cost(g_dim)))
        return items

    def inverse_chunk_plan(self, factors: dict) -> dict[tuple, int]:
        """Static item -> chunk assignment for ``inv_pipeline_chunks``.

        Greedy LPT bin-packing (``parallel.placement.load_balance``, the
        same balancer the KAISA work assignment uses) of the
        :meth:`inverse_chunk_items` onto ``k`` chunks. Deterministic
        (registration order + sorted dims), so every trace — and the
        single-chip vs SPMD paths — sees the identical plan. Raises if
        ``k`` exceeds the item count (more chunks than schedulable
        buckets cannot balance anything).
        """
        items = self.inverse_chunk_items(factors)
        k = self.inv_pipeline_chunks
        if k > len(items):
            raise ValueError(
                f'inv_pipeline_chunks={k} exceeds the {len(items)} '
                'inverse work items of this model (dense factor '
                'matrices + grouped/diagonal layers); lower it to at '
                f'most {len(items)}')
        return plan_inverse_chunks(items, k)

    # ------------------------------------------------------------------
    # Registration / state init
    # ------------------------------------------------------------------

    def init(self, rng, *args, init_model: nn.Module | None = None,
             **kwargs):
        """Init model variables and K-FAC state in one pass.

        Returns ``(variables, kfac_state)``; layer registration (the
        analogue of reference register_model, preconditioner.py:355-402)
        happens as a side effect of tracing the model. ``init_model``
        substitutes a structurally-identical single-device twin for the
        registration trace (see KFACCapture.init) — used by
        sequence-parallel models whose ring collectives only trace inside
        ``shard_map``.
        """
        variables, specs = self.capture.init(rng, *args,
                                             init_model=init_model,
                                             **kwargs)
        # Resolve the weight-sharing approximation per layer and bake
        # it into the registry (sharing.annotate_specs) — after this,
        # every factor-math consumer reads spec.kfac_approx. The
        # capture object keeps its own unannotated copy (it only needs
        # call/tied counts for pairing).
        specs = self._approx_mod.annotate_specs(specs, self.kfac_approx)
        # Which layers share an A was registered under one approximation
        # for all; a follower whose resolved one differs from its
        # owner's makes another statistic of the input and leaves.
        root = {n: s.a_owner or n for n, s in specs.items()}
        self._specs = specs = share_a_owners(
            specs, lambda owner, name: root[owner] == root[name])
        if (self.deferred_factor_reduction or self.hierarchical_reduce) \
                and any(s.kind == EXPERTS for s in specs.values()):
            # An expert's A is a ratio (row sums over row counts) and an
            # expert without rows skips its update: neither is linear in
            # the step's contribution, which the deferred EMA rests on.
            raise ValueError(
                'deferred_factor_reduction / hierarchical_reduce do not '
                'support stacked-expert layers '
                f'({[n for n, s in specs.items() if s.kind == EXPERTS]})')
        if self.verbose:
            for name, spec in specs.items():
                print(f'Registered {name}: {spec.kind} '
                      f'(bias={spec.has_bias}, calls={spec.num_calls}, '
                      f'approx={spec.kfac_approx}'
                      + (f', tied_calls={spec.tied_calls}'
                         if spec.tied_calls else '')
                      + (f', A of {spec.a_owner}'
                         if spec.a_owner else '') + ')')
            for name, reason in self.capture.skipped_modules.items():
                print(f'Skipped {name}: {reason}')
        state = self.init_state(variables['params'])
        return variables, state

    @property
    def specs(self):
        if self._specs is None:
            raise ValueError('call init() first')
        return self._specs

    def approx_summary(self, left_to_sgd: bool = False,
                       shared_a: bool = False) -> dict[str, str]:
        """{layer name: resolved approx} for run provenance.

        The per-layer map the observability meta records (the JSONL
        ``kind='meta'`` record the CLIs append after registration) —
        tied registrations are labeled ``<approx>+tied``. See
        ``sharing.approx_summary``. ``left_to_sgd=True`` adds every
        parameterized module K-FAC does not precondition (norm scales,
        ``skip_layers`` matches such as an untied head) as ``'sgd:
        <reason>'``: their gradients reach the optimizer as they are.
        ``shared_a=True`` labels a layer that reads the input of an
        earlier one ``<approx>+A of <owner>``: one A statistic and one
        A inverse serve both (:meth:`a_followers`).
        """
        out = self._approx_mod.approx_summary(self.specs)
        if shared_a:
            for name, owner in self.a_followers().items():
                out[name] += f'+A of {owner}'
        if left_to_sgd:
            out.update({name: f'sgd: {reason}' for name, reason
                        in self.capture.skipped_modules.items()})
        return out

    def a_followers(self) -> dict[str, str]:
        """``{layer: the layer that owns its A}`` for the layers that
        read the very input an earlier layer reads
        (``LayerSpec.a_owner``, found at registration): their A
        statistic is contracted once and inverted once, by the owner,
        and their inverse state holds no A side. Empty where no two
        layers read one input."""
        return {n: s.a_owner for n, s in self.specs.items()
                if s.a_owner is not None}

    def _a_side_baked(self, sides: dict) -> dict[str, bool]:
        """Per A owner: does its eigen-family A side also carry the
        dense inverse baked at firing time? It does where the owner or
        a layer that follows it is *mixed* (its G side is a baked
        inverse), as a layer on its own does for itself. ``sides``:
        ``{layer: (A method, G method)}``."""
        baked: dict[str, bool] = {}
        for name, spec in self.specs.items():
            ma, mg = sides[name]
            if spec.kind == EMBEDDING or ma is None:
                continue
            owner = spec.a_owner or name
            baked[owner] = baked.get(owner, False) or (
                eigen_family(ma) != eigen_family(mg))
        return baked

    def init_state(self, params) -> dict:
        """Fresh K-FAC state pytree for the registered layers.

        Factors start at identity — the reference seeds the running
        average with identity on the first update (base.py:389,416); with a
        functional state we materialize that seed up front (the first EWMA
        update then matches exactly). Eigen-path slots start at the exact
        eigendecomposition of those identity seeds (``Q = I, d = 1``) so
        the warm-start polish (eigh_method 'auto'/'warm') has a valid
        basis from step 0 — no cold-start eigh exists anywhere in the
        training path. Non-eigen inverse slots start as zeros; every slot
        is computed at step 0 before first use (0 % freq == 0).
        """
        factors, inverses = {}, {}
        dims = {name: L.factor_shapes(spec, _get(params, spec.path))
                for name, spec in self.specs.items()}
        sides = {name: self._side_methods(spec, *dims[name])
                 for name, spec in self.specs.items()}
        a_baked = self._a_side_baked(sides)
        for name, spec in self.specs.items():
            a_dim, g_dim = dims[name]
            fdt = self.factor_dtype or jnp.float32
            idt = self.inv_dtype
            ma, mg = sides[name]
            # A follower's A is inverted by its owner and read there.
            follows = spec.a_owner is not None
            for which, m, dim in (('A', ma, a_dim), ('G', mg, g_dim)):
                if m == 'lowrank' and self.inv_lowrank_rank >= dim:
                    # Fail closed: a rank at or above the engaged dim
                    # cannot truncate anything — never silently fall
                    # back to the exact path (CI pins this error).
                    raise ValueError(
                        f'inv_lowrank_rank={self.inv_lowrank_rank} '
                        f'must be < the engaged factor dim {dim} '
                        f'(layer {name!r} side {which}; dims >= '
                        f'inv_lowrank_dim_threshold='
                        f'{self.inv_lowrank_dim_threshold} run the '
                        'randomized low-rank path) — lower the rank '
                        'or raise the threshold')
            # Mixed layers carry a firing-time-baked dense inverse for
            # their eigen-family side too (zero-seeded; step 0 fires
            # before first use) — see update_inverses.
            mixed = (spec.kind != EMBEDDING
                     and eigen_family(ma) != eigen_family(mg))

            def eigen_seed(dim: int, method: str):
                """Identity eigenpair seed. Low-rank sides carry a
                rectangular (dim, r) identity-column basis — orthonormal
                columns, a valid warm start for the subspace-refresh +
                polish from step 0 — and r unit eigenvalues."""
                r = (self.inv_lowrank_rank if method == 'lowrank'
                     else dim)
                return (jnp.eye(dim, r, dtype=idt),
                        jnp.ones((r,), idt))

            entry: dict[str, Any] = {}
            if spec.kind in BLOCK_STACK_KINDS:
                ng = spec.num_blocks
                factors[name] = {
                    'A': jnp.broadcast_to(jnp.eye(a_dim, dtype=fdt),
                                          (ng, a_dim, a_dim)),
                    'G': jnp.broadcast_to(jnp.eye(g_dim, dtype=fdt),
                                          (ng, g_dim, g_dim))}
                inverses[name] = {
                    'G_inv': jnp.zeros((ng, g_dim, g_dim), idt)}
                if not follows:
                    inverses[name]['A_inv'] = jnp.zeros(
                        (ng, a_dim, a_dim), idt)
                continue
            if spec.kind == EMBEDDING:
                factors[name] = {'A': jnp.ones((a_dim,), fdt),
                                 'G': jnp.eye(g_dim, dtype=fdt)}
                entry['A_inv'] = jnp.zeros((a_dim,), idt)
            else:
                factors[name] = {'A': jnp.eye(a_dim, dtype=fdt),
                                 'G': jnp.eye(g_dim, dtype=fdt)}
                if not follows and eigen_family(ma):
                    entry['QA'], entry['dA'] = eigen_seed(a_dim, ma)
                if not follows and (a_baked[name]
                                    or not eigen_family(ma)):
                    entry['A_inv'] = jnp.zeros((a_dim, a_dim), idt)
            if eigen_family(mg):
                entry['QG'], entry['dG'] = eigen_seed(g_dim, mg)
                if mixed:
                    entry['G_inv'] = jnp.zeros((g_dim, g_dim), idt)
            else:
                entry['G_inv'] = jnp.zeros((g_dim, g_dim), idt)
            inverses[name] = entry
        state = {'step': jnp.zeros((), jnp.int32),
                 'factors': factors, 'inverses': inverses,
                 # Pipelined-firing position: the next chunk index due
                 # (always 0 at init and after a monolithic firing;
                 # constant 0 under inv_pipeline_chunks=1). Checkpointed
                 # so resumed runs report where the pipeline stood;
                 # restore of pre-r9 bundles defaults it to 0
                 # (MIGRATION.md).
                 'inv_chunk_phase': jnp.zeros((), jnp.int32)}
        if self.deferred_factor_reduction:
            # Local pre-reduction accumulator (the decayed sum of
            # factor contributions since the last window-boundary
            # reduce) + the matching running decay product. Zero/one
            # seeds = "nothing accumulated" (the boundary update is
            # then the identity).
            state['factor_accum'] = jax.tree.map(jnp.zeros_like,
                                                 factors)
            state['accum_decay'] = jnp.ones((), jnp.float32)
        if self.inv_staleness:
            # The window-head factor snapshot the in-window firings
            # decompose (refreshed on factor_snapshot/inv_update
            # steps). Seeded with the identity-seeded factors — step 0
            # fires monolithically from a fresh snapshot before any
            # slot is consumed.
            state['frozen_factors'] = jax.tree.map(lambda x: x,
                                                   factors)
        if self.pipelined_firing:
            # Eager validation: the chunk count must not exceed the
            # model's inverse work buckets (raises with the bucket
            # count); the plan itself is recomputed statically at trace
            # time from the same shapes.
            self.inverse_chunk_plan(factors)
        if self.collect_metrics:
            state['metrics'] = obs_metrics.init_metrics(
                self.metric_bucket_keys(params))
        return state

    def metric_bucket_keys(self, params) -> list[str]:
        """Precondition shape-bucket keys for the metrics pytree.

        Derived by ``eval_shape`` over the same ``grads_to_matrix``
        transform the precondition pass runs, so the keys in the state
        structure match the runtime grouping exactly (one source of
        shape truth; trace-static).
        """
        keys: list[str] = []
        for name, spec in self.specs.items():
            sh = jax.eval_shape(
                lambda p, s=spec: L.grads_to_matrix(s, p),
                _get(params, spec.path)).shape
            key = obs_metrics.shape_key(sh)
            if key not in keys:
                keys.append(key)
        return keys

    def _tracked_factor_update(self, state: dict, captures: dict,
                               factor_decay) -> tuple[dict, jax.Array]:
        """Factor update + finiteness flag (metrics/guard path); the
        guard semantics live in :func:`guard_nonfinite_factors` (shared
        with the SPMD step)."""
        return guard_nonfinite_factors(
            self.update_factors(state, captures, factor_decay),
            state['factors'], self.nonfinite_guard)

    # NOTE: worker assignment (the reference's one-time deferred
    # _assign_workers, preconditioner.py:616-659) lives in
    # ``parallel.distributed.assign_work`` — the single LPT cost model
    # and placement path for the whole framework (round-1 review found a
    # parallel unused implementation here; it was removed).

    # ------------------------------------------------------------------
    # The pipeline stages (pure; called under jit)
    # ------------------------------------------------------------------

    def factor_contribs(self, captures: dict) -> dict:
        """Combined per-layer covariance contribution of one batch.

        The pre-EWMA half of :meth:`update_factors`: ``{name: {'A',
        'G'}}`` with the tied-embedding attend extras already folded in
        (single-chip captures are global, so no world rescale — cf.
        the SPMD path's g_scale). Shared by the eager EWMA path and the
        deferred-reduction accumulator so the contribution math cannot
        drift between them. A layer that follows another's A
        (:meth:`a_followers`) is handed its owner's A statistic: one
        contraction of the input both read.
        """
        cdt = self.factor_compute_dtype
        captures = subsample_captures(captures, self.factor_batch_fraction)
        out = {}
        for name, spec in self.specs.items():
            owner = out.get(spec.a_owner)
            if owner is not None:
                tracing.count('kfac/factors/shared_a')
            if spec.kind == EXPERTS:
                out[name] = L.experts_contrib(spec, captures[name], cdt,
                                              a_of=owner)
                continue
            a_new = (owner['A'] if owner is not None
                     else L.compute_a_factor(spec, captures[name]['a'],
                                             compute_dtype=cdt))
            g_new = L.compute_g_factor(spec, captures[name]['g'],
                                       compute_dtype=cdt)
            extras = L.compute_tied_factor_extras(spec, captures[name],
                                                  compute_dtype=cdt)
            if extras is not None:
                # Tied embedding: the attend call site folds into the
                # SAME factor pair.
                a_new = a_new + extras['A_g2']
                g_new = g_new + extras['G_a']
            out[name] = {'A': a_new, 'G': g_new}
        return out

    @profiling.scope('kfac/factors')
    def update_factors(self, state: dict, captures: dict,
                       factor_decay=None) -> dict:
        """EWMA-update all factor running averages from captures.

        Reference: compute_factors + allreduce (preconditioner.py:566-575,
        525-533); under GSPMD the allreduce is implicit in the covariance
        contraction over the batch-sharded captures.
        """
        alpha = self.factor_decay if factor_decay is None else factor_decay
        contribs = self.factor_contribs(captures)
        new_factors = {}
        for name in self.specs:
            old = state['factors'][name]
            if 'rows' in contribs[name]:
                new_factors[name] = F.experts_running_avg(
                    old, contribs[name]['A'], contribs[name]['G'],
                    contribs[name]['rows'], alpha)
                continue
            a_new = contribs[name]['A'].astype(old['A'].dtype)
            g_new = contribs[name]['G'].astype(old['G'].dtype)
            new_factors[name] = {
                'A': F.update_running_avg(a_new, old['A'], alpha),
                'G': F.update_running_avg(g_new, old['G'], alpha)}
        return new_factors

    @profiling.scope('kfac/factors')
    def accumulate_factors(self, state: dict, captures: dict,
                           factor_decay=None) -> tuple[dict, jax.Array]:
        """Deferred-reduction factor step: fold one batch's contribution
        into the local accumulator, leave the running averages alone.

        ``acc ← α·acc + (1-α)·c`` and ``decay ← α·decay``; at the
        window boundary :meth:`reduce_factors` applies
        ``F ← decay·F + acc`` — by EMA linearity exactly the per-step
        recursion's value at the boundary (up to fp associativity).
        Returns ``(new_accum, new_decay)``.
        """
        alpha = self.factor_decay if factor_decay is None else factor_decay
        contribs = self.factor_contribs(captures)
        acc = state['factor_accum']
        new_acc = {}
        for name in self.specs:
            old = acc[name]
            new_acc[name] = {
                which: F.update_running_avg(
                    contribs[name][which].astype(old[which].dtype),
                    old[which], alpha)
                for which in ('A', 'G')}
        return new_acc, alpha * state['accum_decay']

    @profiling.scope('kfac/factors')
    def reduce_factors(self, state: dict, acc: dict, decay) -> dict:
        """Deferred-reduction window boundary: apply the accumulated
        contributions to the running averages (single-chip form — no
        collective; the SPMD analogue pmeans ``acc`` first)."""
        new_factors = {}
        for name in self.specs:
            old = state['factors'][name]
            new_factors[name] = {
                which: (decay * old[which]
                        + acc[name][which]).astype(old[which].dtype)
                for which in ('A', 'G')}
        return new_factors

    def _bucketed_eigh(self, mats: dict[str, jax.Array],
                       prev: dict[str, jax.Array] | None = None
                       ) -> dict[str, tuple[jax.Array, jax.Array]]:
        """Eigendecompose a dict of SPD matrices, batching equal sizes.

        Equal-size factors are stacked and decomposed with one vmapped
        fp32 ``eigh`` — the TPU-native answer to the reference's per-layer
        sequential cuSOLVER calls (base.py:432-441), and the unit that
        ``parallel.distributed`` shards across the mesh. ``prev`` maps the
        same keys to the previous firing's eigenbases; when present (and
        ``eigh_method`` is 'auto'/'warm') the decomposition is the
        warm-start matmul-only polish instead of a cold eigh.
        """
        out: dict[str, tuple[jax.Array, jax.Array]] = {}
        method = resolve_eigh_method(self.eigh_method)
        for names, stack in _size_buckets(mats):
            q_prev = None
            if prev is not None and method == 'auto':
                q_prev = jnp.stack([prev[n].astype(jnp.float32)
                                    for n in names])
            qs, ds = linalg.batched_eigh(
                stack, method, clip=0.0, q_prev=q_prev,
                polish_iters=self.eigh_polish_iters)
            for i, n in enumerate(names):
                out[n] = (qs[i], ds[i])
        return out

    def _bucketed_lowrank(self, mats: dict[str, jax.Array],
                          prev: dict[str, jax.Array] | None = None
                          ) -> dict[str, tuple[jax.Array, jax.Array]]:
        """Truncated-eigendecompose a dict of SPD matrices, batching
        equal sizes (the r19 low-rank analogue of :meth:`_bucketed_eigh`).

        ``prev`` maps the same keys to the carried (dim, r) truncated
        bases; when present the decomposition is the subspace-refresh +
        warm polish, else the deterministic Gaussian range-finder
        sketch (cold rebuilds). Unlike the exact path, warm starting is
        not gated on ``eigh_method`` — the carried basis IS the
        low-rank state, re-randomizing it every firing would throw the
        converged subspace away.
        """
        out: dict[str, tuple[jax.Array, jax.Array]] = {}
        for names, stack in _size_buckets(mats):
            q_prev = (jnp.stack([prev[n].astype(jnp.float32)
                                 for n in names])
                      if prev is not None else None)
            qs, ds = linalg.batched_lowrank_eigh(
                stack, self.inv_lowrank_rank, q_prev=q_prev,
                polish_iters=self.eigh_polish_iters)
            for i, n in enumerate(names):
                out[n] = (qs[i], ds[i])
        return out

    def _bucketed_inverse(self, mats: dict[str, jax.Array], damping
                          ) -> dict[str, jax.Array]:
        """Damped-inverse a dict of SPD matrices, batching equal sizes.

        Non-eigen analogue of :meth:`_bucketed_eigh` (reference damped
        Cholesky inverse, kfac/layers/base.py:432-441): 'newton' runs the
        matmul-only Newton–Schulz stack (Pallas VMEM-resident on TPU),
        'cholesky' a vmapped XLA Cholesky inverse. Per-bucket method
        comes from :meth:`method_for_dim` (callers only route factors
        here whose dim resolves to a non-eigen method).
        """
        out: dict[str, jax.Array] = {}
        for names, stack in _size_buckets(mats):
            invs = linalg.damped_inverse_stack(
                stack, damping, self.method_for_dim(stack.shape[-1]),
                iters=self.newton_iters, out_dtype=self.inv_dtype)
            for i, n in enumerate(names):
                out[n] = invs[i]
        return out

    @profiling.scope('kfac/inverses')
    def update_inverses(self, state: dict, damping, *,
                        warm: bool = True,
                        chunk: int | None = None) -> dict:
        """Recompute inverses/eigendecompositions from current factors.

        Reference: compute_inverses (preconditioner.py:555-564,
        base.py:198-308). Embedding A is diagonal: elementwise inverse
        (embedding.py fixed version). ``warm`` (default) seeds the eigen
        path from the previous bases in ``state['inverses']`` (the
        eigh_method='auto' fast path); pass ``warm=False`` where the
        stored bases are untrustworthy (e.g. rebuilding from a
        factor-only checkpoint, where inverse slots are fresh identity).

        ``chunk``: pipelined firing — recompute only the work items the
        :meth:`inverse_chunk_plan` assigns to this chunk index, passing
        every other slot through from ``state['inverses']`` unchanged.
        ``None`` (monolithic, the default) fires everything. Per-bucket
        decompositions are identical either way (chunking selects whole
        same-dim buckets, never splits one), which is what makes a
        frozen-factor pipelined window bit-identical to one monolithic
        firing (test-pinned).
        """
        plan = (self.inverse_chunk_plan(state['factors'])
                if self.pipelined_firing else None)
        if chunk is not None and plan is None:
            raise ValueError('inv_chunk requires inv_pipeline_chunks > 1 '
                             'or inv_staleness=1')

        def fires(key: tuple) -> bool:
            return chunk is None or plan[key] == chunk

        # Split the dense factors by per-dim method ('auto' mixes the
        # groups; global modes put everything in one). Prev-basis warm
        # starts apply to the eigen-family groups (exact + lowrank).
        eigen_mats: dict[str, jax.Array] = {}
        lowrank_mats: dict[str, jax.Array] = {}
        inv_mats: dict[str, jax.Array] = {}
        prev: dict[str, jax.Array] = {}
        sides: dict[str, tuple[str | None, str]] = {}
        for name, spec in self.specs.items():
            f = state['factors'][name]
            ma, mg = self._side_methods(spec, f['A'].shape[-1],
                                        f['G'].shape[-1])
            sides[name] = (ma, mg)
            if spec.kind in BLOCK_STACK_KINDS:
                continue
            for which, m in (('A', ma), ('G', mg)):
                if m is None or (which == 'A' and spec.a_owner):
                    # (A follower's A is inverted once, as its owner's.)
                    continue
                if not fires(('mat', name, which)):
                    continue
                key = f'{name}/{which}'
                if m == 'eigen':
                    eigen_mats[key] = f[which]
                    if warm:
                        prev[key] = state['inverses'][name][f'Q{which}']
                elif m == 'lowrank':
                    lowrank_mats[key] = f[which]
                    if warm:
                        prev[key] = state['inverses'][name][f'Q{which}']
                else:
                    inv_mats[key] = f[which]

        if plan is None:
            eigs = self._bucketed_eigh(eigen_mats, prev if warm else None)
            eigs.update(self._bucketed_lowrank(
                lowrank_mats, prev if warm else None))
            invs = self._bucketed_inverse(inv_mats, damping)
        else:
            # Pipelined mode (k > 1): decompose in the SAME per-chunk
            # sub-stacks whether this is a monolithic firing (all
            # groups) or one chunk's firing (its group alone). The
            # frozen-window bit-identity contract is then structural —
            # it does not rest on the backend's batched kernels being
            # slice-stable across batch sizes, which they are NOT
            # (observed on CPU: a 1-matrix vs 6-matrix vmapped polish
            # rotates Q by O(1) within near-degenerate eigenvalue
            # clusters; same amplification class as PERF.md's
            # static-vs-dynamic fusion note).
            def by_chunk(mats: dict) -> dict[int, dict]:
                out: dict[int, dict] = {}
                for key, m in mats.items():
                    name, which = key.rsplit('/', 1)
                    out.setdefault(plan[('mat', name, which)],
                                   {})[key] = m
                return out

            eigs, invs = {}, {}
            for _j, mats in sorted(by_chunk(eigen_mats).items()):
                eigs.update(self._bucketed_eigh(
                    mats, prev if warm else None))
            for _j, mats in sorted(by_chunk(lowrank_mats).items()):
                eigs.update(self._bucketed_lowrank(
                    mats, prev if warm else None))
            for _j, mats in sorted(by_chunk(inv_mats).items()):
                invs.update(self._bucketed_inverse(mats, damping))

        a_baked = self._a_side_baked(sides)
        new_inv = {}
        for name, spec in self.specs.items():
            old = state['inverses'][name]
            follows = spec.a_owner is not None
            if spec.kind in BLOCK_STACK_KINDS:
                new_inv[name] = (grouped_block_inverses(
                    state['factors'][name], damping, self.inv_dtype,
                    sides='G' if follows else 'AG')
                    if fires(('grouped', name)) else old)
                continue
            ma, mg = sides[name]
            # A dense layer with exactly one eigen-family side is
            # *mixed*: that side is additionally baked into a dense
            # damped inverse at THIS firing's damping (linalg.
            # eigen_side_inverse — truncated-aware, the low-rank bake
            # carries the I/λ tail complement), so both sides of the
            # split operator carry the same firing-time λ — the
            # reference non-eigen timing semantics — and precondition
            # does no per-step eigen-side reconstruction. Q/d stay
            # stored for the next firing's warm start. (Under chunked
            # firing the two sides may bake at different phase steps'
            # λ — the same situation a damping schedule already
            # creates across firings.)
            mixed = (spec.kind != EMBEDDING
                     and eigen_family(ma) != eigen_family(mg))
            # Chunked firing: start from the stored entry and overwrite
            # only the sides whose bucket fires this chunk.
            entry: dict[str, Any] = dict(old) if chunk is not None else {}
            if spec.kind == EMBEDDING:
                if fires(('diag', name)):
                    entry['A_inv'] = linalg.get_elementwise_inverse(
                        state['factors'][name]['A'].astype(jnp.float32),
                        damping=damping).astype(self.inv_dtype)
            elif follows:
                pass  # the A side lives in the owner's entry
            elif eigen_family(ma):
                if fires(('mat', name, 'A')):
                    qa, da = eigs[f'{name}/A']
                    entry['QA'] = qa.astype(self.inv_dtype)
                    entry['dA'] = da.astype(self.inv_dtype)
                    if a_baked[name]:
                        # (also where only a follower is mixed)
                        entry['A_inv'] = linalg.eigen_side_inverse(
                            qa, da, damping).astype(self.inv_dtype)
            elif fires(('mat', name, 'A')):
                entry['A_inv'] = invs[f'{name}/A'].astype(self.inv_dtype)
            if eigen_family(mg):
                if fires(('mat', name, 'G')):
                    qg, dg = eigs[f'{name}/G']
                    entry['QG'] = qg.astype(self.inv_dtype)
                    entry['dG'] = dg.astype(self.inv_dtype)
                    if mixed:
                        entry['G_inv'] = linalg.eigen_side_inverse(
                            qg, dg, damping).astype(self.inv_dtype)
            elif fires(('mat', name, 'G')):
                entry['G_inv'] = invs[f'{name}/G'].astype(self.inv_dtype)
            new_inv[name] = entry
        return new_inv

    @profiling.scope('kfac/precond')
    def precondition(self, state: dict, grads: dict, damping, lr,
                     layer_filter: Sequence[str] | None = None,
                     with_stats: bool = False, gates: dict | None = None):
        """Precondition registered layers' grads; KL-clip scale on-device.

        Reference: compute_preconditioned_gradients + _compute_grad_scale +
        update_gradients (preconditioner.py:577-590,661-682). Unregistered
        params pass through unchanged. ``layer_filter`` restricts which
        layers this device computes (MEM/HYBRID placement).

        Dense layers are bucketed by gradient-matrix shape and
        preconditioned as ONE vmapped batched matmul per bucket — the
        single-chip analogue of the row-sharded KAISA batching
        (``parallel.distributed._rowsharded_precond_mats``). On a
        transformer, the q/k/v/o and MLP Denses of every block share
        shapes, so ~100 per-layer (dim, dim) matmul dispatches collapse
        into a handful of batched MXU kernels. Within a bucket the
        per-slice contraction is the same matmul the per-layer path ran
        (vmap adds a batch dim; it does not reassociate a slice's
        contraction); tests/test_mixed_precision.py holds every branch
        to a dense per-layer oracle.

        ``with_stats=True`` additionally returns
        ``(out, observability.metrics.precond_stats(...))`` — ν, grad /
        preconditioned-grad norms and per-shape-bucket norms, all traced
        scalars (the metrics path; default False is the historical
        single-value return).

        ``gates`` (r16 self-healing quarantine): an optional
        ``{shape-bucket key -> traced 0/1 scalar}`` dict (keys from
        ``observability.metrics.shape_key``, the same grouping the
        bucketed paths batch over). A gated-off (0) bucket's layers
        fall back to the RAW gradient direction — plain SGD — via
        ``jnp.where`` (a ``select``: NaN/Inf in the unselected
        preconditioned branch does not propagate), applied BEFORE the
        KL-clip so the clip scale and all downstream stats see the
        blended directions. Gate VALUES are traced scalars riding in
        ``hyper`` (engine), so flipping one is a value change — zero
        retraces. ``None`` (default) is the bit-identical historical
        path.
        """
        names = list(self.specs) if layer_filter is None else list(
            layer_filter)
        cdt = self.precond_compute_dtype
        grad_mats = {
            name: L.grads_to_matrix(self.specs[name],
                                    _get(grads, self.specs[name].path))
            for name in names}
        precond_mats = self._bucketed_precond_mats(
            state['inverses'], grad_mats, damping, names)
        for name in names:
            if name in precond_mats:
                continue  # dense layer: computed by a shape bucket
            spec = self.specs[name]
            inv = state['inverses'][name]
            if spec.a_owner is not None:
                inv = {**inv, 'A_inv':
                       state['inverses'][spec.a_owner]['A_inv']}
            # Per-layer path for the non-dense kinds: embedding A is the
            # diagonal elementwise inverse; grouped convs broadcast the
            # batched G_inv @ grad @ A_inv over their block stacks (a
            # stack that follows another's A reads the owner's).
            # Same dispatch as the SPMD preconditioner:
            # linalg.precondition_dispatch.
            precond_mats[name] = linalg.precondition_dispatch(
                grad_mats[name], inv, damping,
                diag_a=(inv['A_inv'] if spec.kind == EMBEDDING else None),
                compute_dtype=cdt)

        if gates is not None:
            # Quarantine blend (r16): a gated-off bucket serves the raw
            # gradient. jnp.where is a select — the poisoned
            # preconditioned branch's NaNs stay un-propagated.
            for name in names:
                g = gates.get(obs_metrics.shape_key(
                    grad_mats[name].shape))
                if g is None:
                    continue
                pm = precond_mats[name]
                precond_mats[name] = jnp.where(
                    jnp.asarray(g, jnp.float32) >= 0.5, pm,
                    grad_mats[name].astype(pm.dtype))

        if self.kl_clip is not None:
            # Fused with the precondition pass: the grad matrices are
            # already live (no second grads_to_matrix walk), and XLA
            # fuses each product-reduce with its bucket's batched
            # matmul output. Accumulation stays per-layer in
            # registration order — the historical summation order, so
            # the clip scale is bit-stable against bucketing.
            vg_sum = jnp.zeros((), jnp.float32)
            for name in names:
                vg_sum += jnp.sum(precond_mats[name] *
                                  grad_mats[name].astype(jnp.float32)
                                  * lr ** 2)
            nu = jnp.minimum(
                1.0, jnp.sqrt(self.kl_clip / (jnp.abs(vg_sum) + 1e-30)))
        else:
            nu = jnp.ones((), jnp.float32)

        stats = (obs_metrics.precond_stats(grad_mats, precond_mats, nu)
                 if with_stats else None)
        out = jax.tree.map(lambda x: x, grads)  # copy structure
        for name in names:
            spec = self.specs[name]
            sub = _get(grads, spec.path)
            new_sub = L.matrix_to_grads(
                spec, (nu * precond_mats[name]).astype(jnp.float32), sub)
            out = _set(out, spec.path, jax.tree.map(
                lambda n, o: n.astype(o.dtype), new_sub, sub))
        return (out, stats) if with_stats else out

    def _bucketed_precond_mats(self, inverses: dict, grad_mats: dict,
                               damping, names: Sequence[str]) -> dict:
        """Batched precondition matmuls for the dense layers in ``names``.

        Maps each bucketed layer to its preconditioned matrix. Layers
        are grouped by gradient-matrix shape; each group stacks its
        grads and inverse operands and runs ONE batched matmul chain —
        per-group entry keys are uniform
        because the per-dim method is a function of the factor dims
        alone (``method_for_dim``), so a shape group is wholly
        eigen-typed (QA/dA/QG/dG) or wholly baked (A_inv/G_inv; mixed
        layers carry baked inverses for both sides). Embedding
        (diagonal A) and grouped-conv (block-stack) layers are not
        dense (g, a) matmuls and stay on the caller's per-layer path.
        A layer that follows another's A reads the A-side operands in
        its owner's entry (its own holds none).
        """
        cdt = self.precond_compute_dtype
        groups: dict[tuple[int, ...], list[str]] = {}
        for name in names:
            if self.specs[name].kind in (EMBEDDING, *BLOCK_STACK_KINDS):
                continue
            groups.setdefault(tuple(grad_mats[name].shape),
                              []).append(name)
        mats: dict = {}
        for (g_dim, a_dim), members in groups.items():
            gstack = jnp.stack([grad_mats[n] for n in members])
            both_eigen = (eigen_family(self.method_for_dim(a_dim))
                          and eigen_family(self.method_for_dim(g_dim)))
            keys = (('QA', 'dA', 'QG', 'dG') if both_eigen
                    else ('A_inv', 'G_inv'))
            entry = {k: jnp.stack([
                inverses[(self.specs[n].a_owner or n) if k in A_SIDE_KEYS
                         else n][k] for n in members]) for k in keys}
            vs = jax.vmap(
                lambda gm, e: linalg.precondition_dispatch(
                    gm, e, damping, compute_dtype=cdt))(gstack, entry)
            for i, n in enumerate(members):
                mats[n] = vs[i]
        return mats

    # ------------------------------------------------------------------
    # The full step
    # ------------------------------------------------------------------

    def step(self, state: dict, grads: dict, captures: dict, *,
             damping=None, lr=None, factor_decay=None,
             factor_update_freq=None, inv_update_freq=None,
             factor_update: bool | None = None,
             inv_update: bool | None = None,
             inv_chunk: int | None = None,
             factor_reduce: bool = False,
             factor_snapshot: bool = False,
             gates: dict | None = None) -> tuple[dict, dict]:
        """One K-FAC update: returns (preconditioned_grads, new_state).

        The analogue of reference KFAC.step() (preconditioner.py:472-523).
        Cadence gating comes in two forms:

          - **Static** (recommended on TPU): pass Python bools
            ``factor_update`` / ``inv_update`` — the caller owns the
            schedule (``step % freq == 0`` on a host counter) and the
            gated work is simply present or absent from the traced
            program. Two program variants get compiled; the expensive
            decomposition program exists only where it runs.
          - **Dynamic** (``None``, the default): ``lax.cond`` on the
            on-device step counter, fully schedulable without
            recompilation. CAUTION: on TPU, a conditional whose branch
            holds the O(n^3) decompositions degrades the surrounding
            program — measured 10-18x step slowdowns on v5e from
            XLA layout/copy pathologies around the cond — so training
            loops should prefer the static form (the engine and
            ``DistributedKFAC.build_train_step`` do).

        ``inv_chunk``: pipelined inverse firing (static cadence only —
        a Python int, mutually exclusive with ``inv_update=True``):
        recompute only chunk ``j``'s share of the inverse work this
        step (see ``inv_pipeline_chunks`` / :meth:`update_inverses`).
        The engine fires chunk ``j`` on phase step
        ``j * inv_update_freq / k`` of each cadence window; each chunk
        value is its own statically-compiled program variant. The
        dynamic (``None``-flag) path always fires monolithically —
        chunking is a static-program-structure feature by design
        (PERF.md pitfall 2).

        ``factor_reduce`` (requires ``deferred_factor_reduction``,
        static): apply the locally-accumulated factor contributions to
        the running averages this step — the single collective per
        window on the SPMD path. ``factor_snapshot`` (requires
        ``inv_staleness=1``, static): refresh ``frozen_factors`` from
        this step's post-update factors (window-head steps); in-window
        chunk firings always decompose the carried snapshot, and a
        monolithic ``inv_update=True`` firing snapshots-then-fires
        (eager semantics — the step-0 warmup). Both features are
        static-cadence only: dynamic (``None``) flags raise.

        ``gates``: per-shape-bucket quarantine mask (r16 self-healing)
        — see :meth:`precondition`. Traced scalar VALUES; ``None``
        (default) keeps the historical program bit-identical.
        """
        damping = self.damping if damping is None else damping
        lr = self.lr if lr is None else lr
        f_freq = (self.factor_update_freq if factor_update_freq is None
                  else factor_update_freq)
        i_freq = (self.inv_update_freq if inv_update_freq is None
                  else inv_update_freq)
        step = state['step']

        track = self.collect_metrics or self.nonfinite_guard
        if self.hierarchical_reduce:
            raise ValueError(
                'hierarchical_reduce is SPMD-only (it reduces over '
                "mesh slice axes) — use DistributedKFAC on a "
                'multislice.make_multislice_mesh mesh with '
                'num_slices > 1')
        if self.deferred_factor_reduction:
            # Deferred reduce: the EWMA (and, under SPMD, the factor
            # collective) advances only on factor_reduce steps; factor
            # steps fold into the local accumulator. Static cadence
            # only — the boundary update is program structure.
            if factor_update is None:
                raise ValueError(
                    'deferred_factor_reduction requires static cadence '
                    'flags (Python-bool factor_update/factor_reduce) — '
                    'the window-boundary reduce is static program '
                    'structure, like inv_chunk')
            acc, decay = state['factor_accum'], state['accum_decay']
            if factor_update:
                acc, decay = self.accumulate_factors(state, captures,
                                                     factor_decay)
            if factor_reduce:
                candidate = self.reduce_factors(state, acc, decay)
                # Guard/metrics check the post-accumulation candidate
                # at the reduce point (the collective-safe analogue of
                # the eager per-step check); a non-finite window is
                # skipped WHOLE and the accumulator resets either way.
                factors, finite_f = guard_nonfinite_factors(
                    candidate, state['factors'], self.nonfinite_guard)
                acc = jax.tree.map(jnp.zeros_like, acc)
                decay = jnp.ones((), jnp.float32)
            else:
                factors = state['factors']
                finite_f = jnp.ones((), jnp.int32)
            state_f = {**state, 'factors': factors,
                       'factor_accum': acc, 'accum_decay': decay}
        else:
            if factor_reduce:
                raise ValueError(
                    'factor_reduce requires '
                    'deferred_factor_reduction=True')
            if track:
                # Tracked form: the factor branch additionally yields
                # the candidate factors' finiteness flag
                # (guard + metrics).
                factors, finite_f = cadence_gate(
                    factor_update, step, f_freq,
                    lambda: self._tracked_factor_update(state, captures,
                                                        factor_decay),
                    lambda: (state['factors'], jnp.ones((), jnp.int32)))
            else:
                # Metrics/guard off: the historical program, untouched
                # (bit-identity pinned by tests/test_observability.py).
                factors = cadence_gate(
                    factor_update, step, f_freq,
                    lambda: self.update_factors(state, captures,
                                                factor_decay),
                    lambda: state['factors'])
            state_f = {**state, 'factors': factors}

        if self.inv_staleness:
            if inv_update is None:
                raise ValueError(
                    'inv_staleness=1 requires static cadence flags '
                    '(the frozen-snapshot firing schedule is static '
                    'program structure, like inv_chunk)')
            # Window-head steps (and a monolithic firing — the step-0
            # warmup, which must decompose the step's fresh factors,
            # not the identity seeds) refresh the snapshot; everything
            # else decomposes the carried one.
            frozen = (state_f['factors']
                      if factor_snapshot or inv_update
                      else state['frozen_factors'])
            state_f = {**state_f, 'frozen_factors': frozen}
            fire_state = {**state_f, 'factors': frozen}
        else:
            if factor_snapshot:
                raise ValueError(
                    'factor_snapshot requires inv_staleness=1')
            fire_state = state_f

        if inv_chunk is not None:
            k = self.inv_pipeline_chunks
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
                inverses = self.update_inverses(fire_state, damping,
                                                chunk=inv_chunk)
            chunk_phase = jnp.asarray((inv_chunk + 1) % k, jnp.int32)
        else:
            inverses = cadence_gate(
                inv_update, step, i_freq,
                lambda: self.update_inverses(fire_state, damping),
                lambda: state['inverses'])
            # Static monolithic firing resets the pipeline position;
            # otherwise (no firing, or the dynamic cond path — which
            # only ever fires monolithically from phase 0) the stored
            # phase passes through untouched.
            chunk_phase = (jnp.zeros((), jnp.int32) if inv_update
                           else state['inv_chunk_phase'])
        state_i = {**state_f, 'inverses': inverses,
                   'inv_chunk_phase': chunk_phase}

        if not self.collect_metrics:
            precond = self.precondition(state_i, grads, damping, lr,
                                        gates=gates)
            new_state = {**state_i, 'step': step + 1}
            return precond, new_state

        precond, stats = self.precondition(state_i, grads, damping, lr,
                                           with_stats=True, gates=gates)
        one = lambda: jnp.ones((), jnp.int32)
        zero = lambda: jnp.zeros((), jnp.int32)
        did_f = cadence_gate(factor_update, step, f_freq, one, zero)
        did_i = (zero() if inv_chunk is not None
                 else cadence_gate(inv_update, step, i_freq, one, zero))
        did_c = one() if inv_chunk is not None else zero()
        new_state = {**state_i, 'step': step + 1,
                     'metrics': obs_metrics.update_metrics(
                         state['metrics'], damping=damping, stats=stats,
                         did_factor=did_f, did_inv=did_i,
                         did_chunk=did_c,
                         factor_finite=finite_f,
                         eig_clipped=obs_metrics.count_clipped_eigvals(
                             inverses))}
        return precond, new_state

    # ------------------------------------------------------------------
    # Introspection / checkpoint helpers
    # ------------------------------------------------------------------

    def memory_usage(self, state: dict) -> dict[str, int]:
        """Bytes held by each K-FAC state component.

        Reference: KFAC.memory_usage (preconditioner.py:592-614); capture
        buffers don't persist here (they are step-local values).
        """
        return {'factors': _tree_size_bytes(state['factors']),
                'inverses': _tree_size_bytes(state['inverses'])}

    def state_dict(self, state: dict, include_inverses: bool = False):
        """Checkpointable pytree: factors + step, inverses optional.

        Inverses are recomputed on load rather than stored, matching the
        reference's checkpoint policy (preconditioner.py:294-353,
        README.md:222-223).
        """
        out = {'step': state['step'], 'factors': state['factors'],
               'inv_chunk_phase': state.get(
                   'inv_chunk_phase', jnp.zeros((), jnp.int32))}
        # r14 overlap state: present only when the knobs are on, so
        # default checkpoints keep the historical layout (MIGRATION.md).
        for key in ('factor_accum', 'accum_decay', 'frozen_factors'):
            if key in state:
                out[key] = state[key]
        if include_inverses:
            out['inverses'] = state['inverses']
        return out

    def load_state_dict(self, sd: dict, params,
                        compute_inverses: bool = True) -> dict:
        """Rebuild full K-FAC state from a checkpointed pytree.

        Validates layer congruence like reference load_state_dict
        (preconditioner.py:334-336) and recomputes inverses from factors.
        """
        state = self.init_state(params)
        if set(sd['factors']) != set(state['factors']):
            raise ValueError(
                'checkpoint layers do not match registered layers: '
                f'{sorted(sd["factors"])} vs {sorted(state["factors"])}')
        state = {**state, 'step': jnp.asarray(sd['step'], jnp.int32),
                 'factors': sd['factors'],
                 # Pre-r9 checkpoints have no pipeline position: default
                 # 0 (window head — always a safe resume point, the
                 # engine re-derives the schedule from the step counter).
                 'inv_chunk_phase': jnp.asarray(
                     sd.get('inv_chunk_phase', 0), jnp.int32)}
        state = _overlay_overlap_state(state, sd)
        # A checkpoint written under a different inverse layout (e.g.
        # 'eigen' saved, 'auto' loading) is structurally incompatible —
        # rebuild from factors instead of splicing mismatched slots in.
        # Shapes matter as much as key sets (r19): a pre-r19 full-rank
        # (d, d) basis shares the QA/dA key names with a truncated
        # (d, r) one — splicing it into a low-rank config (or vice
        # versa) would hand the wrong-shape operand to every firing.
        # A checkpoint from before layers shared an A still carries an
        # A side for every layer: a follower's is dropped (its owner's
        # inverts the same matrix), and the rest must match as ever.
        import numpy as np
        follows = self.a_followers()
        saved = {n: {k: v for k, v in entry.items()
                     if not (n in follows and k in A_SIDE_KEYS)}
                 for n, entry in sd.get('inverses', {}).items()}
        compatible = 'inverses' in sd and all(
            set(saved.get(n, ())) == set(state['inverses'][n])
            and all(tuple(np.shape(saved[n][k]))
                    == tuple(np.shape(state['inverses'][n][k]))
                    for k in state['inverses'][n])
            for n in state['inverses'])
        if compatible and not _degenerate_bases(saved):
            state = {**state, 'inverses': saved}
        elif compute_inverses:
            # warm=False: the fresh state's identity bases are not a
            # valid warm start for arbitrary checkpointed factors — use
            # an exact decomposition for this one-time host-side rebuild.
            state = {**state,
                     'inverses': self.update_inverses(state, self.damping,
                                                      warm=False)}
        return state


#: The entries of a layer's inverse state that belong to its A factor:
#: a layer that follows another's A holds none and reads its owner's.
A_SIDE_KEYS = ('A_inv', 'QA', 'dA')


def _overlay_overlap_state(state: dict, sd: dict) -> dict:
    """Restore the r14 compute/communication-overlap state fields.

    ``factor_accum``/``accum_decay`` (deferred factor reduction) and
    ``frozen_factors`` (inv_staleness=1) are overlaid from the
    checkpoint when the live config carries them AND the saved shapes
    match; otherwise the init seeds stand — pre-r14 bundles (and
    cross-topology elastic restores, whose per-device accumulator
    stacks cannot transfer) resume as "eager reduce / snapshot =
    restored factors": at most one window of un-reduced statistics is
    dropped, and the snapshot seeds from the factors the checkpoint
    DID reduce (never the identity). The accumulator and its decay
    product move together — splicing one without the other would
    decay the factors without the compensating contributions
    (MIGRATION.md). Single point of truth for the single-chip and
    SPMD loaders.
    """
    import numpy as np
    out = dict(state)
    if 'frozen_factors' in state:
        frozen = sd.get('frozen_factors')
        compatible = frozen is not None and jax.tree.structure(
            frozen) == jax.tree.structure(state['frozen_factors'])
        out['frozen_factors'] = (frozen if compatible
                                 else jax.tree.map(lambda x: x,
                                                   out['factors']))
    if 'factor_accum' in state:
        acc = sd.get('factor_accum')
        compatible = (
            acc is not None and 'accum_decay' in sd
            and jax.tree.structure(acc) == jax.tree.structure(
                state['factor_accum'])
            and all(tuple(np.shape(a)) == tuple(np.shape(b))
                    for a, b in zip(jax.tree.leaves(acc),
                                    jax.tree.leaves(
                                        state['factor_accum']))))
        if compatible:
            out['factor_accum'] = acc
            out['accum_decay'] = jnp.asarray(sd['accum_decay'],
                                             jnp.float32)
    return out


def guard_nonfinite_factors(new_factors: dict, old_factors: dict,
                            guard: bool) -> tuple[dict, jax.Array]:
    """``(factors, finite 0/1)`` — the non-finite factor-guard
    transition, single point of truth for the single-chip and SPMD
    steps (they must not drift).

    Finiteness is checked on the *candidate* post-average factors —
    collective-safe under SPMD (every device sees the same averaged
    values, so the skip cannot diverge across the mesh) and it catches
    NaN *and* Inf contamination from any capture batch. With ``guard``
    a non-finite candidate keeps the previous factors (reference
    GradScaler spirit, engine.py:75-80, extended to the factor
    statistics the reference leaves unprotected); without, the flag is
    detection-only (the metrics path).
    """
    finite = fp16_ops.tree_all_finite(new_factors)
    if guard:
        new_factors = jax.tree.map(
            lambda n, o: jnp.where(finite, n, o),
            new_factors, old_factors)
    return new_factors, finite.astype(jnp.int32)


def grouped_block_inverses(factors: dict, damping, inv_dtype,
                           sides: str = 'AG') -> dict:
    """Per-block damped inverses for a grouped-conv or stacked-expert
    layer (``capture.BLOCK_STACK_KINDS``).

    One batched damped Cholesky per side over the ``(G, d, d)`` factor
    stacks, through the call every dense Cholesky bucket makes
    (``damped_inverse_stack``: a stack over its byte budget, as eight
    2048-dim experts are, runs sub-stack by sub-stack under
    ``lax.map``). A depthwise group's blocks are tiny — ``kh*kw+1`` —
    so eigen warm-start bookkeeping would cost more than it saves.
    ``sides``: ``'G'`` for a layer that follows another's A (the owner
    inverts it). Single point of truth for the single-chip and SPMD
    inverse updates.
    """
    return {f'{side}_inv': linalg.damped_inverse_stack(
                factors[side].astype(jnp.float32), damping,
                'cholesky').astype(inv_dtype)
            for side in sides}


def measured_unit_scale(measured: dict, dim_counts: dict[int, int],
                        scope: str) -> float:
    """Fit the ms-per-dim^3 factor for a measured chunk-cost dict.

    ``measured`` maps dim -> whole-bucket ms, ``dim_counts`` maps
    dim -> work units in that bucket (per-matrix counts on the
    single-chip planner, slots_per_col on the SPMD one). Measured ms
    and the dim^3 proxy are DIFFERENT UNITS, so a measurement must
    cover every dim in ``dim_counts`` (raises otherwise — a partial
    dict like ``{4096: 531.8}`` would weight the genuinely heaviest
    bucket ~1e7x too cheap and silently un-balance the plan). Returns
    the factor that converts remaining proxy costs (grouped/diagonal
    items) into the measured unit; 1.0 when nothing is measured.
    Shared by both planners so the unit discipline cannot drift.
    """
    if not measured:
        return 1.0
    from distributed_kfac_pytorch_tpu.ops.linalg import (
        decomposition_cost,
    )
    missing = sorted(d for d in dim_counts if d not in measured)
    if missing:
        raise ValueError(
            f'inv_pipeline_costs must cover every {scope} (missing '
            f'{missing}): measured ms and the dim^3 proxy are '
            'different units and cannot be mixed in one chunk packing '
            '— pass the full bucket_parts of a firing leg')
    proxy_total = sum(decomposition_cost(d, c)
                      for d, c in dim_counts.items())
    ms_total = sum(float(measured[d]) for d in dim_counts)
    return ms_total / proxy_total if ms_total > 0 else 1.0


def plan_inverse_chunks(items: Sequence[tuple[Any, float]],
                        k: int) -> dict[Any, int]:
    """Greedy LPT assignment of inverse work items onto ``k`` chunks.

    ``items`` are ``(key, cost)`` pairs (see
    :meth:`KFAC.inverse_chunk_items`); returns ``{key: chunk_index}``.
    Single point of truth for the single-chip and SPMD pipelined-firing
    paths — both must fire the same buckets on the same phase steps.
    Balance quality on the flagship factor sets is test-pinned
    (tests/test_inv_pipeline.py: max chunk load <= 1.5x the ideal
    ``total/k`` on the ResNet-50 and xl-LM sets).
    """
    from distributed_kfac_pytorch_tpu.parallel.placement import (
        load_balance,
    )
    assignment = load_balance(k, [cost for _, cost in items])
    return {key: chunk for (key, _), chunk in zip(items, assignment)}


def eigen_family(method: str | None) -> bool:
    """True for methods whose inverse representation is an eigenpair
    (Q, d) consumed through the eigen precondition path: the exact
    'eigen' dispatch and the r19 'lowrank' truncated one. Single point
    of truth for the mixed-layer logic in the single-chip and SPMD
    paths — a layer is *mixed* exactly when one side is eigen-family
    and the other is a baked dense inverse."""
    return method in ('eigen', 'lowrank')


def resolve_eigh_method(method: str) -> str:
    """Normalize the eigh-method alias: 'warm' behaves as 'auto'.

    Both polish when a previous basis exists and fall back to the exact
    eigh when not (one-time host-side rebuilds like load_state_dict).
    Single point of truth for the single-chip and SPMD dispatchers.
    """
    return 'auto' if method in ('auto', 'warm') else method


def q_stack_degenerate(q) -> bool:
    """True if a stored eigenbasis (or stack of bases) is unusable.

    Checkpoints written by pre-warm-eigh versions initialized inverse
    slots to zeros; Q=0 is a *fixed point* of the warm polish (every
    update is right-multiplication by Q), which would silently zero the
    preconditioned gradients forever. An orthonormal (n, n) basis has
    ``|Q|_F = sqrt(n)`` (a (B, n, n) stack: ``sqrt(B * n)``), so a tiny
    Frobenius norm is an unambiguous degeneracy signal. A TRUNCATED
    (n, r) basis (r19) has ``|Q|_F = sqrt(r)`` — the expectation counts
    columns, not rows, so deep truncations are not falsely flagged.

    Multi-host safe: on a sharded ``jax.Array`` only the *addressable*
    shards are inspected (fetching the global value of an array spanning
    other hosts' devices is impossible); an all-zero stack is all-zero
    in every shard. Host-side, eager — used only on checkpoint restore.
    """
    import numpy as np

    def shard_bad(arr) -> bool:
        a = np.asarray(arr)
        # Orthonormal COLUMNS: norm = sqrt(batch dims x column count).
        expect = np.sqrt(float(np.prod(a.shape[:-2], dtype=np.float64)
                               * a.shape[-1]))
        return float(np.linalg.norm(a)) < 0.5 * expect

    shards = getattr(q, 'addressable_shards', None)
    if shards is not None:
        return any(shard_bad(s.data) for s in shards)
    return shard_bad(q)


def _degenerate_bases(inverses: dict) -> bool:
    """True if any stored eigenbasis in a per-layer inverse dict is
    unusable (see :func:`q_stack_degenerate`); the caller falls back to
    recomputing inverses from factors (the reference's behavior,
    preconditioner.py:347-353). Checks whatever eigen slots exist —
    under 'auto' dispatch only the below-cutoff sides carry bases."""
    return any(q_stack_degenerate(entry[key])
               for entry in inverses.values()
               for key in ('QA', 'QG') if key in entry)


def _size_buckets(mats: dict[str, jax.Array]):
    """Group a dict of square matrices by size: yields (names, fp32 stack).

    Ordering is deterministic (dict insertion order within a size), so the
    stacked layout is stable across traces.
    """
    buckets: dict[int, list[str]] = {}
    for name, m in mats.items():
        buckets.setdefault(m.shape[-1], []).append(name)
    for dim, names in buckets.items():
        yield names, jnp.stack([mats[n].astype(jnp.float32)
                                for n in names])


def _get(tree, path: tuple[str, ...]):
    for part in path:
        tree = tree[part]
    return tree


def _set(tree, path: tuple[str, ...], value):
    """Immutable deep-set on nested dicts."""
    if not path:
        return value
    out = dict(tree)
    out[path[0]] = _set(tree[path[0]], path[1:], value)
    return out
