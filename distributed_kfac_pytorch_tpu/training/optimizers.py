"""Optimizer factory: SGD + distributed K-FAC + schedulers.

Reference parity: examples/cnn_utils/optimizers.py:8-74 (SGD with momentum
and L2, optional KFAC with CommMethod mapping, KFACParamScheduler, and a
warmup/decay LR schedule applied to both) — built on optax and the
functional preconditioner.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax.numpy as jnp
import optax

from distributed_kfac_pytorch_tpu.preconditioner import CommMethod, KFAC
from distributed_kfac_pytorch_tpu.scheduler import KFACParamScheduler
from distributed_kfac_pytorch_tpu.training.utils import create_lr_schedule

# CLI string -> CommMethod (reference optimizers.py:18-26).
COMM_METHODS = {
    'comm-opt': CommMethod.COMM_OPT,
    'mem-opt': CommMethod.MEM_OPT,
    'hybrid-opt': CommMethod.HYBRID_OPT,
    'hybrid_opt': CommMethod.HYBRID_OPT,
    'comm_opt': CommMethod.COMM_OPT,
    'mem_opt': CommMethod.MEM_OPT,
}


@dataclasses.dataclass
class OptimConfig:
    """Hyperparameters for the optimizer stack (reference CLI flags,
    torch_cifar10_resnet.py:46-97)."""
    base_lr: float = 0.1
    momentum: float = 0.9
    weight_decay: float = 5e-4
    nesterov: bool = False
    warmup_epochs: float = 5.0
    lr_decay: Sequence[int] = (35, 75, 90)
    lr_decay_alpha: float = 0.1
    workers: int = 1                      # world size for LR scaling
    # K-FAC (0 update freq disables, like the reference's --kfac-update-freq 0)
    kfac_inv_update_freq: int = 10
    kfac_cov_update_freq: int = 1
    damping: float = 0.003
    factor_decay: float = 0.95
    kl_clip: float = 0.001
    use_eigen_decomp: bool | None = None  # None: follow inverse_method
    # 'auto' | 'eigen' | 'cholesky' | 'newton'; None (default) -> the
    # per-dim 'auto' dispatch (eigen below KFAC.auto_eigen_max_dim,
    # cholesky above — fast at every factor scale).
    inverse_method: str | None = None
    # 'auto' dispatch knobs (KFAC defaults: 640 / 'cholesky' — the
    # measured v5e crossover; see PERF.md round 4).
    auto_eigen_max_dim: int = 640
    auto_large_method: str = 'cholesky'
    # Randomized low-rank inverse path (r19, arXiv:2206.15397): with
    # rank > 0, dense factor dims >= inv_lowrank_dim_threshold
    # decompose as a rank-r truncated eigenpair (Gaussian range-finder
    # sketch, warm subspace-refresh + polish each firing — r·d^2
    # matmul work instead of the O(d^3) eigh/cholesky wall) and
    # precondition through the truncated basis plus the damping-only
    # tail complement (full-rank correct). 0 (default) = off, the
    # bit-identical exact path. rank must be < every engaged dim
    # (hard error at registration, never a silent fallback).
    inv_lowrank_rank: int = 0
    inv_lowrank_dim_threshold: int = 2048
    # 'auto' (default): warm-start basis polish seeded from the state's
    # previous eigenbasis (the TPU fast path — see ops.linalg.eigh_polish);
    # 'xla' | 'jacobi' | 'warm' as in KFAC.
    eigh_method: str = 'auto'
    eigh_polish_iters: int = 8
    # Fraction of the batch used for factor statistics (1.0 = reference
    # parity; < 1 thins the covariance sample within the step — see
    # KFAC.factor_batch_fraction).
    factor_batch_fraction: float = 1.0
    # bf16 factor storage/averaging AND bf16 covariance-matmul inputs
    # (the matmuls accumulate fp32; the EWMA running averages are kept in
    # bf16) — the reference's --fp16 factor mode. For bf16 matmuls with
    # fp32 running averages, pass factor_compute_dtype to KFAC directly.
    bf16_factors: bool = False
    # bf16 INVERSE storage (KFAC inv_dtype; decompositions stay fp32 —
    # the reference's configurable inv_dtype, base.py:435-441). Halves
    # K-FAC state; with bf16_factors it is what fits the monolithic
    # b256 ResNet-50 capture-free step on a 16 GB chip and speeds the
    # 'auto' firing 1.5x (PERF.md round 5).
    bf16_inverses: bool = False
    # bf16 precondition-contraction operands (KFAC
    # precond_compute_dtype; accumulation stays fp32) — the every-step
    # inverse·grad matmuls run on the MXU bf16 path, and with
    # bf16_inverses the stored inverses are consumed resident (no fp32
    # upcast-on-read). Default False = the bit-identical fp32 path.
    bf16_precond: bool = False
    # Pipelined inverse firing (r9): partition the per-firing inverse
    # work into k cost-balanced chunks and fire chunk j on step
    # j*inv_update_freq/k of each cadence window — smears the
    # decomposition spike across the window (step-time uniformity).
    # 1 (default) = reference parity, monolithic firing, bit-identical.
    inv_pipeline_chunks: int = 1
    # Deferred factor reduction (r14): accumulate factor statistics
    # locally on factor steps and reduce across replicas once per
    # cadence window (one bucketed collective where the eager path
    # pays a per-factor-step pmean). Mathematically exact by EMA
    # linearity; off (default) = bit-identical eager path.
    deferred_factor_reduction: bool = False
    # Hierarchical two-level factor reduction (r20, multi-slice
    # meshes only; mutually exclusive with deferred_factor_reduction):
    # intra-slice pmean on ICI every factor step, one bucketed
    # inter-slice DCN reduce per cadence window. Exact by the same
    # EMA-linearity argument; off (default) = flat reduce.
    hierarchical_reduce: bool = False
    # One-window-stale off-critical-path inverses (r14): 0 (default,
    # bit-identical) or 1 — decompositions for window w+1 are computed
    # from factors frozen at the end of window w and chunk-fired
    # across w+1's plain steps, so the eigh spike overlaps plain
    # compute instead of blocking the mesh. Convergence-gated like the
    # r9 chunk knob (PERF.md r14).
    inv_staleness: int = 0
    # Weight-sharing Kronecker approximation (r13, arXiv:2311.00636):
    # 'expand' (default — bit-identical pre-sharing path) or 'reduce'
    # (sequence/patch-shared Denses + patch-embed convs reduce over the
    # shared axis before the covariance: a factor-T cheaper factor
    # update; tied in/out embeddings then also share one factor pair).
    # See KFAC.kfac_approx / sharing.approx.
    kfac_approx: str = 'expand'
    # r7 observability: carry an on-device K-FAC metrics pytree in the
    # state (damping, KL-clip nu, grad/precond norms, firing counts —
    # see observability.metrics). Off (default) = bit-identical step.
    kfac_metrics: bool = False
    # Skip factor EWMA updates whose candidate factors are non-finite
    # (the on-device health guard; counted in metrics when they are on).
    nonfinite_guard: bool = False
    skip_layers: Sequence[str] = ()
    symmetry_aware_comm: bool = False
    comm_method: str = 'comm-opt'
    grad_worker_fraction: float = 0.25
    damping_alpha: float = 1.0
    damping_schedule: Sequence[int] = ()
    kfac_update_freq_alpha: float = 1.0
    kfac_update_freq_schedule: Sequence[int] = ()


#: OptimConfig fields the perf autotuner may override from a committed
#: ``TUNED_<workload>.json`` artifact (``autotune.apply_tuned``). The
#: set is restricted to per-KFAC knobs that leave the mesh topology
#: alone: mesh-shaping knobs (``comm_method``,
#: ``grad_worker_fraction``) would desync the already-constructed mesh
#: from the config, so they stay CLI-flag-only (the artifact records
#: them as provenance instead). An artifact naming a knob outside this
#: set is rejected whole (fail-closed) rather than applied partially.
TUNABLE_FIELDS = (
    'bf16_precond',
    'bf16_factors',
    'bf16_inverses',
    'inv_pipeline_chunks',
    'deferred_factor_reduction',
    'hierarchical_reduce',
    'inv_staleness',
    'factor_batch_fraction',
    'kfac_cov_update_freq',
    'kfac_inv_update_freq',
    'eigh_polish_iters',
    'kfac_approx',
    'inv_lowrank_rank',
    'inv_lowrank_dim_threshold',
)


def make_sgd(cfg: OptimConfig) -> optax.GradientTransformation:
    """SGD with L2 and momentum, torch-ordered (wd before momentum).

    Matches torch.optim.SGD semantics used by the reference
    (optimizers.py:10-14): ``g += wd * p``; ``buf = m * buf + g``;
    ``p -= lr * buf``. The learning rate is injected so the engine can
    schedule it without rebuilding the transformation.
    """
    def tx(learning_rate):
        chain = []
        if cfg.weight_decay:
            chain.append(optax.add_decayed_weights(cfg.weight_decay))
        if cfg.momentum:
            chain.append(optax.trace(decay=cfg.momentum,
                                     nesterov=cfg.nesterov))
        chain.append(optax.scale_by_learning_rate(learning_rate))
        return optax.chain(*chain)

    return optax.inject_hyperparams(tx)(learning_rate=cfg.base_lr)


def set_lr(opt_state, lr):
    """Return opt_state with the injected learning rate replaced.

    Accepts the bare ``inject_hyperparams`` state or a ``chain`` state
    containing one (e.g. when gradient clipping is chained in front).
    """
    states = (opt_state,) if hasattr(opt_state, 'hyperparams') else (
        opt_state if isinstance(opt_state, tuple) else ())
    for s in states:
        if hasattr(s, 'hyperparams'):
            # Preserve the leaf's exact aval (array-ness, dtype AND
            # weak_type): writing a Python float where an array leaf
            # lived — or a strong-typed array where a weak one lived —
            # changes the jit argument signature and silently recompiles
            # the train step every epoch (~15-45 s per variant on TPU).
            prev = jnp.asarray(s.hyperparams['learning_rate'])
            if prev.weak_type:
                new = jnp.asarray(float(lr))
            else:
                new = jnp.asarray(lr, dtype=prev.dtype)
            s.hyperparams['learning_rate'] = new
            return opt_state
    raise ValueError('no injected learning_rate in optimizer state')


def get_optimizer(model, cfg: OptimConfig):
    """(tx, lr_schedule, kfac | None, kfac_scheduler | None).

    ``lr_schedule(epoch) -> lr`` (base_lr x warmup/decay factor, reference
    optimizers.py:68-72 applies the same LambdaLR to SGD and KFAC — here
    the engine feeds the same value to optax and to the KL-clip ``lr``).
    K-FAC is enabled when ``kfac_inv_update_freq > 0`` (reference
    optimizers.py:28).
    """
    tx = make_sgd(cfg)
    factor = create_lr_schedule(cfg.workers, cfg.warmup_epochs,
                                cfg.lr_decay, cfg.lr_decay_alpha)
    lr_schedule = lambda epoch: cfg.base_lr * factor(epoch)

    kfac = None
    kfac_scheduler = None
    if cfg.kfac_inv_update_freq > 0:
        kfac = KFAC(
            model,
            damping=cfg.damping,
            factor_decay=cfg.factor_decay,
            factor_update_freq=cfg.kfac_cov_update_freq,
            inv_update_freq=cfg.kfac_inv_update_freq,
            kl_clip=cfg.kl_clip,
            lr=cfg.base_lr,
            use_eigen_decomp=cfg.use_eigen_decomp,
            inverse_method=cfg.inverse_method,
            auto_eigen_max_dim=cfg.auto_eigen_max_dim,
            auto_large_method=cfg.auto_large_method,
            inv_lowrank_rank=cfg.inv_lowrank_rank,
            inv_lowrank_dim_threshold=cfg.inv_lowrank_dim_threshold,
            eigh_method=cfg.eigh_method,
            eigh_polish_iters=cfg.eigh_polish_iters,
            factor_batch_fraction=cfg.factor_batch_fraction,
            factor_dtype=jnp.bfloat16 if cfg.bf16_factors else None,
            factor_compute_dtype=(jnp.bfloat16 if cfg.bf16_factors
                                  else None),
            inv_dtype=(jnp.bfloat16 if cfg.bf16_inverses
                       else jnp.float32),
            precond_compute_dtype=(jnp.bfloat16 if cfg.bf16_precond
                                   else None),
            inv_pipeline_chunks=cfg.inv_pipeline_chunks,
            deferred_factor_reduction=cfg.deferred_factor_reduction,
            hierarchical_reduce=cfg.hierarchical_reduce,
            inv_staleness=cfg.inv_staleness,
            kfac_approx=cfg.kfac_approx,
            skip_layers=list(cfg.skip_layers) or None,
            symmetry_aware_comm=cfg.symmetry_aware_comm,
            comm_method=COMM_METHODS[cfg.comm_method.lower()],
            grad_worker_fraction=cfg.grad_worker_fraction,
            collect_metrics=cfg.kfac_metrics,
            nonfinite_guard=cfg.nonfinite_guard)
        kfac_scheduler = KFACParamScheduler(
            kfac,
            damping_alpha=cfg.damping_alpha,
            damping_schedule=list(cfg.damping_schedule) or None,
            update_freq_alpha=cfg.kfac_update_freq_alpha,
            update_freq_schedule=(
                list(cfg.kfac_update_freq_schedule) or None))
    return tx, lr_schedule, kfac, kfac_scheduler
