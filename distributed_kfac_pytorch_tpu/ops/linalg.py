"""Dense linear algebra for K-FAC factor inversion, jit/vmap friendly.

TPU-native replacements for the reference's cuSOLVER-backed ops
(kfac/layers/utils.py:45-105): ``torch.symeig`` -> ``jnp.linalg.eigh``,
``torch.cholesky`` + ``cholesky_inverse`` -> XLA Cholesky + triangular solves.
Decompositions always run in float32 regardless of the factor storage dtype,
matching the reference's policy (kfac/layers/base.py:432-441).

All functions are shape-polymorphic over leading batch dims via ``vmap`` at
the call site; the preconditioner batches same-size factors so XLA can run
the O(n^3) decompositions as one batched kernel spread across the mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from distributed_kfac_pytorch_tpu.observability import profiling, tracing


def decomposition_cost(dim: int, count: int = 1,
                       rank: int | None = None) -> float:
    """Cost proxy for decomposing ``count`` SPD matrices of ``dim``.

    The classic ``dim^3`` FLOP scaling every dense factorization here
    shares (Cholesky, Newton–Schulz, the warm-polish matmuls, eigh) —
    the same proxy the KAISA work balancer uses
    (``assignment_strategy='compute'``, reference
    preconditioner.py:625-628). Used by the pipelined-firing chunk
    planner (``KFAC.inverse_chunk_plan``) to bin-pack same-dim bucket
    stacks into cost-balanced chunks; per-dim *measured* firing costs
    (the ``bucket_parts`` ms of a flagship firing leg) refine it via
    ``KFAC(inv_pipeline_costs={dim: ms})``.

    ``rank``: when the dim's dispatch resolves to the randomized
    low-rank path (r19, ``inv_lowrank_rank``), the firing is
    matmul-dominated at ``rank * dim^2`` FLOPs (sketch/subspace-refresh
    products of a (dim, rank) basis against the (dim, dim) factor)
    instead of ``dim^3`` — without this the r9/r14 LPT chunk planners
    would weight a low-rank bucket ``dim/rank``x too heavy and
    un-balance every pipelined window that mixes exact and low-rank
    buckets. ``None``/0 keeps the dense proxy.
    """
    if rank:
        return float(count) * float(rank) * float(dim) ** 2
    return float(count) * float(dim) ** 3


def get_eigendecomp(x: jax.Array, clip: float | None = 0.0
                    ) -> tuple[jax.Array, jax.Array]:
    """Symmetric eigendecomposition in fp32 with eigenvalue clipping.

    Returns ``(Q, d)`` with eigenvalues ascending. ``clip`` floors the
    eigenvalues (``max(d, clip)``), like the reference's
    ``get_eigendecomp(clip=0.0)`` (kfac/layers/utils.py:45-74), which
    guards against tiny negative eigenvalues from round-off.
    """
    d, q = jnp.linalg.eigh(x.astype(jnp.float32))
    if clip is not None:
        d = jnp.maximum(d, clip)
    return q, d


def default_jacobi_sweeps(n: int) -> int:
    """Sweep count reaching fp32 roundoff: 12 up to n=512, +log2 beyond."""
    return 12 if n <= 512 else 12 + max(0, (n - 1).bit_length() - 9)


def jacobi_slot_iteration(a: jax.Array, v: jax.Array, sweeps: int
                          ) -> tuple[jax.Array, jax.Array]:
    """The Brent–Luk Jacobi inner loop over an even-dim slot-basis pair.

    Runs ``sweeps * (n - 1)`` rounds: rotate the paired half-blocks of
    ``a`` (rows then columns) and of ``v`` (columns), then move to the
    next tournament pairing with the systolic slice/concat exchange.
    Every op is elementwise/slice/concat — usable verbatim inside a
    Pallas kernel (ops.pallas_kernels) and under vmap.

    Returns (a, v) with ``a`` ~diagonal in the final slot order and
    ``v``'s columns the matching eigenvector candidates (original row
    basis). Callers sort by the diagonal afterwards.
    """
    n_pad = a.shape[-1]
    p = n_pad // 2
    eye_p = jnp.eye(p, dtype=jnp.float32)

    def halves(m, axis):
        return (jax.lax.slice_in_dim(m, 0, p, axis=axis),
                jax.lax.slice_in_dim(m, p, n_pad, axis=axis))

    def rotate(m, c, s, axis):
        """Mix the two halves along ``axis`` with per-pair (c, s)."""
        lo, hi = halves(m, axis)
        shape = (-1, 1) if axis == 0 else (1, -1)
        c = c.reshape(shape)
        s = s.reshape(shape)
        return jnp.concatenate([c * lo - s * hi, s * lo + c * hi],
                               axis=axis)

    def exchange(m, axis):
        """Brent–Luk systolic move to the next pairing (slice/concat).

        tops' = [t0, b0, t1..t_{p-2}]; bots' = [b1..b_{p-1}, t_{p-1}].
        """
        t, b = halves(m, axis)
        sl = lambda h, lo, hi: jax.lax.slice_in_dim(h, lo, hi, axis=axis)
        t_new = jnp.concatenate(
            [sl(t, 0, 1), sl(b, 0, 1), sl(t, 1, p - 1)], axis=axis)
        b_new = jnp.concatenate(
            [sl(b, 1, p), sl(t, p - 1, p)], axis=axis)
        return jnp.concatenate([t_new, b_new], axis=axis)

    def round_step(_, carry):
        a, v = carry
        # Pair i = (slot i, slot p+i): diagonals of the three p x p
        # blocks, extracted by mask-sum (no gathers).
        tl, tr = halves(halves(a, 0)[0], 1)     # a[:p,:p], a[:p,p:]
        br = halves(halves(a, 0)[1], 1)[1]      # a[p:,p:]
        app = jnp.sum(tl * eye_p, axis=1)
        aqq = jnp.sum(br * eye_p, axis=1)
        apq = jnp.sum(tr * eye_p, axis=1)
        small = jnp.abs(apq) <= 1e-30
        tau = (aqq - app) / jnp.where(small, 1.0, 2.0 * apq)
        # sign(0) must be +1: tau=0 (equal diagonal) needs the full
        # 45-degree rotation, not the identity.
        sgn = jnp.where(tau >= 0, 1.0, -1.0)
        t = sgn / (jnp.abs(tau) + jnp.sqrt(1.0 + tau * tau))
        t = jnp.where(small, 0.0, t)
        c = 1.0 / jnp.sqrt(1.0 + t * t)
        s = t * c
        a = rotate(a, c, s, axis=0)             # J^T A
        a = rotate(a, c, s, axis=1)             # (J^T A) J
        v = rotate(v, c, s, axis=1)             # accumulate Q = J_1 J_2 ..
        if p > 1:
            a = exchange(a, axis=0)
            a = exchange(a, axis=1)
            v = exchange(v, axis=1)
        return a, v

    # fori_loop, not scan: identical semantics with no per-round outputs,
    # and it is the loop form the Mosaic (Pallas TPU) compiler can lower,
    # so the same code runs inside the VMEM kernel.
    rounds = sweeps * (n_pad - 1)
    a, v = jax.lax.fori_loop(0, rounds, round_step, (a, v))
    return a, v


def jacobi_eigh(x: jax.Array, sweeps: int | None = None
                ) -> tuple[jax.Array, jax.Array]:
    """Symmetric eigendecomposition by Brent–Luk parallel Jacobi.

    The matrix lives in a *slot* basis where round ``r`` always pairs
    slot ``i`` with slot ``p + i`` (``p = n/2``): each round applies all
    ``p`` disjoint Givens rotations as two half-matrix elementwise
    combines (rows, then columns), then moves pairs to the next
    tournament arrangement with the Brent–Luk systolic exchange — a
    fixed slice/concat shuffle. One sweep = ``n - 1`` rounds covering
    every index pair once. The entire inner loop is elementwise ops,
    slices and concats — no gather/scatter — so it vectorizes cleanly on
    wide vector units and ports directly to a VMEM-resident Pallas
    kernel. Accuracy: off-diagonal mass contracts quadratically once
    small; 12 sweeps reach fp32 roundoff for n <= ~512, and the default
    scales the count with log2(n) beyond that.

    Returns ``(Q, d)`` with eigenvalues ascending (same convention as
    :func:`get_eigendecomp`). Pure JAX, vmap-friendly.
    """
    n = x.shape[-1]
    x = x.astype(jnp.float32)
    if sweeps is None:
        sweeps = default_jacobi_sweeps(n)
    if n == 1:
        return jnp.ones((1, 1), jnp.float32), x.reshape(1)
    n_pad = n + (n % 2)
    a = x
    if n_pad != n:
        # Pad with a decoupled unit eigenvalue; stripped after sorting.
        a = jnp.pad(x, ((0, 1), (0, 1)))
        a = a.at[n, n].set(1.0)
    v0 = jnp.eye(n_pad, dtype=jnp.float32)
    a, v = jacobi_slot_iteration(a, v0, sweeps)
    d = jnp.diagonal(a)
    order = jnp.argsort(d)
    d = d[order]
    v = v[:, order]
    if n_pad != n:
        # Drop the padding eigenpair: its eigenvector is exactly e_n.
        keep = v[n, :] < 0.5
        # Static-shape removal: positions of kept columns among first n.
        idx = jnp.nonzero(keep, size=n)[0]
        v = jnp.take(v[:n, :], idx, axis=1)
        d = jnp.take(d, idx)
    return v, d


def eigh_polish(a: jax.Array, q_prev: jax.Array, iters: int = 16,
                theta: float = 0.8, t_max: float = 0.2,
                ns_steps: int = 3,
                precision=None) -> tuple[jax.Array, jax.Array]:
    """Warm-start symmetric eigendecomposition by basis polishing.

    Given an SPD matrix ``a`` and an orthonormal matrix ``q_prev`` whose
    columns approximately diagonalize it, refine the basis with a fixed
    number of matmul-only iterations. Per iteration:

      1. rotate into the current basis: ``B = Q^T a Q`` (symmetrized);
      2. simultaneous Jacobi correction: for each off-diagonal pair the
         *exact* two-sided Jacobi rotation tangent
         ``t = sign(τ)/(|τ| + sqrt(1 + τ^2))``, ``τ = (d_j - d_i)/2E_ij``
         (``|t| <= 1``, so exactly-degenerate pairs rotate instead of
         dividing by ~0), clipped elementwise to ``t_max`` and assembled
         into a skew-symmetric ``X``. The clip is what keeps the
         *well-separated* pairs converging fast: without it, eigenvalue
         clusters contribute |t|~1 entries that keep ``|X|_2`` large and
         the global rescale (next) would keep damping every pair's
         correction (measured: tail convergence rate 0.65/iter unclipped
         vs 0.4 clipped). Cluster-internal rotations proceed at the
         capped pace — harmless, their basis choice doesn't affect the
         preconditioner. The whole update is then rescaled to spectral
         norm ``theta`` (power iteration on ``-X^2`` estimates
         ``|X|_2``; data-dependent in *value*, never in runtime);
      3. ``Q <- Q (I + X)``, then ``ns_steps`` Newton–Schulz
         orthogonalization steps ``Q <- Q (3I - Q^T Q) / 2`` (for skew
         ``X`` the orthogonality defect of ``I + X`` is exactly
         ``X^T X``; each NS step squares the defect).

    16 iterations reach ~1e-4 preconditioning accuracy from a 0.2-rad
    basis rotation and ~1e-5 steady-state accuracy tracking the
    per-firing factor drift of an EWMA K-FAC run (validated on synthetic
    drifting-spectrum suites; see tests/test_warm_eigh.py).

    Why this beats a cold eigh for K-FAC: factors drift slowly (EWMA
    with decay ~0.95) and the state already carries the previous basis,
    so per inverse update the basis is nearly right already. Every op
    is a dense fp32 matmul or elementwise map — data-independent
    runtime on the MXU, batchable over a factor stack — versus the
    XLA/backend eigh whose iterative while-loops run longer as
    conditioning worsens (observed 45 -> 240+ ms on trained ResNet-32
    factor sets on v5e, PERF.md §6). The reference pays a sequential
    cuSOLVER ``symeig`` per layer per update instead
    (kfac/layers/base.py:432-441).

    Accuracy note: within tight eigenvalue *clusters* the returned
    basis may briefly mix cluster members (rotations there are capped
    per iteration) — harmless for K-FAC preconditioning, where the
    damping quotient ``1/(dG dA + λ)`` is flat across near-equal
    eigenvalues, and self-correcting across firings.

    ``q_prev`` may be RECTANGULAR ``(n, r)`` with orthonormal columns
    (the r19 randomized low-rank path): every step then operates on
    the ``r x r`` projected matrix ``B = Q^T a Q`` — the polish
    diagonalizes *within* ``span(Q)`` (a Rayleigh–Ritz refinement;
    the span itself is rotated toward the dominant subspace by the
    caller's subspace-iteration refresh, :func:`lowrank_eigh`). For a
    square ``q_prev`` the ops are identical to the historical path
    (``r == n``), bit-for-bit.

    Returns ``(Q, d)`` with eigenvalues in *tracked* order (continuity
    with ``q_prev``'s columns), NOT sorted.
    """
    a = a.astype(jnp.float32)
    q = q_prev.astype(jnp.float32)
    n = q.shape[-1]  # basis rank: == a dim for the classic square case
    eye = jnp.eye(n, dtype=jnp.float32)
    if precision is None:
        # HIGHEST: measured on v5e (benchmarks/eigh_methods.py), HIGH
        # (3-pass bf16 emulation) saves only ~7% wall clock — the
        # firing is not MXU-bound at these sizes — while its absolute
        # rounding floor costs 300x accuracy on spread spectra
        # (9e-6 -> 3e-3 worst preconditioning error).
        precision = jax.lax.Precision.HIGHEST
    mm = functools.partial(jnp.matmul, precision=precision)

    def body(_, q):
        b = mm(q.T, mm(a, q))
        b = 0.5 * (b + b.T)
        d = jnp.sum(b * eye, axis=1)
        e = b - d[:, None] * eye
        delta = d[None, :] - d[:, None]          # Δ_ij = d_j - d_i
        sgn_e = jnp.where(e >= 0, 1.0, -1.0)
        abs_e = jnp.abs(e)
        tau = delta / jnp.maximum(2.0 * abs_e, 1e-30)
        # sign(0) -> +1 so exactly-degenerate pairs still rotate.
        t = (jnp.where(tau >= 0, 1.0, -1.0)
             / (jnp.abs(tau) + jnp.sqrt(1.0 + tau * tau)))
        t = jnp.clip(t, -t_max, t_max)
        x = sgn_e * t * (abs_e > 1e-30)
        x = jnp.triu(x, 1)
        x = x - x.T                              # skew by construction
        # Spectral-norm estimate via power iteration on X^T X = -X^2
        # (matvecs only, O(n^2)); scale X into the NS-orthogonalization
        # basin. The shrink engages only while strongly-coupled pairs
        # overlap (early tracking transients); near convergence it is
        # the identity and quadratic convergence takes over.
        v0 = jnp.full((n,), 1.0 / n, jnp.float32)

        def pw(_, v):
            w = x @ (x @ v)
            return -w / jnp.maximum(jnp.linalg.norm(w), 1e-30)

        v = jax.lax.fori_loop(0, 10, pw, v0)
        nrm = jnp.sqrt(jnp.linalg.norm(x @ (x @ v)))
        x = x * jnp.minimum(1.0, theta / jnp.maximum(nrm, 1e-30))
        q = q + mm(q, x)
        for _ in range(ns_steps):
            q = 0.5 * mm(q, 3.0 * eye - mm(q.T, q))
        return q

    q = jax.lax.fori_loop(0, iters, body, q)
    d = jnp.sum(mm(q.T, mm(a, q)) * eye, axis=1)
    return q, d


def batched_eigh(stack: jax.Array, method: str = 'xla',
                 clip: float | None = 0.0,
                 sweeps: int | None = None,
                 q_prev: jax.Array | None = None,
                 polish_iters: int = 16
                 ) -> tuple[jax.Array, jax.Array]:
    """Eigendecompose a (B, n, n) SPD stack: ``(Q, d)``.

    ``method='xla'`` vmaps the backend eigh (eigenvalues ascending);
    ``'jacobi'`` dispatches through
    ``ops.pallas_kernels.batched_jacobi_eigh`` (Brent–Luk parallel
    Jacobi — vmapped pure JAX by default; the VMEM Pallas kernel is
    opt-in, hardware-validated but VMEM-bound at n >= 128 — see its
    dispatch comment); ``'warm'`` requires ``q_prev`` (a (B, n, n)
    stack of previous bases) and runs the matmul-only
    :func:`eigh_polish` (eigenvalues in tracked, not sorted, order);
    ``'auto'`` picks 'warm' when ``q_prev`` is given, else 'xla'.
    Single dispatch point for the bucketed eigen paths in
    ``preconditioner`` and ``parallel.distributed``.
    """
    if method == 'auto':
        method = 'warm' if q_prev is not None else 'xla'
    if method == 'warm':
        if q_prev is None:
            raise ValueError("eigh method 'warm' requires q_prev")
        with profiling.annotate('kfac/eigh/warm'):
            qs, ds = jax.vmap(
                lambda m, q0: eigh_polish(m, q0, iters=polish_iters))(
                    stack, q_prev)
            if clip is not None:
                ds = jnp.maximum(ds, clip)
            return qs, ds
    if method == 'jacobi':
        from distributed_kfac_pytorch_tpu.ops import pallas_kernels
        with profiling.annotate('kfac/eigh/jacobi'):
            qs, ds = pallas_kernels.batched_jacobi_eigh(stack, sweeps)
            if clip is not None:
                ds = jnp.maximum(ds, clip)
            return qs, ds
    if method != 'xla':
        raise ValueError(
            "eigh method must be 'auto', 'xla', 'jacobi' or 'warm', "
            f'got {method!r}')
    with profiling.annotate('kfac/eigh/xla'):
        return jax.vmap(lambda m: get_eigendecomp(m, clip=clip))(stack)


def lowrank_eigh(a: jax.Array, rank: int,
                 q_prev: jax.Array | None = None,
                 power_iters: int = 2,
                 polish_iters: int = 8,
                 seed: int = 0) -> tuple[jax.Array, jax.Array]:
    """Rank-``r`` truncated eigendecomposition of an SPD matrix.

    Randomized NLA (Halko-Martinsson-Tropp range finder, the
    *Randomized K-FACs* recipe, arXiv:2206.15397) turns the O(d^3)
    eigh wall into O(r d^2) matmul work:

      - **cold** (``q_prev=None`` — checkpoint rebuilds, factor-only
        restores): a Gaussian test matrix ``Ω (d, r)`` sketches the
        range, ``power_iters`` subspace iterations
        ``Y <- A orth(Y)`` sharpen it against slow spectral decay,
        and an exact ``r x r`` Rayleigh–Ritz (``eigh`` of
        ``Q^T A Q`` — r^3, negligible) extracts the eigenpairs. The
        test matrix is a fixed-seed deterministic draw, so rebuilds
        are reproducible run to run.
      - **warm** (the in-run firing path): one subspace-iteration
        refresh ``orth(A q_prev)`` rotates the carried basis toward
        the factor's current dominant subspace (EWMA factors drift
        slowly, so one step per firing tracks it — the same argument
        as the full-rank warm polish), then :func:`eigh_polish`
        re-diagonalizes within the span with the proven matmul-only
        iteration — run in the PROJECTED ``r x r`` space: the polish
        never leaves ``span(Q)``, so ``Q_k = Q_0 Z_k`` and
        ``B_k = Z_k^T (Q_0^T A Q_0) Z_k`` — project once (two thin
        A-products, the whole O(r d^2) cost), polish ``Z`` against
        the small ``B_0`` at O(r^3)/iter, recombine ``Q = Q_0 Z``.
        Identical math to polishing the rectangular basis directly
        (``Q_0`` has orthonormal columns, so ``Q^T Q = Z^T Z`` and
        the Newton–Schulz orthogonalization maps 1:1), at 2·r·d^2
        instead of 2·iters·r·d^2 — the constant that makes the
        firing beat a d^3/3 Cholesky from d ~ 1.5k upward. The
        carried basis CONVERGES across firing windows instead of
        re-randomizing each time.

    Every sketch product is an fp32-pinned matmul
    (``preferred_element_type=jnp.float32`` — the r6 dtype-discipline
    contract, enforced by kfaclint's dtype family on these call
    sites), so bf16-stored factors cannot silently degrade the basis.

    Returns ``(Q, d)`` with ``Q (d, r)`` orthonormal columns and ``d``
    the ``r`` Rayleigh eigenvalues (ascending on the cold path,
    tracked order on the warm path — consumers are order-invariant).
    The discarded tail is treated as 0 by every consumer: the damped
    operator is ``Q diag(1/(d+λ)) Q^T + (I - Q Q^T)/λ`` — full-rank
    correct, with tail curvature regularized to the damping floor
    (see :func:`eigen_side_inverse` / :func:`precondition_eigen`).
    """
    a = a.astype(jnp.float32)
    n = a.shape[-1]
    if not 0 < rank < n:
        raise ValueError(
            f'lowrank_eigh needs 0 < rank < dim, got {rank=} dim={n}')
    if q_prev is not None:
        lowrank_sketch = q_prev.astype(jnp.float32)
        refreshed = jnp.matmul(a, lowrank_sketch,
                               preferred_element_type=jnp.float32,
                               precision=jax.lax.Precision.HIGHEST)
        q0, _ = jnp.linalg.qr(refreshed)
        # Project once (the only other O(r d^2) product), polish the
        # r x r rotation Z in the projected space, recombine. See the
        # docstring for why this is identical to polishing the
        # rectangular basis directly.
        aq0 = jnp.matmul(a, q0, preferred_element_type=jnp.float32,
                         precision=jax.lax.Precision.HIGHEST)
        b0 = jnp.matmul(q0.T, aq0,
                        preferred_element_type=jnp.float32,
                        precision=jax.lax.Precision.HIGHEST)
        b0 = 0.5 * (b0 + b0.T)
        z, d = eigh_polish(b0, jnp.eye(rank, dtype=jnp.float32),
                           iters=polish_iters)
        q = jnp.matmul(q0, z, preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST)
        return q, d
    # Cold start: Gaussian range-finder sketch + power iterations.
    lowrank_sketch = jax.random.normal(jax.random.PRNGKey(seed),
                                       (n, rank), jnp.float32)
    y = jnp.matmul(a, lowrank_sketch,
                   preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)
    for _ in range(max(0, power_iters)):
        q0, _ = jnp.linalg.qr(y)
        y = jnp.matmul(a, q0, preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST)
    q0, _ = jnp.linalg.qr(y)
    # Rayleigh–Ritz on the r x r projection: exact within the sketched
    # subspace, and r^3 is noise next to the r d^2 sketch products.
    b = jnp.matmul(q0.T, jnp.matmul(a, q0,
                                    preferred_element_type=jnp.float32,
                                    precision=jax.lax.Precision.HIGHEST),
                   preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)
    d, u = jnp.linalg.eigh(0.5 * (b + b.T))
    q = jnp.matmul(q0, u, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)
    return q, d


def batched_lowrank_eigh(stack: jax.Array, rank: int,
                         q_prev: jax.Array | None = None,
                         power_iters: int = 2,
                         polish_iters: int = 8,
                         clip: float | None = 0.0,
                         seed: int = 0
                         ) -> tuple[jax.Array, jax.Array]:
    """Truncated-eigendecompose a (B, n, n) SPD stack: ``(Q, d)`` with
    ``Q (B, n, rank)`` / ``d (B, rank)``.

    The low-rank analogue of :func:`batched_eigh` — one vmapped
    :func:`lowrank_eigh` per same-dim bucket; ``q_prev`` is a
    ``(B, n, rank)`` stack of carried truncated bases (the warm
    subspace-refresh + polish path). ``clip`` floors the Rayleigh
    eigenvalues like the exact path (tiny negatives from round-off on
    a PSD factor). Single dispatch point for the single-chip and SPMD
    bucketed firing paths.
    """
    with profiling.annotate('kfac/eigh/lowrank'):
        if q_prev is None:
            qs, ds = jax.vmap(
                lambda m: lowrank_eigh(m, rank,
                                       power_iters=power_iters,
                                       seed=seed))(stack)
        else:
            qs, ds = jax.vmap(
                lambda m, q0: lowrank_eigh(
                    m, rank, q_prev=q0,
                    polish_iters=polish_iters))(stack, q_prev)
        if clip is not None:
            ds = jnp.maximum(ds, clip)
        return qs, ds


# The halved route of the damped Cholesky inverse (get_inverse), a
# function of the static dim alone. A dim of INVERSE_HALVE_MIN_DIM or
# more is split in two at a multiple of INVERSE_HALVE_ALIGN (the lane
# width: 3073 -> 1536 + 1537, 6144 -> 3072 + 3072), and each half
# again, down to blocks of INVERSE_HALVE_LEAF or less, which XLA's own
# cholesky and triangular solve take (2048 -> 2 x 1024; 3072 and 6144
# -> leaves of 768; 1536 -> 2 x 768). Set by the function alone on one
# v5e (benchmarks/inverse_forms.py; PERF.md section 6, PR 32), ms a
# matrix, whole -> halved at leaf 512 / 1024 / 2048: 1536 dims 0.85 ->
# 0.66 / 0.72; 2048: 1.63 -> 1.19 / 1.27; 3072: 3.99 -> 2.77 / 2.81 /
# 3.08; 3073: 4.49 -> 2.85 / 2.85 / 3.22; 6144: 25.2 -> 15.9 / 15.4 /
# 15.5; 768 and 769 gain nothing (0.27 -> 0.25, 0.29 -> 0.27 at best)
# and stay whole. Leaf 1024 and not 512, which is 6 % faster at 2048:
# every product of the recursion is a kernel of its own in the loaded
# program, which is HBM (46 against 53 MB a (8, 2048, 2048) call site,
# 34 whole), and the cells' peak_hbm_gib has 1 % of room.
INVERSE_HALVE_MIN_DIM = 1536
INVERSE_HALVE_LEAF = 1024
INVERSE_HALVE_ALIGN = 128


def inverse_is_halved(n: int) -> bool:
    """Whether a damped Cholesky inverse of dim ``n`` takes the halved
    route (see the ``INVERSE_HALVE_*`` constants)."""
    return n >= INVERSE_HALVE_MIN_DIM


def _halving_point(n: int) -> int:
    """Where a dim ``n`` over the leaf is split: the multiple of
    ``INVERSE_HALVE_ALIGN`` at or under its middle."""
    return n // 2 // INVERSE_HALVE_ALIGN * INVERSE_HALVE_ALIGN


def _whole_inverse(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``(L^-1, x^-1)`` for ``x = L L^T``: XLA's Cholesky, a triangular
    solve against the identity, and ``X^T X``."""
    chol = jnp.linalg.cholesky(x)
    eye = jnp.eye(x.shape[-1], dtype=x.dtype)
    inv_l = jax.scipy.linalg.solve_triangular(chol, eye, lower=True)
    return inv_l, inv_l.T @ inv_l


def _halved_inverse(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``(L^-1, x^-1)`` for ``x = L L^T`` by recursive halving.

    With ``x = [[A11, A21^T], [A21, A22]]`` and ``X11 = L11^-1`` from
    the first half: ``L21 = A21 X11^T``, the Schur complement
    ``S = A22 - L21 L21^T`` is ``L22 L22^T``, ``X22 = L22^-1`` from
    the second half, and ``X21 = -X22 (L21 X11)``: the factorization a
    blocked Cholesky and a blocked forward substitution compute, at the
    same ``4/3 d^3`` flops, every product a square one of half the dim
    at ``Precision.HIGHEST`` (what XLA's own expanders use for theirs).
    The inverse ``X^T X`` is put together from the halves' own
    (``X11^T X11`` and ``X22^T X22`` come back from the recursion), so
    the zero block of a triangular ``X`` is never multiplied: a third
    of the whole product's flops, at the precision it always had (the
    default: ROADMAP D10). A half that is not positive definite comes
    back NaN from its leaf's ``cholesky`` and every later product
    carries it into all four blocks. (Nothing reads the outermost
    call's ``L^-1``; under ``jit`` its assembly is dead code.)
    """
    n = x.shape[-1]
    if n <= INVERSE_HALVE_LEAF:
        return _whole_inverse(x)
    mm = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)
    h = _halving_point(n)
    x11, inv11 = _halved_inverse(x[:h, :h])
    l21 = mm(x[h:, :h], x11.T)
    x22, inv22 = _halved_inverse(x[h:, h:] - mm(l21, l21.T))
    x21 = -mm(x22, mm(l21, x11))
    low = x22.T @ x21
    inv_l = jnp.block([[x11, jnp.zeros((h, n - h), x.dtype)], [x21, x22]])
    return inv_l, jnp.block([[inv11 + x21.T @ x21, low.T], [low, inv22]])


@profiling.scope('kfac/inverse/cholesky')
def get_inverse(x: jax.Array, damping: float | jax.Array | None = None
                ) -> jax.Array:
    """Damped SPD inverse via Cholesky: ``(x + damping*I)^-1`` in fp32.

    ``L^-T L^-1`` with ``L`` the Cholesky factor — the XLA analogue of
    torch's ``cholesky_inverse(cholesky(x))``
    (kfac/layers/utils.py:76-96) — by one of two routes, chosen from
    the static dim (``inverse_is_halved``):

      - under ``INVERSE_HALVE_MIN_DIM``: XLA's ``cholesky``, a
        triangular solve against the identity, and ``X^T X``. On a TPU
        the first two walk the matrix in 128-wide strips (a custom call
        on each diagonal block and one panel product a strip, the solve
        against a *dense* identity), re-reading it strip by strip: for
        six 3073-dim matrices the compiler counts 12.7 GB accessed and
        3.0 GB of temporaries, and the chip takes 4.5 ms a matrix
        (1.6 at 2048 dims, 25 at 6144) where the flops need 1.2.
      - from it up: the same factorization by recursive halving
        (:func:`_halved_inverse`): square ``Precision.HIGHEST``
        products of half the dim in place of the strips, XLA's pair
        only on leaves of at most ``INVERSE_HALVE_LEAF``, and ``X^T X``
        assembled from the halves' own. 2.9 ms a 3073-dim matrix, 1.3
        at 2048, 15.4 at 6144 (one v5e, the function alone:
        ``benchmarks/inverse_forms.py``; PERF.md section 6, PR 32), to
        the same 2e-6 relative distance from the whole route's result
        as float32 summation order gives on the CPU.

    Same operator and precisions on both: float32 throughout, the
    final product at the default matmul precision (ROADMAP D10), a
    matrix that is not positive definite comes back non-finite. One
    matrix a call: ``damped_inverse_stack`` vmaps it and counts the
    matrices by route.
    """
    x = x.astype(jnp.float32)
    if damping is not None:
        x = x + damping * jnp.eye(x.shape[-1], dtype=x.dtype)
    route = (_halved_inverse if inverse_is_halved(x.shape[-1])
             else _whole_inverse)
    return route(x)[1]


@profiling.scope('kfac/inverse/newton')
def newton_schulz_inverse(x: jax.Array,
                          damping: float | jax.Array | None = None,
                          iters: int = 100,
                          tol: float = 1e-5) -> jax.Array:
    """Damped SPD inverse via Newton–Schulz (Hotelling–Bodewig) iteration.

    ``X_{k+1} = X_k (2I - M X_k)`` with ``M = x + damping*I`` and
    ``X_0 = I / ||M||_inf``. Matmul-only — every FLOP lands on the MXU,
    unlike the partly-sequential Cholesky/eigh factorizations. The error
    squares each step, so ``~log2(cond(M)) + 6`` iterations suffice
    (cond <= ||M||_inf/damping); the loop exits early once the residual
    ``max|M X - I|`` drops below ``tol``, with ``iters`` as the hard cap
    for pathologically-conditioned inputs.

    The same trick production TPU second-order optimizers use for inverse
    matrix roots (distributed Shampoo's coupled Newton iteration); for
    K-FAC only the plain inverse is needed. Semantically interchangeable
    with :func:`get_inverse` (the reference's damped Cholesky inverse,
    kfac/layers/utils.py:76-96) — same operator, different algorithm.
    """
    x = x.astype(jnp.float32)
    n = x.shape[-1]
    eye = jnp.eye(n, dtype=jnp.float32)
    m = x if damping is None else x + damping * eye
    bound = jnp.maximum(jnp.max(jnp.sum(jnp.abs(m), axis=-1)), 1e-30)
    x0 = eye / bound
    # Full fp32 matmul precision: with the TPU default (bf16 passes) the
    # iteration stalls at a ~1e-1 residual floor once ||X|| ~ 1/damping.
    mm = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)

    def cond_fn(state):
        k, _, res = state
        return jnp.logical_and(k < iters, res > tol)

    def body(state):
        k, xk, _ = state
        y = mm(m, xk)
        res = jnp.max(jnp.abs(y - eye))  # residual of xk, costs O(n^2)
        return k + 1, 2.0 * xk - mm(xk, y), res

    _, out, _ = jax.lax.while_loop(
        cond_fn, body, (jnp.zeros((), jnp.int32), x0,
                        jnp.full((), jnp.inf, jnp.float32)))
    return out


# Largest fp32 sub-stack one batched damped inverse works on at a time.
# The factorization, its triangular inverse and their product are each
# a temporary of the sub-stack's size, so a bucket taken whole costs
# several times its own bytes in HBM on top of the resident state: the
# xl LM's 18 x 4096^2 bucket is 1.2 GB in fp32 and does not fit a 16 GB
# chip that way (PERF.md, PR 21). 256 MiB is four 4096^2 matrices.
INVERSE_SUBSTACK_BYTES = 256 << 20


def damped_inverse_stack(stack: jax.Array, damping, method: str,
                         iters: int = 100, out_dtype=None) -> jax.Array:
    """Shared newton/cholesky dispatch for a same-size factor stack.

    Single point of truth for the single-device bucketed path
    (preconditioner.KFAC._bucketed_inverse) and the SPMD path
    (parallel.distributed._spmd_update_inverses), so algorithm changes
    stay in lockstep across both.

    A stack whose fp32 size exceeds ``INVERSE_SUBSTACK_BYTES`` runs as
    a ``lax.map`` over sub-stacks of at most that size — the count
    follows from the stack's shape — so the solver's temporaries are
    bounded by the budget, not by the bucket. Each sub-stack is upcast
    from, and its inverses cast to ``out_dtype`` (default fp32), inside
    the map, so neither a whole-bucket fp32 input nor output exists.

    The sub-stacks are EQUAL, at least two, and all under the one loop
    (the stack is padded with identities to a multiple, fewer than one
    a sub-stack). Full batches and a remainder of another size would be
    a second solver unrolled beside the loop, and a single full batch
    no loop at all (XLA unrolls a loop of one trip): two unrolled
    solvers to compile and to hold. Measured on a v5e (PERF.md, PR 31):
    gpt2s's 12 x 3072 buckets as 2 x 6 under the loop against 7 and 5
    inline: fired step 259.4 -> 255.3 ms, peak HBM -0.14 GiB, the
    firing program's cold build -54 s; kanana's 25 x 2048 as 2 x 13
    against 16 and 9 inline: 44.6 against 38.1 ms a firing, build
    -27 s.

    A Cholesky stack's matrices are counted, once a traced call, on
    the recorder (``observability.tracing``) by the route their dim
    takes in :func:`get_inverse`: ``kfac/inverse/halved`` or
    ``kfac/inverse/whole`` (the padding is not counted).
    """
    def solve(sub):
        if method == 'newton':
            from distributed_kfac_pytorch_tpu.ops import pallas_kernels
            inv = pallas_kernels.batched_inverse(sub, damping, iters=iters)
        else:
            inv = jax.vmap(
                lambda m: get_inverse(m, damping=damping))(sub)
        return inv if out_dtype is None else inv.astype(out_dtype)

    b, n, _ = stack.shape
    if method != 'newton':
        tracing.count('kfac/inverse/halved' if inverse_is_halved(n)
                      else 'kfac/inverse/whole', b)
    per_chunk = max(1, INVERSE_SUBSTACK_BYTES // (n * n * 4))
    if b <= per_chunk:
        return solve(stack)
    chunks = -(-b // per_chunk)
    size = -(-b // chunks)
    pad = chunks * size - b
    if pad:
        stack = jnp.concatenate([stack, jnp.broadcast_to(
            jnp.eye(n, dtype=stack.dtype), (pad, n, n))])
    out = jax.lax.map(solve, stack.reshape(chunks, size, n, n))
    return out.reshape(chunks * size, n, n)[:b]


def get_elementwise_inverse(v: jax.Array,
                            damping: float | jax.Array | None = None
                            ) -> jax.Array:
    """Reciprocal of each non-zero element (zeros stay zero).

    Used for diagonal factors (embedding A). Reference parity:
    kfac/layers/utils.py:98-105.
    """
    if damping is not None:
        v = v + damping
    return jnp.where(v != 0.0, 1.0 / jnp.where(v != 0.0, v, 1.0), 0.0)


def _precond_mm(compute_dtype):
    """(operand dtype, matmul) for a non-default precondition compute dtype.

    Mirrors the ``ops.factors.get_cov`` contract: operands are cast to
    ``compute_dtype`` while every contraction accumulates in fp32
    (``preferred_element_type``); ``float32`` additionally requests
    ``Precision.HIGHEST`` (strict fp32 — no TPU bf16 rounding of the
    inputs). Callers keep the legacy upcast-to-fp32 path for
    ``compute_dtype=None`` so the default is bit-identical to the
    pre-knob behavior.
    """
    cdt = jnp.dtype(compute_dtype)
    precision = (jax.lax.Precision.HIGHEST if cdt == jnp.float32
                 else None)
    mm = functools.partial(jnp.matmul,
                           preferred_element_type=jnp.float32,
                           precision=precision)
    return cdt, mm


def _truncated_side(q: jax.Array) -> bool:
    """Static: is this eigenbasis truncated (rectangular (n, r), r < n —
    the r19 randomized low-rank representation)?"""
    return q.shape[-1] < q.shape[-2]


@profiling.scope('kfac/precond/eigen')
def precondition_eigen(grad: jax.Array, qa: jax.Array, qg: jax.Array,
                       da: jax.Array, dg: jax.Array,
                       damping: float | jax.Array,
                       compute_dtype=None) -> jax.Array:
    """Eigenbasis preconditioning: ``QG ((QG^T grad QA) / (dG dA^T + λ)) QA^T``.

    ``grad`` is the (out_dim, in_dim[+1]) gradient matrix. Matches the
    reference's eigen path (kfac/layers/base.py:459-470), returning fp32.

    ``compute_dtype``: input dtype for the four contractions (fp32
    accumulation; see :func:`_precond_mm`). The eigenvalue quotient —
    the damping-sensitive part — always runs in fp32; only the matmul
    *operands* drop precision. ``None`` (default) keeps the legacy
    upcast-everything-to-fp32 path bit-for-bit.

    **Truncated sides** (r19): either basis may be rectangular
    ``(n, r)`` with ``r`` matching its eigenvalue vector — the
    randomized low-rank representation, whose discarded tail
    eigenvalues are 0 by convention. The joint quotient then splits
    into the captured block plus a damping-only complement:

        ``P = grad/λ + QG (C/(dG dA^T + λ) - C/λ) QA^T``,
        ``C = QG^T grad QA``

    — algebraically exact for the operator whose tail eigenvalues are
    0 (the three complement blocks all carry denominator λ), and
    full-rank correct: no gradient direction is dropped, tail
    curvature is regularized to the damping floor. All products are
    ``r``-thin (O(r d^2) per step instead of O(d^3)). A square/square
    pair keeps the historical formula bit-for-bit (the static shape
    check selects at trace time).
    """
    truncated = _truncated_side(qa) or _truncated_side(qg)
    if compute_dtype is None:
        grad = grad.astype(jnp.float32)
        v1 = qg.T @ grad @ qa
        v2 = v1 / (dg[:, None] * da[None, :] + damping)
        if not truncated:
            return qg @ v2 @ qa.T
        return grad / damping + qg @ (v2 - v1 / damping) @ qa.T
    cdt, mm = _precond_mm(compute_dtype)
    qa = qa.astype(cdt)
    qg = qg.astype(cdt)
    v1 = mm(qg.T, mm(grad.astype(cdt), qa))
    denom = (dg.astype(jnp.float32)[:, None]
             * da.astype(jnp.float32)[None, :] + damping)
    if not truncated:
        v2 = (v1 / denom).astype(cdt)
        return mm(qg, mm(v2, qa.T))
    # Complement term in fp32 (damping-sensitive), thin products in cdt.
    mid = (v1 / denom - v1 / damping).astype(cdt)
    return (grad.astype(jnp.float32) / damping
            + mm(qg, mm(mid, qa.T)))


@profiling.scope('kfac/precond/inv')
def precondition_inv(grad: jax.Array, a_inv: jax.Array,
                     g_inv: jax.Array, compute_dtype=None) -> jax.Array:
    """Inverse-method preconditioning: ``G_inv @ grad @ A_inv``.

    Reference parity: kfac/layers/base.py:472-475. With
    ``compute_dtype=jnp.bfloat16`` and bf16-stored inverses
    (``inv_dtype=jnp.bfloat16``) the casts are no-ops: the inverses are
    consumed *resident* — no fp32 upcast copy of the (dim, dim) operand
    is ever materialized, which is the bandwidth lever at LM scale
    (4096² inverse reads every step; PERF.md r6).
    """
    if compute_dtype is None:
        return g_inv @ grad.astype(jnp.float32) @ a_inv
    cdt, mm = _precond_mm(compute_dtype)
    return mm(g_inv.astype(cdt), mm(grad.astype(cdt),
                                    a_inv.astype(cdt)))


@profiling.scope('kfac/precond/diag_a')
def precondition_diag_a(grad: jax.Array, a_inv_diag: jax.Array,
                        g_inv: jax.Array, compute_dtype=None) -> jax.Array:
    """Preconditioning with a diagonal A inverse (embedding layers).

    ``(A_inv[:, None] * grad) @ G_inv`` for a (vocab, dim) gradient.
    Reference analogue: kfac/layers/embedding.py:87-99 (disabled there).
    The diagonal scale (elementwise, VPU-bound) always runs in fp32;
    ``compute_dtype`` governs the G-side contraction only.
    """
    if compute_dtype is None:
        return (a_inv_diag[:, None] * grad.astype(jnp.float32)) @ g_inv
    cdt, mm = _precond_mm(compute_dtype)
    scaled = a_inv_diag.astype(jnp.float32)[:, None] * grad.astype(
        jnp.float32)
    return mm(scaled.astype(cdt), g_inv.astype(cdt))


def eigen_side_inverse(q: jax.Array, d: jax.Array,
                       damping: float | jax.Array) -> jax.Array:
    """Per-side damped inverse from an eigendecomposition:
    ``Q diag(1/(d+λ)) Q^T`` = ``(F + λI)^{-1}`` (exact when (Q, d) is).

    Used at inverse-*firing* time to bake a mixed-method layer's eigen
    side into a dense damped inverse, so both sides of a split layer
    carry the same firing-time λ (the reference non-eigen timing
    semantics, kfac/layers/base.py:439: damping is baked at
    compute-inverses time, not read at precondition time).

    A TRUNCATED ``(n, r)`` basis (r19 low-rank) bakes the full-rank-
    correct damped inverse of the tail-zero operator:
    ``I/λ + Q diag(1/(d+λ) - 1/λ) Q^T`` — the same complement
    convention as :func:`precondition_eigen`, assembled in O(r n^2).
    """
    q = q.astype(jnp.float32)
    d = d.astype(jnp.float32)
    if _truncated_side(q):
        eye = jnp.eye(q.shape[-2], dtype=jnp.float32)
        scale = 1.0 / (d + damping) - 1.0 / damping
        return eye / damping + (q * scale[None, :]) @ q.T
    return (q * (1.0 / (d + damping))[None, :]) @ q.T


def precondition_dispatch(grad: jax.Array, entry: dict,
                          damping: float | jax.Array,
                          diag_a: jax.Array | None = None,
                          compute_dtype=None) -> jax.Array:
    """Per-layer preconditioning, dispatched on the inverse slots present.

    Single point of truth for the single-chip and SPMD preconditioners
    under per-dim inverse dispatch (``inverse_method='auto'``):

      - both sides eigen (``QA``/``dA``/``QG``/``dG``, no baked
        inverses): the reference eigen path with *joint* damping
        ``1/(dG dA^T + λ)`` read at precondition time
        (kfac/layers/base.py:459-470 — λ is the live scheduled value,
        like the reference's);
      - any baked inverse present: ``G_inv @ grad @ A_inv``
        (kfac/layers/base.py:472-475). Mixed-method layers carry a
        firing-time-baked dense inverse for their eigen side too
        (:func:`eigen_side_inverse`, computed in the inverse update),
        so BOTH sides of a split layer use the same firing-time λ —
        the reference non-eigen timing semantics — and the per-step
        eigen-side reconstruction cost is gone. Damping-semantics
        note: PARITY.md.

    ``diag_a``: diagonal A inverse for embedding layers (elementwise,
    damping already baked) — then ``entry`` carries only the G side.

    ``compute_dtype``: operand dtype for the precondition contractions
    (``KFAC.precond_compute_dtype``), threaded through every branch so
    ``auto`` mixed-method layers cannot drift: ``None`` = the legacy
    fp32-upcast path (bit-identical default), ``jnp.bfloat16`` = bf16
    operands with fp32 accumulation (the MXU fast path; bf16-stored
    inverses are consumed resident, no upcast copy), ``jnp.float32`` =
    strict fp32 (``Precision.HIGHEST``).
    """
    if diag_a is not None:
        if 'G_inv' in entry:
            return precondition_diag_a(grad, diag_a, entry['G_inv'],
                                       compute_dtype=compute_dtype)
        with profiling.annotate('kfac/precond/diag_a_eigen'):
            # Truncated QG (r19): the G side serves the tail-zero
            # damped operator grad/λ + grad QG (1/(dG+λ) - 1/λ) QG^T —
            # same complement convention as precondition_eigen.
            truncated = _truncated_side(entry['QG'])
            if compute_dtype is None:
                v1 = grad.astype(jnp.float32) @ entry['QG']
                v2 = v1 / (entry['dG'][None, :] + damping)
                if truncated:
                    return diag_a[:, None] * (
                        grad.astype(jnp.float32) / damping
                        + (v2 - v1 / damping) @ entry['QG'].T)
                return diag_a[:, None] * (v2 @ entry['QG'].T)
            cdt, mm = _precond_mm(compute_dtype)
            qg = entry['QG'].astype(cdt)
            v1 = mm(grad.astype(cdt), qg)
            v2 = v1 / (entry['dG'].astype(jnp.float32)[None, :] + damping)
            if truncated:
                mid = (v2 - v1 / damping).astype(cdt)
                return diag_a.astype(jnp.float32)[:, None] * (
                    grad.astype(jnp.float32) / damping + mm(mid, qg.T))
            return diag_a.astype(jnp.float32)[:, None] * mm(
                v2.astype(cdt), qg.T)
    a_baked = 'A_inv' in entry
    g_baked = 'G_inv' in entry
    if not a_baked and not g_baked:
        return precondition_eigen(grad, entry['QA'], entry['QG'],
                                  entry['dA'], entry['dG'], damping,
                                  compute_dtype=compute_dtype)
    return precondition_inv(grad, entry['A_inv'], entry['G_inv'],
                            compute_dtype=compute_dtype)
