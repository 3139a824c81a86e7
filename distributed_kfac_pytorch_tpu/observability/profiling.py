"""Profiler scopes for the K-FAC hot paths.

Two complementary mechanisms behind one ``annotate(name)`` context
manager:

  - ``jax.named_scope``: prefixes the HLO metadata of every op traced
    under it, so an XLA profile (``jax.profiler.start_trace`` /
    TensorBoard) attributes device time inside the ONE jitted train
    step to named K-FAC stages (``kfac/factors/...``,
    ``kfac/precond/...``, ``kfac/comm/...``). Pure metadata: the
    compiled program is numerically and structurally identical, so the
    scopes are always on — no knob, no bit-identity risk.
  - ``jax.profiler.TraceAnnotation``: a host-timeline range for the
    eager/dispatch side (visible in the profiler's python/host lanes).

Scope-name convention (what shows up in the profile):

  kfac/factors/<layer-kind>   covariance contraction per layer kind
  kfac/eigh/<method>          bucketed eigendecompositions
  kfac/inverse/<method>       bucketed damped inverses
  kfac/precond/<branch>       precondition_dispatch branches
  kfac/comm/<collective>      factor pmean / inverse all_gather /
                              gradient psum (COMM_OPT & KAISA paths)

``start_trace``/``stop_trace`` wrap ``jax.profiler`` with rank gating
and idempotence so the example CLIs can expose a bare ``--profile-dir``
flag (capture one epoch, rank 0 only).

Caveat (measured, PERF.md r7): after a profiler session, a small
per-dispatch overhead persists in the process even once the trace is
stopped — take steady-state timing numbers from a run WITHOUT
``--profile-dir``, and keep A/B rows all-profiled or all-unprofiled.
"""

from __future__ import annotations

import contextlib
import functools

import jax


def annotate(name: str):
    """Combined XLA named scope + host trace annotation for one stage.

    Usable around traced (in-jit) and eager code alike; cheap enough to
    leave on unconditionally (metadata only — never changes numerics or
    program structure).
    """
    stack = contextlib.ExitStack()
    stack.enter_context(jax.named_scope(name))
    stack.enter_context(jax.profiler.TraceAnnotation(name))
    return stack


def scope(name: str):
    """Decorator form of :func:`annotate` (wraps the whole function).

    Used on the hot-path stage functions (factor contractions,
    precondition branches, SPMD pipeline stages) so their ops carry the
    stage name into XLA profiles without reindenting the bodies.
    """
    def decorator(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with annotate(name):
                return fn(*args, **kwargs)
        return wrapper
    return decorator


_ACTIVE_TRACE_DIR: str | None = None


def start_trace(log_dir: str, *, process_index: int | None = None) -> bool:
    """Start an XLA profiler trace into ``log_dir`` (rank-0 gated).

    Returns True when a trace actually started. Idempotent: a second
    call while a trace is active is a no-op (the CLIs call this at the
    top of the profiled epoch without tracking state themselves).
    """
    global _ACTIVE_TRACE_DIR
    if _ACTIVE_TRACE_DIR is not None:
        return False
    if process_index is None:
        process_index = jax.process_index()
    if process_index != 0:
        return False
    jax.profiler.start_trace(log_dir)
    _ACTIVE_TRACE_DIR = log_dir
    return True


def stop_trace() -> str | None:
    """Stop the active profiler trace; returns its dir (None if none).

    Blocks on outstanding device work first (a fresh computation is
    enqueued behind everything already dispatched on the default
    device's in-order stream, plus an effects barrier) so the captured
    window contains the complete steps dispatched inside it — without
    this, async dispatch truncates the tail steps from the capture.
    """
    global _ACTIVE_TRACE_DIR
    if _ACTIVE_TRACE_DIR is None:
        return None
    out = _ACTIVE_TRACE_DIR
    try:
        import jax.numpy as jnp
        jax.block_until_ready(jnp.zeros(()) + 0)
        jax.effects_barrier()
    except Exception:
        pass  # best-effort: never lose the capture over the barrier
    jax.profiler.stop_trace()
    _ACTIVE_TRACE_DIR = None
    return out
