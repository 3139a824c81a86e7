"""Host time in one of the program's own spans
(``observability.tracing``), in milliseconds times ``unit_scale``.

``per='step'``: over the window's steps, which are the newest
``run['steps']`` spans named ``kfac/host/step`` (the epoch loop's root
of a step; readers run straight after the window), less the first of
them where there are more: the harness opens its clock, and in a traced
run the profiler, inside that step's ``next()`` on the batches (my chip
run, PR 25: 50 ms of a 1.7 ms wait). ``span`` is that root or a child
of it; each span counts for its duration less that of its children
named in ``minus``; the sum is divided by the steps.
``per='run'``: the summed duration of every span of the process named
``span`` or, where ``span`` ends in ``/``, under it.

Nothing where the program keeps no such span (a program from before its
recorder keeps none at all)."""

from distributed_kfac_pytorch_tpu.observability import tracing

STEP = 'kfac/host/step'


def per_step_ms(spans, steps: int, span: str, minus=()) -> float | None:
    """``spans``: span records, oldest first."""
    roots = [s for s in spans if s.name == STEP][-steps:] if steps else []
    if not roots:
        return None
    roots = roots[1:] or roots
    if span == STEP:
        targets = roots
    else:
        root_ids = {s.id for s in roots}
        targets = [s for s in spans
                   if s.name == span and s.parent in root_ids]
    if not targets:
        return None
    target_ids = {s.id for s in targets}
    total = sum(s.end_ns - s.start_ns for s in targets)
    total -= sum(s.end_ns - s.start_ns for s in spans
                 if s.name in minus and s.parent in target_ids)
    return total / len(roots) / 1e6


def per_run_ms(snapshot: dict, span: str) -> float | None:
    """``snapshot``: what ``tracing.snapshot_trace()`` gives."""
    rows = [row for name, row in snapshot.items()
            if name == span or (span.endswith('/')
                                and name.startswith(span))]
    return sum(row['total_ms'] for row in rows) if rows else None


def read(run, span, minus=(), per='step', unit_scale=1.0):
    if per not in ('step', 'run'):
        raise ValueError(f"per is 'step' or 'run', not {per!r}")
    if not hasattr(tracing, 'spans'):
        return None
    if per == 'step':
        ms = per_step_ms(tracing.spans(), run['steps'], span, minus)
    else:
        ms = per_run_ms(tracing.snapshot_trace(), span)
    return None if ms is None else ms * unit_scale
