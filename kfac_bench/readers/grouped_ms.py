"""Device time a step spends under the given ``jax.named_scope``
fragments (as ``scope_ms``) together with XLA's grouped-matmul kernels
of one class, in milliseconds.

``jax.lax.ragged_dot`` and ``ragged_dot_general`` reach a TPU as custom
calls that XLA's own rewriter makes (``%ragged-dot-none.N``), and the
rewriter keeps no ``op_name``: the kernels run under no scope (compiled
for a described v5e, PR 27), so ``scope_ms`` cannot see them. They are
told apart by what they produce, which an event's name (its HLO
instruction's text) shows:

- ``covariances``: a float32 ``[experts, d, d]`` stack, square in its
  last two dims: the per-expert statistics of ``ops.factors``
  (``kfac/factors/experts_a|g``);
- ``products``: every other one: a stacked-expert layer's forward
  product, its input gradient (``[rows, d]``) and its weight gradient
  (``[experts, in, out]``, in != out). A stacked matrix with in == out
  would be counted with the covariances: none of the benchmark's is.

The time is the union of the matching events' intervals over the traced
window's steps. Nothing where the trace holds neither such a scope nor
such a kernel.
"""

import re

from kfac_bench import trace_reduce

KERNEL = re.compile(r'^%?ragged-dot-none[\w.\-]* = (\w+)\[([\d,]*)\]')


def kernel_class(event_name: str) -> str | None:
    """'covariances', 'products' or None for an event's name."""
    found = KERNEL.match(event_name)
    if found is None:
        return None
    dims = found.group(2).split(',')
    square = len(dims) == 3 and dims[1] == dims[2]
    return ('covariances' if square and found.group(1) == 'f32'
            else 'products')


def read(run, scopes, kernels):
    if kernels not in ('covariances', 'products'):
        raise ValueError(f"kernels is 'covariances' or 'products', "
                         f'not {kernels!r}')
    if run['trace'] is None or not run['steps']:
        return None
    planes = [p for p in run['trace']['device'].values() if p]
    total = 0
    for events in planes:
        total += sum(end - start for start, end in trace_reduce._union(
            (start, start + dur) for name, start, dur, scope in events
            if any(f in scope for f in scopes)
            or kernel_class(name) == kernels))
    seconds = total / 1e9 / max(len(planes), 1)
    return seconds * 1e3 / run['steps'] if seconds > 0 else None
