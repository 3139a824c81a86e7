"""Device time a step spends in the operations under the given
``jax.named_scope`` fragments: their summed duration in the traced
window over its steps, in milliseconds. Nothing where the trace ties no
operation to the scopes."""

from kfac_bench import trace_reduce


def read(run, scopes):
    if run['trace'] is None or not run['steps']:
        return None
    seconds = trace_reduce.scope_seconds(run['trace'], scopes)
    return seconds * 1e3 / run['steps'] if seconds > 0 else None
