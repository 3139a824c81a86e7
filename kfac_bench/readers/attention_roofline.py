"""The attention calls' share of their roofline: the least time the
chip could take for the work a step's attention must do (the family's
``counters()['attention_work']``: FLOPs and bytes, forward and
backward), the larger of FLOPs over the bf16 peak and bytes over the
HBM bandwidth, over the device time a step spends under the given
``jax.named_scope`` fragments, in percent. Nothing where the family
reports no such work or the trace ties no operation to the scopes."""

from kfac_bench import trace_reduce


def read(run, scopes):
    work = (run.get('counters') or {}).get('attention_work')
    if (not work or run['trace'] is None or not run['steps']
            or run['peaks'] is None):
        return None
    seconds = trace_reduce.scope_seconds(run['trace'], scopes)
    if seconds <= 0:
        return None
    peaks = run['peaks']
    bound = max(work['flops'] / peaks['bf16_flops'],
                work['bytes'] / peaks['hbm_bytes_per_s'])
    return 100.0 * bound * run['steps'] / seconds
