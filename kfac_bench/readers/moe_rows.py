"""Routed rows a held expert got a step, mean over the window's steps
and the MoE layers: the program's ``moe/rows_here`` step metric (as the
family's ``counters()`` averaged it) over the experts held. Nothing
where the run counted none (a family without routed experts, a program
from before the metric)."""


def read(run):
    moe = (run.get('counters') or {}).get('moe') or {}
    if not moe.get('rows_here') or not moe.get('experts_held'):
        return None
    return moe['rows_here'] / moe['experts_held']
