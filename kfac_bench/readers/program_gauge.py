"""A number the program keeps by name in its recorder
(``observability.tracing``: a gauge or a counter), times
``unit_scale``. Nothing where the program keeps no such number (a
program from before its recorder keeps none at all)."""

from distributed_kfac_pytorch_tpu.observability import tracing


def read(run, gauge, unit_scale=1.0):
    if not hasattr(tracing, 'counters'):
        return None
    value = tracing.counters().get(gauge)
    return None if value is None else value * unit_scale
