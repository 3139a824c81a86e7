"""The share of the (query, key) pairs the attention kernel visits that
its mask keeps, in percent: ``100 x kept / visited`` from the program's
counters ``<prefix>kept_pairs`` and ``<prefix>visited_pairs``
(``parallel.sequence.local_causal_attention`` adds to them at each
traced call on the splash route under a block-diffusion mask, from the
mask's tile map at the kernel's tile size). Nothing where the program
keeps no such counters (another mask, or a program from before them)."""

from distributed_kfac_pytorch_tpu.observability import tracing


def read(run, prefix):
    if not hasattr(tracing, 'counters'):
        return None
    counters = tracing.counters()
    visited = counters.get(prefix + 'visited_pairs')
    if not visited:
        return None
    return 100.0 * counters.get(prefix + 'kept_pairs', 0.0) / visited
