"""A percentile of the completion-to-completion interval of every step
of the window (lag-one clock), in milliseconds."""

import math


def read(run, q):
    xs = sorted(run['intervals_ms'])
    if not xs:
        return None
    return xs[min(len(xs) - 1, math.ceil(q / 100.0 * len(xs)) - 1)]
