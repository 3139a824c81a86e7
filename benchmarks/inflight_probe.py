#!/usr/bin/env python3
"""How many device executions the host may queue behind a running
program before a dispatch blocks.

    chiprun --chips 1 -- python3 benchmarks/inflight_probe.py

The epoch loop used to keep its running means with two eager device
operations a metric a step, each queued behind the step still running
(PERF.md, PR 28). This starts one long program (~0.3 s of matmuls),
then issues ``N`` small executions that read its result and times each
dispatch on the host: eager scalar operations as ``Metric.update``
made them, one jitted sum over a 40-key dict as ``RunningMeans`` makes
it, and ``copy_to_host_async`` as the sink asks for its copies. Prints
one JSON line a row: the dispatches' total, the first one that took
over 5 ms (the host blocked there) and how long the busy program ran.
It needs a TPU: a CPU client runs eager operations inline.
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp

BLOCKED_MS = 5.0


@jax.jit
def busy(x):
    def body(_, a):
        a = a @ a
        return a / (jnp.max(jnp.abs(a)).astype(a.dtype) + 1)
    y = jax.lax.fori_loop(0, 400, body, x)
    return y, [jnp.sum(y[i]).astype(jnp.float32) for i in range(40)]


@jax.jit
def add_all(sums, values):
    return jax.tree.map(jnp.add, sums, values)


def probe(name: str, x, issue, n: int) -> None:
    """``issue(i, scalars)`` makes one dispatch; ``scalars`` are the
    running program's 40 outputs."""
    jax.block_until_ready(busy(x))
    t_busy = time.perf_counter()
    _, scalars = busy(x)
    took = []
    for i in range(n):
        t0 = time.perf_counter()
        issue(i, scalars)
        took.append((time.perf_counter() - t0) * 1e3)
    queued_ms = (time.perf_counter() - t_busy) * 1e3
    jax.block_until_ready(scalars)
    busy_ms = (time.perf_counter() - t_busy) * 1e3
    first = next((i for i, ms in enumerate(took) if ms > BLOCKED_MS), None)
    row = {'probe': name, 'dispatches': n,
           'dispatches_total_ms': round(sum(took), 3),
           'median_ms': round(sorted(took)[n // 2], 4),
           'first_over_5ms': first,
           'its_ms': None if first is None else round(took[first], 3),
           'host_done_after_ms': round(queued_ms, 3),
           'busy_program_ms': round(busy_ms, 3)}
    print(json.dumps(row), flush=True)


def main() -> int:
    if jax.default_backend() != 'tpu':
        print('needs a TPU', file=sys.stderr)
        return 2
    x = jax.random.normal(jax.random.PRNGKey(0), (4096, 4096),
                          jnp.bfloat16)
    state = {'sum': jnp.float32(0)}

    def eager_metric_update(i, scalars):
        # Metric.update: ``self._sum + value * n``, two executions.
        state['sum'] = state['sum'] + scalars[i % 40] * 1.0

    for n in (10, 20, 33, 60, 120):      # 2 n executions
        probe(f'eager_update_x{n}', x, eager_metric_update, n)

    sums = {'s': {str(i): jnp.float32(0) for i in range(40)}}
    jax.block_until_ready(add_all(
        sums['s'], {str(i): jnp.float32(1) for i in range(40)}))

    def jitted_sum(i, scalars):
        sums['s'] = add_all(sums['s'],
                            {str(j): v for j, v in enumerate(scalars)})

    probe('one_jitted_sum_of_40', x, jitted_sum, 1)
    probe('jitted_sum_of_40_x60', x, jitted_sum, 60)
    probe('copy_to_host_async_x40', x,
          lambda i, scalars: scalars[i].copy_to_host_async(), 40)

    # What the same eager executions cost with nothing running.
    ready = jax.block_until_ready(busy(x))[1]
    t0 = time.perf_counter()
    for i in range(66):
        state['sum'] = state['sum'] + ready[i % 40] * 1.0
    print(json.dumps({'probe': 'eager_update_x66_device_idle',
                      'dispatches_total_ms': round(
                          (time.perf_counter() - t0) * 1e3, 3)}),
          flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
