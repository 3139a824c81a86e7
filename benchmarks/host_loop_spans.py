#!/usr/bin/env python3
"""Where a benchmark cell's epoch loop spends a step on the host.

    chiprun --chips 1 -- python3 benchmarks/host_loop_spans.py \
        [--checkout DIR] --label NAME -- --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs ``kfac_bench/run.py``'s one run in this process, from ``DIR`` (the
repo's root, or a second checkout unpacked under it: the parent, for a
comparison in one call), prints the run's result line as the harness
does, and then reads the program's own recorder
(``observability.tracing``), which the harness prints nothing of: the
children of the window's ``kfac/host/step`` spans (mean, median and
largest, in ms a step, the window's first step left out as
``readers/span_ms`` does), every counter (among them the route each
traced attention call and stacked-expert product took,
``kfac/attention/*``, ``kfac/moe/grouped_pallas`` / ``grouped_xla``,
and the route each traced per-expert covariance took,
``kfac/factors/experts_cov_pallas`` / ``experts_cov_xla``), the
``kfac/build/*`` spans'
attributes and JAX's backend compiles by the span they came in (PR
36: one a variant, on a checkout's first run and on a warm one alike).
Writes them to
``chiprun_out/host_loop/NAME.json`` and to standard error.

With ``--trace 1`` it also writes the traced window's whole operation
table, which the harness cuts to ten lines (``breakdown.device_ops``):
``chiprun_out/host_loop/NAME.ops.json.gz``, one row an instruction,
``[event text (output shape first), scope, events, total ms]``, read
off the harness's own parse of the trace (``trace_reduce.load``). A
question such as "how many operations under ``kfac/factors`` write a
``f32[2048,2048]``" is one pass over it.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import statistics
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEP = 'kfac/host/step'
BUILD = 'kfac/build/'
BACKEND_COMPILE = '/jax/core/compile/backend_compile_duration'


def children_ms(spans, steps: int) -> dict:
    """``{child name: {'mean', 'median', 'max'}}`` over the newest
    ``steps`` roots less the first, with the root itself and what of it
    no child covers (``self``)."""
    roots = [s for s in spans if s.name == STEP][-steps:][1:]
    ids = {s.id: i for i, s in enumerate(roots)}
    by_name = {STEP: [(s.end_ns - s.start_ns) / 1e6 for s in roots]}
    rest = list(by_name[STEP])
    for s in spans:
        if s.parent in ids:
            ms = (s.end_ns - s.start_ns) / 1e6
            by_name.setdefault(s.name, [0.0] * len(roots))
            by_name[s.name][ids[s.parent]] += ms
            rest[ids[s.parent]] -= ms
    by_name['self'] = rest
    return {name: {'mean': statistics.fmean(v),
                   'median': statistics.median(v), 'max': max(v)}
            for name, v in by_name.items() if v}


def op_table(loaded: dict) -> list:
    """``[[event text, scope, events, total ms], ...]``, heaviest
    first, over the device planes of ``trace_reduce.load``'s result."""
    rows: dict[tuple, list] = {}
    for events in loaded['device'].values():
        for name, _start, dur_ns, scope in events:
            row = rows.setdefault((name, scope), [0, 0.0])
            row[0] += 1
            row[1] += dur_ns / 1e6
    return sorted(([name, scope, n, ms]
                   for (name, scope), (n, ms) in rows.items()),
                  key=lambda r: -r[3])


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    parser.add_argument('--checkout', default=REPO)
    parser.add_argument('--label', required=True)
    parser.add_argument('run_args', nargs=argparse.REMAINDER)
    args = parser.parse_args()
    checkout = os.path.abspath(args.checkout)
    sys.path.insert(0, checkout)
    from kfac_bench import run as bench
    # Before anything imports jax, which reads the cache's directory
    # and cap from the environment as it is imported. Both checkouts
    # of a comparison load the step's programs from the repo's cache:
    # the loop's change does not touch them.
    bench.use_own_cache()
    os.environ['JAX_COMPILATION_CACHE_DIR'] = os.path.join(REPO,
                                                           '.jax_cache')

    from distributed_kfac_pytorch_tpu.observability import tracing
    if not tracing.__file__.startswith(checkout):
        sys.exit(f'the program came from {tracing.__file__}, not from '
                 f'{checkout}')
    # Every backend compile of the run (a cache hit is one too), and
    # the span it came in: a build that compiles a variant twice shows.
    import jax
    compiles: dict[str, int] = {}

    def on_duration(event, _seconds, **_kw):
        if event == BACKEND_COMPILE:
            span = tracing.current()
            name = span.name if span else '(none)'
            compiles[name] = compiles.get(name, 0) + 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    run_args = argparse.ArgumentParser()
    run_args.add_argument('--workload', required=True)
    run_args.add_argument('--seed', type=int, required=True)
    run_args.add_argument('--seconds', type=float, required=True)
    run_args.add_argument('--trace', type=int, default=0)
    cell = run_args.parse_args([a for a in args.run_args if a != '--'])
    out_dir = os.path.join(REPO, 'chiprun_out', 'host_loop')
    os.makedirs(out_dir, exist_ok=True)
    if cell.trace:
        # The harness reduces the trace and deletes it: take the table
        # where it hands the parsed events to its own reduction.
        from kfac_bench import trace_reduce
        reduce = trace_reduce.reduce

        def reduce_and_keep(loaded, *a, **kw):
            with gzip.open(os.path.join(
                    out_dir, f'{args.label}.ops.json.gz'), 'wt') as f:
                json.dump(op_table(loaded), f)
            return reduce(loaded, *a, **kw)

        trace_reduce.reduce = reduce_and_keep
    code, result = bench.run_cell(cell.workload, cell.seed, cell.seconds,
                                  bool(cell.trace))
    if result is None:
        return code
    print(json.dumps(result), flush=True)
    read = {'label': args.label, 'checkout': checkout,
            'workload': cell.workload, 'seed': cell.seed,
            'trace': cell.trace, 'steps': result['attempted'],
            'metrics': {k: v['value']
                        for k, v in result['metrics'].items()},
            'idle_gaps': result.get('breakdown', {}).get('idle_gaps'),
            'step_children_ms': children_ms(tracing.spans(),
                                            result['attempted']),
            'counters': {k: v for k, v in tracing.counters().items()
                         if not k.startswith('kfac/state_bytes/')
                         or k.endswith(('/shared_saved', '/total'))},
            'builds': {s.name: s.attrs for s in tracing.spans()
                       if s.name.startswith(BUILD)},
            'backend_compiles': compiles}
    with open(os.path.join(out_dir, f'{args.label}.json'), 'w') as f:
        json.dump(read, f, indent=1)
    print(json.dumps({k: read[k] for k in (
        'label', 'steps', 'step_children_ms', 'counters', 'builds',
        'backend_compiles')}),
        file=sys.stderr)
    return code


if __name__ == '__main__':
    sys.exit(main())
