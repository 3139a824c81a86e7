#!/usr/bin/env python3
"""The benchmark's command.

    python3 kfac_bench/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

One process, one cell, one run: builds the cell's program from its
family, warms up whole cadence periods (the first steps are the ones the
plain reference follows), measures whole periods for ``--seconds`` with a
lag-one clock, reads the device's memory, frees the program, runs the
reference over the same first steps, compares, and prints one JSON
object as the last line of standard output.

This file names no cell, configuration, family or metric. It finds
them by the names in ``BENCHMARK.json``:

    configs/<configuration>.json   sizes, dtypes, family (BENCHMARK.json
                                   gives the path)
    traffic/<traffic>.json         what a step is fed and on what cadence
    limits/<cell>.json             the comparison's limits for the cell
    families/<family>.py           builds the cell (program and reference)
    end_to_end/<metric>.json       a metric: its reader and parameters
    layer_metrics/<metric>.json    likewise, read in the --trace 1 run
    readers/<reader>.py            read(run, **parameters) -> number|None
    peaks.json                     the chips' peaks, by device_kind

It measures the device or nothing: with no TPU, or fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


METRIC_DIRS = {'end_to_end': 'end_to_end', 'per_layer': 'layer_metrics'}


class SpecError(Exception):
    """A name that BENCHMARK.json or a data file gives finds nothing."""


def _listing(directory: str, suffix: str) -> list[str]:
    if not os.path.isdir(directory):
        return []
    return sorted(f[:-len(suffix)] for f in os.listdir(directory)
                  if f.endswith(suffix) and not f.startswith('_'))


def load_json(kind: str, name: str, directory: str | None = None) -> dict:
    """``<directory or kind>/<name>.json`` of the benchmark; an unknown
    name fails with the list of those found."""
    directory = os.path.join(BENCH, directory or kind)
    path = os.path.join(directory, f'{name}.json')
    if not os.path.isfile(path):
        raise SpecError(f'unknown {kind} {name!r}; found '
                        f'{_listing(directory, ".json")}')
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, directory: str):
    found = _listing(os.path.join(BENCH, directory), '.py')
    if name not in found:
        raise SpecError(f'unknown {kind} {name!r}; found {found}')
    return importlib.import_module(f'kfac_bench.{directory}.{name}')


def load_cell_spec(spec: dict, workload: str) -> dict:
    """Everything the data files say about one cell."""
    cells = {w['name']: w for w in spec['workloads']}
    if workload not in cells:
        raise SpecError(f'unknown workload {workload!r}; found '
                        f'{sorted(cells)}')
    cell = cells[workload]
    configs = {c['name']: c for c in spec['configs']}
    if cell['config'] not in configs:
        raise SpecError(f'unknown configuration {cell["config"]!r}; '
                        f'found {sorted(configs)}')
    path = os.path.join(ROOT, configs[cell['config']]['file'])
    if not os.path.isfile(path):
        raise SpecError(
            f'configuration file {configs[cell["config"]]["file"]} is '
            f'missing; found {_listing(os.path.dirname(path), ".json")}')
    with open(path) as f:
        config = json.load(f)

    def metrics(kind):
        out = {}
        for m in spec[kind]:
            if workload in m.get('workloads', [workload]):
                out[m['name']] = {
                    **load_json(f'{kind} metric', m['name'],
                                METRIC_DIRS[kind]), 'unit': m['unit']}
        return out

    return {'name': workload, 'chips': cell['chips'], 'config': config,
            'traffic': load_json('traffic', cell['traffic']),
            'limits': load_json('limits', workload),
            'end_to_end': metrics('end_to_end'),
            'per_layer': metrics('per_layer')}


class BuildCounter:
    """Counts the executables JAX builds (compiled, or loaded from the
    persistent cache) through its monitoring events. Copied from
    ``chip_smoke._BuildCounter``."""

    def __init__(self, jax):
        self.builds = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if event == '/jax/core/compile/backend_compile_duration':
            self.builds += 1


class LagOneClock:
    """Completion times of every step, without draining the device:
    dispatch step k, then wait for step k-1. The wait is on a scalar
    the step returns, which is ready when its program has finished."""

    def __init__(self, jax, cell, counter):
        self.jax, self.cell, self.counter = jax, cell, counter
        self.calls = 0
        self.pending = None          # (loss, stage) of the last dispatch
        self.timing = False
        self.done: list[float] = []  # completion times in the window
        self.stages: list[str] = []
        self.dispatch_s: list[float] = []
        self.losses: list = []
        self.t_open = self.t_close = None
        self.builds_open = self.traces_open = 0
        self.builds = self.traces = 0

    def _traces(self):
        return sum(self.cell.step_fn.trace_counts.values())

    def wrap(self, step_fn):
        annotate = self.jax.profiler.TraceAnnotation

        @functools.wraps(step_fn)
        def clocked(*args, **flags):
            with annotate('bench/dispatch'):
                t0 = time.perf_counter()
                out = step_fn(*args, **flags)
                dt = time.perf_counter() - t0
            index, self.calls = self.calls, self.calls + 1
            if index < self.cell.check_steps:
                self.cell.after_step(index, out)
            with annotate('bench/wait_previous_step'):
                self._finish_pending()
            self.pending = (out[4]['loss'], self.cell.stage_of(flags))
            if self.timing:
                self.dispatch_s.append(dt)
            return out

        return clocked

    def _finish_pending(self):
        if self.pending is None:
            return
        loss, stage = self.pending
        self.jax.block_until_ready(loss)
        if self.timing:
            self.done.append(time.perf_counter())
            self.stages.append(stage)
            self.losses.append(loss)
        self.pending = None

    def open(self):
        self._finish_pending()
        self.builds_open, self.traces_open = (self.counter.builds,
                                              self._traces())
        self.timing = True
        self.t_open = time.perf_counter()

    def close(self):
        self._finish_pending()
        self.t_close = time.perf_counter()
        self.timing = False
        self.builds = self.counter.builds - self.builds_open
        self.traces = self._traces() - self.traces_open

    def intervals_ms(self) -> list[float]:
        edges = [self.t_open, *self.done]
        return [(b - a) * 1e3 for a, b in zip(edges, edges[1:])]


class GcLog:
    """Every collection Python's cycle collector makes in a run: its
    generation, its seconds and, once a window is open, how many steps
    had completed. A full collection of this process's heap takes
    tenths of a second, longer than a step, so one that falls into a
    window drains the device. Set-up therefore ends with one full
    collection and freezes what survives; this log shows how long the
    full collections of set-up took and what is left in the window."""

    def __init__(self):
        self.clock, self.events, self._t0 = None, [], 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == 'start':
            self._t0 = time.perf_counter()
            return
        timing = self.clock is not None and self.clock.timing
        self.events.append([info['generation'],
                            time.perf_counter() - self._t0, timing,
                            len(self.clock.done) if timing else None])

    def close(self) -> dict:
        if self._on in gc.callbacks:
            gc.callbacks.remove(self._on)
        self.clock = None
        full = sorted(e[1] for e in self.events if e[0] == 2 and not e[2])
        window = [e for e in self.events if e[2]]
        return {'setup_full_collections_s': full,
                'window_collections': len(window),
                'window_seconds': sum(e[1] for e in window),
                'window_longest': sorted(
                    ([e[0], e[1], e[3]] for e in window),
                    key=lambda e: -e[1])[:3]}


def device_peak_bytes(jax) -> int | None:
    peaks = [(d.memory_stats() or {}).get('peak_bytes_in_use')
             for d in jax.local_devices()]
    return None if any(p is None for p in peaks) else max(peaks)


def chip_peaks(kind: str) -> dict:
    with open(os.path.join(BENCH, 'peaks.json')) as f:
        table = json.load(f)['device_kinds']
    if kind not in table:
        raise SpecError(f'unknown device_kind {kind!r}: add it to '
                        f'kfac_bench/peaks.json with its source; found '
                        f'{sorted(table)}')
    return table[kind]


def read_metrics(wanted: dict, run: dict) -> dict:
    out = {}
    for name, entry in wanted.items():
        reader = load_module('reader', entry['reader'], 'readers')
        value = reader.read(run, **entry.get('parameters', {}))
        if value is not None:
            if not math.isfinite(value):
                raise ValueError(f'metric {name} read {value}')
            out[name] = {'value': value, 'unit': entry['unit']}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             spec_path: str | None = None, require_chip: bool = True,
             sabotage=None) -> tuple[int, dict | None]:
    """One run. Returns ``(exit code, result)``; the result is what the
    last line of standard output holds, None where none is due.

    ``require_chip=False`` and ``sabotage`` exist for the tests under
    ``kfac_bench/tests``: the first lets the rest of a run be driven
    where there is no TPU (the result then carries no metric: a device
    metric is never reported from another platform), the second gets
    the built cell before its first step, to break the timed path
    underneath. The command line reaches neither."""
    with open(spec_path or os.path.join(ROOT, 'BENCHMARK.json')) as f:
        spec = json.load(f)
    cell_spec = load_cell_spec(spec, workload)
    family = load_module('family', cell_spec['config']['family'],
                         'families')
    for entry in (*cell_spec['end_to_end'].values(),
                  *cell_spec['per_layer'].values()):
        load_module('reader', entry['reader'], 'readers')

    import jax

    devices = jax.devices()
    on_chip = devices[0].platform == 'tpu'
    if require_chip and (not on_chip
                         or len(devices) < cell_spec['chips']):
        print(f'kfac_bench: cell {workload} needs {cell_spec["chips"]} '
              f'TPU chip(s); JAX reports {len(devices)} x '
              f'{devices[0].platform}. This benchmark runs on the chip '
              'or not at all.', file=sys.stderr)
        return 2, None
    device = {'platform': devices[0].platform,
              'kind': devices[0].device_kind, 'count': len(devices)}
    peaks = chip_peaks(device['kind']) if on_chip else None

    from distributed_kfac_pytorch_tpu.utils import (
        enable_compilation_cache,
    )
    cache_dir = enable_compilation_cache() if on_chip else None
    counter = BuildCounter(jax)
    work_dir = tempfile.mkdtemp(prefix='kfac_bench_')
    gc_log = GcLog()
    try:
        return _measure(jax, family, cell_spec, seed, seconds, trace,
                        device, peaks, counter, work_dir, cache_dir,
                        sabotage, gc_log)
    finally:
        gc_log.close()
        shutil.rmtree(work_dir, ignore_errors=True)


def _measure(jax, family, cell_spec, seed, seconds, trace, device, peaks,
             counter, work_dir, cache_dir, sabotage, gc_log):
    traffic = cell_spec['traffic']
    on_chip = peaks is not None
    phases = {'imports_s': time.perf_counter() - T_START}
    cell = family.build(cell_spec['config'], traffic, seed,
                        cell_spec['chips'], work_dir)
    if sabotage is not None:
        sabotage(cell)
    phases['build_s'] = time.perf_counter() - T_START
    clock = LagOneClock(jax, cell, counter)
    step_fn = clock.wrap(cell.step_fn)
    annotate = jax.profiler.TraceAnnotation

    def warm_up():
        cell.before_first_step()
        for _ in range(traffic['warmup_periods'] * cell.period):
            yield cell.next_batch()

    cell.drive(step_fn, warm_up())

    periods_cap = traffic['trace_periods'] if trace else math.inf
    trace_dir = os.path.join(work_dir, 'trace')

    def window():
        if trace:
            jax.profiler.start_trace(trace_dir)
        clock.open()
        periods = 0
        while True:
            for _ in range(cell.period):
                with annotate('bench/next_batch'):
                    batch = cell.next_batch()
                yield batch
            periods += 1
            if (periods >= periods_cap
                    or time.perf_counter() - clock.t_open >= seconds):
                break
        clock.close()
        if trace:
            jax.profiler.stop_trace()

    phases['warm_up_s'] = time.perf_counter() - T_START
    gc.collect()
    gc.freeze()
    gc_log.clock = clock
    setup_s = time.perf_counter() - T_START
    phases['gc_collect_s'] = setup_s - phases['warm_up_s']
    cell.drive(step_fn, window())
    gc_summary = gc_log.close()
    gc.unfreeze()

    peak_bytes = device_peak_bytes(jax)
    steps = len(clock.done)
    window_s = clock.t_close - clock.t_open
    losses = [float(x) for x in jax.device_get(clock.losses)]
    counters = cell.counters()
    run = {
        'setup_s': setup_s, 'window_s': window_s, 'steps': steps,
        'period': cell.period, 'intervals_ms': clock.intervals_ms(),
        'stages': clock.stages,
        'dispatch_ms': [d * 1e3 for d in clock.dispatch_s],
        'samples_per_step': cell.samples_per_step,
        'flops_per_step': cell.flops_per_step,
        'chips': cell_spec['chips'], 'peaks': peaks,
        'peak_bytes': peak_bytes, 'builds_in_window': clock.builds,
        'traces_in_window': clock.traces, 'counters': counters,
        'trace': None, 'reduced': None}
    failed = sum(not math.isfinite(x) for x in losses)

    from kfac_bench import reference, trace_reduce
    if trace:
        run['trace'] = trace_reduce.load(
            trace_reduce.find_xplane(trace_dir))
        run['reduced'] = trace_reduce.reduce(run['trace'])
        device['busy_s'] = run['reduced']['busy_s']
        device['window_s'] = window_s
    device['memory_peak_bytes'] = peak_bytes

    stage_medians = _medians(run)
    slowest = _slowest_steps(run, stage_medians)
    reduced = run['reduced']
    metrics = {}
    if on_chip:
        metrics = read_metrics(
            cell_spec['per_layer' if trace else 'end_to_end'], run)

    # The program's state goes; the plain reference follows the same
    # first steps from the same seed, and the comparison decides.
    observed = cell.observed
    builds_in_window, traces_in_window = clock.builds, clock.traces
    clock = step_fn = run = None  # they hold the loaded programs
    cell.free()
    in_use_after_free = [(d.memory_stats() or {}).get('bytes_in_use')
                         for d in jax.local_devices()]
    t_ref = time.perf_counter()
    expected = cell.reference_run()
    checks = reference.compare(observed, expected, cell_spec['limits'])
    reference_s = time.perf_counter() - t_ref
    # The program's own count of factor updates it found non-finite,
    # over warm-up and window: a run that went non-finite is no run.
    skips = counters.get('nonfinite_skips')
    skips = math.inf if skips is None else float(skips)
    checks['nonfinite_factor_updates'] = {
        'value': skips, 'limit': 0.0, 'ok': skips == 0}
    checks['nonfinite_window_losses'] = {
        'value': float(failed), 'limit': 0.0, 'ok': failed == 0}
    correct = all(c['ok'] for c in checks.values())

    result = {'correct': correct, 'attempted': steps, 'failed': failed,
              'metrics': metrics, 'device': device}
    if trace and reduced['events']:
        result['breakdown'] = {'device_ops': reduced['device_ops'],
                               'idle_gaps': reduced['idle_gaps']}
    if not on_chip:
        result['not_measured'] = (
            f'platform {device["platform"]}: no device metric is '
            'reported from anything but a TPU')
    result['info'] = {
        'workload': cell_spec['name'], 'seed': seed,
        'window_s': window_s, 'setup_s': setup_s, 'phases_s': phases,
        'reference_s': reference_s,
        'window_losses_first_last': losses[:1] + losses[-1:], 'cache_dir': cache_dir,
        'builds_in_window': builds_in_window,
        'traces_in_window': traces_in_window,
        'bytes_in_use_before_reference': in_use_after_free,
        'first_call_ms': counters.get('first_call_ms'),
        'trace_counts': counters.get('trace_counts'),
        'slowest_steps': slowest, 'gc': gc_summary,
        'first_losses': observed['losses'],
        'reference_losses': expected['losses'],
        'stage_ms_median': stage_medians}
    result['checks'] = checks
    print(f'kfac_bench {cell_spec["name"]} seed {seed}: correct='
          f'{correct}; compared (value <= limit):', file=sys.stderr)
    for name, c in checks.items():
        print(f'  {name}: {c["value"]:.6g} <= {c["limit"]} '
              f'{"ok" if c["ok"] else "FAILED"} {c.get("at", "")}',
              file=sys.stderr)
    return 0, result


def _medians(run) -> dict:
    import statistics
    by: dict[str, list[float]] = {}
    for stage, ms in zip(run['stages'], run['intervals_ms']):
        by.setdefault(stage, []).append(ms)
    return {k: statistics.median(v) for k, v in by.items()}


def _slowest_steps(run, medians, n=3) -> list:
    """The steps farthest over their stage's median: ``[index, stage,
    interval ms, excess ms, its dispatch ms]``. A stall in a window
    names itself here."""
    rows = [[i, stage, ms, ms - medians[stage], dispatch]
            for i, (stage, ms, dispatch) in enumerate(zip(
                run['stages'], run['intervals_ms'], run['dispatch_ms']))]
    return sorted(rows, key=lambda r: -r[3])[:n]


def use_own_cache() -> None:
    """The compile cache is the benchmark's: the fixed directory
    ``<checkout>/.jax_cache``, which the program's helper takes from the
    environment, and no cap on its size. A machine's own
    ``JAX_COMPILATION_CACHE_DIR`` is not used: the parent's and the
    change's checkouts would share it, and a cap below one cell's
    programs makes every run compile (the chip machines come with 192
    MiB, under which gpt2s_f1i10 never loaded a program; PERF.md)."""
    os.environ['JAX_COMPILATION_CACHE_DIR'] = os.path.join(ROOT,
                                                           '.jax_cache')
    os.environ.pop('JAX_COMPILATION_CACHE_MAX_SIZE', None)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seed', type=int, required=True)
    parser.add_argument('--seconds', type=float, required=True)
    parser.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    use_own_cache()
    try:
        code, result = run_cell(args.workload, args.seed, args.seconds,
                                bool(args.trace))
    except SpecError as e:
        print(f'kfac_bench: {e}', file=sys.stderr)
        return 2
    sys.stderr.flush()
    if result is not None:
        print(json.dumps(result), flush=True)
    return code


if __name__ == '__main__':
    sys.exit(main())
