#!/usr/bin/env python3
"""Read the comparison's upper ends: the control and the planted faults.

    python3 kfac_bench/control.py --workload <cell> --seeds 1,2,3

For each seed, at the cell's own size, the plain reference is run as it
is and then in the program's place three more times: with the operands
of every contraction rounded to float8_e4m3 (the control: the nearest
precision below the configurations' bfloat16), with half of the batch
left out and the mean taken over the rest, and with every step
returning its state unchanged. Each is compared with the reference by
the cell's own comparison; one JSON line per seed and mode goes to
standard output and to ``chiprun_out/control_<cell>.jsonl``. No
program is built for these.

    python3 kfac_bench/control.py --workload <cell> \
        --program-seeds 1,2,3 [--state-dtype bfloat16]

The program itself, several seeds off one compiled step, through its
first ``check_steps`` steps and no window: as the configuration states
(the comparison's lower readings), or with its own narrower K-FAC state
switched on (``--state-dtype``: the control that the readings of the
stored factors and inverses have to fail). The benchmark's runs never
call this file.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import tempfile
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from kfac_bench import reference, run  # noqa: E402

CONTROL_DTYPE = 'float8_e4m3fn'
NARROW_STATE = 'bfloat16'     # below the configurations' float32 K-FAC state
MODES = {
    'control_' + CONTROL_DTYPE: {
        'rounding': reference.Rounding(CONTROL_DTYPE)},
    'half_batch': {'half_batch': True},
    'unchanged_state': {'unchanged_state': True},
}


def read(workload: str, seeds, spec_path=None, modes=MODES):
    """Yield one record per seed and mode."""
    with open(spec_path or os.path.join(ROOT, 'BENCHMARK.json')) as f:
        spec = json.load(f)
    cell = run.load_cell_spec(spec, workload)
    family = run.load_module('family', cell['config']['family'],
                             'families')
    args = (cell['config'], cell['traffic'])
    for seed in seeds:
        t0 = time.perf_counter()
        want = family.reference_observe(*args, seed, cell['chips'])
        base_s = time.perf_counter() - t0
        for mode, planted in modes.items():
            got = family.reference_observe(*args, seed, cell['chips'],
                                           **planted)
            checks = reference.compare(got, want, cell['limits'])
            yield {'workload': workload, 'seed': seed, 'mode': mode,
                   'correct': all(c['ok'] for c in checks.values()),
                   'reference_s': base_s, 'checks': checks}


def first_steps(cell) -> dict:
    """Drive a built cell through the steps the reference follows, by
    the window's own call and feed, and return what it observed."""
    calls = 0

    def stepped(*args, **flags):
        nonlocal calls
        out = cell.step_fn(*args, **flags)
        cell.after_step(calls, out)
        calls += 1
        return out

    def feed():
        cell.before_first_step()
        for _ in range(cell.check_steps):
            yield cell.next_batch()

    cell.drive(stepped, feed())
    return {**cell.observed,
            'losses': [float(x) for x in cell.observed['losses']]}


def read_program(workload: str, seeds, state_dtype=None, spec_path=None):
    """Yield one record per seed: the program's first steps compared
    with the reference's, all seeds off one compiled step. The
    references run once the program is freed."""
    with open(spec_path or os.path.join(ROOT, 'BENCHMARK.json')) as f:
        spec = json.load(f)
    cell = run.load_cell_spec(spec, workload)
    family = run.load_module('family', cell['config']['family'],
                             'families')
    config = dict(cell['config'])
    mode = 'program'
    if state_dtype is not None:
        config['kfac_state_dtype'] = state_dtype
        mode = f'program_state_{state_dtype}'
    built = family.build(config, cell['traffic'], seeds[0], cell['chips'],
                         tempfile.mkdtemp(prefix='kfac_bench_'))
    seen = []
    for i, seed in enumerate(seeds):
        if i:
            built.restart(seed)
        seen.append((seed, first_steps(built), list(built.checked)))
    built.free()
    for seed, got, batches in seen:
        want = family.reference_observe(cell['config'], cell['traffic'],
                                        seed, cell['chips'], batches)
        checks = reference.compare(got, want, cell['limits'])
        yield {'workload': workload, 'seed': seed, 'mode': mode,
               'correct': all(c['ok'] for c in checks.values()),
               'checks': checks}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seeds', default='')
    parser.add_argument('--program-seeds', default='')
    parser.add_argument('--narrow-state-seeds', default='')
    args = parser.parse_args(argv)
    run.use_own_cache()
    import jax
    from distributed_kfac_pytorch_tpu.utils import (
        enable_compilation_cache,
    )
    enable_compilation_cache()
    print(f'device: {jax.devices()}', file=sys.stderr)
    out_dir = os.path.join(ROOT, 'chiprun_out')
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f'control_{args.workload}.jsonl'),
              'a') as f:
        def seeds(text):
            return [int(s) for s in text.split(',') if s]
        records = [read(args.workload, seeds(args.seeds))]
        for chosen, dtype in ((args.program_seeds, None),
                              (args.narrow_state_seeds, NARROW_STATE)):
            if chosen:
                records.append(read_program(args.workload, seeds(chosen),
                                            dtype))
        for record in itertools.chain(*records):
            line = json.dumps(record)
            print(line, flush=True)
            f.write(line + '\n')
    return 0


if __name__ == '__main__':
    sys.exit(main())
