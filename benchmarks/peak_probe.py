#!/usr/bin/env python3
"""Which of a benchmark cell's programs holds the device's high-water
mark of HBM, with the harness's own observations left out.

    chiprun --chips 1 -- python3 benchmarks/peak_probe.py \
        [--checkout DIR] --label NAME --workload <cell> --seed <n>

``peak_hbm_gib`` is the allocator's ``peak_bytes_in_use`` over the whole
run, set-up and the comparison's reads of the state included. This
builds the cell as ``kfac_bench/run.py`` does (from ``DIR``, with its
cache directory, so programs a run of that checkout left there load),
drives twelve steps through the cell's own entry point and nothing
else, and reads ``peak_bytes_in_use`` / ``bytes_in_use`` after each: the
peak only rises, so the step at which it last rose names the program.
Prints a line a step and writes ``chiprun_out/peak_probe/NAME.json``.
PR 31 read from it that kanana's steps peak 0.35-0.73 GiB under the
harness's mark, which is set while the comparison slices and sketches
the stored factors (PERF.md).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    parser.add_argument('--checkout', default=REPO)
    parser.add_argument('--label', required=True)
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seed', type=int, required=True)
    parser.add_argument('--spec', default=None,
                        help='another BENCHMARK.json (the toy one of '
                             'kfac_bench/tests, for a rehearsal)')
    args = parser.parse_args()
    checkout = os.path.abspath(args.checkout)
    sys.path.insert(0, checkout)
    from kfac_bench import run as bench
    bench.use_own_cache()       # before anything imports jax

    import jax

    from distributed_kfac_pytorch_tpu.utils import enable_compilation_cache
    enable_compilation_cache()
    with open(args.spec or os.path.join(checkout, 'BENCHMARK.json')) as f:
        cell_spec = bench.load_cell_spec(json.load(f), args.workload)
    family = bench.load_module('family', cell_spec['config']['family'],
                               'families')
    device = jax.local_devices()[0]
    rows = []

    def read(what: str) -> None:
        stats = device.memory_stats() or {}    # None off the chip
        rows.append([what, stats.get('peak_bytes_in_use', 0),
                     stats.get('bytes_in_use', 0)])
        print(args.label, what,
              f'{rows[-1][1] / 2**30:.6f} GiB peak',
              f'{rows[-1][2] / 2**30:.6f} GiB in use', flush=True)

    cell = family.build(cell_spec['config'], cell_spec['traffic'],
                        args.seed, cell_spec['chips'], tempfile.mkdtemp())
    jax.block_until_ready(cell.state.kfac_state)
    read('built')

    @functools.wraps(cell.step_fn)
    def probed(*step_args, **flags):
        out = cell.step_fn(*step_args, **flags)
        jax.block_until_ready(out)
        read(cell.stage_of(flags))
        return out

    cell.drive(probed, (cell.next_batch() for _ in range(12)))
    out_dir = os.path.join(REPO, 'chiprun_out', 'peak_probe')
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f'{args.label}.json'), 'w') as f:
        json.dump({'label': args.label, 'checkout': checkout,
                   'workload': args.workload, 'seed': args.seed,
                   'device': device.device_kind,
                   'rows': rows}, f, indent=1)
    return 0


if __name__ == '__main__':
    sys.exit(main())
