"""Autotune driver: probe -> score -> commit a tuned-config artifact.

    python -m distributed_kfac_pytorch_tpu.autotune \\
        --workload flagship_lm --out TUNED_flagship_lm.json

Enumerates the knob space (:mod:`autotune.space`), probes each
candidate through short warm segments (:mod:`autotune.probe`), ranks
them on the r10 gate metrics (:mod:`autotune.score`), re-probes the
winner as a reproducibility self-check, and writes the committed
per-workload artifact ``TUNED_<workload>.json``:

  {"format": "kfac-autotune-v1", "workload": ..., "platform": "cpu",
   "topology": {topo_* ints}, "sink_schema": 4,
   "best": {knob: value}, "best_score": ..., "objective": ...,
   "candidates": [{knobs, metrics, score, disqualified}, ...],
   "self_check": {...}, "probe": {...}, "created_unix": ...}

The best candidate's recorded probe stream lands next to the artifact
as ``<out>.probe.jsonl`` — the evidence the committed numbers came
from, exactly like ``BASELINE_OBS.json.source.jsonl`` (r10).

Loading is **fail-closed** (:func:`load_tuned_config`): an unreadable
/ torn / wrong-format artifact, a platform mismatch, a topology
(world-size) mismatch, or a knob outside ``TUNABLE_FIELDS`` all fall
back to defaults and queue exactly one ``autotune_fallback`` event for
the metrics stream; a clean load queues one ``autotune_apply`` event.
The example CLIs consume this via ``--tuned-config``
(:mod:`autotune.cli`).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

ARTIFACT_FORMAT = 'kfac-autotune-v1'


# ---------------------------------------------------------------------------
# Artifact IO + fail-closed loading
# ---------------------------------------------------------------------------

def tuned_path(workload: str) -> str:
    return f'TUNED_{workload}.json'


def write_tuned(path: str, obj: dict) -> dict:
    obj = {'format': ARTIFACT_FORMAT, **obj}
    with open(path, 'w') as f:
        json.dump(obj, f, indent=1, sort_keys=True)
        f.write('\n')
    return obj


def read_tuned(path: str) -> dict:
    """Strict artifact read (the replay/bench consumer); raises on any
    problem — fail-closed consumers use :func:`load_tuned_config`."""
    with open(path) as f:
        obj = json.load(f)
    if obj.get('format') != ARTIFACT_FORMAT:
        raise ValueError(f'{path}: not a {ARTIFACT_FORMAT} file '
                         f'(format={obj.get("format")!r})')
    if not isinstance(obj.get('best'), dict):
        raise ValueError(f'{path}: artifact has no best-knobs object')
    return obj


def live_world() -> dict:
    """The world-size slice of the live topology, for artifact
    validation before any mesh exists (the CLIs load tuned configs
    before mesh construction — the full KAISA grid may itself depend
    on flags the artifact tunes)."""
    import jax
    return {'devices': int(jax.device_count()),
            'processes': int(jax.process_count())}


def load_tuned_config(path: str, *, platform: str | None = None,
                      world: dict | None = None
                      ) -> tuple[dict | None, list[dict]]:
    """Fail-closed artifact load: ``(knobs | None, events)``.

    ``platform`` is the live ``jax.default_backend()``; ``world`` is
    :func:`live_world` (or a checkpoint ``TopologySpec``'s
    process/device counts). Validation compares the artifact's
    recorded platform and ``topo_devices``/``topo_processes``/
    ``topo_seq`` world scalars — the tuning evidence only transfers
    within the world it was measured on. The KAISA grid scalars
    (``topo_rows``/``topo_cols``) are provenance, not preconditions:
    the artifact may legitimately be applied under different
    mesh-shaping flags, which the tuned knob set cannot touch
    (``TUNABLE_FIELDS``).

    Every outcome queues exactly one event dict (``autotune_fallback``
    with a ``reason``, or ``autotune_apply``); flush them into a
    metrics sink with :func:`emit_events` once one exists.
    """
    from distributed_kfac_pytorch_tpu.training.optimizers import (
        TUNABLE_FIELDS,
    )

    def fallback(reason: str, **data) -> tuple[None, list[dict]]:
        return None, [{'event': 'autotune_fallback', 'path': str(path),
                       'reason': reason, **data}]

    try:
        obj = read_tuned(path)
    except FileNotFoundError:
        return fallback('missing')
    except (OSError, ValueError, json.JSONDecodeError) as e:
        return fallback('unreadable', error=str(e)[:200])

    if platform is not None:
        recorded = obj.get('platform')
        if recorded != platform:
            return fallback('platform_mismatch',
                            artifact=str(recorded), live=str(platform))
    topo = obj.get('topology') or {}
    if world is not None:
        for live_key, topo_key, default in (
                ('devices', 'topo_devices', None),
                ('processes', 'topo_processes', None),
                ('seq', 'topo_seq', 1)):
            if live_key not in world:
                continue
            recorded = topo.get(topo_key, default)
            if recorded is None or int(recorded) != int(world[live_key]):
                return fallback('topology_mismatch', key=topo_key,
                                artifact=-1 if recorded is None
                                else int(recorded),
                                live=int(world[live_key]))
    knobs = dict(obj['best'])
    unknown = sorted(set(knobs) - set(TUNABLE_FIELDS))
    if unknown:
        return fallback('unknown_knobs', knobs=','.join(unknown))
    return knobs, [{'event': 'autotune_apply', 'path': str(path),
                    'workload': str(obj.get('workload')),
                    'knobs': json.dumps(knobs, sort_keys=True)}]


def apply_tuned(cfg, knobs: dict) -> tuple:
    """Overlay tuned knobs on an ``OptimConfig``: ``(new_cfg, error)``.

    ``error`` is non-None when the MERGED config violates a validity
    constraint (e.g. the artifact tuned ``inv_pipeline_chunks`` against
    a different ``--kfac-update-freq`` than the CLI now runs) — the
    caller falls back to the un-tuned config, fail-closed.
    """
    from distributed_kfac_pytorch_tpu.autotune import space as space_mod
    from distributed_kfac_pytorch_tpu.training.optimizers import (
        TUNABLE_FIELDS,
    )
    unknown = sorted(set(knobs) - set(TUNABLE_FIELDS))
    if unknown:
        return cfg, f'unknown knob(s) {unknown}'
    new_cfg = dataclasses.replace(cfg, **knobs)
    merged = dataclasses.asdict(new_cfg)
    violated = [c.doc for c in space_mod.BASE_CONSTRAINTS
                if not c.ok(merged)]
    if violated:
        return cfg, '; '.join(violated)
    return new_cfg, None


def emit_events(sink, events: list[dict]) -> None:
    """Flush queued autotune events into a metrics sink (None ok)."""
    if sink is None:
        return
    emit = getattr(sink, 'event_record', None)
    if emit is None:
        return
    for ev in events:
        emit(ev['event'], **{k: v for k, v in ev.items()
                             if k != 'event'})


def kfac_overrides(knobs: dict) -> tuple[dict, int | None, list[str]]:
    """Map tuned OptimConfig knobs to raw ``KFAC(...)`` kwargs.

    For consumers that build a bare ``KFAC`` instead of going through
    ``get_optimizer`` (``benchmarks/step_breakdown.py``'s
    ``tuned_vs_default`` row). Returns ``(kwargs, inv_update_freq,
    ignored)`` — ``ignored`` lists knobs the consumer's harness cannot
    express (e.g. a scan-based bench fires monolithically, so
    ``inv_pipeline_chunks`` is surfaced rather than silently dropped).
    """
    import jax.numpy as jnp
    kwargs: dict = {}
    inv_freq = None
    ignored: list[str] = []
    for name, value in knobs.items():
        if name == 'bf16_precond':
            if value:
                kwargs['precond_compute_dtype'] = jnp.bfloat16
        elif name == 'bf16_factors':
            if value:
                kwargs['factor_dtype'] = jnp.bfloat16
                kwargs['factor_compute_dtype'] = jnp.bfloat16
        elif name == 'bf16_inverses':
            if value:
                kwargs['inv_dtype'] = jnp.bfloat16
        elif name == 'factor_batch_fraction':
            kwargs['factor_batch_fraction'] = float(value)
        elif name == 'eigh_polish_iters':
            kwargs['eigh_polish_iters'] = int(value)
        elif name == 'kfac_approx':
            kwargs['kfac_approx'] = str(value)
        elif name == 'inv_lowrank_rank':
            kwargs['inv_lowrank_rank'] = int(value)
        elif name == 'inv_lowrank_dim_threshold':
            kwargs['inv_lowrank_dim_threshold'] = int(value)
        elif name == 'kfac_inv_update_freq':
            inv_freq = int(value)
        elif name in ('deferred_factor_reduction', 'inv_staleness',
                      'hierarchical_reduce'):
            # Engine-scheduled knobs (window-boundary reduce /
            # frozen-snapshot chunk phases / the r20 two-level reduce,
            # which additionally needs a multi-slice mesh): a bare-KFAC
            # scan harness fires monolithically with no factor_reduce/
            # factor_snapshot schedule, so constructing with them on
            # would leave the accumulator un-reduced forever. Surfaced
            # as ignored, never silently dropped.
            if value:
                ignored.append(name)
        else:
            ignored.append(name)
    return kwargs, inv_freq, sorted(ignored)


# ---------------------------------------------------------------------------
# The tuning run
# ---------------------------------------------------------------------------

def tune(workload_name: str, *, out: str | None = None,
         steps: int = 8, warmup_windows: int = 2,
         inv_update_freq: int = 4, cov_update_freq: int = 1,
         objective: str = 'weighted', hbm_ceiling: float | None = None,
         max_candidates: int | None = None, pruner: str = 'auto',
         space_overrides: dict | None = None, seed: int = 0,
         self_check: bool = True, self_check_tol: float = 0.75,
         mesh=None, log=print) -> dict:
    """Run the probe -> score -> commit loop; returns the artifact."""
    import jax

    from distributed_kfac_pytorch_tpu import elastic as elastic_lib
    from distributed_kfac_pytorch_tpu.autotune import probe as probe_mod
    from distributed_kfac_pytorch_tpu.autotune import score as score_mod
    from distributed_kfac_pytorch_tpu.autotune import space as space_mod
    from distributed_kfac_pytorch_tpu.observability.sink import (
        SCHEMA_VERSION,
    )
    from distributed_kfac_pytorch_tpu.parallel import distributed as D
    from distributed_kfac_pytorch_tpu.training import optimizers

    workload = probe_mod.get_workload(workload_name)
    out = out or tuned_path(workload_name)
    base_cfg = optimizers.OptimConfig(
        kfac_inv_update_freq=int(inv_update_freq),
        kfac_cov_update_freq=int(cov_update_freq))
    base = {f: getattr(base_cfg, f)
            for f in optimizers.TUNABLE_FIELDS}
    if (not workload.weight_shared
            and 'kfac_approx' not in (space_overrides or {})):
        # No weight-shared layers -> 'reduce' resolves to the identical
        # program as 'expand' (sharing.approx auto-policy): probing
        # both would double the table for zero information. An explicit
        # override still wins.
        space_overrides = {**(space_overrides or {}),
                           'kfac_approx': ['expand']}
        log(f'autotune[{workload_name}]: kfac_approx knob dropped '
            '(workload has no weight-shared layers; reduce == expand)')
    if (workload.max_factor_dim
            and workload.max_factor_dim
            < base_cfg.inv_lowrank_dim_threshold
            and 'inv_lowrank_rank' not in (space_overrides or {})):
        # No factor dim can reach the engagement threshold -> every
        # rank value compiles the identical exact-dispatch program;
        # probing them would pad the table with duplicates. An
        # explicit override (e.g. probing a lowered threshold) wins.
        space_overrides = {**(space_overrides or {}),
                           'inv_lowrank_rank': [0]}
        log(f'autotune[{workload_name}]: inv_lowrank_rank knob '
            f'dropped (max factor dim {workload.max_factor_dim} < '
            f'threshold {base_cfg.inv_lowrank_dim_threshold}; the '
            'low-rank path cannot engage)')
    space = space_mod.default_space(space_overrides)

    if mesh is None:
        mesh = D.make_kfac_mesh(
            comm_method=optimizers.COMM_METHODS[
                base_cfg.comm_method.lower()],
            grad_worker_fraction=base_cfg.grad_worker_fraction)
    topo = elastic_lib.TopologySpec.of_mesh(mesh)

    candidates = space.enumerate(base)
    dropped = 0
    if max_candidates is not None and len(candidates) > max_candidates:
        dropped = len(candidates) - max_candidates
        candidates = candidates[:max_candidates]
    log(f'autotune[{workload_name}]: {len(candidates)} candidate(s)'
        + (f' ({dropped} dropped by --max-candidates)' if dropped
           else '') + f', probe {steps} step(s) @ '
        f'f{cov_update_freq}/i{inv_update_freq}, '
        f'objective={objective}')

    def run_probe(knobs: dict, n_steps: int) -> probe_mod.ProbeResult:
        return probe_mod.probe_candidate(
            workload, base_cfg, knobs, steps=n_steps,
            warmup_windows=warmup_windows, mesh=mesh, seed=seed)

    # Probe scores are only comparable at EQUAL probe length (a probe
    # always starts on a firing step, so the firing-spike fraction in
    # the percentiles scales with 1/steps): the committed winner must
    # be picked among full-length probes only. Pruners therefore
    # nominate a winner themselves (their short-rung scores order
    # candidates within a rung, never across rungs), every nominee is
    # guaranteed a full-length probe, and the final ranking below runs
    # over the full-length rows alone. Shorter-rung rows stay in the
    # artifact's candidate table as provenance (their metrics carry
    # n_steps, so the table is self-describing).
    results: list[probe_mod.ProbeResult] = []

    def pruner_eval(knobs, n_steps):
        r = run_probe(knobs, n_steps)
        results.append(r)
        reason = score_mod.hard_violation(r, hbm_ceiling=hbm_ceiling)
        if reason is not None:
            return None
        return score_mod.objective_value(r.metrics, objective)

    if pruner == 'auto':
        pruner = 'full' if len(candidates) <= 8 else 'halving'
    if pruner == 'full':
        for knobs in candidates:
            r = run_probe(knobs, steps)
            results.append(r)
            log(f'  probe {json.dumps(knobs, sort_keys=True)}: '
                + (f'DISQUALIFIED ({r.disqualified})'
                   if r.disqualified else
                   f"p50 {r.metrics.get('step_p50_ms'):.3g} ms"))
    elif pruner == 'halving':
        winner, _ = space_mod.successive_halving(
            candidates, pruner_eval, min_steps=max(2, steps // 4),
            max_steps=steps)
        if winner is not None and not any(
                r.knobs == winner
                and r.metrics.get('n_steps', 0) >= steps
                for r in results):
            # The last rung may have raced below the full budget.
            results.append(run_probe(winner, steps))
    elif pruner == 'coordinate':
        winner, _ = space_mod.coordinate_descent(
            space, base, lambda knobs: pruner_eval(knobs, steps))
    else:
        raise ValueError(f'unknown pruner {pruner!r}')

    full_length = [r for r in results
                   if r.disqualified is not None
                   or r.metrics.get('n_steps', 0) >= steps]
    ranked = score_mod.rank_candidates(full_length or results,
                                       objective=objective,
                                       hbm_ceiling=hbm_ceiling)
    best = next((r for r in ranked if r['disqualified'] is None), None)
    if best is None:
        all_rows = score_mod.rank_candidates(
            results, objective=objective, hbm_ceiling=hbm_ceiling)
        raise SystemExit(
            f'autotune[{workload_name}]: every candidate was '
            'disqualified — nothing to commit. Reasons: '
            + '; '.join(sorted({r['disqualified'] for r in all_rows
                                if r['disqualified']})))
    table = score_mod.rank_candidates(results, objective=objective,
                                      hbm_ceiling=hbm_ceiling)

    # Reproducibility self-check: re-probe the winner (fresh build,
    # same seed) and keep its recorded stream as the artifact evidence.
    check: dict = {'enabled': bool(self_check)}
    stream_path = out + '.probe.jsonl'
    rescore = probe_mod.probe_candidate(
        workload, base_cfg, best['knobs'], steps=steps,
        warmup_windows=warmup_windows, mesh=mesh, seed=seed,
        keep_stream=stream_path)
    if self_check:
        reason = score_mod.hard_violation(rescore,
                                          hbm_ceiling=hbm_ceiling)
        if reason is not None:
            check.update({'pass': False, 'reason': reason})
        else:
            s2 = score_mod.objective_value(rescore.metrics, objective)
            ok = score_mod.scores_close(best['score'], s2,
                                        self_check_tol)
            check.update({
                'pass': bool(ok), 'tol': self_check_tol,
                'rescore': list(s2) if isinstance(s2, tuple) else s2,
                'rescore_metrics': rescore.metrics})
        log(f"  self-check: {'PASS' if check.get('pass') else 'FAIL'} "
            f"({json.dumps({k: v for k, v in check.items() if k not in ('rescore_metrics',)}, sort_keys=True)})")

    def _json_score(s):
        return list(s) if isinstance(s, tuple) else s

    artifact = write_tuned(out, {
        'created_unix': int(time.time()),
        'workload': workload_name,
        'platform': jax.default_backend(),
        'topology': topo.scalars(),
        'sink_schema': SCHEMA_VERSION,
        'objective': objective,
        'base': {k: (list(v) if isinstance(v, tuple) else v)
                 for k, v in base.items()},
        'best': best['knobs'],
        'best_score': _json_score(best['score']),
        'best_metrics': best['metrics'],
        'candidates': [{**r, 'score': _json_score(r['score'])}
                       for r in table],
        'self_check': check,
        'probe': {'steps': int(steps),
                  'warmup_windows': int(warmup_windows),
                  'cov_update_freq': int(cov_update_freq),
                  'inv_update_freq': int(inv_update_freq),
                  'seed': int(seed), 'pruner': pruner,
                  'hbm_ceiling': hbm_ceiling,
                  'stream': stream_path},
    })
    log(f'wrote {out}: best={json.dumps(best["knobs"], sort_keys=True)}'
        f' score={best["score"]}')
    return artifact


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    from distributed_kfac_pytorch_tpu.autotune import probe as probe_mod
    from distributed_kfac_pytorch_tpu.autotune import score as score_mod

    p = argparse.ArgumentParser(
        prog='python -m distributed_kfac_pytorch_tpu.autotune',
        description='Closed-loop perf autotuner: probe candidate '
                    'configs through short warm segments, score them '
                    'on the r10 gate metrics, commit the winner as a '
                    'per-workload TUNED_<workload>.json the example '
                    'CLIs load via --tuned-config (fail-closed).')
    p.add_argument('--workload', default='flagship_lm',
                   choices=sorted(probe_mod.WORKLOADS))
    p.add_argument('--out', default=None,
                   help='artifact path (default TUNED_<workload>.json; '
                        'the best probe stream lands at '
                        '<out>.probe.jsonl)')
    p.add_argument('--steps', type=int, default=8,
                   help='recorded probe steps per candidate')
    p.add_argument('--warmup-windows', type=int, default=2,
                   help='unrecorded cadence windows compiled+run '
                        'before the recorded segment')
    p.add_argument('--inv-update-freq', type=int, default=4,
                   help='probe inverse cadence (the recorded segment '
                        'covers steps/freq firing windows)')
    p.add_argument('--cov-update-freq', type=int, default=1)
    p.add_argument('--objective', default='weighted',
                   choices=score_mod.OBJECTIVES)
    p.add_argument('--hbm-ceiling', type=float, default=None,
                   metavar='BYTES',
                   help='hard-disqualify candidates whose probe peak '
                        'HBM exceeds this')
    p.add_argument('--max-candidates', type=int, default=None,
                   help='truncate the enumerated space (deterministic '
                        'order) — the CI smoke uses 2')
    p.add_argument('--pruner', default='auto',
                   choices=['auto', 'full', 'halving', 'coordinate'],
                   help='auto = full enumeration up to 8 candidates, '
                        'successive halving beyond')
    p.add_argument('--set', action='append', default=[],
                   metavar='KNOB=V1,V2',
                   help="override a knob's value list, e.g. --set "
                        'inv_pipeline_chunks=1,2,4; an empty list '
                        '(KNOB=) drops the knob; repeatable')
    p.add_argument('--seed', type=int, default=0)
    p.add_argument('--no-self-check', action='store_true',
                   help='skip the winner re-probe reproducibility '
                        'check')
    p.add_argument('--self-check-tol', type=float, default=0.75,
                   help='max relative score drift between the two '
                        'winner probes')
    p.add_argument('--strict-self-check', action='store_true',
                   help='exit non-zero when the self-check fails '
                        '(default: record the failure in the artifact '
                        'and warn)')
    p.add_argument('--list', action='store_true',
                   help='print the constraint-filtered candidate '
                        'table and exit without probing')
    args = p.parse_args(argv)

    overrides = {}
    for item in args.set:
        name, _, raw = item.partition('=')
        vals = []
        for tok in filter(None, raw.split(',')):
            low = tok.lower()
            if low in ('true', 'false'):
                vals.append(low == 'true')
            else:
                try:
                    vals.append(int(tok))
                except ValueError:
                    vals.append(float(tok))
        overrides[name] = vals

    if args.list:
        from distributed_kfac_pytorch_tpu.autotune import (
            space as space_mod,
        )
        from distributed_kfac_pytorch_tpu.training import optimizers
        base_cfg = optimizers.OptimConfig(
            kfac_inv_update_freq=args.inv_update_freq,
            kfac_cov_update_freq=args.cov_update_freq)
        base = {f: getattr(base_cfg, f)
                for f in optimizers.TUNABLE_FIELDS}
        for cand in space_mod.default_space(
                overrides or None).enumerate(base):
            print(json.dumps(cand, sort_keys=True))
        return 0

    artifact = tune(
        args.workload, out=args.out, steps=args.steps,
        warmup_windows=args.warmup_windows,
        inv_update_freq=args.inv_update_freq,
        cov_update_freq=args.cov_update_freq,
        objective=args.objective, hbm_ceiling=args.hbm_ceiling,
        max_candidates=args.max_candidates, pruner=args.pruner,
        space_overrides=overrides or None, seed=args.seed,
        self_check=not args.no_self_check,
        self_check_tol=args.self_check_tol)
    check = artifact.get('self_check', {})
    if check.get('enabled') and not check.get('pass'):
        print('warning: self-check failed — the probe may be '
              'measuring noise; re-run with more --steps before '
              'committing this artifact', file=sys.stderr)
        if args.strict_self_check:
            return 1
    return 0


if __name__ == '__main__':
    sys.exit(main())
