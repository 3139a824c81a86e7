#!/usr/bin/env bash
# Fleet-scheduler smoke (r18): the training-as-a-service layer proven
# end-to-end on CPU through the real CIFAR CLI — a 3-job pack with an
# urgent admission (preempt-by-shrink then regrow, through the per-job
# capacity files and the elastic resume), a job-kill + pool-loss chaos
# leg (recovery inside the job's own supervisor budget, then a
# pool-capacity shrink), a crash-loop-isolation leg (the looping job
# quarantined with its diagnostic while its pool-mate completes), and
# the observability round-trip (report --json fleet key-set pinned +
# the gate's fleet_quarantines metric). The fast jax-free matrix rides
# in tests/test_fleet.py; this wrapper is the standalone/CI form.
set -euo pipefail
cd "$(dirname "$0")/.."

out="$(mktemp -d)"
trap 'rm -rf "$out"' EXIT

export JAX_PLATFORMS=cpu KFAC_SYNTHETIC_CIFAR=384
fleet=(python -m distributed_kfac_pytorch_tpu.fleet)
# Per-job supervisor knobs: hang timeout above the lease-silent
# eval/checkpoint/compile tail, zero backoff for speed.
fleet_args=(--poll 0.5 --aging-secs 5 --hang-timeout 600
            --startup-grace 600 --job-poll 0.5 --drain-grace 300
            --backoff 0 --crash-loop-after 2 --deadline 1800)

cifar_argv() {  # cifar_argv <leg> <job> <epochs> -> JSON argv tail
    python - "$@" <<'EOF'
import json, sys
leg, job, epochs = sys.argv[1:4]
print(json.dumps([
    'python', 'examples/train_cifar10_resnet.py',
    '--epochs', epochs, '--model', 'resnet20',
    '--batch-size', '128', '--val-batch-size', '96',
    '--kfac-update-freq', '1', '--kfac-cov-update-freq', '1',
    '--checkpoint-steps', '1', '--metrics-interval', '1',
    '--log-dir', f'{leg}/logs-{job}',
    '--checkpoint-dir', f'{leg}/ckpt-{job}']))
EOF
}

echo "== leg 1: 3-job pack — urgent admission shrinks the steady =="
echo "==        job 2 -> 1 and regrows it after (capacity channel) =="
mkdir -p "$out/leg1"
python - "$out" "$(cifar_argv "$out/leg1" steady 10)" \
               "$(cifar_argv "$out/leg1" mate 1)" \
               "$(cifar_argv "$out/leg1" urgent 1)" <<'EOF'
import json, sys
out, steady, mate, urgent = sys.argv[1:5]
jobs = {'jobs': [
    {'name': 'steady', 'argv': json.loads(steady), 'priority': 1,
     'min_devices': 1, 'max_devices': 2},
    {'name': 'mate', 'argv': json.loads(mate), 'priority': 2,
     'min_devices': 1, 'max_devices': 1},
    {'name': 'urgent', 'argv': json.loads(urgent), 'priority': 9,
     'min_devices': 2, 'max_devices': 2, 'after_s': 40},
]}
json.dump(jobs, open(f'{out}/leg1/jobs.json', 'w'), indent=1)
EOF
env KFAC_COMPILE_CACHE=0 \
"${fleet[@]}" "$out/leg1/jobs.json" --pool-devices 4 \
    --workdir "$out/leg1/fleet" "${fleet_args[@]}"

python - "$out/leg1" <<'EOF'
import sys
from distributed_kfac_pytorch_tpu.observability import sink

leg = sys.argv[1]
ev = [(r['event'], r['data'])
      for r in sink.read_jsonl(f'{leg}/fleet/fleet.jsonl')
      if r['kind'] == 'event']
kinds = [k for k, _ in ev]
assert kinds.count('fleet_admit') == 3, kinds
assert kinds.count('fleet_complete') == 3, kinds
pre = next(d for k, d in ev if k == 'fleet_preempt')
assert pre['job'] == 'steady', pre
assert (pre['from_devices'], pre['to_devices']) == (2, 1), pre
assert pre['reason'] == 'admission', pre
re = next(d for k, d in ev if k == 'fleet_regrow')
assert (re['job'], re['from_devices'], re['to_devices']) \
    == ('steady', 1, 2), re
# The urgent admission ordering: preempt before urgent's admit,
# urgent's completion before the regrow.
assert kinds.index('fleet_preempt') \
    < kinds.index('fleet_complete'), kinds
side = [r['event'] for r in sink.read_jsonl(
    f'{leg}/fleet/jobs/steady/metrics.jsonl.supervisor')
    if r['kind'] == 'event']
assert 'supervisor_failover' in side and 'supervisor_growback' in side, side
print('leg 1: urgent admission shrank steady 2->1 and regrew it, '
      'all 3 jobs completed')
EOF

echo "== leg 2: job-kill + pool-loss chaos — supervised recovery, =="
echo "==        then a pool shrink 2 -> 1 =="
mkdir -p "$out/leg2"
python - "$out" "$(cifar_argv "$out/leg2" a 6)" <<'EOF'
import json, sys
out, a = sys.argv[1:3]
jobs = [{'name': 'a', 'argv': json.loads(a),
         'min_devices': 1, 'max_devices': 2}]
json.dump(jobs, open(f'{out}/leg2/jobs.json', 'w'), indent=1)
EOF
env KFAC_COMPILE_CACHE=0 KFAC_FLEET_CHAOS='job-kill@30,pool-loss@160->1' \
"${fleet[@]}" "$out/leg2/jobs.json" --pool-devices 2 \
    --workdir "$out/leg2/fleet" "${fleet_args[@]}"

python - "$out/leg2" <<'EOF'
import sys
from distributed_kfac_pytorch_tpu.observability import sink

leg = sys.argv[1]
ev = [(r['event'], r['data'])
      for r in sink.read_jsonl(f'{leg}/fleet/fleet.jsonl')
      if r['kind'] == 'event']
kinds = [k for k, _ in ev]
assert 'fleet_quarantine' not in kinds, ev
done = next(d for k, d in ev if k == 'fleet_complete')
assert done['restarts'] >= 1, done  # the kill burned one relaunch
pre = next(d for k, d in ev if k == 'fleet_preempt')
assert pre['reason'] == 'pool-loss', pre
assert (pre['from_devices'], pre['to_devices']) == (2, 1), pre
side = [(r['event'], r['data']) for r in sink.read_jsonl(
    f'{leg}/fleet/jobs/a/metrics.jsonl.supervisor')
    if r['kind'] == 'event']
assert any(k == 'supervisor_restart' and d['reason'] == 'crash'
           for k, d in side), side
assert any(k == 'supervisor_failover' and d['to_devices'] == 1
           for k, d in side), side
print('leg 2: job-kill recovered inside the job budget; pool-loss '
      'shrank the world 2->1 through the elastic resume')
EOF

echo "== leg 3: crash-loop isolation — the looping job quarantined =="
echo "==        (exit 77 + diagnostic), its pool-mate completes =="
mkdir -p "$out/leg3"
python - "$out" "$(cifar_argv "$out/leg3" loop 1)" \
               "$(cifar_argv "$out/leg3" ok 1)" <<'EOF'
import json, sys
out, loop, ok = sys.argv[1:4]
jobs = [
    {'name': 'loop', 'argv': json.loads(loop), 'priority': 5,
     'max_restarts': 5, 'keep_faults': True,
     'env': {'KFAC_CHAOS': 'crash@2'}},
    {'name': 'ok', 'argv': json.loads(ok), 'priority': 1},
]
json.dump(jobs, open(f'{out}/leg3/jobs.json', 'w'), indent=1)
EOF
set +e
env JAX_COMPILATION_CACHE_DIR="$out/cache" \
"${fleet[@]}" "$out/leg3/jobs.json" --pool-devices 1 \
    --workdir "$out/leg3/fleet" "${fleet_args[@]}"
rc=$?
set -e
[ "$rc" -eq 1 ] || { echo "expected fleet exit 1 (quarantine), got $rc"; exit 1; }

python - "$out/leg3" <<'EOF'
import json, sys
from distributed_kfac_pytorch_tpu.observability import sink

leg = sys.argv[1]
ev = [(r['event'], r['data'])
      for r in sink.read_jsonl(f'{leg}/fleet/fleet.jsonl')
      if r['kind'] == 'event']
q = next(d for k, d in ev if k == 'fleet_quarantine')
assert q['job'] == 'loop' and q['rc'] == 77, q
assert q['reason'] == 'crash_loop', q
diag = json.load(open(q['diagnostic']))
assert diag['history'], diag
done = next(d for k, d in ev if k == 'fleet_complete')
assert done['job'] == 'ok', done
print('leg 3: crash-looping job quarantined with its diagnostic, '
      'pool-mate completed')
EOF

echo "== report --json fleet key-set pinned + gate round-trip =="
python -m distributed_kfac_pytorch_tpu.observability.report \
    "$out/leg3/fleet/fleet.jsonl"
python - "$out" <<'EOF'
import json, subprocess, sys
out = sys.argv[1]
js = json.loads(subprocess.check_output(
    [sys.executable, '-m',
     'distributed_kfac_pytorch_tpu.observability.report',
     f'{out}/leg3/fleet/fleet.jsonl', '--json']))
fleet = js['fleet']
assert fleet['quarantines'] == 1 and fleet['completes'] == 1, fleet
rows = fleet['jobs']
assert set(rows) == {'loop', 'ok'}, rows
for row in rows.values():
    assert set(row) == {'outcome', 'rc', 'devices', 'queue_wait_s',
                        'run_s', 'restarts', 'preemptions', 'gate',
                        'reason'}, row
print('report: fleet key + per-job SLO rows pinned')
EOF
# Gate: a clean fleet stream baselines fleet_quarantines=0; the
# quarantined leg must breach it.
python -m distributed_kfac_pytorch_tpu.observability.gate \
    "$out/leg1/fleet/fleet.jsonl" --write-baseline "$out/base.json" \
    --allow-missing
set +e
python -m distributed_kfac_pytorch_tpu.observability.gate \
    "$out/leg3/fleet/fleet.jsonl" --baseline "$out/base.json" \
    --allow-missing --no-anomaly --json > "$out/gate.json"
rc=$?
set -e
[ "$rc" -eq 1 ] || { echo "expected gate breach exit 1, got $rc"; exit 1; }
python - "$out" <<'EOF'
import json, sys
v = json.load(open(f'{sys.argv[1]}/gate.json'))
assert v['current']['fleet_quarantines'] == 1, v['current']
assert any(b['metric'] == 'fleet_quarantines' for b in v['breaches']), v
print('gate: fleet_quarantines round-trips and gates the quarantine')
EOF
echo "fleet smoke OK"
