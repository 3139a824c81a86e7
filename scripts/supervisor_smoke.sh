#!/usr/bin/env bash
# Failure-supervision smoke (r17): every rung of the supervisor proven
# end-to-end on CPU through the real CIFAR CLI — crash relaunch, hang
# detection (lease expiry -> kill -> relaunch), survivor-mesh failover
# (capacity loss -> drain -> shrunken relaunch through the elastic
# resume), and crash-loop escalation with its distinct exit code. The
# LM-CLI variant rides in the test suite as
# tests/test_supervisor.py::TestLMCLISupervised (slow tier); this
# wrapper is the standalone/CI-pipeline form.
set -euo pipefail
cd "$(dirname "$0")/.."

out="$(mktemp -d)"
trap 'rm -rf "$out"' EXIT

# One shared compile cache for the single-device legs (warm relaunches;
# the multi-device leg runs cache-off — see tests/conftest.py for the
# multi-device warm-cache caveat).
common_env=(JAX_PLATFORMS=cpu KFAC_SYNTHETIC_CIFAR=384
            JAX_COMPILATION_CACHE_DIR="$out/cache")
common_args=(--epochs 1 --model resnet20
             --batch-size 128 --val-batch-size 96
             --kfac-update-freq 1 --kfac-cov-update-freq 1
             --checkpoint-steps 1 --metrics-interval 1
             --log-dir "$out/logs")
# --hang-timeout must outlast the child's longest lease-silent healthy
# stretch: the post-training eval + checkpoint tail (compile included)
# writes no leases. 90 s is ~3x the observed CPU tail.
sup_args=(--hang-timeout 90 --startup-grace 600 --poll 0.5
          --drain-grace 300 --backoff 0 --max-restarts 3)
supervisor=(python -m distributed_kfac_pytorch_tpu.resilience.supervisor)

echo "== leg 1: crash@2 — supervised relaunch to completion =="
env "${common_env[@]}" KFAC_CHAOS='crash@2' \
"${supervisor[@]}" --workdir "$out/sup-crash" --metrics "$out/crash.jsonl" \
    "${sup_args[@]}" -- \
    python examples/train_cifar10_resnet.py "${common_args[@]}" \
    --checkpoint-dir "$out/ckpt-crash" --kfac-metrics "$out/crash.jsonl"

python - "$out" <<'EOF'
import sys
from distributed_kfac_pytorch_tpu.observability import sink

out = sys.argv[1]
sup = [r for r in sink.read_jsonl(f'{out}/crash.jsonl.supervisor')
       if r['kind'] == 'event']
assert [r['event'] for r in sup] == ['supervisor_restart'], sup
assert sup[0]['data']['reason'] == 'crash', sup
# The relaunch RESUMED (the live stream starts past step 0) instead of
# cold-restarting.
steps = [r['step'] for r in sink.read_jsonl(f'{out}/crash.jsonl')
         if r['kind'] == 'step']
assert steps and steps[0] > 0, steps
print('crash leg: supervised relaunch resumed and completed')
EOF

echo "== gate: supervisor_restarts metric round-trips =="
python -m distributed_kfac_pytorch_tpu.observability.gate \
    "$out/crash.jsonl" --write-baseline "$out/base.json"
python -m distributed_kfac_pytorch_tpu.observability.gate \
    "$out/crash.jsonl" --baseline "$out/base.json" \
    --allow-missing --no-anomaly
python - "$out" <<'EOF'
import json, sys
base = json.load(open(f'{sys.argv[1]}/base.json'))
assert base['metrics']['supervisor_restarts'] == 1, base['metrics']
print('gate: supervisor_restarts recorded in the baseline vector')
EOF

echo "== leg 2: hang@2 — lease expiry, kill-and-relaunch =="
env "${common_env[@]}" KFAC_CHAOS='hang@2' \
"${supervisor[@]}" --workdir "$out/sup-hang" --metrics "$out/hang.jsonl" \
    "${sup_args[@]}" -- \
    python examples/train_cifar10_resnet.py "${common_args[@]}" \
    --checkpoint-dir "$out/ckpt-hang" --kfac-metrics "$out/hang.jsonl"

python - "$out" <<'EOF'
import sys
from distributed_kfac_pytorch_tpu.observability import sink

out = sys.argv[1]
sup = [r for r in sink.read_jsonl(f'{out}/hang.jsonl.supervisor')
       if r['kind'] == 'event']
assert [r['event'] for r in sup] == ['hang_detected',
                                     'supervisor_restart'], sup
assert sup[0]['data']['last_step'] == 2, sup
assert sup[1]['data']['reason'] == 'hang', sup
print('hang leg: lease expiry detected, wedged child killed, '
      'relaunch completed')
EOF

echo "== leg 3: failover-shrink — capacity 4 -> 2 through the =="
echo "==        elastic resume (supervisor_failover -> topology_change) =="
echo 2 > "$out/capacity"
env JAX_PLATFORMS=cpu KFAC_SYNTHETIC_CIFAR=384 KFAC_COMPILE_CACHE=0 \
"${supervisor[@]}" --workdir "$out/sup-shrink" --metrics "$out/shrink.jsonl" \
    "${sup_args[@]}" --devices 4 --capacity-file "$out/capacity" -- \
    python examples/train_cifar10_resnet.py "${common_args[@]}" \
    --checkpoint-dir "$out/ckpt-shrink" \
    --kfac-metrics "$out/shrink.jsonl"

python - "$out" <<'EOF'
import sys
from distributed_kfac_pytorch_tpu.observability import sink

out = sys.argv[1]
sup = [r for r in sink.read_jsonl(f'{out}/shrink.jsonl.supervisor')
       if r['kind'] == 'event']
assert [r['event'] for r in sup] == ['supervisor_failover'], sup
fo = sup[0]
assert fo['data']['from_devices'] == 4, fo
assert fo['data']['to_devices'] == 2, fo
live = sink.read_jsonl(f'{out}/shrink.jsonl')
tcs = [r for r in live if r.get('event') == 'topology_change']
assert tcs, [r.get('event') for r in live if r['kind'] == 'event']
tc = tcs[-1]
assert tc['data']['from_devices'] == 4, tc
assert tc['data']['to_devices'] == 2, tc
assert tc['data']['resharded'], tc
# The pinned SEQUENCE: the supervisor's failover decision precedes the
# relaunched child's elastic topology_change.
assert fo['wall_time'] <= tc['wall_time'], (fo, tc)
events = [r['event'] for r in live if r['kind'] == 'event']
assert 'restore' in events, events
print('failover leg: supervisor_failover -> topology_change 4->2, '
      'resumed via the elastic reshard (no cold restart)')
EOF

echo "== leg 4: crash loop — same step failing twice, distinct exit =="
set +e
env "${common_env[@]}" KFAC_CHAOS='crash@2' \
"${supervisor[@]}" --workdir "$out/sup-loop" --metrics "$out/loop.jsonl" \
    "${sup_args[@]}" --keep-faults --crash-loop-after 2 -- \
    python examples/train_cifar10_resnet.py "${common_args[@]}" \
    --checkpoint-dir "$out/ckpt-loop" --kfac-metrics "$out/loop.jsonl"
rc=$?
set -e
[ "$rc" -eq 77 ] || { echo "expected crash-loop exit 77, got $rc"; exit 1; }

python - "$out" <<'EOF'
import json, sys
from distributed_kfac_pytorch_tpu.observability import sink

out = sys.argv[1]
sup = [r for r in sink.read_jsonl(f'{out}/loop.jsonl.supervisor')
       if r['kind'] == 'event']
kinds = [r['event'] for r in sup]
assert kinds == ['supervisor_restart', 'crash_loop'], kinds
loop = sup[-1]['data']
assert loop['failure_step'] == 2 and loop['consecutive'] == 2, loop
diag = json.load(open(loop['diagnostic']))
assert diag['failure_step'] == 2 and diag['history'], diag
print('crash-loop leg: detected at step 2 after 2 launches, exit 77, '
      'diagnostic bundle written')
EOF

# The report's supervision section summarizes the whole session from
# the sidecar (schema-validates both streams; non-zero exit fails the
# smoke).
python -m distributed_kfac_pytorch_tpu.observability.report \
    "$out/shrink.jsonl"
python - "$out" <<'EOF'
import json, subprocess, sys
out = sys.argv[1]
js = json.loads(subprocess.check_output(
    [sys.executable, '-m',
     'distributed_kfac_pytorch_tpu.observability.report',
     f'{out}/shrink.jsonl', '--json']))
assert js['supervision']['failovers'] == 1, js['supervision']
print('report: supervision section carries the failover')
EOF
echo "supervisor smoke OK"
