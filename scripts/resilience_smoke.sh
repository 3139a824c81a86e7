#!/usr/bin/env bash
# Kill-and-resume smoke (r8): preempt a tiny CIFAR run mid-epoch via an
# injected fault, relaunch it, and assert the combined per-step loss
# sequence is BIT-IDENTICAL to an uninterrupted run's. The same check
# runs in the test suite as
# tests/test_resilience.py::TestCLIKillAndResume (full tier); this
# wrapper is the standalone/CI-pipeline form.
#
# One-command equivalent (single metrics file, relaunch handled by the
# chaos harness):
#   python -m distributed_kfac_pytorch_tpu.resilience.chaos \
#       'preempt@1' --relaunch 1 -- python examples/train_cifar10_resnet.py ...
# The two launches are driven explicitly below so each gets its own
# metrics JSONL (a fresh sink owns its path).
set -euo pipefail
cd "$(dirname "$0")/.."

out="$(mktemp -d)"
trap 'rm -rf "$out"' EXIT

# One shared compile cache: the relaunch recompiles the identical
# program, so runs 2-3 are warm (single-device CPU warm reads are fine;
# see utils.enable_compilation_cache for the multi-device caveat).
common_env=(JAX_PLATFORMS=cpu KFAC_SYNTHETIC_CIFAR=384
            JAX_COMPILATION_CACHE_DIR="$out/cache")
common_args=(--epochs 1 --model resnet20
             --batch-size 128 --val-batch-size 96
             --kfac-update-freq 1 --kfac-cov-update-freq 1
             --checkpoint-steps 1 --metrics-interval 1
             --log-dir "$out/logs")

echo "== reference (uninterrupted) run =="
env "${common_env[@]}" python examples/train_cifar10_resnet.py \
    "${common_args[@]}" --no-resume \
    --checkpoint-dir "$out/ckpt-ref" \
    --kfac-metrics "$out/ref.jsonl"

echo "== preempted run (injected preemption after step 1) =="
set +e
env "${common_env[@]}" KFAC_CHAOS='preempt@1' \
python examples/train_cifar10_resnet.py "${common_args[@]}" \
    --checkpoint-dir "$out/ckpt" --kfac-metrics "$out/run1.jsonl"
rc=$?
set -e
[ "$rc" -eq 75 ] || { echo "expected exit 75 (preempted), got $rc"; exit 1; }

echo "== relaunch (auto-resume from the step checkpoint) =="
env "${common_env[@]}" python examples/train_cifar10_resnet.py \
    "${common_args[@]}" --checkpoint-dir "$out/ckpt" \
    --kfac-metrics "$out/run2.jsonl"

echo "== comparing per-step loss sequences =="
python - "$out" <<'EOF'
import sys
from distributed_kfac_pytorch_tpu.observability import sink

out = sys.argv[1]
losses = lambda p: [(r['step'], r['metrics']['loss'])
                    for r in sink.read_jsonl(p) if r['kind'] == 'step']
ref = losses(f'{out}/ref.jsonl')
got = losses(f'{out}/run1.jsonl') + losses(f'{out}/run2.jsonl')
assert len(ref) == 3, ref
assert got == ref, f'loss sequences diverged:\nref {ref}\ngot {got}'
events = [r['event'] for r in sink.read_jsonl(f'{out}/run1.jsonl')
          if r['kind'] == 'event']
assert 'preemption' in events and 'checkpoint_save' in events, events
print('kill-and-resume: per-step losses BIT-IDENTICAL to the '
      'uninterrupted run')
EOF

python -m distributed_kfac_pytorch_tpu.observability.report \
    "$out/run2.jsonl"

echo "== elastic resize leg (resize@1->2: drain a 4-device run, =="
echo "== relaunch with 2 devices, resume via the reshard path)  =="
# The chaos harness owns the whole loop: it injects the fault, sees the
# relaunch exit code, rewrites XLA_FLAGS to the new world size, and
# relaunches. Both launches share one metrics path — the drained
# incarnation survives as resize.jsonl.prev.1. Compile cache OFF for
# this leg: multi-device CPU warm reads are the known-segfaulting
# combination (see tests/conftest.py).
env JAX_PLATFORMS=cpu KFAC_SYNTHETIC_CIFAR=384 KFAC_COMPILE_CACHE=0 \
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
python -m distributed_kfac_pytorch_tpu.resilience.chaos \
    'resize@1->2' --relaunch 1 -- \
    python examples/train_cifar10_resnet.py "${common_args[@]}" \
    --checkpoint-dir "$out/ckpt-resize" \
    --kfac-metrics "$out/resize.jsonl"

echo "== checking the grow/shrink loop completed without a cold restart =="
python - "$out" <<'EOF'
import sys
from distributed_kfac_pytorch_tpu.observability import sink

out = sys.argv[1]
live = sink.read_jsonl(f'{out}/resize.jsonl')
steps = [r['step'] for r in live if r['kind'] == 'step']
events = [r['event'] for r in live if r['kind'] == 'event']
# The relaunch CONTINUED the run (global steps 1..2 after the drained
# step 0) instead of cold-restarting at 0, and the topology change was
# recorded alongside the restore.
assert steps == [1, 2], steps
assert 'topology_change' in events and 'restore' in events, events
tc = next(r for r in live if r.get('event') == 'topology_change')
assert tc['data']['from_devices'] == 4, tc
assert tc['data']['to_devices'] == 2, tc
assert tc['data']['resharded'], tc
prev = sink.read_incarnation(f'{out}/resize.jsonl.prev.1')
prev_events = [r.get('event') for r in prev if r['kind'] == 'event']
assert 'preemption' in prev_events, prev_events
print('resize leg: 4->2 grow/shrink loop resumed elastically '
      '(topology_change + restore recorded; steps continued 1..2)')
EOF
# The report surfaces the resize alongside the preemption/restore
# lifecycle (schema-validates the stream; non-zero exit fails the
# smoke).
python -m distributed_kfac_pytorch_tpu.observability.report \
    "$out/resize.jsonl"

echo "== supervisor pure-relaunch leg (r17): the same preempt-and- =="
echo "== resume loop, driven by the real failure supervisor        =="
# The chaos harness leg above hand-rolls the relaunch; this is the
# production form — the supervisor classifies the drain exit and
# relaunches with the checkpoint fresh (no backoff, no budget). Full
# failure-class coverage (crash/hang/failover/crash-loop) lives in
# scripts/supervisor_smoke.sh.
env "${common_env[@]}" KFAC_CHAOS='preempt@1' \
python -m distributed_kfac_pytorch_tpu.resilience.supervisor \
    --workdir "$out/sup" --metrics "$out/sup.jsonl" \
    --hang-timeout 90 --startup-grace 600 --backoff 0 -- \
    python examples/train_cifar10_resnet.py "${common_args[@]}" \
    --checkpoint-dir "$out/ckpt-sup" --kfac-metrics "$out/sup.jsonl"

python - "$out" <<'EOF'
import sys
from distributed_kfac_pytorch_tpu.observability import sink

out = sys.argv[1]
sup = [r for r in sink.read_jsonl(f'{out}/sup.jsonl.supervisor')
       if r['kind'] == 'event']
assert [r['event'] for r in sup] == ['supervisor_restart'], sup
assert sup[0]['data']['reason'] == 'drain', sup
steps = [r['step'] for r in sink.read_jsonl(f'{out}/sup.jsonl')
         if r['kind'] == 'step']
assert steps and steps[0] > 0, steps  # resumed, not cold-started
print('supervisor leg: drain classified, relaunch resumed mid-epoch')
EOF
echo "resilience smoke OK"
