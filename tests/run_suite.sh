#!/usr/bin/env bash
# Parallel test-suite runner: shards test files across N pytest
# processes (default 3) so the full gate finishes in ~1/N the wall time
# (the single-process suite is ~8 min; this brings it under 5).
#
# The fault-injection tier (`-m faults`: SIGKILL/SIGTERM workers,
# FlakyProxy, corruption) runs as its OWN shard under a hard timeout:
# a hung fault test (a worker that survived its kill, a proxy that
# never released a socket) must fail the gate, not wedge it.
# Usage: tests/run_suite.sh [N]
set -u
cd "$(dirname "$0")/.."
N="${1:-3}"
FAULTS_TIMEOUT="${FAULTS_TIMEOUT:-900}"
mapfile -t FILES < <(ls tests/test_*.py)

# static-analysis gate, tier 1 (ISSUE 13): the fast jax-free AST
# passes (the jax import fence among them) run BEFORE the shards — a
# tree that fails them is broken no matter what the tests say, and
# they cost ~a second.
if ! python tools/framework_lint.py --fast; then
  echo "[framework_lint] fast passes FAILED — not running the suite"
  exit 1
fi

pids=()
for ((i = 0; i < N; i++)); do
  shard=()
  for ((j = i; j < ${#FILES[@]}; j += N)); do
    shard+=("${FILES[$j]}")
  done
  JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -m pytest "${shard[@]}" -q -m 'not faults' \
    >"/tmp/suite_shard_$i.log" 2>&1 &
  pids+=($!)
done

rc=0
for ((i = 0; i < N; i++)); do
  wait "${pids[$i]}" || rc=1
  tail -2 "/tmp/suite_shard_$i.log" | sed "s/^/[shard $i] /"
done

# fault-injection shard: every faults-marked test, one process,
# timeout-guarded (timeout -k: SIGKILL if SIGTERM is ignored — these
# tests spawn processes that are SUPPOSED to survive SIGTERM). Runs
# AFTER the regular shards drain: the tier's SIGTERM windows and
# loss-curve comparisons are timing-sensitive, and racing them
# against N parallel pytest processes makes them flaky.
# PADDLE_LOCK_CHECK=1 (ISSUE 13): the known locks are created
# instrumented and conftest's sessionfinish hook fails the shard on
# any lock-order inversion observed during the fault tier. The tier
# includes the ISSUE 20 elastic sparse-CTR kill/resume tests
# (test_sparse_shard_elastic.py::
# test_sigkill_mid_epoch_zero_lost_zero_retrained,
# test_online_learning.py): SIGKILLed sharded-table workers and subprocess serving replicas run under
# the same lock-order instrumentation.
JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PADDLE_LOCK_CHECK=1 \
  timeout -k 15 "$FAULTS_TIMEOUT" \
  python -m pytest tests/ -q -m faults \
  >"/tmp/suite_shard_faults.log" 2>&1 || rc=1
tail -2 /tmp/suite_shard_faults.log | sed "s/^/[shard faults] /"

# static-analysis gate, tier 2 (ISSUE 13): the HLO program audit runs
# AFTER the shards — donation/aliasing, host-transfer
# and byte budgets, forbidden-op patterns over the committed captures
# plus committed *.audit.json freshness.
if ! python tools/framework_lint.py hlo-audit; then
  echo "[framework_lint] hlo-audit FAILED"
  rc=1
fi

# static-analysis gate, tier 3 (ISSUE 15): the SPMD partitioning &
# collective-schedule audit over the committed mc_* multichip
# captures — replication floor, collective byte budgets, required/
# forbidden collective kinds, channel-order/permute-ring deadlock
# checks, plus the same *.audit.json freshness discipline.
if ! python tools/framework_lint.py spmd-audit; then
  echo "[framework_lint] spmd-audit FAILED"
  rc=1
fi
exit $rc
