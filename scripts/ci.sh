#!/usr/bin/env bash
# CI entry point (`make verify`): tier-1 tests + the serving-path smoke.
#
# The smoke drives the real serve driver end-to-end; JoinService's
# no-retrace assertion (launch/serve.py) makes it a hard failure if any
# steady-state request traces or compiles, so the serving path can never
# silently regress to per-request compilation again (ISSUE 2).
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "[ci] static analysis gate (contract prover + retrace/dtype linter vs baseline)"
timeout 300 python -m repro.analysis

echo "[ci] analysis mutation check (seeded bugs must each produce a new finding)"
timeout 300 python scripts/mutation_check.py

echo "[ci] tier-1: pytest"
python -m pytest -x -q

echo "[ci] serve smoke (steady state must not retrace)"
timeout 120 python -m repro.launch.serve --arch selfjoin --requests 4

echo "[ci] batching serve smoke (admission queue + coalesced launches)"
timeout 180 python -m repro.launch.serve --arch selfjoin --requests 8 \
  --batching --request-batch 64 --max-batch 512

echo "[ci] load smoke (fixed offered load: p99 must hold the recorded SLO, coalesce factor must be > 1)"
timeout 300 python benchmarks/bench_selfjoin.py --mode load --smoke

echo "[ci] bench smoke, merged-range sweep (harness + BENCH schema + merged-vs-unmerged AND run-loop-vs-row-loop pair-set parity + DMA-window decrease on the clustered workload)"
timeout 300 python benchmarks/bench_selfjoin.py --smoke

echo "[ci] bench smoke, per-cell sweep oracle (--no-merge; parity asserted again)"
timeout 300 python benchmarks/bench_selfjoin.py --smoke --no-merge

echo "[ci] bench smoke under REPRO_SANITIZE=1 (sanitized kernel mode: invariant checks must stay clean)"
REPRO_SANITIZE=1 timeout 300 python benchmarks/bench_selfjoin.py --smoke

echo "[ci] distributed bench smoke (2 slabs: pair-set parity vs single-device fused join)"
XLA_FLAGS="--xla_force_host_platform_device_count=2" \
  timeout 300 python benchmarks/bench_selfjoin.py --mode distributed --smoke

echo "[ci] index bench smoke (device build bit-identical to host, downstream pairs identical)"
timeout 300 python benchmarks/bench_selfjoin.py --mode index --smoke

echo "[ci] metrics bench smoke (cosine + jaccard pair-set parity vs brute oracles)"
timeout 300 python benchmarks/bench_selfjoin.py --mode metrics --smoke

echo "[ci] reindex smoke (mid-load snapshot swap must not trip the no-retrace watchdog)"
timeout 180 python -m repro.launch.serve --arch selfjoin --requests 8 --reindex

echo "[ci] OK"
