#!/bin/bash
# Run an n-replica testnet as local processes and commit a request through
# it — the no-Docker deployment check (reference README.md:411-458 runs the
# same flow by hand).  Usage: deploy/local_testnet.sh [n] [dir]
set -euo pipefail
N="${1:-3}"
DIR="${2:-$(mktemp -d /tmp/minbft-testnet.XXXXXX)}"
PORT=43700
cd "$(dirname "$0")/.."
export PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}"

python -m minbft_tpu.sample.peer testnet -n "$N" -d "$DIR" --base-port "$PORT"

pids=()
cleanup() { kill "${pids[@]}" 2>/dev/null || true; }
trap cleanup EXIT

# Each replica runs from its least-privilege keystore copy (only its own
# private material); the full keys.yaml stays client/operator-side.
# --no-batch: host crypto — these replicas never initialize a JAX backend,
# so none of them needs (or takes) a chip.  A chip belongs to one process:
# to put one replica on it, drop ITS --no-batch and keep JAX_PLATFORMS=cpu
# and --no-batch on the others (chip_smoke.py's deployment phase does).
for i in $(seq 0 $((N - 1))); do
    python -m minbft_tpu.sample.peer \
        --keys "$DIR/keys.replica$i.yaml" --config "$DIR/consensus.yaml" \
        run "$i" --no-batch >"$DIR/replica$i.log" 2>&1 &
    pids+=($!)
done

sleep 8
python -m minbft_tpu.sample.peer \
    --keys "$DIR/keys.yaml" --config "$DIR/consensus.yaml" \
    request "local-testnet-$(date +%s)"
echo "testnet OK (logs in $DIR)"
