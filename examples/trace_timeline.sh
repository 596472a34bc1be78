#!/bin/bash
# Cross-host distributed tracing end-to-end on plain CPU: a 2-process
# federation with span_trace on (host 1 deliberately slowed at every
# spill-exchange barrier via DLS_STRAGGLE_S), then the stitcher merges
# the per-host span journals into ONE timeline — per-round barrier skew
# with the straggling host named, per-host DCN-wait vs compute split,
# and a perfetto-loadable Chrome trace (open trace.json at
# https://ui.perfetto.dev). span_trace='off' (the default) compiles the
# exact pre-feature program; the bench gate bounds the 'on' overhead at
# 5% (scripts/compare_bench.py --span-overhead-threshold).
#
# JAX_PLATFORMS=cpu keeps both processes on the CPU backend.
set -e
PORT=${PORT:-8478}
OUT=${OUT:-/tmp/dls_trace_demo}
rm -rf "$OUT"
mkdir -p "$OUT/spans"

run() {
  JAX_PLATFORMS=cpu python -m distributed_learning_simulator_tpu.simulator \
    --dataset_name synthetic --model_name mlp --distributed_algorithm fed \
    --worker_number 8 --round 3 --epoch 1 --learning_rate 0.1 \
    --multihost true --coordinator_address "127.0.0.1:$PORT" \
    --num_processes 2 --process_id "$1" \
    --mesh_devices 2 --log_level INFO \
    --client_residency streamed --participation_fraction 0.5 \
    --participation_sampler hashed \
    --span_trace on --span_dir "$OUT/spans" --log_root "$OUT" \
    "${@:2}"
}

# Host 0 runs clean; host 1 sleeps 200 ms before every spill barrier —
# the stitched timeline must attribute the skew to host 1.
run 0 &
PID0=$!
DLS_STRAGGLE_S=0.2 run 1
wait $PID0

echo
echo "== stitched cross-host timeline =="
python scripts/trace_timeline.py "$OUT/spans" --out "$OUT/trace.json"
echo
echo "Chrome trace written to $OUT/trace.json (load in ui.perfetto.dev)"

# The run report composes the same stitcher: v12 span rollup from the
# primary's metrics.jsonl + the cross-host section from the journals.
METRICS=$(find "$OUT" -name metrics.jsonl | head -1)
if [ -n "$METRICS" ]; then
  echo
  echo "== report_run =="
  python scripts/report_run.py "$METRICS" --spans "$OUT/spans"
fi
