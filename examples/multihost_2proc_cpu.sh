#!/bin/bash
# Multi-host (DCN) bring-up demo on plain CPU: two processes join one
# jax.distributed job over localhost, after which jax.devices() spans both
# processes and the ordinary mesh/sharding code runs the client axis
# across them (on a TPU pod, just pass --multihost true and let the
# environment auto-configure; the explicit flags below are for non-TPU
# clusters and CI). Each process must see the same worker_number and a
# mesh over the GLOBAL device count.
#
# JAX_PLATFORMS=cpu keeps both processes on the CPU backend, so the demo
# runs the same on a machine that has an accelerator.
set -e
PORT=${PORT:-8476}

run() {
  JAX_PLATFORMS=cpu python -m distributed_learning_simulator_tpu.simulator \
    --dataset_name synthetic --model_name mlp --distributed_algorithm fed \
    --worker_number 8 --round 3 --epoch 1 --learning_rate 0.1 \
    --multihost true --coordinator_address "127.0.0.1:$PORT" \
    --num_processes 2 --process_id "$1" \
    --mesh_devices 2 --log_level INFO \
    "${@:2}"
}

run 0 &
PID0=$!
run 1
wait $PID0

# The same topology with the DISTRIBUTED SHARD STORE (ISSUE 15): each
# process owns half the clients and serves its members of every round's
# owner-permuted cohort into its addressable mesh shards — streamed
# million-client residency composed with multi-process scale. Requires
# the hashed O(cohort) sampler (every host replays the full cohort per
# round); metrics gain the schema-v11 multihost sub-object.
PORT=$((PORT + 1))
run 0 \
  --client_residency streamed --participation_fraction 0.5 \
  --participation_sampler hashed &
PID0=$!
run 1 \
  --client_residency streamed --participation_fraction 0.5 \
  --participation_sampler hashed
wait $PID0
