#!/bin/bash
# Multi-chip sharding validated on 8 virtual CPU devices (no TPU pod needed):
# the client axis gets PartitionSpec("clients") over a 1-D mesh and
# aggregation lowers to cross-device collectives. On a real pod slice, drop
# the two env vars and set --mesh_devices to the real chip count.
JAX_PLATFORMS=cpu \
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
python -m distributed_learning_simulator_tpu.simulator \
  --dataset_name synthetic --model_name mlp \
  --distributed_algorithm fed \
  --worker_number 64 --round 3 --epoch 1 --learning_rate 0.1 \
  --mesh_devices 8 --n_train 4096 --n_test 512 --log_level INFO
