"""FedQuant: quantized FedAvg (QAT locally, 8-bit stochastic exchange).

Replaces the reference's FedQuantServer/FedQuantWorker pair
(servers/fed_quant_server.py, workers/fed_quant_worker.py), whose *intent*
(per SURVEY 2.1#11-12 — both classes are broken as written against a stale
API) is: QAT local training + quantized bidirectional parameter exchange +
compression-ratio reporting. Here:

  * local training applies straight-through fake-quant to params inside the
    loss (ops/quantize.py fake_quant_tree) — the JAX-native QAT, replacing
    PyTorch's QuantizationAwareTraining attach (fed_quant_worker.py:19-20);
  * client uploads are stochastically quantized to ``levels`` levels then
    dequantized at the server before the weighted average (parity with
    fed_quant_server.py:25-39); the server's aggregated params are
    re-quantized for the downlink broadcast;
  * compression ratios are computed analytically (ops/payload.py) and
    reported every round, parity with the serialized-size logs at
    fed_quant_server.py:41-48;
  * every client's model is evaluated on the test set before aggregation
    and the global model after, per round (parity with the pre/post-
    aggregation accuracy logs at fed_quant_worker.py:55-69 — there each
    worker thread evaluates its own local model; here the per-client evals
    batch under one vmapped inference program). The evaluated model is the
    local QAT model BEFORE the quantized upload — the reference's
    observable (fed_quant_worker.py:55-58) — and the inference forward
    applies the QAT fake-quant transform, matching the reference's
    QAT-instrumented model at eval time. Disable with
    ``client_eval=False`` (the per-client stack must materialize, which
    caps feasible cohort size for large models).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distributed_learning_simulator_tpu.algorithms.fedavg import FedAvg
from distributed_learning_simulator_tpu.ops.payload import (
    compression_ratio,
    payload_bytes,
    quantized_payload_bytes,
)
from distributed_learning_simulator_tpu.ops.quantize import (
    dequantize_tree,
    fake_quant_tree,
    stochastic_quantize_tree,
)
from distributed_learning_simulator_tpu.telemetry.client_stats import (
    ClientStats,
)


class FedQuant(FedAvg):
    name = "fed_quant"

    # Per-client eval telemetry (reference fed_quant_worker.py:55-69) is
    # FedAvg-family machinery now — FedAvg.__init__ auto-enables it for
    # this algorithm at reference-like cohort sizes.

    @property
    def levels(self) -> int:
        # 256 levels = 8-bit, the reference's choice (fed_quant_server.py:37).
        return getattr(self.config, "quant_levels", 256)

    def client_param_transform(self):
        levels = self.levels
        if not getattr(self.config, "qat", True):
            return None
        return lambda params: fake_quant_tree(params, levels)

    def process_client_payload(self, client_params, key):
        """Simulate the quantized uplink: per-client stochastic quantize ->
        dequantize. Unbiased, so aggregation statistics match a real 8-bit
        wire exchange."""
        levels = self.levels
        n_clients = jax.tree_util.tree_leaves(client_params)[0].shape[0]
        keys = jax.random.split(key, n_clients)

        def one(params, k):
            return dequantize_tree(stochastic_quantize_tree(params, levels, k))

        return jax.vmap(one)(client_params, keys), {}

    def process_aggregated(self, global_params, key):
        """Simulate the quantized downlink broadcast.

        With ``client_stats`` on, also report the per-round mean-squared
        quantization error of that broadcast (device-side scalar; lands
        in the ``client_stats`` sub-object of the metrics record) — the
        payload-compression loss the analytic byte ratios cannot show.
        Trace-time gated: 'off' compiles the exact pre-feature program.
        """
        q = stochastic_quantize_tree(global_params, self.levels, key)
        deq = dequantize_tree(q)
        aux = {}
        if ClientStats.from_config(self.config) is not None:
            se = sum(
                jnp.sum((d.astype(jnp.float32) - g.astype(jnp.float32)) ** 2)
                for g, d in zip(
                    jax.tree_util.tree_leaves(global_params),
                    jax.tree_util.tree_leaves(deq),
                )
            )
            count = sum(
                g.size for g in jax.tree_util.tree_leaves(global_params)
            )
            aux["quant_mse"] = se / count
        return deq, aux

    def post_round(self, ctx):
        raw = payload_bytes(ctx.global_params)
        comp = quantized_payload_bytes(ctx.global_params, self.levels)
        ratio = compression_ratio(raw, comp)
        out = {
            "uplink_compression_ratio": ratio,
            "downlink_compression_ratio": ratio,
            "payload_bytes_raw": raw,
            "payload_bytes_quantized": comp,
        }
        out.update(super().post_round(ctx))  # client_eval telemetry
        return out
