"""SignSGD with majority vote — per-step synchronized 1-bit SGD.

Replaces the reference's SignSGDServer/SignSGDWorker pair
(servers/sign_sgd_server.py, workers/sign_sgd_worker.py). Reference
semantics (per SURVEY 3.3): every optimizer step, each worker computes its
effective SGD update direction (momentum/dampening/nesterov math replicated
at sign_sgd_worker.py:22-42), signs it (1-bit compression, :44), ships it to
the server, which sums signs elementwise and re-signs (majority vote,
sign_sgd_server.py:16-18); workers then apply weight decay plus
``p <- p - lr * voted_sign`` (:47-58). (The reference server is mis-wired —
its vote method is never invoked — so this implements the intended, fixed
behavior, SURVEY 2.1#13.)

TPU-native formulation: because every worker applies the same voted update,
all workers hold identical params at every step. So the round function keeps
ONE shared params pytree; per-step "communication" is a sign + sum + sign
over the client axis *inside* the step scan — the highest-frequency
communication pattern in the system becomes a fused reduction in a single
XLA program (an ICI psum when the client axis is sharded), instead of a
GPU->CPU->queue round-trip per optimizer step (sign_sgd_worker.py:44-46).

SGD is required, parity with the reference's assertion
(sign_sgd_worker.py:14).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distributed_learning_simulator_tpu.algorithms.base import (
    Algorithm,
    adapt_full_cohort_streamed,
)
from distributed_learning_simulator_tpu.ops.cohort import batched_take
from distributed_learning_simulator_tpu.ops.sign import (
    direction_leaf,
    momentum_leaf,
    vote_apply_leaf,
)
from distributed_learning_simulator_tpu.parallel.engine import (
    chunked_accumulate,
    make_loss_fn,
)
from distributed_learning_simulator_tpu.robustness.faults import (
    FailureModel,
    all_finite,
)
from distributed_learning_simulator_tpu.telemetry.client_stats import (
    ClientStats,
)


class SignSGD(Algorithm):
    name = "sign_SGD"
    # Streamed residency (config.client_residency='streamed'): the
    # per-step vote synchronizes EVERY client (the constructor rejects
    # participation_fraction < 1), so the "cohort" is always the whole
    # population — the round adapts to the streamed calling convention
    # via adapt_full_cohort_streamed and the data upload happens once.
    supports_streamed_residency = True

    def __init__(self, config):
        super().__init__(config)
        if config.optimizer_name.lower() != "sgd":
            raise ValueError(
                "sign_SGD requires the SGD optimizer "
                "(parity with reference sign_sgd_worker.py:14)"
            )
        if getattr(config, "augment", "none").lower() not in ("none", ""):
            # sign_SGD builds its own per-step sync loop that doesn't plumb
            # augmentation; reject rather than silently train un-augmented.
            raise ValueError(
                "sign_SGD does not support data augmentation; set "
                "augment='none'"
            )
        if getattr(config, "aggregation", "mean").lower() != "mean":
            # Aggregation IS the sign majority vote here; a robust-mean
            # setting would be silently meaningless.
            raise ValueError(
                "sign_SGD aggregates by sign majority vote; set "
                "aggregation='mean'"
            )
        if getattr(config, "local_compute_dtype", "float32") != "float32":
            # sign_SGD keeps ONE shared params tree (no per-client diverged
            # state to compress); reject rather than silently ignore.
            raise ValueError(
                "sign_SGD does not use local_compute_dtype; set it to "
                "'float32'"
            )
        if getattr(config, "participation_fraction", 1.0) < 1.0:
            # Per-step votes are over the FULL population (the reference
            # barrier, sign_sgd_server.py:13-18); reject rather than
            # silently train everyone.
            raise ValueError(
                "sign_SGD votes over every client each step; "
                "participation_fraction < 1 is not supported"
            )
        if FailureModel.from_config(config) is not None and getattr(
            config, "failure_mode", "none"
        ) in ("corrupt_nan", "corrupt_scale"):
            # The uplink here is a 1-bit sign vote — there is no
            # parameter-space payload to corrupt (sign(NaN) would poison
            # the vote sum itself, which models a broken SERVER, not a
            # faulty client). Dropout/straggler apply: a failed client's
            # votes are excluded and the threshold counts survivors only.
            raise ValueError(
                "sign_SGD supports failure_mode dropout/straggler only "
                "(its 1-bit vote has no parameter payload to corrupt); "
                f"got {config.failure_mode!r}"
            )

    def init_client_state(self, optimizer, global_params, n_clients):
        """Per-client momentum buffers + step counters (reference replicates
        torch-SGD momentum state per worker, sign_sgd_worker.py:22-42; the
        counter reproduces torch's buf-initialized-to-raw-gradient first
        step). With momentum 0 there is NO buffer (torch never allocates
        one) — at 1000 clients x ResNet-18 the buffers alone would be
        ~44 GB, so skipping them is what makes momentum-free sign_SGD run
        at large-model scale on one chip."""
        if self.config.momentum == 0.0:
            return None
        zeros = jax.tree_util.tree_map(jnp.zeros_like, global_params)
        momenta = jax.tree_util.tree_map(
            lambda z: jnp.broadcast_to(z, (n_clients,) + z.shape), zeros
        )
        return {"momenta": momenta, "steps": jnp.zeros(n_clients, jnp.int32)}

    def make_round_fn(self, apply_fn, optimizer, n_clients: int,
                      preprocess=None, client_sizes=None):
        # client_sizes (size-aware scheduling) is accepted but unused: the
        # per-step majority vote synchronizes EVERY client at every
        # optimizer step, so all clients must run the same step count.
        cfg = self.config
        lr = cfg.learning_rate
        mu = cfg.momentum
        dampening = getattr(cfg, "dampening", 0.0)
        nesterov = getattr(cfg, "nesterov", False)
        wd = cfg.weight_decay
        batch_size = cfg.batch_size
        epochs = cfg.epoch
        loss_fn = make_loss_fn(apply_fn)
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

        chunk = cfg.client_chunk_size
        has_momentum = mu != 0.0
        # Failure model (robustness/faults.py): dropout/straggler only (the
        # constructor rejects corrupt modes). Drawn ONCE per round from the
        # round key — a failed client misses the whole round's lockstep:
        # its per-step votes are excluded (the majority threshold counts
        # surviving voters only) and its momentum/step state freezes.
        # Every fm-gated branch is trace-time, so failure-free runs compile
        # the exact pre-feature program.
        fm = FailureModel.from_config(cfg)
        min_survivors = getattr(cfg, "min_survivors", 0)
        quorum = fm is not None or min_survivors > 0
        # Per-client stats (telemetry/client_stats.py): sign_SGD keeps ONE
        # shared params tree, so there is no per-client delta to score —
        # instead expose the per-step majority-vote agreement fraction
        # (computed and thrown away inside the vote until now) as a round
        # statistic. Trace-time gated like the failure model: 'off'
        # compiles the exact pre-feature program.
        cs = ClientStats.from_config(cfg)

        def round_fn(global_params, client_state, cx, cy, cmask, sizes, key,
                     lr_scale=1.0):
            # lr_scale: accepted for round-program signature uniformity;
            # config.validate() rejects non-constant schedules for sign_SGD
            # (the lr lives in the vote-apply, torch-parity semantics).
            del lr_scale
            del sizes  # vote is unweighted, parity with sign_sgd_server.py:16-18
            shard_size = cx.shape[1]
            steps_per_epoch = shard_size // batch_size
            if fm is not None:
                key, fault_key = jax.random.split(key)
                failed = fm.draw_failed(fault_key, n_clients)
                surv_f = (~failed).astype(jnp.float32)  # [C]
                n_surv = jnp.sum(surv_f).astype(jnp.int32)
                any_surv = n_surv > 0
            else:
                surv_f = None

            def chunk_compute(params, momenta_c, is_first_c, bx, by, bm,
                              surv_c=None):
                """Per-chunk: grads at the shared params -> torch-SGD
                direction -> partial sign-sum over the chunk's clients.
                ``surv_c`` (f32 0/1 per client; None when no failure model)
                zeroes excluded voters' signs, freezes their momenta, and
                drops them from the loss sum.
                Returns (vote partial sums, new momenta, summed loss)."""
                if preprocess is not None:
                    bx = jax.vmap(preprocess)(bx)
                (losses, _), grads = jax.vmap(
                    grad_fn, in_axes=(None, 0, 0, 0)
                )(params, bx, by, bm)
                if has_momentum:
                    # torch-SGD step math: ops/sign.py leaf formulas, the
                    # single source shared with the threaded oracle.
                    momenta_new = jax.tree_util.tree_map(
                        lambda m, g: momentum_leaf(
                            m, g,
                            is_first_c.reshape((-1,) + (1,) * (g.ndim - 1)),
                            mu, dampening,
                        ),
                        momenta_c, grads,
                    )
                    direction = jax.tree_util.tree_map(
                        lambda g, m: direction_leaf(g, m, mu, nesterov),
                        grads, momenta_new,
                    )
                else:
                    # torch allocates no buffer at momentum 0: the
                    # direction IS the raw gradient (nesterov with mu=0
                    # reduces to it too).
                    momenta_new = momenta_c
                    direction = grads
                if surv_c is None:
                    partial = jax.tree_util.tree_map(
                        lambda d: jnp.sum(jnp.sign(d), axis=0), direction
                    )
                    loss_sum = jnp.sum(losses)
                else:
                    partial = jax.tree_util.tree_map(
                        lambda d: jnp.sum(
                            jnp.sign(d)
                            * surv_c.reshape((-1,) + (1,) * (d.ndim - 1)),
                            axis=0,
                        ),
                        direction,
                    )
                    loss_sum = jnp.sum(losses * surv_c)
                    if has_momentum:
                        momenta_new = jax.tree_util.tree_map(
                            lambda old, new: jnp.where(
                                surv_c.reshape(
                                    (-1,) + (1,) * (new.ndim - 1)
                                ) > 0,
                                new, old,
                            ),
                            momenta_c, momenta_new,
                        )
                return partial, momenta_new, loss_sum

            def epoch_body(carry, epoch_key):
                params, momenta, step_counts = carry
                perm_keys = jax.random.split(epoch_key, n_clients)
                perms = jax.vmap(
                    lambda k: jax.random.permutation(k, shard_size)
                )(perm_keys)  # [C, S]

                def step_body(carry, step):
                    params, momenta, step_counts = carry
                    idx = jax.lax.dynamic_slice_in_dim(
                        perms, step * batch_size, batch_size, axis=1
                    )  # [C, B]
                    # Per-client minibatch gather over the client axis:
                    # ops/cohort.batched_take, the ONE copy shared with
                    # the FedAvg-family cohort index ops.
                    bx = batched_take(cx, idx)
                    by = batched_take(cy, idx)
                    bm = batched_take(cmask, idx)
                    is_first = step_counts == 0  # [C]

                    if chunk is None or chunk >= n_clients:
                        vote_sum, momenta_new, loss_sum = chunk_compute(
                            params, momenta, is_first, bx, by, bm, surv_f
                        )
                    else:
                        # Chunked vote: per-client gradients exist only
                        # chunk-at-a-time; partial sign-sums accumulate into
                        # the vote so the full [n_clients, n_params] gradient
                        # stack never materializes (at 1000 clients x
                        # ResNet-18 it would be ~44 GB). chunked_accumulate
                        # (parallel/engine.py) holds the reshape/scan/
                        # remainder discipline — any chunk size works.
                        def compute(chunk_trees, _pc):
                            if surv_f is None:
                                m_c, f_c, bx_c, by_c, bm_c = chunk_trees
                                s_c = None
                            else:
                                m_c, f_c, bx_c, by_c, bm_c, s_c = chunk_trees
                            partial, m_new, l_sum = chunk_compute(
                                params, m_c, f_c, bx_c, by_c, bm_c, s_c
                            )
                            return (partial, l_sum), m_new

                        acc0 = (
                            jax.tree_util.tree_map(
                                lambda p: jnp.zeros_like(p, jnp.float32),
                                params,
                            ),
                            jnp.float32(0.0),
                        )
                        trees = (momenta, is_first, bx, by, bm)
                        if surv_f is not None:
                            trees = trees + (surv_f,)
                        (vote_sum, loss_sum), momenta_new = (
                            chunked_accumulate(
                                trees, chunk,
                                compute, acc0,
                                shards=cfg.mesh_devices or 1,
                            )
                        )
                    # sign of the summed signs: the majority vote
                    # (sign_sgd_server.py:16-18) — over surviving voters
                    # only when a failure model is active (excluded signs
                    # contribute 0 to the sum).
                    voted = jax.tree_util.tree_map(jnp.sign, vote_sum)
                    new_params = jax.tree_util.tree_map(
                        lambda p, v: vote_apply_leaf(p, v, lr, wd),
                        params, voted,
                    )
                    if surv_f is not None:
                        # A zero-survivor round must not silently apply
                        # weight decay (no client stepped at all); steps
                        # advance only for clients that participated.
                        new_params = jax.tree_util.tree_map(
                            lambda nw, od: jnp.where(any_surv, nw, od),
                            new_params, params,
                        )
                        step_inc = surv_f.astype(jnp.int32)
                        denom = jnp.maximum(n_surv, 1).astype(jnp.float32)
                    else:
                        step_inc = 1
                        denom = n_clients
                    step_out = loss_sum / denom
                    if cs is not None:
                        # Majority-vote agreement fraction: a coordinate
                        # with vote sum v over V voters has (V + |v|) / 2
                        # voters agreeing with the majority, so the mean
                        # agreement over all P coordinates is
                        # 1/2 + mean|v| / (2V). 1.0 = unanimous step,
                        # 0.5 = coin-flip gradient directions.
                        n_params_total = sum(
                            v.size
                            for v in jax.tree_util.tree_leaves(vote_sum)
                        )
                        abs_sum = sum(
                            jnp.sum(jnp.abs(v).astype(jnp.float32))
                            for v in jax.tree_util.tree_leaves(vote_sum)
                        )
                        agree = 0.5 + abs_sum / (
                            2.0 * denom * n_params_total
                        )
                        step_out = (step_out, agree)
                    return (
                        new_params, momenta_new, step_counts + step_inc
                    ), step_out

                (params, momenta, step_counts), step_outs = jax.lax.scan(
                    step_body, (params, momenta, step_counts),
                    jnp.arange(steps_per_epoch),
                )
                if cs is not None:
                    step_losses, step_agree = step_outs
                    return (params, momenta, step_counts), (
                        jnp.mean(step_losses), jnp.mean(step_agree)
                    )
                return (params, momenta, step_counts), jnp.mean(step_outs)

            epoch_keys = jax.random.split(key, epochs)
            if has_momentum:
                momenta0 = client_state["momenta"]
                steps0 = client_state["steps"]
            else:
                momenta0 = None
                steps0 = jnp.zeros(n_clients, jnp.int32)
            carry0 = (global_params, momenta0, steps0)
            (params, momenta, step_counts), epoch_outs = jax.lax.scan(
                epoch_body, carry0, epoch_keys
            )
            if cs is not None:
                epoch_losses, epoch_agree = epoch_outs
            else:
                epoch_losses = epoch_outs
            aux = {
                "mean_client_loss": epoch_losses[-1],
                "sync_steps": jnp.asarray(epochs * steps_per_epoch),
            }
            if cs is not None:
                # Round-mean vote agreement (per-step fractions averaged
                # over the round's epochs x steps).
                aux["vote_agreement"] = jnp.mean(epoch_agree)
            if quorum:
                # Quorum policy (mirrors fedavg.round_fn): reject the round
                # — revert to the round-start params — when survivors fall
                # below min_survivors or the voted params went non-finite.
                # Momentum/step state keeps its per-client masking (failed
                # clients froze themselves above); rejection only refuses
                # the SHARED model the round produced.
                survivor_count = (
                    n_surv if fm is not None
                    else jnp.asarray(n_clients, jnp.int32)
                )
                finite = all_finite(params)
                rejected = (~finite) | (survivor_count < min_survivors)
                params = jax.tree_util.tree_map(
                    lambda nw, od: jnp.where(rejected, od.astype(nw.dtype), nw),
                    params, global_params,
                )
                aux["survivor_count"] = survivor_count
                aux["round_rejected"] = rejected
            new_state = (
                {"momenta": momenta, "steps": step_counts}
                if has_momentum else None
            )
            return params, new_state, aux

        if (
            getattr(cfg, "client_residency", "resident").lower()
            == "streamed"
        ):
            # Full-cohort streamed convention: identical program, the
            # idx operand (always None here) absorbed by the adapter.
            return adapt_full_cohort_streamed(round_fn)
        return round_fn

    def post_round(self, ctx):
        from distributed_learning_simulator_tpu.ops.payload import (
            compression_ratio,
            payload_bytes,
            sign_payload_bytes,
        )

        raw = payload_bytes(ctx.global_params)
        signed = sign_payload_bytes(ctx.global_params)
        return {
            "uplink_compression_ratio": compression_ratio(raw, signed),
            "payload_bytes_sign": signed,
        }
