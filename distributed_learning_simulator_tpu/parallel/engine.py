"""Client-axis training engine: local training as scan, clients as vmap.

Replaces the reference's per-worker ``Trainer`` objects driven by one OS
thread each (reference workers/fed_worker.py:19-27: block for global params,
run E local epochs, ship params). Here a client's local training run is a
pure function

    local_train(params, shard_x, shard_y, mask, key) -> (params', metrics)

built as ``lax.scan`` over epochs x steps (compiler-friendly: static shapes,
no Python control flow inside jit), and the whole client population is
``vmap(local_train)`` — N clients train in lockstep as one batched XLA
program, with every matmul carrying the client axis as an extra batch
dimension onto the MXU.

Padding discipline: shards are fixed-size with 0/1 sample masks
(data/partition.py); masked samples contribute zero loss and zero gradient,
so Dirichlet/heterogeneous shards need no recompilation.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax

from distributed_learning_simulator_tpu.ops.quantize import hash_mix


def make_optimizer(name: str, learning_rate: float, momentum: float = 0.0,
                   weight_decay: float = 0.0):
    """Optimizer registry, parity with the reference's ``--optimizer_name``
    flag (reference simulator.sh:1; SGD is the reference default and the
    required optimizer for SignSGD, sign_sgd_worker.py:14)."""
    key = name.lower()
    if key == "sgd":
        tx = optax.sgd(learning_rate, momentum=momentum or None)
    elif key == "adam":
        tx = optax.adam(learning_rate)
    elif key == "adamw":
        tx = optax.adamw(learning_rate, weight_decay=weight_decay)
    else:
        raise ValueError(f"unknown optimizer {name!r}")
    if weight_decay and key == "sgd":
        tx = optax.chain(optax.add_decayed_weights(weight_decay), tx)
    return tx


def split_model_output(out):
    """``(logits, counts)``: a model returns its outputs alone or with a
    dict of counters of the batch (an expert layer's routing counts,
    models/solar_open2.py); ``counts`` is ``{}`` for a model that has
    none, which adds nothing to the traced program."""
    if isinstance(out, tuple):
        return out
    return out, {}


def _target_mask(y, mask):
    return jnp.broadcast_to(mask[:, None], y.shape) if y.ndim == 2 else mask


def target_nll(logits, y, mask):
    """``(nll, mask)`` of softmax cross-entropy, one value a target:
    ``y`` ``[B]`` over ``logits`` ``[B, V]``, or one target a position,
    ``[B, T]`` over ``[B, T, V]``; ``mask`` ``[B]`` marks the real
    samples and comes back in the targets' shape."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    nll = -jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]
    return nll, _target_mask(y, mask)


def makes_own_loss(outputs) -> bool:
    """Whether a model handed on, in place of logits, a head that has not
    made them (``weighted_nll(targets, weight) -> (sum weight * nll, sum
    weight * correct)``; ``logits()`` for whoever wants them): the loss
    and its gradient are then the head's to make, and no array of
    vocabulary width crosses this seam (models/solar_open2.py
    ``head_nll``). An array of logits takes the path it always took."""
    return hasattr(outputs, "weighted_nll")


def make_loss_fn(apply_fn, param_transform: Callable | None = None):
    """Masked softmax cross-entropy + accuracy.

    ``param_transform`` hooks QAT: e.g. ``fake_quant_tree`` applied to params
    inside the loss gives straight-through-estimator quantization-aware
    training (replaces reference workers/fed_quant_worker.py:19-20).

    Returns ``(loss, (acc, counts))``, ``counts`` the model's counters of
    the batch (:func:`split_model_output`).
    """

    def loss_fn(params, x, y, mask):
        p = param_transform(params) if param_transform is not None else params
        logits, counts = split_model_output(apply_fn({"params": p}, x))
        if makes_own_loss(logits):
            mask = _target_mask(y, mask)
            loss, acc = logits.weighted_nll(
                y, mask / jnp.maximum(jnp.sum(mask), 1.0))
            return loss, (acc, counts)
        nll, mask = target_nll(logits, y, mask)
        denom = jnp.maximum(jnp.sum(mask), 1.0)
        loss = jnp.sum(nll * mask) / denom
        acc = jnp.sum((jnp.argmax(logits, axis=-1) == y) * mask) / denom
        return loss, (acc, counts)

    return loss_fn


def make_decoder(sample_shape):
    """Batch decoder for compact (uint8-flattened) client storage: cast,
    rescale to [0, 1], restore the sample shape. See ClientData.compact."""

    def decode(b):
        return (b.astype(jnp.float32) / 255.0).reshape(
            (b.shape[0],) + tuple(sample_shape)
        )

    return decode


def _sr_to_bf16(x32, salt):
    """Stochastically round an f32 array to bf16 storage (hash dither).

    bf16 keeps the top 16 bits of the f32 pattern; adding a uniform random
    16-bit value below the cut before truncating rounds each weight up with
    probability equal to its truncated fraction — unbiased, so updates
    smaller than the weight's bf16 ulp survive in expectation. Without
    this, bf16 local state silently stalls long-horizon training: the
    round-to-nearest broadcast cast quantizes identically for every client
    and the per-step stores swallow the common-mode (mean-gradient)
    component of every update the same way on every client, so aggregation
    cannot recover it (measured: 0.49 vs 0.69 final accuracy at 50 bench
    rounds; per-client decorrelation is the load-bearing property).

    The dither is a multiplicative hash of the value bits mixed with a
    per-(client, call-site) salt — pure fused elementwise ALU, no PRNG
    tensor generated or moved. A real counter PRNG
    (``lax.rng_bit_generator``) costs ~15% of the ResNet-18 round in
    generation traffic alone; the hash is free (within noise) and
    empirically matches f32 final accuracy on every config tested, with
    statistical unbiasedness covered by tests/test_utils.py. Returns
    (bf16 array, advanced salt).
    """
    u = jax.lax.bitcast_convert_type(x32, jnp.uint32)
    h = hash_mix(u, salt)  # ops/quantize.py: the one copy of the mixing
    u = (u + (h & jnp.uint32(0xFFFF))) & jnp.uint32(0xFFFF0000)
    rounded = jax.lax.bitcast_convert_type(u, jnp.float32)
    return rounded.astype(jnp.bfloat16), salt + jnp.uint32(0x9E3779B9)


def _sr_tree_to_bf16(tree, salt):
    """Stochastically round every leaf of an f32 pytree to bf16, threading
    the dither salt through the leaves. Used for both SR sites (broadcast
    cast and per-step param store)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out = []
    for x in leaves:
        r, salt = _sr_to_bf16(x.astype(jnp.float32), salt)
        out.append(r)
    return jax.tree_util.tree_unflatten(treedef, out), salt


#: A local run of at most this many optimizer steps (epochs x steps per
#: epoch, both read from shapes while tracing) is unrolled: each step is
#: compiled as its own copy, so XLA sees that the first step's optimizer
#: state is ``optimizer.init``'s zeros and that the last step's is dead
#: (under ``reset_optimizer``), moves neither through HBM, and fuses the
#: last step's update into the reduce over clients. That is 2 of the
#: update's 4*S state transfers (momentum and parameters, in and out) and
#: the last parameter store: a quarter of them at S = 2, an eighth at
#: S = 4, while the compiled program and its cold compile grow S-fold. 2 is
#: the step count the benchmark's cells run and the only one measured on
#: the chip (+3.5 % client-rounds/s on the flagship; PERF.md § 6, PR 26).
UNROLL_MAX_LOCAL_STEPS = 2


def local_steps_unrolled(local_epochs: int, steps_per_epoch: int) -> int:
    """How many local steps ``local_train`` compiles unrolled for a run of
    ``local_epochs`` x ``steps_per_epoch`` steps: all of them up to
    :data:`UNROLL_MAX_LOCAL_STEPS`, else 0 (both scans stay rolled)."""
    total = local_epochs * steps_per_epoch
    return total if total <= UNROLL_MAX_LOCAL_STEPS else 0


def make_local_train_fn(
    apply_fn,
    optimizer,
    local_epochs: int,
    batch_size: int,
    param_transform: Callable | None = None,
    reset_optimizer: bool = True,
    preprocess: Callable | None = None,
    augment: Callable | None = None,
    compute_dtype=None,
    collect_stats: bool = False,
    accumulate_updates: bool = False,
):
    """Build ``local_train(params, opt_state, xs, ys, mask, key)``.

    E epochs over the client's fixed-size shard, fresh random permutation per
    epoch, minibatches of ``batch_size`` (shard_size must be a multiple —
    data/partition.py guarantees it). Matches the reference hot loop
    ``for _ in range(E): epoch of SGD`` (external Trainer.train called at
    fed_worker.py:25-27) but as two nested ``lax.scan``s.

    What one step moves through HBM per client, counted in the flagship's
    device trace (PERF.md § 5): the parameters are read three times (the
    forward, the input-gradient and the weight-gradient convolution, the
    last fused with the momentum trace, the update and the stochastic
    rounding) and written once, the momentum is read once and written
    once; around the steps the broadcast's rounding writes the parameters
    and the reduce reads them. The scans carry that state through a
    ``while`` loop, so a run of S steps makes 6*S + 2 such transfers. A run
    of at most :data:`UNROLL_MAX_LOCAL_STEPS` steps is unrolled instead
    (``lax.scan``'s own ``unroll``: the body is still traced and lowered
    once): XLA then drops the first step's momentum read (zeros from
    ``optimizer.init``), the last step's momentum write (dead under
    ``reset_optimizer``) and, fusing the last update into the reduce, the
    last parameter write and the reduce's read — 10 transfers for 14 at
    S = 2. The mathematics, the rounding salts and the permutation stream
    are the scan's.

    vmap over the client axis: ``jax.vmap(local_train, in_axes=(None, 0, 0,
    0, 0, 0))`` — global params broadcast (the init-model broadcast of
    fed_server.py:19-24), everything else per-client.

    ``compute_dtype`` (e.g. ``jnp.bfloat16``): store the per-client DIVERGED
    params/grads/momenta in this dtype for the duration of the local run.
    These buffers exist per in-flight client — at 1000 clients x ResNet-18
    they are the round's dominant HBM traffic — and only live within one
    round: the f32 global model is the broadcast source every round and the
    aggregation accumulates client params in f32 (fedavg.py reduce_chunk),
    so precision loss is confined to a few local SGD steps, the regime where
    bf16 training is standard practice.

    ``collect_stats`` (telemetry/client_stats.py): additionally report
    ``loss_first`` (the very first optimizer step's batch loss — the
    local loss at the incoming global params) and ``grad_sq_mean`` (mean
    per-step squared gradient L2 norm) in the metrics dict. A trace-time
    flag: False (the default) compiles the exact pre-feature program and
    consumes no extra RNG either way.

    ``accumulate_updates`` builds, from the same body, ``add_updates(
    update_sum, weight, params, opt_state, xs, ys, mask, key)`` ->
    ``(update_sum, opt_state, metrics)``: the client trains as above and
    every step's optimizer update, times ``weight``, is added into the
    f32 tree ``update_sum`` as it is computed; no parameters come back
    (the last store is dead). The round then moves the global model by
    the weighted mean UPDATE. Where the local state is bf16 that keeps
    the stochastic rounding of the broadcast copy and of each store out
    of the global model: summed as parameters, 8 clients' roundings leave
    about 20 times the norm of a two-round update at 841 M parameters
    (PERF.md § 6, PR 29); the rounding still decides where each gradient
    is taken. The engine's path for one client at a time
    (``algorithms/fedavg.py``), which has no room for a third
    parameter-sized tensor.
    """
    loss_fn = make_loss_fn(apply_fn, param_transform)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    sr_enabled = compute_dtype == jnp.bfloat16

    def run(params, opt_state, xs, ys, mask, key, lr_scale, update_sum,
            weight):
        sr_state = jnp.uint32(0)
        if sr_enabled:
            # Per-client dither salt from the client's key: independent
            # rounding decisions across clients under vmap (the property
            # the aggregate's unbiasedness rests on — see _sr_to_bf16).
            sr_state = jax.random.key_data(
                jax.random.fold_in(key, 7)
            ).reshape(-1)[0].astype(jnp.uint32)
            # The broadcast cast f32 global -> bf16 must be stochastic TOO:
            # round-to-nearest here is the same bias for every client, i.e.
            # the global model gets deterministically re-quantized to bf16
            # resolution every round and progress below one bf16 ulp is
            # erased. With per-client SR the 1000-client aggregate
            # preserves the f32 global to ~ulp/sqrt(N).
            params, sr_state = _sr_tree_to_bf16(params, sr_state)
        elif compute_dtype is not None:
            params = jax.tree_util.tree_map(
                lambda p: p.astype(compute_dtype), params
            )
        shard_size = xs.shape[0]
        steps_per_epoch = shard_size // batch_size
        # One traced body either way; unrolled, XLA sees the iterations
        # apart (UNROLL_MAX_LOCAL_STEPS).
        unroll = local_steps_unrolled(local_epochs, steps_per_epoch) > 0
        aug_key = None
        if augment is not None:
            # Split only when augmenting so the un-augmented RNG stream
            # (shuffles) is unchanged by this feature.
            key, aug_key = jax.random.split(key)
        if reset_optimizer:
            # Fresh optimizer every round (standard FedAvg). The incoming
            # opt_state is ignored and None is returned in its place — at
            # 1000-client scale a returned per-client optimizer state would
            # be dead weight the size of the whole model per client.
            opt_state = optimizer.init(params)

        def epoch_body(carry, scan_in):
            epoch_key, epoch_idx = scan_in
            params, opt_state, sr_state, update_sum = carry
            perm = jax.random.permutation(epoch_key, shard_size)

            def step_body(carry, step):
                params, opt_state, sr_state, update_sum = carry
                if unroll:
                    # What a loop carry gives for free: the parameters
                    # exist in HBM at the step's entry. Without it XLA
                    # recomputes their stochastic rounding inside every
                    # convolution that reads them — fewer bytes, but the
                    # flagship round 1.7 % slower (PERF.md § 6, PR 26).
                    # The optimizer state stays free to fold.
                    params = jax.lax.optimization_barrier(params)
                    if update_sum is not None:
                        # Likewise the sum so far: left free, the adds of
                        # one step wait for the next and keep that step's
                        # gradients alive beside it (1.4 GB at 841 M
                        # parameters, by the compiler's count).
                        update_sum = jax.lax.optimization_barrier(update_sum)
                idx = jax.lax.dynamic_slice_in_dim(
                    perm, step * batch_size, batch_size
                )
                bx = jnp.take(xs, idx, axis=0)
                by = jnp.take(ys, idx, axis=0)
                bm = jnp.take(mask, idx, axis=0)
                if preprocess is not None:
                    bx = preprocess(bx)
                if augment is not None:
                    # Fresh per-(epoch, step) augmentation randomness,
                    # independent of the shuffle keys.
                    bx = augment(
                        bx, jax.random.fold_in(jax.random.fold_in(
                            aug_key, epoch_idx), step),
                    )
                (loss, (acc, counts)), grads = grad_fn(params, bx, by, bm)
                updates, opt_state = optimizer.update(grads, opt_state, params)
                # Round-level lr schedule (config.lr_schedule): the per-round
                # factor multiplies the final update, which is EXACT for
                # both sgd (lr sits outside the momentum buffer, torch
                # semantics) and adam (lr sits outside the normalization) —
                # equivalent to rebuilding the optimizer with lr*factor but
                # without retracing. f32 math, original dtype preserved.
                updates = jax.tree_util.tree_map(
                    lambda u: (
                        u.astype(jnp.float32) * lr_scale
                    ).astype(u.dtype),
                    updates,
                )
                if update_sum is not None:
                    # The step's own update, before any rounding of the
                    # stored parameters, goes into the round's aggregate.
                    update_sum = jax.tree_util.tree_map(
                        lambda a, u: a + weight * u.astype(jnp.float32),
                        update_sum, updates,
                    )
                if sr_enabled:
                    # f32 update math, stochastically-rounded bf16 storage:
                    # plain bf16 apply_updates swallows updates below the
                    # weight's bf16 ulp (see _sr_to_bf16).
                    summed = jax.tree_util.tree_map(
                        lambda p, u: (
                            p.astype(jnp.float32) + u.astype(jnp.float32)
                        ),
                        params, updates,
                    )
                    params, sr_state = _sr_tree_to_bf16(summed, sr_state)
                else:
                    params = optax.apply_updates(params, updates)
                step_out = (loss, acc)
                if collect_stats:
                    # Exact per-step gradient L2 norm (f32 even when the
                    # local run computes in bf16).
                    grad_sq = sum(
                        jnp.sum(g.astype(jnp.float32) ** 2)
                        for g in jax.tree_util.tree_leaves(grads)
                    )
                    step_out = (loss, acc, grad_sq)
                carry = (params, opt_state, sr_state, update_sum)
                return carry, (step_out, counts)

            carry, (step_outs, counts) = jax.lax.scan(
                step_body, carry, jnp.arange(steps_per_epoch), unroll=unroll,
            )
            if collect_stats:
                losses, accs, grad_sqs = step_outs
                epoch_out = (
                    jnp.mean(losses), jnp.mean(accs),
                    losses[0], jnp.mean(grad_sqs),
                )
            else:
                losses, accs = step_outs
                epoch_out = (jnp.mean(losses), jnp.mean(accs))
            return carry, (epoch_out, _sum_leading(counts))

        epoch_keys = jax.random.split(key, local_epochs)
        (params, opt_state, sr_state, update_sum), (epoch_outs, counts) = (
            jax.lax.scan(
                epoch_body, (params, opt_state, sr_state, update_sum),
                (epoch_keys, jnp.arange(local_epochs)), unroll=unroll,
            )
        )
        if collect_stats:
            epoch_losses, epoch_accs, first_losses, grad_means = epoch_outs
            metrics = {
                "loss": epoch_losses[-1],
                "accuracy": epoch_accs[-1],
                # First epoch's first step: the loss of the INCOMING
                # global params on this client's first batch.
                "loss_first": first_losses[0],
                "grad_sq_mean": jnp.mean(grad_means),
            }
        else:
            epoch_losses, epoch_accs = epoch_outs
            metrics = {"loss": epoch_losses[-1], "accuracy": epoch_accs[-1]}
        if counts:
            # A model's counters, summed over the run's batches
            # (telemetry: an expert layer's routing counts).
            metrics["model_counts"] = _sum_leading(counts)
        opt_state = None if reset_optimizer else opt_state
        return params, opt_state, metrics, update_sum

    def local_train(params, opt_state, xs, ys, mask, key, lr_scale=1.0):
        return run(
            params, opt_state, xs, ys, mask, key, lr_scale, None, None
        )[:3]

    def add_updates(update_sum, weight, params, opt_state, xs, ys, mask,
                    key, lr_scale=1.0):
        _, opt_state, metrics, update_sum = run(
            params, opt_state, xs, ys, mask, key, lr_scale, update_sum,
            weight,
        )
        return update_sum, opt_state, metrics

    return add_updates if accumulate_updates else local_train


def _sum_leading(tree):
    return jax.tree_util.tree_map(lambda c: jnp.sum(c, axis=0), tree)


def chunked_accumulate(trees, chunk: int, compute_fn, acc0, per_chunk=None,
                       shards: int = 1):
    """Sequential-over-chunks client scan with remainder handling — the ONE
    copy of the slice/reshape/scan/concatenate discipline shared by the
    FedAvg fused reduction (algorithms/fedavg.py train_and_reduce) and the
    sign_SGD per-step vote (algorithms/sign_sgd.py): both bound HBM by
    processing ``chunk`` clients at a time while accumulating a reduction,
    and both must hand remainder clients (C % chunk) their own call so the
    memory bound never silently degrades.

    ``trees``: pytree of client-stacked arrays ``[C, ...]`` (None leaves
    allowed — e.g. absent momentum buffers). ``per_chunk``: optional PRNG
    key; the helper splits it into one key per chunk plus one for the
    remainder call (splitting happens HERE so callers can't mis-size the
    key array against this function's own chunk count).
    ``compute_fn(chunk_trees, per_chunk_key) -> (partial, per_client)``:
    ``partial`` is tree-added into ``acc0``; ``per_client`` (leading chunk
    axis, None allowed) is restacked to ``[C, ...]``. Returns
    ``(accumulated, per_client_full)``.

    ``shards``: how many devices the client axis is split over
    (``config.mesh_devices``). Device ``d`` holds the contiguous clients
    ``[d*C/shards, (d+1)*C/shards)``, so a chunk of ``chunk`` CONSECUTIVE
    clients lives on one or two devices: cutting chunks that way makes
    the SPMD partitioner all-gather the population and train every
    client on every device. Instead each chunk takes ``chunk // shards``
    clients from EVERY shard — a reshape/transpose of the sharded axis
    that moves no data, leaves each scan step split evenly over the
    devices, and keeps ``chunk`` the number of clients in flight across
    the mesh. Which clients share a chunk is bookkeeping: per-client
    results return in client order and only the order of the partial
    sums differs from ``shards=1``. A cohort that does not divide into
    ``shards`` falls back to consecutive chunks.
    """
    n = jax.tree_util.tree_leaves(trees)[0].shape[0]
    if n % shards:
        shards = 1
    per = n // shards
    local = max(1, chunk // shards)
    n_chunks, rem = divmod(per, local)

    def by_shard(a):
        return a.reshape((shards, per) + a.shape[1:])

    def chunked(a):
        # [C, ...] -> [n_chunks, shards * local, ...]
        a = by_shard(a)[:, : per - rem]
        a = a.reshape((shards, n_chunks, local) + a.shape[2:])
        return jnp.swapaxes(a, 0, 1).reshape(
            (n_chunks, shards * local) + a.shape[3:]
        )

    def unchunked(a):
        # [n_chunks, shards * local, ...] -> [shards, per - rem, ...]
        a = a.reshape((n_chunks, shards, local) + a.shape[2:])
        return jnp.swapaxes(a, 0, 1).reshape(
            (shards, per - rem) + a.shape[3:]
        )

    xs = jax.tree_util.tree_map(chunked, trees)
    keys = None
    if per_chunk is not None:
        keys = jax.random.split(per_chunk, n_chunks + 1)
    scan_xs = xs if keys is None else (xs, keys[:n_chunks])

    def body(acc, scan_in):
        if per_chunk is None:
            chunk_trees, pc = scan_in, None
        else:
            chunk_trees, pc = scan_in
        partial, per_client = compute_fn(chunk_trees, pc)
        return jax.tree_util.tree_map(jnp.add, acc, partial), per_client

    acc, stacked = jax.lax.scan(body, acc0, scan_xs)
    per_client = jax.tree_util.tree_map(unchunked, stacked)
    if rem:
        tail = jax.tree_util.tree_map(
            lambda a: by_shard(a)[:, per - rem:].reshape(
                (shards * rem,) + a.shape[1:]
            ),
            trees,
        )
        partial_t, per_client_t = compute_fn(
            tail, None if keys is None else keys[-1]
        )
        acc = jax.tree_util.tree_map(jnp.add, acc, partial_t)
        per_client = jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate(
                [a, b.reshape((shards, rem) + b.shape[1:])], axis=1
            ),
            per_client, per_client_t,
        )
    per_client = jax.tree_util.tree_map(
        lambda a: a.reshape((n,) + a.shape[2:]), per_client
    )
    return acc, per_client


def make_experiment_round_fn(round_fn, lr_schedule: bool):
    """vmap a resident-convention round fn over a leading EXPERIMENT axis
    (the sweep engine's vmapped fleet, sweep/engine.py).

    Each experiment carries its own global params and RNG key chain
    (stacked ``[E, ...]`` / ``[E]`` operands); the client data, masks and
    sizes broadcast (``in_axes=None`` — one shared partition, the sweep
    data contract). The per-experiment body replays the solo host loop's
    round sequence exactly — ``key, round_key = jax.random.split(key)``
    then the round program — so experiment ``i``'s outputs are
    bit-identical to a solo run whose loop holds that key
    (tests/test_sweep.py pins it). ``jax.random.split`` is elementwise on
    the key data, so the vmapped split equals the solo eager split
    bit-for-bit; everything downstream is the same XLA ops with one more
    batch dimension.

    ``lr_schedule`` (trace-time): when True
    the returned function takes a ``[E]`` f32 vector — per-experiment lr
    factor x the round's schedule factor — consumed with ``in_axes=0``;
    when False the round fn is called WITHOUT the operand so the
    constant default constant-folds exactly like the solo program.

    Returns ``fleet(params_E, keys_E, cx, cy, cmask, sizes[, lr_vec]) ->
    (new_params_E, new_keys_E, aux_E)``. Per-client state is not carried
    (the sweep spec refuses persistent client state for fleets — E full
    per-client stacks would defeat the memory envelope).
    """

    def one(params, key, cx, cy, cmask, sizes, lr=None):
        key, round_key = jax.random.split(key)
        args = (params, None, cx, cy, cmask, sizes, round_key)
        if lr is not None:
            args = args + (lr,)
        new_params, _state, aux = round_fn(*args)
        return new_params, key, aux

    data_axes = (None, None, None, None)

    def fleet(params_e, keys_e, cx, cy, cmask, sizes, lr_vec=None):
        if lr_schedule:
            return jax.vmap(one, in_axes=(0, 0) + data_axes + (0,))(
                params_e, keys_e, cx, cy, cmask, sizes, lr_vec
            )
        return jax.vmap(one, in_axes=(0, 0) + data_axes)(
            params_e, keys_e, cx, cy, cmask, sizes
        )

    return fleet


def make_experiment_eval_fn(eval_fn, n_eval_operands: int):
    """vmap a server-eval fn over the experiment axis: stacked params,
    broadcast test batches — the fleet's one-dispatch evaluation of all
    E experiment models (pairs with :func:`make_experiment_round_fn`;
    kept a SEPARATE jitted program like the solo loop's ``evaluate``, so
    the fleet's program structure mirrors the solo round/eval pair)."""
    return jax.vmap(eval_fn, in_axes=(0,) + (None,) * n_eval_operands)


def make_reshaper(sample_shape):
    """Batch preprocess for flattened eval storage: restore sample shape.

    Feeding eval batches as ``[B, prod(shape)]`` instead of ``[B, H, W, C]``
    matters on TPU: device arrays are tiled (8, 128) over the trailing two
    dims, so an explicit 3-channel NHWC input buffer pads its lane dim
    3 -> 128 (a ~40x HBM inflation); a flat last dim has no such padding,
    and XLA picks good layouts for the in-program reshape.
    """

    def reshape(b):
        return b.reshape((b.shape[0],) + tuple(sample_shape))

    return reshape


def pad_eval_set(x, y, batch_size: int, flatten: bool = False):
    """Host-side: pad + reshape a test set to ``[n_batches, batch_size, ...]``
    with a mask, so evaluation is a fixed-shape ``lax.scan``.

    ``flatten=True`` stores samples flattened to 1-D (pair with
    ``make_reshaper`` as the eval preprocess — see its TPU layout note).
    """
    n = x.shape[0]
    if flatten:
        x = x.reshape(n, -1)
    n_batches = (n + batch_size - 1) // batch_size
    padded = n_batches * batch_size
    xp = np.zeros((padded,) + x.shape[1:], dtype=x.dtype)
    yp = np.zeros((padded,) + np.shape(y)[1:], dtype=np.int32)
    mp = np.zeros((padded,), dtype=np.float32)
    xp[:n], yp[:n], mp[:n] = x, y, 1.0
    return (
        xp.reshape((n_batches, batch_size) + x.shape[1:]),
        yp.reshape((n_batches, batch_size) + yp.shape[1:]),
        mp.reshape((n_batches, batch_size)),
    )


def make_eval_fn(apply_fn, preprocess: Callable | None = None,
                 name: str = "evaluate"):
    """Build ``evaluate(params, xb, yb, mb) -> {"loss", "accuracy"}``.

    Full-test-set inference as a scan over pre-padded batches; parity with the
    reference's per-round server-side evaluation (``get_metric`` ->
    ``tester.inference()``, fed_server.py:26-32,85-86). vmap-able over a
    params batch for Shapley subset evaluation. ``preprocess`` is applied to
    each x batch inside the scan (e.g. ``make_reshaper`` for flat storage).

    ``name`` becomes the jitted program's display name (compile logs, the
    telemetry recompile counter, profiler traces): several distinct
    programs are built from this factory per run (server eval, Shapley
    subset eval), and an anonymous shared "evaluate" would make a
    recompile warning unattributable.
    """
    def evaluate(params, xb, yb, mb):
        def body(carry, batch):
            x, y, m = batch
            if preprocess is not None:
                x = preprocess(x)
            logits, _ = split_model_output(apply_fn({"params": params}, x))
            loss_sum, correct_sum, count = carry
            if makes_own_loss(logits):
                m = _target_mask(y, m)
                nll_sum, correct = logits.weighted_nll(y, m)
                return (
                    loss_sum + nll_sum, correct_sum + correct,
                    count + jnp.sum(m),
                ), None
            nll, m = target_nll(logits, y, m)
            correct = (jnp.argmax(logits, axis=-1) == y).astype(jnp.float32)
            return (
                loss_sum + jnp.sum(nll * m),
                correct_sum + jnp.sum(correct * m),
                count + jnp.sum(m),
            ), None

        (loss_sum, correct_sum, count), _ = jax.lax.scan(
            body, (0.0, 0.0, 0.0), (xb, yb, mb)
        )
        count = jnp.maximum(count, 1.0)
        return {"loss": loss_sum / count, "accuracy": correct_sum / count}

    evaluate.__name__ = evaluate.__qualname__ = name
    return evaluate
