"""Participation sampling (config.participation_sampler; ops/sampling.py).

The contract under test, per mode:

* ``exact`` (default) is THE pre-feature draw: the shared helper returns
  ``jax.random.choice(replace=False)`` bit-for-bit, run histories and
  ``config_hash`` are unchanged for pre-feature configs, and the
  streamed host replay still equals the in-program draw.
* ``hashed`` is a NEW O(cohort) mode: statistically uniform (chi-square
  over many rounds at small N), duplicate-free, deterministic from the
  round key, and — the load-bearing property — the jitted in-program
  draw and the numpy host mirror select IDENTICAL indices, which is
  what keeps streamed residency bit-identical to resident under the new
  sampler without any O(N) host work.
"""

import dataclasses

import jax
import numpy as np
import pytest

from distributed_learning_simulator_tpu.config import ExperimentConfig
from distributed_learning_simulator_tpu.algorithms.fedavg import (
    _hashed_part_key_words,
    round_key_splits,
)
from distributed_learning_simulator_tpu.ops.sampling import (
    draw_cohort,
    draw_cohort_host,
    hashed_cohort,
    hashed_cohort_np,
    overdraw_block,
    threefry2x32,
)
from distributed_learning_simulator_tpu.simulator import run_simulation
from distributed_learning_simulator_tpu.utils.reporting import config_hash


def _part_key(i: int = 0):
    return jax.random.split(jax.random.fold_in(jax.random.key(42), i))[0]


def _key_words_np(part_key) -> np.ndarray:
    return np.asarray(jax.random.key_data(part_key)).ravel()


# ------------------------------------------------------------- validation


def test_config_validation():
    ExperimentConfig(participation_sampler="hashed").validate()
    ExperimentConfig(participation_sampler="exact").validate()
    with pytest.raises(ValueError, match="participation_sampler"):
        ExperimentConfig(participation_sampler="reservoir").validate()


def test_default_is_exact():
    assert ExperimentConfig().participation_sampler == "exact"


# ------------------------------------------------- the hashed draw itself


def test_hashed_jit_equals_numpy_mirror():
    """The in-program draw and the host replay must select identical
    indices — the property streamed-residency bit-identity rests on."""
    for i, (n, k) in enumerate([
        (50, 10), (1000, 256), (8, 4), (20, 19), (7, 7), (100_000, 64),
        # ~1/3 of stream values hit the modulo-bias rejection here
        # (2^32 // n == 2), so the -1-marking path is exercised hard in
        # BOTH backends and must still agree.
        (2**32 // 3 + 1, 8),
    ]):
        pk = _part_key(i)
        jitted = np.asarray(
            jax.jit(hashed_cohort, static_argnums=(1, 2))(pk, n, k)
        )
        mirror = hashed_cohort_np(_key_words_np(pk), n, k)
        np.testing.assert_array_equal(jitted, mirror)


def test_hashed_no_duplicates_in_range():
    for i, (n, k) in enumerate([(30, 29), (1000, 500), (10_000, 256)]):
        idx = hashed_cohort_np(_key_words_np(_part_key(i)), n, k)
        assert idx.shape == (k,)
        assert len(np.unique(idx)) == k
        assert idx.min() >= 0 and idx.max() < n


def test_hashed_deterministic_and_key_sensitive():
    kw = _key_words_np(_part_key(3))
    a = hashed_cohort_np(kw, 5000, 64)
    b = hashed_cohort_np(kw, 5000, 64)
    np.testing.assert_array_equal(a, b)
    c = hashed_cohort_np(_key_words_np(_part_key(4)), 5000, 64)
    assert not np.array_equal(a, c)


def test_hashed_block_size_independent():
    """'First k distinct of the counter stream' is the definition, so
    the over-draw block size must not change the selection — the
    guarantee that the jitted fixed-shape loop and any mirror block
    size agree."""
    kw = _key_words_np(_part_key(5))
    a = hashed_cohort_np(kw, 1000, 256, block=70)
    b = hashed_cohort_np(kw, 1000, 256, block=4096)
    np.testing.assert_array_equal(a, b)


def test_overdraw_block_bounds():
    assert overdraw_block(256, 1_000_000) < 4 * 256 + 65
    assert overdraw_block(256, 1_000_000) > 256
    # Near-1 fractions stay capped (the while loop absorbs the rest).
    assert overdraw_block(999, 1000) <= 4 * 999 + 64


def test_threefry_numpy_matches_jnp():
    import jax.numpy as jnp

    ctr = np.arange(128, dtype=np.uint32)
    a0, a1 = threefry2x32(np, np.uint32(7), np.uint32(9), ctr,
                          np.zeros(128, np.uint32))
    b0, b1 = threefry2x32(jnp, jnp.uint32(7), jnp.uint32(9),
                          jnp.asarray(ctr), jnp.zeros(128, jnp.uint32))
    np.testing.assert_array_equal(a0, np.asarray(b0))
    np.testing.assert_array_equal(a1, np.asarray(b1))


def test_part_key_words_match_eager_split():
    """The jitted round_key_splits+key_data chain (the O(cohort)
    replay's fast path) must produce the eager chain's bits exactly —
    jit moves where the threefry runs, never what it computes. Built
    FROM round_key_splits, so both fault-gating flavors are the one
    split-chain definition."""
    key = jax.random.key(11)
    for with_faults in (False, True):
        fast = _hashed_part_key_words(key, with_faults)
        eager = np.asarray(
            jax.random.key_data(round_key_splits(key, with_faults)[0])
        ).ravel()
        np.testing.assert_array_equal(fast, eager)


def test_hashed_uniformity_chi_square():
    """Inclusion counts over many independent round keys at small N:
    each client must appear with probability k/N. Chi-square over N=50
    cells; the 0.999 quantile of chi2(df=49) is 85.4 — a generous
    one-shot bound for a deterministic test (the draw stream is fixed
    by the seed, so this can never flake)."""
    n, k, rounds = 50, 10, 2000
    counts = np.zeros(n)
    base = jax.random.key(0)
    for r in range(rounds):
        pk = jax.random.split(jax.random.fold_in(base, r))[0]
        counts[hashed_cohort_np(_key_words_np(pk), n, k)] += 1
    expected = rounds * k / n
    chi2 = float(((counts - expected) ** 2 / expected).sum())
    assert chi2 < 85.4, f"chi2={chi2} over df={n - 1}"


# ------------------------------------------------------ shared-helper pins


def test_exact_mode_is_bit_identical_to_choice():
    """The deduped helper must return jax.random.choice's draw
    bit-for-bit in both the traced and host entries — the pre-feature
    pin the 'exact' default rests on."""
    pk = _part_key(6)
    reference = np.asarray(
        jax.random.choice(pk, 100, (10,), replace=False)
    )
    np.testing.assert_array_equal(
        np.asarray(draw_cohort(pk, 100, 10, "exact")), reference
    )
    np.testing.assert_array_equal(
        draw_cohort_host(pk, 100, 10, "exact"), reference
    )


def test_unknown_sampler_rejected():
    pk = _part_key(7)
    with pytest.raises(ValueError, match="participation_sampler"):
        draw_cohort(pk, 10, 2, "reservoir")
    with pytest.raises(ValueError, match="participation_sampler"):
        draw_cohort_host(pk, 10, 2, "reservoir")


def test_config_hash_off_gate():
    """'exact' IS the pre-feature program, so it drops out of
    config_hash (pre-feature bench hashes survive the knob landing);
    'hashed' changes the drawn cohorts and auto-lands."""
    cfg = ExperimentConfig(participation_fraction=0.5)
    h = config_hash(cfg)
    assert h == config_hash(
        dataclasses.replace(cfg, participation_sampler="exact")
    )
    assert h != config_hash(
        dataclasses.replace(cfg, participation_sampler="hashed")
    )


# ------------------------------------------------------- end-to-end pins


def _series(result, *keys):
    return {k: [h.get(k) for h in result["history"]] for k in keys}


_BIT_KEYS = ("test_accuracy", "test_loss", "mean_client_loss",
             "cohort_hash")


def test_exact_default_history_unchanged(tiny_config):
    """participation_sampler='exact' (and the default) run the exact
    pre-feature program: identical histories, cohort hashes included."""
    cfg = dataclasses.replace(
        tiny_config, worker_number=8, round=3, participation_fraction=0.5,
    )
    base = _series(run_simulation(cfg, setup_logging=False), *_BIT_KEYS)
    explicit = _series(
        run_simulation(
            dataclasses.replace(cfg, participation_sampler="exact"),
            setup_logging=False,
        ),
        *_BIT_KEYS,
    )
    assert base == explicit
    assert None not in base["cohort_hash"]


def test_hashed_streamed_matches_resident(tiny_config):
    """The hashed mode's self-consistency contract: streamed residency
    (host numpy mirror replay) is bit-identical to resident (in-program
    jitted draw) — with faults active, so the 5-way key split is
    exercised too — while drawing DIFFERENT cohorts than exact (it is a
    new sampling mode, not a bit-compatible one)."""
    cfg = dataclasses.replace(
        tiny_config, worker_number=8, round=3, participation_fraction=0.5,
        participation_sampler="hashed",
        failure_mode="dropout", failure_prob=0.3, min_survivors=1,
    )
    resident = _series(run_simulation(cfg, setup_logging=False), *_BIT_KEYS,
                       "survivor_count")
    streamed = _series(
        run_simulation(
            dataclasses.replace(cfg, client_residency="streamed"),
            setup_logging=False,
        ),
        *_BIT_KEYS, "survivor_count",
    )
    assert resident == streamed
    exact = _series(
        run_simulation(
            dataclasses.replace(cfg, participation_sampler="exact"),
            setup_logging=False,
        ),
        *_BIT_KEYS,
    )
    assert exact["cohort_hash"] != resident["cohort_hash"]
