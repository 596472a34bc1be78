"""scripts/compare_bench.py: the bench-JSON regression gate's self-test.

Pure-Python (the script deliberately imports no jax), so this is the
fast tier-1 wiring the satellite task asks for: the gate's direction
semantics, the provenance refusal, and the CLI exit codes.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = os.path.join(
    os.path.dirname(__file__), "..", "scripts", "compare_bench.py"
)


@pytest.fixture(scope="module")
def cb():
    spec = importlib.util.spec_from_file_location("compare_bench", _SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _record(value=9000.0, gtg=50.0, bytes_gb=100.0, **extra):
    return {
        "schema_version": 2,
        "config_hash": "abcdef123456",
        "metric": "simulated_clients_x_rounds_per_sec",
        "value": value,
        "mean_rate": value * 0.98,
        "flagship.unused": 1,
        "gtg": {"value": gtg},
        "proxy": {"traced_bytes_gb": bytes_gb, "traced_op_count": 500},
        "robustness": {"rounds_rejected": 0, "mean_survivor_count": 9.0},
        **extra,
    }


def test_no_regression_within_threshold(cb):
    old, new = _record(), _record(value=9100.0, gtg=49.0)
    assert cb.check_comparable(old, new) is None
    result = cb.compare_records(old, new, threshold=0.05)
    assert result["regressions"] == []
    assert any(e["metric"] == "value" for e in result["unchanged"])


def test_detects_regressions_in_both_directions(cb):
    """higher-is-better dropping and lower-is-better growing both gate."""
    old = _record(value=9000.0, gtg=50.0, bytes_gb=100.0)
    new = _record(value=8000.0, gtg=60.0, bytes_gb=120.0)  # all worse >5%
    result = cb.compare_records(old, new, threshold=0.05)
    flagged = {e["metric"] for e in result["regressions"]}
    assert {"value", "gtg.value", "proxy.traced_bytes_gb"} <= flagged
    # The same moves in the GOOD direction are improvements, not flags.
    result_rev = cb.compare_records(new, old, threshold=0.05)
    assert result_rev["regressions"] == []
    assert {e["metric"] for e in result_rev["improvements"]} >= {
        "value", "gtg.value", "proxy.traced_bytes_gb",
    }


def test_zero_baseline_counter_gates_on_any_increase(cb):
    """rounds_rejected 0 -> 2 must gate even though relative change is
    undefined at a zero baseline."""
    old, new = _record(), _record()
    new["robustness"]["rounds_rejected"] = 2
    result = cb.compare_records(old, new, threshold=0.05)
    assert any(
        e["metric"] == "robustness.rounds_rejected"
        for e in result["regressions"]
    )


def test_missing_metrics_are_skipped_not_flagged(cb):
    old, new = _record(), _record()
    del new["gtg"]
    result = cb.compare_records(old, new, threshold=0.05)
    assert any(e["metric"] == "gtg.value" for e in result["skipped"])
    assert not any(
        e["metric"] == "gtg.value" for e in result["regressions"]
    )


def test_client_stats_overhead_not_relatively_tracked(cb):
    """The overhead ratio is a near-zero noisy quantity: it must NOT be
    in the relative-change TRACKED list (0.01 -> 0.02 would read as
    +100%); only the absolute self-gate below judges it."""
    old, new = _record(), _record()
    old["client_stats"] = {"overhead_ratio": 0.01}
    new["client_stats"] = {"overhead_ratio": 0.04}  # within the gate
    result = cb.compare_records(old, new, threshold=0.05)
    assert not any(
        "client_stats" in e["metric"]
        for e in result["regressions"] + result["improvements"]
    )


def test_client_stats_overhead_self_gate(cb, tmp_path):
    """The in-record gate fires on the NEW record alone: its own bench
    run already measured the on-vs-off round-time ratio."""
    assert cb.overhead_gate(_record(), 0.10) is None  # leg absent: skip
    ok = _record(client_stats={"overhead_ratio": 0.04})
    assert cb.overhead_gate(ok, 0.10) is None
    bad = _record(client_stats={"overhead_ratio": 0.37})
    entry = cb.overhead_gate(bad, 0.10)
    assert entry and entry["new"] == 0.37

    # CLI: the self-gate alone must exit 1 even when every cross-record
    # metric is unchanged, and the threshold flag overrides.
    old_p = tmp_path / "old.json"
    bad_p = tmp_path / "bad.json"
    old_p.write_text(json.dumps(_record()))
    bad_p.write_text(json.dumps(bad))
    import subprocess

    proc = subprocess.run(
        [sys.executable, _SCRIPT, str(old_p), str(bad_p)],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1
    assert "client_stats.overhead_ratio" in proc.stdout
    proc = subprocess.run(
        [sys.executable, _SCRIPT, str(old_p), str(bad_p),
         "--stats-overhead-threshold", "0.5"],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0


def test_async_speedup_not_relatively_tracked(cb):
    """The async speedup sits at a fixed operating point per config —
    like the other in-record ratios it must never be a relative TRACKED
    metric; only the absolute in-record floor judges it."""
    old = _record(**{"async": {"async_speedup_ratio": 7.4}})
    new = _record(**{"async": {"async_speedup_ratio": 6.9}})
    result = cb.compare_records(old, new, threshold=0.05)
    assert not any(
        "async" in e["metric"]
        for e in result["regressions"] + result["improvements"]
    )


def test_async_speedup_self_gate(cb, tmp_path):
    """In-record absolute floor: deadline rounds that stop beating the
    sync wait-for-everyone counterfactual gate on the NEW record alone."""
    assert cb.async_speedup_gate(_record(), 1.0) is None  # leg absent
    ok = _record(**{"async": {"async_speedup_ratio": 4.2}})
    assert cb.async_speedup_gate(ok, 1.0) is None
    bad = _record(**{"async": {"async_speedup_ratio": 0.84}})
    entry = cb.async_speedup_gate(bad, 1.0)
    assert entry and entry["new"] == 0.84 and entry["direction"] == "higher"

    old_p = tmp_path / "old.json"
    bad_p = tmp_path / "bad.json"
    old_p.write_text(json.dumps(_record()))
    bad_p.write_text(json.dumps(bad))
    proc = subprocess.run(
        [sys.executable, _SCRIPT, str(old_p), str(bad_p)],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1
    assert "async.async_speedup_ratio" in proc.stdout
    proc = subprocess.run(
        [sys.executable, _SCRIPT, str(old_p), str(bad_p),
         "--async-speedup-threshold", "0.5"],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0


def test_stream_overlap_not_relatively_tracked(cb):
    """The prefetch overlap ratio sits near a fixed operating point —
    like the other in-record ratios it must never be a relative TRACKED
    metric; only the absolute in-record floor judges it."""
    old = _record(stream={"overlap_ratio": 0.97})
    new = _record(stream={"overlap_ratio": 0.90})
    result = cb.compare_records(old, new, threshold=0.05)
    assert not any(
        "stream" in e["metric"]
        for e in result["regressions"] + result["improvements"]
    )


def test_stream_overlap_self_gate(cb, tmp_path):
    """In-record absolute floor: a streamed-residency prefetch that
    stops hiding the host->HBM upload behind compute gates on the NEW
    record alone."""
    assert cb.stream_overlap_gate(_record(), 0.5) is None  # leg absent
    ok = _record(stream={"overlap_ratio": 0.93})
    assert cb.stream_overlap_gate(ok, 0.5) is None
    bad = _record(stream={"overlap_ratio": 0.12})
    entry = cb.stream_overlap_gate(bad, 0.5)
    assert entry and entry["new"] == 0.12 and entry["direction"] == "higher"

    old_p = tmp_path / "old.json"
    bad_p = tmp_path / "bad.json"
    old_p.write_text(json.dumps(_record()))
    bad_p.write_text(json.dumps(bad))
    proc = subprocess.run(
        [sys.executable, _SCRIPT, str(old_p), str(bad_p)],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1
    assert "stream.overlap_ratio" in proc.stdout
    proc = subprocess.run(
        [sys.executable, _SCRIPT, str(old_p), str(bad_p),
         "--stream-overlap-threshold", "0.05"],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0


def test_stream_cohort_rate_not_relatively_tracked(cb):
    """The streamed cohort rate is gated by its own absolute in-record
    floor, never as a relative TRACKED metric (the PR 4/5/7 precedent
    for in-record gates)."""
    old = _record(stream={"cohort_rate": 19000.0})
    new = _record(stream={"cohort_rate": 15000.0})
    result = cb.compare_records(old, new, threshold=0.05)
    assert not any(
        "stream" in e["metric"]
        for e in result["regressions"] + result["improvements"]
    )


def test_stream_cohort_rate_self_gate(cb, tmp_path):
    """In-record absolute floor: the largest-population streamed leg
    going host-bound again (cohort rate under the floor) gates on the
    NEW record alone — the O(cohort) sampler's regression signal."""
    assert cb.stream_cohort_rate_gate(_record(), 900.0) is None  # absent
    ok = _record(stream={"cohort_rate": 18000.0, "overlap_ratio": 0.9})
    assert cb.stream_cohort_rate_gate(ok, 900.0) is None
    bad = _record(stream={"cohort_rate": 330.0, "overlap_ratio": 0.9})
    entry = cb.stream_cohort_rate_gate(bad, 900.0)
    assert entry and entry["new"] == 330.0 and entry["direction"] == "higher"

    old_p = tmp_path / "old.json"
    bad_p = tmp_path / "bad.json"
    old_p.write_text(json.dumps(_record()))
    bad_p.write_text(json.dumps(bad))
    proc = subprocess.run(
        [sys.executable, _SCRIPT, str(old_p), str(bad_p)],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1
    assert "stream.cohort_rate" in proc.stdout
    proc = subprocess.run(
        [sys.executable, _SCRIPT, str(old_p), str(bad_p),
         "--stream-cohort-rate-threshold", "100"],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0


def test_valuation_corr_not_relatively_tracked(cb):
    """The estimator-fidelity correlation sits near a fixed operating
    point (~0.85-0.9) — like every other in-record ratio it must never
    be a relative TRACKED metric; only the absolute floor judges it."""
    old = _record(valuation={"audit_spearman": 0.95})
    new = _record(valuation={"audit_spearman": 0.85})
    result = cb.compare_records(old, new, threshold=0.05)
    assert not any(
        "valuation" in e["metric"]
        for e in result["regressions"] + result["improvements"]
    )


def test_valuation_corr_self_gate(cb, tmp_path):
    """In-record absolute floor: a streaming valuation vector that stops
    tracking the exact GTG audit SVs gates on the NEW record alone."""
    assert cb.valuation_corr_gate(_record(), 0.8) is None  # leg absent
    ok = _record(valuation={"audit_spearman": 0.881,
                            "overhead_ratio": 0.01})
    assert cb.valuation_corr_gate(ok, 0.8) is None
    # A null correlation (degenerate audit) is absent data, not a
    # regression — the leg reports it, the gate skips it.
    assert cb.valuation_corr_gate(
        _record(valuation={"audit_spearman": None}), 0.8
    ) is None
    bad = _record(valuation={"audit_spearman": 0.41})
    entry = cb.valuation_corr_gate(bad, 0.8)
    assert entry and entry["new"] == 0.41 and entry["direction"] == "higher"

    old_p = tmp_path / "old.json"
    bad_p = tmp_path / "bad.json"
    old_p.write_text(json.dumps(_record()))
    bad_p.write_text(json.dumps(bad))
    proc = subprocess.run(
        [sys.executable, _SCRIPT, str(old_p), str(bad_p)],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1
    assert "valuation.audit_spearman" in proc.stdout
    proc = subprocess.run(
        [sys.executable, _SCRIPT, str(old_p), str(bad_p),
         "--valuation-corr-threshold", "0.3"],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0


def test_sweep_amortization_not_relatively_tracked(cb):
    """The serial-vs-fleet wall ratio sits at the operating point the
    compile/run balance sets — like every other in-record ratio it must
    never be a relative TRACKED metric; only the absolute floor judges
    it."""
    old = _record(sweep={"sweep_amortization_ratio": 5.0})
    new = _record(sweep={"sweep_amortization_ratio": 2.6})
    result = cb.compare_records(old, new, threshold=0.05)
    assert not any(
        "sweep" in e["metric"]
        for e in result["regressions"] + result["improvements"]
    )


def test_sweep_amortization_self_gate(cb, tmp_path):
    """In-record absolute floor: a vmapped fleet that stops amortizing
    its compile/dispatch (ratio under the floor) gates on the NEW
    record alone."""
    assert cb.sweep_amortization_gate(_record(), 2.0) is None  # absent
    ok = _record(sweep={"sweep_amortization_ratio": 3.4,
                        "compile_reuse_fraction": 0.875})
    assert cb.sweep_amortization_gate(ok, 2.0) is None
    bad = _record(sweep={"sweep_amortization_ratio": 1.3})
    entry = cb.sweep_amortization_gate(bad, 2.0)
    assert entry and entry["new"] == 1.3 and entry["direction"] == "higher"

    old_p = tmp_path / "old.json"
    bad_p = tmp_path / "bad.json"
    old_p.write_text(json.dumps(_record()))
    bad_p.write_text(json.dumps(bad))
    proc = subprocess.run(
        [sys.executable, _SCRIPT, str(old_p), str(bad_p)],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1
    assert "sweep.sweep_amortization_ratio" in proc.stdout
    proc = subprocess.run(
        [sys.executable, _SCRIPT, str(old_p), str(bad_p),
         "--sweep-amortization-threshold", "1.0"],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0


def test_churn_overhead_not_relatively_tracked(cb):
    """The dynamic-vs-static round-time overhead sits near a fixed small
    operating point — like every other in-record ratio it must never be
    a relative TRACKED metric; only the absolute ceiling judges it."""
    old = _record(churn={"churn_overhead_ratio": 0.01})
    new = _record(churn={"churn_overhead_ratio": 0.06})
    result = cb.compare_records(old, new, threshold=0.05)
    assert not any(
        "churn" in e["metric"]
        for e in result["regressions"] + result["improvements"]
    )


def test_churn_overhead_self_gate(cb, tmp_path):
    """In-record absolute ceiling: a registration stream that stops
    riding the round at marginal cost (10x-growth overhead above the
    ceiling) gates on the NEW record alone."""
    assert cb.churn_overhead_gate(_record(), 0.10) is None  # leg absent
    ok = _record(churn={"churn_overhead_ratio": 0.04,
                        "population": {"growth_ratio": 10.0}})
    assert cb.churn_overhead_gate(ok, 0.10) is None
    # A NEGATIVE ratio (dynamic measured faster — run noise) holds too.
    assert cb.churn_overhead_gate(
        _record(churn={"churn_overhead_ratio": -0.02}), 0.10
    ) is None
    bad = _record(churn={"churn_overhead_ratio": 0.31})
    entry = cb.churn_overhead_gate(bad, 0.10)
    assert entry and entry["new"] == 0.31 and entry["direction"] == "lower"

    old_p = tmp_path / "old.json"
    bad_p = tmp_path / "bad.json"
    old_p.write_text(json.dumps(_record()))
    bad_p.write_text(json.dumps(bad))
    proc = subprocess.run(
        [sys.executable, _SCRIPT, str(old_p), str(bad_p)],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1
    assert "churn.churn_overhead_ratio" in proc.stdout
    proc = subprocess.run(
        [sys.executable, _SCRIPT, str(old_p), str(bad_p),
         "--churn-overhead-threshold", "0.5"],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0


def test_gtg_scaling_not_relatively_tracked(cb):
    """The D=2/D=1 subset-eval throughput ratio sits near a fixed
    operating point (~2.0 on a real mesh) — like every other in-record
    ratio it must never be a relative TRACKED metric; only the absolute
    floor judges it."""
    old, new = _record(), _record()
    old["gtg"]["gtg_scaling_ratio"] = 1.9
    new["gtg"]["gtg_scaling_ratio"] = 1.6
    result = cb.compare_records(old, new, threshold=0.05)
    assert not any(
        "gtg_scaling" in e["metric"]
        for e in result["regressions"] + result["improvements"]
    )


def test_gtg_scaling_self_gate(cb, tmp_path):
    """In-record absolute floor: a mesh-sharded walk that stops buying
    throughput (D=2/D=1 below the floor) gates on the NEW record
    alone; an unarmed record (1-core host — bench keeps the measured
    ratio under gtg.scaling but never sets the gated key) skips."""
    assert cb.gtg_scaling_gate(_record(), 1.5) is None  # key absent
    # Unarmed 1-core measurement: ratio recorded, gate key absent.
    unarmed = _record()
    unarmed["gtg"]["scaling"] = {"d2_over_d1": 1.05, "host_cores": 1}
    assert cb.gtg_scaling_gate(unarmed, 1.5) is None
    ok = _record()
    ok["gtg"]["gtg_scaling_ratio"] = 1.82
    assert cb.gtg_scaling_gate(ok, 1.5) is None
    bad = _record()
    bad["gtg"]["gtg_scaling_ratio"] = 1.12
    entry = cb.gtg_scaling_gate(bad, 1.5)
    assert entry and entry["new"] == 1.12 and entry["direction"] == "higher"

    old_p = tmp_path / "old.json"
    bad_p = tmp_path / "bad.json"
    old_p.write_text(json.dumps(_record()))
    bad_p.write_text(json.dumps(bad))
    proc = subprocess.run(
        [sys.executable, _SCRIPT, str(old_p), str(bad_p)],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1
    assert "gtg.gtg_scaling_ratio" in proc.stdout
    proc = subprocess.run(
        [sys.executable, _SCRIPT, str(old_p), str(bad_p),
         "--gtg-scaling-threshold", "1.0"],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0


def test_model_drift_not_relatively_tracked(cb):
    """model_error_ratio sits near 1.0 — like the other in-record
    ratios it must never be a relative TRACKED metric (PR 4/5
    precedent); only the absolute band gate judges it."""
    old = _record(costmodel={"cnn": {"model_error_ratio": 1.02}})
    new = _record(costmodel={"cnn": {"model_error_ratio": 0.93}})
    result = cb.compare_records(old, new, threshold=0.05)
    assert not any(
        "costmodel" in e["metric"]
        for e in result["regressions"] + result["improvements"]
    )


def test_model_drift_gate_is_a_band(cb):
    """The in-record gate fires when predicted-vs-measured leaves the
    absolute band around 1.0 — in EITHER direction, per program."""
    assert cb.model_drift_gate(_record(), 0.35) == []  # leg absent
    ok = _record(costmodel={
        "cnn": {"model_error_ratio": 0.75},
        "flagship": {"model_error_ratio": 1.0},
        "pod_projection": {"topology": "v4-32"},
    })
    assert cb.model_drift_gate(ok, 0.35) == []
    # Under-prediction out of band (cnn) and over-prediction out of
    # band (flagship) both gate, each with its own entry.
    bad = _record(costmodel={
        "cnn": {"model_error_ratio": 0.5},
        "flagship": {"model_error_ratio": 1.6},
    })
    entries = cb.model_drift_gate(bad, 0.35)
    assert {e["metric"] for e in entries} == {
        "costmodel.cnn.model_error_ratio",
        "costmodel.flagship.model_error_ratio",
    }
    # A leg that degraded to an error sub-object is skipped, not gated.
    degraded = _record(costmodel={"cnn": {"error": "no byte annotations"}})
    assert cb.model_drift_gate(degraded, 0.35) == []


def test_model_drift_gate_cli(cb, tmp_path):
    """The drift gate alone must exit 1, and the threshold flag widens
    the band back to passing."""
    old_p, bad_p = tmp_path / "old.json", tmp_path / "bad.json"
    old_p.write_text(json.dumps(_record()))
    bad_p.write_text(json.dumps(
        _record(costmodel={"flagship": {"model_error_ratio": 1.55}})
    ))
    proc = subprocess.run(
        [sys.executable, _SCRIPT, str(old_p), str(bad_p)],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1
    assert "costmodel.flagship.model_error_ratio" in proc.stdout
    proc = subprocess.run(
        [sys.executable, _SCRIPT, str(old_p), str(bad_p),
         "--model-drift-threshold", "0.6"],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0


def test_provenance_refusal(cb):
    old, new = _record(), _record()
    new["config_hash"] = "fedcba654321"
    assert "config_hash" in cb.check_comparable(old, new)
    new["config_hash"] = old["config_hash"]
    new["schema_version"] = 3
    assert "schema_version" in cb.check_comparable(old, new)
    # Records predating the stamp can't prove incomparability -> allowed.
    legacy = {"metric": "simulated_clients_x_rounds_per_sec", "value": 9000}
    assert cb.check_comparable(legacy, _record()) is None


def test_cli_exit_codes(cb, tmp_path):
    """0 = clean, 1 = regression, 2 = provenance refusal (--force
    overrides)."""
    old, good, bad = _record(), _record(value=9050.0), _record(value=5000.0)
    foreign = _record(value=9050.0)
    foreign["config_hash"] = "fedcba654321"
    paths = {}
    for name, rec in [("old", old), ("good", good), ("bad", bad),
                      ("foreign", foreign)]:
        p = tmp_path / f"{name}.json"
        p.write_text(json.dumps(rec))
        paths[name] = str(p)

    def run(*args):
        return subprocess.run(
            [sys.executable, _SCRIPT, *args],
            capture_output=True, text=True, timeout=120,
        )

    assert run(paths["old"], paths["good"]).returncode == 0
    proc = run(paths["old"], paths["bad"])
    assert proc.returncode == 1
    assert "REGRESSIONS" in proc.stdout and "value" in proc.stdout
    proc = run(paths["old"], paths["foreign"])
    assert proc.returncode == 2
    assert "config_hash" in proc.stderr
    # --force compares anyway; identical-enough values -> clean exit.
    assert run(paths["old"], paths["foreign"], "--force").returncode == 0
    # --json emits the machine-readable comparison.
    proc = run(paths["old"], paths["bad"], "--json")
    assert proc.returncode == 1
    assert json.loads(proc.stdout)["regressions"]


def test_mhost_cohort_rate_not_relatively_tracked(cb):
    """The 2-process distributed-store cohort rate is machine-bound —
    like every other in-record gated value it must never be a relative
    TRACKED metric; only the absolute floor judges it."""
    old = _record(mhost={"mhost_cohort_rate": 9000.0})
    new = _record(mhost={"mhost_cohort_rate": 5000.0})
    result = cb.compare_records(old, new, threshold=0.05)
    assert not any(
        "mhost" in e["metric"]
        for e in result["regressions"] + result["improvements"]
    )


def test_mhost_cohort_rate_self_gate(cb, tmp_path):
    """In-record absolute floor on the 2-process streamed sweep's
    steady cohort rate; an unarmed record (1-core host — bench keeps
    the honest number under mhost.cohort_rate but never sets the gated
    key, the PR 14 arming precedent) skips."""
    assert cb.mhost_cohort_rate_gate(_record(), 200.0) is None  # absent
    unarmed = _record(mhost={"cohort_rate": 38.2, "host_cores": 1})
    assert cb.mhost_cohort_rate_gate(unarmed, 200.0) is None
    ok = _record(mhost={"mhost_cohort_rate": 512.0, "cohort_rate": 512.0})
    assert cb.mhost_cohort_rate_gate(ok, 200.0) is None
    bad = _record(mhost={"mhost_cohort_rate": 61.0, "cohort_rate": 61.0})
    entry = cb.mhost_cohort_rate_gate(bad, 200.0)
    assert entry and entry["new"] == 61.0 and entry["direction"] == "higher"

    old_p = tmp_path / "old.json"
    bad_p = tmp_path / "bad.json"
    old_p.write_text(json.dumps(_record()))
    bad_p.write_text(json.dumps(bad))
    proc = subprocess.run(
        [sys.executable, _SCRIPT, str(old_p), str(bad_p)],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1
    assert "mhost.mhost_cohort_rate" in proc.stdout
    proc = subprocess.run(
        [sys.executable, _SCRIPT, str(old_p), str(bad_p),
         "--mhost-cohort-rate-threshold", "50"],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0


def test_span_overhead_not_relatively_tracked(cb):
    """The span-trace overhead ratio hovers near zero like the
    client-stats one: it must NOT be in the relative-change TRACKED
    list; only the absolute ceiling below judges it."""
    old = _record(spans={"overhead_ratio": 0.005})
    new = _record(spans={"overhead_ratio": 0.03})  # within the gate
    result = cb.compare_records(old, new, threshold=0.05)
    assert not any(
        "spans" in e["metric"]
        for e in result["regressions"] + result["improvements"]
    )


def test_span_overhead_self_gate(cb, tmp_path):
    """In-record absolute ceiling on the spans leg's on-vs-off round
    time ratio (span_trace='on', telemetry/spans.py): the distributed
    tracer must stay cheap enough to leave on."""
    assert cb.span_overhead_gate(_record(), 0.05) is None  # leg absent
    ok = _record(spans={"overhead_ratio": 0.018})
    assert cb.span_overhead_gate(ok, 0.05) is None
    bad = _record(spans={"overhead_ratio": 0.22})
    entry = cb.span_overhead_gate(bad, 0.05)
    assert entry and entry["new"] == 0.22 and entry["direction"] == "lower"

    old_p = tmp_path / "old.json"
    bad_p = tmp_path / "bad.json"
    old_p.write_text(json.dumps(_record()))
    bad_p.write_text(json.dumps(bad))
    proc = subprocess.run(
        [sys.executable, _SCRIPT, str(old_p), str(bad_p)],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1
    assert "spans.overhead_ratio" in proc.stdout
    proc = subprocess.run(
        [sys.executable, _SCRIPT, str(old_p), str(bad_p),
         "--span-overhead-threshold", "0.5"],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0
