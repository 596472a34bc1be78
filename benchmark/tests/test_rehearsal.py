"""CPU rehearsals of ``benchmark/run.py`` at a tiny size: the whole run
with only the look for a chip lifted; the same with the program computing
in the next precision down, which has to come out not correct; and the
mesh cell's wiring on four virtual devices. No number read here is a
device metric."""

import glob
import json
import os

import calibrate
from conftest import (BENCH_DIR, TINY_LIMITS, last_line,
                      lower_precision_cell, write_tiny_root)

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_tiny(bench_run, tmp_path, capsys, trace, traffic="fed", seed=3):
    root = write_tiny_root(str(tmp_path), traffic)
    rc = bench_run.main(
        ["--workload", "tiny_cell", "--seed", str(seed), "--seconds", "1",
         "--trace", str(trace)], root=root,
    )
    assert rc == 0
    return last_line(capsys)


def test_run_end_to_end(bench_run, tmp_path, capsys):
    line = run_tiny(bench_run, tmp_path, capsys, trace=0,
                    seed=2**31 + 12345)
    assert KEYS <= set(line)
    assert list(line)[-1] == "compared"
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "client_rounds_per_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    for value, limit in line["compared"].values():
        assert value <= limit


def test_traced_run_reports_per_layer_metrics(bench_run, tmp_path, capsys):
    line = run_tiny(bench_run, tmp_path, capsys, trace=1)
    assert line["correct"] is True
    # A CPU trace has no device lanes: the trace's readers return nothing
    # and their metrics are left out, never written as 0.
    assert set(line["metrics"]) == {
        "compile_s", "round_s_median", "compiles_in_window",
    }
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert "breakdown" not in line


def test_lower_precision_program_is_not_correct(bench_run, tmp_path,
                                                capsys, monkeypatch):
    """The control: the program with its own float8 path switched on."""
    import harness.spec as spec

    real = spec.load_cell
    monkeypatch.setattr(
        spec, "load_cell",
        lambda root, name: lower_precision_cell(
            real(root, name), "float8_e4m3fn"
        ),
    )
    line = run_tiny(bench_run, tmp_path, capsys, trace=0)
    assert line["correct"] is False
    assert any(v > limit for v, limit in line["compared"].values())


def test_reference_in_lower_precision_is_not_correct(bench_run, tmp_path,
                                                     capsys):
    """The control as the chip reads it (``calibrate.py``): the reference
    put in the program's place in float8 fails a held number; so does
    every planted fault; the program itself passes every one."""
    root = write_tiny_root(str(tmp_path))
    rc = calibrate.main(
        ["--workload", "tiny_cell", "--seeds", "7", "--seconds", "0.5"],
        root=root, out_dir=str(tmp_path / "out"),
    )
    assert rc == 0
    line = last_line(capsys)

    def fails(numbers):
        return [n for n, limit in TINY_LIMITS.items() if numbers[n] > limit]

    assert set(line["program"]) == set(TINY_LIMITS)
    assert not fails(line["program"])
    has_to_fail = ["control_float8_e4m3fn"] + [
        k for k in line if k.startswith("fault_")
    ]
    assert len(has_to_fail) == 5
    for name in has_to_fail:
        assert fails(line[name]), name
    assert "control_float8_e4m3fn_products" in line


def test_tiny_cell_holds_the_numbers_the_real_cells_hold():
    files = glob.glob(os.path.join(BENCH_DIR, "workloads", "*.json"))
    assert files
    for path in files:
        with open(path) as f:
            assert set(json.load(f)["limits"]) == set(TINY_LIMITS), path


def test_mesh_cell_wiring_on_four_virtual_devices(bench_run, tmp_path,
                                                  capsys):
    line = run_tiny(bench_run, tmp_path, capsys, trace=0,
                    traffic="fed_mesh4")
    assert line["correct"] is True
    assert line["device"]["count"] == 4
