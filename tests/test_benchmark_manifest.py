"""Tier-1 runs the benchmark's own manifest check
(``benchmark/tests/test_manifest.py``: every file ``BENCHMARK.json`` names
exists and loads; no jax). That file takes two paths from the benchmark's
``conftest``, which shares its module name with this directory's, so it is
loaded by path with those two names handed in."""

import importlib.util
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")


def _load():
    if BENCH_DIR not in sys.path:
        sys.path.insert(0, BENCH_DIR)
    stub = types.ModuleType("conftest")
    stub.BENCH_DIR, stub.ROOT = BENCH_DIR, ROOT
    ours = sys.modules.get("conftest")
    sys.modules["conftest"] = stub
    try:
        spec = importlib.util.spec_from_file_location(
            "benchmark_test_manifest",
            os.path.join(BENCH_DIR, "tests", "test_manifest.py"),
        )
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
    finally:
        if ours is not None:
            sys.modules["conftest"] = ours
        else:
            del sys.modules["conftest"]
    return module


_manifest = _load()
test_every_cell_finds_its_files = _manifest.test_every_cell_finds_its_files
test_every_metric_has_a_reader_and_names_cells_that_exist = (
    _manifest.test_every_metric_has_a_reader_and_names_cells_that_exist
)
