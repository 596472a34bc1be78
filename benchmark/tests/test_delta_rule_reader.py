"""The reader of ``delta_rule_ms_per_round`` on a synthetic op table: it
sums every launch of the Gated DeltaNet delta rule's forward and backward
kernels by their names, per traced period, and reads ``None`` where
neither kernel ran. No number read here is a device metric."""

import importlib.util
import os

import pytest
from conftest import BENCH_DIR


def _reader():
    spec = importlib.util.spec_from_file_location(
        "delta_rule_reader",
        os.path.join(BENCH_DIR, "metrics", "delta_rule_ms_per_round.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_delta_rule_kernel_reader_sums_both_kernels_by_name():
    """``delta_rule_ms_per_round`` sums every launch of the delta rule's
    forward and backward kernels by their names, whatever line they carry,
    per traced period; ``None`` where neither ran (the parent's program, a
    CPU run, the XLA form), or where there is no trace."""
    from distributed_learning_simulator_tpu.ops import delta_rule as dr

    reader = _reader()
    assert reader.KERNELS == (dr.FORWARD_NAME, dr.BACKWARD_NAME)
    table = [
        {"name": "delta_rule_forward.3", "seconds": 0.25, "executions": 30,
         "source": "olmo_hybrid.py:80"},
        {"name": "delta_rule_forward.7", "seconds": 0.125, "executions": 30,
         "source": "olmo_hybrid.py:217"},
        {"name": "delta_rule_backward.9", "seconds": 0.5, "executions": 12,
         "source": "olmo_hybrid.py:217"},
        {"name": "selective_scan_forward.1", "seconds": 8.0,
         "executions": 1, "source": "jamba.py:1"},
        {"name": "fusion.2", "seconds": 4.0, "source": "olmo_hybrid.py:80"},
    ]
    ctx = {"trace": {"ops": {"periods": 2, "table": table}}}
    assert reader.read(ctx) == pytest.approx(437.5)
    parent = {"trace": {"ops": {"periods": 2, "table": table[3:]}}}
    assert reader.read(parent) is None
    assert reader.read({"trace": None}) is None
