"""Find a cell's files by the names ``BENCHMARK.json`` gives.

A cell (``workloads`` entry) names a configuration and a traffic mix.
Each is a data file of its own: ``configs[].file`` for the
configuration, ``<paths[0]>/traffic/<traffic>.json`` for the mix,
``<paths[0]>/workloads/<cell>.json`` for what belongs to the pairing (how
many rounds the comparison follows, its limits, the traced span). A later
PR adds a cell by adding such files and entries; no file here is edited.
"""

from __future__ import annotations

import json
import os


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, workload: str) -> dict:
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(
            f"unknown workload {workload!r}; BENCHMARK.json has "
            f"{sorted(cells)}"
        )
    cell = cells[workload]
    config_entry = next(
        c for c in bench["configs"] if c["name"] == cell["config"]
    )
    base = os.path.join(root, bench["paths"][0])
    return {
        "bench": bench,
        "cell": cell,
        "config": _load(os.path.join(root, config_entry["file"])),
        "traffic": _load(
            os.path.join(base, "traffic", cell["traffic"] + ".json")
        ),
        "pairing": _load(
            os.path.join(base, "workloads", workload + ".json")
        ),
    }


def metrics_for(bench: dict, kind: str, workload: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports: an
    entry without a ``workloads`` key applies to every cell that reports
    the end-to-end metric it moves (for an end-to-end entry: every cell)."""
    e2e = {
        m["name"]: m for m in bench["end_to_end"]
        if workload in m.get("workloads", [workload])
    }
    if kind == "end_to_end":
        return list(e2e.values())
    return [
        m for m in bench["per_layer"]
        if workload in m.get("workloads", [workload]) and m["moves"] in e2e
    ]


def program_argv(spec: dict, seed: int, extra: list[str]) -> list[str]:
    """The argv the program's CLI would be given for this cell."""
    return (
        list(spec["config"]["argv"]) + list(spec["traffic"]["argv"])
        + ["--seed", str(seed)] + list(extra)
    )
