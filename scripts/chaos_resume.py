"""Chaos harness: prove bit-exact crash resume (docs/ROBUSTNESS.md).

Runs a reference simulation to completion, then re-runs it killing the
process at a chosen round — an in-process injected crash
(``robustness.chaos.InjectedCrash``) and a subprocess ``SIGKILL`` (no
cleanup, no ``finally`` blocks: the torn-state variant a real preemption
produces) — resumes via ``config.resume``, and asserts the stitched
``history`` is **bit-identical** to the uninterrupted run. The workload
deliberately exercises both resume-sensitive RNG streams: cohort sampling
(``participation_fraction < 1``) and an active dropout failure model, so
the assertion covers the checkpointed ``rng_key`` chain end to end. A
third variant sends ``SIGTERM`` (the TPU preemption notice): the run must
finish its in-flight round, write a final checkpoint, log
``preempted at round N``, exit cleanly — and the resumed tail must again
match the reference bit-for-bit.

Usage::

    python scripts/chaos_resume.py                    # all variants; JSON verdict
    python scripts/chaos_resume.py --rounds 8 --crash-round 3
    python scripts/chaos_resume.py --variants inprocess,sigkill

Internal: ``--child --config '<json>'`` runs one crashed leg in a fresh
interpreter (the parent sets ``DLS_CRASH_AT_ROUND`` / ``DLS_CRASH_KIND``
in its environment). Exit status: 0 when every requested variant is
bit-identical, 1 otherwise.

An accelerator belongs to one process at a time, so the legs are ordered:
every crashed leg that dies in a child runs FIRST, while this process has
not yet touched JAX; the straight, in-process-crash and resumed legs run
in this process afterwards.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import signal
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# Wall-clock fields legitimately differ between runs; everything else in a
# history record must match bit-for-bit.
VOLATILE_KEYS = ("round_seconds",)

# Wall-clock fields inside the schema-v5 ``stream`` sub-object
# (client_residency='streamed' workloads, e.g. the dynamic-population
# variant): transfer/draw TIMINGS differ run to run, while the byte
# counts and sampler name must still match bit-for-bit.
STREAM_VOLATILE_KEYS = (
    "h2d_seconds", "hidden_seconds", "overlap_ratio", "sample_ms",
    "d2h_seconds",
)


def strip_volatile(records: list[dict]) -> list[dict]:
    out = []
    for r in records:
        r = {k: v for k, v in r.items() if k not in VOLATILE_KEYS}
        if isinstance(r.get("stream"), dict):
            r["stream"] = {
                k: v for k, v in r["stream"].items()
                if k not in STREAM_VOLATILE_KEYS
            }
        out.append(r)
    return out


def normalize(records: list[dict]) -> list[dict]:
    """JSON-roundtrip in-memory records so they compare exactly against
    records read back from metrics.jsonl (Python floats survive the trip
    bit-for-bit via repr; this only normalizes types like np.bool_)."""
    return json.loads(json.dumps(strip_volatile(records)))


def read_metrics_jsonl(log_root: str) -> list[dict]:
    """Per-round records a (possibly SIGKILLed) run managed to flush."""
    paths = sorted(glob.glob(os.path.join(log_root, "**", "metrics.jsonl"),
                             recursive=True))
    if not paths:
        return []
    records = []
    for path in paths:
        with open(path) as f:
            records.extend(json.loads(line) for line in f if line.strip())
    return strip_volatile(records)


def chaos_config(workdir: str, leg: str, rounds: int, **overrides):
    """The harness workload: small enough for CPU CI, adversarial enough
    to cover every resume-sensitive stream (client sampling + dropout
    failure model + quorum telemetry in every record)."""
    from distributed_learning_simulator_tpu.config import ExperimentConfig

    kw = dict(
        dataset_name="synthetic",
        model_name="mlp",
        distributed_algorithm="fed",
        worker_number=6,
        round=rounds,
        epoch=1,
        learning_rate=0.1,
        batch_size=32,
        n_train=384,
        n_test=128,
        log_level="INFO",
        dataset_args={"difficulty": 0.5},
        participation_fraction=0.5,
        failure_mode="dropout",
        failure_prob=0.3,
        failure_correlation=0.5,
        min_survivors=1,
        log_root=os.path.join(workdir, leg, "log"),
    )
    kw.update(overrides)
    return ExperimentConfig(**kw)


def run_straight(workdir: str, rounds: int) -> list[dict]:
    from distributed_learning_simulator_tpu.simulator import run_simulation

    result = run_simulation(chaos_config(workdir, "straight", rounds))
    return normalize(result["history"])


def _crash_env(crash_round: int, kind: str) -> dict:
    env = dict(os.environ)
    env["DLS_CRASH_AT_ROUND"] = str(crash_round)
    env["DLS_CRASH_KIND"] = kind
    return env


def run_crashed_inprocess(config, crash_round: int) -> list[dict]:
    """Crashed leg, same interpreter: InjectedCrash unwinds run_simulation;
    the records it already flushed come back from metrics.jsonl."""
    from distributed_learning_simulator_tpu.robustness.chaos import (
        InjectedCrash,
    )
    from distributed_learning_simulator_tpu.simulator import run_simulation

    os.environ["DLS_CRASH_AT_ROUND"] = str(crash_round)
    os.environ["DLS_CRASH_KIND"] = "raise"
    try:
        run_simulation(config)
    except InjectedCrash:
        pass
    else:
        raise AssertionError("injected crash did not fire")
    finally:
        os.environ.pop("DLS_CRASH_AT_ROUND", None)
        os.environ.pop("DLS_CRASH_KIND", None)
    return read_metrics_jsonl(config.log_root)


def run_crashed_subprocess(config, crash_round: int, kind: str):
    """Crashed leg in a fresh interpreter; returns the CompletedProcess
    (callers assert the death signal / clean exit) — flushed records are
    read from the leg's metrics.jsonl afterwards."""
    payload = vars(config)
    return subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child",
         "--config", json.dumps(payload)],
        env=_crash_env(crash_round, kind),
        capture_output=True, text=True, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )


def run_resumed(config) -> list[dict]:
    import dataclasses

    from distributed_learning_simulator_tpu.simulator import run_simulation

    result = run_simulation(dataclasses.replace(config, resume=True))
    return normalize(result["history"])


def stitch_and_compare(straight, crashed, resumed) -> dict:
    """Stitch crashed-prefix + resumed-tail and diff against the straight
    run. The resumed run's first record tells where the prefix ends (a
    crash between checkpoints replays the rounds after the newest valid
    checkpoint — those must reproduce bit-identically too)."""
    if not resumed:
        return {"bit_identical": False, "error": "resumed run has no rounds"}
    start = resumed[0]["round"]
    stitched = [r for r in crashed if r["round"] < start] + resumed
    mismatches = [
        {"round": a.get("round"), "straight": a, "stitched": b}
        for a, b in zip(straight, stitched) if a != b
    ]
    if len(straight) != len(stitched):
        mismatches.append({
            "error": f"length {len(stitched)} != straight {len(straight)}"
        })
    return {
        "bit_identical": not mismatches,
        "resume_start_round": start,
        "rounds": len(straight),
        "mismatches": mismatches[:3],
    }


VARIANTS = ("inprocess", "sigkill", "sigterm")


def variant_config(variant: str, workdir: str, rounds: int):
    return chaos_config(
        workdir, variant, rounds,
        checkpoint_dir=os.path.join(workdir, variant, "ckpt"),
        # Off the crash round's cadence on purpose: resume must also
        # bit-exactly REPLAY the rounds between the newest checkpoint and
        # the crash.
        checkpoint_every=2 if variant == "sigkill" else 1,
    )


def crash_in_child(variant: str, cfg, crash_round: int) -> str | None:
    """The ``sigkill`` / ``sigterm`` crashed leg; returns what went wrong
    with the child, or None when it died (or exited) the way it should."""
    proc = run_crashed_subprocess(cfg, crash_round, variant)
    if variant == "sigkill":
        if proc.returncode != -signal.SIGKILL:
            return (f"child exited {proc.returncode}, expected "
                    f"-SIGKILL; stderr tail: {proc.stderr[-500:]}")
        return None
    if proc.returncode != 0:
        return (f"child exited {proc.returncode}, expected a clean "
                f"0; stderr tail: {proc.stderr[-500:]}")
    # With round pipelining the SIGTERM lands while the NEXT round is
    # already in flight; "finish the in-flight round" then completes
    # crash_round + 1, and that is the round the log names.
    if "preempted at round" not in proc.stderr:
        return "child log lacks the 'preempted at round N' line"
    return None


def run_variant(variant: str, cfg, crash_round: int, straight,
                child_error: str | None) -> dict:
    """Resume ``variant``'s crashed leg in this process and compare the
    stitched history with ``straight``."""
    if child_error is not None:
        return {"bit_identical": False, "error": child_error}
    if variant == "inprocess":
        crashed = run_crashed_inprocess(cfg, crash_round)
    else:
        crashed = read_metrics_jsonl(cfg.log_root)
    verdict = stitch_and_compare(straight, crashed, run_resumed(cfg))
    verdict["crashed_rounds_flushed"] = len(crashed)
    return verdict


def child_main(config_json: str) -> None:
    from distributed_learning_simulator_tpu.config import ExperimentConfig
    from distributed_learning_simulator_tpu.simulator import run_simulation

    result = run_simulation(ExperimentConfig(**json.loads(config_json)))
    print(json.dumps({
        "preempted_at": result["preempted_at"],
        "rounds": len(result["history"]),
    }))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--rounds", type=int, default=6)
    parser.add_argument("--crash-round", type=int, default=3)
    parser.add_argument("--variants", default="inprocess,sigkill,sigterm")
    parser.add_argument("--workdir", default=None,
                        help="scratch dir (default: a fresh temp dir)")
    parser.add_argument("--child", action="store_true")
    parser.add_argument("--config", default=None)
    args = parser.parse_args(argv)
    if args.child:
        child_main(args.config)
        return 0
    if not 0 <= args.crash_round < args.rounds - 1:
        parser.error("--crash-round must leave at least one round to resume")
    variants = [v.strip() for v in args.variants.split(",")]
    unknown = sorted(set(variants) - set(VARIANTS))
    if unknown:
        parser.error(f"unknown variant(s) {unknown}; known: {VARIANTS}")
    workdir = args.workdir or tempfile.mkdtemp(prefix="chaos_resume_")
    configs = {
        v: variant_config(v, workdir, args.rounds) for v in variants
    }
    # Children first: this process has not initialized a JAX backend yet,
    # so each child gets the device to itself and releases it on death.
    child_errors = {
        v: crash_in_child(v, configs[v], args.crash_round)
        for v in variants if v != "inprocess"
    }
    straight = run_straight(workdir, args.rounds)
    report = {"workdir": workdir, "rounds": args.rounds,
              "crash_round": args.crash_round, "variants": {}}
    ok = True
    for variant in variants:
        verdict = run_variant(
            variant, configs[variant], args.crash_round, straight,
            child_errors.get(variant),
        )
        report["variants"][variant] = verdict
        ok = ok and verdict.get("bit_identical", False)
    report["ok"] = ok
    print(json.dumps(report, indent=2))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
