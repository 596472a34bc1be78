"""Compile a benchmark cell's round program for a described TPU v5e, with
no chip: what the chip's compiler will do, read before a chip call.

    python scripts/compile_for_v5e.py --workload solar_open2_fed_seq4k_c8

Builds the cell's ``round_fn`` as ``run_simulation`` does, from the cell's
configuration and traffic files (``BENCHMARK.json``, ``benchmark/``): the
program's config from their argv, the model, the algorithm's round program,
the shapes of the packed client data and of the parameters, the donation
``run_simulation`` would choose. It lowers that for ONE described v5e chip
(``jax.experimental.topologies``; nothing runs, no array is placed) and
compiles it with the TPU compiler installed here, which reproduces the
chip's fusion names. Printed, as one JSON object a line:

* ``memory``: the compiler's count (argument, output, alias, temporaries,
  generated code; ``peak`` = temp + argument + output - alias) and ``compile_s``;
* ``remat``: the instructions XLA's rematerialisation cloned (named
  ``<op>.remat...``) outside fused computations, and how many of them are
  PAIRS: ``<op>`` and ``<op>.remat`` both alive in one computation, so the
  same result made twice (a ``.remat`` name standing alone is an op that
  was moved, not extra work: PERF.md § 5);
* one ``pair`` line each: both names, the shape, the source line the op was
  traced from, and the users of the original and of the twin;
* ``cycles_by_computation``: the sums of the ``estimated_cycles`` the TPU
  compiler writes on its fusions, for the computations that hold most (the
  entry and the client loop's body, which runs once a client: a while
  body's sum is NOT multiplied by its trip count), and ``remat_cycles`` /
  ``pair_cycles`` of the clones. A proxy to rank two forms of one program
  before a chip call (the sequence cell's 8 x body + entry came within
  0.5 % of the traced round at PR 32), never a device number.

``--root`` compiles another checkout's program (the parent's, unpacked with
``git archive``), ``--dump`` keeps the compiled module's text, ``--source``
prints every instruction traced from lines that match (``solar_open2.py:36``).

A compile that passes is not a chip run: no time, rate or share comes from
here. Imported by neither the package nor the benchmark.
"""

from __future__ import annotations

import argparse
import collections
import importlib.util
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*(?P<shape>\(.*?\)|\S+)\s+"
    r"(?P<opcode>[\w\-]+)\((?P<operands>.*?)\)(?P<rest>.*)$"
)
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?(?P<name>[\w.\-]+)\s*\(.*\{\s*$")
_OPERAND = re.compile(r"%([\w.\-]+)")
_FRAME = re.compile(r"stack_frame_id=(\d+)")
_TABLE_ROW = re.compile(r"^(\d+) (.*)$")
_FIELD = re.compile(r"(\w+)=(\d+)")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_CYCLES = re.compile(r'"estimated_cycles":"(\d+)"')
#: Instructions a value passes through unchanged: a user behind one of
#: these is the user that matters.
_TRANSPARENT = ("bitcast", "get-tuple-element", "copy", "tuple",
                "opt-barrier", "copy-start", "copy-done")


def frame_sources(text: str) -> dict:
    """``{stack_frame_id: "file.py:line"}`` from the tables a module's
    text opens with (``FileNames``, ``FileLocations``, ``StackFrames``):
    the innermost frame of user code an instruction was traced from."""
    tables, table = {}, None
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            table = tables.setdefault(line, {})
        elif not line.strip():
            table = None
        elif table is not None:
            row = _TABLE_ROW.match(line)
            if row:
                table[int(row.group(1))] = row.group(2)
        elif line.startswith(("ENTRY", "%")):
            break
    out = {}
    for frame, row in tables.get("StackFrames", {}).items():
        location = dict(_FIELD.findall(row)).get("file_location_id")
        fields = dict(_FIELD.findall(
            tables["FileLocations"].get(int(location or 0), "")))
        name = tables["FileNames"].get(int(fields.get("file_name_id", 0)))
        if name:
            out[frame] = (
                f"{os.path.basename(name.strip('\"'))}:{fields['line']}")
    return out


def parse_module(text: str) -> dict:
    """``{name: instruction}`` of a compiled module's text; an instruction
    has ``computation``, ``shape``, ``opcode``, ``operands``, ``source``
    (``file:line`` or ``None``), ``calls`` and ``fused`` (it lies inside a
    fusion's computation)."""
    instructions, computation = {}, None
    frames = frame_sources(text)
    for line in text.splitlines():
        if computation is None:
            m = _COMPUTATION.match(line)
            if m:
                computation = m.group("name")
            continue
        if line.startswith("}"):
            computation = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        frame = _FRAME.search(m.group("rest"))
        calls = _CALLS.search(m.group("rest"))
        cycles = _CYCLES.search(m.group("rest"))
        instructions[m.group("name")] = {
            "computation": computation,
            "shape": m.group("shape"),
            "opcode": m.group("opcode"),
            "operands": _OPERAND.findall(m.group("operands")),
            "source": frames.get(int(frame.group(1))) if frame else None,
            "calls": calls.group(1) if calls else None,
            "cycles": int(cycles.group(1)) if cycles else 0,
        }
    fused = {
        i["calls"] for i in instructions.values()
        if i["opcode"] == "fusion" and i["calls"]
    }
    for i in instructions.values():
        i["fused"] = i["computation"] in fused
    return instructions


def users_of(instructions: dict, name: str, depth: int = 4) -> list[str]:
    """``<user> <opcode> <shape> @ <source>`` of every instruction that
    reads ``name``, looking through copies, bitcasts and tuples."""
    out = []
    for user, i in instructions.items():
        if i["fused"] or name not in i["operands"]:
            continue
        if i["opcode"] in _TRANSPARENT and depth:
            out.extend(users_of(instructions, user, depth - 1))
        else:
            out.append(
                f"{user} {i['opcode']} {i['shape'].split('{')[0]} "
                f"@ {i['source']}"
            )
    return sorted(set(out))


def remat_report(instructions: dict) -> tuple[dict, list[dict]]:
    top = {n: i for n, i in instructions.items() if not i["fused"]}
    clones = sorted(n for n in top if ".remat" in n)
    pairs = []
    for clone in clones:
        base = clone.split(".remat")[0]
        if base in top and (
            top[base]["computation"] == top[clone]["computation"]
        ):
            pairs.append({
                "pair": [base, clone],
                "shape": top[clone]["shape"].split("{")[0],
                "source": top[clone]["source"],
                "computation": top[clone]["computation"],
                "users": users_of(instructions, base),
                "twin_users": users_of(instructions, clone),
            })
    by_source = collections.Counter(top[n]["source"] for n in clones)
    cycles = collections.Counter()
    for i in top.values():
        cycles[i["computation"]] += i["cycles"]
    return {
        "remat_instructions": len(clones),
        "pairs": len(pairs),
        "remat_cycles": sum(top[n]["cycles"] for n in clones),
        "pair_cycles": sum(top[p["pair"][1]]["cycles"] for p in pairs),
        "by_source": dict(by_source.most_common(12)),
        "cycles_by_computation": dict(cycles.most_common(4)),
    }, pairs


def _load_file(path: str):
    spec = importlib.util.spec_from_file_location("_cell_task", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def build_round(root: str, workload: str, seed: int):
    """``(round_fn, argument shapes, donate_argnums)`` of the cell, built
    the way ``run_simulation`` builds them (simulator.py, "programs")."""
    for path in (os.path.join(root, "benchmark"), root):
        sys.path.insert(0, path)
    import jax
    from harness import spec as bench_spec

    from distributed_learning_simulator_tpu.config import get_config
    from distributed_learning_simulator_tpu.factory import get_algorithm
    from distributed_learning_simulator_tpu.models.registry import get_model
    from distributed_learning_simulator_tpu.parallel.engine import (
        make_decoder,
        make_eval_fn,
        make_optimizer,
        make_reshaper,
    )
    from distributed_learning_simulator_tpu.simulator import build_client_data

    cell = bench_spec.load_cell(root, workload)
    if cell["cell"]["chips"] != 1:
        raise SystemExit("one described chip: a mesh cell is not built here")
    config = get_config(bench_spec.program_argv(cell, seed, []))
    task = _load_file(os.path.join(
        root, "benchmark", "tasks",
        cell["config"].get("task", "image_classification") + ".py"))
    data_spec = cell["config"]["data"]
    dataset = task.program_dataset(
        config.dataset_name, task.make(seed, data_spec), data_spec)
    client_data = build_client_data(config, dataset)
    model = get_model(config.model_name, num_classes=dataset.num_classes,
                      **config.model_args)
    params = jax.eval_shape(lambda: model.init(
        jax.random.key(0), dataset.x_train[:1])["params"])
    optimizer = make_optimizer(
        config.optimizer_name, config.learning_rate,
        momentum=config.momentum, weight_decay=config.weight_decay)
    algorithm = get_algorithm(config.distributed_algorithm, config)
    algorithm.prepare(model.apply, make_eval_fn(
        model.apply, preprocess=make_reshaper(dataset.x_test.shape[1:])))
    round_fn = algorithm.make_round_fn(
        model.apply, optimizer, client_data.n_clients,
        preprocess=(make_decoder(client_data.sample_shape)
                    if client_data.compact else None),
        client_sizes=client_data.sizes,
    )
    # run_simulation's choice where the cell has no auditor, no server
    # optimizer and no client state to checkpoint (the benchmark's cells).
    pipelined = config.pipeline_rounds and algorithm.supports_round_pipelining
    donate = (0, 1) if (
        not pipelined and algorithm.supports_global_donation) else (1,)
    arrays = (client_data.x, client_data.y, client_data.mask,
              client_data.sizes)
    return round_fn, params, arrays, donate


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--dump", help="write the compiled module here")
    parser.add_argument("--source", help="list instructions traced from "
                        "lines that contain this (file.py:line prefix)")
    args = parser.parse_args(argv)

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    # A compile for a described device can be written to the persistent
    # cache but never read back without the chip.
    jax.config.update("jax_enable_compilation_cache", False)
    round_fn, params, arrays, donate = build_round(
        os.path.abspath(args.root), args.workload, args.seed)
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def described(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)

    shapes = jax.tree_util.tree_map(described, (params, None) + arrays)
    key = described(jax.eval_shape(lambda: jax.random.key(0)))
    t0 = time.perf_counter()
    lowered = jax.jit(round_fn, donate_argnums=donate).lower(*shapes, key)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    m = compiled.memory_analysis()
    print(json.dumps({"memory": {
        "argument": m.argument_size_in_bytes,
        "output": m.output_size_in_bytes,
        "alias": m.alias_size_in_bytes,
        "temp": m.temp_size_in_bytes,
        "generated_code": m.generated_code_size_in_bytes,
        "peak": (m.temp_size_in_bytes + m.argument_size_in_bytes
                 + m.output_size_in_bytes - m.alias_size_in_bytes),
    }, "donate_argnums": list(donate), "trace_lower_s": round(t1 - t0, 1),
        "compile_s": round(t2 - t1, 1)}), flush=True)
    text = compiled.as_text()
    if args.dump:
        with open(args.dump, "w") as f:
            f.write(text)
    instructions = parse_module(text)
    summary, pairs = remat_report(instructions)
    print(json.dumps({"remat": summary}))
    for pair in pairs:
        print(json.dumps({"pair": pair}))
    if args.source:
        for name, i in instructions.items():
            if not i["fused"] and args.source in (i["source"] or ""):
                print(json.dumps({"op": name, "opcode": i["opcode"],
                                  "shape": i["shape"].split("{")[0],
                                  "source": i["source"],
                                  "users": users_of(instructions, name)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
