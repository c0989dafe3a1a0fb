#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, which imports JAX itself and starts no other. It builds the
cell's model on the device from ``--seed``, warms up the programs the
cell's traffic uses (set-up), measures for ``--seconds``, compares what the
timed path produced with the plain reference, and prints one JSON object as
the last line of standard output. Everything a cell is made of is data that
this file finds by name: ``BENCHMARK.json`` (cells, metrics), and under
``benchmark/``: ``configs/<config>.json``, ``traffic/<traffic>.json``,
``limits/<workload>.json``, ``metrics/<metric>.json``, ``peaks.json``, and
``families/<family>.py`` for the ``family`` that the configuration names
(the model's leaves, the program's model built for the driver, the plain
reference, the work functions of its rooflines).
It exits non-zero, and prints no result, without a TPU whose kind is in
``peaks.json``, with fewer chips than the cell asks for, or without the
program beside it.
"""
from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def cell_files(workload: str):
    """(BENCHMARK.json, the cell's entry, its configuration, mix and
    limits), each found by the name the one before gives."""
    bench = load(ROOT, "BENCHMARK.json")
    cell = by_name(bench["workloads"], workload, "workload")
    config = load(ROOT, by_name(bench["configs"], cell["config"],
                                "configuration")["file"])
    mix = load(HERE, "traffic", cell["traffic"] + ".json")
    check = load(HERE, "limits", cell["name"] + ".json")
    return bench, cell, config, mix, check


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1: after the reference, also put the control "
                         "(the reference in the precision below the "
                         "configuration's) and the planted faults in the "
                         "program's place, judge each as a run is judged, "
                         "and print its correct=false on an earlier line: "
                         "how PERF.md's upper readings are taken. The "
                         "driver's runs never ask for it.")
    args = ap.parse_args()

    bench, cell, config, mix, check = cell_files(args.workload)
    seconds = float(bench["run_seconds"]) if args.seconds is None \
        else args.seconds
    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        raise SystemExit("benchmark/run.py measures the program in this "
                         "checkout, and paddle_tpu/ is not here.")

    import jax

    devs = jax.devices()
    peaks_table = load(HERE, "peaks.json")
    if devs[0].platform != "tpu":
        raise SystemExit("benchmark/run.py needs a TPU: jax.devices()[0]."
                         f"platform is {devs[0].platform!r}. Nothing ran.")
    if devs[0].device_kind not in peaks_table:
        raise SystemExit(f"device kind {devs[0].device_kind!r} is not in "
                         "benchmark/peaks.json: no peak to measure against.")
    if len(devs) < cell["chips"]:
        raise SystemExit(f"{cell['name']} needs {cell['chips']} chips; "
                         f"jax.devices() has {len(devs)}.")

    from benchmark.lib.common import Run, say

    run = Run(root=ROOT, workload=cell["name"], seed=args.seed,
              seconds=seconds, trace=bool(args.trace), config=config,
              mix=mix, check=check, peaks=peaks_table[devs[0].device_kind],
              t_process=T_PROCESS, control=bool(args.control))
    say("start", workload=cell["name"], seed=args.seed, seconds=seconds,
        trace=args.trace, device_kind=devs[0].device_kind,
        devices=len(devs), jax=jax.__version__,
        since_process_start=round(time.time() - T_PROCESS, 2))
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell["chips"]}
    result = execute(run, bench, device)
    print(json.dumps(result), flush=True)


def import_program() -> None:
    """``import paddle_tpu``, before the first compile of the process: the
    import places JAX's persistent compilation cache and sets what is kept
    there. Most of its seconds on the chip's host are not the program's:
    ``paddle_tpu.distributed.checkpoint`` imports ``orbax``, that imports
    ``google.cloud.logging``, and every ``google.*`` package then asks
    ``importlib.metadata.packages_distributions()``, 4 to 12 s a call there,
    for the name it would print in a warning that it does not give. The
    table cannot change while one process imports, so it is built once."""
    import functools
    import importlib.metadata as metadata

    real = metadata.packages_distributions
    metadata.packages_distributions = functools.cache(real)
    try:
        import paddle_tpu  # noqa: F401
    finally:
        metadata.packages_distributions = real


def execute(run, bench: dict, device: dict) -> dict:
    """Everything of a run after the look for a chip: drive the cell,
    compare with the reference, reduce the trace. Returns the object of
    the last line (and prints the numbers compared to standard error)."""
    from benchmark.lib import check, reduce, serve, train
    from benchmark.lib.common import family_of, say

    import_program()
    say("program.imported",
        since_process_start=round(time.time() - run.t_process, 2))
    per_layer = [(m, load(HERE, "metrics", m["name"] + ".json"))
                 for m in bench["per_layer"] if applies(m, run.workload)]
    # the engine's counters that a metric of this cell reads: the serving
    # driver snapshots them at both ends of the window
    run.counters = frozenset(
        spec["args"][k] for _, spec in per_layer
        for k in ("counter", "over") if k in spec.get("args", {}))
    numbers = {"train": train.drive, "serve": serve.drive}[
        run.config["driver"]](run)
    facts = run.facts
    correct, rows = check.judge(numbers, run.check["limits"])
    device = dict(device, memory_peak_bytes=facts["memory_peak_bytes"])
    result = {"correct": correct, "attempted": facts["attempted"],
              "failed": facts["failed"], "metrics": {}, "device": device}
    if run.trace:
        tw = facts["trace"]
        path = tw.file()
        if path is None:
            raise SystemExit("the traced run left no trace")
        trace = reduce.load(path)
        tw.discard()
        facts["model"] = run.config["model"]
        facts["work"] = family_of(run.config).WORK
        for m, spec in per_layer:
            value = reduce.READERS[spec["reader"]](
                trace, facts, spec.get("args", {}), run.peaks)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace.window_s
        result["breakdown"] = trace.breakdown()
    else:
        values = dict(facts["end_to_end"], setup_s=facts["setup_s"])
        for m in bench["end_to_end"]:
            if applies(m, run.workload):
                result["metrics"][m["name"]] = {"value": values[m["name"]],
                                                "unit": m["unit"]}
    result["compared"] = rows
    check.say_compared(rows, correct)
    return result


if __name__ == "__main__":
    main()
