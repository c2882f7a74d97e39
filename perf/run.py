#!/usr/bin/env python3
"""One cell of the benchmark, once.

    python perf/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell by name in ``BENCHMARK.json`` and ``perf/workloads/``, its
configuration in ``perf/configs/``, its traffic kind in
``perf/traffic/<kind>.py`` and, for a traced run, each per-layer metric
in ``perf/metrics/<name>.py``. Fails without a TPU (or with fewer chips
than the cell asks for); never pins or falls back to the CPU. Phase
lines (one JSON object each) go first; the LAST line of standard output
is the result object of the benchmark's contract. See ``perf/README.md``.

Nothing touches JAX at import time: the program's planner pool spawns
workers that re-import this file.
"""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field

T_PROCESS = time.monotonic()

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)

from perf import common  # noqa: E402


@dataclass
class Run:
    """Everything one run knows; traffic kinds fill it, metrics read it."""

    workload: dict  # perf/workloads/<name>.json
    config: dict  # perf/configs/<config>.json
    cell: dict  # the cell's entry in BENCHMARK.json
    seed: int
    seconds: float
    trace: bool
    chips: int
    device: dict = field(default_factory=dict)
    peaks: dict = field(default_factory=dict)
    compiles: common.CompileCounter | None = None
    # filled by the traffic kind
    state: dict = field(default_factory=dict)  # prepare() -> window()/check()
    setup: dict = field(default_factory=dict)  # plan_s, first_call_s, plan info
    window: dict = field(default_factory=dict)  # what the window recorded
    reduced: dict | None = None  # trace reduction of the traced window


def find_cell(benchmark: dict, name: str) -> dict:
    for cell in benchmark["workloads"]:
        if cell["name"] == name:
            return cell
    common.fail(f"BENCHMARK.json has no workload {name!r}")


def load_metric(name: str):
    path = os.path.join(common.PERF_DIR, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "perf_metric_" + name.replace(".", "_").replace("-", "_"), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metrics_of_cell(benchmark: dict, group: str, cell_name: str, reported: set) -> list:
    """Entries of ``group`` that this cell reports: those that list it,
    and those that list nothing and move a metric it reports."""
    out = []
    for entry in benchmark[group]:
        cells = entry.get("workloads")
        if cells is not None:
            if cell_name in cells:
                out.append(entry)
        elif group == "end_to_end" or entry["moves"] in reported:
            out.append(entry)
    return out


def load_benchmark() -> dict:
    with open(os.path.join(CHECKOUT, "BENCHMARK.json"), encoding="utf-8") as fh:
        return json.load(fh)


def open_run(benchmark: dict, workload: str, seed: int, seconds: float, trace: bool) -> Run:
    """The cell's files, the look for its chips (a failure without them:
    never the CPU), the compile cache; prints the ``device`` line."""
    cell = find_cell(benchmark, workload)
    chips = int(cell["chips"])

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        common.fail(
            f"perf/run.py needs a TPU; JAX reports {len(devices)} "
            f"{devices[0].platform!r} device(s)"
        )
    if len(devices) < chips:
        common.fail(f"{cell['name']} needs {chips} chips, JAX reports {len(devices)}")

    from perf import sut

    run = Run(
        workload=common.load_json("workloads", f"{cell['name']}.json"),
        config=common.load_json("configs", f"{cell['config']}.json"),
        cell=cell, seed=seed, seconds=seconds, trace=trace, chips=chips,
        device=common.device_record(jax, chips),
        peaks=common.peaks_for(devices[0].device_kind),
        compiles=common.CompileCounter().install(),
    )
    cache_dir = sut.enable_compile_cache()
    common.name_device(run.device)
    common.emit({
        "phase": "device", "seconds": round(time.monotonic() - T_PROCESS, 3),
        "jax": jax.__version__, "compile_cache_dir": cache_dir,
        "workload": cell["name"], "seed": seed, "trace": int(trace),
    })
    return run


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    benchmark = load_benchmark()
    run = open_run(benchmark, args.workload, args.seed, args.seconds, bool(args.trace))
    result = drive(run, benchmark)
    numbers = result.pop("numbers")
    sys.stdout.flush()
    for name, pair in numbers.items():
        print(f"compared {name}: value {pair['value']!r} limit {pair['limit']!r}",
              file=sys.stderr, flush=True)
    result["compared"] = numbers  # last key of the line
    print(json.dumps(result), flush=True)
    return 0


def drive(run: Run, benchmark: dict) -> dict:
    """Set-up, the window, the check, the metrics: the part of a run that
    needs no look for a chip (``perf/tests`` drive it on the CPU)."""
    import jax

    traffic = importlib.import_module(f"perf.traffic.{run.workload['traffic']['kind']}")
    name = run.cell["name"]

    traffic.prepare(run)
    setup_s = time.monotonic() - T_PROCESS
    common.emit({"phase": "setup", "seconds": round(setup_s, 3),
                 "programs_built": run.compiles.total, **run.setup})

    trace_dir = os.path.join(common.PERF_DIR, "_trace", name)
    if run.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the perf:* spans are host TraceMes
        options.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    run.compiles.mark()
    t0 = time.monotonic()
    try:
        traffic.window(run)  # wraps what it measures in the span perf:window
    finally:
        window_s = time.monotonic() - t0
        if run.trace:
            jax.profiler.stop_trace()
    built_in_window = run.compiles.since_mark()
    memory_peak = common.memory_peak_bytes(jax, run.chips)
    common.emit({"phase": "window", "seconds": round(window_s, 3),
                 "programs_built_in_window": built_in_window,
                 "memory_peak_bytes": memory_peak,
                 **traffic.summary(run)})

    if run.trace:
        from perf import trace_reduce

        t0 = time.monotonic()
        run.reduced = trace_reduce.reduce_dir(trace_dir, chips=run.chips)
        shutil.rmtree(trace_dir, ignore_errors=True)  # tens of MB a run: not kept
        common.progress("trace", "reduced", t0)

    t0 = time.monotonic()
    with common.span("reference"):
        numbers, attempted, failed = traffic.check(run)
    common.progress("check", "reference compared", t0)
    correct = (
        failed == 0 and built_in_window == 0
        and all(within(pair) for pair in numbers.values())
    )
    numbers["programs_built_in_window"] = {"value": built_in_window, "limit": 0}

    e2e = traffic.end_to_end(run)
    e2e["setup_s"] = setup_s
    metrics: dict = {}
    if not run.trace:
        for entry in metrics_of_cell(benchmark, "end_to_end", name, set()):
            if entry["name"] not in e2e:
                raise RuntimeError(f"{name} did not measure {entry['name']}")
            metrics[entry["name"]] = {"value": e2e[entry["name"]], "unit": entry["unit"]}
    else:
        for entry in metrics_of_cell(benchmark, "per_layer", name, set(e2e)):
            value = load_metric(entry["name"]).read(run)
            if value is not None:  # a reader that finds nothing says nothing
                metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}

    device = {**run.device, "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct), "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if run.trace:
        device["busy_s"] = run.reduced["busy_s"]
        device["window_s"] = run.reduced["window_s"]
        result["breakdown"] = {
            "device_ops": run.reduced["device_ops"][:10],
            "idle_gaps": run.reduced["idle_gaps"][:10],
        }
    result["numbers"] = numbers
    return result


def within(pair: dict) -> bool:
    value, limit = pair["value"], pair["limit"]
    return value is not None and value == value and value <= limit


if __name__ == "__main__":
    sys.exit(main())
