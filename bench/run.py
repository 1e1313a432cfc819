#!/usr/bin/env python
"""Run one cell of the benchmark on the accelerator this machine holds.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of BENCHMARK.json's "workloads"; its configuration,
traffic and limits are files under bench/ found by the names there, and
its job kind names a module in bench/jobs/. One process holds the chip and
starts no other. With ``--trace 0`` the last line of stdout carries the
cell's end-to-end metrics; with ``--trace 1`` its per-layer metrics, read
from a profiler trace of the window by the files in bench/metrics/. A run
that finds no TPU, or fewer chips than the cell asks for, prints no result
and exits 3.

``--control fp8`` reads the cell's comparison with the reference, computed
in float8, in the program's place (``half_batch``: a fine-tuning fault,
the reference over half of each batch). The benchmark's own runs do not
pass it.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import common  # noqa: E402

NO_DEVICE = 3


class Context:
    """What a job needs from the harness: the devices, progress notes, the
    end of set-up, and the window (timed, compile-counted, traced)."""

    def __init__(self, devices, trace: bool, trace_dir: pathlib.Path):
        self.devices = devices
        self.trace = trace
        self.trace_dir = trace_dir
        self.counter = common.CompileCounter()
        self.setup_s = None
        self.window_s = None

    def note(self, msg: str) -> None:
        common.note(msg, T0)

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - T0
        self.note(f"set-up done ({self.setup_s:.1f} s)")

    @contextlib.contextmanager
    def window(self):
        import jax
        if self.trace:
            jax.profiler.start_trace(str(self.trace_dir))
        t0 = time.perf_counter()
        with self.counter.window(), common.span("bench.window"):
            yield
        self.window_s = time.perf_counter() - t0
        if self.trace:
            jax.profiler.stop_trace()
        self.note(f"window closed ({self.window_s:.1f} s, "
                  f"{self.counter.count} compilations inside)")


def _limits_line(numbers: dict, limits: dict) -> dict:
    return {k: {"value": numbers[k]["value"], "limit": limits[k]["limit"]}
            for k in limits["compare"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("fp8", "half_batch"), default=None)
    args = ap.parse_args(argv)

    cell = common.Cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s). Nothing was "
              "run.", file=sys.stderr)
        return NO_DEVICE
    return run_cell(cell, args, devices[:cell.chips])


def run_cell(cell: common.Cell, args, devices) -> int:
    """Everything after the look for a chip: set-up, window, reading,
    correctness and the result line."""
    common.use_compile_cache()
    job = importlib.import_module(f"bench.jobs.{cell.job}")
    trace_dir = common.BENCH / ".cache" / "trace" / cell.name
    if args.trace:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = Context(devices, bool(args.trace), trace_dir)
    ctx.control = args.control
    out = job.run(cell, args.seed, args.seconds, ctx)
    if ctx.control:
        print(json.dumps(out["numbers"], default=str), file=sys.stderr)
        return _finish(cell, out["numbers"], None, None, devices)
    metrics, extra = {}, {}
    if args.trace:
        from bench import trace as tr
        events = tr.read(trace_dir)
        reduced = tr.reduce(events)
        _save_op_names(trace_dir, reduced)
        reading = {"cell": cell, "trace": reduced, "run": out,
                   "peaks": tr.peaks(devices[0].device_kind)}
        for m in cell.per_layer():
            value = common.load_module(
                common.BENCH / "metrics" / f"{m['name']}.py").read(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        extra = {"busy_s": reduced["busy_s"], "window_s": reduced["window_s"]}
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduced["idle_gaps"]}
    else:
        for m in cell.end_to_end():
            value = (ctx.setup_s if m["name"] == "setup_s"
                     else out["end_to_end"][m["name"]])
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = None
    device = {**common.device_info(devices),
              "memory_peak_bytes": out["memory_peak_bytes"], **extra}
    if ctx.counter.count:
        out["numbers"]["compilations_in_window"] = {"value": ctx.counter.count}
    return _finish(cell, out["numbers"], metrics, device, devices,
                   attempted=out["attempted"], failed=out["failed"],
                   breakdown=breakdown, detail=out)


def _finish(cell, numbers, metrics, device, devices, attempted=0, failed=0,
            breakdown=None, detail=None) -> int:
    limits = cell.limits
    compared = _limits_line(numbers, limits)
    correct = all(v["value"] <= v["limit"] for v in compared.values())
    if "compilations_in_window" in numbers:
        compared["compilations_in_window"] = {
            "value": numbers["compilations_in_window"]["value"], "limit": 0}
        correct = False
    if detail is not None:
        _save_detail(cell, detail)
    for name, v in compared.items():
        print(f"check {name}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics or {},
            "device": device or common.device_info(devices)}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared
    print(json.dumps(line), flush=True)
    return 0


def _save_op_names(trace_dir: pathlib.Path, reduced: dict) -> None:
    """Each operation name of the window once, with its stats: what a
    metric file's patterns are written against."""
    seen = {}
    for name, t, stats in reduced["ops"]:
        e = seen.setdefault(name, {"seconds": 0.0, "calls": 0,
                                   "stats": stats})
        e["seconds"] += t
        e["calls"] += 1
    with open(trace_dir / "ops.json", "w") as f:
        json.dump(seen, f, indent=0, default=str)


def _save_detail(cell, detail: dict) -> None:
    """The run's numbers in full, beside the result line (for PERF.md)."""
    d = common.BENCH / ".cache" / "runs"
    d.mkdir(parents=True, exist_ok=True)
    keep = {k: v for k, v in detail.items() if k != "end_to_end"}
    keep["end_to_end"] = detail.get("end_to_end")
    with open(d / f"{cell.name}.json", "w") as f:
        json.dump(keep, f, default=str)


if __name__ == "__main__":
    sys.exit(main())
