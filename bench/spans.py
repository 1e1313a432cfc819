"""The program's own spans and counters in a profiler trace of the window.

The program opens host spans whose names start "spt." (``Trainer.run``:
a step span "spt.train" with the children "spt.train.next_batch",
".place", ".dispatch", ".fetch", ".hook" and ".checkpoint", and a
zero-length "spt.train.counters" whose arguments count the routed FFN's
pairs and slots). ``read`` takes them, with device 0's operations and the
benchmark's window, from the ``.xplane.pb`` that ``bench/run.py`` has just
reduced, parsing each file once per process. The rest works on those
plain events alone, so a test can feed it a small recorded trace:

* ``idle``: the stretches inside the window in which device 0 ran no leaf
  operation (``trace.leaves``, ``trace.union``);
* ``idle_by_span``: that idle time divided among the innermost program
  spans open at each instant, by overlap, so a gap that straddles two
  spans is shared between them; time under no program span is ``None``'s;
* ``steps``: the window's step spans that ran a step (those that hold a
  dispatch; the last ask of a feed that has run dry holds none).

A trace without program spans (a program that opens none) reads as empty,
and the metric files read nothing from it.
"""
from __future__ import annotations

import functools
import glob
import pathlib
from typing import Dict, List, Optional, Tuple

from bench import common, trace

PREFIX = "spt."
STEP = "spt.train"
DISPATCH = "spt.train.dispatch"
COUNTERS = "spt.train.counters"


def of_cell(cell) -> Optional[dict]:
    """``read`` of the trace that ``bench/run.py`` has the cell's traced
    run write."""
    return read(common.BENCH / ".cache" / "trace" / cell.name)


def read(trace_dir: pathlib.Path) -> Optional[dict]:
    """Plain events of the newest trace under ``trace_dir``: device 0's
    operations, the benchmark's spans and the program's, each with its
    arguments. None where there is no trace."""
    files = sorted(glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        return None
    return _parse(files[-1])


@functools.lru_cache(maxsize=1)
def _parse(path: str) -> dict:
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices, host, program = {}, [], []
    for plane in data.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            devices[plane.name] = [
                [e.name, float(e.start_ns), float(e.duration_ns), {}]
                for line in plane.lines if line.name == trace.OPS_LINE
                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns)])
                    elif e.name.startswith(PREFIX):
                        program.append([e.name, float(e.start_ns),
                                        float(e.duration_ns),
                                        dict(e.stats)])
    return {"devices": devices, "host": host, "program": program}


def idle(events: dict) -> List[Tuple[float, float]]:
    """Stretches of the window in which device 0 ran nothing."""
    win = trace._window(events["host"])
    if win is None or not events["devices"]:
        return []
    w0, w1 = win
    ops = events["devices"][sorted(events["devices"])[0]]
    busy = trace.union((max(s, w0), min(s + d, w1))
                       for _, s, d, _ in trace.leaves(ops)
                       if s < w1 and s + d > w0)
    edges = [w0] + [x for ab in busy for x in ab] + [w1]
    return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def in_window(events: dict) -> List[list]:
    """The program's events that start inside the window."""
    win = trace._window(events["host"])
    if win is None:
        return []
    return [e for e in events["program"] if win[0] <= e[1] <= win[1]]


def idle_by_span(events: dict) -> Dict[Optional[str], float]:
    """Device-0 idle ns of the window by the innermost program span open,
    split by overlap; ``None`` holds what falls under no program span."""
    spans = [(s, s + d, n) for n, s, d, _ in in_window(events) if d > 0]
    out: Dict[Optional[str], float] = {}
    for a, b in idle(events):
        cuts = sorted({a, b} | {x for s, e, _ in spans for x in (s, e)
                                if a < x < b})
        for lo, hi in zip(cuts, cuts[1:]):
            # innermost: the shortest; of two alike, the child ("a.b.c"
            # is opened inside "a.b")
            open_ = [(e - s, -n.count("."), n) for s, e, n in spans
                     if s <= lo and hi <= e]
            name = min(open_)[2] if open_ else None
            out[name] = out.get(name, 0.0) + (hi - lo)
    return out


def steps(events: dict) -> List[Tuple[float, float]]:
    """(start, end) of the window's step spans that ran a step."""
    prog = in_window(events)
    ran = [s for n, s, _, _ in prog if n == DISPATCH]
    return [(s, s + d) for n, s, d, _ in prog if n == STEP
            and any(s <= t <= s + d for t in ran)]


def idle_in(events: dict, spans: List[Tuple[float, float]]) -> float:
    """Device-0 idle ns of the window inside the given spans."""
    return sum(max(0.0, min(b, e) - max(a, s))
               for a, b in idle(events) for s, e in spans)


def counters(events: dict) -> List[dict]:
    """The arguments of the window's counter events."""
    return [args for n, _, _, args in in_window(events) if n == COUNTERS]
