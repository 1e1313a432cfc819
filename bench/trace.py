"""From a profiler trace of the window to the numbers the metric files read.

``read`` turns the ``.xplane.pb`` that ``jax.profiler`` wrote into plain
events: per device, the operations of its "XLA Ops" line; on the host, the
spans the benchmark opened (names starting "bench."). ``reduce`` works on
those plain events alone, so a test can feed it a small recorded trace:

* the window is the host span "bench.window";
* only leaf operations count: a loop's event spans its body's;
* ``busy_s`` is the union of a device's operation intervals inside the
  window, averaged over the devices; ``window_s`` is the window's length;
* ``device_ops``: the operations that took most time, summed by name;
* ``idle_gaps``: the longest stretches in which device 0 ran nothing,
  each named by the innermost benchmark span open at its middle.
"""
from __future__ import annotations

import glob
import pathlib
import re
from typing import Iterable, List, Optional, Tuple

from bench import common

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
TOP = 10


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown kind is an error."""
    table = common.load_json(common.BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json; known: {sorted(table)}")
    return table[device_kind]


def read(trace_dir: pathlib.Path) -> dict:
    """Plain events of the newest trace under ``trace_dir``."""
    import jax
    files = sorted(glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    devices, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    short, desc = describe(e.name)
                    ops.append([short, float(e.start_ns),
                                float(e.duration_ns), {"desc": desc}])
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns)])
    return {"devices": devices, "host": host}


_INSTR = re.compile(r"^%?([\w.\-]+) = (\S+) ([\w\-]+)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def describe(text: str) -> Tuple[str, str]:
    """(name, "name = type op target") of an ops-line event, whose name is
    the HLO instruction's text ("%name = type op(operands), attributes").
    A Pallas kernel is a custom call whose target is "tpu_custom_call"."""
    m = _INSTR.match(text)
    if not m:
        name = text.split(" = ", 1)[0].lstrip("%")
        return name, name
    t = _TARGET.search(text)
    return m.group(1), " ".join([f"{m.group(1)} = {m.group(2)}", m.group(3)]
                                + ([t.group(1)] if t else []))


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _window(host) -> Optional[Tuple[float, float]]:
    spans = [(s, s + d) for n, s, d in host if n == "bench.window"]
    return spans[0] if spans else None


def _label(host, t: float) -> str:
    """Innermost benchmark span open at time t (the window if none)."""
    open_ = [(d, n) for n, s, d in host
             if s <= t <= s + d and n != "bench.window"]
    return min(open_)[1] if open_ else "bench.window"


def leaves(ops: List[list]) -> List[list]:
    """The operations that contain no other: a loop or call on the ops line
    spans the operations of its body, which are what ran."""
    ordered = sorted(ops, key=lambda o: (o[1], -o[2]))
    out = []
    for i, o in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is None or nxt[1] >= o[1] + o[2] or nxt[2] == 0:
            out.append(o)
    return out


def reduce(events: dict) -> dict:
    """busy_s, window_s, device_ops, idle_gaps and the window's operations
    (name, seconds, stats) of every device, from ``read``'s events."""
    host = events["host"]
    devices = events["devices"]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    win = _window(host)
    if win is None:
        starts = [s for ops in devices.values() for _, s, _, _ in ops]
        ends = [s + d for ops in devices.values() for _, s, d, _ in ops]
        win = (min(starts), max(ends))
    w0, w1 = win
    busy, totals, window_ops = [], {}, []
    gaps: List[Tuple[float, str]] = []
    for i, name in enumerate(sorted(devices)):
        clipped = [(max(s, w0), min(s + d, w1), n, st)
                   for n, s, d, st in leaves(devices[name])
                   if s < w1 and s + d > w0]
        merged = union((a, b) for a, b, _, _ in clipped)
        busy.append(sum(b - a for a, b in merged))
        for a, b, n, st in clipped:
            totals[n] = totals.get(n, 0.0) + (b - a)
            window_ops.append((n, (b - a) * 1e-9, st))
        if i == 0:
            edges = [w0] + [x for ab in merged for x in ab] + [w1]
            for a, b in zip(edges[::2], edges[1::2]):
                if b > a:
                    gaps.append((b - a, _label(host, (a + b) / 2)))
    ndev = len(devices)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": sum(busy) / ndev * 1e-9,
        "window_s": (w1 - w0) * 1e-9,
        "devices": ndev,
        "device_ops": [[n, t / ndev * 1e-9] for n, t in ranked],
        "idle_gaps": [[label, g * 1e-9]
                      for g, label in sorted(gaps, reverse=True)[:TOP]],
        "ops": window_ops,
    }


def kernel(reduced: dict, patterns: Iterable[str]) -> Tuple[float, int]:
    """(seconds per device, calls per device) of the window's operations
    whose description ("name = type op target") matches a pattern."""
    rx = re.compile("|".join(patterns))
    hits = [(n, t) for n, t, st in reduced["ops"]
            if rx.search(st.get("desc", n))]
    ndev = reduced["devices"]
    return sum(t for _, t in hits) / ndev, len(hits) // ndev
