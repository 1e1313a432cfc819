"""Roofline analysis from compiled dry-run artifacts (EXPERIMENTS.md §Roofline).

Three terms per (arch x shape x mesh), all in seconds-per-step-per-chip:

    compute    = HLO_FLOPs(per device)      / peak FLOP/s
    memory     = HLO_bytes(per device)      / peak HBM bytes/s
    collective = collective_bytes(per dev)  / ICI bytes/s per link

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()`` (the SPMD
partitioned per-device module).  Collective bytes are parsed from the
optimized HLO text: sum of result-shape bytes of every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute (async
``-start`` forms counted once, ``-done`` skipped).

Peaks come from ``PEAKS``, keyed by ``jax.Device.device_kind``; a kind
that is not in the table raises instead of borrowing another chip's
numbers.  The collective term assumes the payload crosses one logical
link serially — a deliberate, consistent upper bound; ring
algorithms overlap hops, so treat it as a comparison metric, not a wall
clock prediction.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

# Published per-chip peaks, keyed by device_kind.  TPU v5e: Google Cloud
# documentation, "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s
# of interconnect over 4 links = 50 GB/s per link.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of one chip of ``device_kind``; KeyError if unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f"; known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")
_COLL_RE = re.compile(
    r"=\s*([^=]+?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")


def shape_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s*((?:\([^)]*\)|\S+?))\s+"
    r"([a-z][\w\-]*)\((.*)$")
_COMP_RE = re.compile(r"^(%?[\w.\-]+)\s+\(.*\)\s+->\s+.*\{")
_OPERAND_RE = re.compile(r"%[\w.\-]+")

_MAJOR_OPS = ("dot", "convolution", "gather", "scatter", "scatter-add",
              "dynamic-update-slice", "dynamic-slice", "sort")


def hbm_traffic(hlo_text: str) -> int:
    """Fusion-aware HBM traffic model: sum operand+result bytes of *major*
    ops only (dot / conv / gather / scatter / dynamic-(update-)slice /
    sort), attributing a fusion node's operands when its fused computation
    contains a major op.  Elementwise chains are assumed fused (free), which
    matches TPU codegen far better than XLA:CPU's ``bytes accessed``.
    Still an upper-ish bound: VMEM-resident reuse is not modeled."""
    name_bytes: Dict[str, int] = {}
    comp_major: Dict[str, bool] = {}
    comp_of_line: Dict[int, str] = {}
    cur_comp = ""
    lines = hlo_text.splitlines()
    for i, line in enumerate(lines):
        mc = _COMP_RE.match(line)
        if mc:
            cur_comp = mc.group(1).lstrip("%")
            comp_major.setdefault(cur_comp, False)
        comp_of_line[i] = cur_comp
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, typ, op = m.group(1), m.group(2), m.group(3)
        name_bytes[name] = shape_bytes(typ)
        if op in _MAJOR_OPS:
            comp_major[cur_comp] = True
    total = 0
    for i, line in enumerate(lines):
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, typ, op, rest = m.groups()
        is_major = op in _MAJOR_OPS
        if op == "fusion":
            mcalls = re.search(r"calls=(%?[\w.\-]+)", rest)
            if mcalls and comp_major.get(mcalls.group(1).lstrip("%")):
                is_major = True
        if not is_major:
            continue
        # stop operand scan at control fields
        args = rest.split("), ")[0]
        total += name_bytes.get(name, shape_bytes(typ))
        for om in _OPERAND_RE.finditer(args):
            total += name_bytes.get(om.group(0), 0)
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-device bytes moved by collectives, keyed by op kind."""
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group(2)
        out[kind] = out.get(kind, 0) + shape_bytes(m.group(1))
    return out


@dataclasses.dataclass
class Roofline:
    flops: float
    hbm_bytes: float
    coll_bytes: float
    coll_by_kind: Dict[str, int]
    device_kind: str
    peak_memory: Optional[float] = None

    @property
    def t_compute(self) -> float:
        return self.flops / peaks(self.device_kind)["flops"]

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / peaks(self.device_kind)["hbm_bw"]

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / peaks(self.device_kind)["ici_bw"]

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        """Lower-bound step time: max of the three terms (perfect overlap)."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    def to_dict(self) -> dict:
        return {
            "flops": self.flops, "hbm_bytes": self.hbm_bytes,
            "coll_bytes": self.coll_bytes, "coll_by_kind": self.coll_by_kind,
            "t_compute": self.t_compute, "t_memory": self.t_memory,
            "t_collective": self.t_collective, "bottleneck": self.bottleneck,
            "t_bound": self.t_bound, "peak_memory": self.peak_memory,
            "device_kind": self.device_kind,
        }


def analyze(compiled, device_kind: str, hlo_text: Optional[str] = None,
            traffic_model: bool = True) -> Roofline:
    peaks(device_kind)              # fail before any work on an unknown chip
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    hbm = float(hbm_traffic(text)) if traffic_model \
        else float(cost.get("bytes accessed", 0.0))
    coll = collective_bytes(text)
    peak_mem = None
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            peak_mem = float(
                getattr(ma, "temp_size_in_bytes", 0)
                + getattr(ma, "argument_size_in_bytes", 0)
                + getattr(ma, "output_size_in_bytes", 0)
                - getattr(ma, "alias_size_in_bytes", 0))
    except Exception:
        pass
    return Roofline(flops=flops, hbm_bytes=hbm,
                    coll_bytes=float(sum(coll.values())),
                    coll_by_kind=coll, device_kind=device_kind,
                    peak_memory=peak_mem)


def model_flops(cfg, tokens: int) -> float:
    """MODEL_FLOPS = 6 N D (dense) / 6 N_active D (MoE) for a train step;
    2 N D for inference steps (caller divides)."""
    n = active_params(cfg)
    return 6.0 * n * tokens


def active_params(cfg) -> float:
    """Active (FLOP-relevant) parameter count: standard 6ND convention —
    embeddings excluded; MoE experts count experts_per_token/num_experts;
    routed-FFN weights count beta = G'/G (only activated blocks compute)."""
    from repro.core.params import count_params
    from repro.train.state import model_defs
    total = count_params(model_defs(cfg))
    total -= cfg.padded_vocab * cfg.d_model          # embedding lookup
    if cfg.positional == "learned":
        total -= cfg.max_position * cfg.d_model
        if cfg.family == "audio":
            total -= cfg.max_position * cfg.d_model  # enc+dec tables
    n_ffn_layers = sum(1 for t in cfg.layer_types() if t != "ssd")
    ffn_mats = 3 if cfg.gated_ffn else 2
    if cfg.num_experts > 0:
        frac = cfg.experts_per_token / cfg.num_experts
        per_layer = cfg.num_experts * cfg.d_model * cfg.d_ff * ffn_mats
        total -= per_layer * n_ffn_layers * (1.0 - frac)
    elif cfg.spt.routed_ffn and cfg.d_ff > 0 \
            and cfg.d_ff % cfg.spt.ffn_groups == 0:
        beta = cfg.spt.ffn_active_groups / cfg.spt.ffn_groups
        per_layer = cfg.d_model * cfg.d_ff * ffn_mats
        if cfg.family == "audio":
            n_ffn_layers += cfg.encoder_layers
        total -= per_layer * n_ffn_layers * (1.0 - beta)
    return float(max(total, 1.0))
