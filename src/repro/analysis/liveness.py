"""Layer 5: memory-lifetime analysis of hot-entrypoint jaxprs.

The paper's headline claim is *peak memory* (sparse MHA cuts peak
consumption by up to 50%), and the serving stack's value proposition is
resident-bytes-per-session — but per-eqn byte budgets (layer 1) cannot
see *when* buffers die, whether donated inputs actually alias, or
whether a change silently doubled the live set of the decode chunk.
This layer runs a backward liveness pass over the (nested) jaxpr of
every registered memory entrypoint and derives:

  * a **peak-live-bytes waterfall** — for each top-level program point,
    the bytes resident under the model below;
  * the **top-k live-set contributors** at the peak point, with
    provenance (arg tree path for inputs, primitive + source line for
    intermediates);
  * a **memory signature** (peak live bytes, donated bytes, eqn count,
    pallas-call count) — the unit the golden-baseline ratchet in
    ``analysis/baselines.py`` diffs against ``scripts/
    analysis_baselines.json``.

The residency model (an upper bound, but a *consistent* one — the
ratchet cares about drift, not absolute truth):

  * non-donated top-level invars and consts are **pinned**: the caller
    holds them for the whole call (params, read-only operands);
  * donated invars die at their last use — donation is how the decode
    chunk's caches/slot-state stop counting twice;
  * an intermediate is resident from the eqn that defines it through its
    last use; at eqn ``i`` the resident set is pinned ∪ live-after(i) ∪
    the eqn's own operands and results;
  * ``while``/``scan``/``cond``/``pjit``/``custom_*`` bodies are
    analyzed recursively: the sub-jaxpr invar is treated as donated iff
    the outer operand dies at the eqn, so a donated cache flowing
    through the while carry is counted once; a while/scan carry whose
    outer operand does NOT die (non-donated, or still read later) pays
    a copy-on-entry surcharge — the caller's buffer stays resident
    alongside the loop's working copy, which is exactly the cost
    donation buys back; in-place cache updates (``scatter*`` /
    ``dynamic_update_slice`` whose operand dies) alias their output;
  * a ``pallas_call`` contributes its operands/results plus kernel
    scratch (VMEM scratch_shapes), never its internal ref vars.

``python -m repro.analysis --memory-report`` prints the waterfalls;
the ``liveness`` audit registered here only sanity-checks that every
entrypoint traces and that entries expected to donate actually report
donated bytes (rules ``liveness.trace-failure``, ``liveness.empty``,
``liveness.donation-unused``).  Regression gating lives in the
``memory`` audit (baselines.py).
"""
from __future__ import annotations

import dataclasses
import os
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
from jax.extend import core as jcore
from jax._src import source_info_util

from repro.analysis import jaxpr_audit as ja
from repro.analysis.registry import Violation, audit

# ------------------------------------------------------------- byte sizes
def aval_bytes(aval) -> int:
    """Static byte size of an abstract value (0 when unknown/dynamic)."""
    aval = getattr(aval, "inner_aval", aval)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    size = 1
    for dim in shape:
        if not isinstance(dim, int):
            return 0
        size *= dim
    return size * getattr(dtype, "itemsize", 0)    # semaphores: 0


def _aval_str(aval) -> str:
    aval = getattr(aval, "inner_aval", aval)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return "?"
    return f"{jnp.dtype(dtype).name}{tuple(shape)}"


def _src(eqn) -> str:
    try:
        return source_info_util.summarize(eqn.source_info)
    except Exception:
        return "?"


# --------------------------------------------------------------- results
@dataclasses.dataclass(frozen=True)
class Contributor:
    nbytes: int
    aval: str         # dtype + shape
    label: str        # arg tree path or defining "prim @ file:line"


@dataclasses.dataclass(frozen=True)
class PeakInfo:
    nbytes: int
    at: str                                # program point description
    contributors: Tuple[Contributor, ...]  # sorted desc, truncated


@dataclasses.dataclass(frozen=True)
class MemorySignature:
    peak_live_bytes: int
    donated_bytes: int
    eqns: int
    pallas_calls: int


@dataclasses.dataclass(frozen=True)
class MemoryReport:
    entry: str
    signature: MemorySignature
    timeline: Tuple[Tuple[str, int], ...]  # top-level (label, live bytes)
    peak: PeakInfo


# ------------------------------------------------------- liveness engine
# primitives that update an operand in place when it is dead: output
# aliases operand 0 (XLA's in-place scatter/DUS path)
_INPLACE_PRIMS = frozenset({
    "scatter", "scatter-add", "scatter-mul", "scatter-min", "scatter-max",
    "dynamic_update_slice",
})
_CONTRIB_KEEP = 12


def _bytes_of(vs) -> int:
    return sum(aval_bytes(v.aval) for v in vs)


def _contributors(vs, labels) -> Tuple[Contributor, ...]:
    cs = [Contributor(aval_bytes(v.aval), _aval_str(v.aval),
                      labels.get(v, "intermediate"))
          for v in vs if aval_bytes(v.aval) > 0]
    cs.sort(key=lambda c: (-c.nbytes, c.label))
    return tuple(cs[:_CONTRIB_KEEP])


def _pallas_scratch_bytes(eqn) -> int:
    try:
        gm = eqn.params["grid_mapping"]
        num = int(gm.num_scratch_operands)
    except Exception:
        return 0
    if not num:
        return 0
    kernel = eqn.params.get("jaxpr")
    if kernel is None:
        return 0
    if isinstance(kernel, jcore.ClosedJaxpr):
        kernel = kernel.jaxpr
    return sum(aval_bytes(v.aval) for v in kernel.invars[-num:])


def _sub_closed(val):
    if isinstance(val, jcore.ClosedJaxpr):
        return val
    if isinstance(val, jcore.Jaxpr):
        return jcore.ClosedJaxpr(val, ())
    return None


def _analyze(jaxpr: jcore.Jaxpr, donated: Sequence[bool],
             labels: Dict) -> Tuple[List[Tuple[str, int]], PeakInfo]:
    """Backward-liveness walk of one jaxpr level.  ``donated[k]`` says
    invar k dies at last use (else pinned for the whole program).
    Returns (timeline of top-level program points, peak info)."""
    eqns = list(jaxpr.eqns)
    invars = list(jaxpr.invars)
    donated = list(donated) + [False] * (len(invars) - len(donated))
    pinned: Set = set(jaxpr.constvars)
    for v in jaxpr.constvars:
        labels.setdefault(v, "const")
    don: Set = set()
    for k, v in enumerate(invars):
        labels.setdefault(v, f"arg{k}")
        (don if donated[k] else pinned).add(v)

    # backward pass: live_after[i] = vars defined at or before eqn i that
    # some later eqn (or the outputs) still needs
    live = {v for v in jaxpr.outvars if isinstance(v, jcore.Var)}
    live_after: List[Set] = [set()] * len(eqns)
    for i in range(len(eqns) - 1, -1, -1):
        live_after[i] = set(live)
        for v in eqns[i].outvars:
            live.discard(v)
        for v in eqns[i].invars:
            if isinstance(v, jcore.Var):
                live.add(v)
    live_entry = live

    def point(resident: Set, extra: int, at: str) -> Tuple[int, PeakInfo]:
        nbytes = _bytes_of(resident) + extra
        return nbytes, PeakInfo(nbytes, at, _contributors(resident, labels))

    entry_resident = pinned | (don & live_entry)
    nbytes, best = point(entry_resident, 0, "entry")
    timeline: List[Tuple[str, int]] = [("entry", nbytes)]

    for i, eqn in enumerate(eqns):
        prim = eqn.primitive.name
        here_in = [v for v in eqn.invars if isinstance(v, jcore.Var)]
        here = set(here_in) | set(eqn.outvars)
        rest = (pinned | live_after[i]) - here

        def dead(v) -> bool:
            return (isinstance(v, jcore.Var) and v not in pinned
                    and v not in live_after[i])

        cost: Optional[int] = None
        info: Optional[PeakInfo] = None
        sub_specs = _call_sub_specs(eqn, dead, labels)
        if prim == "pallas_call":
            extra = _pallas_scratch_bytes(eqn)
            cost, info = point(rest | here, extra, f"{prim} @ {_src(eqn)}")
        elif sub_specs:
            rest_bytes = _bytes_of(rest)
            results = [(_analyze(sub.jaxpr, mask, sub_labels), extra)
                       for sub, mask, sub_labels, extra in sub_specs]
            (sub_tl, inner_best), extra_outer = max(
                results, key=lambda r: r[0][1].nbytes + r[1])
            cost = rest_bytes + inner_best.nbytes + extra_outer
            info = PeakInfo(
                cost, f"{prim} @ {_src(eqn)} -> {inner_best.at}",
                tuple(sorted(
                    _contributors(rest, labels) + inner_best.contributors,
                    key=lambda c: -c.nbytes))[:_CONTRIB_KEEP])
            # splice the body's program points into the waterfall so
            # loop-heavy entrypoints aren't a single opaque bar
            timeline.extend(
                (f"{prim}:{lbl}", rest_bytes + v + extra_outer)
                for lbl, v in sub_tl)
        else:
            save = 0
            if prim in _INPLACE_PRIMS and here_in and eqn.outvars:
                op0, out0 = eqn.invars[0], eqn.outvars[0]
                if (dead(op0) and aval_bytes(op0.aval)
                        == aval_bytes(out0.aval)):
                    save = aval_bytes(out0.aval)
            cost, info = point(rest | here, -save,
                               f"{prim} @ {_src(eqn)}")
        if not sub_specs or prim == "pallas_call":
            timeline.append((prim, cost))
        if cost > best.nbytes:
            best = info
        for v in eqn.outvars:
            labels.setdefault(v, f"{prim} @ {_src(eqn)}")

    exit_resident = pinned | {v for v in jaxpr.outvars
                              if isinstance(v, jcore.Var)}
    nbytes, exit_info = point(exit_resident, 0, "exit")
    timeline.append(("exit", nbytes))
    if nbytes > best.nbytes:
        best = exit_info
    return timeline, best


def _call_sub_specs(eqn, dead, labels):
    """For call-like eqns, yield (ClosedJaxpr, donated mask, sub label
    map, extra outer bytes) per body to recurse into.  The mask marks a
    sub invar donated iff the outer operand dies at this eqn, so donated
    buffers flowing into while carries / pjit bodies count once."""
    prim = eqn.primitive.name
    if prim == "pallas_call":       # kernel body vars are refs, not HBM
        return []

    def lbl(v):
        return labels.get(v) if isinstance(v, jcore.Var) else None

    def names_for(sub_invars, outer_ops):
        out = {}
        for sv, ov in zip(sub_invars, outer_ops):
            name = lbl(ov)
            if name is not None:
                out[sv] = name
        return out

    def carry_copy_bytes(carry):
        # a loop carry updated in place needs its own buffer; when the
        # outer operand does NOT die here (non-donated, or still used
        # later) the caller's buffer ALSO stays resident for the whole
        # loop — this surcharge is exactly what donating the operand
        # buys back
        return sum(aval_bytes(v.aval) for v in carry
                   if isinstance(v, jcore.Var) and not dead(v))

    if prim == "while":
        cn = eqn.params["cond_nconsts"]
        bn = eqn.params["body_nconsts"]
        cond_j = _sub_closed(eqn.params["cond_jaxpr"])
        body_j = _sub_closed(eqn.params["body_jaxpr"])
        ops = list(eqn.invars)
        cconsts, bconsts = ops[:cn], ops[cn:cn + bn]
        carry = ops[cn + bn:]
        copies = carry_copy_bytes(carry)
        # cond reads the carry the body still needs — never donated there
        cond_mask = [dead(v) for v in cconsts] + [False] * len(carry)
        body_mask = [dead(v) for v in bconsts] + [dead(v) for v in carry]
        return [
            (cond_j, cond_mask, names_for(cond_j.jaxpr.invars,
                                          cconsts + carry), copies),
            (body_j, body_mask, names_for(body_j.jaxpr.invars,
                                          bconsts + carry), copies),
        ]
    if prim == "scan":
        closed = _sub_closed(eqn.params["jaxpr"])
        nc = eqn.params["num_consts"]
        ncar = eqn.params["num_carry"]
        ops = list(eqn.invars)
        lead, xs = ops[:nc + ncar], ops[nc + ncar:]
        sub_in = closed.jaxpr.invars
        mask = [dead(v) for v in lead]
        mask += [True] * (len(sub_in) - len(mask))  # per-iter xs slices
        names = names_for(sub_in, lead)
        for sv, ov in zip(sub_in[nc + ncar:], xs):
            name = lbl(ov)
            if name is not None:
                names[sv] = name + "[iter]"
        # full xs stay resident for the whole scan, the stacked ys
        # outputs fill up while it runs, and non-dead carries are copied
        # on entry (see carry_copy_bytes)
        ys = list(eqn.outvars[ncar:])
        extra = (_bytes_of({v for v in xs if isinstance(v, jcore.Var)})
                 + _bytes_of(ys)
                 + carry_copy_bytes(ops[nc:nc + ncar]))
        return [(closed, mask, names, extra)]
    if prim == "cond":
        branches = [_sub_closed(b) for b in eqn.params["branches"]]
        ops = list(eqn.invars[1:])          # invars[0] is the predicate
        mask = [dead(v) for v in ops]
        return [(b, mask, names_for(b.jaxpr.invars, ops), 0)
                for b in branches if b is not None]
    # generic call-like (pjit, closed_call, custom_jvp/vjp, remat):
    # accept any single ClosedJaxpr param whose invars match 1:1
    for val in eqn.params.values():
        closed = _sub_closed(val)
        if closed is None:
            continue
        if len(closed.jaxpr.invars) == len(eqn.invars):
            mask = [dead(v) for v in eqn.invars]
            return [(closed, mask,
                     names_for(closed.jaxpr.invars, eqn.invars), 0)]
    return []


# ------------------------------------------------------- report assembly
def arg_leaf_names(args, prefixes: Sequence[str]) -> List[str]:
    names = []
    for prefix, arg in zip(prefixes, args):
        leaves, _ = jax.tree_util.tree_flatten_with_path(arg)
        if not leaves:
            continue
        for path, _ in leaves:
            names.append(prefix + jax.tree_util.keystr(path))
    return names


def donated_leaf_mask(args, donate_argnums: Sequence[int]) -> List[bool]:
    mask = []
    for i, arg in enumerate(args):
        n = len(jax.tree_util.tree_leaves(arg))
        mask.extend([i in donate_argnums] * n)
    return mask


def analyze_closed(closed: jcore.ClosedJaxpr,
                   donated: Optional[Sequence[bool]] = None,
                   arg_names: Optional[Sequence[str]] = None,
                   entry: str = "jaxpr") -> MemoryReport:
    jaxpr = closed.jaxpr
    donated = list(donated or [False] * len(jaxpr.invars))
    labels: Dict = {}
    if arg_names:
        for v, name in zip(jaxpr.invars, arg_names):
            labels[v] = name
    timeline, peak = _analyze(jaxpr, donated, labels)
    donated_bytes = sum(aval_bytes(v.aval)
                        for v, d in zip(jaxpr.invars, donated) if d)
    sig = MemorySignature(
        peak_live_bytes=peak.nbytes,
        donated_bytes=donated_bytes,
        eqns=sum(1 for _ in ja.iter_eqns(closed)),
        pallas_calls=ja.pallas_call_count(closed))
    return MemoryReport(entry=entry, signature=sig,
                        timeline=tuple(timeline), peak=peak)


# --------------------------------------------------- entrypoint registry
MEMORY_ENTRYPOINTS: Dict[str, Callable[[], MemoryReport]] = {}
# entries whose jit site declares donation — donated_bytes == 0 there
# means the audit's mask plumbing silently broke
_EXPECT_DONATION = set()
_REPORT_CACHE: Dict[str, MemoryReport] = {}


def memory_entrypoint(name: str, expect_donation: bool = False):
    def register(fn):
        if name in MEMORY_ENTRYPOINTS:
            raise ValueError(f"duplicate memory entrypoint {name!r}")
        MEMORY_ENTRYPOINTS[name] = fn
        if expect_donation:
            _EXPECT_DONATION.add(name)
        return fn
    return register


def memory_report(name: str) -> MemoryReport:
    """Compute (and memoize — baselines, the liveness audit, and
    --memory-report all reuse one trace) the report for one entry."""
    if name not in _REPORT_CACHE:
        _REPORT_CACHE[name] = MEMORY_ENTRYPOINTS[name]()
    return _REPORT_CACHE[name]


def all_reports() -> Dict[str, MemoryReport]:
    return {name: memory_report(name) for name in MEMORY_ENTRYPOINTS}


CHUNK_ARG_NAMES = ("params", "caches", "page_table", "astate", "tok",
                   "pos", "active", "n_gen", "limit", "buf", "keys",
                   "temps", "topks", "topps")


def _chunk_report(entry: str, cfg, donate_argnums=None) -> MemoryReport:
    from repro.serving.engine import CHUNK_DONATE_ARGNUMS
    if donate_argnums is None:
        donate_argnums = CHUNK_DONATE_ARGNUMS
    closed, _, _, args = ja._engine_chunk_jaxpr(cfg)
    return analyze_closed(
        closed, donated=donated_leaf_mask(args, donate_argnums),
        arg_names=arg_leaf_names(args, CHUNK_ARG_NAMES), entry=entry)


@memory_entrypoint("engine.decode_chunk", expect_donation=True)
def _mem_decode_chunk() -> MemoryReport:
    cfg = ja._tiny_lm_cfg(decode_attn_impl="kernel", ffn_impl="pallas")
    return _chunk_report("engine.decode_chunk", cfg)


@memory_entrypoint("engine.decode_chunk_kernels_off",
                   expect_donation=True)
def _mem_decode_chunk_off() -> MemoryReport:
    prev = os.environ.get("REPRO_DISABLE_KERNELS")
    os.environ["REPRO_DISABLE_KERNELS"] = "1"
    try:
        cfg = ja._tiny_lm_cfg(decode_attn_impl="kernel",
                              ffn_impl="pallas")
        return _chunk_report("engine.decode_chunk_kernels_off", cfg)
    finally:
        if prev is None:
            os.environ.pop("REPRO_DISABLE_KERNELS", None)
        else:
            os.environ["REPRO_DISABLE_KERNELS"] = prev


@memory_entrypoint("engine.decode_chunk_paged", expect_donation=True)
def _mem_decode_chunk_paged() -> MemoryReport:
    cfg = ja._tiny_lm_cfg(decode_attn_impl="kernel", attn_impl="pallas",
                          ffn_impl="pallas", kv_layout="paged",
                          kv_page_size=16)
    return _chunk_report("engine.decode_chunk_paged", cfg)


@memory_entrypoint("engine.prefill_ragged")
def _mem_prefill_ragged() -> MemoryReport:
    from repro.models import transformer
    cfg = ja._tiny_lm_cfg(ffn_impl="pallas")
    params = ja._lm_params(cfg)
    bpb, s, max_len = 2, 16, 32
    batch = {"tokens": jax.ShapeDtypeStruct((bpb, s), jnp.int32)}
    lengths = jax.ShapeDtypeStruct((bpb,), jnp.int32)
    closed = jax.make_jaxpr(
        lambda p, b, ln: transformer.lm_prefill_ragged(p, cfg, b, ln,
                                                       max_len)
    )(params, batch, lengths)
    args = (params, batch, lengths)
    return analyze_closed(
        closed,
        arg_names=arg_leaf_names(args, ("params", "batch", "lengths")),
        entry="engine.prefill_ragged")


@memory_entrypoint("ops.sparse_mha_decode")
def _mem_sparse_mha_decode() -> MemoryReport:
    from repro.kernels.sparse_attention import ops as sa_ops
    (b, hq, hk, s, d), scfg, cb, q, k, v, codes, kv_valid = \
        ja._sparse_decode_operands()
    closed = jax.make_jaxpr(
        lambda q, k, v, c, cb, kv: sa_ops.sparse_mha_decode(
            q, k, v, c, cb, scfg, d ** -0.5, kv, interpret=True,
            fuse=True)
    )(q, k, v, codes, cb, kv_valid)
    args = (q, k, v, codes, cb, kv_valid)
    return analyze_closed(
        closed,
        arg_names=arg_leaf_names(args, ("q", "k", "v", "codes",
                                        "codebooks", "kv_valid")),
        entry="ops.sparse_mha_decode")


@memory_entrypoint("ops.routed_ffn_decode")
def _mem_routed_ffn_decode() -> MemoryReport:
    from repro.core import lora as lora_mod
    from repro.core import routed_ffn as rf
    from repro.core.params import init_tree
    from repro.kernels.routed_ffn import ops as rffn_ops
    b, d, dff, g, gp = 4, 64, 128, 8, 2
    lcfg = lora_mod.LoRAConfig(rank=4, alpha=4.0, enabled=True)
    rcfg = rf.RoutedFFNConfig(d_model=d, d_ff=dff, num_groups=g,
                              active_groups=gp, capacity_factor=4.0,
                              gated=True, activation="gelu")
    p = jax.eval_shape(lambda: init_tree(rf.param_defs(rcfg, lcfg),
                                         jax.random.PRNGKey(0)))
    x = jax.ShapeDtypeStruct((b, 1, d), jnp.float32)
    closed = jax.make_jaxpr(
        lambda p, x: rffn_ops.routed_ffn_decode(x, p, rcfg, lcfg,
                                                interpret=True)[0])(p, x)
    return analyze_closed(
        closed, arg_names=arg_leaf_names((p, x), ("params", "x")),
        entry="ops.routed_ffn_decode")


@memory_entrypoint("models.moe_decode")
def _mem_moe_decode() -> MemoryReport:
    from repro import configs
    from repro.core.params import init_tree
    from repro.models import moe
    cfg = configs.get_smoke("grok-1-314b").with_spt(ffn_impl="pallas")
    p = jax.eval_shape(lambda: init_tree(moe.moe_defs(cfg),
                                         jax.random.PRNGKey(0)))
    x = jax.ShapeDtypeStruct((4, 1, cfg.d_model), jnp.float32)
    closed = jax.make_jaxpr(
        lambda p, x: moe.moe_apply(p, x, cfg, mode="decode")[0])(p, x)
    return analyze_closed(
        closed, arg_names=arg_leaf_names((p, x), ("params", "x")),
        entry="models.moe_decode")


@memory_entrypoint("kv_pages.alloc_free", expect_donation=True)
def _mem_kv_pages_alloc_free() -> MemoryReport:
    from repro.serving import kv_pages as kvp
    slots, pages_per, pool = 4, 4, 16

    def roundtrip(state, page_table, rows, num_pages):
        state, page_table = kvp.alloc_rows_pages(state, page_table,
                                                 rows, num_pages)
        return kvp.free_slot_pages(state, page_table, jnp.int32(0))

    state = ja._abstract(kvp.init_state(pool))
    pt = ja._abstract(kvp.init_page_table(slots, pages_per))
    rows = jax.ShapeDtypeStruct((slots,), jnp.int32)
    npages = jax.ShapeDtypeStruct((slots,), jnp.int32)
    closed = jax.make_jaxpr(roundtrip)(state, pt, rows, npages)
    args = (state, pt, rows, npages)
    return analyze_closed(
        closed, donated=donated_leaf_mask(args, (0, 1)),
        arg_names=arg_leaf_names(args, ("astate", "page_table", "rows",
                                        "num_pages")),
        entry="kv_pages.alloc_free")


# ----------------------------------------------------------- the reports
_BLOCKS = " ▁▂▃▄▅▆▇█"


def waterfall(timeline: Sequence[Tuple[str, int]], width: int = 60) -> str:
    """Sampled sparkline of live bytes over program points (max per
    bucket, scaled to the peak)."""
    vals = [v for _, v in timeline]
    if not vals:
        return ""
    peak = max(vals) or 1
    width = min(width, len(vals))
    cells = []
    for c in range(width):
        lo = c * len(vals) // width
        hi = max(lo + 1, (c + 1) * len(vals) // width)
        frac = max(vals[lo:hi]) / peak
        cells.append(_BLOCKS[min(len(_BLOCKS) - 1,
                                 int(round(frac * (len(_BLOCKS) - 1))))])
    return "".join(cells)


def format_memory_report(top_k: int = 6, width: int = 60) -> str:
    lines = ["memory-lifetime report (liveness model: pinned params + "
             "donated-dies-at-last-use; see analysis/liveness.py)"]
    for name in MEMORY_ENTRYPOINTS:
        rep = memory_report(name)
        sig = rep.signature
        lines.append("")
        lines.append(
            f"{name}: peak {sig.peak_live_bytes:,} B  "
            f"donated {sig.donated_bytes:,} B  eqns {sig.eqns}  "
            f"pallas {sig.pallas_calls}")
        lines.append("  live |" + waterfall(rep.timeline, width) + "|")
        lines.append(f"  peak at {rep.peak.at}")
        for c in rep.peak.contributors[:top_k]:
            lines.append(f"    {c.nbytes:>12,} B  {c.aval:<18} {c.label}")
    return "\n".join(lines)


# --------------------------------------------------------------- audit
def entry_violations(name: str,
                     builder: Callable[[], MemoryReport]
                     ) -> List[Violation]:
    try:
        rep = builder()
    except Exception as e:               # trace failure IS the finding
        return [Violation("liveness.trace-failure", name,
                          f"{type(e).__name__}: {e}")]
    out = []
    if rep.signature.peak_live_bytes <= 0:
        out.append(Violation(
            "liveness.empty", name,
            "peak live bytes is zero — the analyzer saw no resident "
            "buffers (trace or model bug)"))
    if name in _EXPECT_DONATION and rep.signature.donated_bytes <= 0:
        out.append(Violation(
            "liveness.donation-unused", name,
            "the jit site declares donation but the analyzer saw no "
            "donated invars — the donated-mask plumbing broke"))
    return out


@audit("liveness")
def _liveness_audit() -> List[Violation]:
    out: List[Violation] = []
    for name in MEMORY_ENTRYPOINTS:
        out.extend(entry_violations(name, lambda n=name: memory_report(n)))
    return out
