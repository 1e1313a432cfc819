"""Layer 1: structural audits of hot-entrypoint jaxprs.

The paper's efficiency claims are *absences* — no dense dispatch buffer
at decode, no repeated GQA cache, no host callback inside the compiled
chunk — and absences don't fail parity tests.  Here each registered hot
entrypoint is traced with ``jax.make_jaxpr`` over ShapeDtypeStructs (no
FLOPs, no device buffers) and the resulting program is checked eqn by
eqn.  Rules (stable ids tests key on):

  jaxpr.dispatch-buffer     a decode-shaped call materializes a
                            (B, G/E, C, ·) capacity buffer
  jaxpr.cache-repeat        a decode attention path materializes a
                            (B, Hq, S, ·) tensor with Hq > Hk — the GQA
                            cache was expanded instead of packed
  jaxpr.paged-gather        a paged decode chunk materializes a gathered
                            per-slot (B, Hk, S, ·) view of the KV pool —
                            the kernel-native route reads (page_id,
                            offset) tiles directly
  jaxpr.intermediate-budget an eqn output exceeds a byte budget (rule +
                            ``auto_budget`` kept for tests/ad-hoc use;
                            at HEAD the per-entry byte gate is the
                            liveness-derived memory-signature ratchet in
                            analysis/liveness.py + analysis/baselines.py)
  jaxpr.forbidden-primitive host callbacks / prints inside a hot path
  jaxpr.accum-dtype         a dot/exp inside a Pallas kernel body does
                            not accumulate in float32
  jaxpr.kernel-missing      a dispatch switch says "Pallas" but no
                            pallas_call lowered
  jaxpr.kernel-present      the kill switch (or an impl=jnp override)
                            says "no kernels" but a pallas_call lowered

Helper predicates are importable on their own — tests/test_moe_kernel.py
and tests/test_routed_ffn_kernel.py assert their kernel-shape properties
through them, so the test suite and ``python -m repro.analysis`` enforce
the same definitions.  New entrypoints register with
``@hot_entrypoint("name")``.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Callable, Dict, Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.extend import core as jcore

from repro.analysis.registry import Violation, audit

# Host round-trips that must never appear inside a servable entrypoint.
FORBIDDEN_PRIMITIVES = frozenset({
    "io_callback", "pure_callback", "callback", "debug_callback",
    "debug_print",
})


# ----------------------------------------------------------- jaxpr walking
def iter_eqns(jaxpr) -> Iterator:
    """Every eqn of ``jaxpr`` and of any jaxpr nested in eqn params
    (pjit/while/scan/cond bodies, custom_vjp calls, pallas_call kernels)."""
    if isinstance(jaxpr, jcore.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _param_jaxprs(eqn):
            yield from iter_eqns(sub)


def _param_jaxprs(eqn) -> Iterator:
    for val in eqn.params.values():
        for item in (val if isinstance(val, (list, tuple)) else (val,)):
            if isinstance(item, (jcore.Jaxpr, jcore.ClosedJaxpr)):
                yield item
            elif hasattr(item, "jaxpr") and isinstance(
                    getattr(item, "jaxpr"), (jcore.Jaxpr, jcore.ClosedJaxpr)):
                yield item.jaxpr


def _eqn_site(eqn) -> str:
    return str(eqn.primitive.name)


def count_primitive(jaxpr, name: str) -> int:
    return sum(1 for eqn in iter_eqns(jaxpr) if eqn.primitive.name == name)


def pallas_call_count(jaxpr) -> int:
    return count_primitive(jaxpr, "pallas_call")


def _out_shapes(eqn):
    for v in eqn.outvars:
        shape = getattr(v.aval, "shape", None)
        if shape is not None:
            yield v, shape


# ------------------------------------------------------------ rule bodies
def dispatch_buffer_violations(jaxpr, batch: int, groups: int,
                               entry: str = "jaxpr") -> List[Violation]:
    """Any 4-d intermediate (batch, groups, ·, ·) is a resurrected
    capacity dispatch buffer — decode-shaped calls index weight blocks
    directly and must never build one (PR-3 acceptance property)."""
    out = []
    for eqn in iter_eqns(jaxpr):
        for v, shape in _out_shapes(eqn):
            if len(shape) == 4 and shape[0] == batch and shape[1] == groups:
                out.append(Violation(
                    "jaxpr.dispatch-buffer", entry,
                    f"{eqn.primitive.name} builds dispatch-shaped "
                    f"intermediate {tuple(shape)} (B={batch}, G={groups})"))
    return out


def cache_repeat_violations(jaxpr, num_q_heads: int, num_kv_heads: int,
                            min_seq: int, entry: str = "jaxpr"
                            ) -> List[Violation]:
    """A (B, Hq, S, ·) intermediate with Hq > Hk and S at cache length
    means the GQA KV cache (or its code cache) was expanded to the query
    heads — exactly the materialization the fused decode path avoids by
    packing the head group on the sublane axis."""
    if num_q_heads <= num_kv_heads:
        return []
    out = []
    for eqn in iter_eqns(jaxpr):
        for v, shape in _out_shapes(eqn):
            if (len(shape) == 4 and shape[1] == num_q_heads
                    and shape[2] >= min_seq):
                out.append(Violation(
                    "jaxpr.cache-repeat", entry,
                    f"{eqn.primitive.name} expands a cache to "
                    f"{tuple(shape)} (Hq={num_q_heads} > Hk="
                    f"{num_kv_heads}, S>={min_seq})"))
    return out


def paged_gather_violations(jaxpr, batch: int, num_kv_heads: int,
                            view: int, page_size: int, max_pages: int,
                            entry: str = "jaxpr") -> List[Violation]:
    """A (B, Hk, >=view, ·) — or pre-transpose (B, MP, Hk, ps, ·) —
    intermediate inside a paged decode chunk is a materialized per-slot
    gather of the KV pool: the kernel-native route addresses (page_id,
    offset) tiles straight from the pool and must never build one.
    (The MP dim in the 5-d form is required so layer-stacked pool
    carries (L, P, Hk, ps, ·) of scan/while bodies don't alias it.)"""
    out = []
    for eqn in iter_eqns(jaxpr):
        for v, shape in _out_shapes(eqn):
            gathered = (len(shape) == 4 and shape[0] == batch
                        and shape[1] == num_kv_heads
                        and isinstance(shape[2], int) and shape[2] >= view)
            pre_t = (len(shape) == 5 and shape[0] == batch
                     and shape[1] == max_pages
                     and shape[2] == num_kv_heads and shape[3] == page_size)
            if gathered or pre_t:
                out.append(Violation(
                    "jaxpr.paged-gather", entry,
                    f"{eqn.primitive.name} materializes a gathered "
                    f"per-slot KV view {tuple(shape)} (B={batch}, "
                    f"Hk={num_kv_heads}, view={view}) in a paged decode "
                    "chunk"))
    return out


def big_intermediate_violations(jaxpr, max_bytes: int,
                                entry: str = "jaxpr") -> List[Violation]:
    out = []
    for eqn in iter_eqns(jaxpr):
        for v, shape in _out_shapes(eqn):
            dtype = getattr(v.aval, "dtype", None)
            if dtype is None:
                continue
            size = 1
            for dim in shape:
                if not isinstance(dim, int):
                    size = 0      # dynamic dim — can't bound statically
                    break
                size *= dim
            nbytes = size * jnp.dtype(dtype).itemsize
            if nbytes > max_bytes:
                out.append(Violation(
                    "jaxpr.intermediate-budget", entry,
                    f"{eqn.primitive.name} builds {tuple(shape)} "
                    f"{jnp.dtype(dtype).name} = {nbytes} B "
                    f"(budget {max_bytes} B)"))
    return out


def forbidden_primitive_violations(
        jaxpr, entry: str = "jaxpr",
        forbidden: frozenset = FORBIDDEN_PRIMITIVES) -> List[Violation]:
    out = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name in forbidden:
            out.append(Violation(
                "jaxpr.forbidden-primitive", entry,
                f"{eqn.primitive.name} (host round-trip) inside a hot "
                "entrypoint"))
    return out


def accum_dtype_violations(jaxpr, entry: str = "jaxpr") -> List[Violation]:
    """Inside every pallas_call kernel body: dots and exp must produce
    f32 (the online-softmax state and FFN combine accumulate there even
    for bf16 operands — preferred_element_type=f32 policy)."""
    out = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        kernel = eqn.params.get("jaxpr")
        if kernel is None:
            continue
        name = eqn.params.get("name_and_src_info", "pallas_call")
        for keqn in iter_eqns(kernel):
            if keqn.primitive.name not in ("dot_general", "exp"):
                continue
            for v, shape in _out_shapes(keqn):
                dtype = getattr(v.aval, "dtype", None)
                if dtype is not None and jnp.dtype(dtype) != jnp.float32:
                    out.append(Violation(
                        "jaxpr.accum-dtype", entry,
                        f"{keqn.primitive.name} in kernel "
                        f"{str(name).split(' ')[0]} accumulates in "
                        f"{jnp.dtype(dtype).name}, not float32"))
    return out


def kernel_count_violations(jaxpr, entry: str, expect: str,
                            exact: Optional[int] = None) -> List[Violation]:
    """expect: "some" (dispatch switches selected Pallas), "none" (kill
    switch / jnp override active), or "exact" with ``exact`` calls."""
    n = pallas_call_count(jaxpr)
    if expect == "some" and n == 0:
        return [Violation("jaxpr.kernel-missing", entry,
                          "dispatch selected the Pallas path but no "
                          "pallas_call lowered")]
    if expect == "none" and n > 0:
        return [Violation("jaxpr.kernel-present", entry,
                          f"{n} pallas_call(s) lowered with kernels "
                          "switched off")]
    if expect == "exact" and n != exact:
        return [Violation(
            "jaxpr.kernel-missing" if n < (exact or 0)
            else "jaxpr.kernel-present", entry,
            f"expected exactly {exact} pallas_call(s), found {n}")]
    return []


def auto_budget(*trees, factor: float = 1.5) -> int:
    """Byte budget from the traced call's own operands: ``factor`` x the
    largest param/input/cache leaf.  A decode step that allocates beyond
    every operand is materializing something the paper says it avoids."""
    biggest = 0
    for tree in trees:
        for leaf in jax.tree_util.tree_leaves(tree):
            shape = getattr(leaf, "shape", None)
            dtype = getattr(leaf, "dtype", None)
            if shape is None or dtype is None:
                continue
            size = 1
            for dim in shape:
                size *= int(dim)
            biggest = max(biggest, size * jnp.dtype(dtype).itemsize)
    return int(biggest * factor)


def _abstract(tree):
    """Concrete/initializer tree -> ShapeDtypeStructs (trace-only)."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


# --------------------------------------------------------- hot entrypoints
ENTRYPOINTS: Dict[str, Callable[[], List[Violation]]] = {}


def hot_entrypoint(name: str):
    def register(fn):
        if name in ENTRYPOINTS:
            raise ValueError(f"duplicate hot entrypoint {name!r}")
        ENTRYPOINTS[name] = fn
        return fn
    return register


def _tiny_lm_cfg(**spt):
    from repro import configs
    cfg = dataclasses.replace(
        configs.get_smoke("qwen3-0.6b"), num_layers=2, d_model=64,
        num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
        vocab_size=256, dtype=jnp.float32)
    return cfg.with_spt(ffn_capacity_factor=8.0, **spt)


def _lm_params(cfg):
    from repro.core.params import init_tree
    from repro.train.state import model_defs
    return jax.eval_shape(
        lambda: init_tree(model_defs(cfg), jax.random.PRNGKey(0)))


def engine_chunk_args(eng, slots: int = 2, max_gen: int = 4):
    """Abstract decode-chunk operands exactly as ``Engine._decode_once``
    passes them (contiguous or paged placeholders, following the
    engine's kv_layout).  Shared by the jaxpr trace here, the liveness
    analyzer, and the donation auditor so all three see one signature."""
    from repro.serving import kv_pages as kvp
    from repro.serving.engine import abstract_decode_caches

    cfg, max_len = eng.cfg, eng.max_len
    params = _abstract(eng.params)
    if eng._paged:
        caches = abstract_decode_caches(cfg, slots, max_len,
                                        kv_pages=eng.kv_pages)
        page_table = _abstract(
            kvp.init_page_table(slots, eng.max_pages_per_slot))
        astate = _abstract(kvp.init_state(eng.kv_pages))
    else:
        caches = abstract_decode_caches(cfg, slots, max_len)
        page_table = _abstract(kvp.init_page_table(slots, 1))
        astate = _abstract(kvp.init_state(1))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    return (params, caches, page_table, astate,
            i32(slots), i32(slots),                       # tok, pos
            jax.ShapeDtypeStruct((slots,), jnp.bool_),    # active
            i32(slots), i32(slots),                       # n_gen, limit
            i32(slots, max_gen),                          # buf
            jax.ShapeDtypeStruct((slots, 2), jnp.uint32),  # keys
            f32(slots), i32(slots), f32(slots))           # temps/topks/topps


def _engine_chunk_jaxpr(cfg, slots: int = 2, max_gen: int = 4,
                        max_len: int = 32):
    """Trace the engine's compiled greedy decode chunk exactly as
    ``Engine.run`` builds it."""
    from repro.serving.engine import Engine

    params = _lm_params(cfg)
    eng = Engine(cfg, params, max_len=max_len, jit=False,
                 num_slots=slots, decode_chunk=4)
    chunk = eng._get_chunk(slots, max_gen, greedy=True, eos_id=None)
    args = engine_chunk_args(eng, slots, max_gen)
    caches = args[1]
    return jax.make_jaxpr(chunk)(*args), params, caches, args


@hot_entrypoint("engine.decode_chunk")
def _audit_decode_chunk() -> List[Violation]:
    entry = "engine.decode_chunk[kernel]"
    cfg = _tiny_lm_cfg(decode_attn_impl="kernel", ffn_impl="pallas")
    slots, max_len = 2, 32
    jaxpr, params, caches, _ = _engine_chunk_jaxpr(cfg, slots=slots,
                                                   max_len=max_len)
    out = []
    out += forbidden_primitive_violations(jaxpr, entry)
    out += kernel_count_violations(jaxpr, entry, "some")
    out += dispatch_buffer_violations(jaxpr, slots, cfg.spt.ffn_groups,
                                      entry)
    out += cache_repeat_violations(jaxpr, cfg.num_heads, cfg.num_kv_heads,
                                   max_len, entry)
    out += accum_dtype_violations(jaxpr, entry)
    return out


@hot_entrypoint("engine.decode_chunk_kernels_off")
def _audit_decode_chunk_disabled() -> List[Violation]:
    """REPRO_DISABLE_KERNELS=1 must demote the same chunk to pure jnp —
    no pallas_call may survive the kill switch (trace-time check, so the
    env var is set only around the trace)."""
    entry = "engine.decode_chunk[kernels-off]"
    prev = os.environ.get("REPRO_DISABLE_KERNELS")
    os.environ["REPRO_DISABLE_KERNELS"] = "1"
    try:
        cfg = _tiny_lm_cfg(decode_attn_impl="kernel", ffn_impl="pallas")
        jaxpr, _, _, _ = _engine_chunk_jaxpr(cfg)
    finally:
        if prev is None:
            os.environ.pop("REPRO_DISABLE_KERNELS", None)
        else:
            os.environ["REPRO_DISABLE_KERNELS"] = prev
    return (kernel_count_violations(jaxpr, entry, "none")
            + forbidden_primitive_violations(jaxpr, entry))


@hot_entrypoint("engine.decode_chunk_telemetry")
def _audit_decode_chunk_telemetry() -> List[Violation]:
    """telemetry="off" must be zero-cost: the compiled decode chunk's
    jaxpr is eqn-for-eqn identical to a build that never heard of
    telemetry (the default config), with the legacy 9-output carry.
    telemetry="counters" must actually thread the counter tree (more
    eqns, more outputs) — a silent no-op counter path would report
    zeros as real keep rates."""
    entry = "engine.decode_chunk[telemetry]"
    base = _tiny_lm_cfg()
    jaxpr_default, _, _, _ = _engine_chunk_jaxpr(base)
    jaxpr_off, _, _, _ = _engine_chunk_jaxpr(base.with_spt(telemetry="off"))
    jaxpr_ctr, _, _, _ = _engine_chunk_jaxpr(
        base.with_spt(telemetry="counters"))
    out: List[Violation] = []
    n_default = sum(1 for _ in iter_eqns(jaxpr_default))
    n_off = sum(1 for _ in iter_eqns(jaxpr_off))
    n_ctr = sum(1 for _ in iter_eqns(jaxpr_ctr))
    if n_off != n_default:
        out.append(Violation(
            "jaxpr.telemetry-cost", entry,
            f"telemetry=off chunk has {n_off} eqns vs {n_default} for the "
            "default config — the off path must be zero-cost"))
    n_out_default = len(jaxpr_default.jaxpr.outvars)   # flattened leaves
    n_out_off = len(jaxpr_off.jaxpr.outvars)
    if n_out_off != n_out_default:
        out.append(Violation(
            "jaxpr.telemetry-cost", entry,
            f"telemetry=off chunk returns {n_out_off} output leaves vs "
            f"{n_out_default} for the default config — the off carry "
            "must match the legacy 9-tuple"))
    if n_ctr <= n_off or len(jaxpr_ctr.jaxpr.outvars) <= n_out_off:
        out.append(Violation(
            "jaxpr.telemetry-cost", entry,
            "telemetry=counters chunk is indistinguishable from off — "
            "the counter tree is not riding the carry"))
    return out


@hot_entrypoint("engine.prefill_ragged")
def _audit_prefill_ragged() -> List[Violation]:
    """Batched ragged prefill: admission-path trace must stay free of
    host callbacks and must lower the fused grouped FFN kernel when
    ffn_impl="pallas".  (No byte budget: prefill legitimately builds
    (B, G, C, d) capacity buffers and SxS score tiles.)"""
    from repro.models import transformer
    entry = "engine.prefill_ragged"
    cfg = _tiny_lm_cfg(ffn_impl="pallas")
    params = _lm_params(cfg)
    bpb, s, max_len = 2, 16, 32
    batch = {"tokens": jax.ShapeDtypeStruct((bpb, s), jnp.int32)}
    lengths = jax.ShapeDtypeStruct((bpb,), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, b, ln: transformer.lm_prefill_ragged(p, cfg, b, ln,
                                                       max_len)
    )(params, batch, lengths)
    return (forbidden_primitive_violations(jaxpr, entry)
            + kernel_count_violations(jaxpr, entry, "some")
            + accum_dtype_violations(jaxpr, entry))


def _sparse_decode_operands():
    from repro.core import pq
    from repro.core import sparse_attention as sa
    from repro.core.params import init_tree

    b, hq, hk, s, d, m = 4, 8, 2, 256, 64, 8
    pcfg = pq.PQConfig(head_dim=d, code_dim=m, num_codewords=16)
    cb = jax.eval_shape(lambda: init_tree(
        pq.param_defs(pcfg), jax.random.PRNGKey(0)))["codebooks"]
    scfg = sa.SparseAttentionConfig(pq=pcfg, top_fraction=0.25, min_l=4)
    f32 = lambda *sh: jax.ShapeDtypeStruct(sh, jnp.float32)
    q, k, v = f32(b, hq, 1, d), f32(b, hk, s, d), f32(b, hk, s, d)
    codes = jax.ShapeDtypeStruct((b, hk, s, d // m), jnp.int8)
    kv_valid = jax.ShapeDtypeStruct((b, s), jnp.bool_)
    return (b, hq, hk, s, d), scfg, cb, q, k, v, codes, kv_valid


@hot_entrypoint("ops.sparse_mha_decode")
def _audit_sparse_mha_decode() -> List[Violation]:
    """The one-pass decode attention op at serving-representative shape:
    exactly ONE kernel (histogram prologue + attention in a single
    pallas_call — the thresholds tensor never reaches HBM), nothing bigger
    than the V cache, and no GQA expansion."""
    from repro.kernels.sparse_attention import ops as sa_ops

    entry = "ops.sparse_mha_decode[fused]"
    (b, hq, hk, s, d), scfg, cb, q, k, v, codes, kv_valid = \
        _sparse_decode_operands()
    jaxpr = jax.make_jaxpr(
        lambda q, k, v, c, cb, kv: sa_ops.sparse_mha_decode(
            q, k, v, c, cb, scfg, d ** -0.5, kv, interpret=True, fuse=True)
    )(q, k, v, codes, cb, kv_valid)
    return (kernel_count_violations(jaxpr, entry, "exact", exact=1)
            + forbidden_primitive_violations(jaxpr, entry)
            + cache_repeat_violations(jaxpr, hq, hk, s, entry)
            + accum_dtype_violations(jaxpr, entry))


@hot_entrypoint("ops.sparse_mha_decode_two_pass")
def _audit_sparse_mha_decode_two_pass() -> List[Violation]:
    """The bisection tier: fuse=False still lowers the original
    threshold + attention kernel pair (exactly two pallas_calls), with
    the same byte/shape discipline."""
    from repro.kernels.sparse_attention import ops as sa_ops

    entry = "ops.sparse_mha_decode[two-pass]"
    (b, hq, hk, s, d), scfg, cb, q, k, v, codes, kv_valid = \
        _sparse_decode_operands()
    jaxpr = jax.make_jaxpr(
        lambda q, k, v, c, cb, kv: sa_ops.sparse_mha_decode(
            q, k, v, c, cb, scfg, d ** -0.5, kv, interpret=True, fuse=False)
    )(q, k, v, codes, cb, kv_valid)
    return (kernel_count_violations(jaxpr, entry, "exact", exact=2)
            + forbidden_primitive_violations(jaxpr, entry)
            + cache_repeat_violations(jaxpr, hq, hk, s, entry)
            + accum_dtype_violations(jaxpr, entry))


@hot_entrypoint("engine.decode_chunk_paged")
def _audit_decode_chunk_paged() -> List[Violation]:
    """Paged layout with the kernel tier on: the decode chunk must read
    the KV pool kernel-natively — no gathered per-slot (B, Hk, view, ·)
    view (or its pre-transpose 5-d form) anywhere in the chunk, and no
    intermediate bigger than the pool itself."""
    entry = "engine.decode_chunk[paged-native]"
    cfg = _tiny_lm_cfg(decode_attn_impl="kernel", attn_impl="pallas",
                       ffn_impl="pallas", kv_layout="paged",
                       kv_page_size=16)
    slots, max_len = 2, 32
    jaxpr, params, caches, _ = _engine_chunk_jaxpr(cfg, slots=slots,
                                                   max_len=max_len)
    ps = cfg.spt.kv_page_size
    from repro.serving import kv_pages as kvp
    view = kvp.view_len(max_len, ps)
    out = []
    out += forbidden_primitive_violations(jaxpr, entry)
    out += kernel_count_violations(jaxpr, entry, "some")
    out += paged_gather_violations(jaxpr, slots, cfg.num_kv_heads, view,
                                   ps, kvp.num_pages(max_len, ps), entry)
    out += cache_repeat_violations(jaxpr, cfg.num_heads, cfg.num_kv_heads,
                                   view, entry)
    out += accum_dtype_violations(jaxpr, entry)
    return out


@hot_entrypoint("ops.routed_ffn_decode")
def _audit_routed_ffn_decode() -> List[Violation]:
    """Block-gather decode FFN: one kernel, no (B, G, C, d) dispatch
    buffer at any width (the PR-3 acceptance property, now enforced at
    HEAD instead of only in one test fixture)."""
    from repro.core import lora as lora_mod
    from repro.core import routed_ffn as rf
    from repro.core.params import init_tree
    from repro.kernels.routed_ffn import ops as rffn_ops

    entry = "ops.routed_ffn_decode"
    b, d, dff, g, gp = 4, 64, 128, 8, 2
    lcfg = lora_mod.LoRAConfig(rank=4, alpha=4.0, enabled=True)
    rcfg = rf.RoutedFFNConfig(d_model=d, d_ff=dff, num_groups=g,
                              active_groups=gp, capacity_factor=4.0,
                              gated=True, activation="gelu")
    p = jax.eval_shape(lambda: init_tree(rf.param_defs(rcfg, lcfg),
                                         jax.random.PRNGKey(0)))
    x = jax.ShapeDtypeStruct((b, 1, d), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda p, x: rffn_ops.routed_ffn_decode(x, p, rcfg, lcfg,
                                                interpret=True)[0])(p, x)
    return (kernel_count_violations(jaxpr, entry, "exact", exact=1)
            + dispatch_buffer_violations(jaxpr, b, g, entry)
            + forbidden_primitive_violations(jaxpr, entry)
            + accum_dtype_violations(jaxpr, entry))


@hot_entrypoint("models.moe_decode")
def _audit_moe_decode() -> List[Violation]:
    """MoE decode through the shared block-gather kernel: expert ids
    index weight blocks directly — no (B, E, C, d) capacity buffer."""
    from repro import configs
    from repro.core.params import init_tree
    from repro.models import moe

    entry = "models.moe_decode"
    cfg = configs.get_smoke("grok-1-314b").with_spt(ffn_impl="pallas")
    p = jax.eval_shape(lambda: init_tree(moe.moe_defs(cfg),
                                         jax.random.PRNGKey(0)))
    b = 4
    x = jax.ShapeDtypeStruct((b, 1, cfg.d_model), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda p, x: moe.moe_apply(p, x, cfg, mode="decode")[0])(p, x)
    return (dispatch_buffer_violations(jaxpr, b, cfg.num_experts, entry)
            + kernel_count_violations(jaxpr, entry, "some")
            + forbidden_primitive_violations(jaxpr, entry))


@audit("jaxpr")
def _jaxpr_audit() -> List[Violation]:
    out: List[Violation] = []
    for name in ENTRYPOINTS:
        out.extend(ENTRYPOINTS[name]())
    return out
