"""Layer 2: static Pallas-kernel audits — VMEM residency, tile
divisibility, scalar-prefetch arity.

Kernels are captured by tracing their public wrapper ops with
``jax.make_jaxpr`` and reading each ``pallas_call`` eqn's GridMapping —
no monkeypatching, no execution, and (critically) no pollution of the
module-level jit caches the wrappers sit behind.  From the BlockSpecs +
grid + scratch shapes we bound what one grid step keeps resident in
VMEM; on TPU, blowing that budget is a *compile-time* failure, so this
audit is the CPU-side tripwire for a BlockSpec edit that would brick the
TPU build.

Rules:
  pallas.vmem-budget       2x (double-buffered) per-step block bytes +
                           scratch bytes > VMEM_BUDGET_BYTES
  pallas.tile-divisibility a VMEM block breaks the TPU's tiling rule:
                           its last two dims must be divisible by (8, 128)
                           or equal the array's dims (Mosaic refuses the
                           kernel otherwise; interpret mode never does)
  pallas.scalar-prefetch   a kernel's scalar-prefetch operand count
                           drifted from its contract (grouped FFN
                           prefetches the plan index; decode FFN
                           prefetches choices + gates; everything else
                           prefetches nothing)
  pallas.no-kernel         a registered entry traced zero pallas_calls
                           (the audit itself went vacuous)

Representative shapes are serving-scale (d_model 1024, S 512, d_ff
3072) so the VMEM estimate reflects deployment tiles, not smoke tests.
New kernels register with ``@kernel_entry("name")`` returning
``(fn, args, expectations)``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.jaxpr_audit import iter_eqns
from repro.analysis.liveness import aval_bytes
from repro.analysis.registry import Violation, audit

# Per-core VMEM on current TPU generations (see the Pallas guide); one
# grid step's working set must fit with room for double buffering.
VMEM_BUDGET_BYTES = 16 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class BlockInfo:
    block_shape: Tuple[int, ...]   # squeezed dims count as 1
    array_shape: Tuple[int, ...]
    dtype: str
    itemsize: int
    any_space: bool            # ANY-space operands stay in HBM

    @property
    def block_bytes(self) -> int:
        size = 1
        for bdim in self.block_shape:
            size *= bdim
        return size * self.itemsize


@dataclasses.dataclass(frozen=True)
class PallasCallInfo:
    name: str                  # kernel fn name (+ src line)
    grid: Tuple[int, ...]
    num_index_operands: int    # scalar-prefetch operands
    num_scratch_operands: int
    blocks: Tuple[BlockInfo, ...]   # inputs then outputs
    scratch_bytes: int

    @property
    def short_name(self) -> str:
        return str(self.name).split(" ")[0]

    @property
    def vmem_bytes(self) -> int:
        """One grid step's VMEM residency bound: every non-ANY in/out
        block double-buffered (the pipeline overlaps the next step's
        copies) plus all scratch."""
        blocks = sum(b.block_bytes for b in self.blocks if not b.any_space)
        return 2 * blocks + self.scratch_bytes


def _scratch_nbytes(kernel_jaxpr, num_scratch: int) -> int:
    if not num_scratch:
        return 0
    return sum(aval_bytes(var.aval)
               for var in kernel_jaxpr.invars[-num_scratch:])


def _block_dim(bdim) -> int:
    """Extent of one block dim: ``Blocked(n)`` -> n, squeezed -> 1."""
    return int(getattr(bdim, "block_size", 1))


def calls_in_jaxpr(jaxpr) -> List[PallasCallInfo]:
    """Decode every pallas_call eqn of ``jaxpr``, however deeply nested."""
    out = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        gm = eqn.params["grid_mapping"]
        blocks = []
        for bm in gm.block_mappings:
            aval = bm.array_aval
            blocks.append(BlockInfo(
                block_shape=tuple(_block_dim(d) for d in bm.block_shape),
                array_shape=tuple(aval.shape),
                dtype=jnp.dtype(aval.dtype).name,
                itemsize=jnp.dtype(aval.dtype).itemsize,
                any_space=str(getattr(bm.transformed_block_aval,
                                      "memory_space", "")) == "any"))
        kernel = eqn.params["jaxpr"]
        out.append(PallasCallInfo(
            name=str(kernel.debug_info.func_src_info),
            grid=tuple(int(g) for g in gm.grid),
            num_index_operands=int(gm.num_index_operands),
            num_scratch_operands=int(gm.num_scratch_operands),
            blocks=tuple(blocks),
            scratch_bytes=_scratch_nbytes(kernel, gm.num_scratch_operands)))
    return out


def collect_pallas_calls(fn: Callable, *args) -> List[PallasCallInfo]:
    """Trace ``fn(*args)`` (ShapeDtypeStructs welcome) and decode every
    pallas_call eqn."""
    return calls_in_jaxpr(jax.make_jaxpr(fn)(*args))


# ------------------------------------------------------------ rule bodies
def vmem_violations(calls: Sequence[PallasCallInfo], entry: str,
                    budget: int = VMEM_BUDGET_BYTES) -> List[Violation]:
    out = []
    for c in calls:
        if c.vmem_bytes > budget:
            out.append(Violation(
                "pallas.vmem-budget", entry,
                f"{c.short_name}: ~{c.vmem_bytes} B resident per grid "
                f"step (2x blocks + {c.scratch_bytes} B scratch) > "
                f"budget {budget} B"))
    return out


# The TPU's (sublane, lane) tile: a block's last two dims must be
# multiples of these or equal the array's own dims.
TPU_TILE = (8, 128)


def tile_divisibility_violations(calls: Sequence[PallasCallInfo],
                                 entry: str) -> List[Violation]:
    out = []
    for c in calls:
        for i, b in enumerate(c.blocks):
            if b.any_space:
                continue
            pairs = list(zip(b.block_shape, b.array_shape))[-2:]
            for (bdim, adim), tile in zip(pairs, TPU_TILE[-len(pairs):]):
                if bdim != adim and bdim % tile:
                    out.append(Violation(
                        "pallas.tile-divisibility", entry,
                        f"{c.short_name} operand {i}: block "
                        f"{b.block_shape} over array {b.array_shape} — "
                        f"the last two block dims must be divisible by "
                        f"{TPU_TILE} or equal the array's"))
                    break
    return out


def scalar_prefetch_violations(calls: Sequence[PallasCallInfo], entry: str,
                               expected: Dict[str, int]) -> List[Violation]:
    """expected: substring of the kernel's name+src info -> required
    num_index_operands (kernels not matched by any key must prefetch
    nothing)."""
    out = []
    for c in calls:
        want = 0
        for key, n in expected.items():
            if key in c.name:
                want = n
                break
        if c.num_index_operands != want:
            out.append(Violation(
                "pallas.scalar-prefetch", entry,
                f"{c.short_name} prefetches {c.num_index_operands} "
                f"scalar operand(s), contract says {want}"))
    return out


def audit_calls(calls: Sequence[PallasCallInfo], entry: str,
                prefetch: Optional[Dict[str, int]] = None,
                budget: int = VMEM_BUDGET_BYTES) -> List[Violation]:
    if not calls:
        return [Violation("pallas.no-kernel", entry,
                          "entry traced zero pallas_calls — the audit "
                          "is vacuous (wrapper stopped lowering?)")]
    return (vmem_violations(calls, entry, budget)
            + tile_divisibility_violations(calls, entry)
            + scalar_prefetch_violations(calls, entry, prefetch or {}))


# --------------------------------------------------------- kernel entries
KERNEL_ENTRIES: Dict[str, Callable[[], List[Violation]]] = {}


def kernel_entry(name: str):
    def register(fn):
        if name in KERNEL_ENTRIES:
            raise ValueError(f"duplicate kernel entry {name!r}")
        KERNEL_ENTRIES[name] = fn
        return fn
    return register


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _pq_setup(d: int = 64, m: int = 8):
    from repro.core import pq
    from repro.core.params import init_tree
    pcfg = pq.PQConfig(head_dim=d, code_dim=m, num_codewords=16)
    cb = jax.eval_shape(lambda: init_tree(
        pq.param_defs(pcfg), jax.random.PRNGKey(0)))["codebooks"]
    return pcfg, cb


@kernel_entry("sparse_attention.prefill")
def _entry_sparse_prefill() -> List[Violation]:
    from repro.core import sparse_attention as sa
    from repro.kernels.sparse_attention import ops as sa_ops
    entry = "kernels.sparse_mha[prefill b2 h8/2 s512 d64]"
    b, hq, hk, s, d = 2, 8, 2, 512, 64
    pcfg, cb = _pq_setup(d)
    scfg = sa.SparseAttentionConfig(pq=pcfg, top_fraction=0.25, min_l=8)
    calls = collect_pallas_calls(
        lambda q, k, v, cb: sa_ops.sparse_mha(q, k, v, cb, scfg,
                                              d ** -0.5, causal=True,
                                              interpret=True)[0],
        _f32(b, hq, s, d), _f32(b, hk, s, d), _f32(b, hk, s, d), cb)
    return audit_calls(calls, entry)


@kernel_entry("sparse_attention.decode")
def _entry_sparse_decode() -> List[Violation]:
    """Both decode tiers at serving scale: the fused one-pass kernel
    (histogram scratch rides the same grid — nothing prefetched) and the
    two-pass bisection pair."""
    from repro.core import sparse_attention as sa
    from repro.kernels.sparse_attention import ops as sa_ops
    b, hq, hk, s, d, m = 4, 8, 2, 1024, 64, 8
    pcfg, cb = _pq_setup(d, m)
    scfg = sa.SparseAttentionConfig(pq=pcfg, top_fraction=0.25, min_l=8)
    args = (_f32(b, hq, 1, d), _f32(b, hk, s, d), _f32(b, hk, s, d),
            jax.ShapeDtypeStruct((b, hk, s, d // m), jnp.int8), cb,
            jax.ShapeDtypeStruct((b, s), jnp.bool_))
    out = []
    for fuse, tag in ((True, "fused"), (False, "two-pass")):
        entry = f"kernels.sparse_mha_decode[{tag} b4 h8/2 s1024 d64]"
        calls = collect_pallas_calls(
            lambda q, k, v, c, cb, kv: sa_ops.sparse_mha_decode(
                q, k, v, c, cb, scfg, d ** -0.5, kv, interpret=True,
                fuse=fuse), *args)
        out += audit_calls(calls, entry)
        want = 1 if fuse else 2
        if len(calls) != want:
            out.append(Violation(
                "pallas.no-kernel", entry,
                f"expected {want} pallas_call(s), traced {len(calls)}"))
    return out


@kernel_entry("sparse_attention.decode_paged")
def _entry_sparse_decode_paged() -> List[Violation]:
    """Kernel-native paged decode: sparse and dense kernels must each
    prefetch exactly ONE scalar operand (the clamped page table driving
    the pool index_maps), tile within page bounds, and stay inside the
    VMEM budget at serving-scale page counts."""
    from repro.core import sparse_attention as sa
    from repro.kernels.sparse_attention import ops as sa_ops
    b, hq, hk, d, m = 4, 8, 2, 64, 8
    ps, mp, pool = 128, 8, 64                 # view 1024 rows/slot
    pcfg, cb = _pq_setup(d, m)
    scfg = sa.SparseAttentionConfig(pq=pcfg, top_fraction=0.25, min_l=8)
    pt = jax.ShapeDtypeStruct((b, mp), jnp.int32)
    kvv = jax.ShapeDtypeStruct((b, mp * ps), jnp.bool_)
    entry = f"kernels.sparse_mha_decode_paged[b4 h8/2 ps{ps} mp{mp} d64]"
    calls = collect_pallas_calls(
        lambda q, k, v, c, cb, kv, pt: sa_ops.sparse_mha_decode_paged(
            q, k, v, c, cb, scfg, d ** -0.5, kv, pt, interpret=True),
        _f32(b, hq, 1, d), _f32(pool, hk, ps, d), _f32(pool, hk, ps, d),
        jax.ShapeDtypeStruct((pool, hk, ps, d // m), jnp.int8), cb,
        kvv, pt)
    out = audit_calls(calls, entry, prefetch={"sparse_attention.py": 1})
    entry_d = f"kernels.dense_mha_decode_paged[b4 h8/2 ps{ps} mp{mp} d64]"
    calls_d = collect_pallas_calls(
        lambda q, k, v, kv, pt: sa_ops.dense_mha_decode_paged(
            q, k, v, d ** -0.5, kv, pt, interpret=True),
        _f32(b, hq, 1, d), _f32(pool, hk, ps, d), _f32(pool, hk, ps, d),
        kvv, pt)
    out += audit_calls(calls_d, entry_d, prefetch={"sparse_attention.py": 1})
    return out


@kernel_entry("routed_ffn.grouped")
def _entry_routed_grouped() -> List[Violation]:
    from repro.core import lora as lora_mod
    from repro.core import routed_ffn as rf
    from repro.core.params import init_tree
    from repro.kernels.routed_ffn import ops as rffn_ops
    entry = "kernels.routed_ffn[grouped b2 s256 d1024 f3072 g8]"
    lcfg = lora_mod.LoRAConfig(rank=8, alpha=8.0, enabled=True)
    rcfg = rf.RoutedFFNConfig(d_model=1024, d_ff=3072, num_groups=8,
                              active_groups=2, capacity_factor=2.0,
                              gated=True, activation="gelu")
    p = jax.eval_shape(lambda: init_tree(rf.param_defs(rcfg, lcfg),
                                         jax.random.PRNGKey(0)))
    calls = collect_pallas_calls(
        lambda p, x: rffn_ops.routed_ffn(x, p, rcfg, lcfg,
                                         interpret=True)[0],
        p, _f32(2, 256, 1024))
    # the grouped kernel scalar-prefetches the (B, G, C) plan index
    return audit_calls(calls, entry, prefetch={"routed_ffn.py": 1})


@kernel_entry("routed_ffn.decode")
def _entry_routed_decode() -> List[Violation]:
    from repro.core import lora as lora_mod
    from repro.core import routed_ffn as rf
    from repro.core.params import init_tree
    from repro.kernels.routed_ffn import ops as rffn_ops
    entry = "kernels.routed_ffn_decode[b8 d1024 f3072 g8]"
    lcfg = lora_mod.LoRAConfig(rank=8, alpha=8.0, enabled=True)
    rcfg = rf.RoutedFFNConfig(d_model=1024, d_ff=3072, num_groups=8,
                              active_groups=2, capacity_factor=2.0,
                              gated=True, activation="gelu")
    p = jax.eval_shape(lambda: init_tree(rf.param_defs(rcfg, lcfg),
                                         jax.random.PRNGKey(0)))
    calls = collect_pallas_calls(
        lambda p, x: rffn_ops.routed_ffn_decode(x, p, rcfg, lcfg,
                                                interpret=True)[0],
        p, _f32(8, 1, 1024))
    # block-gather decode kernel scalar-prefetches choices AND gates
    return audit_calls(calls, entry, prefetch={"routed_ffn.py": 2})


@kernel_entry("pq_quantize.assign")
def _entry_pq_assign() -> List[Violation]:
    from repro.kernels.pq_quantize import ops as pq_ops
    entry = "kernels.pq_assign[b2 h8 s512 d64]"
    _, cb = _pq_setup(64)
    calls = collect_pallas_calls(
        lambda x, cb: pq_ops.pq_assign(x, cb, interpret=True),
        _f32(2, 8, 512, 64), cb)
    return audit_calls(calls, entry)


@audit("pallas")
def _pallas_audit() -> List[Violation]:
    out: List[Violation] = []
    for name in KERNEL_ENTRIES:
        out.extend(KERNEL_ENTRIES[name]())
    return out
