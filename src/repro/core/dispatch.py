"""Capacity-bucketed token dispatch — the TPU analogue of the paper's BSpMV
(§5.2): "batch the tokens that activate the same block for efficient
computation".

On GPU the paper gathers a dynamic number of tokens per weight block and runs
one GEMM per block on its own stream.  Under XLA/jit shapes must be static,
so we use the standard fixed-capacity formulation; crucially the dispatch is
**per sequence** (batch-local): ranks come from a cumsum along the sequence
axis only, so under pjit every buffer keeps its batch sharding and no global
collective is ever induced by routing (a global-cumsum formulation forces
XLA to replicate the (B*S*K, d) dispatch buffers — measured in
EXPERIMENTS.md §Dry-run calibration).

Token t of sequence b activating block g lands in slot rank(t within (b, g))
if below capacity; overflowing (token, choice) pairs are dropped (the
monitor reports the fraction so capacity_factor can be raised).

The same engine serves the routed FFN (top-G' of G row-blocks) and MoE
layers (top-k of E experts) — the paper notes they are the same mechanism.

Shapes: x (B, S, d); choice/gate (B, S, K); plan.index (B, G, C).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class DispatchPlan:
    """Static-shape routing plan for one layer invocation."""
    index: jax.Array      # (B, G, C) int32 — slot -> token position (S if empty)
    slot_ok: jax.Array    # (B, G, C) bool
    combine_w: jax.Array  # (B, G, C) f32
    dropped: jax.Array    # () f32 — dropped fraction of (token, choice) pairs


jax.tree_util.register_pytree_node(
    DispatchPlan,
    lambda p: ((p.index, p.slot_ok, p.combine_w, p.dropped), None),
    lambda _, c: DispatchPlan(*c))


def capacity(tokens_per_seq: int, num_groups: int, topk: int,
             capacity_factor: float, pad: int = 8) -> int:
    """Slots per (sequence, group), padded to a multiple of ``pad`` (>= 8).
    pad=128 makes the capacity dim shardable 16-way for the dispatch-SP
    optimization (EXPERIMENTS.md §Perf)."""
    pad = max(8, pad)
    c = int(tokens_per_seq * topk * capacity_factor / num_groups) + 1
    c = -(-c // pad) * pad
    return min(c, max(pad, -(-tokens_per_seq * topk // pad) * pad))


def slot_counts(batch: int, tokens_per_seq: int, layers: int,
                num_groups: int, topk: int, capacity_factor: float,
                dropped: float, pad: int = 8) -> Dict[str, float]:
    """Host-side counts of one step's dispatch over ``layers`` routed
    layers, from static shapes and ``dropped`` (the dropped fractions of
    the layers, summed):

      routed_pairs  (token, choice) pairs routed;
      routed_kept   pairs that found a slot under ``capacity``;
      routed_slots  slots the (B, G, C) buffers hold.

    kept / slots is how full the grouped kernel's slots run."""
    pairs = batch * tokens_per_seq * topk * layers
    cap = capacity(tokens_per_seq, num_groups, topk, capacity_factor, pad)
    return {"routed_pairs": float(pairs),
            "routed_kept": pairs * (1.0 - float(dropped) / layers),
            "routed_slots": float(batch * layers * num_groups * cap)}


def capacity_dyn(tokens_per_seq: jax.Array, num_groups: int, topk: int,
                 capacity_factor: float, pad: int = 8) -> jax.Array:
    """Traced counterpart of ``capacity`` for per-row lengths (B,) int32 —
    batched ragged prefill needs each row's capacity to match what a
    batch-1 exact-length call would have used.  Bit-identical to the host
    formula whenever ``capacity_factor`` is exactly representable in
    float32 (true for every dyadic factor in the repo's configs)."""
    pad = max(8, pad)
    t = jnp.asarray(tokens_per_seq, jnp.int32)
    c = (t.astype(jnp.float32) * topk * capacity_factor
         / num_groups).astype(jnp.int32) + 1
    c = -(-c // pad) * pad
    return jnp.minimum(c, jnp.maximum(pad, -(-t * topk // pad) * pad))


def make_plan(choice: jax.Array, gate: jax.Array, num_groups: int,
              cap: int, cap_dyn: Optional[jax.Array] = None) -> DispatchPlan:
    """choice: (B, S, K) int32; gate: (B, S, K) f32.

    cap_dyn: optional per-row (B,) capacities (<= cap) — ragged prefill
    rows right-padded to a common S keep the capacity their exact length
    would have had, so drops match the batch-1 serial engine row-for-row
    (pad tokens sit after the real ones in position order, so real-token
    ranks are unaffected either way)."""
    b, s, k = choice.shape
    flat_choice = choice.reshape(b, s * k)
    flat_gate = gate.reshape(b, s * k)
    oh = jax.nn.one_hot(flat_choice, num_groups, dtype=jnp.int32)  # (B,SK,G)
    ranks = jnp.cumsum(oh, axis=1) - oh                  # exclusive, per seq
    rank = jnp.sum(ranks * oh, axis=-1)                  # (B, SK)
    limit = cap if cap_dyn is None else jnp.minimum(
        jnp.asarray(cap_dyn, jnp.int32), cap)[:, None]
    keep = rank < limit
    dropped = 1.0 - jnp.mean(keep.astype(jnp.float32))
    token_id = jnp.broadcast_to(
        jnp.repeat(jnp.arange(s, dtype=jnp.int32), k)[None, :], (b, s * k))
    # flat (G*C)-destination per (token, choice); dropped -> OOB (drop mode).
    # vmapped scatters lower to batched scatter so SPMD keeps batch sharding.
    dest = jnp.where(keep, flat_choice * cap + rank, num_groups * cap)

    def _scatter_row(di, so, cw, pos, tid, gt):
        return (di.at[pos].set(tid, mode="drop"),
                so.at[pos].set(True, mode="drop"),
                cw.at[pos].set(gt, mode="drop"))

    index0 = jnp.full((b, num_groups * cap), s, dtype=jnp.int32)
    ok0 = jnp.zeros((b, num_groups * cap), dtype=bool)
    cw0 = jnp.zeros((b, num_groups * cap), dtype=jnp.float32)
    index, slot_ok, combine_w = jax.vmap(_scatter_row)(
        index0, ok0, cw0, dest, token_id, flat_gate)
    shape = (b, num_groups, cap)
    return DispatchPlan(index.reshape(shape), slot_ok.reshape(shape),
                        combine_w.reshape(shape), dropped)


def gather(x: jax.Array, plan: DispatchPlan) -> jax.Array:
    """(B, S, d) -> (B, G, C, d); empty slots read a zero row."""
    b, s, d = x.shape
    _, g, c = plan.index.shape
    xz = jnp.concatenate([x, jnp.zeros((b, 1, d), x.dtype)], axis=1)
    out = jnp.take_along_axis(xz, plan.index.reshape(b, g * c)[..., None],
                              axis=1)
    return out.reshape(b, g, c, d)


def combine(y: jax.Array, plan: DispatchPlan, seq_len: int) -> jax.Array:
    """(B, G, C, d) -> (B, S, d) scatter-add with combine weights."""
    b, g, c, d = y.shape
    w = jnp.where(plan.slot_ok, plan.combine_w, 0.0).astype(y.dtype)
    yw = (y * w[..., None]).reshape(b, g * c, d)

    def _row(acc, pos, vals):                     # vmapped: batched scatter
        return acc.at[pos].add(vals, mode="drop")

    out = jnp.zeros((b, seq_len + 1, d), y.dtype)
    out = jax.vmap(_row)(out, plan.index.reshape(b, g * c), yw)
    return out[:, :seq_len]


# ------------------------------------------------------ kernel dispatch
# The second dispatch concern this module owns: which *execution path* a
# layer lowers through.  Kernel selection is a trace-time decision (shapes
# are static under jit), gated by one global kill switch plus per-feature
# config flags, so the jnp fallbacks stay one env var away for debugging
# and CI bisection.

def kernels_disabled() -> bool:
    """REPRO_DISABLE_KERNELS=1 forces every jnp fallback path (checked at
    trace time; unset/0/false = kernels allowed)."""
    import os
    return os.environ.get("REPRO_DISABLE_KERNELS", "0").strip().lower() \
        not in ("", "0", "false")


def use_sparse_decode_kernel(cfg) -> bool:
    """Should sparse-MHA decode lower through the fused Pallas kernel?

    cfg is a ModelConfig (duck-typed — importing configs here would cycle).
    spt.decode_attn_impl: "kernel" | "jnp" | "auto" (auto follows the
    train/prefill attn_impl, i.e. kernels on iff attn_impl == "pallas").
    REPRO_DISABLE_KERNELS=1 overrides everything.
    """
    if kernels_disabled():
        return False
    impl = getattr(cfg.spt, "decode_attn_impl", "auto")
    if impl == "auto":
        return cfg.spt.attn_impl == "pallas"
    return impl == "kernel"


def use_fused_decode_attn(cfg) -> bool:
    """Within the sparse-decode kernel tier, should the ONE-PASS fused
    kernel run (threshold histogram as a prologue phase of the attention
    grid) instead of the two-pass threshold + attention kernel pair?

    cfg is a ModelConfig (duck-typed).  spt.decode_attn_fuse: "fused" |
    "two_pass" | "auto" (auto = fused; two_pass is the bisection tier —
    both produce bit-identical output).  Only consulted when
    use_sparse_decode_kernel(cfg) already said yes, so the kill switch
    needs no separate handling here.
    """
    mode = getattr(cfg.spt, "decode_attn_fuse", "auto")
    if mode == "auto":
        return True
    return mode == "fused"


def use_paged_native_decode(cfg) -> bool:
    """Should paged-pool decode attention address K/V/code tiles straight
    from the page pools (scalar-prefetched page table in the kernels'
    BlockSpec index_maps) instead of materializing a gathered per-slot
    (B, Hk, S, .) view first?

    cfg is a ModelConfig (duck-typed).  spt.kv_paged_native: "kernel" |
    "gather" | "auto" (auto follows the decode attention kernel tier:
    native iff attn_impl == "pallas").  Unlike the layout switch
    (use_paged_kv) this IS a kernel decision, so REPRO_DISABLE_KERNELS=1
    forces the gathered-view fallback.
    """
    if kernels_disabled():
        return False
    impl = getattr(cfg.spt, "kv_paged_native", "auto")
    if impl == "auto":
        return cfg.spt.attn_impl == "pallas"
    return impl == "kernel"


def use_routed_ffn_kernel(cfg) -> bool:
    """Should train/prefill routed FFN lower through the fused Pallas
    grouped-GEMM kernel (in-kernel scalar-prefetch dispatch)?

    cfg is a ModelConfig (duck-typed).  spt.ffn_impl == "pallas" selects
    the kernel; REPRO_DISABLE_KERNELS=1 demotes it to the jnp grouped
    path (identical routing plan, so identical function).
    """
    if kernels_disabled():
        return False
    return getattr(cfg.spt, "ffn_impl", "grouped") == "pallas"


def use_decode_ffn_kernel(cfg) -> bool:
    """Should the serving-decode routed FFN (x of shape (B, 1, d)) lower
    through the block-gather Pallas kernel (no capacity plan, no dispatch
    buffer)?

    spt.decode_ffn_impl: "kernel" | "jnp" | "auto" (auto follows the
    train/prefill ffn_impl, i.e. kernel on iff ffn_impl == "pallas").
    REPRO_DISABLE_KERNELS=1 overrides everything.
    """
    if kernels_disabled():
        return False
    impl = getattr(cfg.spt, "decode_ffn_impl", "auto")
    if impl == "auto":
        return getattr(cfg.spt, "ffn_impl", "grouped") == "pallas"
    return impl == "kernel"


def use_paged_kv(cfg) -> bool:
    """Should the serving engine lay the KV cache out as fixed-size pages
    (shared pool + per-slot page tables, serving/kv_pages.py) instead of
    one contiguous max_len strip per slot?

    cfg is a ModelConfig (duck-typed).  spt.kv_layout: "paged" |
    "contiguous".  A pure layout decision — not a kernel — so the
    REPRO_DISABLE_KERNELS kill switch does not apply; the engine
    additionally requires transformer.paged_applicable(cfg) (an attention
    stack without a SWA ring cache) before engaging it.
    """
    return getattr(cfg.spt, "kv_layout", "contiguous") == "paged"


def telemetry_mode(cfg) -> str:
    """Serving-observability level: "off" | "counters" | "trace".

    cfg is a ModelConfig (duck-typed).  Like the KV-layout switch this is
    a pure config decision — no kernel is involved, so the
    REPRO_DISABLE_KERNELS kill switch does not apply.  "counters" threads
    jit-pure device counters through the compiled decode chunk / batched
    prefill; "trace" additionally records host-side request lifecycle
    events and scheduler spans (serving/telemetry.py).
    """
    return getattr(cfg.spt, "telemetry", "off")


def use_telemetry_counters(cfg) -> bool:
    """Should the model layers emit jit-pure telemetry counters (tel_*
    aux entries: sparse-MHA kept/eligible slots, routed-FFN/MoE expert
    loads and capacity drops)?

    Off by default so the decode-chunk jaxpr stays eqn-identical to a
    telemetry-free build (jaxpr.telemetry-cost audit); both "counters"
    and "trace" turn the device counters on.
    """
    return telemetry_mode(cfg) in ("counters", "trace")


def load_balance_loss(router_probs: jax.Array, choice: jax.Array,
                      num_groups: int) -> jax.Array:
    """Switch-style auxiliary loss (paper §4.2 'load-balancing loss'):
    G * sum_g f_g p_g over tokens of all sequences; == 1 at perfect balance.
    router_probs: (B, S, G); choice: (B, S, K)."""
    k = choice.shape[-1]
    oh = jax.nn.one_hot(choice, num_groups, dtype=jnp.float32)  # (B,S,K,G)
    f = jnp.mean(jnp.sum(oh, axis=2), axis=(0, 1)) / k          # (G,)
    p = jnp.mean(router_probs.astype(jnp.float32), axis=(0, 1))  # (G,)
    return num_groups * jnp.sum(f * p)
