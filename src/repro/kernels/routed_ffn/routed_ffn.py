"""Blocked grouped-GEMM kernels — the TPU form of the paper's BSpMV (§5.2).

The paper iterates over weight blocks, gathers the tokens that activated
each block, and runs one dense GEMM per block on its own CUDA stream.  Two
kernels cover the two serving regimes:

``grouped_ffn_kernel`` (train / prefill) fuses the token *gather* into the
grouped GEMMs: the capacity plan's ``index`` (core/dispatch.py) rides in as
a scalar-prefetch operand, and each (Tc, d) token tile is DMA'd row-by-row
from the raw (B, S, d) activations straight into VMEM — the (B, G, C, d)
dispatch buffer the jnp path materializes in HBM never exists.  The
activations ride as f32 (B, S, 1, d), so each row DMA indexes two untiled
leading dims and copies one whole (1, d) tile row: the TPU refuses a
1-row slice of a tiled (S, d) array, and a 1-row bf16 slice of a packed
(2, 128) tile.  Per tile —

    y[b, g] = act(x[index[b, g]] @ W_I[g] (+ LoRA)) @ W_O[g] (+ LoRA)

— optionally gated (GeGLU/SwiGLU), with the FFN hidden dim tiled so each
weight column slab streams through VMEM once while a (Tc, d) f32
accumulator carries partial y.  LoRA rides inside the kernel as rank-r
side-matmuls so the fused op is exactly the fine-tuned layer.  The gather
runs once per token tile (at the first F step) and the tile is reused for
every F slab — the jnp path re-reads the gathered buffer per slab.

``decode_ffn_kernel`` (serving decode, x of shape (B, d)) skips dispatch
entirely: at one token per sequence a capacity plan is G*C slots of
bookkeeping to use G', so the per-token top-G' ``choice`` is
scalar-prefetched instead and indexes the weight blocks directly in the
BlockSpec index_maps —

    y[b] = sum_g  gate[b, g] * act(x[b] @ W_I[choice[b, g]]) @ W_O[...]

— no plan, no gather, no scatter-add.

Tiling: capacities / hidden dims that are not tile multiples are zero-
padded up to one (pad slots carry the empty-slot index, pad hidden columns
carry zero weights, so both are exact no-ops) instead of silently falling
back to whole-dimension tiles that blow the VMEM budget at odd sizes.

Grid (grouped): (B, G, C/Tc, F/Tf), F minor.  VMEM @ defaults (Tc=128,
Tf=256): the (Tc, 1, d) f32 token tile pads each row to 8 sublanes
(32*Tc*d B, 4 MiB at d=1024) + weight slabs 2-3 x 2*d*Tf B double-
buffered + the (Tc, d) f32 accumulator.  ``_grouped_tiles`` halves Tf,
then Tc, until a grid step fits 12 MiB, so wide models (d up to 6144)
compile for v5e too (tests/test_tpu_compile.py).  ``interpret=None`` derives from the
backend (compiled on TPU, interpreter elsewhere).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret as _resolve_interpret
from repro.kernels.topl_select.topl_select import vmem

_ACTS = {"relu": jax.nn.relu, "gelu": jax.nn.gelu, "silu": jax.nn.silu}


def _pad_tile(n: int, tile: int) -> int:
    """Tile size for a dim of extent n: whole dim when it fits in one tile,
    else the requested tile with n zero-padded up to a multiple."""
    return n if n <= tile else tile


def _pad_to(n: int, tile: int) -> int:
    return -(-n // tile) * tile


# Of v5e's 16 MiB scoped VMEM default; the rest is Mosaic's own headroom.
_VMEM_BUDGET = 12 * 1024 * 1024


def _grouped_vmem_bytes(tc: int, tf: int, d: int, n_w: int, w_bytes: int,
                        y_bytes: int, r: int) -> int:
    """One grid step of the grouped kernel: double-buffered weight slabs
    (n_w of (d, Tf)) and y block, the (Tc, 1, d) f32 token tile (each row
    padded to 8 sublanes), the (Tc, d) f32 accumulator and, with LoRA
    (rank r > 0), its rank-r blocks and (Tc, r) scratch."""
    total = 2 * n_w * d * tf * w_bytes + 2 * tc * d * y_bytes
    total += 8 * tc * d * 4 + tc * d * 4
    if r:
        total += 2 * (n_w * (d * r + r * tf) + tf * r + r * d) * w_bytes
        total += tc * r * 4
    return total


def _grouped_tiles(c: int, f: int, d: int, n_w: int, w_bytes: int,
                   y_bytes: int, r: int, tile_c: int, tile_f: int):
    """(Tc, Tf) for the grouped kernel: the requested tiles, halved (Tf
    first, down to 128, then Tc, down to 8) until one grid step fits the
    VMEM budget.  Wide models (d of 2560-6144) need the smaller tiles."""
    tc, tf = _pad_tile(c, tile_c), _pad_tile(f, tile_f)
    fits = lambda: _grouped_vmem_bytes(tc, tf, d, n_w, w_bytes, y_bytes,
                                       r) <= _VMEM_BUDGET
    while not fits() and tf > 128 and tf % 256 == 0:
        tf //= 2
    while not fits() and tc > 8 and tc % 16 == 0:
        tc //= 2
    return tc, tf


def _pad_f_operands(tf_pad, w_inner, w_outer, w_gate, lora_params):
    """Zero-pad the FFN hidden dim of every F-carrying operand.  Exact:
    act(0 (+ gated 0*act(0))) = 0 for relu/gelu/silu, and the padded
    W_O rows / LoRA-outer rows are zero, so pad columns contribute
    nothing to y."""
    if not tf_pad:
        return w_inner, w_outer, w_gate, lora_params
    zf = ((0, 0), (0, 0), (0, tf_pad))
    w_inner = jnp.pad(w_inner, zf)
    w_outer = jnp.pad(w_outer, ((0, 0), (0, tf_pad), (0, 0)))
    if w_gate is not None:
        w_gate = jnp.pad(w_gate, zf)
    if lora_params is not None:
        lora_params = dict(lora_params)
        for k in ("lora_inner", "lora_gate"):
            if k in lora_params:
                li = lora_params[k]
                lora_params[k] = {"b": li["b"], "c": jnp.pad(li["c"], zf)}
        lo = lora_params["lora_outer"]
        lora_params["lora_outer"] = {
            "b": jnp.pad(lo["b"], ((0, 0), (0, tf_pad), (0, 0))),
            "c": lo["c"]}
    return w_inner, w_outer, w_gate, lora_params


# ------------------------------------------------------------ train/prefill
def _make_grouped_kernel(act: str, s: int, tc: int, nft: int, gated: bool,
                         use_lora: bool, scale: float):
    def kernel(*refs):
        i = 0
        idx_ref = refs[i]; i += 1                        # scalar prefetch
        x_hbm = refs[i]; i += 1                          # (B, S, 1, d) f32
        wi_ref = refs[i]; i += 1
        wg_ref = None
        if gated:
            wg_ref = refs[i]; i += 1
        wo_ref = refs[i]; i += 1
        li_b = li_c = lg_b = lg_c = lo_b = lo_c = None
        if use_lora:
            li_b = refs[i]; i += 1
            li_c = refs[i]; i += 1
            if gated:
                lg_b = refs[i]; i += 1
                lg_c = refs[i]; i += 1
            lo_b = refs[i]; i += 1
            lo_c = refs[i]; i += 1
        y_ref = refs[i]; i += 1
        xs_ref = refs[i]; i += 1                         # (Tc, 1, d) tile
        acc_ref = refs[i]; i += 1
        hb_ref = refs[i] if use_lora else None
        if use_lora:
            i += 1
        sem = refs[i]

        bi = pl.program_id(0)
        gi = pl.program_id(1)
        ci = pl.program_id(2)
        fi = pl.program_id(3)

        @pl.when(fi == 0)
        def _gather_and_init():
            # In-kernel dispatch: DMA this tile's Tc token rows from the
            # raw activations in HBM.  Empty slots (index == S) clamp to a
            # real row; their garbage y rows are killed downstream (the
            # combine scatter drops index-S slots and zero-weights them).
            # Start every row copy before draining the semaphore so the
            # DMAs overlap instead of paying Tc serial round-trips (each
            # wait retires one row's worth of bytes; rows are same-sized).
            def row_copy(j):
                row = jnp.minimum(idx_ref[bi, gi, ci * tc + j], s - 1)
                return pltpu.make_async_copy(x_hbm.at[bi, row],
                                             xs_ref.at[j], sem)

            def start_row(j, _):
                row_copy(j).start()
                return 0

            def wait_row(j, _):
                row_copy(j).wait()
                return 0

            jax.lax.fori_loop(0, tc, start_row, 0)
            jax.lax.fori_loop(0, tc, wait_row, 0)
            acc_ref[...] = jnp.zeros_like(acc_ref)
            if hb_ref is not None:
                hb_ref[...] = jnp.zeros_like(hb_ref)

        x = xs_ref[:, 0, :]                              # (Tc, d) f32
        f32 = jnp.float32
        dot = lambda a, b: jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())), preferred_element_type=f32)
        up = dot(x, wi_ref[0].astype(f32))               # (Tc, Tf)
        if use_lora:
            xb = dot(x, li_b[...].astype(f32))           # (Tc, r)
            up = up + scale * dot(xb, li_c[0].astype(f32))
        if gated:
            gate = dot(x, wg_ref[0].astype(f32))
            if use_lora:
                xbg = dot(x, lg_b[...].astype(f32))
                gate = gate + scale * dot(xbg, lg_c[0].astype(f32))
            h = _ACTS[act](gate) * up
        else:
            h = _ACTS[act](up)
        acc_ref[...] += dot(h, wo_ref[0].astype(f32))
        if use_lora:
            hb_ref[...] += dot(h, lo_b[0].astype(f32))   # (Tc, r)

        @pl.when(fi == nft - 1)
        def _finish():
            y = acc_ref[...]
            if use_lora:
                y = y + scale * jax.lax.dot_general(
                    hb_ref[...], lo_c[...].astype(f32),
                    (((1,), (0,)), ((), ())), preferred_element_type=f32)
            y_ref[0, 0] = y.astype(y_ref.dtype)

    return kernel


def grouped_ffn_kernel(x: jax.Array, index: jax.Array, w_inner: jax.Array,
                       w_outer: jax.Array,
                       w_gate: Optional[jax.Array] = None,
                       lora_params: Optional[dict] = None,
                       lora_scale: float = 1.0, *,
                       act: str = "relu", tile_c: int = 128,
                       tile_f: int = 256,
                       interpret: Optional[bool] = None) -> jax.Array:
    """x: (B, S, d) raw activations; index: (B, G, C) int32 dispatch plan
    (slot -> token position, S = empty); w_inner: (G, d, F); w_outer:
    (G, F, d).  Returns y: (B, G, C, d).

    The gather is fused: token tiles are DMA'd from x per plan index
    inside the kernel, so no (B, G, C, d) input buffer touches HBM.
    Empty slots produce unspecified (finite) y rows — ``dispatch.combine``
    both zero-weights and scatter-drops them; standalone callers must mask
    by ``plan.slot_ok``.

    lora_params (optional): {"lora_inner": {b (d,r), c (G,r,F)},
    ["lora_gate": ...,] "lora_outer": {b (G,F,r), c (r,d)}}.
    """
    interpret = _resolve_interpret(interpret)
    b, s, d = x.shape
    _, g, c = index.shape
    _, _, f = w_inner.shape
    rank = (lora_params["lora_inner"]["b"].shape[-1]
            if lora_params is not None else 0)
    tc, tf = _grouped_tiles(c, f, d, 2 + (w_gate is not None),
                            w_inner.dtype.itemsize, x.dtype.itemsize, rank,
                            tile_c, tile_f)
    c_pad = _pad_to(c, tc) - c
    tf_pad = _pad_to(f, tf) - f
    if c_pad:                                 # pad slots are empty (-> S)
        index = jnp.pad(index, ((0, 0), (0, 0), (0, c_pad)),
                        constant_values=s)
    w_inner, w_outer, w_gate, lora_params = _pad_f_operands(
        tf_pad, w_inner, w_outer, w_gate, lora_params)
    cp_, fp_ = c + c_pad, f + tf_pad
    nft = fp_ // tf
    gated = w_gate is not None
    use_lora = lora_params is not None
    grid = (b, g, cp_ // tc, nft)

    wi_spec = pl.BlockSpec((1, d, tf), lambda bi, gi, ci, fi, idx: (gi, 0, fi))
    wo_spec = pl.BlockSpec((1, tf, d), lambda bi, gi, ci, fi, idx: (gi, fi, 0))
    y_spec = pl.BlockSpec((1, 1, tc, d),
                          lambda bi, gi, ci, fi, idx: (bi, gi, ci, 0))
    inputs = [x.astype(jnp.float32).reshape(b, s, 1, d), w_inner]
    in_specs = [pl.BlockSpec(memory_space=pl.ANY), wi_spec]
    if gated:
        inputs.append(w_gate)
        in_specs.append(wi_spec)
    inputs.append(w_outer)
    in_specs.append(wo_spec)
    scratch = [vmem((tc, 1, d), jnp.float32), vmem((tc, d), jnp.float32)]
    if use_lora:
        li = lora_params["lora_inner"]
        r = li["b"].shape[-1]
        b_in_spec = pl.BlockSpec((d, r), lambda bi, gi, ci, fi, idx: (0, 0))
        c_in_spec = pl.BlockSpec((1, r, tf),
                                 lambda bi, gi, ci, fi, idx: (gi, 0, fi))
        inputs += [li["b"], li["c"]]
        in_specs += [b_in_spec, c_in_spec]
        if gated:
            lg = lora_params["lora_gate"]
            inputs += [lg["b"], lg["c"]]
            in_specs += [b_in_spec, c_in_spec]
        lo = lora_params["lora_outer"]
        b_out_spec = pl.BlockSpec((1, tf, r),
                                  lambda bi, gi, ci, fi, idx: (gi, fi, 0))
        c_out_spec = pl.BlockSpec((r, d), lambda bi, gi, ci, fi, idx: (0, 0))
        inputs += [lo["b"], lo["c"]]
        in_specs += [b_out_spec, c_out_spec]
        scratch.append(vmem((tc, r), jnp.float32))
    scratch.append(pltpu.SemaphoreType.DMA)
    kernel = _make_grouped_kernel(act, s, tc, nft, gated, use_lora,
                                  lora_scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
        out_specs=y_spec, scratch_shapes=scratch)
    y = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, g, cp_, d), x.dtype),
        interpret=interpret)(index.astype(jnp.int32), *inputs)
    return y[:, :, :c] if c_pad else y


# ----------------------------------------------------------------- decode
def _make_decode_kernel(act: str, n_active: int, nft: int, gated: bool,
                        use_lora: bool, scale: float):
    def kernel(*refs):
        i = 0
        ch_ref = refs[i]; i += 1                         # scalar prefetch
        gt_ref = refs[i]; i += 1                         # scalar prefetch
        x_ref = refs[i]; i += 1
        wi_ref = refs[i]; i += 1
        wg_ref = None
        if gated:
            wg_ref = refs[i]; i += 1
        wo_ref = refs[i]; i += 1
        li_b = li_c = lg_b = lg_c = lo_b = lo_c = None
        if use_lora:
            li_b = refs[i]; i += 1
            li_c = refs[i]; i += 1
            if gated:
                lg_b = refs[i]; i += 1
                lg_c = refs[i]; i += 1
            lo_b = refs[i]; i += 1
            lo_c = refs[i]; i += 1
        y_ref = refs[i]; i += 1
        acc_ref = refs[i]; i += 1
        hb_ref = refs[i] if use_lora else None

        bi = pl.program_id(0)
        gi = pl.program_id(1)
        fi = pl.program_id(2)

        @pl.when((gi == 0) & (fi == 0))
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        if hb_ref is not None:
            @pl.when(fi == 0)
            def _init_hb():
                hb_ref[...] = jnp.zeros_like(hb_ref)

        gt = gt_ref[bi, gi]
        x = x_ref[0].astype(jnp.float32)                 # (1, d)
        f32 = jnp.float32
        dot = lambda a, b: jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())), preferred_element_type=f32)
        up = dot(x, wi_ref[0].astype(f32))               # (1, Tf)
        if use_lora:
            xb = dot(x, li_b[...].astype(f32))           # (1, r)
            up = up + scale * dot(xb, li_c[0].astype(f32))
        if gated:
            gate = dot(x, wg_ref[0].astype(f32))
            if use_lora:
                xbg = dot(x, lg_b[...].astype(f32))
                gate = gate + scale * dot(xbg, lg_c[0].astype(f32))
            h = _ACTS[act](gate) * up
        else:
            h = _ACTS[act](up)
        acc_ref[...] += gt * dot(h, wo_ref[0].astype(f32))
        if use_lora:
            hb_ref[...] += dot(h, lo_b[0].astype(f32))   # (1, r)

            @pl.when(fi == nft - 1)
            def _lora_out():
                acc_ref[...] += gt * scale * jax.lax.dot_general(
                    hb_ref[...], lo_c[...].astype(f32),
                    (((1,), (0,)), ((), ())), preferred_element_type=f32)

        @pl.when((gi == n_active - 1) & (fi == nft - 1))
        def _finish():
            y_ref[0] = acc_ref[...].astype(y_ref.dtype)

    return kernel


def decode_ffn_kernel(x: jax.Array, choice: jax.Array, gate: jax.Array,
                      w_inner: jax.Array, w_outer: jax.Array,
                      w_gate: Optional[jax.Array] = None,
                      lora_params: Optional[dict] = None,
                      lora_scale: float = 1.0, *,
                      act: str = "relu", tile_f: int = 256,
                      interpret: Optional[bool] = None) -> jax.Array:
    """Decode-shaped routed FFN: one token per sequence, no dispatch plan.

    x: (B, d); choice: (B, G') int32 top-G' group ids; gate: (B, G') f32
    per-choice output gates (ones when ungated).  choice and gate ride as
    scalar-prefetch operands: choice drives the weight-block BlockSpec
    index_maps (the "block gather"), gate scales each block's contribution
    inside the accumulator.  Returns y: (B, d) = sum over active blocks.
    """
    interpret = _resolve_interpret(interpret)
    b, d = x.shape
    _, n_active = choice.shape
    _, _, f = w_inner.shape
    rank = (lora_params["lora_inner"]["b"].shape[-1]
            if lora_params is not None else 0)
    _, tf = _grouped_tiles(1, f, d, 2 + (w_gate is not None),
                           w_inner.dtype.itemsize, x.dtype.itemsize, rank,
                           1, tile_f)
    tf_pad = _pad_to(f, tf) - f
    w_inner, w_outer, w_gate, lora_params = _pad_f_operands(
        tf_pad, w_inner, w_outer, w_gate, lora_params)
    nft = (f + tf_pad) // tf
    gated = w_gate is not None
    use_lora = lora_params is not None
    grid = (b, n_active, nft)

    # x and y ride as (B, 1, d): a (1, 1, d) block meets the TPU's
    # (8, 128)-or-full tiling rule, a (1, d) block of (B, d) does not
    x_spec = pl.BlockSpec((1, 1, d), lambda bi, gi, fi, ch, gt: (bi, 0, 0))
    wi_spec = pl.BlockSpec(
        (1, d, tf), lambda bi, gi, fi, ch, gt: (ch[bi, gi], 0, fi))
    wo_spec = pl.BlockSpec(
        (1, tf, d), lambda bi, gi, fi, ch, gt: (ch[bi, gi], fi, 0))
    y_spec = x_spec
    inputs = [x.reshape(b, 1, d), w_inner]
    in_specs = [x_spec, wi_spec]
    if gated:
        inputs.append(w_gate)
        in_specs.append(wi_spec)
    inputs.append(w_outer)
    in_specs.append(wo_spec)
    scratch = [vmem((1, d), jnp.float32)]
    if use_lora:
        li = lora_params["lora_inner"]
        r = li["b"].shape[-1]
        b_in_spec = pl.BlockSpec((d, r), lambda bi, gi, fi, ch, gt: (0, 0))
        c_in_spec = pl.BlockSpec(
            (1, r, tf), lambda bi, gi, fi, ch, gt: (ch[bi, gi], 0, fi))
        inputs += [li["b"], li["c"]]
        in_specs += [b_in_spec, c_in_spec]
        if gated:
            lg = lora_params["lora_gate"]
            inputs += [lg["b"], lg["c"]]
            in_specs += [b_in_spec, c_in_spec]
        lo = lora_params["lora_outer"]
        b_out_spec = pl.BlockSpec(
            (1, tf, r), lambda bi, gi, fi, ch, gt: (ch[bi, gi], fi, 0))
        c_out_spec = pl.BlockSpec((r, d), lambda bi, gi, fi, ch, gt: (0, 0))
        inputs += [lo["b"], lo["c"]]
        in_specs += [b_out_spec, c_out_spec]
        scratch.append(vmem((1, r), jnp.float32))
    kernel = _make_decode_kernel(act, n_active, nft, gated, use_lora,
                                 lora_scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=grid, in_specs=in_specs,
        out_specs=y_spec, scratch_shapes=scratch)
    y = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, d), x.dtype),
        interpret=interpret)(choice.astype(jnp.int32),
                             gate.astype(jnp.float32), *inputs)
    return y.reshape(b, d)
