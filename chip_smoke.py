#!/usr/bin/env python
"""Chip smoke test: drive the SPT main path once on a TPU, at the published
shape of qwen3-0.6b (28 layers, d_model 1024, 16/8 heads, head_dim 128,
d_ff 3072, vocab 151,936), with random weights made from ``--seed``.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # 2x2 (data, model) fine-tuning only

Phases, in one process (no child process touches JAX), each printing one
JSON line; a phase that fails prints its error, the later phases still
run, and the script exits nonzero:

  finetune  ``Trainer`` (the ``launch/train.py`` path), variant "spt",
            Pallas kernels on: batch 4 x seq 1024, 3 steps.  Losses must
            be finite; prints the device's ``peak_bytes_in_use``.
  serve     ``Engine`` in the model's bfloat16 with sparse MHA + routed
            FFN on the Pallas kernels, paged KV (page 128), 8 slots: 16
            ragged requests (prompts 256-1024, 64 new tokens) through
            ``Engine.run`` twice (warm-up and steady), then a Poisson
            ``Engine.serve`` of six of them.  The compiled decode chunk
            must contain ``tpu_custom_call``.
  compare   the same requests in float32 under "highest" matmul precision
            on the kernels and on the jnp paths.  Both serve every request;
            their greedy streams are reported, not compared token by token:
            routing, capacity drops and PQ codes are discontinuous, and
            over 28 layers of random weights they turn the paths' 1e-6
            differences into logit differences of several units.  What is
            checked is each difference from a shared state: every layer's
            prefill output from the reference's input to that layer
            (within LAYER_RTOL), and one decode step of every row from the
            reference's prefill caches, whose greedy tokens must agree by
            the tie-aware rule of tests/test_sparse_decode.py.
  kernels   each main-path kernel against its jnp oracle at these widths,
            twice: float32 under "highest" and bfloat16 (the serving
            dtype) at default precision, to the interpret tests'
            tolerances.

The last line of stdout is ``{"ok": true, "device": {...}}``, printed only
when every phase passed on a TPU.  ``--chips 4`` runs the fine-tuning
phase on a 2x2 mesh and on one chip of it and compares the losses; kernels
stay off there (a Pallas call in a GSPMD-partitioned program is not
partitioned), on both sides of the comparison.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

from repro import configs  # noqa: E402
from repro.analysis.jaxpr_audit import (  # noqa: E402
    engine_chunk_args, pallas_call_count)
from repro.core import pq  # noqa: E402
from repro.core import routed_ffn as rf  # noqa: E402
from repro.core import sparse_attention as sa  # noqa: E402
from repro.core.params import init_tree  # noqa: E402
from repro.data.pipeline import DataConfig, synthetic_dataset  # noqa: E402
from repro.kernels.pq_quantize import ops as pq_ops  # noqa: E402
from repro.kernels.routed_ffn import ops as rffn_ops  # noqa: E402
from repro.kernels.sparse_attention import ops as sa_ops  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import attention, ffn, transformer  # noqa: E402
from repro.optim.adamw import OptimizerConfig  # noqa: E402
from repro.serving import kv_pages as kvp  # noqa: E402
from repro.serving.engine import ArrivalSchedule, Engine, Request  # noqa: E402
from repro.sharding import axis_rules, rules_for_mesh  # noqa: E402
from repro.train.state import model_defs  # noqa: E402
from repro.train.trainer import Trainer, TrainerConfig  # noqa: E402

ARCH = "qwen3-0.6b"
TIE_TOL = 1e-3           # logits: the near-tie of tests/test_sparse_decode.py
LAYER_RTOL = 1e-4        # one layer, kernel vs jnp from a shared input, f32
# The interpret tests' tolerances (tests/test_sparse_decode.py,
# test_kernels.py, test_routed_ffn_kernel.py), absolute plus relative.
KERNEL_TOL = {"float32": {"decode": 1e-5, "prefill": 2e-3, "ffn": 1e-4},
              "bfloat16": {"decode": 3e-2, "prefill": 3e-2, "ffn": 3e-2}}
LOSS_RTOL = 1e-2         # 2x2 mesh vs one chip, bf16 weights and activations


T0 = time.perf_counter()


def _emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def _note(what: str) -> None:
    """Progress on stderr, so a failed run shows how far it got."""
    print(f"chip_smoke [{time.perf_counter() - T0:7.1f} s] {what}",
          file=sys.stderr, flush=True)


# ------------------------------------------------------------- fine-tune
def finetune(cfg, *, seed: int, mesh_shape, devices, batch: int, seq: int,
             steps: int) -> dict:
    mesh = make_mesh(mesh_shape, ("data", "model"), devices=devices)
    rules = rules_for_mesh(mesh)
    data = list(synthetic_dataset(
        DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                   global_batch=batch, seed=seed), steps=steps + 1))
    with mesh, axis_rules(rules):
        t0 = time.perf_counter()
        trainer = Trainer(cfg, OptimizerConfig(total_steps=steps),
                          TrainerConfig(total_steps=steps, log_interval=1),
                          mesh=mesh, rules=rules, seed=seed)
        kernels = pallas_call_count(jax.make_jaxpr(
            lambda s, b: trainer._step(s, b))(trainer.state,
                                              trainer._place(data[0])))
        marks = [time.perf_counter()]      # step 1's time holds its compile
        report = trainer.run(iter(data), step_hook=lambda *_: marks.append(
            time.perf_counter()))
        wall = time.perf_counter() - t0
    losses = [m["loss"] for m in report["metrics"]]
    if len(losses) != steps or not all(np.isfinite(losses)):
        raise AssertionError(f"fine-tune losses not {steps} finite: {losses}")
    spread = max(len(a.sharding.device_set)
                 for a in jax.tree_util.tree_leaves(trainer.state))
    return {"losses": losses, "pallas_calls": kernels, "wall_s": wall,
            "step_wall_s": np.diff(marks).tolist(), "state_devices": spread,
            "mesh": list(mesh_shape)}


def finetune_phase(seed: int) -> dict:
    cfg = configs.apply_variant(configs.get_config(ARCH), "spt").with_spt(
        attn_impl="pallas", ffn_impl="pallas")
    out = finetune(cfg, seed=seed, mesh_shape=(1, 1),
                   devices=jax.devices()[:1], batch=4, seq=1024, steps=3)
    if out["pallas_calls"] == 0:
        raise AssertionError("the fine-tune step traced no Pallas kernel")
    stats = jax.devices()[0].memory_stats() or {}
    return {"phase": "finetune", **out,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}


def mesh_phase(seed: int) -> dict:
    """2x2 (data, model) fine-tuning vs the same steps on one chip."""
    devs = jax.devices()
    if len(devs) != 4:
        raise AssertionError(f"--chips 4 needs 4 devices, found {len(devs)}")
    cfg = configs.apply_variant(configs.get_config(ARCH), "spt").with_spt(
        attn_impl="sparse_masked", ffn_impl="grouped")
    kw = dict(seed=seed, batch=4, seq=1024, steps=3)
    four = finetune(cfg, mesh_shape=(2, 2), devices=devs, **kw)
    one = finetune(cfg, mesh_shape=(1, 1), devices=devs[:1], **kw)
    if four["state_devices"] != 4:
        raise AssertionError(f"state on {four['state_devices']} devices")
    if four["pallas_calls"] or one["pallas_calls"]:
        raise AssertionError("a Pallas kernel in the sharded fine-tune step")
    worst = max(abs(a - b) / abs(b)
                for a, b in zip(four["losses"], one["losses"]))
    if worst > LOSS_RTOL:
        raise AssertionError(f"2x2 losses {four['losses']} vs one chip "
                             f"{one['losses']}: rel diff {worst:.3e}")
    return {"phase": "finetune_2x2_vs_1", "losses_2x2": four["losses"],
            "losses_1": one["losses"], "max_rel_diff": worst,
            "rtol": LOSS_RTOL, "state_devices": four["state_devices"],
            "wall_s_2x2": four["wall_s"], "wall_s_1": one["wall_s"]}


# ----------------------------------------------------------------- serve
def make_requests(vocab: int, n: int, lo: int, hi: int, gen: int,
                  seed: int):
    rng = np.random.default_rng(seed)
    return [Request(uid=i, tokens=rng.integers(
        0, vocab, size=int(rng.integers(lo, hi + 1))).tolist(),
        max_new_tokens=gen) for i in range(n)]


def _check_done(done, gen: int) -> None:
    for c in done:
        if c.finish_reason != "length" or len(c.tokens) != gen:
            raise AssertionError(f"request {c.uid}: {c.finish_reason}, "
                                 f"{len(c.tokens)} tokens")


def _params(cfg, seed: int):
    """Random weights from ``seed``, in ``cfg.dtype``."""
    return jax.jit(lambda key: jax.tree_util.tree_map(
        lambda a: a.astype(cfg.dtype),
        init_tree(model_defs(cfg), key)))(jax.random.PRNGKey(seed))


def run_once(cfg, params, reqs, *, max_len: int, slots: int):
    """A fresh ``Engine`` serves ``reqs`` once; returns (engine, greedy
    streams, wall seconds, Pallas calls traced in its decode chunk)."""
    gen = reqs[0].max_new_tokens
    _note(f"serve: {jnp.dtype(cfg.dtype).name} "
          f"{cfg.spt.attn_impl}/{cfg.spt.ffn_impl}")
    eng = Engine(cfg, params, max_len=max_len, num_slots=slots,
                 decode_chunk=16)
    t0 = time.perf_counter()
    done = eng.run(reqs)
    wall = time.perf_counter() - t0
    _check_done(done, gen)
    chunk = eng._get_chunk(slots, gen, True, None)
    kernels = pallas_call_count(jax.make_jaxpr(chunk)(
        *engine_chunk_args(eng, slots, gen)))
    return eng, [c.tokens for c in done], wall, kernels


def serve_phase(reqs, *, max_len: int, slots: int, seed: int) -> dict:
    """The kernel path in the model's dtype: a warm-up and a steady run of
    ``reqs``, then a Poisson ``Engine.serve`` of six long-prompt requests.
    The compiled decode chunk must hold a Pallas kernel."""
    cfg = serve_config(configs.get_config(ARCH))
    params = _params(cfg, seed)
    gen = reqs[0].max_new_tokens
    eng, streams, warm_s, _ = run_once(cfg, params, reqs, max_len=max_len,
                                       slots=slots)
    record = {
        "dtype": jnp.dtype(cfg.dtype).name,
        "requests": len(streams),
        "tokens_generated": sum(map(len, streams)),
        "finish_reasons": ["length"],
        "prompt_tokens": sum(len(r.tokens) for r in reqs),
        "warmup_wall_s": warm_s,
    }
    _note("serve: decode chunk HLO")
    chunk = eng._get_chunk(slots, gen, True, None)
    hlo = chunk.lower(*engine_chunk_args(eng, slots, gen)).compile().as_text()
    record["decode_chunk_tpu_custom_call"] = "tpu_custom_call" in hlo
    if not record["decode_chunk_tpu_custom_call"]:
        raise AssertionError("decode chunk HLO has no tpu_custom_call")

    _note("serve: steady run")
    t0 = time.perf_counter()
    done = eng.run(reqs)
    record["steady_wall_s"] = time.perf_counter() - t0
    record["steady_decode_s"] = eng.last_stats.decode_s
    record["steady_prefill_s"] = eng.last_stats.prefill_s
    if [c.tokens for c in done] != streams:
        raise AssertionError("greedy streams differ between two runs")

    # Mid-run arrivals.  Prompts from the top length bucket keep the
    # admissions on prefill shapes (rows x 1024) the runs above compiled,
    # save the few-row buckets that staggered arrivals need.
    _note("serve: Poisson schedule")
    long = [r for r in reqs if len(r.tokens) > max_len // 2]
    sub = [dataclasses.replace(r, uid=100 + r.uid) for r in long[:6]]
    t0 = time.perf_counter()
    served = eng.serve(ArrivalSchedule.poisson(sub, rate_qps=4.0, seed=seed))
    record["poisson_wall_s"] = time.perf_counter() - t0
    _check_done(served, gen)
    record["poisson_requests"] = len(served)
    record["poisson_tokens"] = sum(len(c.tokens) for c in served)
    return {"phase": "serve", **record}


def serve_config(base):
    return base.with_spt(
        sparse_mha=True, routed_ffn=True, kv_layout="paged",
        kv_page_size=128, attn_impl="pallas", ffn_impl="pallas")


def jnp_config(cfg):
    return cfg.with_spt(attn_impl="sparse_masked", ffn_impl="grouped",
                        decode_attn_impl="jnp", decode_ffn_impl="jnp",
                        kv_paged_native="gather")


def _padded_prompts(reqs):
    """(rows, 2^k) right-padded prompt tokens and their lengths."""
    lens = np.array([len(r.tokens) for r in reqs], np.int32)
    toks = np.zeros((len(reqs), 1 << (int(lens.max()) - 1).bit_length()),
                    np.int32)
    for i, r in enumerate(reqs):
        toks[i, :lens[i]] = r.tokens
    return jnp.asarray(toks), jnp.asarray(lens)


def layerwise_prefill(cfg, ref, params, toks, lens, view: int) -> dict:
    """Each layer's ragged-prefill output under ``cfg`` and under ``ref``
    from the same input, the reference's output of the layer below, so a
    kernel's error is not amplified by the discontinuous choices of the
    layers above it.  Returns the largest |difference| / max |reference|
    of every layer."""
    (kind,) = cfg.pattern
    name = f"b0_{kind}"
    cache = jax.tree_util.tree_map(
        lambda a: a[0],
        transformer.init_caches(ref, toks.shape[0], view)["units"][name])

    def block(c):
        return jax.jit(lambda p, h, n: transformer.block_apply(
            p, h, c, kind, mode="prefill", cache=cache, pos=0,
            seq_lengths=n)[0])

    run, run_ref = block(cfg), block(ref)
    h = jax.jit(lambda p, t: transformer._embed_inputs(
        p, ref, {"tokens": t}))(params, toks)
    errs = []
    for i in range(cfg.num_layers):
        p = jax.tree_util.tree_map(lambda a: a[i], params["units"][name])
        got, h = run(p, h, lens), run_ref(p, h, lens)
        errs.append(float(jnp.max(jnp.abs(got - h)) / jnp.max(jnp.abs(h))))
    return {"layer_rel_err": errs, "max_layer_rel_err": max(errs)}


def shared_state_decode(cfg, ref, params, toks, lens, view: int) -> dict:
    """One decode step of every row under ``cfg`` and under ``ref`` from
    the reference's prefill caches, fed the reference's greedy token.
    Greedy tokens must agree, or sit within TIE_TOL of the reference's
    max logit (the tie-aware rule of tests/test_sparse_decode.py)."""
    caches, logits = jax.jit(lambda p, t, n: transformer.lm_prefill_ragged(
        p, ref, {"tokens": t}, n, view))(params, toks, lens)
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)

    def step(c):
        return np.asarray(jax.jit(lambda p, cc, t, n: transformer.lm_decode_step(
            p, c, cc, t, n)[1][:, -1])(params, caches, tok, lens))

    got, want = step(cfg), step(ref)
    pick = got.argmax(-1)
    gaps = want.max(-1) - want[np.arange(len(pick)), pick]
    return {"max_abs_logit_diff": float(np.abs(got - want).max()),
            "identical_tokens": int(np.sum(pick == want.argmax(-1))),
            "max_tie_gap": float(gaps.max()), "tie_tol": TIE_TOL}


def compare_phase(reqs, *, max_len: int, slots: int, seed: int) -> dict:
    """The same requests in float32 under "highest" precision on the
    kernels and on the jnp paths: both serve them all; the kernel path
    agrees with the reference from a shared state, layer by layer in
    prefill and by the tie-aware rule for a decode step."""
    cfg = dataclasses.replace(serve_config(configs.get_config(ARCH)),
                              dtype=jnp.float32)
    ref = jnp_config(cfg)
    kw = dict(max_len=max_len, slots=slots)
    with jax.default_matmul_precision("highest"):
        params = _params(cfg, seed)
        # [1:] lets each engine, and its KV pool, go at once
        streams, wall, kernels = run_once(cfg, params, reqs, **kw)[1:]
        streams_j, wall_j, kernels_j = run_once(ref, params, reqs, **kw)[1:]
        if not kernels or kernels_j:
            raise AssertionError(f"decode chunk Pallas calls: kernel path "
                                 f"{kernels}, jnp path {kernels_j}")
        _note("compare: layer by layer and one decode step, shared state")
        view = -(-max_len // cfg.spt.kv_page_size) * cfg.spt.kv_page_size
        toks, lens = _padded_prompts(reqs)
        cfg_c, ref_c = (c.with_spt(kv_layout="contiguous") for c in (cfg, ref))
        layers = layerwise_prefill(cfg_c, ref_c, params, toks, lens, view)
        step = shared_state_decode(cfg_c, ref_c, params, toks, lens, view)
    if layers["max_layer_rel_err"] > LAYER_RTOL:
        raise AssertionError(f"a prefill layer differs by more than "
                             f"{LAYER_RTOL}: {layers['layer_rel_err']}")
    if step["max_tie_gap"] > TIE_TOL:
        raise AssertionError(f"decode step from a shared state: greedy "
                             f"tokens differ beyond a near-tie: {step}")
    first = [next((t for t, (a, b) in enumerate(zip(g, w)) if a != b), None)
             for g, w in zip(streams, streams_j)]
    return {"phase": "compare_jnp", "dtype": "float32", "kernel_wall_s": wall,
            "jnp_wall_s": wall_j, "decode_chunk_pallas_calls": kernels,
            "jnp_decode_chunk_pallas_calls": kernels_j,
            "identical_rows": first.count(None), "rows": len(first),
            "first_divergence_step": first, "prefill": layers,
            "decode_step": step}


# --------------------------------------------------------------- kernels
def _close(name, got, want, tol) -> float:
    """Largest |got - want|; fails beyond tol + tol * |want|."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if not np.all(np.isfinite(got)):
        raise AssertionError(f"{name}: non-finite output")
    excess = float(np.max(np.abs(got - want) - tol * (1 + np.abs(want))))
    if excess > 0:
        raise AssertionError(f"{name}: |got-want| exceeds {tol} "
                             f"(+{tol} rel) by {excess:.3e}")
    return float(np.max(np.abs(got - want)))


def _jit(fn, *args):
    return jax.jit(fn)(*args)


def near_codewords(key, cb, shape):
    """Vectors of ``shape`` (last dim M * d') whose every sub-vector is a
    random codeword of its book plus noise of 1/20 of the codebooks'
    spread.  Nearest-codeword margins then lie far above float error, so
    kernel and oracle assign the same PQ codes and select the same keys:
    the comparison tests the kernels, not argmin near-ties."""
    m, e, dp = cb.shape
    kc, kn = jax.random.split(key)
    idx = jax.random.randint(kc, shape[:-1] + (m,), 0, e)
    sub = cb[jnp.arange(m), idx]                          # (..., M, d')
    noise = jax.random.normal(kn, sub.shape, jnp.float32)
    return (sub + 0.05 * jnp.std(cb) * noise).reshape(shape)


def kernel_checks(cfg, seed: int, dtype, *, batch: int = 8,
                  seq: int = 2048, pool: int = 160) -> dict:
    """Each main-path kernel vs its jnp oracle at ``cfg``'s widths, on
    ``dtype`` inputs: float32 under "highest" matmul precision, bfloat16
    (the serving dtype) at default precision; float32 oracles."""
    name = jnp.dtype(dtype).name
    tol = KERNEL_TOL[name]
    precision = "highest" if name == "float32" else "default"
    f32 = jnp.float32
    hq, hk, dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ps = cfg.spt.kv_page_size
    scfg = attention._sa_config(cfg)
    scale = dh ** -0.5
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    cb = init_tree(pq.param_defs(attention._pq_config(cfg)),
                   ks[0])["codebooks"].astype(f32)
    q = near_codewords(ks[1], cb, (batch, hq, 1, dh)).astype(dtype)
    k = near_codewords(ks[2], cb, (batch, hk, seq, dh)).astype(dtype)
    v = jax.random.normal(ks[3], (batch, hk, seq, dh), f32).astype(dtype)
    codes = pq.assign(k, cb).astype(jnp.int8)
    valid = jax.random.uniform(ks[4], (batch, seq)) < 0.9
    up = lambda x: x.astype(f32)
    out = {}
    with jax.default_matmul_precision(precision):
        want = _jit(lambda q, k, v, c, m: sa.sparse_mha_decode(
            up(q), up(k), up(v), c, cb, scfg, scale, m),
            q, k, v, codes, valid)
        for fuse in (True, False):
            got = _jit(lambda q, k, v, c, m, fuse=fuse:
                       sa_ops.sparse_mha_decode(q, k, v, c, cb, scfg, scale,
                                                m, fuse=fuse),
                       q, k, v, codes, valid)
            tier = "fused" if fuse else "two_pass"
            out["sparse_decode_" + tier] = _close(
                f"sparse decode ({tier})", got, want, tol["decode"])

        # paged pool: each slot's pages scattered over a shuffled pool
        mp = seq // ps
        perm = np.random.default_rng(seed).permutation(pool)
        pt = jnp.asarray(perm[:batch * mp].reshape(batch, mp), jnp.int32)

        def to_pool(x):                   # (B, Hk, S, .) -> (P, Hk, ps, .)
            pages = x.reshape(batch, hk, mp, ps, -1).transpose(0, 2, 1, 3, 4)
            pool_x = jnp.zeros((pool, hk, ps, x.shape[-1]), x.dtype)
            return pool_x.at[pt.reshape(-1)].set(
                pages.reshape(batch * mp, hk, ps, -1))

        kp, vp, cp = _jit(lambda *xs: [to_pool(x) for x in xs], k, v, codes)
        if not bool(jnp.all(kvp.gather_pages(kp, pt) == k)):
            raise AssertionError("paged pool fixture does not round-trip")
        got = _jit(lambda q, k, v, c, m, t: sa_ops.sparse_mha_decode_paged(
            q, k, v, c, cb, scfg, scale, m, t), q, kp, vp, cp, valid, pt)
        out["sparse_decode_paged"] = _close("paged sparse decode", got,
                                            want, tol["decode"])
        got = _jit(lambda q, k, v, m, t: sa_ops.dense_mha_decode_paged(
            q, k, v, scale, m, t), q, kp, vp, valid, pt)
        want_d = _jit(lambda q, k, v, m: sa.dense_attention(
            up(q), up(k), up(v), scale, causal=False, kv_valid=m, chunk_q=1),
            q, k, v, valid)
        out["dense_decode_paged"] = _close("paged dense decode", got,
                                           want_d, tol["decode"])

        # sparse prefill (the fine-tuning forward)
        s_pre = 1024
        qp = near_codewords(ks[5], cb, (1, hq, s_pre, dh)).astype(dtype)
        kp_, vp_ = k[:1, :, :s_pre], v[:1, :, :s_pre]
        got = _jit(lambda q, k, v: sa_ops.sparse_mha(
            q, k, v, cb, scfg, scale, causal=True)[0], qp, kp_, vp_)
        want_p = _jit(lambda q, k, v: sa.sparse_mha(
            up(q), up(k), up(v), cb, scfg, scale, causal=True)[0],
            qp, kp_, vp_)
        out["sparse_prefill"] = _close("sparse prefill", got, want_p,
                                       tol["prefill"])
        got = _jit(lambda k: pq_ops.pq_assign(k, cb), kp_)
        agree = float(jnp.mean(got == _jit(lambda k: pq.assign(up(k), cb),
                                           kp_)))
        if agree != 1.0:
            raise AssertionError(f"pq_assign agrees on {agree:.4%} only")
        out["pq_assign_agreement"] = agree

        # routed FFN: grouped (prefill) and decode kernels
        rcfg = ffn._routed_cfg(cfg)
        lc = cfg.spt.lora
        p = init_tree(rf.param_defs(rcfg, lc), ks[6])
        x = jax.random.normal(ks[7], (2, 1024, cfg.d_model), f32).astype(dtype)
        got = _jit(lambda p, x: rffn_ops.routed_ffn(x, p, rcfg, lc)[0], p, x)
        want_g = _jit(lambda p, x: rf.routed_ffn(
            up(x), jax.tree_util.tree_map(up, p), rcfg, lc,
            impl="grouped")[0], p, x)
        out["routed_ffn_grouped"] = _close("routed FFN grouped", got,
                                           want_g, tol["ffn"])
        xd = jnp.concatenate([x[:, :1]] * (batch // 2))
        got = _jit(lambda p, x: rffn_ops.routed_ffn_decode(
            x, p, rcfg, lc)[0], p, xd)
        want_d = _jit(lambda p, x: rf.routed_ffn(
            up(x), jax.tree_util.tree_map(up, p), rcfg, lc, impl="grouped",
            need_aux=False)[0], p, xd)
        out["routed_ffn_decode"] = _close("routed FFN decode", got, want_d,
                                          tol["ffn"])
    return {"phase": f"kernels_{name}", "precision": precision, "tol": tol,
            "max_abs_diff": out}


# ------------------------------------------------------------------ main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: fine-tuning on a 2x2 mesh vs one chip only")
    args = ap.parse_args(argv)

    dev = jax.devices()
    if dev[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 1

    cache = use_compile_cache()
    print(f"chip_smoke: compile cache at {cache}", file=sys.stderr)
    seed = args.seed
    # ragged prompts 256-1024 tokens, 64 new tokens each, 8 slots; max_len
    # leaves room for the 1024 prompt bucket
    reqs = make_requests(configs.get_config(ARCH).vocab_size, 16, 256, 1024,
                         64, seed)
    kw = dict(max_len=1024 + 64, slots=8, seed=seed)
    if args.chips == 4:
        phases = [("finetune_2x2_vs_1", lambda: mesh_phase(seed))]
    else:
        phases = [
            ("finetune", lambda: finetune_phase(seed)),
            ("serve", lambda: serve_phase(reqs, **kw)),
            ("compare_jnp", lambda: compare_phase(reqs, **kw)),
            ("kernels_float32", lambda: kernel_checks(
                configs.get_config(ARCH), seed, jnp.float32)),
            ("kernels_bfloat16", lambda: kernel_checks(
                configs.get_config(ARCH), seed, jnp.bfloat16))]
    failed = []
    for name, phase in phases:
        _note(name)
        try:
            _emit(phase())
        except Exception as e:  # noqa: BLE001 - reported, and fails the run
            traceback.print_exc()
            _emit({"phase": name, "error": f"{type(e).__name__}: {e}"[:2000]})
            failed.append(name)
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    _emit({"ok": True, "device": {"platform": dev[0].platform,
                                  "kind": dev[0].device_kind,
                                  "count": len(dev)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
