"""Production serving launcher: continuous batching over decode slots.

    python -m repro.launch.serve --arch qwen3-0.6b --smoke --requests 16 \
        --prompt-len 32 --gen 16

More requests than `--slots` stream through the engine's request queue;
finished slots are recycled for waiting prompts.  Timing is honest: the
first run is a warmup that absorbs jit tracing/compilation, the second run
is timed with `block_until_ready` at every prefill/decode boundary, and
prefill vs. steady-state decode tokens/s are reported separately.
"""
import argparse
import json
import sys
import time

import jax
import numpy as np

from repro import configs
from repro.core.params import init_tree
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.serving.engine import Engine, Request
from repro.sharding import axis_rules, rules_for_mesh
from repro.train.state import model_defs


def build_requests(cfg, num: int, prompt_len: int, gen: int,
                   ragged: bool, seed: int = 1, top_k: int = 0,
                   top_p: float = 0.0):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(num):
        ln = (int(rng.integers(max(4, prompt_len // 2), prompt_len + 1))
              if ragged else prompt_len)
        toks = rng.integers(0, cfg.vocab_size, size=ln, dtype=np.int32)
        fe = None
        if cfg.frontend:
            fe = rng.standard_normal(
                (cfg.frontend_tokens, cfg.d_model)).astype(np.float32)
        reqs.append(Request(uid=i, tokens=toks.tolist(),
                            max_new_tokens=gen, frontend_embeds=fe,
                            top_k=top_k, top_p=top_p))
    return reqs


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--slots", type=int, default=8,
                    help="decode slots (batch width); requests beyond this "
                         "queue and stream in as slots free up")
    ap.add_argument("--decode-chunk", type=int, default=16,
                    help="decode steps per compiled while_loop chunk")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="token id that retires a slot early")
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--ragged", action="store_true",
                    help="draw ragged prompt lengths in [L/2, L]")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--decode-impl", default="auto",
                    choices=("auto", "kernel", "jnp"),
                    help="sparse-MHA decode path: fused Pallas kernel vs "
                         "jnp fallback (auto follows spt.attn_impl; "
                         "REPRO_DISABLE_KERNELS=1 forces jnp)")
    ap.add_argument("--ffn-impl", default=None,
                    choices=("pallas", "grouped", "dense"),
                    help="routed-FFN train/prefill path: 'pallas' = fused "
                         "grouped-GEMM kernel with in-kernel dispatch; "
                         "default keeps the arch config's setting")
    ap.add_argument("--decode-ffn-impl", default="auto",
                    choices=("auto", "kernel", "jnp"),
                    help="routed-FFN decode path at (B, 1, d): block-gather "
                         "Pallas kernel (no dispatch buffer) vs the grouped "
                         "jnp capacity path (auto follows --ffn-impl; "
                         "REPRO_DISABLE_KERNELS=1 forces jnp)")
    ap.add_argument("--kv-layout", default="contiguous",
                    choices=("contiguous", "paged"),
                    help="serving KV-cache layout: 'paged' shares a pool of "
                         "fixed-size pages across slots (admission waits for "
                         "pages, not just a free slot) so long-context "
                         "max_len no longer reserves a full strip per slot")
    ap.add_argument("--page-size", type=int, default=128,
                    help="rows per KV page (paged layout)")
    ap.add_argument("--kv-pages", type=int, default=None,
                    help="page-pool size (default: contiguous-parity "
                         "slots*ceil(max_len/page_size); set lower to serve "
                         "under a fixed KV-memory budget)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k sampling truncation inside the compiled "
                         "decode chunk (0 = off; needs --temperature > 0)")
    ap.add_argument("--top-p", type=float, default=0.0,
                    help="nucleus sampling inside the compiled decode chunk"
                         " (keep the smallest probability mass >= p; 0 = "
                         "off; needs --temperature > 0)")
    ap.add_argument("--prefill-batch", type=int, default=None,
                    help="max queued requests drained per batched ragged "
                         "prefill call (default: --slots; 1 = the old "
                         "serial batch-1 admission)")
    ap.add_argument("--arrival-qps", type=float, default=None,
                    help="serve through the long-lived loop with seeded "
                         "Poisson arrivals at this offered rate instead of "
                         "one burst (engine.serve(); stats add p50/p99 "
                         "TTFT/TPOT, preemptions, shed)")
    ap.add_argument("--priorities", action="store_true",
                    help="phased priority workload: first half of the "
                         "requests are background (priority 0), second "
                         "half interactive (priority 1) — under page-pool "
                         "pressure the scheduler preempts backgrounds and "
                         "re-admits them by recompute")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="TTFT deadline for the interactive requests "
                         "(all requests without --priorities): a queued "
                         "request past its deadline is shed, and one past "
                         "half of it may preempt deadline-free peers")
    ap.add_argument("--prefill-decode-ratio", type=float, default=0.0,
                    help="overlap knob: with decodes in flight, admit at "
                         "most ratio * decode_chunk * active_slots prompt "
                         "tokens per scheduling iteration instead of "
                         "pausing decode until every free slot is filled "
                         "(0 = fill all free slots before each chunk)")
    ap.add_argument("--telemetry", default="off",
                    choices=("off", "counters", "trace"),
                    help="serving observability: 'counters' threads "
                         "jit-pure sparsity/expert/page counters through "
                         "the compiled chunk (drained once per scheduling "
                         "iteration); 'trace' adds per-request lifecycle "
                         "timelines and scheduler spans; outputs are "
                         "bit-identical across all three")
    ap.add_argument("--trace-out", default=None,
                    help="write a Perfetto-loadable Chrome trace.json of "
                         "the timed run here (implies --telemetry trace)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the final metrics snapshot (counters/"
                         "gauges/histograms) as JSON here")
    args = ap.parse_args()
    use_compile_cache()

    cfg = configs.get_smoke(args.arch) if args.smoke \
        else configs.get_config(args.arch)
    telemetry = "trace" if args.trace_out else args.telemetry
    cfg = cfg.with_spt(decode_attn_impl=args.decode_impl,
                       decode_ffn_impl=args.decode_ffn_impl,
                       kv_layout=args.kv_layout,
                       kv_page_size=args.page_size,
                       telemetry=telemetry)
    if args.ffn_impl is not None:
        cfg = cfg.with_spt(ffn_impl=args.ffn_impl)
    dp, tp = (int(x) for x in args.mesh.split("x"))
    mesh = make_mesh((dp, tp), ("data", "model"))
    rules = rules_for_mesh(mesh)
    with mesh, axis_rules(rules):
        params = init_tree(model_defs(cfg), jax.random.PRNGKey(0))
        engine = Engine(cfg, params,
                        max_len=args.prompt_len + args.gen + 8,
                        num_slots=args.slots, eos_id=args.eos_id,
                        decode_chunk=args.decode_chunk,
                        kv_pages=args.kv_pages,
                        prefill_batch=args.prefill_batch,
                        prefill_decode_ratio=args.prefill_decode_ratio)
        key = jax.random.PRNGKey(3) if args.temperature > 0 else None
        if cfg.family == "audio":
            return _serve_audio_legacy(cfg, engine, args, key)
        reqs = build_requests(cfg, args.requests, args.prompt_len, args.gen,
                              args.ragged, top_k=args.top_k,
                              top_p=args.top_p)
        # warmup: absorbs tracing + compilation for every shape in the run
        # (deadlines/priorities are applied AFTER it — a deadline shorter
        # than compile time would shed the very requests being traced)
        t0 = time.perf_counter()
        engine.run(reqs, temperature=args.temperature, key=key)
        warmup_wall_s = time.perf_counter() - t0
        if args.priorities or args.deadline_s is not None:
            import dataclasses as _dc
            half = len(reqs) // 2
            reqs = [_dc.replace(
                r,
                priority=(0 if args.priorities and i < half
                          else 1 if args.priorities else r.priority),
                deadline_s=(args.deadline_s
                            if (not args.priorities or i >= half)
                            else None))
                for i, r in enumerate(reqs)]

        # steady state: compiled throughout, synced at every boundary
        t0 = time.perf_counter()
        if args.arrival_qps is not None:
            from repro.serving.engine import ArrivalSchedule
            result = engine.serve(
                ArrivalSchedule.poisson(reqs, args.arrival_qps, seed=0),
                temperature=args.temperature, key=key)
        else:
            result = engine.run(reqs, temperature=args.temperature, key=key)
        wall_s = time.perf_counter() - t0
        stats = engine.last_stats
    out = {
        "arch": cfg.name,
        "requests": args.requests, "slots": args.slots,
        "generated_tokens": sum(len(c.tokens) for c in result),
        "warmup_wall_s": round(warmup_wall_s, 2),
        "steady_wall_s": round(wall_s, 2),
        **stats.as_dict(),
        "finish_reasons": sorted({c.finish_reason for c in result}),
        "sample": result[0].tokens[:8],
    }
    if args.trace_out:
        from repro.serving import trace_export
        trace = trace_export.write_trace(engine.last_recorder,
                                         args.trace_out)
        out["trace_out"] = args.trace_out
        out["trace_events"] = len(trace["traceEvents"])
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(stats.snapshot().as_dict(), f, indent=1)
        out["metrics_out"] = args.metrics_out
    print(json.dumps(out, indent=1))
    return 0


def _serve_audio_legacy(cfg, engine, args, key):
    """Enc-dec audio family: continuous batching does not cover it yet, so
    serve the fixed batch through the per-token path — still warmed up and
    timed honestly (generate() syncs on its host-side token lists)."""
    import jax.numpy as jnp
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (args.requests, args.prompt_len), 0,
        cfg.vocab_size, dtype=jnp.int32)}
    if cfg.frontend:
        batch["frontend_embeds"] = jax.random.normal(
            jax.random.PRNGKey(2),
            (args.requests, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16)
    engine.generate(batch, steps=args.gen, temperature=args.temperature,
                    key=key)                                      # warmup
    t0 = time.perf_counter()
    result = engine.generate(batch, steps=args.gen,
                             temperature=args.temperature, key=key)
    dt = time.perf_counter() - t0
    toks = args.requests * args.gen
    print(json.dumps({
        "arch": cfg.name, "mode": "legacy-audio",
        "requests": args.requests, "generated_tokens": toks,
        "steady_wall_s": round(dt, 2),
        "tokens_per_s": round(toks / dt, 1),
        "sample": result.tokens[0][:8],
    }, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
