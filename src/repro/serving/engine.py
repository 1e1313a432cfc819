"""Serving: prefill/decode step builders and a continuous-batching engine.

serve_step semantics for the dry-run cells:
  prefill_32k  — lower `prefill_step` over (B, S) prompts
  decode_32k / long_500k — lower `decode_step`: one new token per sequence
                 against a KV cache of seq_len (the cache is a donated input)

The `Engine` runs **continuous batching** over a fixed number of decode
slots (vLLM-style, in JAX):

  * requests queue up and are admitted into free slots as they open;
  * admission is **disaggregated and batched**: up to `prefill_batch`
    queued requests drain through ONE batched ragged prefill call — rows
    are right-padded to a joint (Bp, S) power-of-2 bucket (per-row lengths
    are threaded into sparse-MHA top-L budgets and routed-FFN/MoE dispatch
    capacities, so every row's output is identical to a batch-1
    exact-length prefill) and ALL resulting cache rows scatter into their
    slots in one jit call (one page allocation + one page-wise scatter in
    the paged layout) instead of one host round-trip per admission; the
    scatter replaces whole rows, which doubles as slot recycling.
    Non-right-paddable stacks (recurrent/SSM states, SWA rings) batch
    equal-length rows only;
  * with `prefill_decode_ratio > 0` the scheduler **overlaps** admission
    with decode: while decodes are in flight, each scheduling iteration
    admits at most ratio * decode_chunk * active_slots prompt tokens
    before running the next decode chunk, so a burst of arrivals no
    longer pauses every in-flight generation until the queue drains;
    `ServeStats` reports time-to-first-token and prefill-batch occupancy
    so the overlap is measurable;
  * admission never head-of-line-blocks on the page pool: a request whose
    worst case does not fit is counted as a stall and skipped, while
    later requests that do fit are admitted (the stalled one retries
    every iteration);
  * decode runs in jit-compiled `lax.while_loop` chunks with per-slot
    positions, so the whole generation traces ONCE instead of per token;
    the loop exits a chunk early when every slot has finished;
  * each decode step lowers through the fused Pallas kernel paths when
    the config selects them (`core/dispatch.py`): sparse-MHA decode
    attention, and the routed-FFN block-gather kernel — at (B, 1, d)
    the latter indexes weight blocks by the scalar-prefetched top-G'
    choices directly, so no (B, G, C, d) dispatch buffer is built and
    the router's softmax/load-balance aux is skipped (inference mode);
  * slots retire on EOS or on their per-request token budget, freeing the
    slot for the next queued request;
  * with `SPTConfig.kv_layout="paged"` the attention caches are pools of
    fixed-size pages shared across slots (serving/kv_pages.py): admission
    requires a free slot AND pages for the request's worst case, pages
    grow on demand *inside* the compiled chunk (pure allocator state in
    the while_loop carry), and retirement frees them — so short requests
    no longer pin max_len-sized strips and long-context max_len stops
    capping the slot count;
  * per-request sampling (Request.temperature / top_k / top_p nucleus
    truncation via a per-slot sorted cumsum) runs inside the chunk via
    per-slot arrays; greedy decoding remains the bit-identical default.

Timing is honest: prefill and decode are accumulated separately with
`block_until_ready` at each boundary, and reported via `ServeStats` so
callers can separate compile/warmup (first run) from steady state.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import dispatch as kdispatch
from repro.models import attention, encdec, ffn, transformer
from repro.serving import kv_pages as kvp
from repro.serving.telemetry import (MetricsSnapshot, Reservoir,
                                     TelemetryRecorder)


def build_prefill_step(cfg: ModelConfig, max_len: int) -> Callable:
    if cfg.family == "audio":
        def prefill(params, batch):
            return encdec.encdec_prefill(params, cfg, batch, max_len)
    else:
        def prefill(params, batch):
            return transformer.lm_prefill(params, cfg, batch, max_len)
    return prefill


def build_decode_step(cfg: ModelConfig) -> Callable:
    if cfg.family == "audio":
        def decode(params, caches, token, pos):
            return encdec.encdec_decode_step(params, cfg, caches, token, pos)
    else:
        def decode(params, caches, token, pos):
            return transformer.lm_decode_step(params, cfg, caches, token, pos)
    return decode


def abstract_decode_caches(cfg: ModelConfig, batch: int, cache_len: int,
                           kv_pages: Optional[int] = None):
    if cfg.family == "audio":
        fn = lambda: encdec.init_dec_caches(cfg, batch, cache_len,
                                            cfg.frontend_tokens)
    else:
        fn = lambda: transformer.init_caches(cfg, batch, cache_len,
                                             kv_pages=kv_pages)
    shapes = jax.eval_shape(fn)
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), shapes)


def decode_cache_axes(cfg: ModelConfig, kv_paged: bool = False):
    if cfg.family == "audio":
        return encdec.cache_axes(cfg)
    return transformer.cache_axes(cfg, kv_paged=kv_paged)


# Donated positions of the compiled decode chunk
# chunk(params, caches, page_table, astate, tok, pos, active, n, limit,
#       buf, keys, temps, topks, topps): everything the chunk returns
# updated with an identical aval — caches/page_table/astate plus the
# per-slot decode state (tok, pos, active, n_gen, buf).  The scheduler
# passes fresh device arrays from its numpy mirrors each call and copies
# the outputs back, so the donated buffers are never re-read on the
# host.  limit/keys/temps/topks/topps are read-only inputs (not chunk
# outputs) and must NOT be donated — XLA would warn and silently copy.
# analysis/liveness.py and analysis/donation.py key on this constant, so
# the audit and the jit site cannot drift apart.
CHUNK_DONATE_ARGNUMS = (1, 2, 3, 4, 5, 6, 7, 9)


# ---------------------------------------------------------------- arrivals
class ManualClock:
    """Deterministic serve clock: ``clock()`` reads virtual time, and the
    serve loop calls ``advance()`` once per scheduling iteration.  The
    chaos/robustness suites drive arrivals, deadlines, and preemption off
    this clock so every run is a pure function of the seed — no wall-clock
    flake.  Production serving uses the default wall clock instead."""

    def __init__(self, dt: float = 1.0):
        self.now = 0.0
        self.dt = float(dt)

    def __call__(self) -> float:
        return self.now

    def advance(self) -> None:
        self.now += self.dt


class ArrivalSchedule:
    """An arrival process feeding ``Engine.serve``: (t_s, Request) events
    in time order, popped as the serve clock passes each arrival time.

    Build one from a Poisson process (``poisson``), an explicit trace
    (``from_trace``), or an all-at-t=0 burst (``burst`` — equivalent to
    the legacy ``Engine.run`` workload)."""

    def __init__(self, events: Sequence[Tuple[float, Request]]):
        self._events = sorted(events, key=lambda e: e[0])      # stable
        self._i = 0

    @classmethod
    def burst(cls, requests: Sequence[Request],
              at: float = 0.0) -> "ArrivalSchedule":
        return cls([(at, r) for r in requests])

    @classmethod
    def poisson(cls, requests: Sequence[Request], rate_qps: float,
                seed: int = 0) -> "ArrivalSchedule":
        """Seeded Poisson arrivals at ``rate_qps`` mean offered load."""
        rng = np.random.default_rng(seed)
        t, events = 0.0, []
        for r in requests:
            t += float(rng.exponential(1.0 / max(rate_qps, 1e-9)))
            events.append((t, r))
        return cls(events)

    @classmethod
    def from_trace(cls, pairs: Sequence[Tuple[float, Request]]
                   ) -> "ArrivalSchedule":
        return cls(list(pairs))

    @property
    def exhausted(self) -> bool:
        return self._i >= len(self._events)

    def next_time(self) -> Optional[float]:
        return None if self.exhausted else self._events[self._i][0]

    def due(self, now: float) -> List[Request]:
        out = []
        while (self._i < len(self._events)
               and self._events[self._i][0] <= now):
            out.append(self._events[self._i][1])
            self._i += 1
        return out


# ---------------------------------------------------------------- requests
@dataclasses.dataclass
class Request:
    """One generation request for the continuous-batching engine."""
    uid: int
    tokens: Sequence[int]                  # prompt token ids
    max_new_tokens: int = 16
    frontend_embeds: Optional[Any] = None  # (F, d) for VLM-style frontends
    # per-request sampling (applied inside the compiled decode chunk):
    # temperature None = inherit run()'s temperature; <= 0 = greedy.
    # top_k 0 = no truncation; 1 = deterministic argmax sampling.
    # top_p in (0, 1) keeps the smallest nucleus with that much probability
    # mass (0 or >= 1 = off); composes with top_k (intersection).
    temperature: Optional[float] = None
    top_k: int = 0
    top_p: float = 0.0
    # long-lived serving (Engine.serve):
    # priority — higher admits first; under slot/page pressure a queued
    #   request may preempt a strictly-lower-priority running one.
    # deadline_s — TTFT target in serve-clock seconds after arrival; a
    #   queued request whose deadline lapses before its first token is
    #   shed (finish_reason="shed") instead of occupying the queue, and a
    #   deadline at >= 50% of its budget makes the request "urgent"
    #   (may preempt deadline-free peers of equal priority).
    # on_token — per-token streaming callback (uid, token_id, done),
    #   called from the host scheduler as tokens leave each decode chunk.
    priority: int = 0
    deadline_s: Optional[float] = None
    on_token: Optional[Callable[[int, int, bool], None]] = None


@dataclasses.dataclass
class Completion:
    uid: int
    tokens: List[int]                      # generated ids (EOS included)
    finish_reason: str     # "eos"|"length"|"rejected"|"cancelled"|"shed"
    prompt_len: int
    detail: str = ""                       # reject/shed reason, else ""
    preemptions: int = 0                   # evict+resume count for this uid


@dataclasses.dataclass
class ServeStats:
    """Wall-clock split of one `Engine.run` (block_until_ready-bounded)."""
    prefill_s: float = 0.0
    decode_s: float = 0.0
    prefill_tokens: int = 0                # prompt tokens processed
    decode_tokens: int = 0                 # tokens produced by decode steps
    decode_steps: int = 0                  # batch-wide while_loop trips
    admitted: int = 0
    completed: int = 0
    # disaggregated batched prefill
    prefill_batches: int = 0               # batched prefill calls issued
    ttft_s_sum: float = 0.0                # sum over admitted requests of
    ttft_s_max: float = 0.0                # (first token ready - run start)
    # long-lived serving (zeros for plain burst runs)
    submitted: int = 0                     # requests offered (incl. rejects)
    preemptions: int = 0                   # slot evictions under pressure
    rejections: int = 0                    # invalid requests isolated
    cancelled: int = 0                     # cancel() mid-queue/mid-stream
    shed: int = 0                          # TTFT deadline lapsed in queue
    # per-request latency samples: bounded reservoirs (Algorithm R,
    # deterministic seeds) so week-long serve() runs don't grow host
    # memory — the mean stays exact, percentiles carry sampling error
    # only past the cap (serving/telemetry.py)
    ttft_samples: Reservoir = dataclasses.field(
        default_factory=lambda: Reservoir(cap=2048, seed=17))
    tpot_samples: Reservoir = dataclasses.field(
        default_factory=lambda: Reservoir(cap=2048, seed=29))
    # paged KV cache (zeros when kv_layout="contiguous")
    page_size: int = 0
    kv_pages_total: int = 0                # pool capacity in pages
    kv_pages_peak: int = 0                 # peak pages in use
    admission_stalls: int = 0              # free slot but no pages
    # device-counter aggregates (keep_rate, expert_load_imbalance, ...)
    # merged in by the telemetry recorder — empty when telemetry is off,
    # so as_dict stays byte-identical to the pre-telemetry engine
    device: Dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def prefill_tok_s(self) -> float:
        return self.prefill_tokens / self.prefill_s if self.prefill_s else 0.0

    @property
    def decode_tok_s(self) -> float:
        return self.decode_tokens / self.decode_s if self.decode_s else 0.0

    @property
    def ttft_avg_s(self) -> float:
        """Mean time-to-first-token (the first token comes out of prefill,
        so this is prefill latency + any queueing behind earlier groups).
        Exact over every sample seen, not just the retained reservoir."""
        return self.ttft_samples.mean

    @staticmethod
    def _pctl(xs, q: float) -> float:
        vals = xs.values if isinstance(xs, Reservoir) else list(xs)
        return float(np.percentile(np.array(vals), q)) if vals else 0.0

    @property
    def ttft_p50_s(self) -> float:
        return self._pctl(self.ttft_samples, 50)

    @property
    def ttft_p99_s(self) -> float:
        return self._pctl(self.ttft_samples, 99)

    @property
    def tpot_p50_s(self) -> float:
        """Median per-request time-per-output-token (completion wall time
        after the first token, over tokens generated after it)."""
        return self._pctl(self.tpot_samples, 50)

    @property
    def tpot_p99_s(self) -> float:
        return self._pctl(self.tpot_samples, 99)

    @property
    def prefill_batch_occupancy(self) -> float:
        """Mean admitted rows per batched prefill call (1.0 == the old
        serial batch-1 admission)."""
        return (self.admitted / self.prefill_batches
                if self.prefill_batches else 0.0)

    # as_dict key order the benchmarks/tests have always consumed —
    # snapshot() keys not in this tuple (device-counter aggregates)
    # append after it, sorted
    LEGACY_ORDER = (
        "prefill_s", "decode_s", "prefill_tokens", "decode_tokens",
        "decode_steps", "prefill_tok_s", "decode_tok_s", "admitted",
        "completed", "prefill_batches", "prefill_batch_occupancy",
        "ttft_avg_s", "ttft_max_s", "ttft_p50_s", "ttft_p99_s",
        "tpot_p50_s", "tpot_p99_s", "preemptions", "rejections",
        "cancelled", "shed", "page_size", "kv_pages_total",
        "kv_pages_peak", "admission_stalls")

    def snapshot(self) -> MetricsSnapshot:
        """Point-in-time counters/gauges/histograms view — what as_dict
        flattens, what the chaos watchdog dumps on invariant failures."""
        counters: Dict[str, float] = {
            "prefill_tokens": self.prefill_tokens,
            "decode_tokens": self.decode_tokens,
            "decode_steps": self.decode_steps,
            "admitted": self.admitted, "completed": self.completed,
            "prefill_batches": self.prefill_batches,
            "preemptions": self.preemptions,
            "rejections": self.rejections,
            "cancelled": self.cancelled, "shed": self.shed}
        gauges: Dict[str, float] = {
            "prefill_s": round(self.prefill_s, 4),
            "decode_s": round(self.decode_s, 4),
            "prefill_tok_s": round(self.prefill_tok_s, 1),
            "decode_tok_s": round(self.decode_tok_s, 1),
            "prefill_batch_occupancy": round(
                self.prefill_batch_occupancy, 2)}
        if self.kv_pages_total:
            gauges.update(page_size=self.page_size,
                          kv_pages_total=self.kv_pages_total,
                          kv_pages_peak=self.kv_pages_peak,
                          admission_stalls=self.admission_stalls)
        hists = {
            "ttft": {"avg_s": round(self.ttft_avg_s, 4),
                     "max_s": round(self.ttft_s_max, 4),
                     "p50_s": round(self.ttft_p50_s, 4),
                     "p99_s": round(self.ttft_p99_s, 4)},
            "tpot": {"p50_s": round(self.tpot_p50_s, 5),
                     "p99_s": round(self.tpot_p99_s, 5)}}
        counters.update(self.device)
        return MetricsSnapshot(counters=counters, gauges=gauges,
                               histograms=hists,
                               legacy_order=self.LEGACY_ORDER)

    def as_dict(self) -> Dict[str, float]:
        return self.snapshot().as_dict()


@dataclasses.dataclass
class GenerationResult:
    tokens: List[List[int]]
    steps: int


# ------------------------------------------------------- scheduler state
@dataclasses.dataclass
class _QItem:
    """A request's live scheduling record: queued, running in a slot, or
    re-queued after preemption (``done`` holds the tokens generated before
    eviction; re-admission recomputes their KV via the batched ragged
    prefill and forces the last one as the resume token, so the stream
    continues bit-identically)."""
    req: Request
    order: int                             # submission order (stable key)
    arrival_s: float                       # serve-clock arrival time
    temp: float                            # resolved sampling temperature
    done: List[int] = dataclasses.field(default_factory=list)
    arrival_wall: float = 0.0              # wall clock at submit (TTFT base)
    first_tok_wall: Optional[float] = None
    preemptions: int = 0

    def prefill_tokens(self) -> List[int]:
        """Tokens to (re)compute through prefill: the prompt, plus — when
        resuming — every generated token except the last (which becomes
        the pending decode input, exactly like a fresh admission's
        prefill-sampled first token)."""
        if self.done:
            return list(self.req.tokens) + self.done[:-1]
        return list(self.req.tokens)


@dataclasses.dataclass
class _SchedState:
    """Mutable state of one serve()/run() — held on ``Engine._live`` so
    submit()/cancel()/preempt() and the chaos watchdog can reach it
    mid-loop."""
    stats: ServeStats
    clock: Callable[[], float]
    eos_id: Optional[int]
    greedy: bool
    use_topp: bool
    base_key: jax.Array
    max_gen: int
    caches: Any
    page_table: Any
    astate: Any
    reserved: int
    slot_ws: List[int]
    tok: Any
    pos: Any
    active: Any
    n_gen: Any
    limit: Any
    buf: Any
    keys: Any
    temps: Any
    topks: Any
    topps: Any
    slot_item: List[Optional[_QItem]]
    queue: List[_QItem]
    results: Dict[int, Completion]
    seen_uids: set
    default_temp: float
    order: int = 0
    iteration: int = 0
    t0_wall: float = 0.0


def _queue_key(it: _QItem) -> Tuple[int, int]:
    """Admission order: priority descending, then submission order (a
    preempted request keeps its original order, so it re-admits ahead of
    later arrivals of its priority class)."""
    return (-it.req.priority, it.order)


# ---------------------------------------------------------------- engine
class Engine:
    """Continuous-batching serving engine over `num_slots` decode slots.

    `run(requests)` is the native API (queue admission, EOS/budget exits,
    ragged prompts).  `generate(batch, steps)` keeps the legacy fixed-batch
    API used by the benchmarks and system tests; for greedy decoding it is
    routed through the slot engine, whose outputs are row-for-row identical
    to the old per-token Python loop.
    """

    def __init__(self, cfg: ModelConfig, params: dict, max_len: int = 512,
                 jit: bool = True, *, num_slots: int = 8,
                 eos_id: Optional[int] = None, decode_chunk: int = 16,
                 pad_id: int = 0, kv_pages: Optional[int] = None,
                 prefill_batch: Optional[int] = None,
                 prefill_decode_ratio: float = 0.0):
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.num_slots = num_slots
        self.eos_id = eos_id
        self.decode_chunk = max(1, decode_chunk)
        self.pad_id = pad_id
        self.last_stats: Optional[ServeStats] = None
        self._use_jit = jit
        # live scheduler state while serve()/run() is on the stack —
        # submit()/cancel()/preempt() and the chaos watchdog read it
        self._live: Optional[_SchedState] = None
        # observability (serving/telemetry.py): "off" adds nothing to the
        # compiled chunk (jaxpr.telemetry-cost audit); "counters" threads
        # the jit-pure device-counter tree through the chunk carry and
        # drains it once per scheduling iteration; "trace" additionally
        # records the host-side request/scheduler event timeline
        self._tel_mode = kdispatch.telemetry_mode(cfg)
        self._tel_counters = kdispatch.use_telemetry_counters(cfg)
        self.recorder: Optional[TelemetryRecorder] = None      # live run
        self.last_recorder: Optional[TelemetryRecorder] = None
        # disaggregated prefill scheduler: up to prefill_batch queued
        # requests drain through ONE batched ragged prefill call per
        # admission group (prefill_batch=1 == the old serial admission).
        # prefill_decode_ratio > 0 interleaves prefill micro-batches with
        # decode chunks instead of filling every free slot first: per
        # scheduling iteration at most ratio * decode_chunk * active_slots
        # prompt tokens are admitted while decodes are in flight (always
        # at least one request, so admission cannot starve); 0 = admit
        # greedily into all free slots before each decode chunk.
        self.prefill_batch = max(1, min(num_slots, num_slots
                                        if prefill_batch is None
                                        else prefill_batch))
        self.prefill_decode_ratio = max(0.0, prefill_decode_ratio)
        # paged KV cache: pool of kv_pages fixed-size pages shared across
        # slots (cfg.spt.kv_layout="paged"); kv_pages=None defaults to the
        # contiguous footprint — pass a smaller pool to serve under a
        # fixed cache-memory budget.
        self._paged = (kdispatch.use_paged_kv(cfg)
                       and transformer.paged_applicable(cfg))
        self.page_size = cfg.spt.kv_page_size if self._paged else 0
        if self._paged:
            self.max_pages_per_slot = kvp.num_pages(max_len, self.page_size)
            self.kv_pages = (num_slots * self.max_pages_per_slot
                             if kv_pages is None else int(kv_pages))
        else:
            self.kv_pages = 0
        # legacy per-token step fns (audio family + sampled generate())
        self._prefill = build_prefill_step(cfg, max_len)
        self._decode = build_decode_step(cfg)
        if jit:
            # no-donate: lm_prefill builds its caches in-jit (no
            # cache-sized operand); batch tokens alias nothing.
            self._prefill = jax.jit(self._prefill)
            self._decode = jax.jit(self._decode, donate_argnums=(1,))
        self._prefill_one: Optional[Callable] = None
        self._chunk_cache: Dict[Any, Callable] = {}
        if self._paged:
            def spt_kv_write(caches, rows, slots, page_table):
                return transformer.write_slot_caches_paged_rows(
                    caches, rows, slots, page_table, cfg)
            self._write_rows = (jax.jit(spt_kv_write, donate_argnums=(0,))
                                if jit else spt_kv_write)
            self._alloc_rows = (
                jax.jit(kvp.alloc_rows_pages, donate_argnums=(0, 1))
                if jit else kvp.alloc_rows_pages)
            self._free_slot = (
                jax.jit(kvp.free_slot_pages, donate_argnums=(0, 1))
                if jit else kvp.free_slot_pages)
        else:
            self._write_rows = (
                jax.jit(transformer.write_slot_caches_rows,
                        donate_argnums=(0,))
                if jit else transformer.write_slot_caches_rows)

    # ------------------------------------------------------------ prefill
    def _pad_invariant(self) -> bool:
        """True when right-padding alone (no per-row length threading)
        provably cannot change real-token outputs.  That requires: a
        pure-attention stack (padding corrupts recurrent states), no
        sliding-window ring cache (padding displaces real KV), dense
        attention (sparse MHA's top-L budget counts the padded keys), and
        dense FFN (routed-FFN/MoE capacity dispatch lets pad tokens compete
        with real ones for slots)."""
        cfg = self.cfg
        return (transformer.supports_ragged_prefill(cfg)
                and cfg.window is None
                and not transformer.length_sensitive(cfg))

    def _ragged_batchable(self) -> bool:
        """True when ragged rows may be right-padded to a common bucket:
        pure-attention stacks without a SWA ring (padding would displace
        real KV from the window-sized ring buffer).  Length-sensitive
        configs (sparse MHA / routed FFN / MoE) stay exact because
        lm_prefill_ragged threads the per-row lengths into selection
        budgets and dispatch capacities.  Everything else (rec/ssd states)
        batches equal-length rows only."""
        return (transformer.supports_ragged_prefill(self.cfg)
                and self.cfg.window is None)

    def _pad_len(self, n: int) -> int:
        """Prompt-length bucket: ragged-batchable configs pad right to a
        power of two (cache slots past the real length are invalidated),
        bounding jit retraces to O(log L); everything else prefills at
        exact length so outputs stay identical to the per-token
        reference."""
        n = max(1, n)
        if not self._ragged_batchable():
            return n
        p = 8
        while p < n:
            p <<= 1
        frontend = self.cfg.frontend_tokens if self.cfg.frontend else 0
        return max(n, min(p, self.max_len - frontend))

    @staticmethod
    def _pad_rows(n: int) -> int:
        """Row-count bucket (power of two), so the (Bp, S) prefill shapes
        stay O(log Bp * log S) and retraces stay bounded."""
        p = 1
        while p < n:
            p <<= 1
        return p

    def _get_prefill(self) -> Callable:
        if self._prefill_one is None:
            cfg, max_len = self.cfg, self.max_len
            tel_on = self._tel_counters

            def spt_prefill_ragged(params, batch, lengths):
                return transformer.lm_prefill_ragged(
                    params, cfg, batch, lengths, max_len,
                    return_counters=tel_on)
            # no-donate: ragged prefill also inits its cache rows in-jit;
            # tokens/lengths are read-only and alias no output.
            self._prefill_one = (jax.jit(spt_prefill_ragged)
                                 if self._use_jit else spt_prefill_ragged)
        return self._prefill_one

    def _prefill_group(self, group: Sequence["_QItem"]):
        """ONE batched ragged prefill over an admission group: rows are
        right-padded to a joint (Bp, S) bucket (dummy rows fill the Bp
        bucket; their results are discarded and their cache rows dropped
        by the scatter).  Resumed (preempted) rows prefill prompt +
        regenerated tokens — the recompute path.  Returns (cache_rows,
        logits (Bpb, 1, V), Bpb, tel-counter tree or None)."""
        cfg = self.cfg
        frontend = cfg.frontend_tokens if cfg.frontend else 0
        rows_toks = [it.prefill_tokens() for it in group]
        p = self._pad_len(max(len(t) for t in rows_toks))
        bpb = self._pad_rows(len(group))
        toks = np.full((bpb, p), self.pad_id, np.int32)
        lens = np.ones(bpb, np.int32)                  # dummies: length 1
        for i, t in enumerate(rows_toks):
            toks[i, :len(t)] = np.asarray(t, np.int32)
            lens[i] = len(t)
        batch = {"tokens": jnp.asarray(toks)}
        if frontend:
            fe = np.zeros((bpb, frontend, cfg.d_model), np.float32)
            for i, it in enumerate(group):
                fe[i] = np.asarray(it.req.frontend_embeds).reshape(
                    frontend, cfg.d_model)
            batch["frontend_embeds"] = jnp.asarray(fe)
        lengths = jnp.asarray(frontend + lens, jnp.int32)
        out = self._get_prefill()(self.params, batch, lengths)
        if self._tel_counters:
            rows, logits, tel = out
        else:
            (rows, logits), tel = out, None
        return rows, logits, bpb, tel

    # ------------------------------------------------------------- decode
    def _counter_shapes(self, slots: int) -> Dict[str, Any]:
        """Abstract tel_* counter tree ONE decode step emits for this
        engine's exact cache layout — derived via eval_shape of the same
        lm_decode_step call the compiled chunk makes, so the chunk carry's
        counter block never drifts from the model's emission."""
        cfg = self.cfg
        caches = abstract_decode_caches(
            cfg, slots, self.max_len,
            kv_pages=self.kv_pages if self._paged else None)
        tok = jax.ShapeDtypeStruct((slots,), jnp.int32)
        pos = jax.ShapeDtypeStruct((slots,), jnp.int32)
        if self._paged:
            view = kvp.view_len(self.max_len, self.page_size)
            kvv = jax.ShapeDtypeStruct((slots, view), jnp.bool_)
            pt = jax.ShapeDtypeStruct(
                (slots, self.max_pages_per_slot), jnp.int32)
            _, _, tel = jax.eval_shape(
                lambda p, c, t, q, m, g: transformer.lm_decode_step(
                    p, cfg, c, t, q, kv_valid=m, page_table=g,
                    return_counters=True),
                self.params, caches, tok, pos, kvv, pt)
        else:
            kvv = jax.ShapeDtypeStruct((slots, self.max_len), jnp.bool_)
            _, _, tel = jax.eval_shape(
                lambda p, c, t, q, m: transformer.lm_decode_step(
                    p, cfg, c, t, q, kv_valid=m, return_counters=True),
                self.params, caches, tok, pos, kvv)
        return tel

    def _get_chunk(self, slots: int, max_gen: int, greedy: bool,
                   eos_id: Optional[int], use_topp: bool = False
                   ) -> Callable:
        key = (slots, max_gen, greedy, eos_id, use_topp)
        fn = self._chunk_cache.get(key)
        if fn is not None:
            return fn
        cfg, chunk_steps = self.cfg, self.decode_chunk
        cache_len = self.max_len
        paged, ps = self._paged, self.page_size
        if paged:
            view = kvp.view_len(self.max_len, ps)
        # telemetry counters ride the while_loop carry (appended at the
        # tuple END so cond's c[0]/c[6] indexing is unchanged) and drain
        # to host ONCE per chunk; tel_off traces the byte-identical
        # pre-telemetry chunk (jaxpr.telemetry-cost audit)
        tel_on = self._tel_counters
        tel_shapes = self._counter_shapes(slots) if tel_on else {}

        def sample_fn(keys, n, lg, temps, topks, topps):
            """Per-slot temperature + top-k + top-p sampling; slots with
            temp <= 0 fall back to argmax (mixed batches share one compiled
            chunk).  Both truncations are computed on the temperature-
            scaled logits and intersected.  The nucleus pass only compiles
            in when some request in the run actually set top_p (use_topp is
            static in the chunk cache key) — runs without it pay nothing."""
            kb = jax.vmap(jax.random.fold_in)(keys, n)
            vocab = lg.shape[-1]

            def draw(k, l, tmp, tk, tp):
                scaled = l / jnp.maximum(tmp, 1e-6)
                srt = -jnp.sort(-scaled)                  # descending
                thr_k = srt[jnp.clip(tk - 1, 0, vocab - 1)]
                masked = jnp.where((tk > 0) & (scaled < thr_k),
                                   -jnp.inf, scaled)
                if use_topp:
                    # nucleus: smallest sorted prefix with mass >= tp (a
                    # token is kept iff the mass strictly before it is
                    # < tp, so the top-1 token always survives)
                    probs = jax.nn.softmax(srt)
                    cum = jnp.cumsum(probs)
                    kcnt = jnp.clip(jnp.sum(((cum - probs) < tp)
                                            .astype(jnp.int32)), 1, vocab)
                    thr_p = srt[kcnt - 1]
                    masked = jnp.where((tp > 0.0) & (tp < 1.0)
                                       & (scaled < thr_p), -jnp.inf, masked)
                return jax.random.categorical(k, masked).astype(jnp.int32)

            sampled = jax.vmap(draw)(kb, lg, temps, topks, topps)
            return jnp.where(temps > 0.0, sampled,
                             jnp.argmax(lg, axis=-1).astype(jnp.int32))

        def spt_decode_chunk(params, caches, page_table, astate, tok, pos,
                             active, n, limit, buf, keys, temps, topks,
                             topps):
            def cond(c):
                return (c[0] < chunk_steps) & jnp.any(c[6])

            def body(c):
                (t, caches, page_table, astate, tok, pos, active, n,
                 buf) = c[:9]
                ctr = c[9] if tel_on else None
                ok = None
                if paged:
                    # grow pages in-loop: a slot writing the first row of a
                    # new page pops one from the free list (admission
                    # reserved the worst case, so the pop cannot fail)
                    needs = active & (pos % ps == 0)
                    astate, pid, ok = kvp.alloc_masked(astate, needs)
                    bidx = jnp.arange(slots, dtype=jnp.int32)
                    pj = jnp.clip(pos // ps, 0, page_table.shape[1] - 1)
                    page_table = page_table.at[bidx, pj].set(
                        jnp.where(ok, pid, page_table[bidx, pj]))
                    caches = transformer.reset_page_slots(caches, cfg,
                                                          pid, ok)
                    # validity = engine positions AND page occupancy
                    kv_valid = (
                        (jnp.arange(view, dtype=jnp.int32)[None, :]
                         <= pos[:, None])
                        & kvp.occupancy(page_table, ps))
                    step_out = transformer.lm_decode_step(
                        params, cfg, caches, tok, pos, kv_valid=kv_valid,
                        page_table=page_table, return_counters=tel_on)
                else:
                    # slot validity from the engine's per-slot positions,
                    # built ONCE per step and shared by every attention
                    # layer (slots fill in position order, so slot j is
                    # live iff j <= pos; ring-buffer SWA layers recompute
                    # their own window mask)
                    kv_valid = (jnp.arange(cache_len,
                                           dtype=jnp.int32)[None, :]
                                <= pos[:, None])
                    step_out = transformer.lm_decode_step(
                        params, cfg, caches, tok, pos, kv_valid=kv_valid,
                        return_counters=tel_on)
                if tel_on:
                    caches, logits, tel = step_out
                else:
                    caches, logits = step_out
                    tel = None
                lg = logits[:, -1].astype(jnp.float32)          # (B, V)
                if greedy:
                    nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                else:
                    nxt = sample_fn(keys, n, lg, temps, topks, topps)
                if tel_on:
                    # accumulate this step's counters, weighting per-slot
                    # leaves by the CURRENT active mask (retired slots
                    # decode dead air inside a chunk — their counts would
                    # pollute keep-rate / expert-load aggregates)
                    amask = active.astype(jnp.float32)

                    def _acc(c0, v):
                        v = v.astype(jnp.float32)
                        if v.ndim >= 2 and v.shape[1] == slots:
                            w = amask.reshape(
                                (1, slots) + (1,) * (v.ndim - 2))
                            v = v * w
                        return c0 + v
                    ctr = dict(ctr)
                    for k, v in tel.items():
                        ctr[k] = _acc(ctr[k], v)
                    ctr["decode_tokens"] = ctr["decode_tokens"] + amask.sum()
                    if paged:
                        ctr["pages_allocated"] = (
                            ctr["pages_allocated"]
                            + ok.astype(jnp.float32).sum())
                    if not greedy:
                        ctr["sampled_tokens"] = (
                            ctr["sampled_tokens"]
                            + (active & (temps > 0.0))
                            .astype(jnp.float32).sum())
                bidx = jnp.arange(slots, dtype=jnp.int32)
                col = jnp.clip(n, 0, max_gen - 1)
                buf = buf.at[bidx, col].set(
                    jnp.where(active, nxt, buf[bidx, col]))
                step = active.astype(jnp.int32)
                n = n + step
                pos = pos + step
                done = n >= limit
                if eos_id is not None:
                    done |= nxt == eos_id
                tok = jnp.where(active, nxt, tok)
                active = active & ~done
                base = (t + 1, caches, page_table, astate, tok, pos,
                        active, n, buf)
                return base + ((ctr,) if tel_on else ())

            init = (jnp.zeros((), jnp.int32), caches, page_table, astate,
                    tok, pos, active, n, buf)
            if tel_on:
                ctr0 = {k: jnp.zeros(s.shape, jnp.float32)
                        for k, s in tel_shapes.items()}
                ctr0["decode_tokens"] = jnp.zeros((), jnp.float32)
                if paged:
                    ctr0["pages_allocated"] = jnp.zeros((), jnp.float32)
                if not greedy:
                    ctr0["sampled_tokens"] = jnp.zeros((), jnp.float32)
                init = init + (ctr0,)
            out = jax.lax.while_loop(cond, body, init)
            (t, caches, page_table, astate, tok, pos, active, n,
             buf) = out[:9]
            res = (caches, page_table, astate, tok, pos, active, n, buf, t)
            return res + ((out[9],) if tel_on else ())

        if self._use_jit:
            chunk = jax.jit(spt_decode_chunk,
                            donate_argnums=CHUNK_DONATE_ARGNUMS)
        else:
            chunk = spt_decode_chunk
        self._chunk_cache[key] = chunk
        return chunk

    # ---------------------------------------------------------- scheduler
    def _pages_ws(self, req: Request) -> int:
        """Worst-case pages ``req`` can ever hold: one per page of rows
        [0, prompt_end + max_new - 1) — the last decode write lands at
        position prompt_end + max_new - 2.  Identical for a resumed item
        (regenerated tokens refill the same decode rows)."""
        frontend = self.cfg.frontend_tokens if self.cfg.frontend else 0
        rows = frontend + len(req.tokens) + req.max_new_tokens - 1
        return kvp.num_pages(max(1, rows), self.page_size)

    def _validate(self, req: Request, seen: set) -> Optional[str]:
        """Reason ``req`` must be rejected, or None.  Failure isolation:
        a bad request becomes Completion(finish_reason="rejected") while
        the rest of the workload keeps serving (the pre-PR-8 engine
        raised ValueError and aborted every other request)."""
        cfg = self.cfg
        frontend = cfg.frontend_tokens if cfg.frontend else 0
        if req.uid in seen:
            return f"duplicate request uid {req.uid}"
        if req.max_new_tokens < 1:
            return "max_new_tokens < 1"
        if frontend and req.frontend_embeds is None:
            return (f"{cfg.name} has a {cfg.frontend} frontend; "
                    "frontend_embeds is required")
        need = frontend + len(req.tokens) + req.max_new_tokens
        if need > self.max_len:
            return f"needs {need} positions > max_len={self.max_len}"
        if self._paged and self._pages_ws(req) > self.kv_pages:
            return (f"needs {self._pages_ws(req)} KV pages > pool size "
                    f"{self.kv_pages}")
        return None

    # ------------------------------------------------- long-lived API
    def submit(self, req: Request, now: Optional[float] = None) -> bool:
        """Queue ``req`` into the live serve()/run() loop — callable from
        arrival schedules, chaos injectors, or streaming callbacks while
        the loop runs.  Returns False when the request is rejected; the
        rejection is a Completion in the results, never an exception."""
        st = self._live
        if st is None:
            raise RuntimeError("submit() requires a live serve()/run()")
        if now is None:
            now = st.clock()
        order = st.order
        st.order += 1
        st.stats.submitted += 1
        rec = self.recorder
        wall = time.perf_counter()
        if rec is not None:
            rec.event(req.uid, "submit", wall, prompt_len=len(req.tokens),
                      priority=req.priority)
        why = self._validate(req, st.seen_uids)
        if why is not None:
            st.stats.rejections += 1
            if rec is not None:
                rec.event(req.uid, "rejected", wall, detail=why)
            st.results[order] = Completion(
                uid=req.uid, tokens=[], finish_reason="rejected",
                prompt_len=len(req.tokens), detail=why)
            return False
        st.seen_uids.add(req.uid)
        if rec is not None:
            rec.event(req.uid, "queued", wall)
        temp = (st.default_temp if req.temperature is None
                else req.temperature)
        if (not st.greedy) and 0.0 < req.top_p < 1.0:
            st.use_topp = True
        self._grow_gen(req.max_new_tokens)
        st.queue.append(_QItem(req=req, order=order, arrival_s=now,
                               temp=temp,
                               arrival_wall=time.perf_counter()))
        st.queue.sort(key=_queue_key)
        return True

    def cancel(self, uid: int) -> bool:
        """Cancel a queued or in-flight request: frees its slot/pages and
        finishes it as Completion(finish_reason="cancelled") carrying the
        tokens generated so far.  False when the uid is not live."""
        st = self._live
        if st is None:
            return False
        rec = self.recorder
        for qi, it in enumerate(st.queue):
            if it.req.uid == uid:
                del st.queue[qi]
                st.stats.cancelled += 1
                if rec is not None:
                    rec.event(uid, "cancelled", time.perf_counter(),
                              detail="while queued")
                st.results[it.order] = Completion(
                    uid=uid, tokens=list(it.done),
                    finish_reason="cancelled",
                    prompt_len=len(it.req.tokens),
                    detail="cancelled while queued",
                    preemptions=it.preemptions)
                return True
        for b, it in enumerate(st.slot_item):
            if it is not None and it.req.uid == uid:
                st.stats.cancelled += 1
                if rec is not None:
                    rec.event(uid, "cancelled", time.perf_counter(),
                              detail="mid-stream",
                              n_gen=int(st.n_gen[b]))
                st.results[it.order] = Completion(
                    uid=uid, tokens=st.buf[b, :st.n_gen[b]].tolist(),
                    finish_reason="cancelled",
                    prompt_len=len(it.req.tokens),
                    detail="cancelled mid-stream",
                    preemptions=it.preemptions)
                self._release_slot(b)
                return True
        return False

    def preempt(self, uid: Optional[int] = None) -> bool:
        """Force-preempt an active request (chaos harness / external
        policy): saves its progress, frees its slot and pages, and
        re-queues it for recompute re-admission.  ``uid`` None picks the
        default victim (lowest priority, most recently admitted).
        Returns False when nothing matches."""
        st = self._live
        if st is None:
            return False
        if uid is None:
            b = self._pick_victim(None, False)
            if b is None:
                return False
            self._preempt_slot(b)
            return True
        for b, it in enumerate(st.slot_item):
            if it is not None and it.req.uid == uid and st.active[b]:
                self._preempt_slot(b)
                return True
        return False

    # ------------------------------------------------ slot-state plumbing
    def _grow_gen(self, need: int) -> None:
        """Grow the per-slot output buffer to a power-of-2 token-budget
        bucket, so chunk retraces stay O(log max_gen) as arrivals raise
        the budget mid-serve (burst run() presizes the exact maximum and
        never grows — the PR 5 trace behavior)."""
        st = self._live
        if need <= st.max_gen:
            return
        new = max(8, st.max_gen)
        while new < need:
            new <<= 1
        st.buf = np.pad(st.buf, ((0, 0), (0, new - st.buf.shape[1])))
        st.max_gen = new

    def _release_slot(self, b: int) -> None:
        """Return slot b to the free pool — retire, cancel, and preempt
        all land here: paged pages go back through the refcount-aware
        free path and the host-side worst-case reservation is dropped."""
        st = self._live
        st.slot_item[b] = None
        st.active[b] = False
        if self._paged:
            st.astate, st.page_table = self._free_slot(
                st.astate, st.page_table, jnp.int32(b))
            st.reserved -= st.slot_ws[b]
            st.slot_ws[b] = 0

    def _retire(self, b: int) -> None:
        st = self._live
        it = st.slot_item[b]
        toks = st.buf[b, :st.n_gen[b]].tolist()
        reason = ("eos" if st.eos_id is not None and toks
                  and toks[-1] == st.eos_id else "length")
        now_wall = time.perf_counter()
        if self.recorder is not None:
            self.recorder.event(it.req.uid, "retired", now_wall,
                                finish=reason, n_gen=int(st.n_gen[b]))
        if it.first_tok_wall is not None and int(st.n_gen[b]) > 1:
            st.stats.tpot_samples.append(
                (now_wall - it.first_tok_wall) / (int(st.n_gen[b]) - 1))
        st.results[it.order] = Completion(
            uid=it.req.uid, tokens=toks, finish_reason=reason,
            prompt_len=len(it.req.tokens), preemptions=it.preemptions)
        st.stats.completed += 1
        self._release_slot(b)

    def _track_peak(self) -> None:
        st = self._live
        if self._paged:
            used = self.kv_pages - int(jax.device_get(st.astate["top"]))
            st.stats.kv_pages_peak = max(st.stats.kv_pages_peak, used)
            if self.recorder is not None:
                self.recorder.gauge("kv_pages_used", time.perf_counter(),
                                    used)

    def _preempt_slot(self, b: int) -> None:
        """Evict slot b: save its generated tokens on the queue item,
        free its pages/slot, and re-queue it — re-admission recomputes
        the KV through the batched ragged prefill (prefill_tokens) and
        resumes the token stream bit-identically."""
        st = self._live
        it = st.slot_item[b]
        it.done = st.buf[b, :st.n_gen[b]].tolist()
        it.preemptions += 1
        st.stats.preemptions += 1
        if self.recorder is not None:
            self.recorder.event(it.req.uid, "preempted",
                                time.perf_counter(), slot=b,
                                n_gen=int(st.n_gen[b]))
        self._release_slot(b)
        st.queue.append(it)
        st.queue.sort(key=_queue_key)

    def _pick_victim(self, cand: Optional[_QItem],
                     urgent: bool) -> Optional[int]:
        """Lowest-priority, most-recently-admitted active slot that
        ``cand`` may evict: strictly lower priority, or — when cand's
        TTFT deadline is at risk (urgent) — a deadline-free peer of
        equal priority.  cand None (forced preemption) matches any
        active slot."""
        st = self._live
        best = None
        for b, it in enumerate(st.slot_item):
            if it is None or not st.active[b]:
                continue
            if cand is not None:
                lower = it.req.priority < cand.req.priority
                peer = (urgent and it.req.priority == cand.req.priority
                        and it.req.deadline_s is None)
                if not (lower or peer):
                    continue
            key = (it.req.priority, -it.order)
            if best is None or key < best[0]:
                best = (key, b)
        return None if best is None else best[1]

    def _shed_expired(self, now: float) -> None:
        """Drop queued requests whose TTFT deadline already lapsed — they
        cannot meet their SLO, so shedding them protects the requests
        that still can (resumed items already produced their first token
        and are never shed)."""
        st = self._live
        keep = []
        for it in st.queue:
            d = it.req.deadline_s
            if (d is not None and it.first_tok_wall is None
                    and now - it.arrival_s > d):
                st.stats.shed += 1
                if self.recorder is not None:
                    self.recorder.event(it.req.uid, "shed",
                                        time.perf_counter(),
                                        deadline_s=d)
                st.results[it.order] = Completion(
                    uid=it.req.uid, tokens=[], finish_reason="shed",
                    prompt_len=len(it.req.tokens),
                    detail=f"TTFT deadline {d}s lapsed in queue",
                    preemptions=it.preemptions)
            else:
                keep.append(it)
        st.queue = keep

    def _pressure_preempt(self, now: float) -> None:
        """Slot / page-pool pressure: when the head-of-queue request
        cannot fit, evict strictly-lower-priority victims (or, for a
        deadline-at-risk head, deadline-free equal-priority peers) until
        it fits or no eligible victim remains.  Uniform-priority burst
        workloads never trigger this, so run() stays bit-identical to
        the PR 5 scheduler."""
        st = self._live
        if not st.queue:
            return
        cand = st.queue[0]

        def blocked() -> bool:
            if not any(s is None for s in st.slot_item):
                return True
            return (self._paged and self._pages_ws(cand.req)
                    > self.kv_pages - st.reserved)

        d = cand.req.deadline_s
        urgent = (d is not None and cand.first_tok_wall is None
                  and now - cand.arrival_s >= 0.5 * d)
        guard = 0
        while blocked() and guard < self.num_slots:
            b = self._pick_victim(cand, urgent)
            if b is None:
                break
            self._preempt_slot(b)
            guard += 1
        if guard:
            # the eviction was FOR cand: re-queued victims of equal
            # priority carry an older submission order and would outrank
            # it at admission (starvation thrash — evict, re-admit the
            # victim, repeat until cand sheds), so cand keeps the head.
            st.queue.remove(cand)
            st.queue.insert(0, cand)

    # -------------------------------------------------- admission + decode
    def _form_group(self, stalled_seen: set) -> List[_QItem]:
        """Scan the queue IN ORDER (priority-major, then submission) for
        the next admission group: up to prefill_batch requests that have
        a free slot and (paged) a worst-case page reservation.  A request
        that does not fit the page pool is counted as a stall (once per
        scheduling iteration — ``stalled_seen`` dedups across the
        admission loop's passes) and SKIPPED — it must not
        head-of-line-block later rows that do fit; it retries every
        iteration and admits once retiring slots release their
        reservations.  Non-ragged-batchable stacks (rec/ssd states, SWA
        rings) group equal-length rows only (no right-padding).  With
        overlap enabled and decodes in flight, the group is bounded by
        the prefill token budget (always >= 1 request, so admission
        cannot starve)."""
        st = self._live
        free = sum(1 for s in st.slot_item if s is None)
        if not free or not st.queue:
            return []
        budget = None
        if self.prefill_decode_ratio > 0 and st.active.any():
            budget = max(1, int(self.prefill_decode_ratio
                                * self.decode_chunk
                                * int(st.active.sum())))
        ragged_ok = self._ragged_batchable()
        group: List[_QItem] = []
        picked: List[int] = []
        group_ws = group_tokens = 0
        for qi, it in enumerate(st.queue):
            if len(group) == min(free, self.prefill_batch):
                break
            ptoks = len(it.prefill_tokens())
            if (budget is not None and group
                    and group_tokens + ptoks > budget):
                break
            if (not ragged_ok and group
                    and ptoks != len(group[0].prefill_tokens())):
                continue
            if (self._paged
                    and self._pages_ws(it.req) > self.kv_pages
                    - st.reserved - group_ws):
                if it.req.uid not in stalled_seen:
                    stalled_seen.add(it.req.uid)
                    st.stats.admission_stalls += 1
                continue
            group.append(it)
            picked.append(qi)
            group_ws += self._pages_ws(it.req) if self._paged else 0
            group_tokens += ptoks
        for qi in reversed(picked):
            del st.queue[qi]
        return group

    def _stream(self, it: _QItem, toks: Sequence[int], done: bool) -> None:
        cb = it.req.on_token
        if cb is None:
            return
        for j, t in enumerate(toks):
            cb(it.req.uid, int(t), done and j == len(toks) - 1)

    def _admit(self, group: List[_QItem]) -> None:
        """ONE batched prefill + ONE jit scatter (and, paged, ONE page
        allocation) admits the whole group — the serial engine paid a
        host round-trip per request.  Resumed (preempted) rows force
        their last generated token as the pending decode input instead
        of sampling from the prefill logits."""
        st = self._live
        cfg = self.cfg
        frontend = cfg.frontend_tokens if cfg.frontend else 0
        ps = self.page_size
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("spt.serve.prefill_batch"):
            rows, logits, bpb, tel = self._prefill_group(group)
            slot_vec = np.full(bpb, -1, np.int32)   # -1 rows: dummies, drop
            assigned: List[int] = []
            for i, it in enumerate(group):
                b = next(j for j, s in enumerate(st.slot_item) if s is None)
                st.slot_item[b] = it
                assigned.append(b)
                slot_vec[i] = b
            if self._paged:
                npages = np.zeros(bpb, np.int32)
                for i, it in enumerate(group):
                    ws = self._pages_ws(it.req)
                    st.reserved += ws
                    st.slot_ws[assigned[i]] = ws
                    npages[i] = kvp.num_pages(
                        frontend + len(it.prefill_tokens()), ps)
                st.astate, st.page_table = self._alloc_rows(
                    st.astate, st.page_table, jnp.asarray(slot_vec),
                    jnp.asarray(npages))
                st.caches = self._write_rows(st.caches, rows,
                                             jnp.asarray(slot_vec),
                                             st.page_table)
            else:
                st.caches = self._write_rows(st.caches, rows,
                                             jnp.asarray(slot_vec))
            logits = jax.block_until_ready(logits)
            jax.block_until_ready(st.caches)
        now_wall = time.perf_counter()
        rec = self.recorder
        if rec is not None and tel is not None:
            # trim dummy bucket rows before folding: real rows are the
            # first len(group) of the Bpb padding bucket
            ng = len(group)
            rec.drain_counters({
                k: (v[:, :ng] if getattr(v, "ndim", 0) >= 2
                    and v.shape[1] == bpb else v)
                for k, v in jax.device_get(tel).items()})
        if rec is not None:
            rec.span("prefill_batch", t0, now_wall, st.iteration,
                     group=len(group), bucket_rows=bpb)
        st.stats.prefill_s += now_wall - t0
        st.stats.prefill_batches += 1
        st.stats.prefill_tokens += sum(
            len(it.prefill_tokens()) for it in group)
        st.stats.admitted += len(group)
        for i, it in enumerate(group):
            b = assigned[i]
            r = it.req
            skey = jax.random.fold_in(st.base_key, r.uid)
            st.keys[b] = np.asarray(skey, np.uint32)
            st.temps[b] = it.temp
            st.topks[b] = r.top_k
            st.topps[b] = r.top_p
            st.limit[b] = r.max_new_tokens
            st.buf[b] = 0
            if it.done:                         # resume after preemption
                nd = len(it.done)
                st.buf[b, :nd] = it.done
                st.tok[b] = it.done[-1]
                st.pos[b] = frontend + len(it.prefill_tokens())
                st.n_gen[b] = nd
                if rec is not None:
                    rec.event(r.uid, "resumed", now_wall, slot=b,
                              regenerated=nd)
                done_now = (nd >= r.max_new_tokens
                            or (st.eos_id is not None
                                and it.done[-1] == st.eos_id))
                st.active[b] = not done_now
                if done_now:
                    self._retire(b)
                continue
            if rec is not None:
                rec.event(r.uid, "admitted", now_wall, slot=b,
                          prompt_len=len(r.tokens))
            lg = np.asarray(logits[i, -1], np.float32)
            if st.greedy or it.temp <= 0.0:
                first = int(lg.argmax())
            else:
                scaled = lg / max(it.temp, 1e-6)
                if r.top_k > 0:
                    thr = np.sort(scaled)[::-1][
                        min(r.top_k, scaled.size) - 1]
                    scaled = np.where(scaled < thr, -np.inf, scaled)
                if 0.0 < r.top_p < 1.0:
                    srt = np.sort(lg / max(it.temp, 1e-6))[::-1]
                    e = np.exp(srt - srt[0])
                    probs = e / e.sum()
                    cum = np.cumsum(probs)
                    kcnt = max(1, int(((cum - probs) < r.top_p).sum()))
                    scaled = np.where(scaled < srt[kcnt - 1],
                                      -np.inf, scaled)
                first = int(jax.random.categorical(
                    jax.random.fold_in(skey, 0), jnp.asarray(scaled)))
            # TTFT is arrival-relative: for a burst every arrival_wall is
            # the serve start (the legacy semantics); under continuous
            # arrivals a late request is not charged for time it did not
            # wait.
            ttft = now_wall - it.arrival_wall
            st.stats.ttft_s_sum += ttft
            st.stats.ttft_s_max = max(st.stats.ttft_s_max, ttft)
            st.stats.ttft_samples.append(ttft)
            it.first_tok_wall = now_wall
            if rec is not None:
                rec.event(r.uid, "first_token", now_wall,
                          ttft_s=round(ttft, 6))
            st.tok[b] = first
            st.pos[b] = frontend + len(r.tokens)
            st.n_gen[b] = 1
            st.buf[b, 0] = first
            done_now = (r.max_new_tokens <= 1
                        or (st.eos_id is not None and first == st.eos_id))
            st.active[b] = not done_now
            self._stream(it, [first], done_now)
            if done_now:
                self._retire(b)

    def _decode_once(self) -> None:
        """One decode chunk (compiled once per shape bucket), then stream
        fresh tokens and retire slots that finished inside the chunk."""
        st = self._live
        chunk_fn = self._get_chunk(self.num_slots, st.max_gen, st.greedy,
                                   st.eos_id, st.use_topp)
        n_prev = st.n_gen.copy()
        # capture the pre-chunk active mask BEFORE handing the device
        # copies to the jit call: the chunk donates the slot-state
        # buffers (CHUNK_DONATE_ARGNUMS), so no donated mirror may be
        # read between the call and its reassignment below
        was_active = st.active.copy()
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("spt.serve.decode_chunk"):
            out = chunk_fn(self.params, st.caches, st.page_table, st.astate,
                           jnp.asarray(st.tok), jnp.asarray(st.pos),
                           jnp.asarray(st.active), jnp.asarray(st.n_gen),
                           jnp.asarray(st.limit), jnp.asarray(st.buf),
                           jnp.asarray(st.keys), jnp.asarray(st.temps),
                           jnp.asarray(st.topks), jnp.asarray(st.topps))
            out = jax.block_until_ready(out)
        (st.caches, st.page_table, st.astate, tok_d, pos_d, act_d, n_d,
         buf_d, steps) = out[:9]
        t1 = time.perf_counter()
        st.stats.decode_s += t1 - t0
        rec = self.recorder
        if rec is not None and self._tel_counters:
            # ONE host fetch per chunk, inside the already-synced region
            with jax.profiler.TraceAnnotation("spt.serve.drain"):
                rec.drain_counters(jax.device_get(out[9]))
            t2 = time.perf_counter()
            rec.span("drain", t1, t2, st.iteration)
        if rec is not None:
            rec.span("decode_chunk", t0, t1, st.iteration,
                     steps=int(steps),
                     active=int(np.array(act_d).sum()))
        self._track_peak()
        prev_total = int(n_prev.sum())
        # writable host mirrors (np.asarray of a jax array is read-only)
        st.tok = np.array(tok_d)
        st.pos = np.array(pos_d)
        act_new = np.array(act_d)
        st.n_gen = np.array(n_d)
        st.buf = np.array(buf_d)
        st.stats.decode_steps += int(steps)
        st.stats.decode_tokens += int(st.n_gen.sum()) - prev_total
        st.active = act_new
        for b in range(self.num_slots):
            it = st.slot_item[b]
            if it is None or not was_active[b]:
                continue
            finished = not act_new[b]
            fresh = st.buf[b, n_prev[b]:st.n_gen[b]]
            if len(fresh):
                self._stream(it, fresh.tolist(), finished)
            if finished:
                self._retire(b)

    # ------------------------------------------------------ loop drivers
    def _start(self, *, temperature, key, eos_id, clock, greedy,
               use_topp, max_gen) -> _SchedState:
        cfg = self.cfg
        if cfg.family == "audio":
            raise NotImplementedError(
                "continuous batching covers decoder-only LMs; use "
                "generate() for the enc-dec audio family")
        if self._live is not None:
            raise RuntimeError("engine already has a live serve()/run()")
        if eos_id == "engine-default":
            eos_id = self.eos_id
        slots = self.num_slots
        caches = transformer.init_caches(
            cfg, slots, self.max_len,
            kv_pages=self.kv_pages if self._paged else None)
        if self._paged:
            page_table = kvp.init_page_table(slots, self.max_pages_per_slot)
            astate = kvp.init_state(self.kv_pages)
        else:                   # inert placeholders riding the carry
            page_table = kvp.init_page_table(slots, 1)
            astate = kvp.init_state(1)
        t0 = time.perf_counter()
        st = _SchedState(
            stats=ServeStats(page_size=self.page_size,
                             kv_pages_total=self.kv_pages),
            clock=(clock if clock is not None
                   else (lambda: time.perf_counter() - t0)),
            eos_id=eos_id, greedy=greedy, use_topp=use_topp,
            base_key=key if key is not None else jax.random.PRNGKey(0),
            max_gen=max_gen,
            caches=caches, page_table=page_table, astate=astate,
            reserved=0, slot_ws=[0] * slots,
            tok=np.zeros(slots, np.int32),
            pos=np.zeros(slots, np.int32),
            active=np.zeros(slots, bool),
            n_gen=np.zeros(slots, np.int32),
            limit=np.ones(slots, np.int32),
            buf=np.zeros((slots, max(0, max_gen)), np.int32),
            keys=np.zeros((slots, 2), np.uint32),
            temps=np.zeros(slots, np.float32),
            topks=np.zeros(slots, np.int32),
            topps=np.zeros(slots, np.float32),
            slot_item=[None] * slots, queue=[], results={},
            seen_uids=set(), default_temp=temperature, t0_wall=t0)
        if self._tel_mode != "off":
            rec = TelemetryRecorder(
                mode=("trace" if self._tel_mode == "trace"
                      else "counters"),
                time_origin=t0)
            self.recorder = rec
            self.last_recorder = rec
        self._live = st
        return st

    def _iterate(self, schedule: Optional[ArrivalSchedule],
                 on_iteration: Optional[Callable]) -> bool:
        """One scheduling iteration: arrivals -> deadline shedding ->
        pressure preemption -> batched admission -> one decode chunk
        (streaming + retirement inside) -> the on_iteration hook (chaos
        injection / invariant watchdog).  Returns True when a decode
        chunk ran."""
        st = self._live
        rec = self.recorder
        now = st.clock()
        if schedule is not None:
            for r in schedule.due(now):
                self.submit(r, now=now)
        self._shed_expired(now)
        tp0 = time.perf_counter()
        pre_before = st.stats.preemptions
        with jax.profiler.TraceAnnotation("spt.serve.pressure_preempt"):
            self._pressure_preempt(now)
        if rec is not None and st.stats.preemptions > pre_before:
            rec.span("pressure_preempt", tp0, time.perf_counter(),
                     st.iteration,
                     evicted=st.stats.preemptions - pre_before)
        ta0 = time.perf_counter()
        admitted_before = st.stats.admitted
        stalled_seen: set = set()
        with jax.profiler.TraceAnnotation("spt.serve.admission"):
            while True:
                group = self._form_group(stalled_seen)
                if not group:
                    break
                self._admit(group)
                if self.prefill_decode_ratio > 0 and st.active.any():
                    break       # overlap: hand control back to decode
        if rec is not None and st.stats.admitted > admitted_before:
            rec.span("admission", ta0, time.perf_counter(), st.iteration,
                     admitted=st.stats.admitted - admitted_before,
                     stalled=len(stalled_seen))
        self._track_peak()
        stepped = False
        if st.active.any():
            self._decode_once()
            stepped = True
        if rec is not None:
            tg = time.perf_counter()
            rec.gauge("queue_depth", tg, len(st.queue))
            rec.gauge("active_slots", tg, int(st.active.sum()))
        st.iteration += 1
        if on_iteration is not None:
            on_iteration(self, st.iteration)
        if hasattr(st.clock, "advance"):
            st.clock.advance()
        return stepped

    def serve(self, schedule: ArrivalSchedule, *,
              temperature: float = 0.0, key: Optional[jax.Array] = None,
              eos_id: Any = "engine-default",
              clock: Optional[Callable[[], float]] = None,
              on_iteration: Optional[Callable] = None,
              _greedy: Optional[bool] = None,
              _use_topp: Optional[bool] = None,
              _max_gen: int = 0) -> List[Completion]:
        """Long-lived serving loop over an ``ArrivalSchedule``.

        Requests arrive mid-run per the schedule (plus any submit() from
        callbacks); the loop runs until the schedule is exhausted and
        every submitted request reached a terminal state (completed,
        rejected, cancelled, or shed).  Completions return in submission
        order; the wall-clock split is left in ``self.last_stats``.

        ``clock`` reads serve time in seconds (default: wall clock since
        serve start; pass a ManualClock for deterministic tests —
        arrivals and deadlines then advance per scheduling iteration).
        ``on_iteration(engine, i)`` fires after every scheduling
        iteration — the chaos-injection / invariant-watchdog hook.  The
        underscore knobs let run() pin the compiled-chunk bucket exactly
        as the PR 5 burst scheduler did."""
        greedy = (key is None) if _greedy is None else _greedy
        st = self._start(temperature=temperature, key=key, eos_id=eos_id,
                         clock=clock, greedy=greedy,
                         use_topp=bool(_use_topp), max_gen=_max_gen)
        try:
            while True:
                stepped = self._iterate(schedule, on_iteration)
                idle = (not stepped and not st.queue
                        and not st.active.any())
                if (schedule.exhausted and idle
                        and all(s is None for s in st.slot_item)):
                    break
                if idle and not schedule.exhausted:
                    nxt = schedule.next_time()
                    wait = (nxt - st.clock()) if nxt is not None else 0.0
                    if wait > 0 and not hasattr(st.clock, "advance"):
                        time.sleep(min(wait, 0.05))
        finally:
            rec = self.recorder
            if rec is not None:
                st.stats.device.update(rec.device_aggregates())
            self.last_stats = st.stats
            self.recorder = None        # last_recorder keeps the handle
            self._live = None
        return [st.results[i] for i in range(st.order)]

    def run(self, requests: Sequence[Request], *, temperature: float = 0.0,
            key: Optional[jax.Array] = None,
            eos_id: Any = "engine-default",
            on_iteration: Optional[Callable] = None) -> List[Completion]:
        """Serve a burst of `requests` (any count vs. `num_slots`) to
        completion — the one-shot API, now a burst-schedule wrapper over
        the long-lived loop (same admission order, chunking, and greedy
        outputs as the PR 5 scheduler).  Invalid requests (oversized,
        duplicate uid, missing frontend) finish as rejected Completions
        instead of raising.  Returns completions in request order;
        wall-clock split is left in `self.last_stats`."""
        eff = [(temperature if r.temperature is None else r.temperature)
               for r in requests]
        sampling = key is not None and any(t > 0.0 for t in eff)
        use_topp = sampling and any(0.0 < r.top_p < 1.0 for r in requests)
        max_gen = max([r.max_new_tokens for r in requests] + [1])
        return self.serve(ArrivalSchedule.burst(requests),
                          temperature=temperature, key=key, eos_id=eos_id,
                          on_iteration=on_iteration, _greedy=not sampling,
                          _use_topp=use_topp, _max_gen=max_gen)

    # ------------------------------------------------------------- legacy
    def generate(self, batch: Dict[str, jax.Array], steps: int,
                 temperature: float = 0.0,
                 key: Optional[jax.Array] = None) -> GenerationResult:
        """Fixed-batch generation (legacy API).  Greedy LM decoding runs on
        the continuous-batching engine; the enc-dec audio family,
        temperature sampling (its key schedule is batch-shaped and is
        preserved bit-for-bit), and rolling-cache workloads where
        prompt + steps exceed max_len keep the original per-token loop."""
        frontend = (self.cfg.frontend_tokens
                    if self.cfg.frontend and self.cfg.family != "audio" else 0)
        need = frontend + batch["tokens"].shape[1] + steps
        if (self.cfg.family == "audio"
                or (temperature > 0.0 and key is not None)
                or need > self.max_len):
            return self._generate_per_token(batch, steps, temperature, key)
        rows = np.asarray(batch["tokens"])
        fes = batch.get("frontend_embeds")
        reqs = [Request(uid=i, tokens=rows[i].tolist(), max_new_tokens=steps,
                        frontend_embeds=None if fes is None else fes[i])
                for i in range(rows.shape[0])]
        outs = self.run(reqs, temperature=0.0, eos_id=None)
        return GenerationResult(tokens=[c.tokens for c in outs], steps=steps)

    def _generate_per_token(self, batch, steps, temperature, key):
        caches, logits = self._prefill(self.params, batch)
        pos0 = batch["tokens"].shape[1]
        if self.cfg.frontend and self.cfg.family != "audio":
            pos0 += self.cfg.frontend_tokens
        outs = []
        tok = self._sample(logits[:, -1], temperature, key, 0)
        outs.append(tok)
        for t in range(1, steps):
            caches, logits = self._decode(
                self.params, caches, tok, jnp.asarray(pos0 + t - 1, jnp.int32))
            tok = self._sample(logits[:, -1], temperature, key, t)
            outs.append(tok)
        toks = jnp.stack(outs, axis=1)
        return GenerationResult(tokens=toks.tolist(), steps=steps)

    def _sample(self, logits, temperature, key, t):
        if temperature <= 0.0 or key is None:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        k = jax.random.fold_in(key, t)
        return jax.random.categorical(
            k, logits.astype(jnp.float32) / temperature, axis=-1
        ).astype(jnp.int32)
