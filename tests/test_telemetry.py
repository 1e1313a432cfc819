"""Serving telemetry tests: reservoir sampling, snapshot/legacy dict
parity, request lifecycle timelines per finish reason, Chrome-trace
schema + uid coverage, device-counter drains against a jnp oracle, and
bit-identical greedy streams across telemetry off/counters/trace.

Engines here are tiny (2 layers, d=64) and jit-compiled once per mode;
the counter oracle replays the decode loop through the model-level
``lm_decode_step(return_counters=True)`` path the compiled chunk calls.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.params import init_tree
from repro.models import transformer
from repro.serving import trace_export
from repro.serving.engine import (ArrivalSchedule, Engine, ManualClock,
                                  Request, ServeStats)
from repro.serving.telemetry import (MetricsSnapshot, Reservoir,
                                     TelemetryRecorder)
from repro.train.state import model_defs

MAX_LEN, GEN, CHUNK = 48, 6, 4


def _tiny_cfg(**spt):
    return dataclasses.replace(
        configs.get_smoke("qwen3-0.6b"), num_layers=2, d_model=64,
        num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
        vocab_size=256).with_spt(ffn_capacity_factor=8.0, **spt)


@pytest.fixture(scope="module")
def tiny_params():
    cfg = _tiny_cfg()
    return cfg, init_tree(model_defs(cfg), jax.random.PRNGKey(0))


def _prompts(cfg, lens, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=ln,
                         dtype=np.int32).tolist() for ln in lens]


# --------------------------------------------------------------- reservoir
def test_reservoir_deterministic_bounded_exact_mean():
    xs = np.random.default_rng(3).random(5000)
    a, b = Reservoir(cap=256, seed=7), Reservoir(cap=256, seed=7)
    a.extend(xs)
    b.extend(xs)
    assert a.values == b.values                 # same seed, same sample
    assert len(a) == 256 and a.n_seen == 5000
    assert a.mean == pytest.approx(float(xs.mean()), rel=1e-12)
    # the retained sample is uniform over the stream: percentiles land
    # near the full-stream truth (256-sample error band)
    assert abs(a.percentile(50) - float(np.percentile(xs, 50))) < 0.12
    assert abs(a.percentile(99) - float(np.percentile(xs, 99))) < 0.12
    c = Reservoir(cap=256, seed=8)
    c.extend(xs)
    assert c.values != a.values                 # different seed, different

    short = Reservoir(cap=16, seed=0)
    short.extend([1.0, 2.0, 3.0])
    assert list(short) == [1.0, 2.0, 3.0] and bool(short)
    assert not Reservoir(cap=4, seed=0)
    assert Reservoir(cap=4, seed=0).percentile(50) == 0.0


# ------------------------------------------------------ snapshot / as_dict
def test_snapshot_matches_legacy_as_dict_exactly():
    st = ServeStats(page_size=16, kv_pages_total=12)
    st.prefill_s, st.decode_s = 0.1234567, 2.5
    st.prefill_tokens, st.decode_tokens, st.decode_steps = 100, 50, 10
    st.admitted, st.completed, st.prefill_batches = 6, 6, 3
    st.prefill_rows = 8
    st.ttft_samples.extend([0.01, 0.02, 0.05])
    st.ttft_s_sum, st.ttft_s_max = 0.08, 0.05
    st.tpot_samples.extend([0.001, 0.002])
    st.preemptions, st.rejections, st.cancelled, st.shed = 1, 2, 1, 1
    st.kv_pages_peak, st.admission_stalls = 9, 4
    d = st.as_dict()
    assert list(d) == list(ServeStats.LEGACY_ORDER)
    assert d["ttft_avg_s"] == round(0.08 / 3, 4)
    assert d["ttft_max_s"] == 0.05
    # no paging -> the paged tail keys are absent, order still legacy
    d2 = ServeStats().as_dict()
    assert list(d2) == [k for k in ServeStats.LEGACY_ORDER
                        if k not in ("page_size", "kv_pages_total",
                                     "kv_pages_peak", "admission_stalls")]
    # device aggregates (telemetry on) append AFTER the legacy keys
    st.device.update({"keep_rate": 0.5, "expert_load_imbalance": 1.2})
    d3 = st.as_dict()
    assert list(d3)[:len(ServeStats.LEGACY_ORDER)] == \
        list(ServeStats.LEGACY_ORDER)
    assert list(d3)[len(ServeStats.LEGACY_ORDER):] == [
        "expert_load_imbalance", "keep_rate"]

    flat = MetricsSnapshot(histograms={"ttft": {"p50_s": 1.0}}).flat()
    assert flat == {"ttft_p50_s": 1.0}


# ----------------------------------------------------- recorder micro-unit
def test_drain_counters_micro_oracle():
    rec = TelemetryRecorder(mode="counters")
    assert not rec.trace
    rec.drain_counters({
        "tel_attn_kept": np.array([[3.0, 4.0], [1.0, 2.0]]),
        "tel_attn_elig": np.array([[6.0, 8.0], [2.0, 4.0]]),
        "tel_expert_load": np.array([[[1.0, 0.0], [2.0, 1.0]]]),
        "tel_expert_drop": np.array([2.0]),
        "decode_tokens": np.array(7.0),
    })
    rec.drain_counters({"tel_expert_load": np.array([[[0.0, 3.0]]])})
    assert rec.attn_kept == 10.0 and rec.attn_elig == 20.0
    assert rec.expert_load_vector() == [3.0, 4.0]
    agg = rec.device_aggregates()
    assert agg["keep_rate"] == 0.5
    # max/mean over per-expert loads: max 4, mean 3.5
    assert agg["expert_load_imbalance"] == round(4.0 / 3.5, 3)
    assert agg["expert_tokens_routed"] == 7.0
    assert agg["expert_dropped"] == 2.0
    assert agg["counted_decode_tokens"] == 7.0
    assert rec.counter_drains == 2
    rec.drain_counters(None)                    # no-op, not a crash
    assert rec.counter_drains == 2

    # counters mode drops lifecycle recording entirely
    rec.event(1, "submit", 0.0)
    rec.span("x", 0.0, 1.0, 0)
    rec.gauge("g", 0.0, 1.0)
    assert not rec.timelines and not rec.spans and not rec.gauge_tracks


# ------------------------------------------- engine counters vs jnp oracle
def test_engine_counter_drain_matches_stepwise_oracle(tiny_params):
    """Seeded single-slot greedy run: the totals the compiled chunk
    accumulates in-carry (and the engine drains once per chunk) must
    equal a host-side replay through lm_prefill_ragged/lm_decode_step
    with return_counters=True — the jnp oracle for every tel_* key."""
    cfg, params = tiny_params
    ctel = cfg.with_spt(telemetry="counters")
    prompt = _prompts(cfg, [8])[0]
    eng = Engine(ctel, params, max_len=MAX_LEN, num_slots=1,
                 decode_chunk=CHUNK)
    eng.run([Request(uid=0, tokens=prompt, max_new_tokens=GEN)])
    rec = eng.last_recorder
    assert rec is not None and rec.counter_drains >= 2  # prefill + chunks

    # oracle prefill: the exact ragged call the engine made (bucket of 1)
    toks = np.zeros((1, 8), np.int32)
    toks[0] = prompt
    _, logits, telp = jax.jit(
        lambda p, b, ln: transformer.lm_prefill_ragged(
            p, ctel, b, ln, MAX_LEN, return_counters=True)
    )(params, {"tokens": jnp.asarray(toks)}, jnp.asarray([8], jnp.int32))
    telp = jax.device_get(telp)
    assert set(telp) == {"tel_expert_load", "tel_expert_drop"}

    # oracle decode: contiguous per-token replay with the engine's own
    # kv_valid construction (slot j live iff j <= pos)
    decode = jax.jit(
        lambda p, c, t, q, m: transformer.lm_decode_step(
            p, ctel, c, t, q, kv_valid=m, return_counters=True))
    caches, lg = jax.jit(
        lambda p, b: transformer.lm_prefill(p, ctel, b, max_len=MAX_LEN)
    )(params, {"tokens": jnp.asarray(toks)})
    tok = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)
    kept = elig = 0.0
    load = None
    for i in range(GEN - 1):
        pos = jnp.asarray([8 + i], jnp.int32)
        kv_valid = (jnp.arange(MAX_LEN, dtype=jnp.int32)[None, :]
                    <= pos[:, None])
        caches, lg, tel = decode(params, caches, tok, pos, kv_valid)
        tel = jax.device_get(tel)
        kept += float(np.array(tel["tel_attn_kept"]).sum())
        elig += float(np.array(tel["tel_attn_elig"]).sum())
        step_load = np.array(tel["tel_expert_load"], np.float64)
        step_load = step_load.reshape(-1, step_load.shape[-1]).sum(0)
        load = step_load if load is None else load + step_load
        tok = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)

    assert rec.attn_kept == kept
    assert rec.attn_elig == elig
    assert rec.counted_decode_tokens == GEN - 1
    prefill_load = np.array(telp["tel_expert_load"], np.float64)
    prefill_load = prefill_load.reshape(-1, prefill_load.shape[-1]).sum(0)
    np.testing.assert_array_equal(rec.expert_load, prefill_load + load)
    # greedy run, decode chunk compiled with no sampling counters
    assert rec.sampled_tokens == 0.0 and rec.pages_allocated == 0.0
    assert eng.last_stats.decode_tokens == GEN - 1


# ------------------------------------- bit-identical streams across modes
def test_streams_bit_identical_across_modes(tiny_params):
    cfg, params = tiny_params
    reqs = [Request(uid=i, tokens=t, max_new_tokens=GEN)
            for i, t in enumerate(_prompts(cfg, [8, 11, 6, 9]))]
    outs, dicts = {}, {}
    for mode in ("off", "counters", "trace"):
        eng = Engine(cfg.with_spt(telemetry=mode), params,
                     max_len=MAX_LEN, num_slots=2, decode_chunk=CHUNK)
        res = eng.run(list(reqs))
        outs[mode] = [(c.uid, c.tokens, c.finish_reason) for c in res]
        dicts[mode] = eng.last_stats.as_dict()
    assert outs["off"] == outs["counters"] == outs["trace"]
    # the off-mode dict is exactly the legacy key set — telemetry keys
    # appear only when telemetry is on
    assert set(dicts["off"]) <= set(ServeStats.LEGACY_ORDER)
    assert "keep_rate" in dicts["counters"]
    assert "keep_rate" in dicts["trace"]


# ----------------------------------------- lifecycle timelines + the trace
@pytest.fixture(scope="module")
def traced_run(tiny_params):
    """One deterministic serve() exercising every finish reason: uid 0 is
    force-preempted mid-stream and resumed, uid 1 sheds on a lapsed TTFT
    deadline, uid 2 is cancelled while queued, uid 3 retires normally,
    uid 99 (oversized, injected mid-run) is rejected."""
    cfg, params = tiny_params
    eng = Engine(cfg.with_spt(telemetry="trace"), params,
                 max_len=MAX_LEN, num_slots=1, decode_chunk=CHUNK)
    pr = _prompts(cfg, [8, 8, 8, 8])
    reqs = [Request(uid=0, tokens=pr[0], max_new_tokens=10),
            Request(uid=1, tokens=pr[1], max_new_tokens=4,
                    deadline_s=0.5),
            Request(uid=2, tokens=pr[2], max_new_tokens=4),
            Request(uid=3, tokens=pr[3], max_new_tokens=4)]
    fired = []

    def hook(e, iteration):
        if iteration == 2 and not fired:
            fired.append(iteration)
            assert e.preempt()                  # evicts uid 0 (active)
            assert e.cancel(2)                  # uid 2 still queued
            e.submit(Request(uid=99, tokens=[1] * 4,
                             max_new_tokens=MAX_LEN + 1))  # must reject
    out = eng.serve(ArrivalSchedule.burst(reqs), clock=ManualClock(dt=1.0),
                    on_iteration=hook)
    return eng, {c.uid: c for c in out}


def test_timelines_per_finish_reason(traced_run):
    eng, by_uid = traced_run
    rec = eng.last_recorder
    assert by_uid[0].finish_reason == "length"
    assert by_uid[1].finish_reason == "shed"
    assert by_uid[2].finish_reason == "cancelled"
    assert by_uid[3].finish_reason == "length"
    assert by_uid[99].finish_reason == "rejected"

    ev = {uid: [e["event"] for e in rec.timeline(uid)]
          for uid in (0, 1, 2, 3, 99)}
    assert ev[0][:4] == ["submit", "queued", "admitted", "first_token"]
    assert "preempted" in ev[0] and "resumed" in ev[0]
    assert ev[0].index("preempted") < ev[0].index("resumed")
    assert ev[0][-1] == "retired"
    assert ev[1] == ["submit", "queued", "shed"]
    assert ev[2] == ["submit", "queued", "cancelled"]
    assert ev[3] == ["submit", "queued", "admitted", "first_token",
                     "retired"]
    assert ev[99] == ["submit", "rejected"]
    # timestamps are monotone within each timeline
    for uid in (0, 1, 2, 3, 99):
        ts = [e["t"] for e in rec.timeline(uid)]
        assert ts == sorted(ts)
    # resumed tokens are never re-counted: the retired n_gen equals the
    # completion's token count
    retired0 = rec.timeline(0)[-1]
    assert retired0["n_gen"] == len(by_uid[0].tokens)


def test_chrome_trace_schema_and_uid_coverage(traced_run, tmp_path):
    eng, by_uid = traced_run
    rec = eng.last_recorder
    path = tmp_path / "trace.json"
    trace = trace_export.write_trace(rec, str(path))
    assert trace_export.validate_chrome_trace(trace) == []
    import json
    with open(path) as f:
        on_disk = json.load(f)
    assert trace_export.validate_chrome_trace(on_disk) == []
    # every submitted uid owns a request lane
    assert set(by_uid) <= trace_export.trace_uids(on_disk)
    names = {e["name"] for e in on_disk["traceEvents"]}
    # scheduler spans + derived request-phase spans are present
    assert {"decode_chunk", "prefill_batch", "queued", "generate",
            "queue_depth", "active_slots"} <= names
    # the preempted request's lane carries TWO queued waits (initial +
    # re-queued after eviction)
    q0 = [e for e in on_disk["traceEvents"]
          if e.get("tid") == 0 and e.get("pid") == trace_export.REQ_PID
          and e.get("name") == "queued" and e.get("ph") == "X"]
    assert len(q0) == 2

    # the validator actually rejects malformed events
    assert trace_export.validate_chrome_trace({"traceEvents": [
        {"ph": "Z", "pid": 1, "tid": 0, "name": "x", "ts": 0}]})
    assert trace_export.validate_chrome_trace({"traceEvents": [
        {"ph": "X", "pid": 1, "tid": 0, "name": "x", "ts": -1.0}]})
    assert trace_export.validate_chrome_trace(["not-a-dict"])


def test_watchdog_dump_on_invariant_failure(tiny_params, capsys):
    """A tripped invariant dumps the metrics snapshot and recent events
    before raising — the postmortem flight recorder."""
    from repro.serving.chaos import Watchdog
    cfg, params = tiny_params
    eng = Engine(cfg.with_spt(telemetry="trace"), params,
                 max_len=MAX_LEN, num_slots=1, decode_chunk=CHUNK)
    wd = Watchdog(dump_events=5)

    def corrupt_then_check(e, iteration):
        st = e._live
        if st.slot_item[0] is not None:
            st.active[0] = True
            st.slot_item[0] = None              # fabricate a slot leak
        wd(e, iteration)

    req = Request(uid=0, tokens=_prompts(cfg, [8])[0], max_new_tokens=10)
    with pytest.raises(AssertionError, match="slot leak"):
        eng.run([req], on_iteration=corrupt_then_check)
    eng._live = None                            # unwind the aborted run
    err = capsys.readouterr().err
    assert "WATCHDOG DUMP" in err and "violations" in err
