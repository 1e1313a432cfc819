"""The analysis package's rules each fire on a deliberately-violating
fixture and stay quiet on a known-good one — so `python -m repro.analysis`
being green means the rules are alive, not vacuous.

Layer coverage: registry plumbing; lint (ast rules over synthetic
sources); jaxpr audits (dispatch buffer, cache repeat, byte budget,
forbidden primitives, accumulator dtype, kernel presence) on tiny traced
programs; pallas audits (VMEM budget, tile divisibility, scalar
prefetch) on toy pallas_calls traced but never run; trace guard (retrace
via weak-type flip, per-iteration jit rebuild) on tiny jitted fns.  The
full registry sweep over the real hot entrypoints is `slow` (ci_fast
runs the same sweep via scripts/analyze.sh anyway)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.analysis import jaxpr_audit as ja
from repro.analysis import lint
from repro.analysis import pallas_audit as pa
from repro.analysis import registry
from repro.analysis import trace_guard as tg


def rules(violations):
    return sorted({v.rule for v in violations})


# -------------------------------------------------------------- registry
def test_registry_rejects_duplicates_and_unknown_names():
    with pytest.raises(ValueError):
        registry.audit("lint")(lambda: [])
    with pytest.raises(KeyError):
        registry.run_audits(["no-such-audit"])


def test_run_audits_streams_reports():
    seen = []
    registry.run_audits(["lint"], report=lambda n, vs: seen.append(n))
    assert seen == ["lint"]


# ------------------------------------------------------------------ lint
def test_lint_jnp_repeat_fires_in_serving_only():
    src = "import jax.numpy as jnp\ny = jnp.repeat(x, 4, axis=1)\n"
    assert rules(lint.lint_source(src, "serving/foo.py")) \
        == ["lint.jnp-repeat"]
    assert rules(lint.lint_source(src, "models/foo.py")) \
        == ["lint.jnp-repeat"]
    # core/ keeps its documented jnp fallback oracles
    assert lint.lint_source(src, "core/foo.py") == []


def test_lint_host_sync_fires_in_hot_modules():
    src = ("import numpy as np\n"
           "n = int(count.item())\n"
           "a = np.asarray(dev)\n")
    vs = lint.lint_source(src, "models/foo.py")
    assert rules(vs) == ["lint.host-sync"] and len(vs) == 2
    # the engine host scheduler is exempt by design
    assert lint.lint_source(src, "serving/engine.py") == []


def test_lint_interpret_default_must_be_none():
    bad = "def kernel_op(x, interpret=True):\n    return x\n"
    good = ("def kernel_op(x, interpret=None):\n    return x\n"
            "def _forward(x, interpret):\n    return x\n")
    assert rules(lint.lint_source(bad, "kernels/foo/ops.py")) \
        == ["lint.interpret-default"]
    assert lint.lint_source(good, "kernels/foo/ops.py") == []
    # kw-only defaults are checked too
    bad_kw = "def kernel_op(x, *, interpret=False):\n    return x\n"
    assert rules(lint.lint_source(bad_kw, "kernels/foo/ops.py")) \
        == ["lint.interpret-default"]


def test_lint_dispatch_routing():
    assert rules(lint.lint_source(
        "from jax.experimental import pallas as pl\n",
        "models/foo.py")) == ["lint.dispatch-routing"]
    assert rules(lint.lint_source(
        "import os\nflag = os.environ.get('REPRO_DISABLE_KERNELS')\n",
        "serving/foo.py")) == ["lint.dispatch-routing"]
    assert lint.lint_source(
        "from repro.core import dispatch\nok = dispatch.kernels_disabled()\n",
        "serving/foo.py") == []


def test_lint_repo_tree_clean():
    assert lint.run_lint() == []


# ---------------------------------------------------------- jaxpr audits
def test_dispatch_buffer_rule_fires_on_capacity_path():
    """The jnp grouped path at decode shape DOES build (B, G, C, d)
    buffers — the rule must see them (this is the violating twin of the
    clean ops.routed_ffn_decode entrypoint)."""
    from repro.core import lora as lora_mod
    from repro.core import routed_ffn as rf
    from repro.core.params import init_tree
    lcfg = lora_mod.LoRAConfig(rank=4, alpha=4.0, enabled=False)
    rcfg = rf.RoutedFFNConfig(d_model=64, d_ff=128, num_groups=8,
                              active_groups=2, capacity_factor=4.0,
                              gated=True, activation="gelu")
    p = jax.eval_shape(lambda: init_tree(rf.param_defs(rcfg, lcfg),
                                         jax.random.PRNGKey(0)))
    x = jax.ShapeDtypeStruct((4, 1, 64), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda p, x: rf.routed_ffn(x, p, rcfg, lcfg, impl="grouped")[0]
    )(p, x)
    assert rules(ja.dispatch_buffer_violations(jaxpr, 4, 8)) \
        == ["jaxpr.dispatch-buffer"]


def test_cache_repeat_rule_fires_on_gqa_expansion():
    def bad(q, k):                        # expands the cache to Hq
        kx = jnp.repeat(k, 4, axis=1)     # (B, Hk, S, d) -> (B, Hq, S, d)
        return jnp.einsum("bhqd,bhsd->bhqs", q, kx)

    jaxpr = jax.make_jaxpr(bad)(
        jax.ShapeDtypeStruct((2, 8, 1, 16), jnp.float32),
        jax.ShapeDtypeStruct((2, 2, 64, 16), jnp.float32))
    assert "jaxpr.cache-repeat" in rules(
        ja.cache_repeat_violations(jaxpr, num_q_heads=8, num_kv_heads=2,
                                   min_seq=64))
    # Hq == Hk (no GQA): nothing to expand, rule is inert
    assert ja.cache_repeat_violations(jaxpr, 8, 8, 64) == []


def test_intermediate_budget_rule_fires_on_big_broadcast():
    def bad(x):                           # materializes 4 MiB from 4 KiB
        return jnp.broadcast_to(x[:, None], (1024, 1024)) * 2.0

    jaxpr = jax.make_jaxpr(bad)(jax.ShapeDtypeStruct((1024,), jnp.float32))
    assert rules(ja.big_intermediate_violations(jaxpr, max_bytes=65536)) \
        == ["jaxpr.intermediate-budget"]
    assert ja.big_intermediate_violations(jaxpr, max_bytes=1 << 24) == []


def test_forbidden_primitive_rule_fires_on_debug_print():
    def bad(x):
        jax.debug.print("x={x}", x=x)
        return x * 2

    jaxpr = jax.make_jaxpr(bad)(jnp.ones(3))
    assert rules(ja.forbidden_primitive_violations(jaxpr)) \
        == ["jaxpr.forbidden-primitive"]


def test_kernel_count_rules():
    jaxpr = jax.make_jaxpr(lambda x: x + 1)(jnp.ones(3))
    assert rules(ja.kernel_count_violations(jaxpr, "e", "some")) \
        == ["jaxpr.kernel-missing"]
    assert ja.kernel_count_violations(jaxpr, "e", "none") == []
    assert rules(ja.kernel_count_violations(jaxpr, "e", "exact", exact=2)) \
        == ["jaxpr.kernel-missing"]


def _toy_pallas(block_shape, array_shape, dtype=jnp.float32,
                compute=None):
    """A minimal copy kernel traced (never run) for audit fixtures."""
    def kernel(x_ref, o_ref):
        val = x_ref[...]
        o_ref[...] = compute(val) if compute else val

    grid = tuple(-(-a // b) for a, b in zip(array_shape, block_shape))
    spec = pl.BlockSpec(block_shape, lambda i, j: (i, j))
    fn = pl.pallas_call(
        kernel, grid=grid, in_specs=[spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(array_shape, dtype),
        interpret=True)
    return jax.make_jaxpr(fn)(jax.ShapeDtypeStruct(array_shape, dtype))


def _toy_pallas3(block_shape, array_shape):
    """3-D twin of ``_toy_pallas`` (grid over the leading two dims)."""
    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    grid = tuple(-(-a // b) for a, b in zip(array_shape[:2], block_shape))
    spec = pl.BlockSpec(block_shape, lambda i, j: (i, j, 0))
    fn = pl.pallas_call(
        kernel, grid=grid, in_specs=[spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(array_shape, jnp.float32),
        interpret=True)
    return jax.make_jaxpr(fn)(jax.ShapeDtypeStruct(array_shape, jnp.float32))


def test_accum_dtype_rule_fires_on_bf16_dot():
    def kernel(a_ref, b_ref, o_ref):
        o_ref[...] = jnp.dot(a_ref[...], b_ref[...])   # bf16 accumulate

    shape = jax.ShapeDtypeStruct((128, 128), jnp.bfloat16)
    fn = pl.pallas_call(
        kernel, out_shape=shape, interpret=True)
    jaxpr = jax.make_jaxpr(fn)(shape, shape)
    assert rules(ja.accum_dtype_violations(jaxpr)) == ["jaxpr.accum-dtype"]


# --------------------------------------------------------- pallas audits
def test_vmem_budget_rule_fires_on_oversized_block():
    # one (4096, 4096) f32 block = 64 MiB; double-buffered in+out blows
    # any budget — traced only, never executed
    jaxpr = _toy_pallas((4096, 4096), (4096, 4096))
    calls = [c for c in _collect(jaxpr)]
    assert rules(pa.vmem_violations(calls, "toy")) == ["pallas.vmem-budget"]
    small = _collect(_toy_pallas((8, 128), (16, 256)))
    assert pa.vmem_violations(small, "toy") == []


def test_tile_divisibility_rule_fires_on_ragged_block():
    """The TPU rule: a (1, tk) block over a (B, S) validity row — the
    layout Mosaic refused in every decode kernel — fires; the (B, 1, S)
    layout with (1, 1, tk) blocks, which the kernels now use, does not."""
    calls = _collect(_toy_pallas((1, 512), (4, 2048)))
    assert rules(pa.tile_divisibility_violations(calls, "toy")) \
        == ["pallas.tile-divisibility"]
    ok = _collect(_toy_pallas3((1, 1, 512), (4, 1, 2048)))
    assert pa.tile_divisibility_violations(ok, "toy") == []


@pytest.mark.parametrize("block,array,fires", [
    ((32, 64), (48, 64), False),      # 32 % 8 == 0, last dim is whole
    ((8, 128), (16, 256), False),     # aligned on both
    ((4, 128), (16, 256), True),      # sublane dim 4 is not a multiple of 8
    ((8, 64), (16, 256), True),       # lane dim 64 is not a multiple of 128
    ((16, 256), (16, 256), False),    # whole array
])
def test_tile_rule_is_tpu_8_128_or_full(block, array, fires):
    calls = _collect(_toy_pallas(block, array))
    got = pa.tile_divisibility_violations(calls, "toy")
    assert rules(got) == (["pallas.tile-divisibility"] if fires else [])


def test_scalar_prefetch_contract():
    """The real decode-FFN kernel prefetches 2 scalar operands; a
    contract of 0 (or a missing contract entry) must flag it."""
    from repro.core import lora as lora_mod
    from repro.core import routed_ffn as rf
    from repro.core.params import init_tree
    from repro.kernels.routed_ffn import ops as rffn_ops
    lcfg = lora_mod.LoRAConfig(rank=4, alpha=4.0, enabled=False)
    rcfg = rf.RoutedFFNConfig(d_model=64, d_ff=128, num_groups=8,
                              active_groups=2, capacity_factor=4.0,
                              gated=True, activation="gelu")
    p = jax.eval_shape(lambda: init_tree(rf.param_defs(rcfg, lcfg),
                                         jax.random.PRNGKey(0)))
    calls = pa.collect_pallas_calls(
        lambda p, x: rffn_ops.routed_ffn_decode(x, p, rcfg, lcfg,
                                                interpret=True)[0],
        p, jax.ShapeDtypeStruct((4, 1, 64), jnp.float32))
    assert [c.num_index_operands for c in calls] == [2]
    assert rules(pa.scalar_prefetch_violations(calls, "e", {})) \
        == ["pallas.scalar-prefetch"]
    assert pa.scalar_prefetch_violations(
        calls, "e", {"routed_ffn.py": 2}) == []


def test_audit_calls_flags_vacuous_entry():
    assert rules(pa.audit_calls([], "e")) == ["pallas.no-kernel"]


def _collect(jaxpr):
    return pa.calls_in_jaxpr(jaxpr)


# ------------------------------------------------------------ trace guard
def test_trace_guard_flags_weak_type_retrace():
    @jax.jit
    def f(x):
        return x * 2

    guard = tg.TraceGuard()
    wrapped = guard.track("decode_step", f)
    wrapped(jnp.float32(1.0))
    wrapped(2.0)                 # weak-type flip: same bucket, new trace
    assert rules(guard.violations()) == ["trace.retrace"]


def test_trace_guard_accepts_one_trace_per_shape_bucket():
    @jax.jit
    def f(x):
        return x * 2

    guard = tg.TraceGuard()
    wrapped = guard.track("decode_step", f)
    for s in (4, 8, 4, 8, 4):    # 2 buckets, 2 traces, 5 calls
        wrapped(jnp.ones(s, jnp.float32))
    assert guard.violations() == []


def test_trace_guard_flags_per_iteration_jit():
    guard = tg.TraceGuard()
    for _ in range(3):           # rebuilding jit each iteration
        wrapped = guard.track("chunk", jax.jit(lambda x: x + 1),
                              unique=True)
        wrapped(jnp.ones(2))
    assert "trace.per-iteration-jit" in rules(guard.violations())


def test_guard_engine_raises_on_injected_retrace():
    """End-to-end negative fixture: an engine whose chunk getter feeds a
    weak-type-flipping wrapper must raise at context exit."""
    @jax.jit
    def f(x):
        return x * 2

    class FakeEngine:
        def _get_chunk(self, *key):
            return f
        def _get_prefill(self):
            return f

    eng = FakeEngine()
    with pytest.raises(RuntimeError, match="trace.retrace"):
        with tg.guard_engine(eng):
            chunk = eng._get_chunk(2, 4)
            chunk(jnp.float32(1.0))
            chunk(2.0)
    assert eng._get_chunk(2, 4) is f          # hooks restored


# ------------------------------------------------- full registry (slow)
@pytest.mark.slow
def test_full_registry_clean_at_head():
    """Every registered audit over the real hot entrypoints is clean —
    the same sweep scripts/analyze.sh gates CI with."""
    assert registry.run_audits() == []


def test_fast_entrypoints_clean_at_head():
    """The cheap op-level entrypoints stay clean (sub-second each; the
    engine-tracing ones ride the slow sweep / analyze.sh)."""
    assert ja.ENTRYPOINTS["ops.routed_ffn_decode"]() == []
    assert pa.KERNEL_ENTRIES["routed_ffn.decode"]() == []
