"""Fine-tuning job: the program's ``Trainer.run`` (the ``launch/train.py``
path) on batches of the cell's traffic, then the float32 reference over the
first steps. The reference is the module that the cell's configuration
names (``common.reference_of``): its model, loss, weight rules and layout.

Set-up makes the trainer once, with the benchmark's weights from the seed,
and drives it through the checked steps with the window's own call and
feed. The window then runs that same trainer on further batches of the pool
until ``--seconds`` have passed (in a traced run: ``traced_steps`` steps
under the profiler). Once the window has closed and the device's peak is
read, the program's state is freed and the reference follows the checked
steps from the same weights and batches. Compared, each against its limit:

* ``loss``: worst relative gap of a checked step's loss;
* ``grad``: the first gradient as the optimizer got it (its first moment
  after step one, over 1 - b1), by the worst per-layer leaf: the gap of the
  two norms against the larger of the reference leaf's norm and the median
  leaf's;
* ``grad_median``: the median leaf's gap of those gradient norms;
* ``update``: the worst leaf's gap for each leaf's change over the checked
  steps.

Leaves whose reference gradient is under a thousandth of the median leaf's
(the codebooks, which nothing differentiates) are left out of ``grad`` and
``update``.
"""
from __future__ import annotations

import gc
import statistics
import time
from typing import Dict, List
from unittest import mock

import numpy as np

from bench import common, program, weights
from bench.reference import common as ref_common

TINY_GRAD = 1e-3
common.add_program_path()


def batches(traffic: dict, vocab: int, seed: int) -> List[Dict]:
    """The pool: ``pool`` batches of uniform random tokens (the paper's
    "Random" set), next-token labels, all drawn from the seed."""
    rng = np.random.default_rng(list(common.seed_words(seed)) + [7])
    b, s = traffic["batch"], traffic["seq_len"]
    out = []
    for _ in range(traffic["pool"]):
        block = rng.integers(0, vocab, size=(b, s + 1), dtype=np.int32)
        out.append({"tokens": block[:, :-1], "labels": block[:, 1:].copy()})
    return out


def optimizer(traffic: dict):
    from repro.optim.adamw import OptimizerConfig
    o = traffic["optimizer"]
    return OptimizerConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                           weight_decay=o["weight_decay"],
                           grad_clip=o["grad_clip"],
                           warmup_steps=o["warmup_steps"],
                           schedule=o["schedule"], total_steps=1 << 30)


def adam(traffic: dict) -> ref_common.AdamW:
    o = traffic["optimizer"]
    if o["schedule"] != "constant" or o["warmup_steps"] != 0:
        raise ValueError("the reference follows a constant learning rate")
    return ref_common.AdamW(lr=o["lr"], b1=o["b1"], b2=o["b2"],
                            eps=o["eps"], weight_decay=o["weight_decay"],
                            grad_clip=o["grad_clip"])


def state_maker(cfg, model: dict, seed: int, ref):
    """(cfg, key) -> state, as the Trainer calls its initialiser (jitted,
    with PRNGKey of the seed's low word, given as its ``seed``), by the
    weight rules of the reference module ``ref``."""
    from repro.train import state as S
    abstract = S.abstract_state(cfg)
    hi, _ = common.seed_words(seed)
    return lambda _cfg, key: weights.make_state(
        abstract, model, common.fold_high(key, hi), ref)


def make_weights(abstract, model: dict, seed: int, ref):
    """The seed's weights in one jitted call, the key an argument."""
    import jax
    return jax.jit(lambda k: weights.make_state(abstract, model, k, ref))(
        common.prng_key(seed))


def _norms(leaves: Dict[str, np.ndarray]) -> Dict[str, float]:
    return {k: float(np.linalg.norm(v)) for k, v in leaves.items()}


def _per_layer(train_tree, ref, m) -> Dict[str, np.ndarray]:
    """A program "train" subtree as per-layer leaves, reference names."""
    t, _ = weights.reference_tree({"train": train_tree, "frozen": {}}, ref)
    return ref.per_layer(t, m)


def run(cell: common.Cell, seed: int, seconds: float, ctx) -> dict:
    import jax
    from repro.launch.mesh import make_mesh
    from repro.sharding import axis_rules, rules_for_mesh
    from repro.train import state as S
    from repro.train.trainer import Trainer, TrainerConfig

    if getattr(ctx, "control", None):
        return {"numbers": control(cell, seed, ctx.control)}
    traffic, model = cell.traffic, cell.config["model"]
    ref = common.reference_of(cell)
    cfg = program.model_config(cell.config, cell.bench_dir)
    m = ref.model_from(model)
    vocab = cell.config["source_config"]["vocab_size"]
    pool = batches(traffic, vocab, seed)
    n_check = traffic["checked_steps"]
    tokens_per_step = traffic["batch"] * traffic["seq_len"]
    make = state_maker(cfg, model, seed, ref)
    mesh = make_mesh((1, 1), ("data", "model"), devices=ctx.devices[:1])
    rules = rules_for_mesh(mesh)

    with mesh, axis_rules(rules):
        ctx.note("set-up: trainer with the seed's weights")
        with mock.patch.object(S, "init_state", make):
            trainer = Trainer(cfg, optimizer(traffic),
                              TrainerConfig(total_steps=1 << 30,
                                            log_interval=1 << 30),
                              mesh=mesh, rules=rules,
                              seed=common.seed_words(seed)[1])
        _check_seams(trainer)
        step_fn = trainer._step
        trainer._step = lambda st, b: _spanned(step_fn, st, b)
        p0 = _per_layer(jax.device_get(trainer.state["train"]), ref, m)
        _check_weights(p0, _initial(cell, seed))
        seen = {"loss": []}

        def hook(step, metrics):
            with common.span("bench.step_hook"):
                seen["loss"].append(metrics["loss"])
                if step == 1:
                    seen["m1"] = _per_layer(
                        jax.device_get(trainer.state["opt"]["m"]), ref, m)
                if step == n_check:
                    seen["p"] = _per_layer(
                        jax.device_get(trainer.state["train"]), ref, m)

        ctx.note(f"set-up: {n_check} checked steps")
        trainer.run(_feed(pool[:n_check]), step_hook=hook)
        plan = step_fn.lower(trainer.state,
                             trainer._place(pool[0])).compile()
        mem = plan.memory_analysis()
        planned = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                   + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        ctx.setup_done()

        # ---- the window
        feed = _window_feed(pool, n_check, seconds, ctx.trace,
                            traffic["traced_steps"])
        with ctx.window():
            report = trainer.run(feed, step_hook=lambda *_: None)
        steps = report["final_step"]
        window_s = ctx.window_s
        peak = common.memory_peak_bytes(ctx.devices)
        del trainer, step_fn, plan
    _free_device(jax)

    # ---- the reference, once the program's state is gone
    ctx.note("reference: float32 steps")
    numbers = check(cell, seed, pool[:n_check], seen, p0, ctx)
    work = {"steps": steps, "tokens": steps * tokens_per_step}
    return {
        "attempted": steps, "failed": 0,
        "end_to_end": {
            "train_tokens_per_s": steps * tokens_per_step / window_s,
            "train_peak_gib": planned / 2 ** 30,
        },
        "memory_peak_bytes": peak,
        "planned_bytes": planned,
        "numbers": numbers,
        "work": work,
        "losses": seen["loss"],
    }


class SeamError(RuntimeError):
    """The program no longer offers what the benchmark drives it through."""


def _check_seams(trainer) -> None:
    """The benchmark reaches into the Trainer at three places: it replaces
    ``state.init_state`` while the trainer is built, wraps ``_step`` in a
    host span and compiles ``_step`` on ``_place``'s batch for its memory
    plan. Fail by name where one is gone."""
    for name in ("_step", "_place", "state"):
        if not hasattr(trainer, name):
            raise SeamError(f"Trainer has no {name!r}, which the benchmark "
                            "drives it through")


def _check_weights(got: Dict, want: Dict) -> None:
    """The trainer's trainable leaves are the benchmark's, to within
    rounding: if the replaced ``state.init_state`` was not the one the
    Trainer called, the program trains on weights of its own, unrelated to
    those the reference starts from."""
    for k, v in want.items():
        g = got.get(k)
        v = np.asarray(v, np.float32)
        if g is None or g.shape != v.shape or np.linalg.norm(
                np.asarray(g, np.float32) - v) > 1e-2 * np.linalg.norm(v):
            raise SeamError(
                f"the trainer's leaf {k!r} is not the benchmark's weight: "
                "Trainer no longer builds its state through "
                "repro.train.state.init_state")


def _spanned(step_fn, state, batch):
    with common.span("bench.step_dispatch"):
        return step_fn(state, batch)


def _feed(items):
    for b in items:
        yield b


def _window_feed(pool, start: int, seconds: float, traced: bool,
                 traced_steps: int):
    """Batches of the pool after the checked ones, round and round, until
    the window's time is up (or, traced, for ``traced_steps`` steps)."""
    end = time.perf_counter() + seconds
    i = 0
    while (i < traced_steps) if traced else (time.perf_counter() < end):
        with common.span("bench.batch"):
            b = pool[(start + i) % len(pool)]
        i += 1
        yield b


def _free_device(jax) -> None:
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    gc.collect()


def reference_steps(cell: common.Cell, seed: int, checked: List[Dict],
                    dots: ref_common.Dots):
    """The cell's reference (or, with lower-precision dots, the control)
    over the checked steps from the seed's weights: (losses, step-one
    gradients, parameters after the steps), each per layer."""
    import jax.numpy as jnp
    from repro.train import state as S
    ref = common.reference_of(cell)
    cfg = program.model_config(cell.config, cell.bench_dir)
    model = cell.config["model"]
    m = ref.model_from(model)
    abstract = {k: v for k, v in S.abstract_state(cfg).items()
                if k in ("train", "frozen")}
    state = make_weights(abstract, model, seed, ref)
    train, frozen = weights.reference_tree(state, ref)
    del state
    data = [(jnp.asarray(b["tokens"]), jnp.asarray(b["labels"]))
            for b in checked]
    losses, g1, p = ref_common.run_train_steps(
        train, frozen, data,
        lambda tr, fr, tok, lab: ref.train_loss(tr, fr, tok, lab, m, dots),
        adam(cell.traffic))
    del train, frozen
    return losses, ref.per_layer(g1, m), ref.per_layer(p, m)


def compare(seen: dict, p0: Dict, want, b1: float) -> Dict[str, dict]:
    """The three numbers of the docstring, from the program's readings
    ``seen`` (losses, first moment after step one, parameters after the
    checked steps) and the reference's ``want``."""
    losses, g_ref, p_ref = want
    loss = max(abs(a - b) / abs(b) for a, b in zip(seen["loss"], losses))
    g_prog = {k: v / (1 - b1) for k, v in seen["m1"].items()}
    gn_ref, gn_prog = _norms(g_ref), _norms(g_prog)
    med = statistics.median(gn_ref.values())
    moving = {k for k, v in gn_ref.items() if v >= TINY_GRAD * med}
    grad, grad_leaf = common.worst_leaf_gap(gn_prog, gn_ref,
                                            lambda k: k in moving)
    d_prog = _norms({k: seen["p"][k] - p0[k] for k in p0})
    d_ref = _norms({k: p_ref[k] - p0[k] for k in p0})
    update, update_leaf = common.worst_leaf_gap(d_prog, d_ref,
                                                lambda k: k in moving)
    return {"loss": {"value": loss},
            "grad": {"value": grad, "leaf": grad_leaf},
            "grad_median": {"value": common.median_leaf_gap(
                gn_prog, gn_ref, lambda k: k in moving)},
            "update": {"value": update, "leaf": update_leaf},
            "left_out": sorted(set(gn_ref) - moving)}


def check(cell, seed, checked, seen, p0, ctx) -> Dict[str, dict]:
    import jax
    with jax.default_matmul_precision("highest"):
        want = reference_steps(cell, seed, checked, ref_common.Dots("f32"))
    out = compare(seen, p0, want, cell.traffic["optimizer"]["b1"])
    out["reference_losses"] = want[0]
    return out


def control(cell: common.Cell, seed: int, kind: str) -> Dict[str, dict]:
    """The cell's comparison with the reference put in the program's place:
    computed in float8 ("fp8", the control), or over half of each batch
    with the mean taken over the rest ("half_batch", a fault). Both
    readings are ones that a limit must fail."""
    import jax
    vocab = cell.config["source_config"]["vocab_size"]
    checked = batches(cell.traffic, vocab, seed)[:cell.traffic["checked_steps"]]
    b1 = cell.traffic["optimizer"]["b1"]
    f32 = ref_common.Dots("f32")
    if kind == "half_batch":
        placed = [{k: v[: len(v) // 2] for k, v in b.items()}
                  for b in checked]
        dots = f32
    else:
        placed, dots = checked, ref_common.Dots(kind)
    with jax.default_matmul_precision("highest"):
        losses, g1, p = reference_steps(cell, seed, placed, dots)
        want = reference_steps(cell, seed, checked, f32)
    p0 = _initial(cell, seed)
    seen = {"loss": losses, "m1": {k: v * (1 - b1) for k, v in g1.items()},
            "p": p}
    return compare(seen, p0, want, b1)


def _initial(cell: common.Cell, seed: int) -> Dict:
    """The seed's trainable weights, per layer, reference names."""
    from repro.train import state as S
    ref = common.reference_of(cell)
    cfg = program.model_config(cell.config, cell.bench_dir)
    abstract = {"train": S.abstract_state(cfg)["train"]}
    model = cell.config["model"]
    state = make_weights(abstract, model, seed, ref)
    return _per_layer(state["train"], ref, ref.model_from(model))
