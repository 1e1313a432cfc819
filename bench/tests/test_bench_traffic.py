"""Traffic and weights drawn from the seed: the same seed gives the same
inputs, another seed other ones, and seeds past 32 bits stay distinct."""
import numpy as np

from bench import common
from bench.jobs import train
from bench.tests import tiny


def test_batches_repeat_for_a_seed_and_differ_across_seeds():
    t = tiny.cell().traffic
    big = 2 ** 31 + 11
    a = train.batches(t, 256, big)
    b = train.batches(t, 256, big)
    c = train.batches(t, 256, big + 2 ** 32)
    assert all(np.array_equal(x["tokens"], y["tokens"]) for x, y in zip(a, b))
    assert not np.array_equal(a[0]["tokens"], c[0]["tokens"])
    # every row of the pool differs, labels are the next tokens
    rows = np.concatenate([x["tokens"] for x in a])
    assert len({r.tobytes() for r in rows}) == len(rows)
    assert np.array_equal(a[0]["labels"][:, :-1], a[0]["tokens"][:, 1:])


def test_seed_words_keep_the_high_bits():
    assert common.seed_words(2 ** 33 + 5) == (2, 5)
    k1 = np.asarray(common.prng_key(2 ** 33 + 5))
    k2 = np.asarray(common.prng_key(5))
    assert not np.array_equal(k1, k2)


def test_weights_repeat_for_a_seed_whatever_the_tree_around_them():
    import jax
    from bench import program, weights
    from repro.train import state as S
    cell = tiny.cell()
    ref = common.reference_of(cell)
    cfg = program.model_config(cell.config)
    full = S.abstract_state(cfg)
    part = {"train": full["train"]}
    key = common.prng_key(2 ** 31 + 3)
    a = weights.make_state(full, cell.config["model"], key, ref)
    b = weights.make_state(part, cell.config["model"], key, ref)
    la = jax.tree_util.tree_leaves(a["train"])
    lb = jax.tree_util.tree_leaves(b["train"])
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    t, _ = weights.reference_tree({"train": b["train"], "frozen": {}}, ref)
    for name, leaf in weights.layer_leaves(t, 2).items():
        # LoRA up factors that write the residual stream start at zero
        zero = name.endswith(("wo/c", "lora_outer/c"))
        assert (float(np.abs(leaf).max()) == 0) == zero, name


def test_one_compiled_program_makes_every_seeds_weights():
    import jax
    from bench import program
    from repro.train import state as S
    cell = tiny.cell()
    ref = common.reference_of(cell)
    cfg = program.model_config(cell.config)
    model = cell.config["model"]

    def text(seed):
        maker = train.state_maker(cfg, model, seed, ref)
        return jax.jit(maker, static_argnums=0).lower(
            cfg, jax.random.PRNGKey(common.seed_words(seed)[1])).as_text()

    assert text(2 ** 31 + 1) == text(2 ** 31 + 99)
    # the trainer's key, from the low word, gives the seed's weights
    a = jax.jit(train.state_maker(cfg, model, 2 ** 33 + 7, ref),
                static_argnums=0)(cfg, jax.random.PRNGKey(7))
    b = train.make_weights(S.abstract_state(cfg), model, 2 ** 33 + 7, ref)
    assert all(np.array_equal(x, y) for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))
