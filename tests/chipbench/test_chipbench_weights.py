"""Benchmark weights: one function of (seed, name, index), whether made as
the program's whole tree or again a row or a layer at a time."""
import chipbench_testpaths  # noqa: F401  (sys.path for chipbench)
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights as W


def test_seed_words_take_seeds_past_32_bits():
    assert list(W.seed_words(2 ** 33 + 5)) == [5, 2]
    with pytest.raises(ValueError):
        W.seed_words(-1)


def test_uniform_has_the_asked_scale():
    key = W.leaf_key(jnp.asarray(W.seed_words(3)), W.name_id("x"))
    v = np.asarray(W.uniform(key, jnp.arange(200_000, dtype=jnp.uint32),
                             0.5))
    assert abs(v.std() - 0.5) < 0.005 and abs(v.mean()) < 0.005
    assert np.abs(v).max() <= 0.5 * np.sqrt(3) + 1e-6


def test_rows_equal_the_whole_weight():
    words = W.seed_words(2 ** 31 + 17)
    full = np.asarray(W.logical(words, "embed.w", (50, 24), "float32"))
    rows = np.asarray(W.rows_of(words, "embed.w", jnp.asarray([3, 49, 0]),
                                (50, 24), "float32"))
    np.testing.assert_array_equal(rows, full[[3, 49, 0]])


def test_by_id_equals_logical():
    words = W.seed_words(9)
    a = W.logical(words, "layer3.mixer.wq", (16, 8))
    b = W.by_id(words, jnp.asarray(W.name_id("layer3.mixer.wq")), (16, 8),
                "layer0.mixer.wq")
    np.testing.assert_array_equal(np.asarray(a, np.float32),
                                  np.asarray(b, np.float32))


def test_program_tree_holds_the_logical_weights():
    """Stacked layers, lane- and row-padded tables and norms of the
    program's tree carry the logical values."""
    import jax
    from repro.configs.deepseek_7b import reduced
    cfg = reduced()
    params = W.program_params(cfg, 5, jax.devices()[0])
    words = W.seed_words(5)
    e = cfg.engram
    tab = np.asarray(params["engram"]["layers"][1]["tables"])
    hd = e.head_dim
    want = np.asarray(W.logical(words, "engram1.tables",
                                (e.n_tables, e.table_vocab, hd), "float32"))
    np.testing.assert_array_equal(tab[:, :e.table_vocab, :hd], want)
    assert not tab[:, e.table_vocab:].any() and not tab[..., hd:].any()
    from repro.models.transformer import segment_plan
    seg = segment_plan(cfg)[0]
    layer = seg.layers[0]
    got = params["segments"][0]
    got = got["prefix"][0] if seg.prefix_len else \
        jax.tree.map(lambda x: x[0], got["stack"][0])
    np.testing.assert_array_equal(
        np.asarray(got["mixer"]["wq"]),
        np.asarray(W.logical(words, f"layer{layer}.mixer.wq",
                             got["mixer"]["wq"].shape, "float32")))
    assert np.all(np.asarray(params["final_norm"]["scale"]) == 1)
