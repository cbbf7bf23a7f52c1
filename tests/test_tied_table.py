"""The tied embedding table of ``TransformerLM`` is held in whole lane tiles
of 128 columns (zeros past the model's width, ``nlp/transformer.py``): what
the programs compute is what a plain computation gives from the UNPADDED
table, and a tree is laid out once, when it is given to the model.  The
width here is 200 (5 heads of 40): like GPT-2 XL's 1,600, no whole number
of lane tiles."""
import dataclasses

import numpy as np
import pytest

from deeplearning4j_tpu.nlp.transformer import (TransformerLM,
                                                TransformerLMConfig,
                                                lane_aligned)

pytestmark = pytest.mark.serving

VOCAB, HEADS, HEAD_SIZE, LAYERS, MAX_LEN = 37, 5, 40, 2, 32
WIDTH, HELD = HEADS * HEAD_SIZE, 256


def _config(**kw):
    return TransformerLMConfig(**{**dict(
        vocabSize=VOCAB, nLayers=LAYERS, nHeads=HEADS, headSize=HEAD_SIZE,
        maxLen=MAX_LEN, seed=3), **kw})


@pytest.fixture(scope="module")
def lm():
    return TransformerLM(_config())


@pytest.fixture(scope="module")
def plain(lm):
    """The model's tree in numpy, its table as a checkpoint has it:
    ``(VOCAB, 200)``."""
    import jax
    tree = jax.tree.map(np.asarray, lm.params)
    tree["emb"] = tree["emb"][:, :WIDTH]
    return tree


def _ln(x, g, b):
    xc = x - x.mean(-1, keepdims=True)
    return xc / np.sqrt((xc * xc).mean(-1, keepdims=True) + 1e-5) * g + b


def _gelu(x):
    return 0.5 * x * (1 + np.tanh(np.sqrt(2 / np.pi) * (x + 0.044715 * x**3)))


def _reference_logits(p, seq):
    """GPT-2's forward over one sequence ``(t,)`` in float64 numpy from
    the unpadded tree ``p``: ``(t, vocab)``."""
    p = {k: ([{n: w.astype(np.float64) for n, w in lp.items()} for lp in v]
             if k == "layers" else v.astype(np.float64))
         for k, v in p.items()}
    t = len(seq)
    x = p["emb"][seq] + p["pos"][:t]
    causal = np.tril(np.ones((t, t), bool))
    for lp in p["layers"]:
        h = _ln(x, lp["ln1_g"], lp["ln1_b"])
        q, k, v = (np.matmul(h, lp[w]).reshape(t, HEADS, HEAD_SIZE)
                   for w in ("Wq", "Wk", "Wv"))
        s = np.einsum("qhd,khd->hqk", q, k) / np.sqrt(HEAD_SIZE)
        s = np.where(causal, s, -np.inf)
        a = np.exp(s - s.max(-1, keepdims=True))
        a /= a.sum(-1, keepdims=True)
        x = x + np.einsum("hqk,khd->qhd", a, v).reshape(t, -1) @ lp["Wo"]
        h = _ln(x, lp["ln2_g"], lp["ln2_b"])
        x = x + _gelu(h @ lp["Wi"] + lp["bi"]) @ lp["Wp"] + lp["bp"]
    return _ln(x, p["lnf_g"], p["lnf_b"]) @ p["emb"].T


def _forward(lm, seq):
    return np.asarray(lm.forward(seq[None, :])[0])


def _prefill(lm, seq):
    """Every prefix through the 16 bucket, left-padded: its last logits."""
    rows = []
    for n in range(1, len(seq) + 1):
        padded = np.concatenate([np.zeros(16 - n, np.int32), seq[:n]])
        rows.append(np.asarray(
            lm.prefillRaw(padded[None, :], lengths=[n])[0][0]))
    return np.stack(rows)


def _paged_step(lm, seq):
    """The first token prefilled into pool pages, the rest decoded one
    paged step each, teacher-forced."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.remote import KVCachePool
    ps, bucket = 4, 4
    pages = -(-(bucket + len(seq)) // ps)
    pool = KVCachePool(LAYERS, HEADS, HEAD_SIZE, ps, numPages=1 + pages,
                       maxSlots=1, maxPagesPerSeq=pages)
    pool.ensure(0, bucket + len(seq))
    padded = np.concatenate([np.zeros(bucket - 1, np.int32), seq[:1]])
    logits, ks, vs = lm.prefillRaw(padded[None, :], lengths=[1])
    rows = [np.asarray(logits[0])]
    pool.k, pool.v = lm.buildPagedPrefillWriteFn()(
        pool.k, pool.v, ks[:, 0], vs[:, 0],
        jnp.asarray(pool.heldIds(0)[:bucket // ps], jnp.int32))
    step = jax.jit(lm.pagedLogits)
    pt = jnp.asarray(pool.pageTable)
    start = jnp.asarray([bucket - 1], jnp.int32)
    for j, tok in enumerate(seq[1:]):
        out, pool.k, pool.v = step(
            lm.params, pool.k, pool.v, jnp.asarray([[tok]], jnp.int32), pt,
            jnp.asarray([bucket + j], jnp.int32), start)
        rows.append(np.asarray(out[0, 0]))
    return np.stack(rows)


@pytest.mark.parametrize("program", [_forward, _prefill, _paged_step],
                         ids=["forward", "prefillRaw", "paged_step"])
def test_logits_are_the_unpadded_tables(lm, plain, program):
    seq = np.random.RandomState(11).randint(0, VOCAB, (9,)).astype(np.int32)
    want = _reference_logits(plain, seq)
    got = program(lm, seq)
    assert got.shape == want.shape == (9, VOCAB)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    assert np.abs(want).max() > 0.05        # 2e-5 is float32's rounding


def test_the_held_table_is_whole_lane_tiles_zero_past_the_width(lm, plain):
    emb = np.asarray(lm.params["emb"])
    assert emb.shape == (VOCAB, HELD)
    assert not emb[:, WIDTH:].any()
    assert np.abs(emb[:, :WIDTH]).min() > 0
    # every other leaf keeps the model's width
    assert lm.params["pos"].shape == (MAX_LEN, WIDTH)
    assert lm.params["layers"][0]["Wq"].shape == (WIDTH, WIDTH)
    np.testing.assert_array_equal(emb[:, :WIDTH], plain["emb"])


def test_assigning_a_plain_tree_lays_the_table_out(lm, plain):
    """The benchmark's order (``benchmark/configs/gpt2.py:build_lm``):
    construct at depth 0, then assign the configuration and the tree."""
    import jax.numpy as jnp
    tree = {k: jnp.asarray(v) if k != "layers" else
            [{n: jnp.asarray(w) for n, w in lp.items()} for lp in v]
            for k, v in plain.items()}
    served = TransformerLM(dataclasses.replace(_config(), nLayers=0))
    served.config = _config()
    served.params = tree
    assert tree["emb"].shape == (VOCAB, WIDTH)      # the caller's is its own
    assert served.params["emb"].shape == (VOCAB, HELD)
    assert not np.asarray(served.params["emb"])[:, WIDTH:].any()
    assert served.params["layers"] is tree["layers"]
    seq = np.arange(6, dtype=np.int32)
    np.testing.assert_array_equal(_forward(served, seq), _forward(lm, seq))


def _own_tree(lm):
    return lm.params


def _tree_of_a_width_256_model(lm):
    return TransformerLM(_config(nHeads=4, headSize=64, nLayers=0)).params


def _replaced_tree(lm):
    import jax
    return jax.device_put(lm.params, jax.devices()[0])


@pytest.mark.parametrize("tree_of", [_own_tree, _tree_of_a_width_256_model,
                                     _replaced_tree],
                         ids=["its_own", "width_256", "re_placed"])
def test_assigning_a_tree_of_whole_tiles_copies_nothing(tree_of):
    lm = TransformerLM(_config(nLayers=0))
    tree = tree_of(lm)
    emb = tree["emb"]
    assert emb.shape[1] == HELD
    lm.params = tree
    assert lm.params is tree and lm.params["emb"] is emb
    assert lane_aligned(emb) is emb


@pytest.mark.parametrize("width,held", [(8, 128), (128, 128), (200, 256),
                                        (1600, 1664), (2048, 2048)])
def test_lane_aligned_pads_to_the_next_multiple_of_128(width, held):
    table = np.ones((3, width), np.float32)
    out = np.asarray(lane_aligned(table))
    assert out.shape == (3, held)
    assert out[:, :width].all() and not out[:, width:].any()


def _toy():
    return TransformerLM(vocabSize=20, nLayers=1, nHeads=2, headSize=8,
                         maxLen=16)


def _toy_given_a_plain_table_around_the_setter():
    lm = _toy()
    lm._params = {**lm.params, "emb": lm.params["emb"][:, :16]}
    return lm


def _model_without_a_table():
    from deeplearning4j_tpu.models.recsys import RetrievalLM
    rng = np.random.RandomState(0)
    return RetrievalLM(rng.randn(20, 8).astype(np.float32),
                       rng.randn(20, 8).astype(np.float32), maxLen=16)


@pytest.mark.parametrize("build,want", [
    (_toy, 1), (_toy_given_a_plain_table_around_the_setter, 0),
    (_model_without_a_table, None)], ids=["held", "plain", "no_table"])
def test_the_batchers_gauge_says_the_table_is_whole_tiles(build, want):
    """``dl4j_tpu_serving_tied_table_lane_aligned`` is set at warm-up
    from the table the step is given; a model with no ``params["emb"]``
    gets no series."""
    from deeplearning4j_tpu.remote import ContinuousBatcher
    from deeplearning4j_tpu.telemetry import serving_metrics
    gauge = serving_metrics().tied_table_lane_aligned()
    name = f"tied-gauge-{build.__name__}"
    cb = ContinuousBatcher(build(), name=name, maxSlots=2, pageSize=4)
    try:
        cb.warm()
    finally:
        cb.shutdown()
    series = [line for line in gauge.expose() if f'model="{name}"' in line]
    assert [float(line.split()[-1]) for line in series] == \
        ([] if want is None else [want])
