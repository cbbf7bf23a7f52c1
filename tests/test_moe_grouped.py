"""The prefill's grouped expert layer (``parallel/moe.py``, PR 49): the
Pallas kernel that walks (row tile, expert) visits, in interpret mode on
the CPU against ``moe_share_dense``; the walk itself against a plain
count; which form is lowered where; the batcher's gauge."""
import functools

import numpy as np
import pytest


def _routing(T, k, nAll, lo, n, seed, everyone=None, nobody=()):
    """``(idx (T, k) int32, w (T, k) float32)``: each token chooses ``k``
    distinct experts of ``nAll`` -- ``everyone`` among them, where given,
    and none of ``nobody``."""
    rng = np.random.RandomState(seed)
    free = [e for e in range(nAll) if e != everyone and e not in nobody]
    idx = np.stack([rng.permutation(free)[:k] for _ in range(T)])
    if everyone is not None:
        idx[:, 0] = everyone
    return idx.astype(np.int32), rng.uniform(0.2, 1.0, (T, k)).astype(
        np.float32)


def _experts(n, d, f, dout, matrices, seed):
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    draw = lambda k, shape: 0.3 * jax.random.normal(k, shape, jnp.float32)
    Eg = draw(ks[0], (n, d, f)) if matrices == 3 else None
    return Eg, draw(ks[1], (n, d, f)), draw(ks[2], (n, f, dout))


#: 24 tokens of which 21 are real, 6 of 16 experts a token, 12 held from
#: expert 2 on: expert 3 is chosen by every token (a group of 21 rows:
#: it fills a tile of 16 and straddles the next), the held experts 5 and
#: 9 by none, the others by 6 of 14 tokens each: 21 + ~72 held pairs
@pytest.mark.parametrize("rows", [16, 32])
@pytest.mark.parametrize("passRows", [None, 40, 96, 144, 200])
@pytest.mark.parametrize("matrices", [3, 2])
def test_grouped_kernel_gives_the_dense_forms_sum(matrices, passRows, rows):
    """``moe_share_grouped`` with every pass's rows through the kernel
    (interpret mode; tiles of 16 and 32 rows), ``passRows`` under, at
    and over the held pairs, dividing ``T k`` = 144 or not: experts of
    three matrices under ``silu(g) * u`` and of two under ``relu2``,
    groups that straddle tiles and one that fills some, held experts
    with no pair, tokens that are not real and dead rows behind the
    pairs -- ``moe_share_dense``'s sum, and the ``ragged_dot`` form's."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.parallel import moe
    T, k, lo, n = 24, 6, 2, 12
    idx, w = _routing(T, k, 16, lo, n, seed=matrices, everyone=3,
                      nobody=(5, 9))
    E = _experts(n, 32, 64, 48, matrices, seed=7)
    act = moe.relu2 if matrices == 2 else None
    x = np.random.RandomState(1).randn(T, 32).astype(np.float32)
    real = jnp.arange(T) >= 3
    args = (jnp.asarray(x), jnp.asarray(idx), jnp.asarray(w), *E, lo, real)
    want = np.asarray(moe.moe_share_dense(*args, act=act))
    held = int(moe.moe_share_counts(args[1], lo, n, real)[0])
    assert 80 < held < 110 and int(moe._hit(args[1], lo, n, real).sum()) == 10
    through = functools.partial(moe._rows_kernel, rows=rows, interpret=True)
    got = np.asarray(moe._share_grouped(through, *args, passRows, act))
    assert np.abs(want).max() > 1.0
    assert np.abs(got - want).max() < 5e-5
    assert not got[:3].any()
    ragged = np.asarray(moe.moe_share_grouped(*args, passRows=passRows,
                                              act=act))
    assert np.abs(got - ragged).max() < 5e-5


def test_grouped_kernel_takes_its_tile_from_the_rows():
    """Left to itself a pass's tile is 128 rows, or the whole pass in
    sublane tiles of 16 where it has fewer; a pass that is not whole
    tiles is padded and cut back: 300 rows of 8 experts (tiles of 128:
    three, the last padded), bfloat16-sized rows or not."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.parallel import moe
    n, d, f = 8, 32, 128
    _, Eu, Ed = _experts(n, d, f, d, 2, seed=3)
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    edge = jnp.asarray([0, 7, 140, 140, 141, 270, 270, 283], jnp.int32)
    for R in (300, 24):
        xs = jax.random.normal(ks[0], (R, d), jnp.float32)
        wrow = jnp.where(jnp.arange(R) < 283,
                         jax.random.uniform(ks[1], (R,), jnp.float32), 0)
        e = jnp.minimum(edge, R)
        want = np.asarray(moe._rows_ragged(xs, wrow, e, Eu, Ed,
                                           act=moe.relu2))
        got = np.asarray(moe._rows_kernel(xs, wrow, e, Eu, Ed, act=moe.relu2,
                                          interpret=True))
        live = min(R, 283)
        assert got.shape == want.shape == (R, d)
        assert np.abs(want[:live]).max() > 0.5
        assert np.abs(got[:live] - want[:live]).max() < 5e-5


def _walk(edge, tile, tiles):
    """The visits by a plain count."""
    out, start = [], 0
    for e, end in enumerate(edge):
        out += [(t, e, start, end) for t in range(tiles)
                if max(start, t * tile) < min(end, (t + 1) * tile)]
        start = end
    return sorted(out)


@pytest.mark.parametrize("edge,tile", [
    ([3, 3, 20, 47, 47, 48], 16),        # straddles, empties, one row
    ([0, 0, 0, 0], 16),                  # nothing held in the pass
    ([64], 16),                          # one expert fills every tile
    ([16, 32, 48, 64], 16),              # groups ON the tiles' edges
    ([1, 2, 3, 4, 5, 6, 7, 8], 8),       # many experts in one tile
    ([0, 100, 100, 128], 128)])          # a pass of one tile
def test_the_walk_names_every_tile_a_group_touches_once(edge, tile):
    """``_visits``: every (row tile, expert) whose group has a row in the
    tile, in order, with the group's bounds; never more than ``tiles +
    n - 1``; an expert with no row is in no visit."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.parallel import moe
    tiles = -(-max(edge[-1], 1) // tile)
    t, e, lo, hi, nvis = map(np.asarray, moe._visits(
        jnp.asarray(edge, jnp.int32), tile, tiles))
    want = _walk(edge, tile, tiles)
    assert t.shape == (tiles + len(edge) - 1,) and t.dtype == np.int32
    assert int(nvis) == len(want) <= tiles + len(edge) - 1
    got = list(zip(*(a[:int(nvis)].tolist() for a in (t, e, lo, hi))))
    assert got == want
    # what lies past the real visits still names a tile and an expert
    assert ((0 <= t) & (t < tiles)).all() and ((0 <= e) & (e < len(edge))).all()


def test_grouped_form_is_the_ragged_dot_off_the_tpu():
    """``moe_share_grouped`` chooses by what the program is lowered for:
    on the CPU (eagerly, under a jit, and with the weights split over two
    of the suite's devices) a pass is ``lax.ragged_dot``, no kernel, and
    no kernel lowering is counted."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from deeplearning4j_tpu.parallel import moe
    T, k, lo, n = 24, 6, 2, 12
    idx, w = _routing(T, k, 16, lo, n, seed=0)
    Eg, Eu, Ed = _experts(n, 32, 64, 32, 3, seed=1)
    x = jnp.asarray(np.random.RandomState(2).randn(T, 32), jnp.float32)
    real = jnp.arange(T) >= 3
    before = moe.moe_grouped_kernel_lowerings()
    grouped = lambda x, Eg, Eu, Ed: moe.moe_share_grouped(
        x, jnp.asarray(idx), jnp.asarray(w), Eg, Eu, Ed, lo, real,
        passRows=48)
    want = np.asarray(moe.moe_share_dense(x, jnp.asarray(idx), jnp.asarray(w),
                                          Eg, Eu, Ed, lo, real))
    eager = np.asarray(grouped(x, Eg, Eu, Ed))
    assert np.abs(eager - want).max() < 5e-5
    # one primitive a pass, whose lowering here is the three ragged dots
    # (which the CPU in turn lowers to masked matmuls): no custom call
    assert str(jax.make_jaxpr(grouped)(x, Eg, Eu, Ed)).count(
        "moe_share_grouped_rows") == 1
    assert "custom_call" not in jax.jit(grouped).lower(
        x, Eg, Eu, Ed).as_text()
    plain = moe._share_grouped(moe._rows_ragged, x, jnp.asarray(idx),
                               jnp.asarray(w), Eg, Eu, Ed, lo, real, 48, None)
    np.testing.assert_array_equal(np.asarray(plain), eager)
    np.testing.assert_array_equal(np.asarray(jax.jit(grouped)(x, Eg, Eu, Ed)),
                                  eager)
    mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
    split = NamedSharding(mesh, P(None, None, "model"))
    sharded = jax.jit(grouped)(x, jax.device_put(Eg, split),
                               jax.device_put(Eu, split), Ed)
    assert np.abs(np.asarray(sharded) - want).max() < 5e-5
    assert moe.moe_grouped_kernel_lowerings() == before


@pytest.mark.parametrize("lowered", [0, 1])
def test_warm_sets_the_grouped_kernel_gauge(monkeypatch, lowered):
    """``ContinuousBatcher.warm`` sets
    ``dl4j_tpu_serving_moe_grouped_kernel{model}`` from what the prefill
    ladder's warm-up lowered: 1 when a prefill counted a kernel lowering
    (here: counted by hand, the CPU never does), 0 when none did."""
    from deeplearning4j_tpu.nlp.transformer import TransformerLM
    from deeplearning4j_tpu.parallel import moe
    from deeplearning4j_tpu.remote import ContinuousBatcher
    from deeplearning4j_tpu.telemetry import serving_metrics
    lm = TransformerLM(vocabSize=40, nLayers=1, nHeads=2, headSize=8,
                       maxLen=64, seed=5)
    prefill = lm.prefillRaw

    def counting(*a, **k):
        moe._groupedKernelLowerings[0] += lowered
        return prefill(*a, **k)
    monkeypatch.setattr(lm, "prefillRaw", counting)
    monkeypatch.setattr(moe, "_groupedKernelLowerings", [0])
    name = f"grouped-gauge-{lowered}"
    cb = ContinuousBatcher(lm, name=name, maxSlots=2, pageSize=4)
    try:
        cb.warm()
    finally:
        cb.shutdown()
    assert serving_metrics().moe_grouped_kernel().value(model=name) == lowered
