"""The Nemotron-H served LM (``nlp/nemotron_h.py``: Mamba-2 blocks, an
attention block and LatentMoE expert blocks, one sub-layer a block, of
whose routed experts a chip holds a share) against the benchmark's plain
reference, at a small size on the CPU: the chunked SSD against the
position-by-position recurrence and against the step folded over
positions, the three forms of an expert of two matrices against each
other, the four shares of an expert layer against the uncut layer, the
full forward on logits, then prefill + decode through the scheduler's
cache manager holding K/V pages, a matrix state and convolution windows
side by side.

The reference is ``benchmark/references/nemotronh.py`` itself, loaded by
path: it imports nothing of the program, so the benchmark stays
independent of what it is compared with.
"""
import importlib.util
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

pytestmark = pytest.mark.cbatch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the pattern MEM*E in small: hidden 64, 4 Mamba heads of 8 in 2 groups,
# N 16; this "chip" holds experts 0..3 of 16 and the router chooses 6
TINY = {"hidden_size": 64, "expand": 2, "mamba_num_heads": 16,
        "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16,
        "conv_kernel": 4, "chunk_size": 8, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 8,
        "moe_intermediate_size": 48, "moe_latent_size": 32,
        "moe_shared_expert_intermediate_size": 96, "router_width": 16,
        "experts_held": [0, 4], "n_routed_experts": 4,
        "num_experts_per_tok": 6, "n_group": 1, "topk_group": 1,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 5, "mlp_hidden_act": "relu2",
        "mamba_hidden_act": "silu", "use_conv_bias": True,
        "mamba_proj_bias": False, "attention_bias": False,
        "mlp_bias": False, "tie_word_embeddings": False,
        "hybrid_override_pattern": "MEM*E", "num_hidden_layers": 5,
        "first_block": 3, "vocab_size": 96, "layer_norm_epsilon": 1e-5,
        "time_step_min": 0.001, "time_step_max": 0.1,
        "time_step_floor": 1e-4}
PAGE, SLOTS, CAP = 4, 3, 64
EXPERT_LAYERS, MAMBA_LAYERS = 2, 2

# float32 weights on the CPU: both sides compute in float32 and differ in
# the order of their sums and in the form of the mixer (the program folds
# a chunk's positions into three matmuls and carries states between
# chunks; the reference runs the recurrence); measured 1.5e-7 on logits
# whose spread is 0.16
TOL_F32 = 1e-5
# bfloat16 weights: the program rounds the residual stream, the K/V rows
# and every matmul's input to 8 bits of mantissa where the reference keeps
# float32.  Held on the MEAN error over positions and vocabulary (measured
# 0.0007 forward, where float8 inputs and weights read 0.011) and on the
# largest (measured 0.0039; float8 0.05)
TOL_BF16_MEAN, TOL_BF16_MAX = 0.0025, 0.03


def _load(rel, name):
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name,
                                                  os.path.join(REPO, rel))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("benchmark/references/nemotronh.py", "bench_ref_nemotronh")


@pytest.fixture(scope="module")
def family():
    return _load("benchmark/configs/nemotronh.py", "bench_cfg_nemotronh")


@pytest.fixture(scope="module")
def weights(ref):
    import jax
    return ref.make_weights(TINY, jax.random.PRNGKey(3))


def _as(weights, dtype):
    """The bfloat16 leaves in ``dtype`` (what is float32 stays so)."""
    import jax
    return jax.tree.map(
        lambda a: a.astype(dtype) if a.dtype == "bfloat16" else a, weights)


def _lm(family, weights, dtype):
    return family.build_lm(dict(TINY, dtype=dtype), _as(weights, dtype), CAP)


def _prompts(lengths, seed=1):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, TINY["vocab_size"], size=n).tolist()
            for n in lengths]


# -- (a) the chunked SSD against the recurrence ------------------------------
def _ssd_inputs(case, b=2, T=37, H=4, P=8, G=2, N=16):
    """Inputs of the recurrence with the decays a step spread as the
    configuration's initialiser spreads them (``spread``: dt from 1e-3 to
    1e-1 against A from 1 to 16), all but gone a step (``fast``: dt A near
    -8) and no decay at all (``none``: dt 0 advances nothing)."""
    rs = np.random.RandomState(0)
    f = lambda *s: rs.standard_normal(s).astype(np.float32)
    dt = {"spread": np.exp(rs.uniform(np.log(1e-3), np.log(1e-1),
                                      (b, T, H))),
          "fast": rs.uniform(0.4, 0.5, (b, T, H)),
          "none": np.zeros((b, T, H))}[case].astype(np.float32)
    A = -rs.uniform(1.0, 16.0, H).astype(np.float32)
    return f(b, T, H, P), dt, A, f(b, T, G, N), f(b, T, G, N)


@pytest.mark.parametrize("case", ["spread", "fast", "none"])
@pytest.mark.parametrize("chunk,T", [(8, 37), (8, 32), (16, 5), (128, 150)])
def test_chunked_ssd_is_the_recurrence(ref, case, chunk, T):
    """``ssd_chunked`` (chunks of matmuls, a scan over the chunks' states)
    against the reference's position-by-position scan: a length of whole
    chunks, one that ends inside a chunk, one shorter than a chunk."""
    from deeplearning4j_tpu.nlp.mamba import ssd_chunked
    x, dt, A, B, C = _ssd_inputs(case, T=T)
    y, S = ssd_chunked(x, dt, A, B, C, chunk)
    for i in range(x.shape[0]):
        want, Sw = ref.ssd_recurrence(x[i], dt[i], A, B[i], C[i])
        assert np.abs(np.asarray(y[i]) - np.asarray(want)).max() < 2e-4
        assert np.abs(np.asarray(S[i]) - np.asarray(Sw)).max() < 2e-4
    if case == "none":
        assert not np.asarray(S).any() and not np.asarray(y).any()
    else:
        assert np.abs(np.asarray(y)).max() > 0.1


def _mamba_layer(ref, family):
    import jax
    p = ref._make_block(jax.random.PRNGKey(5), "M", ref._static(TINY))
    lp = family.to_program({"emb": None, "head": None, "norm_f": None,
                            "layers": [_as(p, "float32")]})["layers"][0]
    D = ref.dims(TINY)
    return p["mamba"], lp, dict(H=D["H"], P=D["P"], G=D["G"], N=D["N"],
                                eps=D["eps"])


@pytest.mark.parametrize("real", [21, 5, 2])
def test_ssd_full_is_ssd_step_folded_over_the_positions(ref, family, real):
    """The prefill's mixer over a LEFT-padded bucket of 24 (three chunks;
    the real tokens end inside one, fill less than one, are fewer than
    the convolution's three rows) gives the outputs, the end state and
    the window that the step gives folded over the real positions from an
    empty slot; both are the reference's mixer."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.nlp.mamba import ssd_full, ssd_step
    p, lp, kw = _mamba_layer(ref, family)
    T, d = 24, TINY["hidden_size"]
    h = np.random.RandomState(real).standard_normal((1, T, d)
                                                    ).astype(np.float32)
    realF = (np.arange(T) >= T - real).astype(np.float32)[None, :, None]
    out, S, tail = ssd_full(lp, jnp.asarray(h), jnp.asarray(realF), K=4,
                            chunk=8, **kw)
    want = np.asarray(ref._mamba(jnp.asarray(h[0, T - real:]), p,
                                 ref.dims(TINY), False))
    assert np.abs(np.asarray(out[0, T - real:]) - want).max() < 1e-5
    cw = tail.shape[-1]
    ssm = jnp.zeros((1, 2, kw["H"], kw["P"], kw["N"]), jnp.float32)
    conv = jnp.zeros((1, 2, 3, cw), jnp.float32)
    active = jnp.asarray([False, True])
    for t in range(T - real, T):
        hs = jnp.stack([jnp.zeros((d,)), jnp.asarray(h[0, t])])
        o, ssm, conv = ssd_step(lp, hs, ssm, conv, 0, active, **kw)
        assert np.abs(np.asarray(o[1]) - want[t - (T - real)]).max() < 1e-5
    assert np.abs(np.asarray(ssm[0, 1]) - np.asarray(S[0])).max() < 1e-5
    assert np.abs(np.asarray(conv[0, 1]) - np.asarray(tail[0])).max() < 1e-6
    # the idle slot kept what it had
    assert not np.asarray(ssm[0, 0]).any() and not np.asarray(conv[0, 0]).any()


# -- (b) an expert of two matrices in its three forms ------------------------
def _expert_layer_inputs(ref, config, T, seed=0):
    """An expert layer's weights at ``config`` and an input ``(T, d)``."""
    import jax
    m = ref._make_block(jax.random.PRNGKey(seed), "E",
                        ref._static(config))["moe"]
    # ten times the seed's N(0, 0.02): outputs of size 1, a decisive
    # router (the bias, +-0.01 against sigmoids, stays as drawn)
    m = jax.tree.map(lambda a: 10.0 * a.astype("float32")
                     if a.shape != (config["router_width"],) else a, m)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (T, config["hidden_size"]), "float32")
    return ref.dims(config), m, x


def _routed(form, x, m, D, real=None, rows=None):
    """The program's routed part IN THE LATENT for the share ``D``
    describes."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.parallel import moe
    idx, w = moe.route_sigmoid_group_topk(
        x, m["w_router"], m["bias"], D["k"], 1, 1, D["scale"])
    u = jnp.matmul(x, m["w_down"], precision="highest")
    ex = (None, m["experts"]["w_1"], m["experts"]["w_2"], D["lo"])
    real = jnp.ones((x.shape[0],), bool) if real is None else real
    if form == "dense":
        return moe.moe_share_dense(u, idx, w, *ex, real, act=moe.relu2)
    if form == "step":
        return moe.moe_share_step(u, idx, w, *ex, real, act=moe.relu2)
    if form == "kernel":
        return moe._share_hit(u, idx, w, *ex, real, act=moe.relu2,
                              interpret=True)
    return moe.moe_share_grouped(u, idx, w, *ex, real, passRows=rows,
                                 act=moe.relu2)


@pytest.mark.parametrize("form,rows", [
    ("step", None), ("kernel", None), ("grouped", None), ("grouped", 24),
    ("grouped", 56), ("grouped", 72), ("grouped", 96), ("grouped", 168)])
def test_an_expert_of_two_matrices_in_its_three_forms(ref, form, rows):
    """``moe_share_dense``, ``moe_share_step`` (off the TPU the dense
    form; its kernel in interpret mode) and ``moe_share_grouped`` with
    ``Eg=None`` and ``relu2``, 6 experts a token of which 12 of 16 are
    held (24 tokens, 21 real: some 95 held pairs), ``passRows`` under,
    at and over the held pairs, dividing ``T k`` or not: one sum."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.parallel import moe
    cfg = dict(TINY, n_routed_experts=12, experts_held=[0, 12])
    D, m, x = _expert_layer_inputs(ref, cfg, T=24)
    real = jnp.arange(24) >= 3
    want = np.asarray(_routed("dense", x, m, D, real))
    got = np.asarray(_routed(form, x, m, D, real, rows))
    idx, _ = moe.route_sigmoid_group_topk(x, m["w_router"], m["bias"], 6, 1,
                                          1, 5.0)
    assert 80 < int(moe.moe_share_counts(idx, 0, 12, real)[0]) < 110
    assert np.abs(want).max() > 1.0
    assert np.abs(got - want).max() < 5e-5
    assert not got[:3].any()
    # and the reference's, one expert at a time, masked the same way
    rp = np.asarray(ref.routed_part(x, m, D))[3:]
    assert np.abs(want[3:] - rp).max() < 5e-5


def test_an_expert_of_two_matrices_names_its_activation():
    import jax.numpy as jnp
    from deeplearning4j_tpu.parallel import moe
    z = jnp.zeros
    with pytest.raises(ValueError, match="activation"):
        moe.moe_share_dense(z((2, 4)), z((2, 1), jnp.int32), z((2, 1)), None,
                            z((1, 4, 8)), z((1, 8, 4)), 0)


@pytest.mark.parametrize("f,rows,size,tile", [
    (2048, 3 * 7680, 2, 512),       # Pangu: the measured tile
    (768, 3 * 2560, 2, 768),        # Ling: the width whole
    (2688, 2 * 1024, 2, 2688),      # this model: whole, 22 MB of blocks
    (2688, 3 * 7680, 2, 384),       # the same width under wide rows: sevenths
    (100, 64, 4, 100)])             # no lane tiles to cut into
def test_the_kernel_takes_a_width_in_whole_lane_tiles_that_fit(f, rows, size,
                                                               tile):
    from deeplearning4j_tpu.parallel.moe import _expert_tile
    assert _expert_tile(f, rows, size) == tile


@pytest.mark.parametrize("form", ["step", "grouped"])
@pytest.mark.parametrize("experts,k", [(512, 22), (16, 6)])
def test_the_four_shares_of_a_layer_add_up_to_the_uncut_layer(
        ref, form, experts, k):
    """The guide's share test: the routed parts that the four shares give
    in the latent (``experts_held = (n r, n r + n)``, every share's
    experts drawn by their index among ALL), each through the ``W_up``
    every chip holds alike, with the shared expert counted ONCE, equal
    what the reference gives for the uncut layer: 4 shares of 128 of 512
    experts at 22 a token, and 4 shares of 4 of 16 at 6.  Float32; the
    sums differ in order."""
    import jax.numpy as jnp
    n = experts // 4
    base = dict(TINY, router_width=experts, n_routed_experts=n,
                num_experts_per_tok=k)
    whole = dict(base, n_routed_experts=experts, experts_held=[0, experts])
    Dw, mw, x = _expert_layer_inputs(ref, whole, T=24)
    want = np.asarray(ref.expert_layer(x, mw, Dw))
    total = np.asarray(ref.shared_part(x, mw))
    for r in range(4):
        D, m, _ = _expert_layer_inputs(
            ref, dict(base, experts_held=[n * r, n * r + n]), T=24)
        for name in ("w_router", "bias", "w_down", "w_up"):
            np.testing.assert_array_equal(np.asarray(m[name]),
                                          np.asarray(mw[name]))
        np.testing.assert_array_equal(
            np.asarray(m["experts"]["w_1"]),
            np.asarray(mw["experts"]["w_1"][n * r:n * r + n]))
        part = np.asarray(_routed(form, x, m, D, rows=7 * 24))
        # and the reference, given the same share, gives the same part
        assert np.abs(part - np.asarray(ref.routed_part(x, m, D))
                      ).max() < 1e-4
        total = total + np.asarray(jnp.matmul(part, m["w_up"],
                                              precision="highest"))
    assert np.abs(want).max() > 1.0
    assert np.abs(total - want).max() < 2e-4 * np.abs(want).max()


# -- (c) the model against the reference ---------------------------------------
def _close(got, want, dtype):
    """The tolerance of ``dtype``, as set out at the top."""
    err = np.abs(got - want)
    if dtype == "float32":
        return err.max() < TOL_F32
    return err.mean() < TOL_BF16_MEAN and err.max() < TOL_BF16_MAX


def test_the_pattern_names_the_blocks_and_the_pool(ref, family, weights):
    """``MEM*E``: one sub-layer a block; the pool holds FIVE arrays side
    by side: K and V pages of the one attention block, the two SSD
    states, the two convolution windows, and the routing's counts
    last."""
    from deeplearning4j_tpu.remote import KVCachePool
    lm = _lm(family, weights, "float32")
    assert lm.config.pattern == "MEM*E" and lm.config.firstBlock == 3
    assert [sorted(lp) for lp in lm.params["layers"]][1] == sorted(
        ["norm", "Wr", "rbias", "Wdown", "Wup", "S1", "S2", "E1", "E2"])
    assert all(len([k for k in lp if k.startswith("norm")]) == 1
               for lp in lm.params["layers"])
    spec = lm.cacheSpec()
    assert spec.arrayKinds == ("paged", "paged", "slot", "slot", "slot")
    assert (spec.pagedLayers, spec.pagedPools, spec.rowWidth) == (1, 2, 16)
    pool = KVCachePool.forSpec(spec, PAGE, 1 + SLOTS * (CAP // PAGE), SLOTS,
                               CAP // PAGE)
    assert [(a.shape, str(a.dtype)) for a in pool.arrays] == [
        ((1, pool.numPages, PAGE, 16), "float32"),
        ((1, pool.numPages, PAGE, 16), "float32"),
        ((MAMBA_LAYERS, SLOTS, 16, 8, 16), "float32"),
        ((MAMBA_LAYERS, SLOTS, 3, 128 + 2 * 2 * 16), "float32"),
        ((1, SLOTS, 3), "int32")]
    with pytest.raises(ValueError, match="pattern"):
        family.build_lm(dict(TINY, hybrid_override_pattern="MEM-E"),
                        weights, CAP)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_forward_matches_the_reference_logits(ref, family, weights, dtype):
    lm = _lm(family, weights, dtype)
    w = _as(weights, dtype)
    for n in (24, 37, 5):       # 3 whole chunks of 8; 4 and a part; a part
        toks = _prompts([n], seed=n)[0]
        got = np.asarray(lm.forward(np.asarray([toks])))[0]
        want = np.asarray(ref.logits(TINY, w, toks))
        assert _close(got, want, dtype)
        if dtype == "bfloat16" and n > 20:
            low = np.asarray(ref.logits(TINY, w, toks, low=True))
            assert np.abs(low - want).mean() > 2 * TOL_BF16_MEAN


def _teacher_forced(lm, pool, write, step, slot, prompt, bucket, forced):
    """Prefill ``prompt`` left-padded into ``bucket`` in ``slot``, then
    one decode step a token of ``forced``: yields the logits of every
    position from the prompt's last on."""
    import jax.numpy as jnp
    pad = bucket - len(prompt)
    padded = np.asarray([[0] * pad + prompt], np.int32)
    assert pool.ensure(slot, bucket)
    logits, *state = lm.prefillRaw(padded, lengths=[len(prompt)])
    ids = jnp.asarray(pool.heldIds(slot), jnp.int32)
    pool.arrays = write(*pool.arrays, *(p[:, 0] for p in state), ids,
                        jnp.asarray(slot, jnp.int32))
    yield np.asarray(logits[0])
    S = pool.maxSlots
    pos, start, tok = (np.zeros(S, np.int32) for _ in range(3))
    pos[slot], start[slot] = bucket, pad
    for t in forced:
        assert pool.ensure(slot, int(pos[slot]) + 1)
        tok[slot] = t
        out = step(lm.params, *pool.arrays, jnp.asarray(tok[:, None]),
                   jnp.asarray(pool.pageTable), jnp.asarray(pos),
                   jnp.asarray(start))
        pool.arrays = out[1:6]
        logits = np.asarray(out[0][slot, 0])    # the step has ended: only
        pos[slot] += 1                          # now may its inputs change
        yield logits


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_and_paged_decode_match_the_reference_logits(
        ref, family, weights, dtype):
    """Logits of every decode step, teacher-forced, through the pool's
    pages, SSD states and convolution windows: a ragged left-padded
    prompt that ends inside a chunk (the chunked form's end state is what
    the recurrence continues from), 40 new tokens, then THE SAME SLOT
    reused by a shorter sequence in another bucket, shorter than a chunk
    and than the convolution's three rows, whose stale pages, state and
    windows must not reach it.  The idle slots' state is left as it was,
    and the routing's counts of both prefills come back with the step
    after them, once."""
    import jax
    from deeplearning4j_tpu.remote import KVCachePool
    lm = _lm(family, weights, dtype)
    w = _as(weights, dtype)
    pool = KVCachePool.forSpec(lm.cacheSpec(), PAGE, 1 + SLOTS * (CAP // PAGE),
                               SLOTS, CAP // PAGE)
    write = lm.buildPagedPrefillWriteFn()
    counted = []
    jitted = jax.jit(lm.pagedLogits)

    def step(*args):
        out = jitted(*args)
        counted.append(np.asarray(out[6]))
        return out
    idle = [np.asarray(a[:, 0]).copy() for a in pool.arrays[2:4]]
    for prompt, bucket in ((_prompts([11])[0], 16), (_prompts([2], 2)[0], 8)):
        forced = _prompts([40], seed=len(prompt))[0]
        seq = prompt + forced
        want = np.asarray(ref.logits(TINY, w, seq, first=len(prompt) - 1))
        got = np.stack(list(_teacher_forced(lm, pool, write, step, 1, prompt,
                                            bucket, forced)))
        assert _close(got, want, dtype)
        assert pool.release(1) == -(-(bucket + 40) // PAGE)
    # slot 0 never held a sequence: the steps left its state untouched
    for before, a in zip(idle, pool.arrays[2:4]):
        np.testing.assert_array_equal(before, np.asarray(a[:, 0]))
    counted = np.stack(counted)                  # (80 steps, 6)
    pairs = TINY["num_experts_per_tok"] * EXPERT_LAYERS
    assert (counted[:, 0] + counted[:, 1] == pairs).all()
    assert (counted[:, 3] + counted[:, 4]).tolist() == \
        [pairs * 11] + [0] * 39 + [pairs * 2] + [0] * 39
    assert not np.asarray(pool.arrays[4]).any()
    assert pool.usedPages() == 0 and pool.stateSlots() == 0


def test_no_position_signal_and_left_padding_changes_nothing(ref, family,
                                                             weights):
    """The last logits of a prompt are those of the reference whatever
    the left padding (21 pads, 5 or none): nothing counts positions, and
    a pad advances no state, is no key and routes no pair."""
    lm = _lm(family, weights, "float32")
    prompt = _prompts([11], seed=4)[0]
    want = np.asarray(ref.logits(TINY, _as(weights, "float32"), prompt,
                                 first=10))[0]
    for bucket in (32, 16):
        padded = np.asarray([[7] * (bucket - 11) + prompt], np.int32)
        got, *state = lm.prefillRaw(padded, lengths=[11])
        assert np.abs(np.asarray(got[0]) - want).max() < TOL_F32
        assert np.asarray(state[4]).reshape(-1)[:2].sum() == \
            TINY["num_experts_per_tok"] * EXPERT_LAYERS * 11
    got = lm.prefillRaw(np.asarray([prompt], np.int32))[0]
    assert np.abs(np.asarray(got[0]) - want).max() < TOL_F32


# -- (d) through the scheduler --------------------------------------------------
@pytest.fixture
def batcher(family, weights):
    from deeplearning4j_tpu.remote import BucketLadder, ContinuousBatcher
    cb = ContinuousBatcher(
        _lm(family, weights, "float32"), name="nemotron_h", maxSlots=SLOTS,
        pageSize=PAGE, numPages=1 + SLOTS * (CAP // PAGE),
        ladder=BucketLadder(batchSizes=(SLOTS,), seqLens=(8, 16)))
    cb.start()
    yield cb
    cb.shutdown()


def _served_gap(ref, weights, prompt, served):
    """How far the served tokens' reference logits lie below the
    reference's best, at their worst."""
    lg = np.asarray(ref.logits(TINY, _as(weights, "float32"),
                               (prompt + served)[:-1],
                               first=len(prompt) - 1))
    return float((lg.max(-1) - lg[np.arange(len(served)), served]).max())


def _routing(name="nemotron_h"):
    from deeplearning4j_tpu.telemetry import serving_metrics
    sm = serving_metrics()
    return {(c, ph): getattr(sm, "moe_" + c)().value(model=name, phase=ph)
            or 0 for c in ("pairs_routed", "pairs_absent", "experts_hit")
            for ph in ("step", "prefill")}


def test_continuous_batcher_serves_the_reference_tokens_and_counts_routing(
        ref, weights, batcher):
    """Five ragged prompts in two buckets on three slots, sent at
    different moments, 40 new tokens each: a slot is freed and taken
    again.  Every served token must be the reference's best up to float32
    rounding of logits; the manager's books are empty afterwards; and the
    three routing counters are consistent: every token that passed an
    expert layer chose 6 experts in each, held here or absent."""
    from deeplearning4j_tpu.telemetry import serving_metrics
    batcher.submit({"tokens": [1, 2], "maxNewTokens": 3})
    before = _routing()
    prompts = _prompts([5, 11, 16, 7, 3])
    outs = [None] * len(prompts)

    def go(i):
        time.sleep(0.05 * i)
        outs[i] = np.asarray(batcher.submit(
            {"tokens": prompts[i], "maxNewTokens": 40}))[0].tolist()
    threads = [threading.Thread(target=go, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(180)
    for p, o in zip(prompts, outs):
        assert o is not None and len(o) == 40
        assert _served_gap(ref, weights, p, o) < TOL_F32
    pool = batcher.pool
    assert len(pool.arrays) == 5
    assert pool.usedPages() == 0 and pool.stateSlots() == 0
    assert pool.freePages() == pool.numPages - 1
    sm = serving_metrics()
    assert sm.cache_bytes().value(model="nemotron_h", kind="paged") == 0
    assert sm.cache_bytes().value(model="nemotron_h", kind="recurrent") == 0
    # off the TPU the step gathers and multiplies every held expert
    assert sm.paged_attention_kernel().value(model="nemotron_h") == 0
    assert sm.moe_step_kernel().value(model="nemotron_h") == 0
    assert sm.moe_grouped_kernel().value(model="nemotron_h") == 0
    assert sm.ssd_step_kernel().value(model="nemotron_h") == 0
    got = {k: v - before[k] for k, v in _routing().items()}
    pairs = TINY["num_experts_per_tok"] * EXPERT_LAYERS
    assert got["pairs_routed", "prefill"] + got["pairs_absent", "prefill"] \
        == pairs * sum(len(p) for p in prompts)
    assert got["pairs_routed", "step"] + got["pairs_absent", "step"] \
        == pairs * 39 * len(prompts)
    for ph in ("step", "prefill"):
        assert 0 < got["experts_hit", ph] <= got["pairs_routed", ph]


def test_serving_telemetry_shows_pages_and_recurrent_state_in_one_pool(
        ref, weights, batcher):
    """The spans and every ``dl4j_tpu_serving_*`` series expose under the
    batcher's name with no line written for this model; while a sequence
    decodes BOTH kinds of cache are non-zero in the one pool: K/V pages
    in ``cache_bytes{kind="paged"}`` and the slot's SSD states, windows
    and counts in ``kind="recurrent"``."""
    from deeplearning4j_tpu.telemetry import serving_metrics, tracer
    sm = serving_metrics()
    seen = {}
    stream = batcher.submitStream({"tokens": _prompts([11])[0],
                                   "maxNewTokens": 30})
    toks = [next(stream) for _ in range(5)]
    for kind in ("paged", "recurrent"):
        seen[kind] = sm.cache_bytes().value(model="nemotron_h", kind=kind)
    seen["slots"] = sm.state_slots_in_use().value(model="nemotron_h")
    seen["pages"] = sm.kv_pages_in_use().value(model="nemotron_h",
                                               pool="target")
    toks.extend(stream)
    assert len(toks) == 30
    pool = batcher.pool
    assert seen["slots"] == 1 and seen["pages"] >= 16 // PAGE
    assert seen["paged"] == seen["pages"] * PAGE * 2 * 16 * 4
    assert seen["recurrent"] == sum(a.nbytes // SLOTS
                                    for a in pool.arrays[2:])
    names = {e["name"] for e in tracer().events()}
    assert {"serving.prefill", "serving.state.write", "serving.decode.step",
            "serving.loop.fetch", "serving.loop.dispatch"} <= names
    admits = [e for e in tracer().events() if e["name"] == "serving.prefill"]
    assert any(e.get("args", {}).get("replica") == "nemotron_h"
               for e in admits)
    assert sm.state_slots_in_use().value(model="nemotron_h") == 0


def test_preempt_replay_and_evacuate_return_the_same_tokens(ref, weights,
                                                            batcher):
    """A preempted sequence restarts from its prompt: prefill rebuilds
    pages, SSD states and windows in whichever slot it gets, the replay
    is teacher-forced, and the client sees each token once.  ``evacuate``
    hands the sequences over reset the same way."""
    from deeplearning4j_tpu.remote.scheduler import _Seq
    prompts = _prompts([9, 6], seed=7)
    want = [np.asarray(batcher.submit(
        {"tokens": p, "maxNewTokens": 24}))[0].tolist() for p in prompts]
    streams = [batcher.submitStream({"tokens": p, "maxNewTokens": 24})
               for p in prompts]
    got = [[next(s)] for s in streams]          # both are decoding now
    done = threading.Event()

    def preempt():                              # on the loop's own thread
        slot = next(i for i, s in enumerate(batcher._slotSeq)
                    if s is not None)
        batcher._preempt(slot)
        done.set()
    orig = batcher._growPages

    def once():
        if not done.is_set():
            preempt()
        return orig()
    batcher._growPages = once
    for g, s in zip(got, streams):
        g.extend(s)
    assert done.is_set()
    assert got == want
    assert batcher.pool.usedPages() == 0 and batcher.pool.stateSlots() == 0
    streams = [batcher.submitStream({"tokens": p, "maxNewTokens": 24})
               for p in prompts]
    firsts = [next(s) for s in streams]
    seqs = batcher.evacuate()
    assert len(seqs) == 2 and all(isinstance(s, _Seq) for s in seqs)
    assert all(not s.emitted and s.forced for s in seqs)
    assert sorted(s.forced[0] for s in seqs) == sorted(firsts)
    assert batcher.pool.usedPages() == 0 and batcher.pool.stateSlots() == 0
    for s in seqs:
        assert s.forced == want[prompts.index(s.tokens[0].tolist())][
            :len(s.forced)]


def test_restart_from_prompt_rebuilds_every_kind_of_state(family, weights):
    """``restartFromPrompt`` is the first admission's dispatch again: the
    same logits, K and V rows, SSD states, windows and counts, bit for
    bit."""
    lm = _lm(family, weights, "float32")
    prompt = np.asarray([[0] * 5 + _prompts([11])[0]], np.int32)
    first = lm.prefillRaw(prompt, lengths=[11])
    again = lm.restartFromPrompt(prompt, lengths=[11])
    assert len(first) == len(again) == 6
    for a, b in zip(first, again):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    k, v, ssm, conv, counts = (np.asarray(a) for a in first[1:])
    assert k.shape == v.shape == (1, 1, 1, 16, 16) and k[..., 5:, :].all()
    assert ssm.shape == (MAMBA_LAYERS, 1, 16, 8, 16) and ssm.any()
    assert conv.shape == (MAMBA_LAYERS, 1, 3, 192) and conv.any()
    assert counts.shape == (1, 1, 3) and counts[0, 0, :2].sum() == \
        TINY["num_experts_per_tok"] * EXPERT_LAYERS * 11


# -- what the traces and the benchmark read ------------------------------------
def test_each_bucket_prefills_under_its_own_name_and_the_scopes_are_there(
        family, weights):
    """The device trace tells a bucket's prefill from another's by the
    program's name (``prefill_mfu_pct.agent``), and the new mechanisms'
    instructions by their scopes: ``ssd_prefill`` and ``latent_moe`` in
    the prefills; ``ssd_step`` (``ssd_state_roofline_pct.agent``) and
    ``latent_moe/moe_share_step`` (``latent_moe_roofline_pct.agent``) in
    the step."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.remote import KVCachePool
    lm = _lm(family, weights, "float32")
    assert lm.compileCacheSize() == 0
    for bucket in (8, 16):
        logits = lm.prefillRaw(np.zeros((1, bucket), np.int32),
                               lengths=[5])[0]
        assert logits.shape == (1, TINY["vocab_size"])
        text = lm._prefillRawFn.at(bucket).lower(
            lm.params, np.zeros((1, bucket), np.int32),
            np.zeros((1,), np.int32)).as_text(debug_info=True)
        assert f"module @jit_prefill_{bucket} " in text
        assert "ssd_prefill" in text and "latent_moe" in text
        assert "ssd_step" not in text and "moe_share_step" not in text
    assert lm.compileCacheSize() == 2
    lm.dropCompiled()
    assert lm.compileCacheSize() == 0
    pool = KVCachePool.forSpec(lm.cacheSpec(), PAGE, 1 + SLOTS * (CAP // PAGE),
                               SLOTS, CAP // PAGE)
    i32 = lambda *s: jnp.zeros(s, jnp.int32)
    text = lm.buildPagedDecodeFn().lower(
        lm.params, *pool.arrays, i32(SLOTS, 1), i32(SLOTS, 7),
        jnp.asarray(pool.pageTable), i32(SLOTS), i32(SLOTS)
    ).as_text(debug_info=True)
    assert text.count("ssd_step") >= MAMBA_LAYERS
    assert text.count("latent_moe/moe_share_step") >= EXPERT_LAYERS
    assert "ssd_prefill" not in text


def test_published_configuration_counts_its_parameters(ref, family):
    """``jax.eval_shape`` of the published sizes as the benchmark's
    configuration cuts them: 4.65 B parameters in published blocks 27-37
    with 128 of 512 experts held and a quarter of the vocabulary, every
    width as published; whole, the same shapes give 120.67 B (the model is
    described as 120B-A12B; the prediction module is not counted)."""
    import jax
    with open(os.path.join(REPO, "benchmark", "configs",
                           "nemotron3_super.json")) as f:
        config = json.load(f)
    assert config["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    pub = config["published"]
    assert {k: pub[k] for k in pub if k != "hybrid_override_pattern"} == {
        "num_hidden_layers": 88, "n_routed_experts": 512,
        "vocab_size": 131072, "num_nextn_predict_layers": 1}
    pattern = pub["hybrid_override_pattern"]
    assert (len(pattern), pattern.count("M"), pattern.count("E"),
            pattern.count("*")) == (88, 40, 40, 8)
    first = config["first_block"]
    assert pattern[first:first + 11] == config["hybrid_override_pattern"] \
        == "MEMEMEMEM*E"
    assert [config[k] for k in (
        "hidden_size", "mamba_num_heads", "mamba_head_dim", "n_groups",
        "ssm_state_size", "conv_kernel", "chunk_size", "expand",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "moe_intermediate_size", "moe_latent_size",
        "moe_shared_expert_intermediate_size", "router_width",
        "num_experts_per_tok", "n_group", "topk_group",
        "routed_scaling_factor")] == [
        4096, 128, 64, 8, 128, 4, 128, 2, 32, 2, 128, 2688, 1024, 5376, 512,
        22, 1, 1, 5]
    assert {"no_rotary", "latent_moe", "dt_not_clamped", "router",
            "gate_then_norm"} <= set(config["assumed"])
    assert "4 chips share each layer" in config["deployment"]
    empty = {"emb": None, "head": None, "norm_f": None, "layers": []}
    lm = family.build_lm(config, empty, config["serving"]["capacity"])
    assert (lm.config.expertsHeld, lm.config.routerWidth,
            lm.config.firstBlock) == ((0, 128), 512, 27)
    shapes = jax.eval_shape(lm._init_params)
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert n == ref.param_count(config) == 4_648_163_712
    assert ref.param_count(ref.published(config)) == 120_668_707_840
    spec = lm.cacheSpec()
    assert (spec.pagedLayers, spec.pagedPools, spec.rowWidth) == (1, 2, 256)
    assert spec.slotState[0][1] == (5, 128, 64, 128)
    assert spec.slotState[1][1] == (5, 3, 10240)
    s = config["serving"]
    assert s["num_pages"] == s["max_slots"] * (s["capacity"]
                                               // s["page_size"]) + 1


@pytest.mark.parametrize("active", ["all", "some", "none"])
def test_ssd_state_kernel_is_the_recurrence_and_leaves_idle_slots_alone(
        active):
    """The kernel of the step's pass (interpret mode: one slot's states
    of one layer a place of the grid, the pool aliased) against the
    ``jax.numpy`` form it stands for off one TPU: layer 1 of 3 updated,
    the other layers and the idle slots' states bit for bit as they
    were."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.nlp import mamba
    rs = np.random.RandomState(3)
    L, S, H, P, G, N = 3, 4, 16, 8, 2, 128
    f = lambda *s: jnp.asarray(rs.standard_normal(s).astype(np.float32))
    pool = f(L, S, H, P, N)
    decay = jnp.asarray(rs.uniform(0.2, 1.0, (S, H)).astype(np.float32))
    xd, B, C = f(S, H, P), f(S, G, N), f(S, G, N)
    on = jnp.asarray({"all": [True] * 4, "some": [True, False, True, False],
                      "none": [False] * 4}[active])
    want, yw = mamba._ssd_state_plain(pool, decay, xd, B, C, on, li=1)
    got, yg = mamba._ssd_state_kernel_form(pool, decay, xd, B, C, on, li=1,
                                           interpret=True)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    live = np.asarray(on)
    assert np.abs(np.asarray(yg) - np.asarray(yw))[live].max(initial=0) < 1e-4
    for layer in (0, 2):
        np.testing.assert_array_equal(np.asarray(got[layer]),
                                      np.asarray(pool[layer]))
    np.testing.assert_array_equal(np.asarray(got[1])[~live],
                                  np.asarray(pool[1])[~live])
    if active != "none":
        assert np.abs(np.asarray(got[1] - pool[1])).max() > 0.1
    # through the primitive, off the TPU: the plain form
    eager, ye = mamba.ssd_state_step(pool, 1, decay, xd, B, C, on)
    assert np.abs(np.asarray(eager) - np.asarray(want)).max() < 1e-5
    assert mamba.ssd_step_kernel_lowerings() == 0
