"""Ahead-of-time compiles for a DESCRIBED TPU v5e (no chip attached): what
the chip's compiler makes of the serving tier's paged programs at real
widths.  Nothing runs, so these say nothing of results or times — they
guard the program's shape: no pool-sized layout copy, temporaries under
one pool.

The topology is described inside a fixture (never at import): one process
at a time may load libtpu, and under xdist every worker imports this file.
Keep every such compile in THIS file, so one worker holds the library.
"""
import functools
import os
import re

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

pytestmark = pytest.mark.cbatch

# gpt2_xl as the serving cells run it (benchmark/configs/gpt2_xl.json):
# 48 layers (the whole step compiles in ~10 s; at fewer layers the logits'
# temporaries outweigh the pool and the bound on them says nothing), and
# the cells' pool: 4 slots, 129 pages of 16 positions
LAYERS, HEADS, HEAD_SIZE, VOCAB, MAX_LEN = 48, 25, 64, 50257, 1024
SLOTS, PAGE_SIZE, NUM_PAGES = 4, 16, 129
PER_SEQ = MAX_LEN // PAGE_SIZE
# the width the model holds its tied table in: 1,600 columns are 12.5 lane
# tiles of 128, padded with zeros to 13 (nlp/transformer.py:lane_aligned)
HIDDEN = HEADS * HEAD_SIZE
TABLE_WIDTH = -(-HIDDEN // 128) * 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def paged(one_chip):
    """The model's hooks and its arguments as shapes on the described
    chip: ``(lm, params, pool, i32)``, the tied table in the shape the
    model holds it in (``(VOCAB, TABLE_WIDTH)``).  ``eval_shape``
    allocates nothing."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nlp.transformer import (TransformerLM,
                                                    TransformerLMConfig)
    from deeplearning4j_tpu.remote import KVCachePool

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    # a one-layer model of distinct toy sizes gives the parameter tree;
    # its sizes are then read as the real ones (drawing 1.56 B weights
    # on the host to learn their shapes would take minutes); the toy's
    # table is held one lane tile wide, 128 columns, like no other leaf
    lm = TransformerLM(TransformerLMConfig(vocabSize=3, nLayers=1, nHeads=1,
                                           headSize=8, ffnMult=4, maxLen=5))
    real = {3: VOCAB, 5: MAX_LEN, 8: HIDDEN, 32: 4 * HIDDEN,
            128: TABLE_WIDTH}
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        tuple(real[n] for n in a.shape), a.dtype), lm.params)
    params["layers"] = params["layers"] * LAYERS
    params = on_chip(params)
    lm.config = TransformerLMConfig(vocabSize=VOCAB, nLayers=LAYERS,
                                    nHeads=HEADS, headSize=HEAD_SIZE,
                                    maxLen=MAX_LEN)
    pool = on_chip(jax.eval_shape(lambda: KVCachePool(
        LAYERS, HEADS, HEAD_SIZE, PAGE_SIZE, NUM_PAGES, SLOTS, PER_SEQ).k))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    return lm, params, pool, i32


def _pool_copies(compiled, pool):
    """Instructions of the optimized program whose result has the pool's
    shape and that are a ``copy`` (a change of layout: it moves the whole
    pool once)."""
    shape = "f32[" + ",".join(str(n) for n in pool.shape) + "]"
    found = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?(%?[\w.\-]+) = (\S+) copy\(", line)
        if m and m.group(2).startswith(shape):
            found.append(m.group(1))
    return found


def _table_copies(compiled):
    """``copy`` instructions of the optimized program whose result is as
    tall as the tied table (``f32[50257,...]``): each moves the whole
    table, 322 MB, every call."""
    return re.findall(rf"(%?[\w.\-]+) = f32\[{VOCAB},\d+\]\S* copy\(",
                      compiled.as_text())


def _table_as_stored(compiled):
    """The tied table as the program's ``entry_computation_layout`` has
    it, ``f32[50257,W]{minor-to-major...}``: how the array lies in HBM."""
    return re.search(rf"f32\[{VOCAB},\d+\]\{{[\d,]+", compiled.as_text()
                     .splitlines()[0]).group(0)


def _with_plain_table(params):
    """``params`` with the table as the checkpoint has it, ``(VOCAB,
    1600)``: what a caller hands a program around the model's setter."""
    import jax
    emb = params["emb"]
    return {**params, "emb": jax.ShapeDtypeStruct(
        (VOCAB, HIDDEN), emb.dtype, sharding=emb.sharding)}


def _lower_step(lm, params, pool, i32):
    return lm.buildPagedDecodeFn().lower(
        params, pool, pool, i32(SLOTS, 1), i32(SLOTS, 1),
        i32(SLOTS, PER_SEQ), i32(SLOTS), i32(SLOTS))


def _lower_prefill(lm, params, pool, i32):
    return lm._prefillRawFn.lower(params, i32(1, 256), i32(1))


def _assert_in_place(compiled, pool, what):
    poolBytes = pool.size * pool.dtype.itemsize
    copies = _pool_copies(compiled, pool)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert not copies, (
        f"{what}: {len(copies)} pool-shaped copies {copies[:6]} "
        f"(temp {temp / 1e9:.2f} GB, one pool {poolBytes / 1e9:.2f} GB)")
    assert temp < poolBytes, (
        f"{what}: temporaries {temp / 1e9:.3f} GB are not under one "
        f"pool's {poolBytes / 1e9:.3f} GB")


def _assert_one_step_program(compiled, cache):
    """The step, with the choice between the host's token and the step
    before's output inside it, is ONE program under the name the traces
    know (``jit_step``), and every cache array it is given comes back
    aliased."""
    text = compiled.as_text()
    assert text.startswith("HloModule jit_step"), text[:80]
    assert text.count("\nHloModule ") == 0
    cacheBytes = sum(a.size * a.dtype.itemsize for a in cache)
    assert compiled.memory_analysis().alias_size_in_bytes >= cacheBytes


@pytest.fixture(scope="module")
def paged_step(paged):
    """``(the compiled decode step, the attention kernels lowered for it,
    their MXU passes)``: compiled once for the tests that read it."""
    from deeplearning4j_tpu.nn.conf.attention import (
        paged_kernel_kv_passes, paged_kernel_lowerings)
    lm, params, pool, i32 = paged
    before = paged_kernel_lowerings()
    compiled = _lower_step(lm, params, pool, i32).compile()
    return compiled, paged_kernel_lowerings() - before, \
        paged_kernel_kv_passes()


def test_paged_decode_step_updates_the_pool_in_place(paged, paged_step):
    lm, params, pool, i32 = paged
    compiled, kernelsLowered, kvPasses = paged_step
    _assert_in_place(compiled, pool, "jit_step")
    _assert_one_step_program(compiled, [pool, pool])
    # every layer attends through the kernel that reads the live pages
    # where they lie: lowered for one TPU, so chosen with no knob, under
    # JAX_PLATFORMS=cpu
    text = compiled.as_text()
    assert kernelsLowered == LAYERS
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          text)) == LAYERS
    # a float32 pool enters the MXU in three bfloat16 pieces
    assert kvPasses == 3
    # so no slot's capacity is gathered (K or V of all four slots, 256
    # pages of 16 rows) and no gathered row is re-laid into heads
    # (f32[256,16,1600], f32[4,1024,25,64])
    gathered = f"f32[{SLOTS * PER_SEQ},{PAGE_SIZE},{HEADS * HEAD_SIZE}]"
    split = f"f32[{SLOTS},{MAX_LEN},{HEADS},{HEAD_SIZE}]"
    assert gathered not in text and split not in text
    # the tied table is read as it lies by the lookup and by the head:
    # held in whole lane tiles (13 of 128 columns) it lies row-minor in
    # HBM and one layout serves both.  Found: 69,622,272 bytes of
    # temporaries; 407,173,632 with the table 1,600 wide, 322 MB of them
    # the whole table copied every step (``copy.725``: at 12.5 lane tiles
    # the array lies vocabulary-minor, whatever form the head's product is
    # written in, and the lookup gets a row-minor copy -- the case
    # below); one pool is 634 MB
    assert _table_as_stored(compiled) == f"f32[{VOCAB},{TABLE_WIDTH}]{{1,0"
    assert not _table_copies(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9


def test_prefill_reads_the_tied_table_as_it_lies(paged):
    """The 256 bucket's prefill (``jit_run``) looks up 256 rows and
    multiplies one: no copy of the table either (found with the table
    1,600 wide: ``copy.394``, 384,142,848 bytes of temporaries)."""
    compiled = _lower_prefill(*paged).compile()
    assert not _table_copies(compiled)
    # found: 0 bytes beside its outputs (the K/V stacks, 157 MB)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9


def test_laying_the_table_out_holds_no_second_table(paged):
    """The one-time pad (``_pad_columns``, run when a ``(50257, 1600)``
    tree is assigned to ``lm.params``): the compiler keeps such an array
    vocabulary-minor and the padded one row-minor, so one ``jnp.pad`` of
    the whole table holds a table-sized temporary beside its result
    (found: 334,565,376 bytes, and as much under ``peak_bytes_reserved``
    on the chip); block by block into a table updated in place, none."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nlp.transformer import _pad_columns
    _lm, params, _pool, _i32 = paged
    plain = _with_plain_table(params)["emb"]
    pad = TABLE_WIDTH - HIDDEN
    whole = jax.jit(lambda e: jnp.pad(e, ((0, 0), (0, pad)))).lower(
        plain).compile()
    assert whole.memory_analysis().temp_size_in_bytes > 0.33e9
    compiled = _pad_columns.lower(plain, pad).compile()
    assert compiled.memory_analysis().output_size_in_bytes > 0.33e9
    assert compiled.memory_analysis().temp_size_in_bytes < 1e6   # found: 0


@pytest.mark.parametrize("lower", [_lower_step, _lower_prefill],
                         ids=["step", "prefill"])
def test_a_table_of_12_5_lane_tiles_is_copied_whole(paged, lower):
    """What the padding is for: handed the table as the checkpoint has
    it, ``(50257, 1600)``, the same code gives the parent's program --
    one table-sized copy a call and its 322 MB among the temporaries.
    An array whose rows are no whole number of lane tiles lies
    VOCABULARY-minor in HBM (dimension 0 minor), which suits the head;
    the lookup needs rows and gets a row-minor copy of the whole table."""
    lm, params, pool, i32 = paged
    compiled = lower(lm, _with_plain_table(params), pool, i32).compile()
    assert _table_as_stored(compiled) == f"f32[{VOCAB},{HIDDEN}]{{0,1"
    assert len(_table_copies(compiled)) == 1
    assert compiled.memory_analysis().temp_size_in_bytes > 0.32e9


def _rows_and_lanes(topo, one_chip, chips):
    """Where a test of a lowering rule puts its small operands and its
    pools: on the one chip, or over four with the pools' lanes split (the
    tensor-parallel replica's placement)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    if chips == 1:
        return one_chip, one_chip
    mesh = Mesh(np.array(topo.devices), ("model",))
    return NamedSharding(mesh, P()), NamedSharding(
        mesh, P(None, None, None, "model"))


@pytest.mark.parametrize("chips", [1, 4])
def test_paged_attention_is_the_kernel_for_one_tpu_only(topo, one_chip,
                                                        chips):
    """The choice is made where the program is lowered: for one described
    TPU the kernel; for four with the pool's lanes split over them (the
    tensor-parallel replica's placement, which a Mosaic kernel cannot be
    partitioned over) the gathered reference — no option says which."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.conf.attention import (
        paged_attention, paged_kernel_lowerings)
    S, h, d, perSeq = 2, 4, 32, 4
    rows, lanes = _rows_and_lanes(topo, one_chip, chips)

    def sds(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    new = sds((S, h, 1, d), jnp.float32, rows)
    pool = sds((2, 1 + S * perSeq, PAGE_SIZE, h * d), jnp.float32, lanes)
    before = paged_kernel_lowerings()
    text = jax.jit(
        lambda q, k, v, pk, pv, pt, pos, start: paged_attention(
            q, k, v, pk, pv, 1, pt, pos, start)).lower(
        new, new, new, pool, pool, sds((S, perSeq), jnp.int32, rows),
        sds((S,), jnp.int32, rows), sds((S,), jnp.int32, rows)).as_text()
    kernels = text.count("tpu_custom_call")
    assert kernels == paged_kernel_lowerings() - before
    assert kernels == (1 if chips == 1 else 0)


# the rows the kernel's callers bring: KV heads, head size, the pool's
# dtype, pages a slot, slots, layers, query heads a KV head.  SambaY's one
# paged layer keeps 20 KV heads of 64 in differential pairs: to the kernel
# 10 heads of 128 lanes with 4 of its 40 query heads on each
KERNEL_ROWS = {
    "olmo_hybrid_30x128_bf16": (30, 128, "bfloat16", 288, 16, 4, 1),
    "gpt2_xl_25x64_f32": (25, 64, "float32", 64, 4, 48, 1),
    "grouped_kv_20x64_bf16": (10, 128, "bfloat16", 160, 32, 1, 4),
    # Jamba2-3B: ONE KV head of 128 lanes, 20 query heads on it, 64 slots
    "one_kv_head_1x128_bf16": (1, 128, "bfloat16", 256, 64, 2, 20),
    # SambaY's rings: a slot's 512 rows as 4 fixed pages of 128 rows (a
    # page a chunk, where the others' chunk is 8 pages of 16), 8 window
    # layers in one stack
    "ring_10x128_bf16_pages_of_128": (10, 128, "bfloat16", 4, 32, 8, 4, 128),
}


@pytest.mark.parametrize("row", sorted(KERNEL_ROWS))
def test_paged_kernel_alone_compiles_at_its_callers_rows(one_chip, row):
    """``_pages_call`` by itself (a kernel compiles in a second or two):
    Mosaic takes the body at each row shape, the last lane tile of 1,600
    lanes half full, four query heads' rows on each KV head of SambaY's,
    and nothing the size of a page is set aside in HBM for it (its blocks
    are VMEM scratch)."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.conf import attention as A
    h, d, dtype, perSeq, slots, layers, nRep, *ps = KERNEL_ROWS[row]
    ps = ps[0] if ps else PAGE_SIZE

    def sds(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    C = A._CHUNK_ROWS // ps
    places = slots * -(-perSeq // C)
    pool = sds((layers, 1 + slots * perSeq, ps, h * d), dtype)
    compiled = A._pages_call.lower(
        sds((1,)), sds((places * C,)), sds((places,)), sds((places,)),
        sds((places,)), sds(()), sds((slots,)), sds((slots,)),
        sds((slots, nRep, h * d), jnp.float32), pool, pool, headSize=d,
        tq=1, interpret=False).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes \
        < ps * h * d * pool.dtype.itemsize


def test_latent_kernel_alone_compiles_at_its_callers_row(one_chip):
    """``_latent_call`` by itself at the row its one caller brings: 128
    heads as the rows of one operand against pages of 640 bfloat16 lanes
    (512 latent + 64 rotated + 64 of padding to whole lane tiles), 384
    pages a slot, 32 slots, 5 layers.  Nothing the size of the pool is set
    aside in HBM: a pool of 576 lanes, which Mosaic cannot read as it
    lies, would come back as a pool-sized copy re-laid into 640 (found:
    1.26 GB of temporaries a call), which is why the row is stored padded."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.conf import attention as A
    h, W, vw, perSeq, slots, layers = 128, 640, 512, 384, 32, 5

    def sds(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def compiled(width):
        C = A._LATENT_CHUNK_ROWS // PAGE_SIZE
        places = slots * -(-perSeq // C)
        pool = sds((layers, 1 + slots * perSeq, PAGE_SIZE, width),
                   jnp.bfloat16)
        return A._latent_call.lower(
            sds((1,)), sds((places * C,)), sds((places,)), sds((places,)),
            sds((places,)), sds(()), sds((slots,)), sds((slots,)),
            sds((slots, h, width), jnp.bfloat16), pool, valueWidth=vw, tq=1,
            interpret=False).compile()
    padded = compiled(W)
    assert padded.as_text().count("tpu_custom_call") == 1
    assert padded.memory_analysis().temp_size_in_bytes < 1e6
    assert compiled(576).memory_analysis().temp_size_in_bytes > 1e9


def _kernel_names(text):
    """The Pallas kernels of a compiled program, by the name each call's
    instruction carries."""
    return re.findall(r"^\s*(?:ROOT )?%?([a-z_]+)[\w.\-]* = .*? custom-call\(.*"
                      r"custom_call_target=\"tpu_custom_call\"", text, re.M)


def test_expert_step_kernel_alone_compiles_at_its_callers_shapes(one_chip):
    """``_hit_call`` by itself, under the package's x64, at the shapes its
    one caller brings: 32 slots' tokens against 16 held experts of
    ``(7680, 2048)`` bfloat16, the hit list and its length as data (the
    grid's first dimension is dynamic).  The stacked weights go in whole:
    nothing is set aside in HBM, no expert is copied out, and the three
    double-buffered blocks fit the 64 MB of VMEM the call asks for."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.parallel import moe
    assert jax.config.jax_enable_x64
    n, d, f, T = 16, 7680, 2048, 32

    def sds(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    up, down = sds((n, d, f), jnp.bfloat16), sds((n, f, d), jnp.bfloat16)
    compiled = moe._hit_call.lower(
        sds((n,)), sds(()), sds((T, d), jnp.bfloat16),
        sds((n, T, 1), jnp.float32), up, up, down,
        interpret=False).compile()
    text = compiled.as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert "moe_share_step" in text
    # found 0: x, the output block and the weights' blocks are VMEM windows
    assert compiled.memory_analysis().temp_size_in_bytes < 1e6
    assert not re.search(r"= bf16\[[\d,]*(?:7680,2048|2048,7680)\]\S* "
                         r"(?:copy|dynamic-slice|fusion|slice)\(", text)


@pytest.mark.parametrize("rows,n,d,f,matrices", [
    (7 * 4096, 128, 1024, 2688, 2),     # nemotron3_super: in the latent
    (3 * 8192, 128, 2560, 768, 3),      # ling3_flash
    (4096, 16, 7680, 2048, 3),          # openpangu: four tiles of 512
    (4096, 16, 2048, 768, 3)])          # keye_vl: a block of 4,096 tokens
def test_grouped_expert_kernel_alone_compiles_at_its_callers_shapes(
        one_chip, rows, n, d, f, matrices):
    """A pass of ``moe_share_grouped`` through the kernel by itself
    (``_rows_kernel``: the walk of visits built from the groups' ends,
    then ``_grouped_call``), under the package's x64, at the largest pass
    of each of its four callers in bfloat16: the stacked weights go in
    whole, the only thing set aside in HBM is the pairs' weights as a
    column (one lane of 128 used: 15 MB at 28,672 rows), and the
    double-buffered blocks fit the 64 MB of VMEM the call asks for."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.parallel import moe
    assert jax.config.jax_enable_x64
    bf16 = jnp.bfloat16

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    E = [sds((n, d, f), bf16)] * (matrices - 1) + [sds((n, f, d), bf16)]
    act = moe.relu2 if matrices == 2 else moe._silu_gate
    before = moe.moe_grouped_kernel_lowerings()
    compiled = jax.jit(functools.partial(moe._rows_kernel, act=act)).lower(
        sds((rows, d), bf16), sds((rows,), jnp.float32),
        sds((n,), jnp.int32), *E).compile()
    text = compiled.as_text()
    assert _kernel_names(text) == ["moe_share_grouped"]
    assert "ragged-dot" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < rows * 512 + 1e6
    assert not re.search(rf"= bf16\[[\d,]*(?:{d},{f}|{f},{d})\]\S* "
                         r"(?:copy|dynamic-slice|fusion|slice)\(", text)
    # called by hand, not through the primitive's rule: nothing counted
    assert moe.moe_grouped_kernel_lowerings() == before


@pytest.mark.parametrize("bucket", [16, 256])
def test_paged_prefill_write_updates_the_pool_in_place(paged, bucket):
    import jax
    import jax.numpy as jnp
    lm, _params, pool, i32 = paged
    stack = jax.ShapeDtypeStruct((LAYERS, HEADS, bucket, HEAD_SIZE),
                                 jnp.float32, sharding=pool.sharding)
    compiled = lm.buildPagedPrefillWriteFn().lower(
        pool, pool, stack, stack, i32(bucket // PAGE_SIZE)).compile()
    _assert_in_place(compiled, pool, f"jit_write[{bucket}]")


# -- Phi-4-mini-flash-reasoning as benchmark/configs/phi4_mini_flash.json
# serves it: all 32 layers and 200064 rows in bfloat16, 32 slots of 2,560
# positions (160 pages of 16) -- three kinds of cache state in one pool
PHI_SLOTS, PHI_CAP = 32, 2560
HBM_BYTES = 15.75e9         # what the compiler grants a program on a v5e


@pytest.fixture(scope="module")
def sambay(one_chip):
    """``(lm, params, pool arrays, i32)`` as shapes on the described
    chip, at the published sizes."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nlp.sambay import SambaYConfig, SambaYLM
    from deeplearning4j_tpu.remote import KVCachePool

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)
    lm = SambaYLM(SambaYConfig(
        vocabSize=200064, nLayers=32, hiddenSize=2560, nHeads=40,
        nKvHeads=20, ffnSize=10240, window=512, stateSize=16, convKernel=4,
        expand=2, dtRank=160, maxLen=PHI_CAP), params={})
    params = on_chip(jax.eval_shape(lm._init_params))
    perSeq = PHI_CAP // PAGE_SIZE
    pool = on_chip(jax.eval_shape(lambda: KVCachePool.forSpec(
        lm.cacheSpec(), PAGE_SIZE, 1 + PHI_SLOTS * perSeq, PHI_SLOTS,
        perSeq).arrays))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    return lm, params, pool, i32


def _whole_array_copies(compiled, arrays):
    """``copy`` instructions whose result is as large as one of the
    pool's arrays."""
    shapes = {str(a.dtype).replace("bfloat16", "bf16").replace(
        "float32", "f32") + "[" + ",".join(str(n) for n in a.shape) + "]"
        for a in arrays}
    found = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?(%?[\w.\-]+) = (\S+) copy\(", line)
        if m and any(m.group(2).startswith(s) for s in shapes):
            found.append(m.group(1))
    return found


@pytest.fixture(scope="module")
def sambay_step(sambay):
    """As ``paged_step``, SambaY's."""
    from deeplearning4j_tpu.nn.conf.attention import (
        paged_kernel_kv_passes, paged_kernel_lowerings)
    lm, params, pool, i32 = sambay
    perSeq = PHI_CAP // PAGE_SIZE
    before = paged_kernel_lowerings()
    compiled = lm.buildPagedDecodeFn().lower(
        params, *pool, i32(PHI_SLOTS, 1), i32(PHI_SLOTS, 1),
        i32(PHI_SLOTS, perSeq), i32(PHI_SLOTS), i32(PHI_SLOTS)).compile()
    return compiled, paged_kernel_lowerings() - before, \
        paged_kernel_kv_passes()


def test_sambay_decode_step_fits_and_updates_its_state_in_place(
        sambay, sambay_step):
    lm, params, pool, i32 = sambay
    compiled, kernelsLowered, kvPasses = sambay_step
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    # the six arrays are donated and come back aliased, not copied
    _assert_one_step_program(compiled, pool)
    assert not _whole_array_copies(compiled, pool)
    # the full layer and the 7 cross layers read the ONE paged layer
    # through the kernel, its bfloat16 rows in one MXU pass a tile, and
    # each of the 8 window layers its ring, as its slots' fixed pages
    text = compiled.as_text()
    kinds = lm.config.layerKinds()
    assert kinds.count("full") + kinds.count("cross") == 8
    assert kinds.count("window") == 8
    # (one lowering serves the eight readers: they bind the same read of
    # layer 0; one for each ring layer: what the batcher's gauge
    # ``ring_attention_kernel`` counts)
    spec = lm.cacheSpec()
    assert kernelsLowered == spec.pagedLayers + spec.ringLayers == 9
    assert kvPasses == 1
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 16
    assert len(re.findall(r"%paged_attention[\w.]* = ", text)) == 16
    # so no slot's capacity is gathered (32 slots of 2,560 rows of 1,280
    # lanes), no gathered row is re-laid into its 10 groups, and no ring
    # layer is cut out of its stack (32 slots of 512 rows) or re-laid
    # group-major for a matmul: the stacks are read where they lie, as
    # pages of 128 rows
    for rows in ((PHI_SLOTS, PHI_CAP), (PHI_SLOTS, 512)):
        assert "bf16[%d,%d,1280]" % rows not in text
        assert "bf16[%d,%d,10,128]" % rows not in text
    assert "bf16[8,%d,128,1280]" % (PHI_SLOTS * 4) in text
    # found: 38,221,824 bytes (75,677,184 with a ring layer's slice and
    # its re-layout; 790,092,288 with the paged layer's gather and its two)
    assert mem.temp_size_in_bytes < 0.05e9


def test_sambay_prefill_and_admission_write_fit(sambay):
    import jax
    lm, params, pool, i32 = sambay
    bucket = 512
    compiled = lm._prefillRawFn.lower(params, i32(1, bucket),
                                      i32(1)).compile()
    mem = compiled.memory_analysis()
    poolBytes = sum(a.size * a.dtype.itemsize for a in pool)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes + poolBytes \
        < HBM_BYTES
    state = jax.eval_shape(lm._prefillRawFn, params, i32(1, bucket),
                           i32(1))[1:]
    parts = [jax.ShapeDtypeStruct(p.shape[:1] + p.shape[2:], p.dtype,
                                  sharding=pool[0].sharding) for p in state]
    write = lm.buildPagedPrefillWriteFn().lower(
        *pool, *parts, i32(bucket // PAGE_SIZE), i32()).compile()
    assert not _whole_array_copies(write, pool)
    assert write.memory_analysis().temp_size_in_bytes < 64e6


# -- Olmo-Hybrid-7B as benchmark/configs/olmo_hybrid_7b.json serves it: 16 of
# its 32 layers at every published width in bfloat16, 16 slots of 4,608
# positions (288 pages of 16) for the 4 full layers, and 12 matrix-valued
# delta states a slot beside them
OLMO_SLOTS, OLMO_CAP, OLMO_BUCKET = 16, 4608, 4096


@pytest.fixture(scope="module")
def olmo(one_chip):
    """``(lm, params, pool arrays, i32, the compiled decode step, the
    attention kernels lowered for it)``: shapes on the described chip, at
    the published widths; the step is compiled once for both tests."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nlp.olmo_hybrid import (OlmoHybridConfig,
                                                    OlmoHybridLM)
    from deeplearning4j_tpu.nn.conf.attention import (
        paged_kernel_kv_passes, paged_kernel_lowerings)
    from deeplearning4j_tpu.remote import KVCachePool

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)
    lm = OlmoHybridLM(OlmoHybridConfig(
        vocabSize=100352, nLayers=16, hiddenSize=3840, nHeads=30,
        ffnSize=11008, linHeads=30, linKeyDim=96, linValueDim=192,
        maxLen=OLMO_CAP), params={})
    params = on_chip(jax.eval_shape(lm._init_params))
    perSeq = OLMO_CAP // PAGE_SIZE
    pool = on_chip(jax.eval_shape(lambda: KVCachePool.forSpec(
        lm.cacheSpec(), PAGE_SIZE, 1 + OLMO_SLOTS * perSeq, OLMO_SLOTS,
        perSeq).arrays))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    before = paged_kernel_lowerings()
    step = lm.buildPagedDecodeFn().lower(
        params, *pool, i32(OLMO_SLOTS, 1), i32(OLMO_SLOTS, 1),
        i32(OLMO_SLOTS, perSeq), i32(OLMO_SLOTS), i32(OLMO_SLOTS)).compile()
    # its bfloat16 pool enters the MXU as it is stored: one pass a tile
    assert paged_kernel_kv_passes() == 1
    return lm, params, pool, i32, step, paged_kernel_lowerings() - before


def test_olmo_hybrid_decode_step_fits_and_updates_its_state_in_place(olmo):
    lm, params, pool, i32, compiled, kernelsLowered = olmo
    perSeq = OLMO_CAP // PAGE_SIZE
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    # k, v, delta and conv are donated and come back aliased, not copied
    _assert_one_step_program(compiled, pool)
    assert not _whole_array_copies(compiled, pool)
    # the four full layers attend through the kernel that reads the live
    # pages where they lie: bfloat16 rows of 3,840 lanes, 288 pages a slot
    text = compiled.as_text()
    assert kernelsLowered == 4
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          text)) == 4
    assert f"bf16[{OLMO_SLOTS * perSeq},{PAGE_SIZE},3840]" not in text


def _scan_lengths(jaxpr):
    """Trip counts of every ``scan`` in a jaxpr, nested ones included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            found.append(int(eqn.params["length"]))
        for sub in eqn.params.values():
            for j in sub if isinstance(sub, (list, tuple)) else [sub]:
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    found += _scan_lengths(inner)
    return found


def test_olmo_hybrid_prefill_and_admission_write_fit(olmo):
    import jax
    lm, params, pool, i32, step, _ = olmo
    traced = lm._prefillRawFn.at(OLMO_BUCKET).trace(
        params, i32(1, OLMO_BUCKET), i32(1))
    # the chunked form: the only loops over positions are the scan over
    # the 64 chunks of each linear layer and the map over the 8 blocks of
    # queries of each full layer -- nothing runs 4,096 times
    assert sorted(_scan_lengths(traced.jaxpr.jaxpr)) == [8] * 4 + [64] * 12
    compiled = traced.lower().compile()
    mem = compiled.memory_analysis()
    # ISSUE 30's reckoning: what the step holds (the weights and the
    # pool, as the chip lays them out) with its temporaries, and beside
    # it the prefill of the largest bucket with what it hands to the
    # admission write, under 15.0 GB.  Found: 13.31 + 0.03 + 0.90 + 0.29
    # = 14.53 GB (before each block's output was held behind an
    # optimization barrier the prefill's temporaries alone were 2.89)
    step = step.memory_analysis()
    assert step.argument_size_in_bytes + step.temp_size_in_bytes \
        + mem.temp_size_in_bytes + mem.output_size_in_bytes < 15.0e9
    state = jax.eval_shape(lm._prefillRawFn, params, i32(1, OLMO_BUCKET),
                           i32(1))[1:]
    parts = [jax.ShapeDtypeStruct(p.shape[:1] + p.shape[2:], p.dtype,
                                  sharding=pool[0].sharding) for p in state]
    write = lm.buildPagedPrefillWriteFn().lower(
        *pool, *parts, i32(OLMO_BUCKET // PAGE_SIZE), i32()).compile()
    assert not _whole_array_copies(write, pool)
    poolBytes = sum(a.size * a.dtype.itemsize for a in pool)
    assert write.memory_analysis().alias_size_in_bytes >= poolBytes
    # found 126 MB: one stack of 4 x 4,096 rows re-laid into pages
    assert write.memory_analysis().temp_size_in_bytes < 0.2e9


# -- openPangu-Ultra-MoE-718B as benchmark/configs/openpangu_ultra_moe.json
# serves it: the leading dense layer and four expert layers holding 16 of
# 256 routed experts, an eighth of the vocabulary, every width as
# published, bfloat16; 32 slots of 6,144 positions (384 pages of 16) of one
# latent row a layer
PANGU_SLOTS, PANGU_CAP, PANGU_BUCKET = 32, 6144, 4096


@pytest.fixture(scope="module")
def pangu(one_chip):
    """``(lm, params, pool arrays, i32, the compiled decode step, the
    attention kernels and the expert kernels lowered for it)``: shapes on
    the described chip."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nlp.pangu_moe import PanguMoEConfig, PanguMoELM
    from deeplearning4j_tpu.nn.conf.attention import (
        paged_kernel_kv_passes, paged_kernel_lowerings)
    from deeplearning4j_tpu.parallel.moe import moe_step_kernel_lowerings
    from deeplearning4j_tpu.remote import KVCachePool

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)
    lm = PanguMoELM(PanguMoEConfig(
        vocabSize=19200, nLayers=5, denseLayers=1, hiddenSize=7680,
        nHeads=128, qRank=1536, kvRank=512, nopeDim=128, ropeDim=64,
        vDim=128, ffnSize=18432, expertSize=2048, nExperts=256,
        expertsPerToken=8, expertsHeld=(0, 16), maxLen=PANGU_CAP), params={})
    params = on_chip(jax.eval_shape(lm._init_params))
    perSeq = PANGU_CAP // PAGE_SIZE
    pool = on_chip(jax.eval_shape(lambda: KVCachePool.forSpec(
        lm.cacheSpec(), PAGE_SIZE, 1 + PANGU_SLOTS * perSeq, PANGU_SLOTS,
        perSeq).arrays))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    before = paged_kernel_lowerings(), moe_step_kernel_lowerings()
    # ``prev`` is a step's own output: the tokens and the six counts
    step = lm.buildPagedDecodeFn().lower(
        params, *pool, i32(PANGU_SLOTS, 1), i32(PANGU_SLOTS, 7),
        i32(PANGU_SLOTS, perSeq), i32(PANGU_SLOTS),
        i32(PANGU_SLOTS)).compile()
    assert paged_kernel_kv_passes() == 1
    return lm, params, pool, i32, step, (
        paged_kernel_lowerings() - before[0],
        moe_step_kernel_lowerings() - before[1])


def test_pangu_moe_decode_step_fits_and_reads_its_latent_rows_in_place(
        pangu):
    """Of the decode step at the cell's sizes: 9 kernel calls, 5 named for
    the latent kernel and 4 for the expert kernel."""
    lm, params, pool, i32, compiled, kernelsLowered = pangu
    perSeq = PANGU_CAP // PAGE_SIZE
    mem = compiled.memory_analysis()
    # found: 11.10 GB of arguments (9.84 of weights, 1.26 of latent rows:
    # ONE pool of 640 bfloat16 lanes a row, no V) + 0.02 of temporaries
    assert len(pool) == 2 and pool[0].shape == (
        5, 1 + PANGU_SLOTS * perSeq, PAGE_SIZE, 640)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    assert mem.temp_size_in_bytes < 0.1e9
    # the latent pool and the routing's counts are donated and come back
    # aliased, not copied
    _assert_one_step_program(compiled, pool)
    assert not _whole_array_copies(compiled, pool)
    # all five layers attend ABSORBED through the latent kernel over the
    # live pages: no slot's capacity is gathered, no key or value formed
    text = compiled.as_text()
    # (the four expert layers are one lowering: same shapes, same rule)
    assert kernelsLowered == (5, 1)
    kernels = _kernel_names(text)
    assert sorted(kernels) == ["moe_share_step"] * 4 \
        + ["paged_latent_attention"] * 5, kernels
    assert f"bf16[{PANGU_SLOTS * perSeq},{PAGE_SIZE},640]" not in text
    assert f"[{PANGU_SLOTS},{PANGU_CAP},128,128]" not in text
    # the four expert layers read the held experts where they lie, through
    # the kernel over the experts that were hit: the stacked weights go in
    # whole, no expert or stack of them is sliced out or copied (the
    # temporaries, found 14 MB, have no room for ONE projection of one
    # expert; the shared expert's ``Sdown`` has an expert's shape and is
    # prefetched by ``copy-start``, which is no ``copy``), and nothing is
    # computed for all 16 (no (slots, 16, 2048) product)
    assert not re.search(
        r"= bf16\[(?:16,|1,)?(?:7680,2048|2048,7680)\]\S* "
        r"(?:copy|dynamic-slice|slice)\(", text)
    assert mem.temp_size_in_bytes < 7680 * 2048 * 2
    assert not re.search(rf"\[{PANGU_SLOTS},(?:16,2048|32768)\]", text)


def test_pangu_moe_prefill_groups_its_experts_and_fits_beside_the_step(
        pangu, monkeypatch):
    import jax
    from deeplearning4j_tpu.nlp import latent
    from deeplearning4j_tpu.parallel import ring
    lm, params, pool, i32, step, _ = pangu
    # the program asks ``jax.default_backend()`` whether the flash kernel
    # can run, and here that is the CPU: steer it to the chip's choice
    for mod in (ring, latent):
        monkeypatch.setattr(mod, "_flash_refusal", lambda *a, **k: None)
    compiled = lm._prefillRawFn.at(PANGU_BUCKET).lower(
        params, i32(1, PANGU_BUCKET), i32(1)).compile()
    text = compiled.as_text()
    # every layer's unabsorbed attention is the flash kernel: no score of
    # 4,096 keys a query is held outside VMEM
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          text)) >= 5 + 4
    assert not re.search(rf"f32\[(?:1,)?128,\d+,{PANGU_BUCKET}\]", text)
    # the held experts are multiplied by GROUP (the grouped kernel, one
    # call an expert layer in place of the compiler's three ragged dots),
    # never all 16 over every token: no (tokens, 16, 2048) intermediate in
    # any layout
    assert "ragged-dot" not in text
    assert _kernel_names(text).count("moe_share_grouped") == 4
    assert not re.search(rf"\[(?:{PANGU_BUCKET},16,2048|16,{PANGU_BUCKET}"
                         rf",2048|{PANGU_BUCKET},32768)\]", text)
    mem = compiled.memory_analysis()
    # found: 11.10 + 0.02 (the step) + 1.45 + 0.03 (the 4,096 prefill:
    # queries, keys and values laid out for the flash kernel) = 12.60 GB
    # (1.39 with the blocked form, 256 queries of 128 heads a block)
    step = step.memory_analysis()
    assert step.argument_size_in_bytes + step.temp_size_in_bytes \
        + mem.temp_size_in_bytes + mem.output_size_in_bytes < 13.5e9
    state = jax.eval_shape(lm._prefillRawFn, params, i32(1, PANGU_BUCKET),
                           i32(1))[1:]
    parts = [jax.ShapeDtypeStruct(p.shape[:1] + p.shape[2:], p.dtype,
                                  sharding=pool[0].sharding) for p in state]
    write = lm.buildPagedPrefillWriteFn().lower(
        *pool, *parts, i32(PANGU_BUCKET // PAGE_SIZE), i32()).compile()
    assert not _whole_array_copies(write, pool)
    poolBytes = sum(a.size * a.dtype.itemsize for a in pool)
    assert write.memory_analysis().alias_size_in_bytes >= poolBytes
    assert write.memory_analysis().temp_size_in_bytes < 0.1e9


# -- AI21-Jamba2-3B as benchmark/configs/jamba2_3b.json serves it: whole, all
# 28 layers and 65,536 rows in bfloat16, 64 slots of 4,096 positions for the
# 2 attention layers (ONE KV head of 128 lanes), 26 Mamba states a slot
JAMBA_BUCKET = 2048


@pytest.fixture(scope="module")
def jamba(one_chip):
    """``(lm, params, pool arrays, i32, serving sizes, the compiled decode
    step, the attention kernels lowered for it)``: shapes on the described
    chip, at the published sizes and the configuration's own pool."""
    import json
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nlp.jamba import JambaConfig, JambaLM
    from deeplearning4j_tpu.nn.conf.attention import (
        paged_kernel_kv_passes, paged_kernel_lowerings)
    from deeplearning4j_tpu.remote import KVCachePool
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "jamba2_3b.json")) as f:
        serving = json.load(f)["serving"]
    slots, ps, cap = (serving[k] for k in ("max_slots", "page_size",
                                           "capacity"))

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)
    lm = JambaLM(JambaConfig(
        vocabSize=65536, nLayers=28, hiddenSize=2560, nHeads=20, nKvHeads=1,
        ffnSize=8192, attnPeriod=14, attnOffset=7, stateSize=16,
        convKernel=4, expand=2, dtRank=160, maxLen=cap), params={})
    params = on_chip(jax.eval_shape(lm._init_params))
    pool = on_chip(jax.eval_shape(lambda: KVCachePool.forSpec(
        lm.cacheSpec(), ps, serving["num_pages"], slots, cap // ps).arrays))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    before = paged_kernel_lowerings()
    step = lm.buildPagedDecodeFn().lower(
        params, *pool, i32(slots, 1), i32(slots, 1), i32(slots, cap // ps),
        i32(slots), i32(slots)).compile()
    assert paged_kernel_kv_passes() == 1
    return (lm, params, pool, i32, serving, step,
            paged_kernel_lowerings() - before)


def test_jamba_decode_step_fits_and_updates_its_state_in_place(jamba):
    lm, params, pool, i32, serving, compiled, kernelsLowered = jamba
    mem = compiled.memory_analysis()
    # weights 6.06 GB + pages 0.27 + state 0.55 + windows 0.05: found
    # 6.924 GB of arguments and 0.05-0.07 of temporaries
    assert 6.9e9 < mem.argument_size_in_bytes < 7.0e9
    assert mem.temp_size_in_bytes < 0.1e9
    # k, v, ssm and conv are donated and come back aliased, not copied
    _assert_one_step_program(compiled, pool)
    assert not _whole_array_copies(compiled, pool)
    # nor is the tied table re-laid for the head (gpt2_xl's copy.725)
    assert not re.search(r" = bf16\[(?:65536,2560|2560,65536)\]\S* copy\(",
                         compiled.as_text())
    # the two attention layers read their pages through the kernel: 20 query
    # heads on the ONE KV head's lane tile, nothing gathered over a slot's
    # capacity
    text = compiled.as_text()
    assert kernelsLowered == 2
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 2
    assert len(re.findall(r"%paged_attention[\w.]* = ", text)) == 2
    assert "bf16[%d,%d,128]" % (serving["max_slots"],
                                serving["capacity"]) not in text


def test_jamba_prefill_and_admission_write_fit(jamba):
    import jax
    lm, params, pool, i32, serving, step, _ = jamba
    ps = serving["page_size"]
    traced = lm._prefillRawFn.at(JAMBA_BUCKET).trace(
        params, i32(1, JAMBA_BUCKET), i32(1))
    # the shared sequential scan, eight positions an iteration, once a
    # Mamba layer, and the map over the 4 blocks of 512 queries of each
    # attention layer
    assert sorted(_scan_lengths(traced.jaxpr.jaxpr)) \
        == [4] * 2 + [JAMBA_BUCKET] * 26
    compiled = traced.lower().compile()
    text = compiled.as_text()
    assert text.startswith(f"HloModule jit_prefill_{JAMBA_BUCKET}")
    mem = compiled.memory_analysis()
    # ISSUE 38 reckoned 7.9 GB: what the step holds (weights and pool)
    # with its temporaries, and beside it the prefill of the largest bucket
    # with what it hands to the admission write.  Found: 6.924 + 0.066 +
    # 0.755 + 0.012 = 7.76 GB
    step = step.memory_analysis()
    assert step.argument_size_in_bytes + step.temp_size_in_bytes \
        + mem.temp_size_in_bytes + mem.output_size_in_bytes < 8.0e9
    # no score of 2,048 keys for all 2,048 queries is ever held
    assert not re.search(r"f32\[(?:1,)?(?:1,)?20,2048,2048\]", text)
    state = jax.eval_shape(lm._prefillRawFn, params, i32(1, JAMBA_BUCKET),
                           i32(1))[1:]
    parts = [jax.ShapeDtypeStruct(p.shape[:1] + p.shape[2:], p.dtype,
                                  sharding=pool[0].sharding) for p in state]
    write = lm.buildPagedPrefillWriteFn().lower(
        *pool, *parts, i32(JAMBA_BUCKET // ps), i32()).compile()
    assert not _whole_array_copies(write, pool)
    poolBytes = sum(a.size * a.dtype.itemsize for a in pool)
    assert write.memory_analysis().alias_size_in_bytes >= poolBytes
    assert write.memory_analysis().temp_size_in_bytes < 64e6


# -- Keye-VL-2.0-30B-A3B's language model as benchmark/configs/
# keye_vl2_30b_a3b.json serves it: 6 of 48 layers at every published width,
# 16 of 128 experts held, the whole vocabulary, bfloat16; 16 slots of
# 34,816 positions in THREE pools (K, V, index rows), pages of 128
KEYE_SLOTS, KEYE_CAP, KEYE_PAGE, KEYE_BUCKET = 16, 34816, 128, 32768


@pytest.fixture(scope="module")
def keye(one_chip):
    """``(lm, params, pool arrays, i32, the compiled decode step, the
    attention and the expert kernels lowered for it and the sparse reads
    lowered in place)``: shapes on the described chip."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nlp.keye_vl import KeyeVLConfig, KeyeVLLM
    from deeplearning4j_tpu.nn.conf.attention import (
        paged_kernel_lowerings, sparse_in_place_lowerings)
    from deeplearning4j_tpu.parallel.moe import moe_step_kernel_lowerings
    from deeplearning4j_tpu.remote import KVCachePool

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)
    lm = KeyeVLLM(KeyeVLConfig(
        vocabSize=151936, nLayers=6, hiddenSize=2048, nHeads=32, nKvHeads=4,
        headSize=128, expertSize=768, nExperts=128, expertsPerToken=8,
        expertsHeld=(0, 16), indexHeads=16, indexSize=64, topk=2048,
        maxLen=KEYE_CAP), params={})
    params = on_chip(jax.eval_shape(lm._init_params))
    perSeq = KEYE_CAP // KEYE_PAGE
    pool = on_chip(jax.eval_shape(lambda: KVCachePool.forSpec(
        lm.cacheSpec(), KEYE_PAGE, 1 + KEYE_SLOTS * perSeq, KEYE_SLOTS,
        perSeq).arrays))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    counts = (paged_kernel_lowerings, moe_step_kernel_lowerings,
              sparse_in_place_lowerings)
    before = [c() for c in counts]
    # ``prev`` is a step's own output: the tokens and the fourteen counts
    step = lm.buildPagedDecodeFn().lower(
        params, *pool, i32(KEYE_SLOTS, 1),
        i32(KEYE_SLOTS, 1 + len(lm.stepCounters)),
        i32(KEYE_SLOTS, perSeq), i32(KEYE_SLOTS), i32(KEYE_SLOTS)).compile()
    return lm, params, pool, i32, step, tuple(
        c() - b for c, b in zip(counts, before))


def test_keye_decode_step_fits_and_scores_its_index_rows_in_place(keye):
    """Of the decode step at the cell's sizes: 18 kernel calls, 6 that
    score a slot's live index pages where they lie, 6 masked passes of the
    paged-attention kernel over its live K and V pages under the
    selection's mask, and 6 over the hit experts; the selection is a mask
    by bisection (no sort) and no chosen row leaves the pools (no row
    gather); every op of the read carries its scope, and of them only the
    scoring kernel is named for it (the benchmark's reader counts a call
    by that name)."""
    lm, params, pool, i32, compiled, kernelsLowered = keye
    perSeq = KEYE_CAP // KEYE_PAGE
    pages = 1 + KEYE_SLOTS * perSeq
    mem = compiled.memory_analysis()
    # found: 10.11 GB of arguments (2.41 of weights, 6.85 of K and V rows,
    # 0.86 of index rows stored 128 lanes wide) + 0.02 of temporaries
    assert [a.shape for a in pool] == [(6, pages, KEYE_PAGE, 512)] * 2 + [
        (6, pages, KEYE_PAGE, 128), (1, KEYE_SLOTS, 9)]
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    assert 10.0e9 < mem.argument_size_in_bytes < 10.2e9
    assert mem.temp_size_in_bytes < 0.1e9
    # the three pools and the counts are donated and come back aliased,
    # none copied
    _assert_one_step_program(compiled, pool)
    assert not _whole_array_copies(compiled, pool)
    text = compiled.as_text()
    # 34,816 positions are 17 x topk: under the crossover, all six in place
    assert kernelsLowered == (6, 1, 6)
    kernels = _kernel_names(text)
    assert sorted(kernels) == ["moe_share_step"] * 6 \
        + ["paged_selected_attention"] * 6 \
        + ["paged_sparse_attention_index"] * 6, kernels
    # no slot's capacity of index rows, keys or values is gathered, and
    # neither are the 2,048 chosen rows a slot: nothing leaves the pools
    for lanes in (128, 512):
        assert f"bf16[{KEYE_SLOTS},{KEYE_CAP},{lanes}]" not in text
        assert f"bf16[{KEYE_SLOTS},{perSeq},{KEYE_PAGE},{lanes}]" not in text
    assert f"bf16[{KEYE_SLOTS},2048,512]" not in text
    # the read's instructions say so in their metadata: the benchmark's
    # driver tells them from the rest of the step's by it, and counts the
    # read's calls by the instructions NAMED for the scope
    scoped = re.findall(
        r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .* ([\w\-]+)\(.*op_name=\"jit\(step\)"
        r"/paged_sparse_attention/", text, re.M)
    assert not any(op == "sort" for _n, op in scoped)
    assert sum(1 for n, _op in scoped
               if n.startswith("paged_sparse_attention")) == 6
    assert sum(1 for n, _op in scoped
               if n.startswith("paged_selected_attention")) == 6


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("capacity", [KEYE_CAP, 262144])
def test_sparse_read_is_in_place_under_the_crossover_on_one_tpu(
        topo, one_chip, capacity, chips):
    """Which form reads the chosen rows is decided where the program is
    lowered, from static shapes: for one TPU the masked pass over the live
    pages while a slot's capacity x a row's bytes is under
    ``_IN_PLACE_BYTES_A_PICK`` a chosen row (the cell's 34,816 positions of
    1 KB = 17 x ``topk``), the sort and the row gather beyond (the
    published 262,144 = 128 x); for four devices the gathered reference,
    and neither counter moves."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.conf import attention as A
    S, perSeq = 2, capacity // KEYE_PAGE
    rows, lanes = _rows_and_lanes(topo, one_chip, chips)

    def sds(shape, dtype, sharding=rows):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    pool = sds((1, 1 + S * perSeq, KEYE_PAGE, 512), bf16, lanes)
    before = A.paged_kernel_lowerings(), A.sparse_in_place_lowerings()
    text = jax.jit(lambda *a: A.paged_sparse_attention(
        *a[:9], 0, *a[9:], topk=2048)).lower(
        sds((S, 32, 1, 128), bf16), sds((S, 4, 1, 128), bf16),
        sds((S, 4, 1, 128), bf16), sds((S, 16, 64), f32), sds((S, 16), f32),
        sds((S, 64), bf16), pool, pool,
        sds((1, 1 + S * perSeq, KEYE_PAGE, 128), bf16, lanes),
        sds((S, perSeq), i32), sds((S,), i32), sds((S,), i32)).as_text()
    kernel = A.paged_kernel_lowerings() - before[0]
    inPlace = A.sparse_in_place_lowerings() - before[1]
    assert (kernel, inPlace) == (
        (0, 0) if chips == 4 else (1, 1) if capacity == KEYE_CAP else (1, 0))
    assert text.count("tpu_custom_call") == kernel + inPlace
    assert ("paged_selected_attention" in text) == bool(inPlace)
    assert ("chlo.top_k" in text or "stablehlo.sort" in text) \
        == (not inPlace)


STEPS_OF_THE_UNMASKED_KERNEL = {
    "gpt2_xl": ("paged_step", 0), "phi4_mini_flash": ("sambay_step", 0),
    "olmo_hybrid_7b": ("olmo", 4), "jamba2_3b": ("jamba", 5)}


@pytest.mark.parametrize("config", sorted(STEPS_OF_THE_UNMASKED_KERNEL))
def test_the_other_steps_call_the_unmasked_kernel_at_128_rows_a_place(
        request, config):
    """The four configurations whose decode step reads K and V through
    ``paged_attention``'s kernel: every kernel of theirs that attends is
    the UNMASKED one under its old name, and a place of its grid holds as
    many pages of K (and of V) as make ``_CHUNK_ROWS`` = 128 rows.  The
    masked variant and its rows-a-place rule are the sparse read's alone."""
    fixture, at = STEPS_OF_THE_UNMASKED_KERNEL[config]
    text = request.getfixturevalue(fixture)[at].as_text()
    assert "paged_selected_attention" not in text
    calls = re.findall(
        r"^\s*%?paged_attention[\w.\-]* = \S+ custom-call\((.*?)\), "
        r"custom_call_target=\"tpu_custom_call\"", text, re.M)
    assert calls
    # an operand is printed by name: what it is stands where it is made
    made = dict(re.findall(r"^\s*(?:ROOT\s+)?(%[\w.\-]+) = (\S+) ", text,
                           re.M))
    for operands in calls:
        pools = [m.group(1) for name in re.findall(r"%[\w.\-]+", operands)
                 for m in [re.match(r"(?:bf16|f32)\[\d+,\d+,(\d+),\d+\]",
                                    made[name])] if m]
        assert len(set(pools)) == 1 and len(pools) % 2 == 0, operands
        assert len(pools) // 2 * int(pools[0]) == 128, operands


def test_keye_prefill_selects_and_attends_in_kernels_and_fits_beside_the_step(
        keye):
    import jax
    lm, params, pool, i32, step, _ = keye
    compiled = lm._prefillRawFn.at(KEYE_BUCKET).lower(
        params, i32(1, KEYE_BUCKET), i32(1)).compile()
    text = compiled.as_text()
    kernels = _kernel_names(text)
    # every layer's selection is the bisection kernel and its attention
    # the flash kernel under the selection's tiles: no score of 32,768
    # keys a query is held outside VMEM, for the indexer's 16 heads or
    # the attention's 32
    assert kernels.count("sparse_prefill_select") == 6
    assert kernels.count("sparse_prefill_attention") == 6
    assert not re.search(rf"f32\[[\d,]*{KEYE_BUCKET},{KEYE_BUCKET}\]", text)
    # the held experts are multiplied by GROUP, 4,096 tokens a pass (the
    # grouped kernel, one call a layer inside the loop over the blocks, no
    # ragged dot): no (tokens, tokens) matrix of a whole bucket brings the
    # pairs home
    assert f"[{KEYE_BUCKET},{KEYE_BUCKET}]" not in text
    assert "ragged-dot" not in text
    assert kernels.count("moe_share_grouped") == 6
    mem = compiled.memory_analysis()
    # found: 10.11 + 0.02 (the step) + 2.49 of temporaries (1.07 of them
    # a layer's selection as int8 tiles) + 0.45 of rows and logits out =
    # 13.07 GB (the same since the flash kernel keeps its running maximum
    # and sum 128 lanes wide: its scratch, 1.5 MB, is VMEM)
    step = step.memory_analysis()
    assert step.argument_size_in_bytes + step.temp_size_in_bytes \
        + mem.temp_size_in_bytes + mem.output_size_in_bytes < 13.5e9
    state = jax.eval_shape(lm._prefillRawFn, params, i32(1, KEYE_BUCKET),
                           i32(1))[1:]
    assert [p.shape for p in state] == [
        (6, 1, 4, KEYE_BUCKET, 128)] * 2 + [(6, 1, 1, KEYE_BUCKET, 128),
                                            (1, 1, 9)]
    parts = [jax.ShapeDtypeStruct(p.shape[:1] + p.shape[2:], p.dtype,
                                  sharding=pool[0].sharding) for p in state]
    write = lm.buildPagedPrefillWriteFn().lower(
        *pool, *parts, i32(KEYE_BUCKET // KEYE_PAGE), i32()).compile()
    assert not _whole_array_copies(write, pool)
    poolBytes = sum(a.size * a.dtype.itemsize for a in pool)
    assert write.memory_analysis().alias_size_in_bytes >= poolBytes
    assert write.memory_analysis().temp_size_in_bytes < 0.5e9


# -- Ling-3.0-flash as benchmark/configs/ling3_flash.json serves it: published
# layers 1-7 (six KDA layers, one MLA layer; one dense FFN, six expert layers
# holding 128 of 512 experts), a quarter of the vocabulary, every width as
# published, bfloat16; 64 slots of 12,288 positions (96 pages of 128) of one
# latent row in the MLA layer, six float32 delta states a slot beside them
LING_SLOTS, LING_CAP, LING_PAGE, LING_BUCKET = 64, 12288, 128, 8192


@pytest.fixture(scope="module")
def ling(one_chip):
    """``(lm, params, pool arrays, i32, the compiled decode step, the
    attention and the expert kernels lowered for it)``: shapes on the
    described chip."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nlp.ling import LingConfig, LingLM
    from deeplearning4j_tpu.nn.conf.attention import paged_kernel_lowerings
    from deeplearning4j_tpu.parallel.moe import moe_step_kernel_lowerings
    from deeplearning4j_tpu.remote import KVCachePool

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)
    lm = LingLM(LingConfig(
        vocabSize=39296, nLayers=7, firstLayer=1, denseLayers=1, mlaEvery=6,
        hiddenSize=2560, nHeads=32, headDim=128, kvRank=512, nopeDim=128,
        ropeDim=64, vDim=128, ffnSize=6144, expertSize=768, nExperts=512,
        expertsPerToken=8, expertsHeld=(0, 128), nGroups=8, groupsPerToken=4,
        maxLen=LING_CAP), params={})
    params = on_chip(jax.eval_shape(lm._init_params))
    perSeq = LING_CAP // LING_PAGE
    pool = on_chip(jax.eval_shape(lambda: KVCachePool.forSpec(
        lm.cacheSpec(), LING_PAGE, 1 + LING_SLOTS * perSeq, LING_SLOTS,
        perSeq).arrays))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    before = paged_kernel_lowerings(), moe_step_kernel_lowerings()
    step = lm.buildPagedDecodeFn().lower(
        params, *pool, i32(LING_SLOTS, 1), i32(LING_SLOTS, 7),
        i32(LING_SLOTS, perSeq), i32(LING_SLOTS), i32(LING_SLOTS)).compile()
    return lm, params, pool, i32, step, (
        paged_kernel_lowerings() - before[0],
        moe_step_kernel_lowerings() - before[1])


def test_ling_decode_step_fits_and_updates_three_kinds_of_state_in_place(
        ling):
    """Of the decode step at the cell's sizes: 13 kernel calls, 6 that
    update a KDA layer's states where they lie (each state read once and
    written once), 1 latent read with 32 query heads as the rows of its
    matmul, 6 over the hit experts of 128 held."""
    lm, params, pool, i32, compiled, kernelsLowered = ling
    perSeq = LING_CAP // LING_PAGE
    mem = compiled.memory_analysis()
    # found: 12.18 GB of arguments (10.34 of weights, 1.01 of latent rows,
    # 0.81 of delta states, 0.03 of windows) + 0.04 of temporaries
    assert [a.shape for a in pool] == [
        (1, 1 + LING_SLOTS * perSeq, LING_PAGE, 640),
        (6, LING_SLOTS, 32, 128, 128), (6, LING_SLOTS, 3, 12288),
        (1, LING_SLOTS, 3)]
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    assert mem.temp_size_in_bytes < 0.1e9
    _assert_one_step_program(compiled, pool)
    assert not _whole_array_copies(compiled, pool)
    text = compiled.as_text()
    # (the six expert layers are one lowering: same shapes, same rule)
    assert kernelsLowered == (1, 1)
    kernels = _kernel_names(text)
    assert sorted(kernels) == ["kda_step"] * 6 + ["moe_share_step"] * 6 \
        + ["paged_latent_attention"], kernels
    # no layer's states (134 MB) are sliced out, updated and put back, and
    # no slot's capacity of latent rows is gathered
    assert not re.search(
        rf"= f32\[(?:1,)?{LING_SLOTS},32,128,128\]\S* "
        r"(?:copy|dynamic-slice|slice|fusion)\(", text)
    assert f"bf16[{LING_SLOTS * perSeq},{LING_PAGE},640]" not in text
    # the kernels and the convolution windows' shift carry the scope the
    # benchmark cuts the step by
    scoped = [line for line in text.splitlines() if "/kda_step/" in line]
    assert sum("custom_call_target=\"tpu_custom_call\"" in line
               for line in scoped) == 6
    # nothing is computed for all 128 held experts (no (slots, 128, 768))
    assert not re.search(rf"\[{LING_SLOTS},(?:128,768|98304)\]", text)


def test_ling_prefill_chunks_its_delta_rule_and_fits_beside_the_step(
        ling, monkeypatch):
    import jax
    from deeplearning4j_tpu.nlp import latent
    from deeplearning4j_tpu.parallel import ring
    lm, params, pool, i32, step, _ = ling
    for mod in (ring, latent):
        monkeypatch.setattr(mod, "_flash_refusal", lambda *a, **k: None)
    traced = lm._prefillRawFn.at(LING_BUCKET).trace(
        params, i32(1, LING_BUCKET), i32(1))
    # the chunked form: the only loops over positions are the scans over
    # the 128 chunks of each KDA layer -- nothing runs 8,192 times
    assert sorted(_scan_lengths(traced.jaxpr.jaxpr)) == [128] * 6
    compiled = traced.lower().compile()
    text = compiled.as_text()
    assert "kda_chunked" in text and "/kda_step/" not in text
    # the MLA layer's unabsorbed attention is the flash kernel, and the
    # held experts are multiplied by GROUP (the grouped kernel, one call
    # an expert layer in place of three ragged dots)
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          text)) >= 1 + 6
    assert "ragged-dot" not in text
    assert _kernel_names(text).count("moe_share_grouped") == 6
    mem = compiled.memory_analysis()
    # found: 12.18 + 0.04 (the step) + 1.97 + 0.02 (the 8,192 prefill)
    # = 14.21 GB
    step = step.memory_analysis()
    assert step.argument_size_in_bytes + step.temp_size_in_bytes \
        + mem.temp_size_in_bytes + mem.output_size_in_bytes < 14.8e9
    state = jax.eval_shape(lm._prefillRawFn, params, i32(1, LING_BUCKET),
                           i32(1))[1:]
    parts = [jax.ShapeDtypeStruct(p.shape[:1] + p.shape[2:], p.dtype,
                                  sharding=pool[0].sharding) for p in state]
    write = lm.buildPagedPrefillWriteFn().lower(
        *pool, *parts, i32(LING_BUCKET // LING_PAGE), i32()).compile()
    assert not _whole_array_copies(write, pool)
    poolBytes = sum(a.size * a.dtype.itemsize for a in pool)
    assert write.memory_analysis().alias_size_in_bytes >= poolBytes
    assert write.memory_analysis().temp_size_in_bytes < 0.1e9


# -- Nemotron-3-Super as benchmark/configs/nemotron3_super.json serves it:
# published blocks 27-37 (MEMEMEMEM*E: five Mamba-2 blocks, five expert
# blocks holding 128 of 512 latent experts, one attention block), a quarter
# of the vocabulary, every width as published, bfloat16; 64 slots of 5,120
# positions (40 pages of 128) of K and V rows of 2 heads in the attention
# block, five float32 SSD states of 4.19 MB a slot beside them
NEMO_SLOTS, NEMO_CAP, NEMO_PAGE, NEMO_BUCKET = 64, 5120, 128, 2048


@pytest.fixture(scope="module")
def nemotron(one_chip):
    """``(lm, params, pool arrays, i32, the compiled decode step, the
    attention and the expert kernels lowered for it)``: shapes on the
    described chip."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nlp.nemotron_h import (NemotronHConfig,
                                                   NemotronHLM)
    from deeplearning4j_tpu.nn.conf.attention import paged_kernel_lowerings
    from deeplearning4j_tpu.parallel.moe import moe_step_kernel_lowerings
    from deeplearning4j_tpu.remote import KVCachePool

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)
    lm = NemotronHLM(NemotronHConfig(
        vocabSize=32768, pattern="MEMEMEMEM*E", firstBlock=27,
        hiddenSize=4096, nHeads=32, nKvHeads=2, headDim=128, mambaHeads=128,
        mambaHeadDim=64, nGroups=8, stateSize=128, convKernel=4, chunk=128,
        latentSize=1024, expertSize=2688, sharedSize=5376, routerWidth=512,
        expertsPerToken=22, expertsHeld=(0, 128), maxLen=NEMO_CAP), params={})
    params = on_chip(jax.eval_shape(lm._init_params))
    perSeq = NEMO_CAP // NEMO_PAGE
    pool = on_chip(jax.eval_shape(lambda: KVCachePool.forSpec(
        lm.cacheSpec(), NEMO_PAGE, 1 + NEMO_SLOTS * perSeq, NEMO_SLOTS,
        perSeq).arrays))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    before = paged_kernel_lowerings(), moe_step_kernel_lowerings()
    step = lm.buildPagedDecodeFn().lower(
        params, *pool, i32(NEMO_SLOTS, 1), i32(NEMO_SLOTS, 7),
        i32(NEMO_SLOTS, perSeq), i32(NEMO_SLOTS), i32(NEMO_SLOTS)).compile()
    return lm, params, pool, i32, step, (
        paged_kernel_lowerings() - before[0],
        moe_step_kernel_lowerings() - before[1])


def test_nemotron_decode_step_fits_and_updates_its_states_in_place(nemotron):
    """Of the decode step at the cell's sizes: 11 kernel calls, 5 that
    update a Mamba-2 block's states where they lie (a slot's 4 MB read
    once and written once), 5 over the hit experts of 128 held (two
    matrices an expert, the width 2,688 whole) and 1 paged read with 16
    query heads on each of 2 KV heads."""
    lm, params, pool, i32, compiled, kernelsLowered = nemotron
    perSeq = NEMO_CAP // NEMO_PAGE
    mem = compiled.memory_analysis()
    # found: 11.01 GB of arguments (9.30 of weights, 0.34 of pages, 1.34
    # of SSD states, 0.02 of windows) + 0.027 of temporaries
    assert [a.shape for a in pool] == [
        (1, 1 + NEMO_SLOTS * perSeq, NEMO_PAGE, 256)] * 2 + [
        (5, NEMO_SLOTS, 128, 64, 128), (5, NEMO_SLOTS, 3, 10240),
        (1, NEMO_SLOTS, 3)]
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    # no temporary the size of a layer's held experts (1.41 GB) or states
    assert mem.temp_size_in_bytes < 0.1e9
    _assert_one_step_program(compiled, pool)
    assert not _whole_array_copies(compiled, pool)
    text = compiled.as_text()
    # (the five expert layers are one lowering: same shapes, same rule)
    assert kernelsLowered == (1, 1)
    kernels = _kernel_names(text)
    assert sorted(kernels) == ["moe_share_step"] * 5 + ["paged_attention"] \
        + ["ssd_step"] * 5, kernels
    # no layer's states (268 MB) are sliced out, updated and put back
    assert not re.search(
        rf"= f32\[(?:1,)?{NEMO_SLOTS},(?:128,64,128|8,16,64,128)\]\S* "
        r"(?:copy|dynamic-slice|slice|fusion)\(", text)
    # the state kernels and the experts' carry the scopes the benchmark
    # cuts the step by
    for scope in ("/ssd_step/", "/latent_moe/moe_share_step/"):
        scoped = [ln for ln in text.splitlines() if scope in ln]
        assert sum("custom_call_target=\"tpu_custom_call\"" in ln
                   for ln in scoped) == 5
    # nothing is computed for all 128 held experts (no (slots, 128, 2688))
    assert not re.search(rf"\[{NEMO_SLOTS},(?:128,2688|344064)\]", text)


def test_nemotron_prefill_chunks_its_ssd_and_fits_beside_the_step(nemotron):
    import jax
    lm, params, pool, i32, step, _ = nemotron
    traced = lm._prefillRawFn.at(NEMO_BUCKET).trace(
        params, i32(1, NEMO_BUCKET), i32(1))
    # the chunked form: the only loops over positions are the scans over
    # the 16 chunks of each Mamba-2 block and the attention block's 4
    # query blocks -- nothing runs 2,048 times
    assert sorted(_scan_lengths(traced.jaxpr.jaxpr)) == [4] + [16] * 5
    compiled = traced.lower().compile()
    text = compiled.as_text()
    assert "ssd_prefill" in text and "/ssd_step/" not in text
    # the held experts are multiplied by GROUP (the grouped kernel, one
    # call an expert block in place of two ragged dots)
    assert "ragged-dot" not in text
    assert _kernel_names(text).count("moe_share_grouped") == 5
    mem = compiled.memory_analysis()
    # found: 11.01 + 0.045 (the step) + 0.55 + 0.02 (the 2,048 prefill);
    # the 4,096 prefill holds 0.98 GB of temporaries
    assert mem.temp_size_in_bytes < 0.8e9
    step = step.memory_analysis()
    assert step.argument_size_in_bytes + step.temp_size_in_bytes \
        + mem.temp_size_in_bytes + mem.output_size_in_bytes < 12.5e9
    state = jax.eval_shape(lm._prefillRawFn, params, i32(1, NEMO_BUCKET),
                           i32(1))[1:]
    parts = [jax.ShapeDtypeStruct(p.shape[:1] + p.shape[2:], p.dtype,
                                  sharding=pool[0].sharding) for p in state]
    write = lm.buildPagedPrefillWriteFn().lower(
        *pool, *parts, i32(NEMO_BUCKET // NEMO_PAGE), i32()).compile()
    assert not _whole_array_copies(write, pool)
    poolBytes = sum(a.size * a.dtype.itemsize for a in pool)
    assert write.memory_analysis().alias_size_in_bytes >= poolBytes
    assert write.memory_analysis().temp_size_in_bytes < 0.1e9


def test_expert_step_kernel_compiles_for_an_expert_of_two_matrices(one_chip):
    """``_hit_call`` by itself at this model's shapes: 64 slots' latent
    rows against 128 held experts of ``(1024, 2688)`` and ``(2688,
    1024)`` bfloat16 under ``relu2``, the width 2,688 (21 lane tiles) in
    ONE place of the grid: two double-buffered blocks of 5.5 MB each."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.parallel import moe
    n, d, f, T = 128, 1024, 2688, 64

    def sds(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = moe._hit_call.lower(
        sds((n,)), sds(()), sds((T, d), jnp.bfloat16),
        sds((n, T, 1), jnp.float32), sds((n, d, f), jnp.bfloat16),
        sds((n, f, d), jnp.bfloat16), act=moe.relu2,
        interpret=False).compile()
    text = compiled.as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert "moe_share_step" in text
    assert moe._expert_tile(f, 2 * d, 2) == f
    assert compiled.memory_analysis().temp_size_in_bytes < 1e6
