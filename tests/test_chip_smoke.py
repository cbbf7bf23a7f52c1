"""``chip_smoke.py`` on the CPU: the same phase bodies at tiny sizes (the
flash kernels in the Pallas interpreter, here and only here), and the parts
of its contract a CPU can show — it refuses to start without a TPU, a
failed phase is a non-zero exit with no result line, the serving LM lowers
without f64, the compile-cache helper leaves a placed cache alone, and a
call that names ``impl="flash"`` never gets another implementation.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke
from deeplearning4j_tpu.compile import DEFAULT_CACHE_DIR, enable_compile_cache
from deeplearning4j_tpu.parallel.ring import (dot_product_attention,
                                              flash_attention)
from deeplearning4j_tpu.zoo import ResNet50

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

class ResNet14(ResNet50):
    """The zoo builder with one bottleneck per stage: a quarter of the
    compile, which is most of what the two ResNet phases cost on a CPU."""
    stages = ((64, 1, 1), (128, 1, 2), (256, 1, 2), (512, 1, 2))


#: ResNet cut to 8 images of 32x32
RESNET = dict(batch=8, img=32, classes=10, model=ResNet14)
#: GPT-2-small cut to 2 heads of 8
LM = dict(vocabSize=97, nHeads=2, headSize=8)


def _run(phase, *args, **kw) -> chip_smoke.Report:
    r = chip_smoke.Report()
    r.returned = phase(r, *args, **kw)
    return r


@pytest.fixture(scope="module")
def resnet():
    return _run(chip_smoke.phase_train_resnet50, steps=3, stream_batches=3,
                **RESNET)


def test_main_refuses_to_start_on_cpu(capsys):
    assert chip_smoke.main() != 0
    out, err = capsys.readouterr()
    assert out == ""
    assert "'cpu'" in err and "TPU" in err


def test_import_does_not_initialize_the_backend():
    """One process owns the chip: a worker or launcher that merely imports
    the package (or this script) must not reach for it."""
    code = ("import jax, deeplearning4j_tpu, chip_smoke\n"
            "from jax._src import xla_bridge\n"
            "raise SystemExit(int(xla_bridge.backends_are_initialized()))")
    assert subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          timeout=120).returncode == 0


def _raises(r):
    raise RuntimeError("injected")


def _fails_a_check(r):
    r.check("injected", False, "detail")


def _skips(r):
    raise chip_smoke.Skipped("1 device")


@pytest.mark.parametrize("bad", [_raises, _fails_a_check])
def test_failed_phase_is_nonzero_exit_without_result_line(capsys, bad):
    counter = chip_smoke.CompileCounter()
    device = {"platform": "cpu"}
    code = chip_smoke.run_phases(
        [("fine", lambda r: None), ("bad", bad), ("skipped", _skips)],
        device, counter)
    lines = [json.loads(line) for line in
             capsys.readouterr().out.splitlines()]
    assert code != 0
    assert [(ln["phase"], ln.get("ok")) for ln in lines] == [
        ("fine", True), ("bad", False), ("skipped", None)]
    assert lines[1].get("error") == "RuntimeError: injected" \
        or lines[1]["failed"] == ["injected: detail"]
    # with the failure taken out, the result line is the last line
    assert chip_smoke.run_phases(
        [("fine", lambda r: None), ("skipped", _skips)], device,
        counter) == 0
    assert json.loads(capsys.readouterr().out.splitlines()[-1]) == {
        "ok": True, "device": device}


def test_train_resnet50_small(resnet):
    # lr 0.1 on 8 images diverges at once, so "lower at the end" is the one
    # check this size cannot hold; everything else is the chip's contract
    assert [f for f in resnet.failed
            if not f.startswith("loss_lower_at_end")] == []
    assert resnet.values["pool_batches"] == 3
    assert resnet.values["train_step_compiles"] == 1
    assert len(resnet.returned) == 3


def test_train_bert_small():
    r = _run(chip_smoke.phase_train_bert, batch=4, seq=16, steps=24,
             numLayers=2, hiddenSize=32, numHeads=2, intermediateSize=64,
             vocabSize=128, maxSeqLength=16)
    assert r.failed == []
    assert r.values["step_flops"] > 0


def test_flash_attention_small_interpreted():
    r = _run(chip_smoke.phase_flash_attention,
             shapes=((1, 2, 32, 8), (1, 2, 64, 8)), dsl_t=16, dsl_heads=2,
             dsl_head_size=8, dsl_nin=16, interpret=True, block_q=16,
             block_k=16)
    assert r.failed == []
    assert r.values["t32_dk_err"] <= chip_smoke.FLASH_TOLERANCE


def test_serve_lm_small_has_no_f64():
    r = _run(chip_smoke.phase_serve_lm, nLayers=2, maxLen=128, maxSlots=4,
             pageSize=8, promptLens=(5, 12, 20, 30, 50), maxNewTokens=6,
             deadlineSeconds=120, **LM)
    assert r.failed == []
    assert [r.values[f"f64_in_{k}"]
            for k in ("forward", "prefill", "decode")] == [0, 0, 0]
    assert r.values["compile_misses_after_warmup"] == 0
    assert r.values["kv_pages_in_use"] == 0
    assert r.values["paged_logit_err"] <= chip_smoke.PAGED_LOGIT_TOLERANCE


def test_mesh_four_devices_small(resnet):
    r = _run(chip_smoke.phase_mesh, resnet.returned, steps=3, maxLen=64,
             replicaLayers=1, replicaBucket=16, maxNewTokens=4, **RESNET,
             **LM)
    # 8 images over 4 devices leave BatchNorm 2 per shard to reduce in
    # another order, in bf16: the loss comparison is for the real size
    assert [f for f in r.failed if "_matches_one_chip" not in f] == []
    assert r.values["mesh_jit_cache_misses"] == 1
    assert r.values["batch_shard_devices"] == [0, 1, 2, 3]
    assert r.values["replicas"] == 4


def test_mesh_phase_skips_below_four_devices():
    with pytest.raises(chip_smoke.Skipped, match="8 device"):
        chip_smoke.phase_mesh(chip_smoke.Report(), [], chips=16)


def test_compile_cache_helper(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    assert enable_compile_cache() == "/placed/from/outside"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert enable_compile_cache() == DEFAULT_CACHE_DIR
        assert DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == DEFAULT_CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_naming_flash_raises_where_it_cannot_run():
    q = jnp.zeros((1, 1, 24, 8), jnp.bfloat16)
    with pytest.raises(ValueError, match="no key mask"):
        dot_product_attention(q, q, q, mask=np.ones((1, 24)), impl="flash")
    with pytest.raises(ValueError, match="not multiples"):
        flash_attention(q, q, q, block_q=16, block_k=16, interpret=True)
    with pytest.raises(ValueError, match="platform is 'cpu'"):
        dot_product_attention(q, q, q, impl="flash")
