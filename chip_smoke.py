"""Does the system still start on the chip?  ``python chip_smoke.py``.

One process, no flags, no CPU mode.  It refuses to start unless
``jax.devices()[0].platform == "tpu"``, then drives the paths the benchmark
cells stand on through the entry points a user calls, at the full width of
the models the repo ships (random weights from a seed):

1. ``train.resnet50``         ``zoo.ResNet50`` bf16, batch 256, ``net.fit``
2. ``train.bert_base``        ``zoo.bert.BertBase`` bf16, B=256 T=128, ``sd.fit``
3. ``kernel.flash_attention`` the Pallas kernels, forward and backward, direct
                              and through a ``SelfAttentionLayer`` net
4. ``serve.gpt2_small``       GPT-2-small-width ``TransformerLM`` behind
                              ``ContinuousBatcher`` + ``InferenceServer``, HTTP
5. ``mesh.four_chips``        (4+ devices) ResNet-50 over ``DeviceMesh(data=4)``
                              and four one-chip serving replicas

Each phase prints one JSON line; the last line of stdout is
``{"ok": true, "device": {...}}`` and the exit code is 0 only if every phase
ran and every check held.  The seconds it prints are set-up facts (how long
a cold or cached start takes), not performance metrics: it prints no rate.

The phase bodies are plain functions of their sizes, so
``tests/test_chip_smoke.py`` runs the same code small on the CPU.
"""
from __future__ import annotations

import collections
import contextlib
import faulthandler
import json
import sys
import threading
import time
import traceback
import urllib.error
import urllib.request

import numpy as np

# in a directory that holds this file and nothing else of the repo, this
# import is where the script ends: non-zero, nothing printed
from deeplearning4j_tpu.datasets import DataSet, ListDataSetIterator
from deeplearning4j_tpu.datasets.iterator import DataSetIterator

#: the whole script must end inside the driver's 1200 s; a hang (a forked
#: worker that never answers, a wedged dispatch) is dumped and killed here
WATCHDOG_SECONDS = 1150

#: bf16 keeps 8 significant bits (eps = 2**-8 = 3.9e-3).  The kernel
#: rounds p to bf16 before the PV product and rounds its outputs to bf16;
#: the float32 reference does neither, so two or three such roundings
#: stack.  Differences are measured against the largest reference
#: magnitude, because gradients here reach the tens.
FLASH_TOLERANCE = 2e-2

#: float32 logits of magnitude ~1 from two orderings of the same sums
#: (paged gather + masked softmax over the capacity vs. one causal pass);
#: on the chip f32 matmuls run as bf16 passes, which is the larger term.
PAGED_LOGIT_TOLERANCE = 5e-2

#: the sharded step computes the same global-batch math in another
#: reduction order, in bf16, through 53 BatchNorms that each re-normalize
#: the difference.  One tolerance per compared step: the first two losses
#: (same weights; one update) must agree closely; by the third the one-chip
#: loss itself moves by a third in one step, and on the 8-device CPU proxy
#: at 64 x 128**2 the two runs were 0.3%, 0.2% and 7% apart.
MESH_LOSS_TOLERANCES = (3e-2, 3e-2, 2.5e-1)


class Skipped(Exception):
    """A phase that cannot run here and is allowed not to."""


class Report:
    """What one phase found: named values, the checks that failed, and its
    seconds split into set-up (trace, compile, warm-up) and run."""

    def __init__(self):
        self.values = {}
        self.failed = []
        self.seconds = {"setup": 0.0, "run": 0.0}

    def check(self, name: str, ok, detail=None) -> None:
        if not ok:
            self.failed.append(name if detail is None
                               else f"{name}: {detail}")

    @contextlib.contextmanager
    def timed(self, kind: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.seconds[kind] += time.perf_counter() - t0


class CompileCounter:
    """Counts XLA compile requests and persistent-cache hits/misses off
    ``jax.monitoring`` — a request served from the cache still counts as a
    compilation, with a hit beside it."""

    _EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
               "/jax/compilation_cache/cache_misses": "cache_misses"}
    _COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.counts = collections.Counter()
        self.compileSeconds = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_kw):
        key = self._EVENTS.get(event)
        if key:
            self.counts[key] += 1

    def _duration(self, event, seconds, **_kw):
        if event == self._COMPILE:
            self.counts["compilations"] += 1
            self.compileSeconds += seconds

    def snapshot(self) -> dict:
        return {"compilations": self.counts["compilations"],
                "compile_seconds": self.compileSeconds,
                "cache_hits": self.counts["cache_hits"],
                "cache_misses": self.counts["cache_misses"]}


def _finite(xs) -> bool:
    return bool(np.all(np.isfinite(np.asarray(xs, np.float64))))


def _f64_count(lowered) -> int:
    """Occurrences of a float64 type in a lowered computation's text."""
    return lowered.as_text().count("f64")


def _mosaic_calls(lowered) -> int:
    """Mosaic (Pallas TPU) custom calls in a lowered computation's text."""
    return lowered.as_text().count("tpu_custom_call")


# ---------------------------------------------------------------------------
# 1. train.resnet50
# ---------------------------------------------------------------------------

def _image_batch(seed: int, batch: int, img: int, classes: int):
    rng = np.random.RandomState(seed)
    x = rng.randn(batch, 3, img, img).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[rng.randint(0, classes, batch)]
    return x, y


class SeededImageStream(DataSetIterator):
    """A picklable streaming source for the ETL pool: every batch is
    generated from its own seed on the host, numpy only, and ``shard``
    hands each pool worker its own batches."""

    def __init__(self, batches: int, batch: int, img: int, classes: int,
                 lo: int = 0, stride: int = 1):
        self.shape = (batches, batch, img, classes)
        self.ids = list(range(lo, batches, stride))
        self.i = 0

    def streaming(self) -> bool:
        return True

    def shard(self, index: int, count: int) -> "SeededImageStream":
        return SeededImageStream(*self.shape, lo=index, stride=count)

    def hasNext(self) -> bool:
        return self.i < len(self.ids)

    def reset(self) -> None:
        self.i = 0

    def next(self, num: int = 0) -> DataSet:
        _n, batch, img, classes = self.shape
        x, y = _image_batch(1000 + self.ids[self.i], batch, img, classes)
        self.i += 1
        return DataSet(x, y)


def phase_train_resnet50(r: Report, batch: int = 256, img: int = 224,
                         classes: int = 1000, steps: int = 12,
                         stream_batches: int = 3, workers: int = 2,
                         model=None):
    """``zoo.ResNet50`` (or ``model``, a subclass of it cut in depth) bf16
    through ``net.fit(DataSet)`` on one repeated seeded batch, then through
    a streaming iterator behind the fork-started ETL pool.  Returns the
    loss after each repeated-batch step (phase 5 compares the sharded run
    against them)."""
    from deeplearning4j_tpu.datavec import (PrefetchingDataSetIterator,
                                            maybe_prefetch)
    from deeplearning4j_tpu.telemetry import etl_metrics
    from deeplearning4j_tpu.zoo import ResNet50

    with r.timed("setup"):
        net = (model or ResNet50)(numClasses=classes,
                                  inputShape=(3, img, img),
                                  dataType="BFLOAT16").init()
        x, y = _image_batch(0, batch, img, classes)
        ds = DataSet(x, y)
        net.fit(ds)
        losses = [net.score()]
    compiles = net._trainStep._cache_size
    with r.timed("run"):
        for _ in range(steps - 1):
            net.fit(ds)
            losses.append(net.score())
    r.values["losses"] = [round(v, 4) for v in losses]
    r.check("loss_finite", _finite(losses), losses)
    r.check("loss_lower_at_end", losses[-1] < losses[0],
            f"{losses[0]} -> {losses[-1]}")
    r.check("one_train_step_compile", compiles() == 1, compiles())

    # streaming: this process holds the device and forks numpy-only decode
    # workers; batches come back through shared memory sized to one batch
    pooled0 = etl_metrics().pool_batches().value()
    with r.timed("run"):
        it = maybe_prefetch(
            SeededImageStream(stream_batches, batch, img, classes),
            numWorkers=workers, hostShard=False,
            shmBytes=x.nbytes + y.nbytes + (1 << 20))
        engaged = isinstance(it, PrefetchingDataSetIterator)
        r.check("etl_pool_engaged", engaged, type(it).__name__)
        try:
            net.fit(it)
            streamLoss = net.score()
        finally:
            if engaged:
                it.close()
    pooled = int(etl_metrics().pool_batches().value() - pooled0)
    r.values.update(stream_loss=round(streamLoss, 4), pool_batches=pooled,
                    train_step_compiles=compiles())
    r.check("stream_loss_finite", _finite(streamLoss), streamLoss)
    r.check("pool_delivered_every_batch", pooled == stream_batches, pooled)
    r.check("no_compile_after_first", compiles() == 1, compiles())
    return losses


# ---------------------------------------------------------------------------
# 2. train.bert_base
# ---------------------------------------------------------------------------

def phase_train_bert(r: Report, batch: int = 256, seq: int = 128,
                     steps: int = 16, **bertConfig):
    """``zoo.bert.BertBase("mlm")`` bf16 through ``sd.fit`` on one repeated
    batch; ``stepCostAnalysis`` must report FLOPs on this platform."""
    from deeplearning4j_tpu.datasets.dataset import MultiDataSet
    from deeplearning4j_tpu.learning import Adam
    from deeplearning4j_tpu.zoo.bert import BertBase

    with r.timed("setup"):
        bert = BertBase("mlm", **bertConfig)
        bert.setTrainingConfig(updater=Adam(2e-5), dataType="BFLOAT16")
        vocab = bert.config.vocabSize
        rng = np.random.RandomState(0)
        mds = MultiDataSet(
            features=[rng.randint(0, vocab, (batch, seq)).astype(np.int32),
                      np.zeros((batch, seq), np.int32),
                      np.ones((batch, seq), np.float32)],
            labels=[rng.randint(0, vocab, (batch, seq)).astype(np.int32),
                    (rng.rand(batch, seq) < 0.15).astype(np.float32)])
        sd = bert.sd
        first = sd.fit(mds, epochs=1).lossCurve()
    with r.timed("run"):
        losses = first + sd.fit(mds, epochs=steps - 1).lossCurve()
    with r.timed("setup"):
        cost = sd.stepCostAnalysis(mds)
    r.values.update(losses=[round(v, 4) for v in losses],
                    step_flops=cost["flops"], step_bytes=cost["bytes"],
                    train_step_compiles=sd._train_step._cache_size())
    r.check("loss_finite", _finite(losses), losses)
    r.check("loss_falling", losses[-1] < losses[0],
            f"{losses[0]} -> {losses[-1]}")
    r.check("cost_analysis_flops", cost["flops"] > 0, cost)
    r.check("one_train_step_compile", sd._train_step._cache_size() == 1,
            sd._train_step._cache_size())


# ---------------------------------------------------------------------------
# 3. kernel.flash_attention
# ---------------------------------------------------------------------------

def phase_flash_attention(r: Report, shapes=((4, 12, 1024, 64),
                                             (4, 12, 4096, 64)),
                          dsl_t: int = 2048, dsl_heads: int = 8,
                          dsl_head_size: int = 64, dsl_nin: int = 128,
                          interpret: bool = False, **blocks):
    """Forward and backward of ``parallel.ring.flash_attention`` (bf16,
    causal) at each ``(b, h, t, d)``; the first shape is compared with the
    dense implementation in float32.  Then a ``SelfAttentionLayer`` net at
    ``dsl_t`` whose ``impl="auto"`` must reach the kernel.  The lowered
    text must hold Mosaic custom calls in both.  ``interpret`` (the Pallas
    interpreter, for the CPU test) has no Mosaic and skips those counts."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.parallel.ring import (dot_product_attention,
                                                  flash_attention)

    def flash_loss(q, k, v, w):
        o = flash_attention(q, k, v, causal=True, interpret=interpret,
                            **blocks)
        return jnp.sum(o.astype(jnp.float32) * w), o

    def dense_loss(q, k, v, w):
        o = dot_product_attention(q, k, v, causal=True, impl="dense")
        return jnp.sum(o * w), o

    fwd_bwd = jax.jit(jax.value_and_grad(flash_loss, argnums=(0, 1, 2),
                                         has_aux=True))
    for i, (b, h, t, d) in enumerate(shapes):
        rng = np.random.RandomState(t)
        q, k, v = (jnp.asarray(rng.randn(b, h, t, d), jnp.bfloat16)
                   for _ in range(3))
        w = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
        with r.timed("setup"):
            calls = _mosaic_calls(fwd_bwd.lower(q, k, v, w))
            (_, out), grads = jax.block_until_ready(fwd_bwd(q, k, v, w))
        with r.timed("run"):
            (_, out), grads = jax.block_until_ready(fwd_bwd(q, k, v, w))
        r.values[f"t{t}_mosaic_calls"] = calls
        # forward, dq and dk/dv are three kernels
        r.check(f"t{t}_mosaic_fwd_and_bwd", interpret or calls >= 3, calls)
        r.check(f"t{t}_finite", _finite(out) and all(map(_finite, grads)))
        if i:
            continue
        with jax.default_matmul_precision("float32"):
            (_, ref), refGrads = jax.value_and_grad(
                dense_loss, argnums=(0, 1, 2), has_aux=True)(
                    *(a.astype(jnp.float32) for a in (q, k, v)), w)
        for name, got, want in zip(("out", "dq", "dk", "dv"),
                                   (out,) + tuple(grads),
                                   (ref,) + tuple(refGrads)):
            want = np.asarray(want, np.float32)
            err = float(np.max(np.abs(np.asarray(got, np.float32) - want))
                        / max(1.0, float(np.max(np.abs(want)))))
            r.values[f"t{t}_{name}_err"] = round(err, 5)
            r.check(f"t{t}_{name}_matches_dense", err <= FLASH_TOLERANCE,
                    err)

    # through the DSL: two SelfAttentionLayers, impl="auto"
    from deeplearning4j_tpu.learning import Adam
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.attention import SelfAttentionLayer
    from deeplearning4j_tpu.nn.conf.recurrent import RnnOutputLayer

    with r.timed("setup"):
        conf = (NeuralNetConfiguration.builder().seed(5).updater(Adam(1e-3))
                .dataType("BFLOAT16").list()
                .layer(SelfAttentionLayer(nHeads=dsl_heads,
                                          headSize=dsl_head_size,
                                          nOut=dsl_nin))
                .layer(SelfAttentionLayer(nHeads=dsl_heads,
                                          headSize=dsl_head_size,
                                          nOut=dsl_nin))
                .layer(RnnOutputLayer.builder("mse").nOut(8)
                       .activation("identity").build())
                .setInputType(InputType.recurrent(dsl_nin, dsl_t)).build())
        net = MultiLayerNetwork(conf).init()
        rng = np.random.RandomState(1)
        ds = DataSet(rng.randn(4, dsl_nin, dsl_t).astype(np.float32),
                     rng.randn(4, 8, dsl_t).astype(np.float32))
        # the fused step exactly as _runTrainStep dispatches it
        calls = _mosaic_calls(net._trainStep.lower(
            net.params_, net.optState_, net.state_,
            ds.features.jax.astype(net._dtype), ds.labels.jax, None, None,
            jax.random.PRNGKey(0), jnp.asarray(0), jnp.asarray(0), None,
            jnp.asarray(1.0, jnp.float32)))
        net.fit(ds)
        losses = [net.score()]
    with r.timed("run"):
        net.fit(ds)
        losses.append(net.score())
    r.values.update(dsl_mosaic_calls=calls,
                    dsl_losses=[round(v, 4) for v in losses])
    # two layers, each a forward and two backward kernels
    r.check("dsl_auto_reaches_kernel", interpret or calls >= 6, calls)
    r.check("dsl_loss_finite", _finite(losses), losses)
    r.check("dsl_one_compile", net._trainStep._cache_size() == 1,
            net._trainStep._cache_size())


# ---------------------------------------------------------------------------
# 4. serve.gpt2_small
# ---------------------------------------------------------------------------

def _post(url: str, payload: dict, timeout: float):
    """POST JSON; returns (status, parsed body).  A streamed reply comes
    back as the list of its NDJSON objects."""
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode("utf-8"),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            status, raw = resp.status, resp.read()
    except urllib.error.HTTPError as e:
        status, raw = e.code, e.read()
    text = raw.decode("utf-8")
    if payload.get("stream") and status == 200:
        return status, [json.loads(line) for line in text.splitlines()
                        if line.startswith("{")]
    return status, json.loads(text)


def _check_lm_dtypes(r: Report, lm, pool, slots: int) -> None:
    """float32 on every leaf and the pool; no f64 anywhere in the lowered
    forward, prefill, or paged decode step."""
    import jax
    import jax.numpy as jnp

    leaves = jax.tree_util.tree_leaves(lm.params)
    notF32 = sorted({str(leaf.dtype) for leaf in leaves
                     if leaf.dtype != jnp.float32})
    r.check("params_float32", not notF32, notF32)
    r.check("pool_float32", pool.k.dtype == jnp.float32
            and pool.v.dtype == jnp.float32, str(pool.k.dtype))
    toks = jnp.zeros((1, 16), jnp.int32)
    zeros = jnp.zeros((slots,), jnp.int32)
    lowered = {
        "forward": lm._fwd.lower(lm.params, toks),
        "prefill": lm._prefillRawFn.lower(lm.params, toks,
                                          jnp.zeros((1,), jnp.int32)),
        "decode": lm.buildPagedDecodeFn().lower(
            lm.params, pool.k, pool.v, jnp.zeros((slots, 1), jnp.int32),
            jnp.zeros((slots, 1), jnp.int32), jnp.asarray(pool.pageTable),
            zeros, zeros)}
    for name, low in lowered.items():
        n = _f64_count(low)
        r.values[f"f64_in_{name}"] = n
        r.check(f"no_f64_in_{name}", n == 0, n)
    # on one TPU the step reads its pages through the kernel (one Mosaic
    # function, called by every layer); anywhere else it gathers
    calls = _mosaic_calls(lowered["decode"])
    r.values["paged_kernel_in_decode"] = calls
    onTpu = jax.devices()[0].platform == "tpu"
    r.check("decode_reads_pages_through_the_kernel",
            calls == (1 if onTpu else 0), calls)


def _check_paged_parity(r: Report, lm, pageSize: int, promptLen: int,
                        bucket: int, decodeSteps: int) -> None:
    """Prefill ``promptLen`` tokens of one seeded sequence into a paged
    pool, decode the next ``decodeSteps`` teacher-forced, and compare
    every logit row with ``lm.forward`` over the whole sequence."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.remote import KVCachePool

    cfg = lm.config
    n = promptLen + decodeSteps
    rng = np.random.RandomState(7)
    seq = rng.randint(0, cfg.vocabSize, (n,)).astype(np.int32)
    ref = np.asarray(lm.forward(seq[None, :])[0])            # (n, vocab)

    pages = -(-(bucket + decodeSteps) // pageSize)
    pool = KVCachePool(cfg.nLayers, cfg.nHeads, cfg.headSize, pageSize,
                       numPages=1 + pages, maxSlots=2, maxPagesPerSeq=pages)
    slot = 1                                   # slot 0 idles on the scratch
    pool.ensure(slot, bucket + decodeSteps)
    padded = np.concatenate([np.zeros(bucket - promptLen, np.int32),
                             seq[:promptLen]])[None, :]
    logits, ks, vs = lm.prefillRaw(padded, lengths=[promptLen])
    got = [np.asarray(logits[0])]
    ids = jnp.asarray(pool.heldIds(slot)[:bucket // pageSize], jnp.int32)
    pool.k, pool.v = lm.buildPagedPrefillWriteFn()(
        pool.k, pool.v, ks[:, 0], vs[:, 0], ids)
    step = jax.jit(lm.pagedLogits)
    pt = jnp.asarray(pool.pageTable)
    start = jnp.asarray([0, bucket - promptLen], jnp.int32)
    for j in range(decodeSteps):
        toks = jnp.asarray([[0], [seq[promptLen + j]]], jnp.int32)
        pos = jnp.asarray([0, bucket + j], jnp.int32)
        out, pool.k, pool.v = step(lm.params, pool.k, pool.v, toks, pt, pos,
                                   start)
        got.append(np.asarray(out[slot, 0]))
    got = np.stack(got)                        # rows promptLen-1 .. n-1
    want = ref[promptLen - 1:]
    err = float(np.max(np.abs(got - want)))
    mismatches = int(np.sum(np.argmax(got, -1) != np.argmax(want, -1)))
    r.values.update(paged_logit_err=float(f"{err:.3g}"),
                    paged_logit_scale=round(float(np.max(np.abs(want))), 3),
                    paged_greedy_mismatches=mismatches,
                    paged_rows=int(got.shape[0]))
    r.check("paged_logits_finite", _finite(got))
    r.check("paged_logits_match_forward", err <= PAGED_LOGIT_TOLERANCE, err)


def phase_serve_lm(r: Report, vocabSize: int = 50257, nLayers: int = 12,
                   nHeads: int = 12, headSize: int = 64, maxLen: int = 1024,
                   maxSlots: int = 8, pageSize: int = 16,
                   promptLens=(12, 37, 90, 200, 330, 500),
                   maxNewTokens: int = 32, deadlineSeconds: float = 300.0):
    """A GPT-2-small-width ``TransformerLM`` behind ``ContinuousBatcher``,
    registered and served over HTTP: concurrent requests with prompts of
    clearly different lengths, the last one streamed."""
    from deeplearning4j_tpu.nlp.transformer import (TransformerLM,
                                                    TransformerLMConfig)
    from deeplearning4j_tpu.remote import (ContinuousBatcher,
                                           InferenceServer, ModelRegistry)
    from deeplearning4j_tpu.telemetry import serving_metrics

    sm = serving_metrics()
    name = "lm"

    def counter(metric) -> float:
        try:
            return metric.value(model=name)
        except ValueError:                     # label set not seen yet
            return 0.0

    with r.timed("setup"):
        lm = TransformerLM(TransformerLMConfig(
            vocabSize=vocabSize, nLayers=nLayers, nHeads=nHeads,
            headSize=headSize, ffnMult=4, maxLen=maxLen))
        cb = ContinuousBatcher(lm, name=name, maxSlots=maxSlots,
                               pageSize=pageSize)
        _check_lm_dtypes(r, lm, cb.pool, maxSlots)
        registry = ModelRegistry()
        registry.register(name, cb)
        warm0 = counter(sm.warmup_compiles())
        srv = InferenceServer(registry, port=0).start()   # warms the ladder
    try:
        r.values.update(
            prompt_buckets=list(cb.ladder.seqLens),
            warmup_compiles=int(counter(sm.warmup_compiles()) - warm0))
        # one prefill per bucket, the pool write, and the decode step
        r.check("warmup_compiled_the_ladder",
                r.values["warmup_compiles"] >= len(cb.ladder.seqLens) + 2,
                r.values["warmup_compiles"])
        misses0 = counter(sm.compile_misses())
        url = f"http://127.0.0.1:{srv.port}/v1/serving/{name}"
        rng = np.random.RandomState(11)
        payloads = [{"tokens": rng.randint(0, vocabSize, (n,)).tolist(),
                     "maxNewTokens": maxNewTokens,
                     "deadlineSeconds": deadlineSeconds}
                    for n in promptLens]
        payloads[-1]["stream"] = True
        replies = [None] * len(payloads)

        def client(i):
            try:
                replies[i] = _post(url, payloads[i], deadlineSeconds + 30)
            except Exception as e:             # reported as a failed reply
                replies[i] = (None, f"{type(e).__name__}: {e}")

        with r.timed("run"):
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(len(payloads))]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
        for i, (status, body) in enumerate(replies):
            tag = f"request{i}_len{promptLens[i]}"
            if status != 200:
                r.check(tag, False, f"HTTP {status}: {body}")
                continue
            if payloads[i].get("stream"):
                toks = [o["token"] for o in body if "token" in o]
                r.check(f"{tag}_stream_closed", body[-1:] == [{"done": True}],
                        body[-1:])
            else:
                toks = body["tokens"][0]
            r.check(tag, len(toks) == maxNewTokens
                    and all(0 <= t < vocabSize for t in toks),
                    f"{len(toks)} tokens")
        # retirement frees a slot's pages just after its reply is released
        deadline = time.monotonic() + 10.0
        while cb.pool.usedPages() and time.monotonic() < deadline:
            time.sleep(0.05)
        r.values.update(
            requests=len(payloads),
            compile_misses_after_warmup=int(counter(sm.compile_misses())
                                            - misses0),
            kv_pages_in_use=cb.pool.usedPages())
        r.check("no_compile_after_warmup",
                r.values["compile_misses_after_warmup"] == 0,
                r.values["compile_misses_after_warmup"])
        r.check("kv_pages_all_free", cb.pool.usedPages() == 0,
                cb.pool.usedPages())
    finally:
        srv.stop()
    with r.timed("run"):
        bucket = next(s for s in cb.ladder.seqLens if s >= promptLens[1])
        _check_paged_parity(r, lm, pageSize, promptLens[1], bucket,
                            decodeSteps=8)


# ---------------------------------------------------------------------------
# 5. mesh.four_chips
# ---------------------------------------------------------------------------

def phase_mesh(r: Report, oneChipLosses, batch: int = 256, img: int = 224,
               classes: int = 1000, steps: int = 3, chips: int = 4,
               vocabSize: int = 50257, nHeads: int = 12, headSize: int = 64,
               maxLen: int = 1024, replicaLayers: int = 2,
               replicaBucket: int = 32, maxNewTokens: int = 8, model=None):
    """The ResNet-50 of phase 1 over ``DeviceMesh(data=chips)`` through
    ``ParallelWrapper.fit``, compared with phase 1's one-chip losses; then
    ``chips`` one-chip serving replicas (GPT-2-small widths, depth cut to
    ``replicaLayers``) behind a ``ReplicaSet`` in this same process."""
    import jax
    if jax.device_count() < chips:
        raise Skipped(f"{jax.device_count()} device")

    from deeplearning4j_tpu.nlp.transformer import (TransformerLM,
                                                    TransformerLMConfig)
    from deeplearning4j_tpu.parallel import DeviceMesh, ParallelWrapper
    from deeplearning4j_tpu.remote import (BucketLadder, ContinuousBatcher,
                                           ReplicaSet)
    from deeplearning4j_tpu.telemetry import mesh_metrics
    from deeplearning4j_tpu.zoo import ResNet50

    devices = jax.devices()[:chips]
    with r.timed("setup"):
        net = (model or ResNet50)(numClasses=classes,
                                  inputShape=(3, img, img),
                                  dataType="BFLOAT16").init()
        ds = DataSet(*_image_batch(0, batch, img, classes))
        pw = ParallelWrapper(net, mesh=DeviceMesh(data=chips,
                                                  devices=devices))
        misses0 = mesh_metrics().jit_cache_misses().value()
        pw.fit(ListDataSetIterator([ds]))
        losses = [net.score()]
    with r.timed("run"):
        for _ in range(steps - 1):
            pw.fit(ListDataSetIterator([ds]))
            losses.append(net.score())
    misses = int(mesh_metrics().jit_cache_misses().value() - misses0)
    paramSets = {frozenset(leaf.sharding.device_set)
                 for leaf in jax.tree_util.tree_leaves(net.params_)}
    # the batch exactly as the sharded fit places it
    net.setBatchSharding(pw.trainer().plan.batch_sharding())
    try:
        placed = net._place_batch(ds.features.jax)
    finally:
        net.setBatchSharding(None)
    shards = placed.addressable_shards
    r.values.update(
        losses=[round(v, 4) for v in losses],
        one_chip_losses=[round(v, 4) for v in oneChipLosses[:steps]],
        mesh_jit_cache_misses=misses,
        batch_shard_devices=sorted(s.device.id for s in shards),
        batch_shard_rows=sorted({int(s.data.shape[0]) for s in shards}))
    r.check("loss_finite", _finite(losses), losses)
    r.check("params_on_every_chip", paramSets == {frozenset(devices)},
            [sorted(d.id for d in s) for s in paramSets])
    r.check("batch_one_shard_per_chip",
            {s.device for s in shards} == set(devices)
            and all(s.data.shape[0] == batch // chips for s in shards))
    r.check("one_mesh_step_compile", misses == 1, misses)
    for i, (got, want, tol) in enumerate(zip(losses, oneChipLosses,
                                             MESH_LOSS_TOLERANCES)):
        r.check(f"loss{i}_matches_one_chip",
                abs(got - want) <= tol * max(1.0, abs(want)),
                f"{got} vs {want}")

    # one serving replica per chip, all in this process
    built = []

    def factory(idx: int):
        lm = TransformerLM(TransformerLMConfig(
            vocabSize=vocabSize, nLayers=replicaLayers, nHeads=nHeads,
            headSize=headSize, ffnMult=4, maxLen=maxLen))
        cb = ContinuousBatcher(
            lm, maxSlots=2, pageSize=16, device=devices[idx],
            ladder=BucketLadder(batchSizes=(2,), seqLens=(replicaBucket,)))
        built.append(cb)
        return cb

    with r.timed("setup"):
        # a probe's first dispatch compiles for its chip while the other
        # replicas are still warming: give it room before it may retire one
        rs = ReplicaSet(factory, name="lm4", replicas=chips,
                        maxReplicas=chips, probeTimeout=60.0).start()
    try:
        r.check("replicas_started", rs.replicaCount() == chips,
                rs.replicaCount())
        rng = np.random.RandomState(13)
        prompt = rng.randint(0, vocabSize, (replicaBucket - 5,)).tolist()
        payload = {"tokens": prompt, "maxNewTokens": maxNewTokens,
                   "deadlineSeconds": 120.0}
        answers = []
        with r.timed("run"):
            for idx, cb in enumerate(built):
                where = {d.id for d in cb.pool.k.devices()} | {
                    d.id for leaf in jax.tree_util.tree_leaves(cb.lm.params)
                    for d in leaf.devices()}
                r.check(f"replica{idx}_on_its_own_chip",
                        where == {devices[idx].id}, sorted(where))
                answers.append(cb.submit(payload, timeout=150)[0].tolist())
            routed = rs.submit(payload, timeout=150)[0].tolist()
        r.values.update(replica_tokens=answers[0], replicas=len(built))
        r.check("every_replica_answered",
                all(len(a) == maxNewTokens for a in answers),
                [len(a) for a in answers])
        # same seed, same prompt, same greedy decode on every chip
        r.check("replicas_agree", all(a == answers[0] for a in answers)
                and routed == answers[0])
    finally:
        rs.shutdown()


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def run_phase(name: str, fn, counter: CompileCounter) -> bool:
    """Run ``fn(report)``, print the phase's JSON line, say whether it
    passed.  A phase that raises is a failed phase, not a crashed script:
    the later phases still run and the exit code says one failed."""
    r = Report()
    before = counter.snapshot()
    error = None
    try:
        fn(r)
    except Skipped as e:
        print(json.dumps({"phase": name, "skipped": str(e)}), flush=True)
        return True
    except Exception as e:
        traceback.print_exc()
        error = f"{type(e).__name__}: {e}"
    after = counter.snapshot()
    ok = error is None and not r.failed
    line = {"phase": name, "ok": ok,
            "setup_seconds": round(r.seconds["setup"], 2),
            "run_seconds": round(r.seconds["run"], 2)}
    line.update({k: round(after[k] - before[k], 2) for k in after})
    line["checks"] = r.values
    if r.failed:
        line["failed"] = r.failed
    if error is not None:
        line["error"] = error
    print(json.dumps(line, default=str), flush=True)
    return ok


def run_phases(phases, device: dict, counter: CompileCounter) -> int:
    """Every phase in order, then the result line — printed only when all
    of them passed.  Returns the exit code."""
    oks = [run_phase(name, fn, counter) for name, fn in phases]
    if not all(oks):
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def main() -> int:
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r} "
              f"({dev.device_kind}); there is no CPU mode", file=sys.stderr)
        return 2
    faulthandler.dump_traceback_later(WATCHDOG_SECONDS, exit=True)

    import importlib.metadata

    import jaxlib

    from deeplearning4j_tpu.compile import enable_compile_cache
    print(json.dumps({
        "platform": dev.platform, "device_kind": dev.device_kind,
        "device_count": jax.device_count(), "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": importlib.metadata.version("libtpu"),
        "compile_cache_dir": enable_compile_cache()}), flush=True)

    shared = {}
    code = run_phases((
        ("train.resnet50",
         lambda r: shared.update(losses=phase_train_resnet50(r))),
        ("train.bert_base", phase_train_bert),
        ("kernel.flash_attention", phase_flash_attention),
        ("serve.gpt2_small", phase_serve_lm),
        ("mesh.four_chips", lambda r: phase_mesh(r, shared["losses"])),
    ), {"platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}, CompileCounter())
    faulthandler.cancel_dump_traceback_later()
    return code


if __name__ == "__main__":
    sys.exit(main())
