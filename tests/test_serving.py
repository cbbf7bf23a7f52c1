"""Serving-tier tests (ISSUE 8): bucketed warm executables, the
recompute reference for generation, admission control, multi-model routing,
plus the ParallelInference shutdown-race / batch-poisoning fixes and the
JsonModelServer client-disconnect guard."""
import json
import socket
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.datasets import DataSet, ListDataSetIterator
from deeplearning4j_tpu.learning import Adam
from deeplearning4j_tpu.models import MultiLayerNetwork
from deeplearning4j_tpu.nlp.transformer import TransformerLM
from deeplearning4j_tpu.nn.conf import (InputType, NeuralNetConfiguration)
from deeplearning4j_tpu.nn.conf.attention import SelfAttentionLayer
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.conf.recurrent import RnnOutputLayer
from deeplearning4j_tpu.remote import (AdmissionControl, BucketedExecutor,
                                       BucketLadder, ForwardServing,
                                       InferenceServer, ModelRegistry,
                                       ServiceOverloaded)
from deeplearning4j_tpu.remote.serving import histogram_quantile
from deeplearning4j_tpu.telemetry import get_registry, serving_metrics

pytestmark = pytest.mark.serving


def _mlp(nIn=4, nOut=2, seed=1):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-2))
            .list()
            .layer(DenseLayer.builder().nIn(nIn).nOut(8).activation("relu")
                   .build())
            .layer(OutputLayer.builder("mcxent").nIn(8).nOut(nOut)
                   .activation("softmax").build())
            .build())
    return MultiLayerNetwork(conf).init()


def _attn_net(nIn=6, t=8, seed=2):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-3))
            .list()
            .layer(SelfAttentionLayer(nHeads=2, headSize=4, nOut=8))
            .layer(RnnOutputLayer.builder("mse").nOut(3)
                   .activation("identity").build())
            .setInputType(InputType.recurrent(nIn, t)).build())
    return MultiLayerNetwork(conf).init()


def _post(port, path, obj, timeout=30):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(obj).encode("utf-8"),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


# ------------------------------------------------------------ ladder ----

def test_bucket_ladder_selection():
    lad = BucketLadder(batchSizes=(1, 2, 4, 8), seqLens=(16, 32, 64))
    assert lad.batchBucket(1) == 1
    assert lad.batchBucket(3) == 4
    assert lad.batchBucket(8) == 8
    assert lad.batchBucket(50) == 8          # chunked, not re-traced
    assert lad.seqBucket(10) == 16
    assert lad.seqBucket(33) == 64
    with pytest.raises(ValueError, match="exceeds the top bucket"):
        lad.seqBucket(65)


# ----------------------------------------------- padding correctness ----

def test_padded_forward_matches_unpadded_mlp():
    net = _mlp()
    fs = ForwardServing(net, BucketLadder(batchSizes=(4, 8), seqLens=()),
                        inputShape=(4,))
    ex = BucketedExecutor(fs, name="pad-mlp").start()
    try:
        rng = np.random.RandomState(0)
        for n in (1, 3, 4, 7):               # all round UP to a bucket
            x = rng.randn(n, 4).astype(np.float32)
            out = ex.submit(x)
            ref = np.asarray(net.output(x).numpy())
            assert out.shape == ref.shape
            np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)
    finally:
        ex.shutdown()


def test_seq_padded_forward_matches_unpadded_attention():
    """Rank-3 requests pad the time axis up to the seq bucket and ride a
    features mask — outputs at every REAL timestep must equal the
    unpadded forward (mask-correct attention padding)."""
    net = _attn_net(nIn=6, t=8)
    fs = ForwardServing(net, BucketLadder(batchSizes=(2, 4),
                                          seqLens=(8, 16)),
                        inputShape=(6, None))
    ex = BucketedExecutor(fs, name="pad-attn").start()
    try:
        rng = np.random.RandomState(1)
        for n, t in ((1, 5), (2, 8), (3, 11)):
            x = rng.randn(n, 6, t).astype(np.float32)
            out = ex.submit(x)
            mask = np.ones((n, t), np.float32)
            ref = np.asarray(net.output(x, featuresMask=mask).numpy())
            assert out.shape == ref.shape == (n, 3, t)
            np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)
    finally:
        ex.shutdown()


def test_oversized_request_chunks_at_top_bucket():
    net = _mlp()
    fs = ForwardServing(net, BucketLadder(batchSizes=(2, 4), seqLens=()),
                        inputShape=(4,))
    ex = BucketedExecutor(fs, name="chunk").start()
    try:
        x = np.random.RandomState(2).randn(11, 4).astype(np.float32)
        out = ex.submit(x)
        np.testing.assert_allclose(out, np.asarray(net.output(x).numpy()),
                                    rtol=1e-5, atol=1e-6)
        # chunking stayed on warm executables
        assert serving_metrics().compile_misses().value(model="chunk") == 0
    finally:
        ex.shutdown()


# ------------------------------------------------------- warm starts ----

def test_warm_start_second_request_zero_compiles():
    net = _mlp()
    fs = ForwardServing(net, BucketLadder(batchSizes=(1, 2, 4), seqLens=()),
                        inputShape=(4,))
    ex = BucketedExecutor(fs, name="warm").start()
    try:
        sm = serving_metrics()
        warmed = sm.warmup_compiles().value(model="warm")
        assert warmed >= 1                   # the ladder compiled eagerly
        rng = np.random.RandomState(3)
        for _ in range(6):
            ex.submit(rng.randn(3, 4).astype(np.float32))
        assert sm.compile_misses().value(model="warm") == 0
        assert sm.compile_hits().value(model="warm") >= 6
        assert ex.compileHitRate() == 1.0
    finally:
        ex.shutdown()


def test_scheduler_coalesces_concurrent_requests():
    """Concurrent submits coalesce into shared dispatches and every
    caller gets exactly its own rows back."""
    net = _mlp()
    fs = ForwardServing(net, BucketLadder(batchSizes=(1, 2, 4, 8),
                                          seqLens=()), inputShape=(4,))
    ex = BucketedExecutor(fs, name="coalesce").start()
    try:
        rng = np.random.RandomState(4)
        xs = [rng.randn(2, 4).astype(np.float32) for _ in range(12)]
        outs = [None] * len(xs)

        def worker(i):
            outs[i] = ex.submit(xs[i])

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(len(xs))]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        for x, o in zip(xs, outs):
            np.testing.assert_allclose(
                o, np.asarray(net.output(x).numpy()), rtol=1e-5, atol=1e-6)
        assert serving_metrics().compile_misses().value(
            model="coalesce") == 0
    finally:
        ex.shutdown()


# --------------------------------------------------- admission control ----

def test_load_shed_429_with_retry_after():
    net = _mlp()

    class SlowServing(ForwardServing):
        def dispatch(self, key, reqs):
            time.sleep(0.15)
            return super().dispatch(key, reqs)

    fs = SlowServing(net, BucketLadder(batchSizes=(1, 2), seqLens=()),
                     inputShape=(4,))
    reg = ModelRegistry()
    reg.register("slow", fs,
                 admission=AdmissionControl(maxQueueRows=2,
                                            retryAfter=2.5))
    srv = InferenceServer(reg, port=0).start()
    try:
        x = np.zeros((1, 4), np.float32).tolist()
        codes, retry_after = [], []
        lock = threading.Lock()

        def hammer():
            try:
                code, _ = _post(srv.port, "/v1/serving/slow",
                                {"features": x})
                with lock:
                    codes.append(code)
            except urllib.error.HTTPError as e:
                with lock:
                    codes.append(e.code)
                    if e.code == 429:
                        retry_after.append(e.headers.get("Retry-After"))

        threads = [threading.Thread(target=hammer) for _ in range(12)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert 429 in codes, codes           # overload shed
        assert 200 in codes, codes           # but admitted work completed
        assert retry_after and retry_after[0] == "3"    # ceil(2.5)
        assert serving_metrics().shed().value(
            model="slow", rule="serving_queue_full") >= 1
    finally:
        srv.stop()


def test_admission_p99_rule_sheds():
    """The p99 admission rule is a plain ThresholdRule over the
    dl4j_tpu_serving_p99_seconds gauge the executor maintains — but it
    only applies while a backlog exists (with everything shed no dispatch
    would ever refresh the gauge, and an idle server would 429 forever
    off the stale value)."""
    net = _mlp()
    fs = ForwardServing(net, BucketLadder(batchSizes=(1, 2), seqLens=()),
                        inputShape=(4,))
    ex = BucketedExecutor(fs, name="p99",
                          admission=AdmissionControl(
                              maxQueueRows=10_000, p99Threshold=0.5)
                          ).start()
    try:
        x = np.zeros((1, 4), np.float32)
        ex.submit(x)                         # healthy: admitted
        serving_metrics().p99_seconds().set(0.75, model="p99")
        fired = ex.admission.check(queuedRows=3)     # backlog: sheds
        assert fired is not None and fired[0] == "serving_p99_high"
        assert ex.admission.check(queuedRows=0) is None   # idle: admits
        ex.submit(x)      # empty queue -> served, refreshing the gauge
        assert serving_metrics().p99_seconds().value(model="p99") < 0.5
        serving_metrics().p99_seconds().set(0.01, model="p99")
        assert ex.admission.check(queuedRows=3) is None   # recovered
    finally:
        ex.shutdown()


def test_submit_timeout_cancels_queued_request():
    """A timed-out submit removes its request from the queue — it must
    not be dispatched later at full device cost with nobody waiting."""
    net = _mlp()

    class SlowServing(ForwardServing):
        def dispatch(self, key, reqs):
            time.sleep(0.4)
            return super().dispatch(key, reqs)

    fs = SlowServing(net, BucketLadder(batchSizes=(1, 2), seqLens=()),
                     inputShape=(4,))
    ex = BucketedExecutor(fs, name="cancel").start()
    try:
        x = np.zeros((1, 4), np.float32)
        th = threading.Thread(target=lambda: ex.submit(x))
        th.start()
        time.sleep(0.1)                      # worker now mid-dispatch
        with pytest.raises(TimeoutError):
            ex.submit(x, timeout=0.05)       # queued behind, abandoned
        assert ex.queuedRows() == 0          # cancelled OUT of the queue
        th.join(timeout=10)
        ex.submit(x)                         # tier still serves
    finally:
        ex.shutdown()


def test_histogram_quantile_reads_bucket_bounds():
    from deeplearning4j_tpu.telemetry import MetricsRegistry
    reg = MetricsRegistry()                  # isolated: custom buckets
    h = reg.histogram("dl4j_tpu_serving_request_seconds",
                      "End-to-end request latency inside the serving "
                      "tier (enqueue to response ready), per model",
                      labelnames=("model",),
                      buckets=(0.01, 0.1, 1.0))
    for _ in range(99):
        h.observe(0.005, model="q")
    h.observe(0.5, model="q")
    assert histogram_quantile(h, 0.5, model="q") == 0.01
    assert histogram_quantile(h, 0.99, model="q") == 0.01
    assert histogram_quantile(h, 1.0, model="q") == 1.0


# ------------------------------------- the LM's prefill and its oracle ----

def test_left_padded_prefill_matches_unpadded():
    lm = TransformerLM(vocabSize=40, nLayers=1, nHeads=2, headSize=8,
                       maxLen=32, seed=9)
    rng = np.random.RandomState(1)
    toks = rng.randint(1, 40, (2, 9)).astype(np.int32)
    ref, kRef, _ = lm.prefillRaw(toks)
    padded = np.concatenate([np.zeros((2, 7), np.int32), toks], axis=1)
    got, kGot, _ = lm.prefillRaw(padded, lengths=[9, 9])
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(lm.forward(toks))[:, -1],
                               rtol=2e-5, atol=2e-5)
    # the real positions' K rows are the unpadded ones, behind the pad
    np.testing.assert_allclose(np.asarray(kGot)[:, :, :, 7:],
                               np.asarray(kRef), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("t", [3, 12])
def test_generate_is_stepwise_argmax_of_forward(t):
    """``generate`` is the reference the served path is held to, so it
    is itself held to the plainest statement: token i is the arg-max of
    ``forward`` over the UNPADDED sequence so far."""
    lm = TransformerLM(vocabSize=60, nLayers=2, nHeads=2, headSize=8,
                       maxLen=24, seed=7)
    seq = np.random.RandomState(t).randint(0, 60, (2, t)).astype(np.int32)
    got = lm.generate(seq, 5)
    assert got.shape == (2, 5) and got.dtype == np.int32
    for i in range(5):
        nxt = np.asarray(lm.forward(seq))[:, -1].argmax(-1)
        np.testing.assert_array_equal(got[:, i], nxt)
        seq = np.concatenate([seq, nxt[:, None].astype(np.int32)], axis=1)
    assert lm.compileCacheSize() == 1 + 5     # (2, maxLen), then 5 lengths
    lm.generate(seq[0, :t], 24 - t)           # one prompt, to the brim
    with pytest.raises(ValueError, match="capacity"):
        lm.generate(seq[:, :t], 24 - t + 1)


# --------------------------------------------------- multi-model HTTP ----

def test_multi_model_routing_and_404():
    netA, netB = _mlp(seed=1), _mlp(nIn=3, nOut=5, seed=2)
    reg = ModelRegistry()
    reg.register("a", ForwardServing(
        netA, BucketLadder(batchSizes=(1, 2, 4), seqLens=()),
        inputShape=(4,)))
    reg.register("b", ForwardServing(
        netB, BucketLadder(batchSizes=(1, 2, 4), seqLens=()),
        inputShape=(3,)))
    srv = InferenceServer(reg, port=0).start()
    try:
        rng = np.random.RandomState(5)
        xa = rng.randn(2, 4).astype(np.float32)
        xb = rng.randn(2, 3).astype(np.float32)
        _, outA = _post(srv.port, "/v1/serving/a", {"features": xa.tolist()})
        _, outB = _post(srv.port, "/v1/serving/b", {"features": xb.tolist()})
        np.testing.assert_allclose(np.asarray(outA["output"]),
                                   np.asarray(netA.output(xa).numpy()),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(outB["output"]),
                                   np.asarray(netB.output(xb).numpy()),
                                   rtol=1e-5, atol=1e-6)
        # bare /v1/serving routes to the FIRST registered model
        _, outD = _post(srv.port, "/v1/serving", {"features": xa.tolist()})
        np.testing.assert_allclose(np.asarray(outD["output"]),
                                   np.asarray(outA["output"]))
        # unknown model -> 404 naming the hosted set
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(srv.port, "/v1/serving/nope", {"features": xa.tolist()})
        assert ei.value.code == 404
        assert "hosted" in json.loads(ei.value.read())["error"]
        # model listing on GET
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/v1/serving",
                timeout=10) as resp:
            assert json.loads(resp.read())["models"] == ["a", "b"]
        # a mismatched trailing shape 400s ONLY the offender
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(srv.port, "/v1/serving/a",
                  {"features": xb.tolist()})      # 3 cols at a 4-col model
        assert ei.value.code == 400
        _, ok = _post(srv.port, "/v1/serving/a", {"features": xa.tolist()})
        assert "output" in ok
    finally:
        srv.stop()


def test_serving_metrics_exposed_on_metrics_endpoint():
    net = _mlp()
    reg = ModelRegistry()
    reg.register("expo", ForwardServing(
        net, BucketLadder(batchSizes=(1, 2), seqLens=()), inputShape=(4,)))
    srv = InferenceServer(reg, port=0).start()
    try:
        _post(srv.port, "/v1/serving/expo",
              {"features": np.zeros((1, 4), np.float32).tolist()})
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=10) as resp:
            text = resp.read().decode()
        for name in ("dl4j_tpu_serving_request_seconds",
                     "dl4j_tpu_serving_queue_depth",
                     "dl4j_tpu_serving_requests_total",
                     "dl4j_tpu_serving_compile_cache_hits_total"):
            assert name in text, name
    finally:
        srv.stop()


# ------------------------------------------- ParallelInference fixes ----

class TestParallelInferenceFixes:
    def test_shutdown_rejects_and_joins(self):
        from deeplearning4j_tpu.parallel import ParallelInference
        net = _mlp()
        pi = ParallelInference.Builder(net).batchLimit(4).build()
        x = np.zeros((2, 4), np.float32)
        assert np.asarray(pi.output(x).numpy()).shape == (2, 2)
        worker = pi._worker
        pi.shutdown()
        assert worker is not None and not worker.is_alive()   # joined
        with pytest.raises(RuntimeError, match="shut down"):
            pi.output(x)                     # immediate, no hang
        pi.shutdown()                        # idempotent

    def test_enqueue_during_shutdown_never_hangs(self):
        """Requests racing a shutdown either serve or fail fast — the
        seed code could strand a request enqueued after the drain loop."""
        from deeplearning4j_tpu.parallel import ParallelInference
        net = _mlp()
        pi = ParallelInference.Builder(net).batchLimit(4).build()
        x = np.zeros((1, 4), np.float32)
        results = []
        lock = threading.Lock()

        def caller():
            try:
                out = pi.output(x)
                with lock:
                    results.append(("ok", out))
            except RuntimeError as e:
                with lock:
                    results.append(("err", str(e)))

        threads = [threading.Thread(target=caller) for _ in range(16)]
        for th in threads:
            th.start()
        pi.shutdown()
        for th in threads:
            th.join(timeout=10)
        assert all(not th.is_alive() for th in threads)   # nobody hangs
        assert len(results) == 16
        for kind, val in results:
            if kind == "err":
                assert "shut down" in val

    def test_bad_first_request_does_not_poison_the_instance(self):
        """The serving shape latches from the first SUCCESSFUL batch —
        a malformed first request fails alone and valid traffic after it
        still serves (latching from the first request seen would 400
        every correct request forever)."""
        from deeplearning4j_tpu.parallel import ParallelInference
        net = _mlp()                         # expects trailing (4,)
        pi = ParallelInference.Builder(net).batchLimit(4).build()
        try:
            with pytest.raises(Exception):
                pi.output(np.zeros((2, 3), np.float32))   # model rejects
            out = pi.output(np.zeros((2, 4), np.float32))  # still serves
            assert np.asarray(out.numpy()).shape == (2, 2)
            with pytest.raises(ValueError, match="does not match"):
                pi.output(np.zeros((2, 3), np.float32))   # now latched
        finally:
            pi.shutdown()

    def test_batch_poisoning_rejects_only_offender(self):
        from deeplearning4j_tpu.parallel import ParallelInference
        net = _mlp()
        pi = ParallelInference.Builder(net).batchLimit(8).build()
        good = np.random.RandomState(0).randn(2, 4).astype(np.float32)
        outs, errs = [], []
        lock = threading.Lock()

        def good_caller():
            out = pi.output(good)
            with lock:
                outs.append(np.asarray(out.numpy()))

        def bad_caller():
            try:
                pi.output(np.zeros((2, 3), np.float32))   # wrong trailing
            except ValueError as e:
                with lock:
                    errs.append(str(e))

        try:
            pi.output(good)                  # pins the serving shape
            threads = [threading.Thread(target=good_caller)
                       for _ in range(6)]
            threads.append(threading.Thread(target=bad_caller))
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=10)
            assert len(errs) == 1 and "does not match" in errs[0]
            assert len(outs) == 6            # every good request served
            ref = np.asarray(net.output(good).numpy())
            for o in outs:
                np.testing.assert_allclose(o, ref, rtol=1e-5, atol=1e-6)
        finally:
            pi.shutdown()


# ------------------------------------------- JsonModelServer guard ----

def test_json_server_survives_client_disconnect():
    """A client that hangs up before reading its reply must not kill the
    handler thread (BrokenPipeError guard) — the next request serves."""
    from deeplearning4j_tpu.remote import JsonModelServer, \
        JsonRemoteInference
    net = _mlp()
    net.fit(ListDataSetIterator(
        [DataSet(np.random.RandomState(0).randn(16, 4).astype(np.float32),
                 np.eye(2, dtype=np.float32)[
                     np.random.RandomState(0).randint(0, 2, 16)])],
        batch=16), epochs=1)
    server = JsonModelServer(net, port=0).start()
    try:
        payload = json.dumps(
            {"features": np.zeros((1, 4)).tolist()}).encode()
        req = (b"POST /v1/serving HTTP/1.1\r\nHost: x\r\n"
               b"Content-Type: application/json\r\n"
               b"Content-Length: " + str(len(payload)).encode() +
               b"\r\n\r\n" + payload)
        # fire the request and slam the socket before the reply lands
        s = socket.create_connection(("127.0.0.1", server.port), timeout=10)
        s.sendall(req)
        s.close()
        time.sleep(0.3)                       # let the handler hit the pipe
        out = JsonRemoteInference(port=server.port).predict(
            np.zeros((2, 4), np.float32))
        assert out.shape == (2, 2)            # server still serving
    finally:
        server.stop()
