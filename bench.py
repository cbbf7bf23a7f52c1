"""Benchmark: ResNet-50 training throughput on one TPU chip.

BASELINE.json metric: "ResNet-50 ImageNet images/sec/chip".  Runs the fused
XLA train step (fwd+bwd+updater in one executable) over a pool of DISTINCT
pre-staged batches cycled per step (params change every step, so no
dispatch dedup is possible); every timed window ends in
``jax.block_until_ready`` or a fetch that depends on the whole chain.

``python bench.py`` (no flag) is the chip benchmark: it fails unless the
platform is ``tpu``, and a phase that raises is a non-zero exit.  The
flagged modes (``--mesh``, ``--recsys``, ``--serving``, ``--streaming``,
``--coldstart``) are CPU proxies: they report counts and ratios, never an
MFU.  Every mode prints ONE JSON line that carries ``platform``,
``device_kind`` and ``device_count``.  Host->device input streaming is
reported separately as ``h2d_mb_s``, never folded into the headline.

MFU basis: 2*MAC standard counting against the published bf16 peak of the
``device_kind`` the run found (``_CHIP_PEAKS``).
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

# First measurement of this project (round 1): the float32, batch-64 fused
# step reached 304.97 images/sec on one v5e chip.  That number is the
# recorded baseline; vs_baseline tracks improvements against it.
_BASELINE_IPS = 304.97

#: published per-chip peaks, keyed by jax's ``device_kind`` (Google Cloud
#: documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM).  A kind that
#: is not in the table is an error, never a default.
_CHIP_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}
# ResNet-50 @224: ~4.09 GMAC forward/image -> 8.18 GFLOP (2*MAC); training
# fwd+bwd ~= 3x forward.
_TRAIN_FLOPS_PER_IMAGE = 3 * 2 * 4.089e9
#: bytes one batch-256 bf16 ResNet-50 step moves, from XLA's cost analysis
#: in an earlier round on older code; not re-derived for today's step
_RESNET50_STEP_BYTES = 75.6e9


def _device_fields() -> dict:
    """What every JSON line says about where it ran."""
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": jax.device_count()}


def _chip_peaks() -> dict:
    import jax
    kind = jax.devices()[0].device_kind
    if kind not in _CHIP_PEAKS:
        raise KeyError(f"no published peak for device_kind {kind!r}: add "
                       "it to _CHIP_PEAKS with its source")
    return _CHIP_PEAKS[kind]


def bench_bert(batch: int = 256, seq: int = 128, steps: int = 64):
    """BERT-base MLM train step (SameDiff graph path, bf16 compute) —
    BASELINE.json config #3.  The History return is ONE stacked loss
    fetch that depends on every step, which ends the timed window.
    Returns (tokens/sec, mfu): mfu uses the XLA cost analysis of the
    exact compiled step against the chip's published bf16 peak."""
    from deeplearning4j_tpu.datasets.dataset import MultiDataSet
    from deeplearning4j_tpu.learning import Adam
    from deeplearning4j_tpu.zoo.bert import BertBase

    bert = BertBase("mlm")
    bert.setTrainingConfig(updater=Adam(2e-5), dataType="BFLOAT16")
    rng = np.random.RandomState(0)
    pool = []
    for _ in range(2):
        toks = rng.randint(0, 30522, (batch, seq)).astype(np.int32)
        segs = np.zeros((batch, seq), np.int32)
        mask = np.ones((batch, seq), np.float32)
        labels = rng.randint(0, 30522, (batch, seq)).astype(np.int32)
        lmask = (rng.rand(batch, seq) < 0.15).astype(np.float32)
        pool.append(MultiDataSet(features=[toks, segs, mask],
                                 labels=[labels, lmask]))

    sd = bert.sd
    sd.fit(pool, epochs=1)               # compile + warm (2 steps, synced)
    step_flops = sd.stepCostAnalysis(pool[0])["flops"]

    t0 = time.perf_counter()
    hist = sd.fit(pool, epochs=steps // 2)   # History -> one stacked sync
    dt = time.perf_counter() - t0
    n_steps = (steps // 2) * len(pool)
    assert hist is not None
    tps = batch * seq * n_steps / dt
    mfu = step_flops / (dt / n_steps) / _chip_peaks()["bf16_flops"]
    return tps, mfu


def bench_attention(t: int, b: int = 4, h: int = 12, d: int = 64,
                    inner: int = 0, reps: int = 5):
    """Fused-attention micro-bench, flash Pallas vs XLA dense, fwd+bwd.
    The step loop runs INSIDE one jitted fori_loop, so per-call dispatch
    is paid once per window, not once per <15 ms kernel.  Each
    iteration's q depends on the previous q-gradient, and ONE final fetch
    ends the chain.  Median of ``reps`` paired windows.  Returns
    {impl: seconds/step}."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.parallel.ring import dot_product_attention

    rng = np.random.RandomState(0)
    q0 = jnp.asarray(rng.randn(b, h, t, d), jnp.bfloat16)
    k0 = jnp.asarray(rng.randn(b, h, t, d), jnp.bfloat16)
    v0 = jnp.asarray(rng.randn(b, h, t, d), jnp.bfloat16)
    if not inner:
        # keep per-window device work well above host timer jitter:
        # shorter sequences get proportionally more in-loop steps
        inner = 16 * max(1, 4096 // t)
    out = {}
    for impl in ("dense", "flash"):
        def loss(q):
            o = dot_product_attention(q, k0, v0, causal=True, impl=impl)
            return jnp.sum(o.astype(jnp.float32))

        def body(_i, q):
            gq = jax.grad(loss)(q)
            return q + (1e-6 * gq).astype(q.dtype)

        def make_run(n):
            @jax.jit
            def run(q):
                q = jax.lax.fori_loop(0, n, body, q)
                return jnp.sum(q.astype(jnp.float32))
            return run

        # paired windows of N and 2N steps: the difference cancels the
        # constant dispatch + final-fetch cost of a window
        run1, run2 = make_run(inner), make_run(2 * inner)
        float(run1(q0))
        float(run2(q0))                  # compile + warm both
        diffs = []
        for _ in range(reps):
            t0 = time.perf_counter()
            float(run1(q0))
            t1 = time.perf_counter()
            float(run2(q0))
            t2 = time.perf_counter()
            diffs.append(((t2 - t1) - (t1 - t0)) / inner)
        # median difference: min of a noisy difference biases toward 0
        out[impl] = max(float(np.median(diffs)), 1e-9)
    return out


def bench_long_context(t: int = 2048, b: int = 4, steps: int = 6):
    """Long-context attention-model train step through the model DSL:
    SelfAttentionLayer at T>=1024 auto-dispatches the flash kernel on TPU
    (nn/conf/attention.py dispatch).  Returns tokens/sec."""
    from deeplearning4j_tpu.datasets import DataSet
    from deeplearning4j_tpu.learning import Adam
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.nn.conf import (InputType,
                                            NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf.attention import SelfAttentionLayer
    from deeplearning4j_tpu.nn.conf.recurrent import RnnOutputLayer

    nIn = 128
    conf = (NeuralNetConfiguration.builder().seed(5).updater(Adam(1e-3))
            .dataType("BFLOAT16").list()
            .layer(SelfAttentionLayer(nHeads=8, headSize=16, nOut=nIn))
            .layer(SelfAttentionLayer(nHeads=8, headSize=16, nOut=nIn))
            .layer(RnnOutputLayer.builder("mse").nOut(8)
                   .activation("identity").build())
            .setInputType(InputType.recurrent(nIn, t)).build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.RandomState(1)
    pool = [DataSet(rng.randn(b, nIn, t).astype(np.float32),
                    rng.randn(b, 8, t).astype(np.float32))
            for _ in range(2)]
    net.fit(pool[0])
    net.fit(pool[1])
    net.score()
    t0 = time.perf_counter()
    for i in range(steps):
        net.fit(pool[i % 2])
    net.score()
    return b * t * steps / (time.perf_counter() - t0)


class StreamingImageSource:
    """Picklable decode-heavy synthetic image source for the streaming-ETL
    benchmark: per image it runs the work a real JPEG path pays on the
    host (entropy-ish byte generation stands in for Huffman decode, then
    bilinear resize, float conversion, per-channel normalize, HWC->CHW)
    so the measurement stresses Python-side decode + H2D, not the model.
    ``shard()`` is the producer-pool contract: worker ``i`` of ``n``
    decodes batches ``i % n`` only — no image decoded twice."""

    def __init__(self, nBatches: int, batch: int, img: int,
                 classes: int = 100, _lo: int = 0, _stride: int = 1):
        from deeplearning4j_tpu.datasets.iterator import DataSetIterator
        self.nBatches, self.batch, self.img = nBatches, batch, img
        self.classes = classes
        self._lo, self._stride = _lo, _stride
        self._ids = list(range(_lo, nBatches, _stride))
        self._i = 0
        self._dsi = DataSetIterator         # keep the SPI import alive

    def streaming(self) -> bool:
        return True

    def shard(self, index: int, count: int) -> "StreamingImageSource":
        return StreamingImageSource(self.nBatches, self.batch, self.img,
                                    self.classes, _lo=index, _stride=count)

    def hasNext(self) -> bool:
        return self._i < len(self._ids)

    def reset(self) -> None:
        self._i = 0

    def batchSizeOf(self) -> int:
        return self.batch

    def _decode_one(self, rng, raw_hw: int):
        raw = rng.randint(0, 256, (raw_hw, raw_hw, 3)).astype(np.uint8)
        ys = (np.arange(self.img) * raw_hw / self.img)
        y0 = ys.astype(int)
        fy = (ys - y0)[:, None, None]
        xs = (np.arange(self.img) * raw_hw / self.img)
        x0 = xs.astype(int)
        fx = (xs - x0)[None, :, None]
        y1 = np.minimum(y0 + 1, raw_hw - 1)
        x1 = np.minimum(x0 + 1, raw_hw - 1)
        f = raw.astype(np.float32)
        img = ((f[y0][:, x0] * (1 - fy) + f[y1][:, x0] * fy) * (1 - fx)
               + (f[y0][:, x1] * (1 - fy) + f[y1][:, x1] * fy) * fx)
        img = (img / 255.0 - 0.45) / 0.225
        return np.ascontiguousarray(img.transpose(2, 0, 1))

    def next(self, num: int = 0):
        from deeplearning4j_tpu.datasets.dataset import DataSet
        bid = self._ids[self._i]
        self._i += 1
        rng = np.random.RandomState(1000 + bid)
        raw_hw = self.img + self.img // 2
        x = np.stack([self._decode_one(rng, raw_hw)
                      for _ in range(self.batch)])
        y = np.eye(self.classes, dtype=np.float32)[
            rng.randint(0, self.classes, self.batch)]
        return DataSet(x.astype(np.float32), y)


#: step-time decomposition series (see telemetry.instrument
#: StepPhaseMetrics) reported by --mesh and --streaming
_STEP_PHASE_SERIES = {
    "data_wait": "dl4j_tpu_step_data_wait_seconds",
    "h2d": "dl4j_tpu_step_h2d_seconds",
    "compute": "dl4j_tpu_step_compute_seconds",
    "checkpoint": "dl4j_tpu_step_checkpoint_seconds",
    "barrier": "dl4j_tpu_step_barrier_seconds",
}


def _phase_snapshot() -> dict:
    """Cumulative bucket counts/sum/count of every step-phase histogram
    — taken before a measured window so the decomposition reports the
    window's delta, not the process's lifetime."""
    from deeplearning4j_tpu.telemetry import get_registry
    reg = get_registry()
    snap = {}
    for phase, name in _STEP_PHASE_SERIES.items():
        h = reg.get(name)
        if h is None:
            snap[phase] = {"counts": {}, "sum": 0.0, "count": 0}
        else:
            snap[phase] = {"counts": dict(h.bucketCounts()),
                           "sum": float(h.sum()), "count": int(h.count())}
    return snap


def _phase_decomposition(before: dict) -> dict:
    """Step-time decomposition over the window since ``before`` (a
    :func:`_phase_snapshot`): per-phase p50/p99 in ms (upper-bound
    bucket attribution — the same convention as
    ``remote.serving.histogram_quantile``) plus each phase's share of
    the summed phase time.  Phases unobserved in the window report null
    quantiles and share 0."""
    import math
    after = _phase_snapshot()
    empty = {"counts": {}, "sum": 0.0, "count": 0}
    deltas = {}
    for phase in _STEP_PHASE_SERIES:
        b = before.get(phase) or empty
        a = after[phase]
        dcounts = {bound: cum - b["counts"].get(bound, 0)
                   for bound, cum in a["counts"].items()}
        deltas[phase] = (dcounts, a["sum"] - b["sum"],
                         a["count"] - b["count"])
    totalSum = sum(max(d[1], 0.0) for d in deltas.values())
    out = {}
    for phase, (dcounts, dsum, dcount) in deltas.items():
        if dcount <= 0:
            out[phase] = {"p50_ms": None, "p99_ms": None, "share": 0.0}
            continue

        def _q(q, dcounts=dcounts, dcount=dcount):
            rank = q * dcount
            prev = 0.0
            for bound, cum in dcounts.items():
                if cum >= rank:
                    return bound if not math.isinf(bound) else prev
                prev = bound
            return prev

        out[phase] = {
            "p50_ms": round(_q(0.5) * 1e3, 3),
            "p99_ms": round(_q(0.99) * 1e3, 3),
            "share": round(dsum / totalSum, 4) if totalSum > 0 else 0.0}
    return out


def bench_streaming(workers: int = 4, batch: int = 64, img: int = 96,
                    batches: int = 24) -> dict:
    """Streaming-ETL benchmark (ROADMAP item 2 / ISSUE 6 acceptance):
    the SAME decode-heavy source drained two ways —

    - ``naive``: the seed streaming path (single process decodes each
      batch inline, then a blocking host->device transfer the step must
      wait out);
    - ``pipeline``: ``PrefetchingDataSetIterator`` — ``workers`` decode
      processes sharded over the batches, shared-memory assembly, and
      the double-buffered async H2D staging ring.

    Both consume through one tiny jitted reduction per batch (forces the
    data on device without model noise).  H2D MB/s comes from the
    ``dl4j_tpu_etl_h2d_bytes_total`` / ``_seconds`` series the staging
    ring maintains — the exact counters the federated dashboards watch:
    ``h2d_mb_s`` is bytes over the summed per-transfer seconds,
    ``h2d_wall_mb_s`` bytes over the whole pipelined window.  With a
    trivial consumer both paths are decode-bound; the real-step overlap
    is measured by the fit-path integration, not this microbench.
    """
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.datavec.pipeline import \
        PrefetchingDataSetIterator
    from deeplearning4j_tpu.telemetry import get_registry

    src = StreamingImageSource(batches, batch, img)

    @jax.jit
    def consume(x):
        return jnp.sum(x)

    # warm the consumer executable outside both windows
    float(consume(jax.device_put(
        np.zeros((batch, 3, img, img), np.float32))))

    # -- naive single-process path (the seed shape) ---------------------
    src.reset()
    t0 = time.perf_counter()
    n_naive = 0
    while src.hasNext():
        ds = src.next()
        xb = ds.features.numpy()
        dev = jax.device_put(xb)
        jax.block_until_ready(dev)          # un-overlapped transfer
        float(consume(dev))
        n_naive += xb.shape[0]
    naive_s = time.perf_counter() - t0
    naive_ips = n_naive / naive_s

    # -- sharded pool + staging ring ------------------------------------
    reg = get_registry()
    b0 = reg.get("dl4j_tpu_etl_h2d_bytes_total")
    bytes0 = b0.value() if b0 is not None else 0.0
    h0 = reg.get("dl4j_tpu_etl_h2d_seconds")
    secs0 = h0.sum() if h0 is not None else 0.0
    pit = PrefetchingDataSetIterator(src, numWorkers=workers,
                                     queueDepth=max(4, workers + 2))
    from deeplearning4j_tpu.telemetry import etl_fetch
    phases0 = _phase_snapshot()
    try:
        t0 = time.perf_counter()
        n_pipe = 0
        while pit.hasNext():
            # etl_fetch is the instrumented fetch seam every training
            # loop drains through — the bench pays the same data_wait
            # accounting the supervised loop reports
            ds = etl_fetch(pit)             # already staged on device
            float(consume(ds.features.jax))
            n_pipe += int(ds.features.shape[0])
        pipe_s = time.perf_counter() - t0
    finally:
        pit.close()
    pipe_ips = n_pipe / pipe_s
    h2d_bytes = (reg.get("dl4j_tpu_etl_h2d_bytes_total").value()
                 - bytes0)
    h2d_secs = reg.get("dl4j_tpu_etl_h2d_seconds").sum() - secs0
    assert n_pipe == n_naive, (n_pipe, n_naive)

    return {
        "metric": "streaming_etl_images_per_sec",
        "value": round(pipe_ips, 1),
        "unit": "images/sec",
        "naive_images_per_sec": round(naive_ips, 1),
        # capped by the HOST's real core parallelism: this container
        # advertises 2 CPUs whose measured 2-process scaling is ~1.1x
        # (sibling threads), so speedup here is a floor for real
        # multi-core hosts, not the pipeline's ceiling
        "speedup_vs_naive": round(pipe_ips / naive_ips, 3),
        "cpu_count": os.cpu_count(),
        # effective H2D rate of the staging ring: issue+wait seconds are
        # near zero once transfers overlap the consumer, so also report
        # wall-clock MB/s over the whole pipelined window
        "h2d_mb_s": round(h2d_bytes / max(h2d_secs, 1e-9) / 1e6, 1),
        "h2d_wall_mb_s": round(h2d_bytes / pipe_s / 1e6, 1),
        "h2d_bytes": int(h2d_bytes),
        "step_phases": _phase_decomposition(phases0),
        "workers": workers,
        "batch": batch,
        "image": img,
        "batches": batches,
    }


def bench_mesh(steps: int = 12, batch: int = 64, width: int = 512,
               depth: int = 4, classes: int = 16) -> dict:
    """Mesh-config sweep (ISSUE 10 acceptance): MFU + images/sec for the
    SAME model stepped through the unified ``MeshTrainer`` path under
    pure DP, DP x TP, and DP + ZeRO-1 ShardingPlans, on the
    ``xla_force_host_platform_device_count=8`` CPU proxy.

    Every config steps through ``ParallelWrapper.fitDataSet`` — the
    facade-over-MeshTrainer path the fault supervisor drives — and the
    steady-state discipline is measured, not assumed:
    ``jit_cache_misses_steady`` must be 0 after the first step.  On a
    TPU each config also carries an MFU (analytic dense-MLP flop count,
    3x fwd 2*MAC, against the chips' published bf16 peak); on the CPU
    proxy there is none, and the images/sec RATIOS between configs are
    the signal.
    """
    import jax

    from deeplearning4j_tpu.datasets import DataSet
    from deeplearning4j_tpu.learning import Adam
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.nn.conf import (InputType,
                                            NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.parallel import (DeviceMesh, ParallelWrapper,
                                             ZeroStage1)
    from deeplearning4j_tpu.telemetry import get_registry

    n_dev = len(jax.devices())
    peak = _chip_peaks()["bf16_flops"] * n_dev \
        if jax.default_backend() == "tpu" else None

    def build_net():
        b = (NeuralNetConfiguration.builder().seed(7).updater(Adam(1e-3))
             .list()
             .layer(DenseLayer.builder().nIn(width).nOut(width)
                    .activation("relu").build()))
        for _ in range(depth - 1):
            b.layer(DenseLayer.builder().nOut(width).activation("relu")
                    .build())
        b.layer(OutputLayer.builder("mcxent").nOut(classes)
                .activation("softmax").build())
        return MultiLayerNetwork(
            b.setInputType(InputType.feedForward(width)).build()).init()

    # fwd 2*MAC flops of the dense stack; train ~= 3x forward
    mlp_flops = 2 * (width * width * depth + width * classes)
    flops_per_image = 3 * mlp_flops

    rng = np.random.RandomState(0)
    pool = [DataSet(rng.randn(batch, width).astype(np.float32),
                    np.eye(classes, dtype=np.float32)[
                        rng.randint(0, classes, batch)])
            for _ in range(2)]

    configs = [
        ("dp", dict(data=n_dev), False, False),
        ("dp_tp", dict(data=n_dev // 2, model=2), True, False),
        ("dp_zero1", dict(data=n_dev), False, True),
    ]
    reg = get_registry()

    def misses():
        c = reg.get("dl4j_tpu_mesh_jit_cache_misses_total")
        return c.value() if c is not None else 0.0

    results = []
    for name, axes, tp, zero in configs:
        net = build_net()
        mesh = DeviceMesh(**axes)
        if zero:
            ZeroStage1(mesh).apply(net)
        pw = ParallelWrapper(net, mesh=mesh, tensorParallel=tp)
        pw.fitDataSet(pool[0])      # compile
        pw.fitDataSet(pool[1])      # warm both staged batches
        net.score()
        m0 = misses()
        phases0 = _phase_snapshot()
        t0 = time.perf_counter()
        for i in range(steps):
            pw.fitDataSet(pool[i % len(pool)])
        net.score()                 # forces the donated-param chain
        dt = time.perf_counter() - t0
        ips = batch * steps / dt
        results.append({
            "config": name,
            "mesh": {k: int(v) for k, v in axes.items()},
            "images_per_sec": round(ips, 1),
            "step_ms": round(dt / steps * 1e3, 3),
            # aggregate throughput over ALL mesh devices vs aggregate
            # peak (n_dev chips); a CPU line carries no mfu
            **({"mfu": round(ips * flops_per_image / peak, 6)}
               if peak else {}),
            "jit_cache_misses_steady": int(misses() - m0),
            "step_phases": _phase_decomposition(phases0),
        })

    best = max(results, key=lambda r: r["images_per_sec"])
    return {
        "metric": "mesh_train_images_per_sec",
        "value": best["images_per_sec"],
        "unit": "images/sec",
        "best_config": best["config"],
        "devices": n_dev,
        "batch": batch,
        "width": width,
        "depth": depth,
        "steps": steps,
        "step_phases": best["step_phases"],
        "configs": results,
    }


def bench_recsys(steps: int = 8, batch: int = 256,
                 tableRows: int = 131072, dim: int = 64) -> dict:
    """Recommender-tier bench (ISSUE 16 acceptance): embedding-lookup
    throughput, the table-parallel train step for a table bigger than
    one proxy device's replicated share, and top-k retrieval p50/p99
    through the continuous batcher.

    Three sections, one JSON line:

    - **lookup**: jitted two-phase ``bag_lookup_dedup`` rows/sec (raw
      id gathers per second) plus the host-observed dedup ratio and the
      static all-to-all bytes one table-parallel lookup would move;
    - **train**: ``ParallelWrapper.fitDataSet`` step time under
      DP x table-parallel (``data=2, model=4``) with the
      ``tableRows x dim`` f32 table row-sharded over ``model`` — on the
      8-device proxy each device holds 1/4 of the table instead of a
      full replica per device; ``jit_cache_misses_steady`` must be 0;
    - **serving**: top-k retrieval latency through ``ContinuousBatcher``
      (single-step sequences), p50/p99 over the request wall times.
    """
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.datasets import DataSet
    from deeplearning4j_tpu.learning import Adam
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.models.recsys import (DotProductScorer,
                                                  RetrievalLM,
                                                  topk_retrieve)
    from deeplearning4j_tpu.nn.conf import (InputType,
                                            NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf.embedding import (
        ShardedEmbeddingBag, alltoall_bytes_per_lookup, bag_lookup_dedup)
    from deeplearning4j_tpu.parallel import DeviceMesh, ParallelWrapper
    from deeplearning4j_tpu.remote import BucketLadder, ContinuousBatcher
    from deeplearning4j_tpu.telemetry import get_registry, recsys_metrics

    n_dev = len(jax.devices())
    fields, bag = 2, 8
    rng = np.random.RandomState(0)

    # -- lookup throughput ------------------------------------------------
    lk = jax.jit(lambda W, ids, w: bag_lookup_dedup(W, ids, w))
    W = jnp.asarray(rng.randn(32768, dim).astype(np.float32))
    ids = jnp.asarray(rng.zipf(1.3, (4096, 16)).clip(0, 32767)
                      .astype(np.int32))      # skewed, like real traffic
    wts = jnp.ones((4096, 16), jnp.float32)
    lk(W, ids, wts).block_until_ready()       # compile
    t0 = time.perf_counter()
    for _ in range(steps):
        lk(W, ids, wts).block_until_ready()
    lookup_s = time.perf_counter() - t0
    raw = int(ids.size) * steps
    uniqPerBatch = int(np.unique(np.asarray(ids)).size)
    rm = recsys_metrics()
    rm.lookup_rows().inc(raw, phase="raw")
    rm.lookup_rows().inc(uniqPerBatch * steps, phase="stored")
    rm.dedup_ratio().set(uniqPerBatch / ids.size)
    a2a = alltoall_bytes_per_lookup(4, uniqPerBatch, dim)
    rm.alltoall_bytes().inc(a2a * steps)
    rows_per_sec = raw / lookup_s

    # -- table-parallel train step ---------------------------------------
    conf = (NeuralNetConfiguration.builder().seed(7).updater(Adam(1e-3))
            .list()
            .layer(ShardedEmbeddingBag.builder()
                   .numEmbeddings(tableRows).embeddingDim(dim)
                   .numFields(fields).build())
            .layer(DotProductScorer.builder().embeddingDim(dim).build())
            .setInputType(InputType.feedForward(fields * bag)).build())
    net = MultiLayerNetwork(conf).init()
    mesh_axes = dict(data=max(n_dev // 4, 1), model=min(4, n_dev))
    pw = ParallelWrapper(net, mesh=DeviceMesh(**mesh_axes),
                         tensorParallel=True)
    pool = [DataSet(rng.randint(0, tableRows, (batch, fields * bag))
                    .astype(np.float32),
                    rng.randint(0, 2, (batch, 1)).astype(np.float32))
            for _ in range(2)]
    reg = get_registry()

    def misses():
        c = reg.get("dl4j_tpu_mesh_jit_cache_misses_total")
        return c.value() if c is not None else 0.0

    pw.fitDataSet(pool[0])      # compile
    pw.fitDataSet(pool[1])
    net.score()
    m0 = misses()
    t0 = time.perf_counter()
    for i in range(steps):
        pw.fitDataSet(pool[i % len(pool)])
    net.score()
    train_s = time.perf_counter() - t0
    table_bytes = tableRows * dim * 4

    # -- top-k serving ----------------------------------------------------
    vocab = 8192
    lm = RetrievalLM(rng.randn(vocab, dim).astype(np.float32),
                     rng.randn(vocab, dim).astype(np.float32),
                     maxLen=64)
    cb = ContinuousBatcher(lm, name="bench-recsys", pageSize=8,
                           maxSlots=4,
                           ladder=BucketLadder(batchSizes=(4,),
                                               seqLens=(16,))).start()
    lats = []
    try:
        prompts = [rng.randint(0, vocab, (12,)).astype(np.int32)
                   for _ in range(48)]
        topk_retrieve(cb, prompts[0][None, :], 10, timeout=120)  # warm
        for p in prompts[1:]:
            t0 = time.perf_counter()
            topk_retrieve(cb, p[None, :], 10, timeout=120)
            lats.append(time.perf_counter() - t0)
    finally:
        cb.shutdown()
    lats = np.asarray(lats)

    return {
        "metric": "recsys_lookup_rows_per_sec",
        "value": round(rows_per_sec, 1),
        "unit": "rows/sec",
        "devices": n_dev,
        "dedup_ratio": round(uniqPerBatch / ids.size, 4),
        "alltoall_bytes_per_lookup": int(a2a),
        "train": {
            "mesh": {k: int(v) for k, v in mesh_axes.items()},
            "table_rows": tableRows,
            "table_bytes": table_bytes,
            # the acceptance framing: the per-device share under
            # model=4 vs the full replica an unsharded table would pin
            "per_device_table_bytes": table_bytes // mesh_axes["model"],
            "step_ms": round(train_s / steps * 1e3, 3),
            "examples_per_sec": round(batch * steps / train_s, 1),
            "jit_cache_misses_steady": int(misses() - m0),
        },
        "serving": {
            "requests": len(lats),
            "topk_p50_ms": round(float(np.percentile(lats, 50)) * 1e3, 3),
            "topk_p99_ms": round(float(np.percentile(lats, 99)) * 1e3, 3),
        },
        "batch": batch,
        "steps": steps,
    }


def bench_serving(clients: int = 8, duration: float = 4.0,
                  warmup: float = 1.0, nIn: int = 32,
                  decodeTokens: int = 48) -> dict:
    """Serving-tier benchmark (ROADMAP item 1 / ISSUE 8 acceptance):
    sustained concurrent RPS + latency percentiles + compile-cache hit
    rate through the continuous-batching tier.

    ``clients`` threads hammer ``POST /v1/serving/mlp`` over HTTP with
    mixed batch sizes (1..4 rows — every request rounds UP to a warm
    bucket), so the measurement covers the full path: HTTP parse,
    admission, queue coalescing, padded dispatch on a warm executable,
    result split.  The hit rate is computed from the
    ``dl4j_tpu_serving_compile_cache_*`` counters over the measurement
    window only (warmup traffic excluded) — the acceptance bar is >= 0.9,
    i.e. steady state never triggers a fresh XLA trace.

    A second, in-process measurement drives the KV-cache decode path
    (``TransformerLM.generate``) and reports tokens/sec — generation cost
    per token is O(cache capacity), independent of tokens generated.
    """
    import urllib.request

    from deeplearning4j_tpu.learning import Adam
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.nlp.transformer import TransformerLM
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.remote import (AdmissionControl, BucketLadder,
                                           ForwardServing, GenerativeServing,
                                           InferenceServer, ModelRegistry)
    from deeplearning4j_tpu.telemetry import get_registry

    conf = (NeuralNetConfiguration.builder().seed(1).updater(Adam(1e-2))
            .list()
            .layer(DenseLayer.builder().nIn(nIn).nOut(64)
                   .activation("relu").build())
            .layer(OutputLayer.builder("mcxent").nIn(64).nOut(10)
                   .activation("softmax").build())
            .build())
    net = MultiLayerNetwork(conf).init()
    registry = ModelRegistry()
    registry.register(
        "mlp",
        ForwardServing(net, BucketLadder(batchSizes=(1, 2, 4, 8, 16),
                                         seqLens=()),
                       inputShape=(nIn,)),
        admission=AdmissionControl(maxQueueRows=4096))
    lm = TransformerLM(vocabSize=128, nLayers=2, nHeads=4, headSize=16,
                       maxLen=128, seed=2)
    registry.register("lm", GenerativeServing(
        lm, BucketLadder(batchSizes=(1, 2, 4), seqLens=(16, 32))))
    srv = InferenceServer(registry, port=0).start()    # warms the ladders

    rng = np.random.RandomState(0)
    payloads = [json.dumps({"features": rng.randn(b, nIn).astype(
        np.float32).tolist()}).encode("utf-8") for b in (1, 2, 3, 4)]
    url = f"http://127.0.0.1:{srv.port}/v1/serving/mlp"
    stop = time.perf_counter() + warmup + duration
    measure_from = time.perf_counter() + warmup
    lat: list = []
    counts = {"ok": 0, "shed": 0, "errors": 0}
    lock = __import__("threading").Lock()
    reg = get_registry()

    def snapshot():
        h = reg.get("dl4j_tpu_serving_compile_cache_hits_total")
        m = reg.get("dl4j_tpu_serving_compile_cache_misses_total")

        def val(c):
            try:
                return c.value(model="mlp") if c is not None else 0.0
            except ValueError:
                return 0.0
        return val(h), val(m)

    marks = {}

    def client(i):
        r = np.random.RandomState(100 + i)
        while True:
            now = time.perf_counter()
            if now >= stop:
                return
            if "t0" not in marks and now >= measure_from:
                with lock:
                    if "t0" not in marks:
                        marks["t0"] = now
                        marks["counters"] = snapshot()
            body = payloads[r.randint(len(payloads))]
            t0 = time.perf_counter()
            try:
                req = urllib.request.Request(
                    url, data=body,
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=30) as resp:
                    resp.read()
                dt = time.perf_counter() - t0
                with lock:
                    if t0 >= measure_from:
                        lat.append(dt)
                        counts["ok"] += 1
            except Exception as e:
                code = getattr(e, "code", None)
                with lock:
                    counts["shed" if code == 429 else "errors"] += 1

    import threading as _th
    threads = [_th.Thread(target=client, args=(i,)) for i in range(clients)]
    t_start = time.perf_counter()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    t_end = time.perf_counter()
    hits0, miss0 = marks.get("counters", (0.0, 0.0))
    hits1, miss1 = snapshot()

    # -- KV-cache decode throughput (in-process, the serving dispatch) ---
    prompt = rng.randint(1, 128, (4, 16)).astype(np.int32)
    lm.generate(prompt, 4)                   # warm prefill + decode
    t0 = time.perf_counter()
    lm.generate(prompt, decodeTokens)
    decode_s = time.perf_counter() - t0
    decode_tps = prompt.shape[0] * decodeTokens / decode_s
    srv.stop()

    cbatch = _bench_continuous_batching()
    spec = _bench_speculative()
    failover = _bench_serving_failover()

    window = t_end - marks.get("t0", t_start)
    lat.sort()

    def pct(q):
        if not lat:
            return None
        return round(lat[min(len(lat) - 1, int(q * len(lat)))] * 1e3, 2)

    dh, dm = hits1 - hits0, miss1 - miss0
    return {
        "metric": "serving_sustained_rps",
        "value": round(counts["ok"] / window, 1),
        "unit": "requests/sec",
        "p50_ms": pct(0.50),
        "p99_ms": pct(0.99),
        "requests_ok": counts["ok"],
        "requests_shed": counts["shed"],
        "requests_errored": counts["errors"],
        # steady-state discipline: EVERY measured dispatch must land on
        # an executable warmed at start() (acceptance: rate >= 0.9)
        "compile_cache_hit_rate": round(dh / (dh + dm), 4)
        if (dh + dm) > 0 else None,
        "compile_cache_hits": int(dh),
        "compile_cache_misses": int(dm),
        "decode_tokens_per_sec": round(decode_tps, 1),
        "decode_batch": int(prompt.shape[0]),
        "decode_new_tokens": int(decodeTokens),
        "clients": clients,
        "window_seconds": round(window, 2),
        **cbatch,
        **spec,
        **failover,
    }


def _bench_continuous_batching(duration: float = 4.0, maxSlots: int = 8,
                               clients: int = 24) -> dict:
    """Ragged-arrival continuous batching (ISSUE 15 acceptance):
    ``clients`` threads submit prompts of random bucketed lengths with
    random generation quotas against an iteration-level scheduler with
    ``maxSlots`` decode slots.  Reported: mean decode-slot occupancy
    (bar: >= 0.9 — a retired slot refills BETWEEN steps, so ragged
    traffic can't collapse the batch), goodput tokens/sec, request p99,
    and the steady-state jit-miss delta across all that admit/retire
    churn (bar: 0 — fixed slot shapes + warm per-bucket prefill means
    churn never re-traces)."""
    from deeplearning4j_tpu.nlp.transformer import TransformerLM
    from deeplearning4j_tpu.remote import ContinuousBatcher

    lm = TransformerLM(vocabSize=256, nLayers=2, nHeads=4, headSize=16,
                       maxLen=128, seed=3)
    cb = ContinuousBatcher(lm, name="cbatch", pageSize=16,
                           maxSlots=maxSlots).start()
    rng = np.random.RandomState(0)
    seen = cb.compileCacheSize()
    stop_at = time.perf_counter() + duration
    lat: list = []
    done = {"tokens": 0, "requests": 0, "shed": 0}
    lock = __import__("threading").Lock()

    def client(i):
        r = np.random.RandomState(1000 + i)
        while time.perf_counter() < stop_at:
            t = int(r.randint(4, 60))
            n = int(r.randint(8, 33))
            prompt = r.randint(1, 256, (1, t)).astype(np.int32)
            t0 = time.perf_counter()
            try:
                out = cb.submit({"tokens": prompt[0].tolist(),
                                 "maxNewTokens": n}, timeout=60)
            except Exception:
                with lock:
                    done["shed"] += 1
                time.sleep(0.01)
                continue
            dt = time.perf_counter() - t0
            with lock:
                lat.append(dt)
                done["tokens"] += int(out.shape[1])
                done["requests"] += 1

    import threading as _th
    threads = [_th.Thread(target=client, args=(i,))
               for i in range(clients)]
    t_start = time.perf_counter()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    window = time.perf_counter() - t_start
    misses = cb.compileCacheSize() - seen
    occ = cb.occupancy()
    cb.shutdown()
    lat.sort()
    p99 = round(lat[min(len(lat) - 1, int(0.99 * len(lat)))] * 1e3, 2) \
        if lat else None
    # latency decomposition off the serving histograms the batcher
    # observed under model="cbatch": time-to-first-token (admission +
    # prefill cost the client feels) vs inter-token gap (decode step
    # cadence) — the end-to-end p99 above conflates the two
    from deeplearning4j_tpu.remote.serving import histogram_quantile
    from deeplearning4j_tpu.telemetry import get_registry
    latq = {}
    for metric, key in (("dl4j_tpu_serving_ttft_seconds", "ttft"),
                        ("dl4j_tpu_serving_inter_token_seconds", "itl")):
        hist = get_registry().get(metric)
        for q, tag in ((0.5, "p50"), (0.99, "p99")):
            v = histogram_quantile(hist, q, model="cbatch") \
                if hist is not None else None
            latq[f"cbatch_{key}_{tag}_ms"] = \
                round(v * 1e3, 3) if v is not None else None
    return {
        "cbatch_occupancy": round(occ, 4) if occ is not None else None,
        "cbatch_goodput_tokens_per_sec": round(done["tokens"] / window, 1),
        "cbatch_requests_ok": done["requests"],
        "cbatch_requests_shed": done["shed"],
        "cbatch_p99_ms": p99,
        **latq,
        "cbatch_jit_cache_misses_steady": int(misses),
        "cbatch_slots": maxSlots,
        "cbatch_clients": clients,
    }


def _bench_serving_failover(replicas: int = 3, clients: int = 6,
                            maxNewTokens: int = 24) -> dict:
    """Serving fault-tolerance benchmark (ISSUE 17 acceptance):
    streaming clients against a :class:`ReplicaSet` while one replica
    is CRASHED mid-window (probe retirement + in-flight failover
    replay) and, after the window, a second is drained via
    ``scaleDown``.  Reported: failover count, request p99 during the
    crash window, drain p99 (the ``dl4j_tpu_serving_drain_seconds``
    histogram), and whether every stream matched the fault-free
    reference bit-for-bit — exactly-once delivery ACROSS the crash is
    part of the measurement, not a separate test."""
    from deeplearning4j_tpu.fault import injection as _inj
    from deeplearning4j_tpu.nlp.transformer import TransformerLM
    from deeplearning4j_tpu.remote import ContinuousBatcher, ReplicaSet
    from deeplearning4j_tpu.remote.serving import histogram_quantile
    from deeplearning4j_tpu.telemetry import get_registry, serving_metrics

    def lm():
        # identical weights per replica: greedy replay on a survivor is
        # bit-identical, so "streams exact" witnesses exactly-once
        return TransformerLM(vocabSize=64, nLayers=1, nHeads=2,
                             headSize=8, maxLen=96, seed=7)

    rs = ReplicaSet(lambda idx: ContinuousBatcher(lm(), maxSlots=2,
                                                  pageSize=8),
                    name="fobench", replicas=replicas,
                    maxReplicas=replicas, probeInterval=0.05,
                    probeTimeout=2.0, probeFailThreshold=2,
                    drainTimeout=10.0, seed=0).start()
    ref = lm()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 64, (int(rng.randint(4, 12)),)
                           ).astype(np.int32) for _ in range(clients)]
    refs = [[int(t) for t in ref.generate(p[None, :], maxNewTokens)[0]]
            for p in prompts]
    lat: list = []
    exact: list = []
    import threading as _th
    lock = _th.Lock()

    def client(i):
        t0 = time.perf_counter()
        try:
            got = [t for t in rs.submitStream(
                {"tokens": prompts[i].tolist(),
                 "maxNewTokens": maxNewTokens}) if isinstance(t, int)]
            dt = time.perf_counter() - t0
            with lock:
                lat.append(dt)
                exact.append(got == refs[i])
        except Exception:
            with lock:
                exact.append(False)

    try:
        # slow decode slightly so the crash lands mid-stream, not after
        for idx in range(replicas):
            _inj.set_replica_slowdown(f"fobench/{idx}", 0.01)
        threads = [_th.Thread(target=client, args=(i,))
                   for i in range(clients)]
        for th in threads:
            th.start()
        time.sleep(0.05)
        _inj.arm_replica_crash("fobench/1")
        for th in threads:
            th.join(timeout=120)
        _inj.clear_serving_faults()
        # graceful drain of one more replica, now that streams are done
        rs.scaleDown()
        drain_p99 = None
        end = time.monotonic() + 15.0
        while time.monotonic() < end:
            drain_p99 = histogram_quantile(
                serving_metrics().drain_seconds(), 0.99, model="fobench")
            if drain_p99 is not None:
                break
            time.sleep(0.05)
        fo = get_registry().get("dl4j_tpu_serving_failovers_total")
        try:
            failovers = int(fo.value(model="fobench")) if fo else 0
        except ValueError:
            failovers = 0
    finally:
        _inj.clear_serving_faults()
        rs.shutdown()
    lat.sort()
    p99 = round(lat[min(len(lat) - 1, int(0.99 * len(lat)))] * 1e3, 2) \
        if lat else None
    return {
        "failover_count": failovers,
        "failover_crash_window_p99_ms": p99,
        "failover_drain_p99_s": round(drain_p99, 4)
        if drain_p99 is not None else None,
        "failover_streams_exact": bool(exact) and all(exact),
        "failover_clients": clients,
        "failover_replicas": replicas,
    }


def _bench_speculative(newTokens: int = 96, draftK: int = 7) -> dict:
    """Speculative-decode tokens/sec comparison (ISSUE 15 acceptance:
    >= 2x on the CPU proxy, output bit-identical to target-only
    greedy).  The draft is constructed to agree with the target — the
    target's tail layers are zero-residual, so its logits EXACTLY equal
    the two-layer draft's (random weights cannot be distilled; the
    construction gives an honest acceptance-rate-1.0 upper bound, and
    the acceptance rate is reported so the number reads as what it
    is).  The win is structural: k+1 greedy tokens cost one fused
    draft-proposal scan plus ONE batched verify forward instead of k+1
    sequential decode dispatches."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.nlp.transformer import TransformerLM

    tgt = TransformerLM(vocabSize=256, nLayers=6, nHeads=4, headSize=16,
                        maxLen=128, seed=4)
    for lp in tgt.params["layers"][2:]:
        lp["Wo"] = jnp.zeros_like(lp["Wo"])
        lp["Wp"] = jnp.zeros_like(lp["Wp"])
        lp["bp"] = jnp.zeros_like(lp["bp"])
    draft = TransformerLM(vocabSize=256, nLayers=2, nHeads=4, headSize=16,
                          maxLen=128, seed=4)
    draft.params = {"emb": tgt.params["emb"], "pos": tgt.params["pos"],
                    "lnf_g": tgt.params["lnf_g"],
                    "lnf_b": tgt.params["lnf_b"],
                    "layers": list(tgt.params["layers"][:2])}
    rng = np.random.RandomState(0)
    prompt = rng.randint(1, 256, (1, 16)).astype(np.int32)
    tgt.generate(prompt, 4)                          # warm both paths
    tgt.speculative_generate(draft, prompt, 4, draftK=draftK)
    t0 = time.perf_counter()
    ref = tgt.generate(prompt, newTokens)
    t_greedy = time.perf_counter() - t0
    t0 = time.perf_counter()
    out, stats = tgt.speculative_generate(draft, prompt, newTokens,
                                          draftK=draftK, returnStats=True)
    t_spec = time.perf_counter() - t0
    return {
        "spec_tokens_per_sec": round(newTokens / t_spec, 1),
        "spec_greedy_tokens_per_sec": round(newTokens / t_greedy, 1),
        "spec_speedup": round(t_greedy / t_spec, 3),
        "spec_bit_identical": bool(np.array_equal(out, ref)),
        "spec_accept_rate": round(stats["acceptRate"], 4),
        "spec_draft_k": draftK,
        "spec_new_tokens": newTokens,
    }


def bench_coldstart(nIn: int = 32, hidden: int = 64, classes: int = 10,
                    batch: int = 16, steps: int = 4) -> dict:
    """Cold-start benchmark (ROADMAP item 2 / ISSUE 13 acceptance):
    restart-to-first-step and server-start-to-ready latency, cold AOT
    cache vs warm.

    Two boots of the SAME topology against one cache directory:

    - **boot 1 (cold)**: empty cache — the supervised fit's first step
      pays trace+compile (and bakes the executable), the serving
      executor's ``start()`` compiles the whole bucket ladder;
    - **boot 2 (warm)**: fresh model/supervisor/executor OBJECTS (their
      in-memory jit caches are empty, exactly like a new process), same
      cache dir — the resume path and the ladder warm-up LOAD serialized
      executables instead, and ``dl4j_tpu_train_compile_seconds_total``
      must stay flat (asserted by tests/test_aotcache.py; reported
      here).

    The headline value is the warm restart-to-first-step, with cold
    numbers and speedups alongside — same one-line JSON shape as the
    other modes.
    """
    import shutil
    import tempfile

    from deeplearning4j_tpu.compile.aotcache import set_aot_cache
    from deeplearning4j_tpu.datasets import DataSet, ListDataSetIterator
    from deeplearning4j_tpu.fault import FaultTolerantTrainer
    from deeplearning4j_tpu.learning import Adam
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.nn.conf import (InputType,
                                            NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.remote import (BucketLadder, BucketedExecutor,
                                           ForwardServing)
    from deeplearning4j_tpu.telemetry import get_registry

    work = tempfile.mkdtemp(prefix="dl4j-coldstart-")
    set_aot_cache(os.path.join(work, "aot"))

    def build_net():
        conf = (NeuralNetConfiguration.builder().seed(7)
                .updater(Adam(1e-3)).list()
                .layer(DenseLayer.builder().nIn(nIn).nOut(hidden)
                       .activation("relu").build())
                .layer(OutputLayer.builder("mcxent").nOut(classes)
                       .activation("softmax").build())
                .setInputType(InputType.feedForward(nIn)).build())
        return MultiLayerNetwork(conf)

    rng = np.random.RandomState(0)
    batches = [DataSet(rng.randn(batch, nIn).astype(np.float32),
                       np.eye(classes, dtype=np.float32)[
                           rng.randint(0, classes, batch)])
               for _ in range(steps)]

    class FirstStep:
        """Listener capturing the wall time to the first completed
        supervised step of a fit (restart-to-first-step)."""

        def __init__(self):
            self.t0 = time.perf_counter()
            self.latency = None

        def iterationDone(self, model, iteration, epoch):
            if self.latency is None:
                self.latency = time.perf_counter() - self.t0

        def onEpochStart(self, model):
            pass

        def onEpochEnd(self, model):
            pass

    def supervised_boot(resume: bool, epochs: int):
        # epochs grows by one per boot: the resumed run must have real
        # steps LEFT to take, or there is no "first step" to time
        net = build_net()
        trainer = FaultTolerantTrainer(
            net, os.path.join(work, "ckpt"), checkpointEveryN=2,
            resume=resume)
        probe = FirstStep()
        net.setListeners(probe)
        trainer.fit(ListDataSetIterator(batches, batch), epochs=epochs)
        trainer.close()
        return probe.latency

    reg = get_registry()

    def compile_s():
        c = reg.get("dl4j_tpu_train_compile_seconds_total")
        return c.value() if c is not None else 0.0

    # -- restart-to-first-step ------------------------------------------
    restart_cold = supervised_boot(resume=False, epochs=1)  # compile+bake
    cs0 = compile_s()
    restart_warm = supervised_boot(resume=True, epochs=2)   # cache load
    warm_compile_delta = compile_s() - cs0

    # -- server-start-to-ready ------------------------------------------
    ladder = BucketLadder(batchSizes=(1, 2, 4, 8, 16), seqLens=())

    def server_boot(name):
        ex = BucketedExecutor(
            ForwardServing(build_net().init(), ladder,
                           inputShape=(nIn,)), name=name)
        t0 = time.perf_counter()
        ex.start()
        ready = time.perf_counter() - t0
        ex.submit(np.zeros((2, nIn), np.float32).tolist())
        ex.shutdown()
        return ready

    server_cold = server_boot("cold")
    server_warm = server_boot("warm")

    def val(name, **labels):
        c = reg.get(name)
        try:
            return c.value(**labels) if c is not None else 0.0
        except ValueError:
            return 0.0

    out = {
        "metric": "coldstart_restart_to_first_step_seconds",
        "value": round(restart_warm, 4),
        "unit": "seconds",
        "restart_first_step_cold_s": round(restart_cold, 4),
        "restart_first_step_warm_s": round(restart_warm, 4),
        "restart_speedup": round(restart_cold / max(restart_warm, 1e-9),
                                 2),
        "server_ready_cold_s": round(server_cold, 4),
        "server_ready_warm_s": round(server_warm, 4),
        "server_ready_speedup": round(server_cold / max(server_warm,
                                                        1e-9), 2),
        # the acceptance bar: a warm boot re-compiles NOTHING
        "warm_compile_seconds_delta": round(warm_compile_delta, 4),
        "warm_server_warmup_compiles": int(val(
            "dl4j_tpu_serving_warmup_compiles_total", model="warm")),
        "aot_cache_hits": int(sum(
            v for _k, v in (reg.get("dl4j_tpu_aot_cache_hits_total")
                            .data().get("cells", []))))
        if reg.get("dl4j_tpu_aot_cache_hits_total") else 0,
        "batch": batch,
        "steps": steps,
    }
    set_aot_cache(None)
    shutil.rmtree(work, ignore_errors=True)
    return out


def _positional() -> list:
    return [a for a in sys.argv[1:] if not a.startswith("--")]


def main() -> None:
    # the CPU proxies: counts and ratios on the host, each line tagged
    # with the platform it ran on
    if "--coldstart" in sys.argv:
        print(json.dumps({**bench_coldstart(), **_device_fields()}))
        return

    if "--mesh" in sys.argv or "--recsys" in sys.argv:
        # both need 8 virtual host devices, configured before jax loads
        from tools.cpu_proxy import reexec_on_cpu_proxy
        reexec_on_cpu_proxy(8, __file__, sys.argv[1:])
        args = _positional()
        if "--mesh" in sys.argv:
            out = bench_mesh(int(args[0]) if args else 12,
                             int(args[1]) if len(args) > 1 else 64)
        else:
            out = bench_recsys(int(args[0]) if args else 8,
                               int(args[1]) if len(args) > 1 else 256)
        print(json.dumps({**out, **_device_fields()}))
        return

    if "--serving" in sys.argv:
        args = _positional()
        clients = int(args[0]) if args else 8
        duration = float(args[1]) if len(args) > 1 else 4.0
        print(json.dumps({**bench_serving(clients, duration),
                          **_device_fields()}))
        return

    if "--streaming" in sys.argv:
        args = _positional()
        workers = int(args[0]) if args else 4
        batch = int(args[1]) if len(args) > 1 else 64
        img = int(args[2]) if len(args) > 2 else 96
        batches = int(args[3]) if len(args) > 3 else 24
        print(json.dumps({**bench_streaming(workers, batch, img, batches),
                          **_device_fields()}))
        return

    # the chip benchmark: no chip, no number; any phase that raises ends
    # the run non-zero
    device = _device_fields()
    if device["platform"] != "tpu":
        raise SystemExit(
            f"bench.py: the benchmark needs a TPU, found platform "
            f"{device['platform']!r} ({device['device_kind']}); the CPU "
            "proxies are --mesh, --recsys, --serving, --streaming and "
            "--coldstart")
    peaks = _chip_peaks()

    import jax

    from deeplearning4j_tpu.compile import enable_compile_cache
    from deeplearning4j_tpu.datasets import DataSet
    from deeplearning4j_tpu.zoo import ResNet50

    enable_compile_cache()

    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 256
    img = int(sys.argv[2]) if len(sys.argv) > 2 else 224
    steps = int(sys.argv[3]) if len(sys.argv) > 3 else 16
    dtype = sys.argv[4] if len(sys.argv) > 4 else "BFLOAT16"

    net = ResNet50(numClasses=1000, inputShape=(3, img, img),
                   dataType=dtype).init()
    rng = np.random.RandomState(0)
    pool = []
    for _ in range(4):
        x = rng.randn(batch, 3, img, img).astype(np.float32)
        y = np.eye(1000, dtype=np.float32)[rng.randint(0, 1000, batch)]
        pool.append(DataSet(x, y))

    # Measure raw host->device bandwidth on one batch (diagnostic only).
    xb = pool[0].features.numpy()
    t0 = time.perf_counter()
    jax.block_until_ready(jax.device_put(xb))
    h2d = xb.nbytes / (time.perf_counter() - t0) / 1e6

    net.fit(pool[0])  # compile + warm up; also stages pool[0] on device
    net.fit(pool[1])
    jax.block_until_ready(net.params_)

    # Time the window TWICE, report the best, and print the spread so a
    # disturbed window reads as spread — not as a regression.
    windows = []
    for _rep in range(2):
        t0 = time.perf_counter()
        for i in range(steps):
            net.fit(pool[i % len(pool)])
        jax.block_until_ready(net.params_)  # the whole donated-param chain
        windows.append(time.perf_counter() - t0)
    dt = min(windows)
    timing_spread = max(windows) / dt

    # End-to-end STREAMING diagnostic: fresh host batches generated and
    # transferred every step, so host generation and the host->device
    # copy are inside the window.
    stream_steps = 3
    t0 = time.perf_counter()
    for i in range(stream_steps):
        x = rng.randn(batch, 3, img, img).astype(np.float32)
        y = np.eye(1000, dtype=np.float32)[rng.randint(0, 1000, batch)]
        net.fit(DataSet(x, y))
    jax.block_until_ready(net.params_)
    stream_ips = batch * stream_steps / (time.perf_counter() - t0)

    # ON-DEVICE pipeline isolation: fresh DISTINCT batches produced
    # on-device every step through the framework's AsyncDataSetIterator —
    # prefetch/compute overlap with the host link taken out of the loop.
    # Parity with the pre-staged number shows the async input pipeline
    # adds no stall.
    import jax.numpy as jnp_

    from deeplearning4j_tpu.datavec.iterators import AsyncDataSetIterator
    from deeplearning4j_tpu.datasets.iterator import DataSetIterator

    # ONE jitted computation per generated batch: eager op-by-op
    # generation would time dispatch, not the pipeline.
    @jax.jit
    def _gen(i):
        k = jax.random.PRNGKey(i)
        x = jax.random.normal(k, (batch, 3, img, img), jnp_.float32)
        y = jnp_.zeros((batch, 1000), jnp_.float32).at[
            :, i % 1000].set(1.0)
        return x, y

    class _OnDeviceGen(DataSetIterator):
        def __init__(self, n):
            self.n, self.i = n, 0

        def hasNext(self):
            return self.i < self.n

        def next(self):
            x, y = _gen(jnp_.asarray(self.i))
            self.i += 1
            return DataSet(x, y)

        def reset(self):
            self.i = 0

    gen_steps = 8
    xw, yw = _gen(jnp_.asarray(999))     # compile outside the window
    net.fit(DataSet(xw, yw))
    jax.block_until_ready(net.params_)
    # hand the async wrapper an EXHAUSTED source: fit()'s epoch-start
    # reset() then drains only the _END sentinel (instant) and restarts
    # the producer fresh — exactly ONE generation epoch lands in the
    # timed window instead of a drained-and-discarded extra one
    src = _OnDeviceGen(gen_steps)
    src.i = gen_steps
    it = AsyncDataSetIterator(src, queueSize=4)
    t0 = time.perf_counter()
    net.fit(it)
    jax.block_until_ready(net.params_)
    ondev_ips = batch * gen_steps / (time.perf_counter() - t0)

    images_per_sec = batch * steps / dt
    mfu = images_per_sec * _TRAIN_FLOPS_PER_IMAGE / peaks["bf16_flops"]

    bert_tps, bert_mfu = bench_bert()

    attn = {}
    for t_attn in (1024, 4096):
        times = bench_attention(t_attn)
        attn[f"attn_flash_vs_dense_speedup_t{t_attn}"] = round(
            times["dense"] / times["flash"], 3)
    attn["longctx_tokens_per_sec"] = round(bench_long_context(), 1)

    print(json.dumps({
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(images_per_sec, 2),
        "unit": "images/sec",
        **device,
        "vs_baseline": round(images_per_sec / _BASELINE_IPS, 3),
        "step_ms": round(dt / steps * 1e3, 2),
        "mfu": round(mfu, 4),
        "h2d_mb_s": round(h2d, 1),
        # the step's HBM floor (bytes over the published HBM bandwidth)
        # over the measured step time
        "roofline_frac": round(
            _RESNET50_STEP_BYTES / peaks["hbm_bytes_per_s"] / (dt / steps),
            3),
        "streaming_images_per_sec": round(stream_ips, 1),
        "ondevice_pipeline_images_per_sec": round(ondev_ips, 1),
        "bert_tokens_per_sec": round(bert_tps, 1),
        "bert_mfu": round(bert_mfu, 4),
        "timing_spread": round(timing_spread, 3),
        **attn,
    }))


if __name__ == "__main__":
    main()
