#!/usr/bin/env python
"""aotc — pre-bake a model's executables into the persistent AOT cache.

Fleet rollout story (ROADMAP item 2): one bake job compiles a model's
FULL serving bucket ladder and/or its fused train step, serializes every
executable into the content-addressed cache (see
``deeplearning4j_tpu.compile.aotcache``), and every subsequent process
on an identical (topology, device set, jax/XLA version) boots by
LOADING executables in milliseconds instead of re-paying XLA.

Usage::

    # serving ladder for an MLP forward model + its fused train step
    python -m tools.aotc bake --cache-dir /ckpts/aot \\
        --mlp 32,64,10 --batches 1,2,4,8 --train

    # sharded train step on a data=N mesh
    python -m tools.aotc bake --cache-dir /ckpts/aot \\
        --mlp 32,64,10 --train --mesh-data 2

    python -m tools.aotc ls --cache-dir /ckpts/aot
    python -m tools.aotc gc --cache-dir /ckpts/aot --max-bytes 1000000

The bake must run on the SAME device topology and jax/jaxlib build the
fleet boots with — both are part of every cache key, so a mismatched
bake is simply never loaded (a miss, not a wrong executable).

Prints one JSON line per subcommand (driver-parseable).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _ints(spec: str):
    return [int(s) for s in spec.split(",") if s != ""]


def _build_mlp(dims):
    from deeplearning4j_tpu.learning import Adam
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.nn.conf import (InputType,
                                            NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    nIn, hidden, nOut = dims
    conf = (NeuralNetConfiguration.builder().seed(0).updater(Adam(1e-3))
            .list()
            .layer(DenseLayer.builder().nIn(nIn).nOut(hidden)
                   .activation("relu").build())
            .layer(OutputLayer.builder("mcxent").nOut(nOut)
                   .activation("softmax").build())
            .setInputType(InputType.feedForward(nIn)).build())
    return MultiLayerNetwork(conf).init()


def _bake_forward_ladder(net, nIn, batches, stats) -> None:
    from deeplearning4j_tpu.compile.aotcache import wrap_serving_model
    from deeplearning4j_tpu.remote import BucketLadder, ForwardServing
    serving = ForwardServing(net, BucketLadder(batchSizes=batches,
                                               seqLens=()),
                             inputShape=(nIn,))
    wrap_serving_model(net)
    t0 = time.perf_counter()
    for key in serving.warmKeys():
        serving.warm(key)
    stats["forward_ladder_seconds"] = round(time.perf_counter() - t0, 3)
    stats["forward_buckets"] = list(batches)


def _bake_train_step(net, nIn, nOut, batches, meshData, stats) -> None:
    import numpy as np

    from deeplearning4j_tpu.datasets import DataSet
    rng = np.random.RandomState(0)
    wrapper = None
    if meshData and meshData > 1:
        import jax

        from deeplearning4j_tpu.parallel import DeviceMesh, ParallelWrapper
        wrapper = ParallelWrapper(
            net, mesh=DeviceMesh(data=meshData,
                                 devices=jax.devices()[:meshData]))
    t0 = time.perf_counter()
    for b in batches:
        x = rng.randn(b, nIn).astype(np.float32)
        y = np.eye(nOut, dtype=np.float32)[rng.randint(0, nOut, b)]
        ds = DataSet(x, y)
        if wrapper is not None:
            wrapper.fitDataSet(ds)
        else:
            net.fit(ds)
    net.score()
    stats["train_step_seconds"] = round(time.perf_counter() - t0, 3)
    stats["train_batches"] = list(batches)
    if meshData:
        stats["mesh_data"] = int(meshData)


def cmd_bake(args) -> dict:
    from deeplearning4j_tpu.compile.aotcache import (aot_cache,
                                                     set_aot_cache)
    from deeplearning4j_tpu.telemetry import get_registry
    set_aot_cache(args.cache_dir)
    cache = aot_cache()
    if cache is None:
        raise SystemExit("aotc: cache disabled (DL4J_TPU_AOT_CACHE=0?)")
    before = len(cache.entries())
    stats: dict = {"command": "bake", "cache_dir": cache.directory}
    batches = _ints(args.batches)
    if args.mlp:
        dims = _ints(args.mlp)
        if len(dims) != 3:
            raise SystemExit("aotc: --mlp wants nIn,hidden,nOut")
        net = _build_mlp(dims)
        _bake_forward_ladder(net, dims[0], batches, stats)
        if args.train:
            _bake_train_step(net, dims[0], dims[2], batches,
                             args.mesh_data, stats)
    reg = get_registry()
    h = reg.get("dl4j_tpu_aot_cache_hits_total")
    stats["entries_baked"] = len(cache.entries()) - before
    stats["entries_total"] = len(cache.entries())
    stats["cache_bytes"] = cache.totalBytes()
    stats["already_cached_hits"] = \
        sum(v for _k, v in h.data().get("cells", [])) if h else 0
    return stats


def cmd_ls(args) -> dict:
    from deeplearning4j_tpu.compile.aotcache import AotCache
    cache = AotCache(args.cache_dir)
    entries = sorted(cache.entries(), key=lambda e: -e[2])
    ladders = [fn for fn in os.listdir(cache.directory)
               if fn.startswith("ladder-")]
    return {"command": "ls", "cache_dir": cache.directory,
            "entries": [{"digest": d[:16], "bytes": size,
                         "age_seconds": round(time.time() - mtime, 1)}
                        for d, size, mtime in entries],
            "entry_count": len(entries),
            "ladder_count": len(ladders),
            "total_bytes": cache.totalBytes()}


def cmd_gc(args) -> dict:
    from deeplearning4j_tpu.compile.aotcache import AotCache
    cache = AotCache(args.cache_dir, maxBytes=args.max_bytes)
    before = cache.totalBytes()
    cache._evict()
    return {"command": "gc", "cache_dir": cache.directory,
            "max_bytes": cache.maxBytes, "bytes_before": before,
            "bytes_after": cache.totalBytes()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="aotc", description="pre-bake executables into the "
                                 "persistent AOT cache")
    sub = ap.add_subparsers(dest="command", required=True)

    bake = sub.add_parser("bake", help="compile + serialize executables")
    bake.add_argument("--cache-dir", required=True)
    bake.add_argument("--mlp", help="nIn,hidden,nOut forward model")
    bake.add_argument("--batches", default="1,2,4,8,16,32",
                      help="batch buckets for the forward/train ladder")
    bake.add_argument("--train", action="store_true",
                      help="also bake the fused train step per batch")
    bake.add_argument("--mesh-data", type=int, default=0,
                      help="bake the train step on a data=N mesh")

    ls = sub.add_parser("ls", help="list cache entries")
    ls.add_argument("--cache-dir", required=True)

    gc = sub.add_parser("gc", help="enforce a size bound now")
    gc.add_argument("--cache-dir", required=True)
    gc.add_argument("--max-bytes", type=int, required=True)

    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    out = {"bake": cmd_bake, "ls": cmd_ls, "gc": cmd_gc}[args.command](args)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
