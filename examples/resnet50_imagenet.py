"""BASELINE config #2: ResNet-50 (ComputationGraph zoo model).

Shaped like dl4j-examples' zoo usage: instantiate from the zoo, feed an
ImageNet-shaped pipeline, train.  Offline this generates synthetic
ImageNet-shaped batches; point an ImageRecordReader at real data to swap in
(see deeplearning4j_tpu.datavec).  bf16 mixed precision by default.
"""
import os as _os
import sys as _sys
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))  # run as a script from anywhere
import sys
import time

import numpy as np

from deeplearning4j_tpu.datasets import DataSet, ListDataSetIterator
from deeplearning4j_tpu.zoo import ResNet50


def main(steps: int = 10, batch: int = 64, img: int = 224,
         numClasses: int = 1000) -> float:
    net = ResNet50(numClasses=numClasses, inputShape=(3, img, img),
                   dataType="BFLOAT16").init()
    rng = np.random.RandomState(0)
    x = rng.randn(batch, 3, img, img).astype(np.float32)
    y = np.eye(numClasses, dtype=np.float32)[
        rng.randint(0, numClasses, batch)]
    ds = DataSet(x, y)
    net.fit(ds)   # compile
    t0 = time.perf_counter()
    for _ in range(steps):
        net.fit(ds)
    import jax
    jax.block_until_ready(net.params_)
    ips = batch * steps / (time.perf_counter() - t0)
    print(f"ResNet-50 train throughput: {ips:.1f} images/sec "
          f"(batch {batch}, {img}x{img}, bf16)")
    return ips


if __name__ == "__main__":
    main(steps=int(sys.argv[1]) if len(sys.argv) > 1 else 10)
