"""Data-parallel training over the cell's chips through
``ParallelWrapper(net, mesh=DeviceMesh(data=chips)).fit``."""
from __future__ import annotations

from harness import cells

_common = cells.load_module("drivers", "train_common")


def make_step(cell, net):
    import jax

    from deeplearning4j_tpu.datasets import ListDataSetIterator
    from deeplearning4j_tpu.parallel import DeviceMesh, ParallelWrapper
    chips = cell.workload["chips"]
    pw = ParallelWrapper(net, mesh=DeviceMesh(
        data=chips, devices=jax.devices()[:chips]))
    return lambda ds: pw.fit(ListDataSetIterator([ds]))


def run(cell) -> dict:
    return _common.run(cell, make_step)
