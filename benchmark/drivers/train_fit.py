"""Training on one chip through the model's own ``fit(DataSet)``."""
from __future__ import annotations

from harness import cells

_common = cells.load_module("drivers", "train_common")


def make_step(cell, net):
    return net.fit


def run(cell) -> dict:
    return _common.run(cell, make_step)
