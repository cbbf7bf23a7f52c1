"""Builder for the bottleneck ResNet family: puts the benchmark's seeded
weights into the program's ``zoo.ResNet50`` graph and reads the program's
state back under the reference's leaf names.

This is the one place that knows both trees.  The program's names
(``res<stage>_<block>_<a|b|c|sc>_conv`` / ``_bn``, ``stem_*``, ``fc``) are
``zoo/models.py``'s; a PR that renames them fails here, loudly, in set-up.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _units(weights):
    """``(program prefix, reference unit)`` of every conv+BN unit."""
    yield "stem", weights["stem"]
    for si, blocks in enumerate(weights["stages"]):
        for bi, block in enumerate(blocks):
            for which, unit in block.items():
                yield f"res{si}_{bi}_{which}", unit


def to_program(weights) -> dict:
    """The program's parameter tree holding copies of ``weights`` (the
    fused step donates its parameters, and the reference still needs the
    originals after the window)."""
    tree = {}
    for prefix, unit in _units(weights):
        tree[prefix + "_conv"] = {"W": jnp.copy(unit["conv"])}
        tree[prefix + "_bn"] = {"gamma": jnp.copy(unit["gamma"]),
                                "beta": jnp.copy(unit["beta"])}
    tree["fc"] = {"W": jnp.copy(weights["fc"]["W"]),
                  "b": jnp.copy(weights["fc"]["b"])}
    return tree


def from_program(tree, weights, leaf=lambda a: a):
    """``tree`` (the program's parameters, or its optimizer state with
    ``leaf`` picking the array out of each entry) under the reference's
    structure, so that leaves pair up with the reference's."""
    def unit(prefix):
        return {"conv": leaf(tree[prefix + "_conv"]["W"]),
                "gamma": leaf(tree[prefix + "_bn"]["gamma"]),
                "beta": leaf(tree[prefix + "_bn"]["beta"])}
    out = {"stem": unit("stem"),
           "stages": [[{which: unit(f"res{si}_{bi}_{which}")
                        for which in block}
                       for bi, block in enumerate(blocks)]
                      for si, blocks in enumerate(weights["stages"])],
           "fc": {"W": leaf(tree["fc"]["W"]), "b": leaf(tree["fc"]["b"])}}
    return out


def build(config: dict, weights):
    """The program's network, holding the seeded weights: the zoo model's
    own graph, initialised through ``ComputationGraph.init(params=...)``."""
    from deeplearning4j_tpu.models import ComputationGraph
    from deeplearning4j_tpu.zoo import ResNet50

    stages = tuple(tuple(s) for s in config["stages"])
    model = ResNet50
    if stages != ResNet50.stages:
        # a cut in depth (the CPU tests): same builder, fewer blocks
        model = type("ResNetCut", (ResNet50,), {"stages": stages})
    zoo = model(numClasses=config["num_classes"],
                inputShape=(config["in_channels"], config["image"],
                            config["image"]),
                dataType=config["data_type"])
    net = ComputationGraph(zoo.graphBuilder().build())
    net.init(params=to_program(weights))
    return net


def dataset(x, y):
    from deeplearning4j_tpu.datasets import DataSet
    return DataSet(x, y)


def first_gradient(net, config: dict, weights):
    """The first gradient as the optimizer got it, from the Nesterov state
    after one step: v1 = -lr * g (v0 = 0)."""
    lr = config["learning_rate"]
    return from_program(net.optState_, weights,
                        leaf=lambda entry: entry["v"] / (-lr))


def parameters(net, weights):
    return from_program(net.params_, weights)


def batch_stats(net, config: dict, weights):
    """The first step's batch ``(mean, variance)`` of every BatchNorm,
    worked out from the running statistics after one step (they start at
    0 and 1 and move by ``1 - decay`` of the way to the batch's)."""
    keep = config["bn_decay"]

    def unit(prefix):
        st = net.state_[prefix + "_bn"]
        return (st["mean"] / (1 - keep),
                (st["var"] - keep) / (1 - keep))
    return {"stem": unit("stem"),
            "stages": [[{which: unit(f"res{si}_{bi}_{which}")
                         for which in block}
                        for bi, block in enumerate(blocks)]
                       for si, blocks in enumerate(weights["stages"])]}
