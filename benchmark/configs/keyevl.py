"""Builder for the ``keyevl`` family: hands the benchmark's seeded weights
to the program's ``KeyeVLLM``, tells it which routed experts this chip
holds, and puts it behind ``ContinuousBatcher`` and ``InferenceServer``,
as the other families' builders do.

This is the one place that knows both trees.  The program is imported at
the top, so that a checkout without the model ends here, before JAX is
asked for a device.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nlp.keye_vl import KeyeVLConfig, KeyeVLLM
from deeplearning4j_tpu.remote import (BucketLadder, ContinuousBatcher,
                                       InferenceServer, ModelRegistry)

_ATTN = {"w_q": "Wq", "w_k": "Wk", "w_v": "Wv", "w_o": "Wo",
         "q_norm": "qnorm", "k_norm": "knorm"}
_INDEXER = {"w_q": "WqI", "w_k": "WkI", "k_norm": "kInorm",
            "k_bias": "kIbias", "w_w": "Ww"}
_EXPERTS = {"w_gate": "Eg", "w_up": "Eu", "w_down": "Ed"}


def to_program(weights) -> dict:
    """The program's parameter tree over the same device arrays: nothing
    is copied."""
    layers = []
    for b in weights["layers"]:
        lp = {"norm1": b["norm_1"], "norm2": b["norm_2"],
              "Wr": b["moe"]["w_router"]}
        lp.update({_ATTN[name]: a for name, a in b["attn"].items()})
        lp.update({_INDEXER[name]: a for name, a in b["indexer"].items()})
        lp.update({_EXPERTS[name]: a
                   for name, a in b["moe"]["experts"].items()})
        layers.append(lp)
    return {"emb": weights["emb"], "head": weights["head"],
            "normf": weights["norm_f"], "layers": layers}


def program_config(config: dict, max_len: int) -> KeyeVLConfig:
    sa = config["sa_config"]
    if not config["norm_topk_prob"] or sa["indexer_num_kv_heads"] != 1 \
            or config["decoder_sparse_step"] != 1 \
            or config["mlp_only_layers"]:
        raise ValueError("the program's KeyeVLLM normalises the chosen "
                         "experts' weights, has one index key for all "
                         "index heads and an expert layer in every block")
    return KeyeVLConfig(
        vocabSize=config["vocab_size"], nLayers=config["num_hidden_layers"],
        hiddenSize=config["hidden_size"],
        nHeads=config["num_attention_heads"],
        nKvHeads=config["num_key_value_heads"], headSize=config["head_dim"],
        expertSize=config["moe_intermediate_size"],
        nExperts=config["router_width"],
        expertsPerToken=config["num_experts_per_tok"],
        expertsHeld=tuple(config["experts_held"]),
        indexHeads=sa["indexer_num_heads"], indexSize=sa["indexer_head_dim"],
        topk=sa["topk"], ropeTheta=float(config["rope_theta"]),
        eps=config["rms_norm_eps"], maxLen=max_len,
        dtype=str(config.get("dtype", "bfloat16")))


def build_lm(config: dict, weights, max_len: int) -> KeyeVLLM:
    return KeyeVLLM(program_config(config, max_len),
                    params=to_program(weights))


def build_server(config: dict, weights, name: str, serving: dict):
    """``(server, batcher)``: the model behind ``ContinuousBatcher`` with
    the cell's slots, page size and prompt buckets, registered under
    ``name`` and served over HTTP on a free port."""
    lm = build_lm(config, weights, serving["capacity"])
    ladder = BucketLadder(batchSizes=(serving["max_slots"],),
                          seqLens=tuple(serving["prompt_buckets"]))
    cb = ContinuousBatcher(lm, name=name, maxSlots=serving["max_slots"],
                           pageSize=serving["page_size"],
                           numPages=serving["num_pages"], ladder=ladder)
    registry = ModelRegistry()
    registry.register(name, cb)
    return InferenceServer(registry, port=0), cb


def step_program_text(batcher) -> str:
    """The optimized program of the batcher's decode step as the chip
    runs it (the step lowered again for the pool's shapes and compiled:
    from the compile cache where there is one): every instruction with
    the ``op_name`` of its metadata, by which
    ``drivers/serve_closed_ordered_scoped.py`` tells the sparse read's
    ops from the rest of the step's."""
    S = batcher.maxSlots
    shape = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    out = 1 + len(batcher.lm.stepCounters)
    batcher._ensureFns()
    return batcher._stepFns["step"].lower(
        jax.tree.map(shape, batcher.lm.params),
        *(shape(a) for a in batcher.pool.arrays), i32(S, 1), i32(S, out),
        i32(*batcher.pool.pageTable.shape), i32(S), i32(S)
    ).compile().as_text()
