"""Builder for the ``ling`` family: hands the benchmark's seeded weights
to the program's ``LingLM``, tells it which published layers and which
routed experts this chip holds, and puts it behind ``ContinuousBatcher``
and ``InferenceServer``, as the other families' builders do.

This is the one place that knows both trees.  The program is imported at
the top, so that a checkout without the model ends here, before JAX is
asked for a device.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nlp.ling import LingConfig, LingLM
from deeplearning4j_tpu.remote import (BucketLadder, ContinuousBatcher,
                                       InferenceServer, ModelRegistry)

_KDA = {"w_q": "Wq", "w_k": "Wk", "w_v": "Wv", "w_f": "Wf", "w_b": "Wb",
        "w_g": "Wg", "w_o": "Wo", "conv_q": "convQ", "conv_k": "convK",
        "conv_v": "convV", "a_log": "Alog", "dt_bias": "dtBias",
        "o_norm": "onorm"}
_MLA = {"w_q": "Wq", "w_dkv": "Wdkv", "kv_norm": "kvnorm", "w_uk": "Wuk",
        "w_uv_t": "Wuv", "w_o": "Wo"}
_GATED = ("w_gate", "w_up", "w_down")


def to_program(weights) -> dict:
    """The program's parameter tree over the same device arrays: nothing
    is copied."""
    layers = []
    for b in weights["layers"]:
        lp = {"norm1": b["norm_1"], "norm2": b["norm_2"]}
        names, mixer = (_KDA, b["kda"]) if "kda" in b else (_MLA, b["mla"])
        lp.update({names[name]: a for name, a in mixer.items()})
        if "mlp" in b:
            lp.update(zip(("Wgate", "Wup", "Wdown"),
                          (b["mlp"][n] for n in _GATED)))
        else:
            m = b["moe"]
            lp["Wr"], lp["rbias"] = m["w_router"], m["bias"]
            lp.update(zip(("Sgate", "Sup", "Sdown"),
                          (m["shared"][n] for n in _GATED)))
            lp.update(zip(("Eg", "Eu", "Ed"),
                          (m["experts"][n] for n in _GATED)))
        layers.append(lp)
    return {"emb": weights["emb"], "head": weights["head"],
            "normf": weights["norm_f"], "layers": layers}


def program_config(config: dict, max_len: int) -> LingConfig:
    if config["num_shared_experts"] != 1 or not config["norm_topk_prob"] \
            or config["topk_method"] != "noaux_tc" \
            or config["score_function"] != "sigmoid" \
            or not config["kda_safe_gate"] or config["q_lora_rank"] \
            or not config["rope_interleave"] \
            or config["gated_attention_proj_granularity_type"] != "head_wise":
        raise ValueError(
            "the program's LingLM has one shared expert, a sigmoid router "
            "that picks groups first and normalises the chosen experts' "
            "weights, KDA's safe gate and head-wise output gate, an "
            "uncompressed MLA query and interleaved rotary pairs")
    return LingConfig(
        vocabSize=config["vocab_size"], nLayers=config["num_hidden_layers"],
        firstLayer=config["first_layer"],
        denseLayers=config["first_k_dense_replace"],
        mlaEvery=config["layer_group_size"],
        hiddenSize=config["hidden_size"],
        nHeads=config["num_attention_heads"], headDim=config["head_dim"],
        convKernel=config["short_conv_kernel_size"],
        lowerBound=float(config["kda_lower_bound"]),
        kvRank=config["kv_lora_rank"], nopeDim=config["qk_nope_head_dim"],
        ropeDim=config["qk_rope_head_dim"], vDim=config["v_head_dim"],
        ffnSize=config["intermediate_size"],
        expertSize=config["moe_intermediate_size"],
        nExperts=config["router_width"],
        expertsPerToken=config["num_experts_per_tok"],
        expertsHeld=tuple(config["experts_held"]),
        nGroups=config["n_group"], groupsPerToken=config["topk_group"],
        routedScale=config["routed_scaling_factor"],
        ropeTheta=float(config["rope_theta"]), eps=config["rms_norm_eps"],
        chunk=config.get("kda_chunk", 64), maxLen=max_len,
        dtype=str(config.get("dtype", "bfloat16")))


def build_lm(config: dict, weights, max_len: int) -> LingLM:
    return LingLM(program_config(config, max_len),
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
    ``drivers/serve_closed_ordered_scoped.py`` tells the ops under the
    scope ``kda_step`` from the rest of the step's."""
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
