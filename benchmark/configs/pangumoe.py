"""Builder for the ``pangumoe`` family: hands the benchmark's seeded
weights to the program's ``PanguMoELM``, tells it which routed experts
this chip holds, and puts it behind ``ContinuousBatcher`` and
``InferenceServer``, as the other families' builders do.

This is the one place that knows both trees.  The program is imported at
the top, so that a checkout without the model ends here, before JAX is
asked for a device.
"""
from __future__ import annotations

from deeplearning4j_tpu.nlp.pangu_moe import PanguMoEConfig, PanguMoELM
from deeplearning4j_tpu.remote import (BucketLadder, ContinuousBatcher,
                                       InferenceServer, ModelRegistry)

_ATTN = {"w_dq": "Wdq", "q_norm": "qnorm", "w_uq": "Wuq", "w_dkv": "Wdkv",
         "kv_norm": "kvnorm", "w_uk": "Wuk", "w_uv_t": "Wuv", "w_o": "Wo"}
_GATED = ("w_gate", "w_up", "w_down")


def to_program(weights) -> dict:
    """The program's parameter tree over the same device arrays: nothing
    is copied."""
    layers = []
    for b in weights["layers"]:
        lp = {f"norm{i}": b[f"norm_{i}"] for i in (1, 2, 3, 4)}
        lp.update({_ATTN[name]: a for name, a in b["attn"].items()})
        if "mlp" in b:
            lp.update(zip(("Wgate", "Wup", "Wdown"),
                          (b["mlp"][n] for n in _GATED)))
        else:
            m = b["moe"]
            lp["Wr"] = m["w_router"]
            lp.update(zip(("Sgate", "Sup", "Sdown"),
                          (m["shared"][n] for n in _GATED)))
            lp.update(zip(("Eg", "Eu", "Ed"),
                          (m["experts"][n] for n in _GATED)))
        layers.append(lp)
    return {"emb": weights["emb"], "head": weights["head"],
            "normf": weights["norm_f"], "layers": layers}


def program_config(config: dict, max_len: int) -> PanguMoEConfig:
    if config["n_shared_experts"] != 1 or not config["norm_topk_prob"] \
            or not config["sandwich_norm"]:
        raise ValueError("the program's PanguMoELM has one shared expert, "
                         "normalises the chosen experts' weights and wraps "
                         "each sub-layer in two norms")
    return PanguMoEConfig(
        vocabSize=config["vocab_size"], nLayers=config["num_hidden_layers"],
        denseLayers=config["first_k_dense_replace"],
        hiddenSize=config["hidden_size"],
        nHeads=config["num_attention_heads"], qRank=config["q_lora_rank"],
        kvRank=config["kv_lora_rank"], nopeDim=config["qk_nope_head_dim"],
        ropeDim=config["qk_rope_head_dim"], vDim=config["v_head_dim"],
        ffnSize=config["intermediate_size"],
        expertSize=config["moe_intermediate_size"],
        nExperts=config["router_width"],
        expertsPerToken=config["num_experts_per_tok"],
        expertsHeld=tuple(config["experts_held"]),
        routedScale=config["routed_scaling_factor"],
        ropeTheta=float(config["rope_theta"]), eps=config["rms_norm_eps"],
        maxLen=max_len, dtype=str(config.get("dtype", "bfloat16")))


def build_lm(config: dict, weights, max_len: int) -> PanguMoELM:
    return PanguMoELM(program_config(config, max_len),
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
