"""Builder for the ``olmohybrid`` family: hands the benchmark's seeded
weights to the program's ``OlmoHybridLM`` and puts it behind
``ContinuousBatcher`` and ``InferenceServer``, as ``configs/gpt2.py`` and
``configs/phi4flash.py`` do for their families.

This is the one place that knows both trees.  The program is imported at
the top, so that a checkout without the model ends here, before JAX is
asked for a device.
"""
from __future__ import annotations

from deeplearning4j_tpu.nlp.olmo_hybrid import OlmoHybridConfig, OlmoHybridLM
from deeplearning4j_tpu.remote import (BucketLadder, ContinuousBatcher,
                                       InferenceServer, ModelRegistry)

_MIXER = {"w_q": "Wq", "w_k": "Wk", "w_v": "Wv", "w_g": "Wg", "w_o": "Wo",
          "w_a": "Wa", "w_b": "Wb", "conv_q": "convQ", "conv_k": "convK",
          "conv_v": "convV", "a_log": "Alog", "dt_bias": "dtBias",
          "g_norm": "gnorm", "q_norm": "qnorm", "k_norm": "knorm"}


def to_program(weights) -> dict:
    """The program's parameter tree over the same device arrays: nothing
    is copied."""
    layers = []
    for b in weights["layers"]:
        lp = {"norm1": b["norm_1"], "norm2": b["norm_2"],
              "Wgate": b["mlp"]["w_gate"], "Wup": b["mlp"]["w_up"],
              "Wdown": b["mlp"]["w_down"]}
        lp.update({_MIXER[name]: a for name, a in b["mixer"].items()})
        layers.append(lp)
    return {"emb": weights["emb"], "head": weights["head"],
            "normf": weights["norm_f"], "layers": layers}


def program_config(config: dict, max_len: int) -> OlmoHybridConfig:
    kinds = config["layer_types"]
    every = kinds.index("full_attention") + 1
    pc = OlmoHybridConfig(
        vocabSize=config["vocab_size"], nLayers=config["num_hidden_layers"],
        hiddenSize=config["hidden_size"],
        nHeads=config["num_attention_heads"],
        ffnSize=config["intermediate_size"],
        linHeads=config["linear_num_value_heads"],
        linKeyDim=config["linear_key_head_dim"],
        linValueDim=config["linear_value_head_dim"],
        convKernel=config["linear_conv_kernel_dim"], fullEvery=every,
        chunk=config.get("delta_chunk", 64), eps=config["rms_norm_eps"],
        maxLen=max_len, dtype=str(config.get("dtype", "bfloat16")))
    want = [{"full": "full_attention", "linear": "linear_attention"}[k]
            for k in pc.layerKinds()]
    if want != list(kinds) or config["num_key_value_heads"] != pc.nHeads \
            or config["linear_num_key_heads"] != pc.linHeads:
        raise ValueError("the program's OlmoHybridLM has one full layer "
                         "closing each period, as many KV heads as query "
                         "heads and as many key heads as value heads")
    return pc


def build_lm(config: dict, weights, max_len: int) -> OlmoHybridLM:
    return OlmoHybridLM(program_config(config, max_len),
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
