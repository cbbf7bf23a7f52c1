"""Builder for the ``jamba`` family: hands the benchmark's seeded weights
to the program's ``JambaLM`` and puts it behind ``ContinuousBatcher`` and
``InferenceServer``, as ``configs/phi4flash.py`` and
``configs/olmohybrid.py`` do for their families.

This is the one place that knows both trees.  The program is imported at
the top, so that a checkout without the model ends here, before JAX is
asked for a device.
"""
from __future__ import annotations

from deeplearning4j_tpu.nlp.jamba import JambaConfig, JambaLM
from deeplearning4j_tpu.remote import (BucketLadder, ContinuousBatcher,
                                       InferenceServer, ModelRegistry)

_MIXER = {"w_in": "Win", "conv_w": "convW", "conv_b": "convB", "w_x": "Wx",
          "w_dt": "Wdt", "b_dt": "bdt", "d_skip": "D", "w_out": "Wout",
          "dt_norm": "dtNorm", "b_norm": "bNorm", "c_norm": "cNorm",
          "w_q": "Wq", "w_k": "Wk", "w_v": "Wv", "w_o": "Wo"}


def to_program(weights) -> dict:
    """The program's parameter tree over the same device arrays (only
    ``a_log``, 82 K numbers a Mamba layer, is copied: the program keeps
    it as ``(N, d_in)``, the way it keeps the state)."""
    layers = []
    for b in weights["layers"]:
        lp = {"norm1": b["norm_1"], "norm2": b["norm_2"],
              "Wgate": b["mlp"]["w_gate"], "Wup": b["mlp"]["w_up"],
              "Wdown": b["mlp"]["w_down"]}
        for name, a in b["mixer"].items():
            if name == "a_log":
                lp["AlogT"] = a.T
            else:
                lp[_MIXER[name]] = a
        layers.append(lp)
    return {"emb": weights["emb"], "normf": weights["norm_f"],
            "layers": layers}


def program_config(config: dict, max_len: int) -> JambaConfig:
    return JambaConfig(
        vocabSize=config["vocab_size"], nLayers=config["num_hidden_layers"],
        hiddenSize=config["hidden_size"],
        nHeads=config["num_attention_heads"],
        nKvHeads=config["num_key_value_heads"],
        ffnSize=config["intermediate_size"],
        attnPeriod=config["attn_layer_period"],
        attnOffset=config["attn_layer_offset"],
        stateSize=config["mamba_d_state"], convKernel=config["mamba_d_conv"],
        expand=config["mamba_expand"], dtRank=config["mamba_dt_rank"],
        eps=config["rms_norm_eps"], maxLen=max_len,
        dtype=str(config.get("dtype", "bfloat16")))


def build_lm(config: dict, weights, max_len: int) -> JambaLM:
    return JambaLM(program_config(config, max_len),
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
