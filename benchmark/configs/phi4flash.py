"""Builder for the ``phi4flash`` family: hands the benchmark's seeded
weights to the program's ``SambaYLM`` and puts it behind
``ContinuousBatcher`` and ``InferenceServer``, as ``configs/gpt2.py``
does for its family.

This is the one place that knows both trees.  The program is imported at
the top, so that a checkout without the model ends here, before JAX is
asked for a device.
"""
from __future__ import annotations

from deeplearning4j_tpu.nlp.sambay import SambaYConfig, SambaYLM
from deeplearning4j_tpu.remote import (BucketLadder, ContinuousBatcher,
                                       InferenceServer, ModelRegistry)

_MIXER = {"w_in": "Win", "conv_w": "convW", "conv_b": "convB", "w_x": "Wx",
          "w_dt": "Wdt", "b_dt": "bdt", "d_skip": "D", "w_out": "Wout",
          "w_1": "W1", "w_2": "W2", "w_q": "Wq", "w_k": "Wk", "w_v": "Wv",
          "w_o": "Wo", "subln_g": "sublnG", "lambda_q1": "lq1",
          "lambda_k1": "lk1", "lambda_q2": "lq2", "lambda_k2": "lk2"}


def to_program(weights) -> dict:
    """The program's parameter tree over the same device arrays (only
    ``a_log``, 82 K numbers a Mamba layer, is copied: the program keeps
    it as ``(N, d_in)``, the way it keeps the state)."""
    layers = []
    for b in weights["layers"]:
        lp = {"ln1_g": b["ln_1"]["g"], "ln1_b": b["ln_1"]["b"],
              "ln2_g": b["ln_2"]["g"], "ln2_b": b["ln_2"]["b"],
              "Wgate": b["mlp"]["w_gate"], "Wup": b["mlp"]["w_up"],
              "Wdown": b["mlp"]["w_down"]}
        for name, a in b["mixer"].items():
            if name == "a_log":
                lp["AlogT"] = a.T
            else:
                lp[_MIXER[name]] = a
        layers.append(lp)
    return {"emb": weights["emb"], "lnf_g": weights["ln_f"]["g"],
            "lnf_b": weights["ln_f"]["b"], "layers": layers}


def program_config(config: dict, max_len: int) -> SambaYConfig:
    return SambaYConfig(
        vocabSize=config["vocab_size"], nLayers=config["num_hidden_layers"],
        hiddenSize=config["hidden_size"],
        nHeads=config["num_attention_heads"],
        nKvHeads=config["num_key_value_heads"],
        ffnSize=config["intermediate_size"],
        window=config["sliding_window"], mbPerLayer=config["mb_per_layer"],
        stateSize=config["mamba_d_state"], convKernel=config["mamba_d_conv"],
        expand=config["mamba_expand"], dtRank=config["mamba_dt_rank"],
        eps=config["layer_norm_eps"], maxLen=max_len,
        dtype=str(config.get("dtype", "bfloat16")))


def build_lm(config: dict, weights, max_len: int) -> SambaYLM:
    return SambaYLM(program_config(config, max_len),
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
