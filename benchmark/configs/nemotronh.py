"""Builder for the ``nemotronh`` family: hands the benchmark's seeded
weights to the program's ``NemotronHLM``, tells it which published blocks
and which routed experts this chip holds, and puts it behind
``ContinuousBatcher`` and ``InferenceServer``, as the other families'
builders do.

This is the one place that knows both trees.  The program is imported at
the top, so that a checkout without the model ends here, before JAX is
asked for a device.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nlp.nemotron_h import NemotronHConfig, NemotronHLM
from deeplearning4j_tpu.remote import (BucketLadder, ContinuousBatcher,
                                       InferenceServer, ModelRegistry)

_MAMBA = {"w_in": "Win", "conv_w": "convW", "conv_b": "convB",
          "dt_bias": "dtBias", "a_log": "Alog", "d": "D", "g_norm": "gnorm",
          "w_out": "Wout"}
_ATTN = {"w_q": "Wq", "w_k": "Wk", "w_v": "Wv", "w_o": "Wo"}


def to_program(weights) -> dict:
    """The program's parameter tree over the same device arrays: nothing
    is copied."""
    layers = []
    for b in weights["layers"]:
        lp = {"norm": b["norm"]}
        if "moe" in b:
            m = b["moe"]
            lp.update(Wr=m["w_router"], rbias=m["bias"], Wdown=m["w_down"],
                      Wup=m["w_up"], S1=m["shared"]["w_1"],
                      S2=m["shared"]["w_2"], E1=m["experts"]["w_1"],
                      E2=m["experts"]["w_2"])
        else:
            names, mixer = (_MAMBA, b["mamba"]) if "mamba" in b \
                else (_ATTN, b["attn"])
            lp.update({names[name]: a for name, a in mixer.items()})
        layers.append(lp)
    return {"emb": weights["emb"], "head": weights["head"],
            "normf": weights["norm_f"], "layers": layers}


def program_config(config: dict, max_len: int) -> NemotronHConfig:
    if config["n_shared_experts"] != 1 or not config["norm_topk_prob"] \
            or config["mlp_hidden_act"] != "relu2" \
            or config["mamba_hidden_act"] != "silu" \
            or not config["use_conv_bias"] or config["mamba_proj_bias"] \
            or config["attention_bias"] or config["mlp_bias"] \
            or config["tie_word_embeddings"]:
        raise ValueError(
            "the program's NemotronHLM has one shared expert, a sigmoid "
            "router that normalises the chosen experts' weights, relu2 "
            "experts, a SiLU convolution with a bias, no other bias and an "
            "untied head")
    return NemotronHConfig(
        vocabSize=config["vocab_size"],
        pattern=config["hybrid_override_pattern"],
        firstBlock=config["first_block"], hiddenSize=config["hidden_size"],
        nHeads=config["num_attention_heads"],
        nKvHeads=config["num_key_value_heads"], headDim=config["head_dim"],
        mambaHeads=config["mamba_num_heads"],
        mambaHeadDim=config["mamba_head_dim"], nGroups=config["n_groups"],
        stateSize=config["ssm_state_size"], convKernel=config["conv_kernel"],
        chunk=config["chunk_size"], latentSize=config["moe_latent_size"],
        expertSize=config["moe_intermediate_size"],
        sharedSize=config["moe_shared_expert_intermediate_size"],
        routerWidth=config["router_width"],
        expertsPerToken=config["num_experts_per_tok"],
        expertsHeld=tuple(config["experts_held"]),
        routerGroups=config["n_group"], groupsPerToken=config["topk_group"],
        routedScale=float(config["routed_scaling_factor"]),
        eps=config["layer_norm_epsilon"], maxLen=max_len,
        dtype=str(config.get("dtype", "bfloat16")))


def build_lm(config: dict, weights, max_len: int) -> NemotronHLM:
    return NemotronHLM(program_config(config, max_len),
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
    scopes ``ssd_step`` and ``moe_share_step`` from the rest of the
    step's."""
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
