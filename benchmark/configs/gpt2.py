"""Builder for the GPT-2 family: hands the benchmark's seeded weights to
the program's ``TransformerLM`` and puts it behind ``ContinuousBatcher``
and ``InferenceServer``.

This is the one place that knows both trees.  The program's names
(``emb``, ``pos``, ``lnf_*``, ``layers[i].{ln1_*, Wq, Wk, Wv, Wo, ln2_*,
Wi, bi, Wp, bp}``) are ``nlp/transformer.py``'s; a PR that renames them
fails in warm-up, loudly.
"""
from __future__ import annotations

import dataclasses


def to_program(weights) -> dict:
    """The program's parameter tree over the same device arrays (serving
    donates no weights, so nothing is copied)."""
    return {"emb": weights["wte"], "pos": weights["wpe"],
            "lnf_g": weights["ln_f"]["g"], "lnf_b": weights["ln_f"]["b"],
            "layers": [{
                "ln1_g": b["ln_1"]["g"], "ln1_b": b["ln_1"]["b"],
                "Wq": b["attn"]["wq"], "Wk": b["attn"]["wk"],
                "Wv": b["attn"]["wv"], "Wo": b["attn"]["wo"],
                "ln2_g": b["ln_2"]["g"], "ln2_b": b["ln_2"]["b"],
                "Wi": b["mlp"]["w_fc"], "bi": b["mlp"]["b_fc"],
                "Wp": b["mlp"]["w_proj"], "bp": b["mlp"]["b_proj"]}
                for b in weights["blocks"]]}


def build_lm(config: dict, weights):
    """The program's language model holding the seeded weights.

    ``TransformerLM.__init__`` draws its own weights with host numpy
    (1.56 B floats, about half a minute at these widths) and has no way
    to be given any.  So the constructor runs as it is at depth 0, which
    costs the embedding alone, and the object then gets the full
    configuration and the seeded tree.  (Device-side initialisation in
    the program is listed in PERF.md for a later PR.)"""
    from deeplearning4j_tpu.nlp.transformer import (TransformerLM,
                                                    TransformerLMConfig)
    full = TransformerLMConfig(
        vocabSize=config["vocab_size"], nLayers=config["n_layer"],
        nHeads=config["n_head"],
        headSize=config["n_embd"] // config["n_head"],
        ffnMult=config["ffn_mult"], maxLen=config["n_positions"])
    lm = TransformerLM(dataclasses.replace(full, nLayers=0))
    lm.config = full
    lm.params = to_program(weights)
    return lm


def build_server(config: dict, weights, name: str, serving: dict):
    """``(server, batcher)``: the model behind ``ContinuousBatcher`` with
    the cell's slots, page size and prompt buckets, registered under
    ``name`` and served over HTTP on a free port.  ``start()`` warms the
    buckets, the pool write and the decode step."""
    from deeplearning4j_tpu.remote import (BucketLadder, ContinuousBatcher,
                                           InferenceServer, ModelRegistry)
    lm = build_lm(config, weights)
    ladder = BucketLadder(batchSizes=(serving["max_slots"],),
                          seqLens=tuple(serving["prompt_buckets"]))
    cb = ContinuousBatcher(lm, name=name, maxSlots=serving["max_slots"],
                           pageSize=serving["page_size"],
                           numPages=serving["num_pages"], ladder=ladder)
    registry = ModelRegistry()
    registry.register(name, cb)
    return InferenceServer(registry, port=0), cb
