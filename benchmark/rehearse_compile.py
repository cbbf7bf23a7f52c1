"""The third rehearsal of the on-chip-measurement guide, run by hand here
on the CPU before chip time is spent (not a test, not part of a run):

    JAX_PLATFORMS=cpu python benchmark/rehearse_compile.py [config] [cell]

Compiles the serving configuration's paged decode step and its largest
prefill bucket for a described v5e chip, at the cell's real sizes, and
prints ``memory_analysis()`` of each — so that weights + KV pool +
temporaries are known to fit one chip's 16 GB.  Nothing runs; a compile
that passes is not a chip run.
"""
from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.dirname(BENCH_DIR))

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from harness import cells


def main(config_name="gpt2_xl", cell_name="gpt2_xl.chat_steady") -> int:
    cfg = cells.load_config(config_name)
    traffic = cells.load_workload(cell_name)["traffic"]
    ref = cells.load_module("references", cfg["family"])
    fam = cells.load_module("configs", cfg["family"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)

    weights = jax.eval_shape(lambda k: ref.make_weights(cfg, k),
                             jax.random.PRNGKey(0))
    from deeplearning4j_tpu.nlp.transformer import (TransformerLM,
                                                    TransformerLMConfig)
    lm = TransformerLM(TransformerLMConfig(vocabSize=8, nLayers=0, nHeads=1,
                                           headSize=8, maxLen=8))
    lm.config = TransformerLMConfig(
        vocabSize=cfg["vocab_size"], nLayers=cfg["n_layer"],
        nHeads=cfg["n_head"], headSize=cfg["n_embd"] // cfg["n_head"],
        ffnMult=cfg["ffn_mult"], maxLen=cfg["n_positions"])
    params = on_chip(fam.to_program(weights))
    S, ps = cfg["serving"]["max_slots"], cfg["serving"]["page_size"]
    per_seq = -(-cfg["n_positions"] // ps)
    pages = cfg["serving"]["num_pages"]
    if len(sys.argv) > 3:
        pages = int(sys.argv[3])        # try another pool by hand
    pool = jax.ShapeDtypeStruct(
        (cfg["n_layer"], pages, cfg["n_head"], ps,
         cfg["n_embd"] // cfg["n_head"]), jnp.float32, sharding=chip)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=chip)
    weight_bytes = sum(a.size * a.dtype.itemsize
                       for a in jax.tree.leaves(weights))
    pool_bytes = 2 * pool.size * 4
    print(f"weights {weight_bytes / 1e9:.2f} GB, KV pool "
          f"{pool_bytes / 1e9:.2f} GB")
    decode = lm.buildPagedDecodeFn().lower(
        params, pool, pool, i32(S, 1), i32(S, per_seq), i32(S),
        i32(S)).compile()
    print("decode step:", decode.memory_analysis())
    # whole-pool copies in the optimized program: each moves the pool once
    text = decode.as_text()
    shape = f"f32[{cfg['n_layer']},{pages},"
    whole = [ln.split(" = ")[0].strip() for ln in text.splitlines()
             if " = " in ln and ln.split(" = ")[1].startswith(shape)
             and (" copy(" in ln or "remat" in ln.split(" = ")[0])]
    print(f"decode step: {len(whole)} whole-pool copies or "
          f"rematerialisations, e.g. {whole[:4]}")
    top = max(traffic["prompt_buckets"])
    prefill = lm._prefillRawFn.lower(params, i32(1, top), i32(1)).compile()
    print(f"prefill bucket {top}:", prefill.memory_analysis())
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:3]))
