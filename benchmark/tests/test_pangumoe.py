"""The ``pangumoe`` family at a tiny size on the CPU: the counts its
reference keeps, the three new readers' arithmetic on a recorded sample,
the driver that keeps the kernels' ops, and the new cell end to end."""
import json

import jax
import pytest

import peaks
import run
import tiny
from harness import cells

CELL = "openpangu_ultra_moe.longgen_closed32"
TINY = {"hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 32,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "intermediate_size": 128,
        "moe_intermediate_size": 32, "router_width": 16,
        "experts_held": [4, 8], "n_routed_experts": 4,
        "num_experts_per_tok": 4, "num_hidden_layers": 3,
        "first_k_dense_replace": 1, "vocab_size": 96,
        "serving": {"max_slots": 4, "page_size": 4, "num_pages": 65,
                    "capacity": 64},
        # the weights are bfloat16 leaves, so the program serves in
        # bfloat16 here too
        "limits": {"served_gap_max": 0.3, "served_gap_mean": 0.02}}


def _cell_of(cfg):
    return type("C", (), {
        "config": cfg, "workload": cells.load_workload(CELL),
        "reference": cells.load_module("references", cfg["family"])})()


def test_the_published_sizes_count_as_reckoned():
    cfg = cells.load_config("openpangu_ultra_moe")
    ref = cells.load_module("references", cfg["family"])
    per = ref.layer_params(cfg)
    # ISSUE 32's arithmetic: attention 196.6 M (its two inner norms
    # among them), a dense FFN 424.7 M, the shared expert and one routed
    # expert 47.19 M each, the router 1.97 M
    assert per == {"attention": 196_577_280, "norms": 30_720,
                   "dense": 424_673_280, "shared": 47_185_920,
                   "router": 1_966_080, "expert": 47_185_920}
    assert ref.param_count(cfg) == 4_919_139_840
    whole = ref.published(cfg)
    assert (whole["num_hidden_layers"], whole["n_routed_experts"],
            whole["vocab_size"], whole["experts_held"]) == (
        61, 256, 153600, [0, 256])
    print("whole:", ref.param_count(whole), "the card: 718 B")
    assert ref.param_count(whole) == 719_093_767_680
    assert ref.cache_bytes(cfg) == {"paged": 5 * 2 * 576.0}
    outside = 2.0 * (4_919_139_840 - 19200 * 7680 - 64 * 47_185_920)
    assert ref.param_bytes(cfg) == outside
    # one argument: what every step reads whatever routes, which the
    # list-less decode_roofline_pct.batch asks of every serving cell
    assert ref.decode_step_bytes(cfg, 1000) == outside + 1000 * 5760
    assert ref.decode_step_bytes(cfg, 1000, 40.0) == \
        outside + 1000 * 5760 + 40 * 2 * 47_185_920
    assert ref.latent_attention_bytes(cfg, 1000) == 1000 * 1152.0
    assert ref.latent_attention_flops(cfg, 1000) == \
        2.0 * 1000 * 128 * (576 + 512)
    assert ref.decode_step_flops(cfg, 1000, 32, 64.0) == pytest.approx(
        32 * outside + 2 * 64 * 47_185_920
        + 5 * ref.latent_attention_flops(cfg, 1000))
    # a prefill: two operations a weight outside the routed experts a
    # position, half a held pair a token an expert layer, the causal
    # half of the unabsorbed attention
    t = 4096
    matrices = 5 * 196_577_280 + 424_673_280 \
        + 4 * (47_185_920 + 1_966_080 + 0.5 * 47_185_920)
    assert ref.prefill_flops(cfg, t) == pytest.approx(
        2.0 * matrices * t + 5.0 * t * t * 128 * (192 + 128)
        + 2.0 * 7680 * 19200)
    assert 17e12 < ref.prefill_flops(cfg, t) < 18e12


def _window(pages, slots, positions, prompt, steps, hit, routed):
    """A window as the driver hands it to the readers: gauge samples and
    the two snapshots of the counters."""
    def snap(scale):
        moe = lambda n: {"labelnames": ["model", "phase"], "cells": [
            [["lm", "step"], scale * n], [["lm", "prefill"], scale * 7 * n]]}
        return {
            "dl4j_tpu_serving_prefill_positions_total": {
                "labelnames": ["model", "bucket"], "cells": [
                    [["lm", str(b)], scale * n * b]
                    for b, n in positions.items()]},
            "dl4j_tpu_serving_prefill_prompt_tokens_total": {
                "labelnames": ["model"], "cells": [[["lm"], scale * prompt]]},
            "dl4j_tpu_serving_decode_steps_total": {
                "labelnames": ["model"], "cells": [[["lm"], scale * steps]]},
            "dl4j_tpu_serving_moe_experts_hit_total": moe(hit),
            "dl4j_tpu_serving_moe_pairs_routed_total": moe(routed)}
    return {"seconds": 40.0, "before": snap(1), "after": snap(2),
            "samples": {
                "dl4j_tpu_serving_kv_pages_in_use": pages,
                "dl4j_tpu_serving_state_slots_in_use": slots}}


def test_the_three_readers_arithmetic():
    cfg = cells.load_config("openpangu_ultra_moe")
    cell = _cell_of(cfg)
    ref = cell.reference
    # 10 prefills of 512 and 10 of 1,024 holding 10,000 real tokens: 268
    # rows of left padding a request; 2,000 steps that hit 80,000 experts
    # (40 a step of the 64 held) with 34 pairs routed a step
    w = _window([6000, 6080], [32.0, 32.0], {512: 10, 1024: 10}, 10000,
                2000, 80000, 68000)
    ctx = {"cell": cell, "peaks": peaks.peaks_for("TPU v5 lite"),
           "trace": {"modules": {"jit_step": {"count": 100,
                                              "total_s": 2.0}},
                     "kernels": {"latent_attention": {"count": 500,
                                                      "total_s": 0.1}}},
           "window": w}
    rows = 16 * 6040 - 32 * 268
    hit = cells.load_module("readers", "moe_experts_hit")
    assert hit.read({"name": "moe_experts_hit_pct.longgen", "args": {}},
                    ctx) == pytest.approx(100.0 * 40 / 64)
    step = cells.load_module("readers", "moe_decode_roofline")
    metric = {"name": "decode_roofline_pct.longgen",
              "args": {"module": "decode"}}
    need = ref.decode_step_bytes(cfg, rows, 40.0)
    assert need / 819e9 > ref.decode_step_flops(cfg, rows, 32.0, 34.0) \
        / 197e12
    assert step.read(metric, ctx) == pytest.approx(
        100.0 * (need / 819e9) / 0.02)
    kernel = cells.load_module("readers", "latent_kernel_roofline")
    metric = {"name": "latent_attention_roofline_pct.longgen",
              "args": {"kernel": "latent_attention"}}
    # at the ridge: the operations' time is a little over the bytes'
    least = max(ref.latent_attention_bytes(cfg, rows) / 819e9,
                ref.latent_attention_flops(cfg, rows) / 197e12)
    assert least == ref.latent_attention_flops(cfg, rows) / 197e12
    assert kernel.read(metric, ctx) == pytest.approx(
        100.0 * least / (0.1 / 500))
    # a trace without the kernels' ops (another driver, the parent), an
    # untraced run, a program without the counters: nothing to read
    ctx["trace"] = {"modules": ctx["trace"]["modules"]}
    assert kernel.read(metric, ctx) is None
    ctx["trace"] = None
    assert kernel.read(metric, ctx) is None
    ctx["trace"] = {"modules": {"jit_step": {"count": 100, "total_s": 2.0}}}
    ctx["window"] = dict(w, before={}, after={})
    assert step.read({"name": "x", "args": {"module": "decode"}}, ctx) is None
    assert hit.read({"name": "x", "args": {}}, ctx) is None
    # and a step faster than its bytes allow is a fault, never clipped
    ctx["window"] = w
    ctx["trace"]["modules"]["jit_step"]["total_s"] = 0.5
    with pytest.raises(ValueError):
        step.read({"name": "x", "args": {"module": "decode"}}, ctx)


def test_the_driver_keeps_the_named_kernels_ops():
    """``serve_closed_ordered_kernels``'s stand-in for ``harness.trace``
    on the recorded fixture: ``reduce_trace``'s own numbers, and beside
    them the calls and device time of the ops a prefix names."""
    from harness import trace as tracelib
    driver = cells.load_module("drivers", "serve_closed_ordered_kernels")
    path = cells.BENCH_DIR + "/tests/fixture.xplane.pb"
    tr = tracelib.Trace(path)
    plain = tracelib.reduce_trace(tr, 1)
    name = plain["device_ops"][0][0]
    kept = driver._KeepKernels({"top": name, "none": "no_such_op"})
    out = kept.reduce_trace(tr, 1)
    assert {k: v for k, v in out.items() if k != "kernels"} == plain
    assert out["kernels"]["none"] == {"count": 0, "total_s": 0}
    assert out["kernels"]["top"]["count"] >= 1
    assert out["kernels"]["top"]["total_s"] >= plain["device_ops"][0][1]


def test_the_cell_reports_its_own_metrics_and_the_listless_five():
    names = {m["name"] for m in cells.layer_metrics_for(
        cells.load_workload(CELL))}
    own = {n for n in names if n.endswith(".longgen")}
    assert own == {
        "device_idle_pct.longgen", "decode_step_device_ms.longgen",
        "prefill_device_ms.longgen", "slot_occupancy_pct.longgen",
        "decode_host_gap_ms.longgen", "admit_host_ms.longgen",
        "decode_roofline_pct.longgen", "prefill_mfu_pct.longgen",
        "latent_attention_roofline_pct.longgen",
        "moe_experts_hit_pct.longgen"}
    assert names - own == {
        "device_idle_pct.batch", "slot_occupancy_pct.batch",
        "decode_step_device_ms.batch", "decode_roofline_pct.batch",
        "decode_host_gap_ms.batch"}
    with open(cells.REPO_DIR + "/BENCHMARK.json") as f:
        listed = {m["name"] for m in json.load(f)["per_layer"]
                  if CELL in m.get("workloads", [])}
    assert listed == own


def test_the_cell_runs_and_is_correct_at_a_tiny_size(tmp_path):
    """Through the cell's own driver; the float8 control's numbers are
    printed on earlier lines."""
    wl = cells.load_workload(CELL)
    cfg = dict(cells.load_config(wl["config"]), **TINY)
    tr = dict(wl["traffic"], ramp_s=1,
              arrivals={"kind": "closed", "clients": 5, "per_client": 40},
              prompt_len={"median": 10, "sigma": 0.5, "lo": 4, "hi": 16},
              output_len={"median": 20, "sigma": 0.4, "lo": 12, "hi": 40},
              prompt_buckets=[8, 16], drain_s=10, check_requests=3)
    wl = dict(wl, traffic=tr)
    cell = tiny._cell(wl, cfg, 2 ** 31 + 77, 3.0)
    cell.control = True
    cell.workload_file = str(tmp_path / "workload.json")
    with open(cell.workload_file, "w", encoding="utf-8") as f:
        json.dump(wl, f)
    line = run.execute(cell)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    assert jax.default_backend() == "cpu"
    # the drivers' stand-ins are gone from serve_common after the run
    common = cells.load_module("drivers", "serve_common")
    assert common.schedule.__name__ == "schedule"
    assert common.subprocess.__name__ == "subprocess"
    assert common.tracelib.__name__ == "harness.trace"
