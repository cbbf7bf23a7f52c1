"""The ``keyevl`` family at a tiny size on the CPU: the counts its
reference keeps, the three new readers' arithmetic on a recorded window,
the driver that keeps a scope's ops, the traffic letter for letter, and
the new cell end to end with both controls."""
import json

import jax
import pytest

import peaks
import run
import tiny
from harness import cells

CELL = "keye_vl2_30b_a3b.longdoc_closed16"
TINY = {"hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16,
        "moe_intermediate_size": 32, "router_width": 16,
        "experts_held": [4, 8], "num_experts": 4, "num_experts_per_tok": 4,
        "num_hidden_layers": 2, "vocab_size": 96,
        "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                      "indexer_num_kv_heads": 1, "topk": 6},
        "serving": {"max_slots": 4, "page_size": 4, "num_pages": 65,
                    "capacity": 64},
        # the weights are bfloat16 leaves, so the program serves in
        # bfloat16 here too; 6 rows a query make a flipped selection
        # count for more than 2,048 do
        "limits": {"served_gap_max": 0.4, "served_gap_mean": 0.03}}


def _cell_of(cfg):
    return type("C", (), {
        "config": cfg, "workload": cells.load_workload(CELL),
        "reference": cells.load_module("references", cfg["family"])})()


def test_the_published_sizes_count_as_reckoned():
    cfg = cells.load_config("keye_vl2_30b_a3b")
    ref = cells.load_module("references", cfg["family"])
    per = ref.layer_params(cfg)
    # ISSUE 40's arithmetic, the norms counted too: attention 18,874,368
    # + two head norms of 128, the indexer 2,260,992 + its key's
    # LayerNorm, the router 262,144, one expert 4,718,592
    assert per == {"attention": 18_874_624, "indexer": 2_261_120,
                   "norms": 4096, "router": 262_144, "expert": 4_718_592}
    assert ref.param_count(cfg) == 1_203_728_640
    whole = ref.published(cfg)
    assert (whole["num_hidden_layers"], whole["num_experts"],
            whole["experts_held"]) == (48, 128, [0, 128])
    print("whole:", ref.param_count(whole), "the card: 30B-A3B")
    assert ref.param_count(whole) == 30_640_656_384
    assert (ref.expert_layers(cfg), ref.experts_held(cfg)) == (6, 16)
    assert ref.cache_bytes(cfg) == {"kv": 6 * 2048.0, "index": 6 * 128.0}
    outside = 2.0 * (1_203_728_640 - 151936 * 2048 - 96 * 4_718_592)
    assert ref.param_bytes(cfg) == outside
    # one argument: what EVERY step reads, which the list-less
    # decode_roofline_pct.batch asks of every serving cell: the weights
    # outside the experts and the live INDEX rows, no K or V row
    assert ref.decode_step_bytes(cfg, 400_000) == outside + 400_000 * 768
    # the fuller form: the counted experts, rows scored and rows selected
    assert ref.decode_step_bytes(cfg, 0.0, 60.0, 2_400_000, 196_608) == \
        outside + 60 * 2 * 4_718_592 + 2_400_000 * 128 + 196_608 * 2048
    assert ref.sparse_attention_bytes(cfg, 400_000, 32768) == \
        400_000 * 128 + 32768 * 2048
    assert ref.decode_step_flops(cfg, 16, 96.0, 2_400_000, 196_608) == \
        pytest.approx(16 * outside + 2 * 96 * 4_718_592
                      + 2 * 2_400_000 * 16 * 64
                      + 2 * 196_608 * 32 * 256)
    # a prefill: two operations a weight outside the routed experts a
    # position, one held pair a token a layer, the index scores of the
    # causal half, scores and context over the rows a query READS
    t = 32768
    matrices = 6 * (18_874_624 + 2_261_120 + 262_144 + 1.0 * 4_718_592)
    read = 2048 * 2049 / 2 + (t - 2048) * 2048
    assert ref.prefill_flops(cfg, t) == pytest.approx(
        2.0 * matrices * t + 2.0 * 6 * (t * (t + 1) / 2 * 16 * 64
                                        + read * 32 * 256)
        + 2.0 * 2048 * 151936)
    assert 23e12 < ref.prefill_flops(cfg, t) < 24e12


def _window(slots, steps, hit, routed, scored, selected):
    """A window as the driver hands it to the readers: gauge samples and
    the two snapshots of the counters."""
    def snap(scale):
        both = lambda n: {"labelnames": ["model", "phase"], "cells": [
            [["lm", "step"], scale * n], [["lm", "prefill"], scale * 7 * n]]}
        return {
            "dl4j_tpu_serving_decode_steps_total": {
                "labelnames": ["model"], "cells": [[["lm"], scale * steps]]},
            "dl4j_tpu_serving_moe_experts_hit_total": both(hit),
            "dl4j_tpu_serving_moe_pairs_routed_total": both(routed),
            "dl4j_tpu_serving_sparse_rows_scored_total": both(scored),
            "dl4j_tpu_serving_sparse_rows_selected_total": both(selected)}
    return {"seconds": 40.0, "before": snap(1), "after": snap(2),
            "samples": {"dl4j_tpu_serving_state_slots_in_use": slots}}


def test_the_three_readers_arithmetic():
    cfg = cells.load_config("keye_vl2_30b_a3b")
    cell = _cell_of(cfg)
    ref = cell.reference
    # 2,000 steps that hit 120,000 experts (60 a step of the 96 held) with
    # 96 pairs routed, 2.4 M index rows scored and 196,608 rows selected
    # a step (16 slots of 25,000 live rows, 6 layers)
    w = _window([16.0, 16.0], 2000, 120_000, 192_000, 4_800_000_000,
                393_216_000)
    ctx = {"cell": cell, "peaks": peaks.peaks_for("TPU v5 lite"),
           "trace": {"modules": {"jit_step": {"count": 100,
                                              "total_s": 1.0}},
                     "kernels": {"sparse_attention": {"count": 600,
                                                      "total_s": 0.6}}},
           "window": w}
    hit = cells.load_module("readers", "held_experts_hit")
    assert hit.read({"name": "moe_experts_hit_pct.longdoc", "args": {}},
                    ctx) == pytest.approx(100.0 * 60 / 96)
    step = cells.load_module("readers", "sparse_decode_roofline")
    metric = {"name": "decode_roofline_pct.longdoc",
              "args": {"module": "decode"}}
    need = ref.decode_step_bytes(cfg, 0.0, 60.0, 2_400_000, 196_608)
    assert need / 819e9 > ref.decode_step_flops(
        cfg, 16.0, 96.0, 2_400_000, 196_608) / 197e12
    assert step.read(metric, ctx) == pytest.approx(
        100.0 * (need / 819e9) / 0.01)
    read = cells.load_module("readers", "sparse_attention_roofline")
    metric = {"name": "sparse_attention_roofline_pct.longdoc",
              "args": {"scope": "sparse_attention"}}
    least = ref.sparse_attention_bytes(cfg, 400_000, 32768) / 819e9
    assert read.read(metric, ctx) == pytest.approx(
        100.0 * least / (0.6 / 600))
    # the list-less metric every serving cell reports reads the floor
    floor = cells.load_module("readers", "decode_roofline")
    ctx["window"]["samples"]["dl4j_tpu_serving_kv_pages_in_use"] = [3200]
    assert floor.read({"name": "decode_roofline_pct.batch",
                       "args": {"module": "decode"}}, ctx) == pytest.approx(
        100.0 * ref.decode_step_bytes(cfg, 3200 * 128) / 819e9 / 0.01)
    assert ref.decode_step_bytes(cfg, 3200 * 128) < need
    # a trace without the scope's ops (another driver, the parent), an
    # untraced run, a program without the counters: nothing to read
    ctx["trace"] = {"modules": ctx["trace"]["modules"]}
    assert read.read(metric, ctx) is None
    ctx["trace"] = None
    assert read.read(metric, ctx) is None
    ctx["trace"] = {"modules": {"jit_step": {"count": 100, "total_s": 1.0}}}
    ctx["window"] = dict(w, before={}, after={})
    assert step.read({"name": "x", "args": {"module": "decode"}}, ctx) is None
    assert hit.read({"name": "x", "args": {}}, ctx) is None
    # and a step faster than its bytes allow is a fault, never clipped
    ctx["window"] = w
    ctx["trace"]["modules"]["jit_step"]["total_s"] = 0.2
    with pytest.raises(ValueError):
        step.read({"name": "x", "args": {"module": "decode"}}, ctx)


def test_the_driver_keeps_a_scope_s_ops():
    """``serve_closed_ordered_scoped``'s stand-in for ``harness.trace`` on
    the recorded fixture: ``reduce_trace``'s own numbers, and beside them
    the device time of the ops whose instructions a program's text puts
    under a scope, nested or overlapping ops counted once."""
    from harness import trace as tracelib
    driver = cells.load_module("drivers", "serve_closed_ordered_scoped")
    text = '''ENTRY %main {
  %copy-done = bf16[512,512] copy-done(%copy-start), metadata={op_name="jit(step)/my_read/copy"}
  %my_read_kernel.3 = bf16[8] custom-call(%x), metadata={op_name="jit(step)/my_read/jit(call)/pallas_call"}
  ROOT %convolution_tanh_fusion = bf16[512,512] fusion(%copy-done), kind=kOutput, metadata={op_name="jit(step)/my_read/dot_general" source_file="a.py"}
  %other = f32[] add(%a, %b), metadata={op_name="jit(step)/my_reader/add"}
}'''
    assert driver.scoped_instructions(text, "my_read") == {
        "copy-done", "my_read_kernel.3", "convolution_tanh_fusion"}
    tr = tracelib.Trace(cells.BENCH_DIR + "/tests/fixture.xplane.pb")
    plain = tracelib.reduce_trace(tr, 1)
    kept = driver._KeepScopes({"read": "my_read", "none": "no_such"},
                              lambda: text)
    out = kept.reduce_trace(tr, 1)
    assert {k: v for k, v in out.items() if k != "kernels"} == plain
    assert out["kernels"]["none"] == {"count": 0, "total_s": 0.0,
                                      "instructions": 0}
    got = out["kernels"]["read"]
    per_op = dict(plain["device_ops"])
    assert got["instructions"] == 3 and got["count"] == 0
    assert got["total_s"] == pytest.approx(
        per_op["copy-done"] + per_op["convolution_tanh_fusion"], rel=1e-3)


def test_the_traffic_is_the_issue_s_letter_for_letter():
    wl = cells.load_workload(CELL)
    t = wl["traffic"]
    assert (wl["chips"], wl["driver"]) == (1, "serve_closed_ordered_scoped")
    assert t["arrivals"] == {"kind": "closed", "clients": 20,
                             "per_client": 6}
    assert t["prompt_len"] == {"median": 16384, "sigma": 0.5, "lo": 8192,
                               "hi": 32768}
    assert t["output_len"] == {"median": 1024, "sigma": 0.4, "lo": 512,
                               "hi": 2048}
    assert t["prompt_buckets"] == [8192, 16384, 32768]
    assert (t["stagger_s"], t["ramp_s"], t["timeout_s"], t["drain_s"],
            t["check_requests"]) == (0.05, 30, 240, 20, 2)
    assert isinstance(t["order_seed"], int)
    cfg = cells.load_config(wl["config"])
    assert cfg["serving"] == {"max_slots": 16, "page_size": 128,
                              "num_pages": 4353, "capacity": 34816}
    assert cfg["serving"]["capacity"] == 32768 + 2048
    assert set(cfg["prefill_positions"].values()) == set(t["prompt_buckets"])


def test_the_cell_reports_its_own_metrics_and_the_listless_five():
    names = {m["name"] for m in cells.layer_metrics_for(
        cells.load_workload(CELL))}
    own = {n for n in names if n.endswith(".longdoc")}
    assert own == {
        "device_idle_pct.longdoc", "decode_step_device_ms.longdoc",
        "prefill_device_ms.longdoc", "slot_occupancy_pct.longdoc",
        "decode_host_gap_ms.longdoc", "admit_host_ms.longdoc",
        "device_starved_pct.longdoc", "admit_idle_ms.longdoc",
        "prefill_mfu_pct.longdoc", "decode_roofline_pct.longdoc",
        "sparse_attention_roofline_pct.longdoc",
        "moe_experts_hit_pct.longdoc"}
    assert names - own == {
        "device_idle_pct.batch", "slot_occupancy_pct.batch",
        "decode_step_device_ms.batch", "decode_roofline_pct.batch",
        "decode_host_gap_ms.batch"}
    with open(cells.REPO_DIR + "/BENCHMARK.json") as f:
        listed = {m["name"] for m in json.load(f)["per_layer"]
                  if CELL in m.get("workloads", [])}
    assert listed == own


def test_the_cell_runs_and_is_correct_at_a_tiny_size(tmp_path, capfd):
    """Through the cell's own driver; the numbers of both controls (float8,
    and the selection left out) are printed on earlier lines."""
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
    family = cells.load_module("configs", cfg["family"])
    line = run.execute(cell)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    assert jax.default_backend() == "cpu"
    said = capfd.readouterr()
    said = said.out + said.err
    assert "control: served_gap_mean" in said
    assert "control (selection left out): served_gap_mean" in said
    # the drivers' stand-ins are gone after the run
    common = cells.load_module("drivers", "serve_common")
    assert common.schedule.__name__ == "schedule"
    assert common.subprocess.__name__ == "subprocess"
    assert common.tracelib.__name__ == "harness.trace"
    assert cell.family is family
    assert cell.reference is cells.load_module("references", cfg["family"])
