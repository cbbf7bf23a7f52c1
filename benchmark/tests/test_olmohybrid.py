"""The ``olmohybrid`` family at a tiny size on the CPU: the counts its
reference keeps, the two new readers' arithmetic on a recorded sample,
and the new cell's driver end to end."""
import json

import jax
import pytest

import peaks
import run
import tiny
from harness import cells

CELL = "olmo_hybrid_7b.docqa_closed16"
TINY = {"num_hidden_layers": 8, "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 4, "intermediate_size": 128,
        "linear_num_key_heads": 4, "linear_num_value_heads": 4,
        "linear_key_head_dim": 8, "linear_value_head_dim": 16,
        "vocab_size": 96, "dtype": "float32", "delta_chunk": 8,
        "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 2,
        "serving": {"max_slots": 4, "page_size": 4, "num_pages": 65,
                    "capacity": 64},
        # the weights are bfloat16 leaves, so the program serves in
        # bfloat16 here too: read 0.053 / 0.0023 at this size, and the
        # float8 control 0.79 / 0.38
        "limits": {"served_gap_max": 0.25, "served_gap_mean": 0.02}}


def _cell_of(cfg):
    return type("C", (), {
        "config": cfg, "workload": cells.load_workload(CELL),
        "reference": cells.load_module("references", cfg["family"])})()


def test_the_published_sizes_count_as_reckoned():
    cfg = cells.load_config("olmo_hybrid_7b")
    ref = cells.load_module("references", cfg["family"])
    per = ref.layer_params(cfg)
    # ISSUE 30's arithmetic: 88.75 M of mixer + 126.81 M of FFN a linear
    # layer, 58.98 M + FFN a full one, 2 x 385.4 M of embedding and head
    assert per == {"linear_attention": 215_570_172,
                   "full_attention": 185_809_920}
    assert ref.param_count(cfg) == 4_100_788_944
    whole = dict(cfg, num_hidden_layers=32,
                 layer_types=cfg["layer_types"] * 2)
    assert ref.param_count(whole) == 7_430_870_688
    assert ref.cache_bytes(cfg) == {
        "paged": 4 * 2 * 3840 * 2.0,
        "recurrent": 12 * (4.0 * 30 * 96 * 192 + 2 * 3 * 11520)}
    weights = 2.0 * (4_100_788_944 - 100352 * 3840)
    assert ref.param_bytes(cfg) == weights
    # with the paged positions alone it is weights + paged rows: what the
    # list-less decode_roofline_pct.batch asks of every serving cell
    assert ref.decode_step_bytes(cfg, 1000) == weights + 1000 * 61440
    assert ref.decode_step_bytes(cfg, 1000, 10) == pytest.approx(
        weights + 1000 * 61440 + 2 * 10 * 27_371_520)
    # a prefill: two operations a weight a position (the embedding is a
    # lookup, the head runs once), the causal half of the attention, the
    # recurrence's 7 a state element
    t = 4096
    matrices = 4_100_788_944 - 2 * 100352 * 3840 - 16 * 2 * 3840 - 3840 \
        - 12 * (4 * 11520 + 2 * 30 + 192) - 4 * 2 * 3840
    assert ref.prefill_flops(cfg, t) == pytest.approx(
        2.0 * matrices * t + 4 * 2.0 * t * t * 3840
        + 12 * 7.0 * 30 * 96 * 192 * t + 2.0 * 3840 * 100352)
    assert 27e12 < ref.prefill_flops(cfg, t) < 29e12


def _window(pages, slots, positions, prompt):
    """A window as the driver hands it to the readers: gauge samples and
    the two snapshots of the prefill counters (by bucket)."""
    def snap(scale):
        return {
            "dl4j_tpu_serving_prefill_positions_total": {
                "labelnames": ["model", "bucket"], "cells": [
                    [["lm", str(b)], scale * n * b]
                    for b, n in positions.items()]},
            "dl4j_tpu_serving_prefill_prompt_tokens_total": {
                "labelnames": ["model"], "cells": [[["lm"], scale * prompt]]}}
    return {"seconds": 40.0, "before": snap(1), "after": snap(2),
            "samples": {
                "dl4j_tpu_serving_kv_pages_in_use": pages,
                "dl4j_tpu_serving_state_slots_in_use": slots}}


def test_delta_roofline_is_bytes_over_peak_over_the_step():
    reader = cells.load_module("readers", "delta_decode_roofline")
    cfg = cells.load_config("olmo_hybrid_7b")
    cell = _cell_of(cfg)
    metric = {"name": "decode_roofline_pct.docqa",
              "args": {"module": "decode"}}
    # 10 prefills of 512 and 10 of 1,024 holding 10,000 real tokens: a
    # request's left padding is (15,360 - 10,000) / 20 = 268 rows
    w = _window([500, 540], [16.0, 16.0], {512: 10, 1024: 10}, 10000)
    ctx = {"cell": cell, "peaks": peaks.peaks_for("TPU v5 lite"),
           "trace": {"modules": {"jit_step": {"count": 100,
                                              "total_s": 2.0}}},
           "window": w}
    need = cell.reference.decode_step_bytes(cfg, 16 * 520 - 16 * 268, 16.0)
    assert reader.read(metric, ctx) == pytest.approx(
        100.0 * (need / 819e9) / 0.02)
    # a program without the new counters (the parent) gives nothing
    ctx["window"] = dict(w, before={}, after={})
    assert reader.read(metric, ctx) is None
    # and a step faster than the bytes allow is a fault, never clipped
    ctx["window"] = w
    ctx["trace"]["modules"]["jit_step"]["total_s"] = 0.5
    with pytest.raises(ValueError):
        reader.read(metric, ctx)


def test_prefill_mfu_is_the_traced_prefills_operations_over_their_time():
    reader = cells.load_module("readers", "prefill_mfu")
    cfg = cells.load_config("olmo_hybrid_7b")
    cell = _cell_of(cfg)
    metric = {"name": "prefill_mfu_pct.docqa", "args": {"module": "prefill"}}
    # the trace names each prefill by its bucket: 3 of 1,024 positions,
    # 5 of 2,048 and 4 of 4,096, 3 s of device time between them (and a
    # program of another name, which is no prefill's)
    modules = {"jit_prefill_1024": {"count": 3, "total_s": 0.3},
               "jit_prefill_2048": {"count": 5, "total_s": 0.9},
               "jit_prefill_4096": {"count": 4, "total_s": 1.8},
               "jit_step": {"count": 150, "total_s": 3.0}}
    ctx = {"cell": cell, "peaks": peaks.peaks_for("TPU v5 lite"),
           "trace": {"modules": modules}, "window": {}}
    flops = 3 * cell.reference.prefill_flops(cfg, 1024) \
        + 5 * cell.reference.prefill_flops(cfg, 2048) \
        + 4 * cell.reference.prefill_flops(cfg, 4096)
    assert reader.read(metric, ctx) == pytest.approx(
        100.0 * flops / 3.0 / 197e12)
    # a program that names its prefill otherwise (the parent's jit_run),
    # or an untraced run, gives nothing
    ctx["trace"] = {"modules": {"jit_run": {"count": 12, "total_s": 3.0}}}
    assert reader.read(metric, ctx) is None
    ctx["trace"] = None
    assert reader.read(metric, ctx) is None
    # and prefills faster than the MXU allows are a fault, never clipped
    modules["jit_prefill_4096"]["total_s"] = 1e-3
    modules["jit_prefill_2048"]["total_s"] = 1e-3
    ctx["trace"] = {"modules": modules}
    with pytest.raises(ValueError):
        reader.read(metric, ctx)


def test_the_cell_reports_its_own_metrics_and_the_listless_five():
    """The cell reads its eight ``.docqa`` metrics and, like every cell
    that reports ``serve_tok_s``, the five ``.batch`` metrics that carry
    no list of cells (``README.olmohybrid.md``)."""
    names = {m["name"] for m in cells.layer_metrics_for(
        cells.load_workload(CELL))}
    assert {n for n in names if n.endswith(".docqa")} == {
        "device_idle_pct.docqa", "decode_step_device_ms.docqa",
        "prefill_device_ms.docqa", "slot_occupancy_pct.docqa",
        "decode_host_gap_ms.docqa", "admit_host_ms.docqa",
        "decode_roofline_pct.docqa", "prefill_mfu_pct.docqa"}
    assert names - {n for n in names if n.endswith(".docqa")} == {
        "device_idle_pct.batch", "slot_occupancy_pct.batch",
        "decode_step_device_ms.batch", "decode_roofline_pct.batch",
        "decode_host_gap_ms.batch"}
    with open(cells.REPO_DIR + "/BENCHMARK.json") as f:
        listed = {m["name"] for m in json.load(f)["per_layer"]
                  if CELL in m.get("workloads", [])}
    assert listed == {n for n in names if n.endswith(".docqa")}


def test_every_seed_sends_the_same_lengths_in_the_same_order():
    """``loadgen/ordered.py``: the lengths, their order and their clients
    are the traffic mix's (``schedule.build`` for its ``order_seed``);
    the seed, which may be over 2**31, draws the prompts' tokens."""
    driver = cells.load_module("drivers", "serve_closed_ordered")
    schedule = cells.load_module("drivers", "serve_common").schedule
    traffic = cells.load_workload(CELL)["traffic"]
    a, b, again = (driver.ordered.build(traffic, 100352, seed, 40.0)
                   for seed in (2 ** 31 + 77, 3, 2 ** 31 + 77))
    plain = schedule.build(traffic, 100352, traffic["order_seed"], 40.0)
    shape = lambda s: [(r["id"], r["client"], r["prompt_len"], r["max_new"])
                       for r in s["requests"]]
    assert shape(a) == shape(b) == shape(plain) and len(shape(a)) == 240
    assert sorted(r["prompt_len"] for r in a["requests"]) == sorted(
        schedule.lognormal_set(240, traffic["prompt_len"]))
    tokens = lambda s: [r["tokens"] for r in s["requests"]]
    assert tokens(a) == tokens(again) and tokens(a) != tokens(b)
    assert all(len(r["tokens"]) == r["prompt_len"]
               and 0 <= min(r["tokens"]) and max(r["tokens"]) < 100352
               for r in a["requests"])
    assert (a["ramp_s"], a["window_s"]) == (traffic["ramp_s"], 40.0)


def test_the_cell_runs_and_is_correct_at_a_tiny_size(tmp_path):
    """Through the cell's own driver, ``serve_closed_ordered``: a
    generator's process that sent another schedule than the run's would
    leave every request short or long of its ``max_new``, and failed."""
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
    # the driver's two stand-ins are gone from serve_common after the run
    common = cells.load_module("drivers", "serve_common")
    assert common.schedule.__name__ == "schedule"
    assert common.subprocess.__name__ == "subprocess"
