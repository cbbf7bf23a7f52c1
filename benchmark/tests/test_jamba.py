"""The ``jamba`` family at a tiny size on the CPU: the counts its reference
keeps, the roofline readers on this family's counts, and the new cell's
driver end to end."""
import json

import jax
import pytest

import peaks
import run
import tiny
from harness import cells

CELL = "jamba2_3b.assist_closed64"
TINY = {"num_hidden_layers": 8, "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 1, "intermediate_size": 128,
        "attn_layer_period": 4, "attn_layer_offset": 2, "mamba_d_state": 4,
        "mamba_dt_rank": 4, "vocab_size": 96, "dtype": "float32",
        "serving": {"max_slots": 4, "page_size": 4, "num_pages": 65,
                    "capacity": 64},
        # every served token is the reference's best at this size (read
        # 0 / 0 over 84 tokens); the float8 control 0.0064 / 7.6e-5
        "limits": {"served_gap_max": 1e-3, "served_gap_mean": 2e-5}}


def _cell_of(cfg):
    return type("C", (), {
        "config": cfg, "workload": cells.load_workload(CELL),
        "reference": cells.load_module("references", cfg["family"])})()


def test_the_published_sizes_count_as_reckoned():
    cfg = cells.load_config("jamba2_3b")
    ref = cells.load_module("references", cfg["family"])
    assert cfg["reduced"] == []
    # ISSUE 38's arithmetic: 41,241,792 of mixer + 62,914,560 of FFN +
    # 5,120 of norms a Mamba layer, 13,762,560 + FFN + norms an attention
    # layer, 26 + 2 of them, the tied table and the final norm
    assert ref.layer_params(cfg) == {
        "mamba": 41_241_792 + 62_914_560 + 5_120,
        "attention": 13_762_560 + 62_914_560 + 5_120}
    assert ref.param_count(cfg) == 26 * 104_161_472 + 2 * 76_682_240 \
        + 167_772_160 + 2_560 == 3_029_337_472
    kinds = ref.layer_kinds(cfg)
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [7, 21]
    # a position: K and V of ONE head of 128 bf16 lanes in 2 layers; a slot:
    # 26 float32 (16, 5120) states and (3, 5120) bf16 windows
    assert ref.cache_bytes(cfg) == {
        "paged": 2 * 512.0, "recurrent": 26 * 5120 * (64 + 6.0)}
    weights = 2.0 * 3_029_337_472
    assert ref.param_bytes(cfg) == weights
    # with the live positions alone it is weights + live K/V rows: what the
    # list-less decode_roofline_pct.batch asks of every serving cell, a
    # floor under the full count
    assert ref.decode_step_bytes(cfg, 80_000) == weights + 80_000 * 1024
    assert ref.decode_step_bytes(cfg, 80_000, 64) == pytest.approx(
        weights + 80_000 * 1024 + 2 * 64 * 9_318_400)
    # 64 slots' state and windows read and written are 1.19 GB, 16% of it
    assert 2 * 64 * 9_318_400 / ref.decode_step_bytes(cfg, 80_000, 64) \
        == pytest.approx(0.162, abs=0.002)
    # a prefill: two operations a weight a position outside the table
    # (gathered) and the vectors, the causal half of two layers' attention,
    # the convolution's 8 a channel and the recurrence's 9 a state element
    t = 2048
    matrices = 3_029_337_472 - 65536 * 2560 - 2560 - 28 * 2 * 2560 \
        - 26 * (5 * 5120 + 5120 + 5120 * 16 + 5120 + 192)
    assert ref.prefill_flops(cfg, t) == pytest.approx(
        2.0 * matrices * t + 2 * 2.0 * t * t * 2560
        + 26 * (8.0 + 9.0 * 16) * 5120 * t + 2.0 * 2560 * 65536)
    assert 11.7e12 < ref.prefill_flops(cfg, t) < 11.9e12


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


def test_both_decode_rooflines_read_this_familys_counts():
    """``decode_roofline_pct.assist`` (reader ``delta_decode_roofline``:
    pages less the left padding, and the live slots' state) and the
    list-less ``decode_roofline_pct.batch`` (reader ``decode_roofline``:
    weights + pages) on one window: the second reads lower, never over
    100 where the first is not."""
    cfg = cells.load_config("jamba2_3b")
    cell = _cell_of(cfg)
    ps = cfg["serving"]["page_size"]
    pages = [90_000 / ps, 94_000 / ps]
    # 30 prefills of 512 and 10 of 1,024 holding 16,000 real tokens: a
    # request's left padding is (25,600 - 16,000) / 40 = 240 rows
    w = _window(pages, [64.0, 64.0], {512: 30, 1024: 10}, 16000)
    ctx = {"cell": cell, "peaks": peaks.peaks_for("TPU v5 lite"),
           "trace": {"modules": {"jit_step": {"count": 100,
                                              "total_s": 1.1}}},
           "window": w}
    full = cells.load_module("readers", "delta_decode_roofline").read(
        {"name": "decode_roofline_pct.assist",
         "args": {"module": "decode"}}, ctx)
    need = cell.reference.decode_step_bytes(cfg, 92_000 - 64 * 240, 64.0)
    assert full == pytest.approx(100.0 * (need / 819e9) / 0.011)
    floor = cells.load_module("readers", "decode_roofline").read(
        {"name": "decode_roofline_pct.batch",
         "args": {"module": "decode"}}, ctx)
    assert floor == pytest.approx(100.0 * (
        cell.reference.decode_step_bytes(cfg, 92_000) / 819e9) / 0.011)
    assert floor < full < 100.0


def test_prefill_mfu_counts_the_five_buckets_by_name():
    reader = cells.load_module("readers", "prefill_mfu")
    cfg = cells.load_config("jamba2_3b")
    cell = _cell_of(cfg)
    assert cfg["prefill_positions"] == {
        f"jit_prefill_{b}": b for b in cell.workload["traffic"][
            "prompt_buckets"]}
    assert cfg["trace_modules"]["prefill"] == list(cfg["prefill_positions"])
    modules = {"jit_prefill_128": {"count": 4, "total_s": 0.04},
               "jit_prefill_512": {"count": 20, "total_s": 0.6},
               "jit_prefill_2048": {"count": 3, "total_s": 0.4},
               "jit_step": {"count": 400, "total_s": 4.4}}
    ctx = {"cell": cell, "peaks": peaks.peaks_for("TPU v5 lite"),
           "trace": {"modules": modules}, "window": {}}
    flops = sum(m["count"] * cell.reference.prefill_flops(cfg, b)
                for b, m in ((128, modules["jit_prefill_128"]),
                             (512, modules["jit_prefill_512"]),
                             (2048, modules["jit_prefill_2048"])))
    assert reader.read({"name": "prefill_mfu_pct.assist",
                        "args": {"module": "prefill"}}, ctx) \
        == pytest.approx(100.0 * flops / 1.04 / 197e12)


def test_the_cell_reports_its_own_metrics_and_the_listless_five():
    names = {m["name"] for m in cells.layer_metrics_for(
        cells.load_workload(CELL))}
    own = {n for n in names if n.endswith(".assist")}
    assert own == {
        "device_idle_pct.assist", "slot_occupancy_pct.assist",
        "decode_host_gap_ms.assist", "admit_host_ms.assist",
        "device_starved_pct.assist", "admit_idle_ms.assist",
        "decode_step_device_ms.assist", "prefill_device_ms.assist",
        "prefill_mfu_pct.assist", "decode_roofline_pct.assist"}
    assert names - own == {
        "device_idle_pct.batch", "slot_occupancy_pct.batch",
        "decode_step_device_ms.batch", "decode_roofline_pct.batch",
        "decode_host_gap_ms.batch"}
    with open(cells.REPO_DIR + "/BENCHMARK.json") as f:
        bench = json.load(f)
    assert {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", [])} == own
    # the file's entries say what the files beside the readers say
    for m in bench["per_layer"]:
        if m["name"] in own:
            kept = cells.load_json("layer_metrics", m["name"] + ".json")
            assert m == {k: kept[k] for k in m}
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    wl = cells.load_workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"], cell["why"]) \
        == (wl["config"], wl["traffic_name"], wl["chips"], wl["why"])


def test_the_traffic_is_the_issues_letter_for_letter():
    t = cells.load_workload(CELL)["traffic"]
    assert t["arrivals"]["clients"] == 80 and t["stagger_s"] == 0.05
    assert cells.load_config("jamba2_3b")["serving"]["max_slots"] == 64
    assert t["prompt_len"] == {"median": 512, "sigma": 0.7, "lo": 64,
                               "hi": 2048}
    assert t["output_len"] == {"median": 512, "sigma": 0.5, "lo": 128,
                               "hi": 2048}
    assert t["prompt_buckets"] == [128, 256, 512, 1024, 2048]
    assert (t["ramp_s"], t["timeout_s"], t["drain_s"]) == (30, 180, 20)
    driver = cells.load_module("drivers", "serve_closed_ordered")
    a, b = (driver.ordered.build(t, 65536, seed, 40.0)
            for seed in (2 ** 31 + 77, 3))
    shape = lambda s: [(r["id"], r["client"], r["prompt_len"], r["max_new"])
                       for r in s["requests"]]
    n = 80 * t["arrivals"]["per_client"]
    assert shape(a) == shape(b) and len(shape(a)) == n
    assert [r["tokens"] for r in a["requests"]] \
        != [r["tokens"] for r in b["requests"]]
    # capacity: the longest request, a prompt in the 2,048 bucket and
    # 2,048 new tokens, fits a slot's pages
    serving = cells.load_config("jamba2_3b")["serving"]
    assert serving["capacity"] == 2048 + 2048
    assert serving["num_pages"] == 64 * 4096 // serving["page_size"] + 1


def test_the_cell_runs_and_is_correct_at_a_tiny_size(tmp_path):
    """Through the cell's own driver, ``serve_closed_ordered``, with the
    float8 control beside it."""
    wl = cells.load_workload(CELL)
    cfg = dict(cells.load_config(wl["config"]), **TINY)
    tr = dict(wl["traffic"], ramp_s=1,
              arrivals={"kind": "closed", "clients": 5, "per_client": 40},
              prompt_len={"median": 10, "sigma": 0.5, "lo": 2, "hi": 16},
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
