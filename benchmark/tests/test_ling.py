"""The ``ling`` family at a tiny size on the CPU: the counts its reference
keeps against sizes worked by hand, the two new readers' arithmetic on a
recorded window, the scope the driver cuts the compiled step by, the
traffic letter for letter, and the new cell end to end with its control."""
import json

import jax
import pytest

import peaks
import run
import tiny
from harness import cells

CELL = "ling3_flash.think_closed64"
TINY = {"hidden_size": 64, "num_attention_heads": 4, "head_dim": 16,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "intermediate_size": 128,
        "moe_intermediate_size": 32, "router_width": 16,
        "experts_held": [0, 4], "num_experts": 4, "num_experts_per_tok": 4,
        "n_group": 4, "topk_group": 2, "num_hidden_layers": 7,
        "vocab_size": 96, "kda_chunk": 8,
        "router_balance": {"tokens": 48, "steps": 40, "speed": 0.001},
        "serving": {"max_slots": 4, "page_size": 4, "num_pages": 65,
                    "capacity": 64},
        # the weights are bfloat16 leaves, so the program serves in
        # bfloat16 here too (tests/test_ling.py holds float32 to 1e-5)
        "limits": {"served_gap_max": 0.4, "served_gap_mean": 0.03}}


def _cell_of(cfg):
    return type("C", (), {
        "config": cfg, "workload": cells.load_workload(CELL),
        "reference": cells.load_module("references", cfg["family"])})()


def test_the_published_sizes_count_as_reckoned():
    cfg = cells.load_config("ling3_flash")
    ref = cells.load_module("references", cfg["family"])
    per = ref.layer_params(cfg)
    # ISSUE 44's arithmetic with the small vectors counted too.  KDA: five
    # matrices of 2,560 x 4,096 (q, k, v, the decay's, the output's),
    # two of 2,560 x 32 (beta, the head-wise gate), three convolutions of
    # 4 x 4,096, A_log 32, dt_bias 4,096, the output norm 128
    assert per["kda"] == 5 * 2560 * 4096 + 2 * 2560 * 32 + 3 * 4 * 4096 \
        + 32 + 4096 + 128 == 52_646_048
    # MLA: W_q 2,560 x 6,144, W_dkv 2,560 x 576, the latent's norm 512,
    # W_uk and W_uv 512 x 8,192 together, W_o 4,096 x 2,560
    assert per["mla"] == 2560 * 6144 + 2560 * 576 + 512 + 512 * 8192 \
        + 4096 * 2560 == 31_883_776
    assert per["expert"] == per["shared"] == 3 * 2560 * 768 == 5_898_240
    assert per["router"] == 2560 * 512 + 512
    assert per["dense"] == 3 * 2560 * 6144 == 47_185_920
    assert ref.layer_kinds(cfg) == ["kda"] * 4 + ["mla"] + ["kda"] * 2
    expert_layer = per["shared"] + per["router"] + 128 * per["expert"]
    assert ref.param_count(cfg) == 2 * 39296 * 2560 + 2560 \
        + 6 * (per["kda"] + 5120) + per["mla"] + 5120 + per["dense"] \
        + 6 * expert_layer == 5_169_285_056
    whole = ref.published(cfg)
    assert (whole["num_hidden_layers"], whole["num_experts"],
            whole["experts_held"], whole["first_layer"],
            whole["vocab_size"]) == (42, 512, [0, 512], 0, 157184)
    assert ref.layer_kinds(whole).count("mla") == 7
    print("whole:", ref.param_count(whole), "the family: ~125B-A5.5B")
    assert ref.param_count(whole) == 124_049_503_712
    assert (ref.expert_layers(cfg), ref.experts_held(cfg)) == (6, 128)
    # a live position keeps ONE latent row (the one MLA layer's 576
    # lanes); a live slot 6 layers of 32 heads of 128 x 128 float32 and of
    # three windows of 3 rows of 4,096 bfloat16
    assert ref.cache_bytes(cfg) == {
        "paged": 2.0 * 576,
        "recurrent": 6.0 * (4 * 32 * 128 * 128 + 2 * 3 * 3 * 4096)}
    assert ref.kda_state_bytes(cfg, 64) == 2 * 64 * 13_025_280
    outside = 2.0 * (5_169_285_056 - 39296 * 2560 - 6 * 128 * 5_898_240)
    assert ref.param_bytes(cfg) == outside
    assert 1.07e9 < outside < 1.08e9
    # one argument: what EVERY step reads, which the list-less
    # decode_roofline_pct.batch asks of every serving cell: the weights
    # outside the experts and the live latent rows; no routed expert, no
    # slot's state
    assert ref.decode_step_bytes(cfg, 200_000) == outside + 200_000 * 1152
    # the fuller form: the counted experts and the live slots' state
    assert ref.decode_step_bytes(cfg, 200_000, 480.0, 64.0) == \
        outside + 200_000 * 1152 + 480 * 2 * 5_898_240 \
        + 2 * 64 * 13_025_280
    # ISSUE 44's reckoning of a step: about 9.3 GB with 63% of 768 held
    # experts hit and 64 slots of some 3,500 rows
    assert 8.5e9 < ref.decode_step_bytes(cfg, 64 * 3500, 0.63 * 768, 64) \
        < 9.5e9
    # a prefill: two operations a weight outside the routed experts a
    # position, two held pairs a token a layer, the MLA layer's causal
    # half, seven operations a state element a position in 6 KDA layers
    t = 8192
    matrices = 6 * (5 * 2560 * 4096 + 2 * 2560 * 32) \
        + (31_883_776 - 512) + 47_185_920 \
        + 6 * (5_898_240 + 2560 * 512 + 2.0 * 5_898_240)
    assert ref.prefill_flops(cfg, t) == pytest.approx(
        2.0 * matrices * t + 1.0 * t * t * 32 * (192 + 128)
        + 6 * 7.0 * 32 * 128 * 128 * t + 2.0 * 2560 * 39296)
    assert 9e12 < ref.prefill_flops(cfg, t) < 9.5e12


def _window(slots, pages, steps, hit):
    """A window as the driver hands it to the readers: gauge samples and
    the two snapshots of the counters (no prompt was padded)."""
    def snap(scale):
        both = lambda n: {"labelnames": ["model", "phase"], "cells": [
            [["lm", "step"], scale * n], [["lm", "prefill"], scale * 7 * n]]}
        return {
            "dl4j_tpu_serving_decode_steps_total": {
                "labelnames": ["model"], "cells": [[["lm"], scale * steps]]},
            "dl4j_tpu_serving_moe_experts_hit_total": both(hit),
            "dl4j_tpu_serving_moe_pairs_routed_total": both(hit),
            "dl4j_tpu_serving_prefill_positions_total": {
                "labelnames": ["model", "bucket"],
                "cells": [[["lm", "1024"], scale * 1024 * 10]]},
            "dl4j_tpu_serving_prefill_prompt_tokens_total": {
                "labelnames": ["model"],
                "cells": [[["lm"], scale * 1024 * 10]]}}
    return {"seconds": 40.0, "before": snap(1), "after": snap(2),
            "samples": {"dl4j_tpu_serving_state_slots_in_use": slots,
                        "dl4j_tpu_serving_kv_pages_in_use": pages}}


def test_the_two_new_readers_arithmetic():
    cfg = cells.load_config("ling3_flash")
    cell = _cell_of(cfg)
    ref = cell.reference
    # 2,000 steps that hit 960,000 experts (480 a step of the 768 held),
    # 64 slots, 1,750 pages of 128 rows in use
    w = _window([64.0, 64.0], [1750.0, 1750.0], 2000, 960_000)
    ctx = {"cell": cell, "peaks": peaks.peaks_for("TPU v5 lite"),
           "trace": {"modules": {"jit_step": {"count": 200,
                                              "total_s": 3.0}},
                     "kernels": {"kda_step": {"count": 1200,
                                              "total_s": 0.5,
                                              "instructions": 500}}},
           "window": w}
    hit = cells.load_module("readers", "held_experts_hit")
    assert hit.read({"name": "moe_experts_hit_pct.think", "args": {}},
                    ctx) == pytest.approx(100.0 * 480 / 768)
    step = cells.load_module("readers", "kda_moe_decode_roofline")
    metric = {"name": "decode_roofline_pct.think",
              "args": {"module": "decode"}}
    need = ref.decode_step_bytes(cfg, 1750 * 128, 480.0, 64.0)
    assert step.read(metric, ctx) == pytest.approx(
        100.0 * (need / 819e9) / 0.015)
    state = cells.load_module("readers", "kda_state_roofline")
    metric = {"name": "kda_state_roofline_pct.think",
              "args": {"scope": "kda_step", "module": "decode"}}
    # the scope's seconds over the STEPS of the stretch (200), whatever
    # the driver counted as calls
    assert state.read(metric, ctx) == pytest.approx(
        100.0 * (2 * 64 * 13_025_280 / 819e9) / (0.5 / 200))
    ctx["trace"]["kernels"]["kda_step"]["count"] = 0
    assert state.read(metric, ctx) == pytest.approx(
        100.0 * (2 * 64 * 13_025_280 / 819e9) / (0.5 / 200))
    # the list-less metric every serving cell reports reads the floor
    floor = cells.load_module("readers", "decode_roofline")
    assert floor.read({"name": "decode_roofline_pct.batch",
                       "args": {"module": "decode"}}, ctx) == pytest.approx(
        100.0 * ref.decode_step_bytes(cfg, 1750 * 128) / 819e9 / 0.015)
    assert ref.decode_step_bytes(cfg, 1750 * 128) < need / 5
    # a trace without the scope's ops (another driver, the parent), an
    # untraced run, a program without the counters: nothing to read
    ctx["trace"] = {"modules": ctx["trace"]["modules"]}
    assert state.read(metric, ctx) is None
    ctx["trace"] = None
    assert state.read(metric, ctx) is None
    assert step.read({"name": "x", "args": {"module": "decode"}}, ctx) is None
    ctx["trace"] = {"modules": {"jit_step": {"count": 200, "total_s": 3.0}},
                    "kernels": {"kda_step": {"count": 0, "total_s": 0.0,
                                             "instructions": 0}}}
    assert state.read(metric, ctx) is None
    ctx["window"] = dict(w, before={}, after={})
    assert step.read({"name": "x", "args": {"module": "decode"}}, ctx) is None
    assert hit.read({"name": "x", "args": {}}, ctx) is None
    # and a step faster than its bytes allow is a fault, never clipped
    ctx["window"] = w
    ctx["trace"]["modules"]["jit_step"]["total_s"] = 1.0
    with pytest.raises(ValueError):
        step.read({"name": "x", "args": {"module": "decode"}}, ctx)
    ctx["trace"]["kernels"]["kda_step"]["total_s"] = 0.1
    with pytest.raises(ValueError):
        state.read(metric, ctx)


def test_the_traffic_is_the_issue_s_letter_for_letter():
    wl = cells.load_workload(CELL)
    t = wl["traffic"]
    assert (wl["chips"], wl["driver"]) == (1, "serve_closed_ordered_scoped")
    assert t["arrivals"] == {"kind": "closed", "clients": 80,
                             "per_client": 6}
    assert t["prompt_len"] == {"median": 1024, "sigma": 0.7, "lo": 256,
                               "hi": 8192}
    assert t["output_len"] == {"median": 2048, "sigma": 0.4, "lo": 1024,
                               "hi": 4096}
    assert t["prompt_buckets"] == [1024, 2048, 4096, 8192]
    assert (t["stagger_s"], t["ramp_s"], t["timeout_s"], t["drain_s"],
            t["check_requests"], t["trace_seconds"], t["trace_offset_s"]) \
        == (0.05, 30, 240, 20, 4, 3, 0)
    assert isinstance(t["order_seed"], int)
    cfg = cells.load_config(wl["config"])
    assert cfg["serving"] == {"max_slots": 64, "page_size": 128,
                              "num_pages": 64 * 96 + 1, "capacity": 12288}
    assert cfg["serving"]["capacity"] == 8192 + 4096
    assert set(cfg["prefill_positions"].values()) == set(t["prompt_buckets"])
    assert cfg["trace_scopes"] == {"kda_step": "kda_step"}
    assert len(wl["why"]) <= 200


def test_the_cell_reports_its_own_metrics_and_the_listless_five():
    names = {m["name"] for m in cells.layer_metrics_for(
        cells.load_workload(CELL))}
    own = {n for n in names if n.endswith(".think")}
    assert own == {
        "device_idle_pct.think", "slot_occupancy_pct.think",
        "decode_host_gap_ms.think", "admit_host_ms.think",
        "admit_idle_ms.think", "device_starved_pct.think",
        "decode_step_device_ms.think", "prefill_device_ms.think",
        "prefill_mfu_pct.think", "moe_experts_hit_pct.think",
        "decode_roofline_pct.think", "kda_state_roofline_pct.think"}
    assert names - own == {
        "device_idle_pct.batch", "slot_occupancy_pct.batch",
        "decode_step_device_ms.batch", "decode_roofline_pct.batch",
        "decode_host_gap_ms.batch"}
    with open(cells.REPO_DIR + "/BENCHMARK.json") as f:
        bench = json.load(f)
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed == own
    assert [w for w in bench["workloads"] if w["name"] == CELL][0]["why"] \
        == cells.load_workload(CELL)["why"]


def _tiny_cell(tmp_path):
    wl = cells.load_workload(CELL)
    cfg = dict(cells.load_config(wl["config"]), **TINY)
    tr = dict(wl["traffic"], ramp_s=1,
              arrivals={"kind": "closed", "clients": 5, "per_client": 40},
              prompt_len={"median": 10, "sigma": 0.5, "lo": 4, "hi": 16},
              output_len={"median": 20, "sigma": 0.4, "lo": 12, "hi": 40},
              prompt_buckets=[8, 16], drain_s=10, check_requests=3)
    wl = dict(wl, traffic=tr)
    cell = tiny._cell(wl, cfg, 2 ** 31 + 77, 3.0)
    cell.workload_file = str(tmp_path / "workload.json")
    with open(cell.workload_file, "w", encoding="utf-8") as f:
        json.dump(wl, f)
    return cell, cfg


def test_the_cell_runs_and_is_correct_at_a_tiny_size(tmp_path, capfd):
    """Through the cell's own driver; the float8 control's numbers are
    printed on earlier lines, and the driver's second control finds none
    to print."""
    cell, cfg = _tiny_cell(tmp_path)
    cell.control = True
    family = cells.load_module("configs", cfg["family"])
    line = run.execute(cell)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    assert jax.default_backend() == "cpu"
    said = capfd.readouterr()
    said = said.out + said.err
    assert "control: served_gap_mean" in said
    assert "selection left out" not in said
    # the drivers' stand-ins are gone after the run
    common = cells.load_module("drivers", "serve_common")
    assert common.schedule.__name__ == "schedule"
    assert common.subprocess.__name__ == "subprocess"
    assert common.tracelib.__name__ == "harness.trace"
    assert cell.family is family
    assert cell.reference is cells.load_module("references", cfg["family"])


def test_the_compiled_step_carries_the_scope_the_driver_cuts_by(tmp_path):
    """``configs/ling.py:step_program_text`` prints the batcher's compiled
    step; the driver finds the KDA layers' instructions in it by the
    scope the configuration names."""
    cell, cfg = _tiny_cell(tmp_path)
    run.attach(cell)
    weights = cell.reference.make_weights(cfg, jax.random.PRNGKey(1))
    server, batcher = cell.family.build_server(
        cfg, weights, "lm", dict(cfg["serving"], prompt_buckets=[8, 16]))
    try:
        text = cell.family.step_program_text(batcher)
    finally:
        batcher.shutdown()
    driver = cells.load_module("drivers", "serve_closed_ordered_scoped")
    found = driver.scoped_instructions(text, cfg["trace_scopes"]["kda_step"])
    assert len(found) >= 6
    assert not driver.scoped_instructions(text, "kda_chunked")
