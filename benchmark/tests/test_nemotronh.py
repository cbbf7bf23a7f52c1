"""The ``nemotronh`` family at a tiny size on the CPU: the counts its
reference keeps against sizes worked by hand, the new reader's arithmetic
on a recorded window, the scopes the driver cuts the compiled step by, the
traffic letter for letter, and the new cell end to end with its control."""
import json

import jax
import pytest

import peaks
import run
import tiny
from harness import cells

CELL = "nemotron3_super.agent_closed64"
TINY = {"hidden_size": 64, "mamba_num_heads": 16, "mamba_head_dim": 8,
        "n_groups": 2, "ssm_state_size": 16, "chunk_size": 8,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
        "moe_intermediate_size": 48, "moe_latent_size": 32,
        "moe_shared_expert_intermediate_size": 96, "router_width": 16,
        "experts_held": [0, 4], "n_routed_experts": 4,
        "num_experts_per_tok": 6, "hybrid_override_pattern": "MEM*E",
        "num_hidden_layers": 5, "first_block": 3, "vocab_size": 96,
        "serving": {"max_slots": 4, "page_size": 4, "num_pages": 65,
                    "capacity": 64},
        # the weights are bfloat16 leaves, so the program serves in
        # bfloat16 here too (tests/test_nemotron_h.py holds float32 to
        # 1e-5)
        "limits": {"served_gap_max": 0.1, "served_gap_mean": 0.01}}


def _cell_of(cfg):
    return type("C", (), {
        "config": cfg, "workload": cells.load_workload(CELL),
        "reference": cells.load_module("references", cfg["family"])})()


def test_the_published_sizes_count_as_reckoned():
    cfg = cells.load_config("nemotron3_super")
    ref = cells.load_module("references", cfg["family"])
    per = ref.block_params(cfg)
    # ISSUE 48's arithmetic with the small vectors counted too.  Mamba-2:
    # W_in 4,096 x 18,560, the convolution 4 x 10,240 and its bias,
    # dt_bias, A_log and D 128 each, the gated norm's gain 8,192, W_out
    # 8,192 x 4,096
    assert per["M"] == 4096 * 18560 + 4 * 10240 + 10240 + 3 * 128 + 8192 \
        + 8192 * 4096 == 109_635_968
    assert per["*"] == 2 * 4096 * 4096 + 2 * 4096 * 256 == 35_651_584
    assert per["router"] == 4096 * 512 + 512
    assert per["latent"] == 2 * 4096 * 1024
    assert per["shared"] == 2 * 4096 * 5376 == 44_040_192
    assert per["expert"] == 2 * 1024 * 2688 == 5_505_024
    expert_block = per["router"] + per["latent"] + per["shared"] \
        + 128 * per["expert"]
    assert ref.param_count(cfg) == 2 * 32768 * 4096 + 4096 + 11 * 4096 \
        + 5 * per["M"] + per["*"] + 5 * expert_block == 4_648_163_712
    whole = ref.published(cfg)
    assert (whole["num_hidden_layers"], whole["n_routed_experts"],
            whole["vocab_size"], whole["first_block"]) == (88, 512, 131072, 0)
    assert ref.param_count(whole) == 120_668_707_840
    assert (ref.experts_held(cfg), ref.expert_layers(cfg)) == (128, 5)
    # the step's bytes: everything but the table and the held experts
    # (the five routers in float32), then 11 MB a hit expert
    rest = ref.param_count(cfg) - 32768 * 4096 - 5 * 128 * per["expert"] \
        - 5 * per["router"]
    assert ref.param_bytes(cfg) == 2 * rest + 4 * 5 * per["router"]
    assert ref.expert_bytes(cfg) == 2 * per["expert"] == 11_010_048
    assert ref.param_bytes(cfg, 600.0) == ref.param_bytes(cfg) \
        + 600 * 11_010_048
    c = ref.cache_bytes(cfg)
    assert c == {"paged": 2 * 2 * 256, "state": 5 * 4 * 128 * 64 * 128,
                 "window": 5 * 2 * 3 * 10240}
    assert ref.ssd_state_bytes(cfg, 64) == 2 * 64 * 20_971_520
    assert ref.decode_step_bytes(cfg, 1000.0, 600.0, 64.0) == \
        ref.param_bytes(cfg, 600.0) + 1000 * 1024 \
        + 2 * 64 * (20_971_520 + 307_200)
    # ISSUE 48's reckoning of a step of 64 slots at 93.6% hit: ~11.3 GB
    assert 11.0e9 < ref.decode_step_bytes(cfg, 64 * 2300.0, 0.936 * 640,
                                          64.0) < 11.6e9
    # a prefill: 1.1 G operations a position in the weights outside the
    # experts... twice 0.86 G weights a position, 5.5 pairs a layer
    t = 2048
    weights = 5 * (4096 * 18560 + 8192 * 4096) + per["*"] \
        + 5 * (4096 * 512 + per["latent"] + per["shared"]
               + 5.5 * per["expert"])
    assert ref.prefill_flops(cfg, t) == pytest.approx(
        2 * weights * t + t * t * 32 * 256 + 5 * 5 * 128 * 64 * 128 * t
        + 2 * 4096 * 32768)
    assert 4.1e12 < ref.prefill_flops(cfg, t) < 4.3e12


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


def test_the_new_reader_s_arithmetic():
    cfg = cells.load_config("nemotron3_super")
    cell = _cell_of(cfg)
    ref = cell.reference
    # 2,000 steps that hit 1,160,000 experts (580 a step of the 640 held),
    # 64 slots, 1,200 pages of 128 rows in use
    w = _window([64.0, 64.0], [1200.0, 1200.0], 2000, 1_160_000)
    ctx = {"cell": cell, "peaks": peaks.peaks_for("TPU v5 lite"),
           "trace": {"modules": {"jit_step": {"count": 200,
                                              "total_s": 3.2}},
                     "kernels": {"ssd_step": {"count": 0, "total_s": 1.2,
                                              "instructions": 40},
                                 "moe_share_step": {"count": 1000,
                                                    "total_s": 1.7,
                                                    "instructions": 300}}},
           "window": w}
    hit = cells.load_module("readers", "held_experts_hit")
    assert hit.read({"name": "moe_experts_hit_pct.agent", "args": {}},
                    ctx) == pytest.approx(100.0 * 580 / 640)
    step = cells.load_module("readers", "kda_moe_decode_roofline")
    metric = {"name": "decode_roofline_pct.agent",
              "args": {"module": "decode"}}
    need = ref.decode_step_bytes(cfg, 1200 * 128, 580.0, 64.0)
    assert step.read(metric, ctx) == pytest.approx(
        100.0 * (need / 819e9) / 0.016)
    scope = cells.load_module("readers", "scope_roofline")
    ssd = {"name": "ssd_state_roofline_pct.agent",
           "args": {"scope": "ssd_step", "module": "decode",
                    "bytes": "ssd_state_bytes", "of": "live_slots"}}
    moe = {"name": "latent_moe_roofline_pct.agent",
           "args": {"scope": "moe_share_step", "module": "decode",
                    "bytes": "expert_bytes", "of": "experts_hit"}}
    # the scope's seconds over the STEPS of the stretch (200), whatever
    # the driver counted as calls
    assert scope.read(ssd, ctx) == pytest.approx(
        100.0 * (2 * 64 * 20_971_520 / 819e9) / (1.2 / 200))
    assert scope.read(moe, ctx) == pytest.approx(
        100.0 * (580 * 11_010_048 / 819e9) / (1.7 / 200))
    assert set(scope.gauges(ssd)) == {"dl4j_tpu_serving_state_slots_in_use"}
    assert scope.gauges(moe) == {}
    # the list-less metric every serving cell reports reads the floor
    floor = cells.load_module("readers", "decode_roofline")
    assert floor.read({"name": "decode_roofline_pct.batch",
                       "args": {"module": "decode"}}, ctx) == pytest.approx(
        100.0 * ref.decode_step_bytes(cfg, 1200 * 128) / 819e9 / 0.016)
    assert ref.decode_step_bytes(cfg, 1200 * 128) < need / 4
    # a trace without the scope's ops (another driver, the parent), an
    # untraced run, a program without the counters, a reference without
    # the function: nothing to read
    ctx["trace"] = {"modules": ctx["trace"]["modules"]}
    assert scope.read(ssd, ctx) is None and scope.read(moe, ctx) is None
    ctx["trace"] = None
    assert scope.read(ssd, ctx) is None
    ctx["trace"] = {"modules": {"jit_step": {"count": 200, "total_s": 3.2}},
                    "kernels": {"ssd_step": {"count": 0, "total_s": 1.2,
                                             "instructions": 40},
                                "moe_share_step": {"count": 0,
                                                   "total_s": 0.0,
                                                   "instructions": 0}}}
    assert scope.read(moe, ctx) is None
    assert scope.read(dict(ssd, args=dict(ssd["args"], bytes="no_such")),
                      ctx) is None
    ctx["window"] = dict(w, before={}, after={}, samples={})
    assert scope.read(ssd, ctx) is None and scope.read(moe, ctx) is None
    # and a pass faster than its bytes allow is a fault, never clipped
    ctx["window"] = w
    ctx["trace"]["kernels"]["ssd_step"]["total_s"] = 0.5
    with pytest.raises(ValueError):
        scope.read(ssd, ctx)
    with pytest.raises(ValueError):
        scope.read(dict(ssd, args=dict(ssd["args"], of="tokens")), ctx)


def test_the_traffic_is_the_issue_s_letter_for_letter():
    wl = cells.load_workload(CELL)
    t = wl["traffic"]
    assert (wl["chips"], wl["driver"]) == (1, "serve_closed_ordered_scoped")
    assert t["arrivals"] == {"kind": "closed", "clients": 80,
                             "per_client": 8}
    assert t["prompt_len"] == {"median": 2048, "sigma": 0.6, "lo": 512,
                               "hi": 4096}
    assert t["output_len"] == {"median": 512, "sigma": 0.5, "lo": 128,
                               "hi": 1024}
    assert t["prompt_buckets"] == [512, 1024, 2048, 4096]
    assert (t["stagger_s"], t["ramp_s"], t["timeout_s"], t["drain_s"],
            t["check_requests"], t["trace_seconds"], t["trace_offset_s"]) \
        == (0.05, 30, 120, 20, 4, 3, 0)
    assert isinstance(t["order_seed"], int)
    cfg = cells.load_config(wl["config"])
    assert cfg["serving"] == {"max_slots": 64, "page_size": 128,
                              "num_pages": 64 * 40 + 1, "capacity": 5120}
    assert cfg["serving"]["capacity"] == 4096 + 1024
    assert set(cfg["prefill_positions"].values()) == set(t["prompt_buckets"])
    assert cfg["trace_scopes"] == {"ssd_step": "ssd_step",
                                   "moe_share_step": "moe_share_step"}
    assert len(wl["why"]) <= 200


def test_the_cell_reports_its_own_metrics_and_the_listless_five():
    names = {m["name"] for m in cells.layer_metrics_for(
        cells.load_workload(CELL))}
    own = {n for n in names if n.endswith(".agent")}
    assert own == {
        "device_idle_pct.agent", "slot_occupancy_pct.agent",
        "decode_host_gap_ms.agent", "admit_host_ms.agent",
        "admit_idle_ms.agent", "device_starved_pct.agent",
        "decode_step_device_ms.agent", "prefill_device_ms.agent",
        "prefill_mfu_pct.agent", "moe_experts_hit_pct.agent",
        "decode_roofline_pct.agent", "ssd_state_roofline_pct.agent",
        "latent_moe_roofline_pct.agent"}
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
    assert CELL in [m for m in bench["end_to_end"]
                    if m["name"] == "serve_tok_s"][0]["workloads"]
    # the catalog's numbers, key by key, but for the five reduced
    cfg = cells.load_config("nemotron3_super")
    entry = [c for c in bench["configs"] if c["name"] == "nemotron3_super"][0]
    assert entry["reduced"] == cfg["reduced"] and \
        entry["source"] == cfg["source"]


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


def test_the_compiled_step_carries_the_scopes_the_driver_cuts_by(tmp_path):
    """``configs/nemotronh.py:step_program_text`` prints the batcher's
    compiled step; the driver finds the SSD pass's and the experts'
    instructions in it by the scopes the configuration names."""
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
    for scope in cfg["trace_scopes"].values():
        assert len(driver.scoped_instructions(text, scope)) >= 2
    assert not driver.scoped_instructions(text, "ssd_prefill")
