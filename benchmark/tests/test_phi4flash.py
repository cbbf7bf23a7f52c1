"""The ``phi4flash`` family at a tiny size on the CPU: the hybrid
roofline reader's arithmetic, the counts its reference keeps, and the
new cell's driver end to end."""
import json

import jax
import pytest

import peaks
import run
import tiny
from harness import cells

CELL = "phi4_mini_flash.reason_closed32"
TINY = {"num_hidden_layers": 8, "hidden_size": 64, "num_attention_heads": 8,
        "num_key_value_heads": 4, "intermediate_size": 128,
        "sliding_window": 8, "vocab_size": 96, "mamba_d_state": 4,
        "mamba_dt_rank": 4, "dtype": "float32",
        "serving": {"max_slots": 4, "page_size": 4, "num_pages": 65,
                    "capacity": 64},
        "limits": {"served_gap_max": 1e-4, "served_gap_mean": 1e-5}}


def test_the_published_sizes_count_as_reckoned():
    cfg = cells.load_config("phi4_mini_flash")
    ref = cells.load_module("references", cfg["family"])
    assert ref.param_count(cfg) == 3_852_457_984
    kinds = ref.layer_kinds(cfg)
    assert [kinds.count(k) for k in ("mamba", "window", "full", "cross",
                                     "gmu")] == [9, 8, 1, 7, 7]
    c = ref.cache_bytes(cfg)
    assert c == {"paged": 5120.0, "ring": 8 * 5120.0,
                 "recurrent": 9 * 5120 * (64 + 6.0), "paged_readers": 8}
    # with the paged positions alone it is the count the gpt2 family makes
    assert ref.decode_step_bytes(cfg, 0) == 2 * 3_852_457_984
    assert ref.decode_step_bytes(cfg, 1000, 100, 10) == pytest.approx(
        2 * 3_852_457_984 + 1000 * 5120 * 8 + 100 * 40960
        + 2 * 10 * 9 * 5120 * 70)


def test_hybrid_roofline_is_bytes_over_peak_over_the_step():
    reader = cells.load_module("readers", "hybrid_decode_roofline")
    cfg = cells.load_config("phi4_mini_flash")
    cell = type("C", (), {"config": cfg, "reference": cells.load_module(
        "references", cfg["family"])})()
    metric = {"name": "decode_roofline_pct.reason",
              "args": {"module": "decode"}}
    samples = {reader.PAGES: [2000, 3000], reader.RING: [16384.0],
               reader.SLOTS: [32.0, 32.0]}
    ctx = {"cell": cell, "peaks": peaks.peaks_for("TPU v5 lite"),
           "trace": {"modules": {"jit_step": {"count": 100,
                                              "total_s": 2.0}}},
           "window": {"samples": samples}}
    need = cell.reference.decode_step_bytes(cfg, 16 * 2500, 16384.0, 32.0)
    assert reader.read(metric, ctx) == pytest.approx(
        100.0 * (need / 819e9) / 0.02)
    # a program without the new gauges (the parent) gives nothing to read
    ctx["window"] = {"samples": {reader.PAGES: [2000]}}
    assert reader.read(metric, ctx) is None
    # and a step faster than the bytes allow is a fault, never clipped
    ctx["window"] = {"samples": samples}
    ctx["trace"]["modules"]["jit_step"]["total_s"] = 0.5
    with pytest.raises(ValueError):
        reader.read(metric, ctx)


def test_the_cell_runs_and_is_correct_at_a_tiny_size(tmp_path):
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
