"""Tiny copies of the cells for the CPU: the same files, drivers and
readers, with sizes a test run can hold."""
import json
import time

import peaks
import run
from harness import cells

TRAIN_LIMITS = {"loss0_gap": 0.01, "loss_gap": 0.1, "batch_var_err": 0.01,
                "grad_norm_gap": 0.1, "delta_norm_gap": 0.2}
SERVE_LIMITS = {"served_gap_max": 1e-3, "served_gap_mean": 1e-4}


def train_cell(name: str, seed: int = 2 ** 31 + 77, seconds: float = 0.5):
    wl = cells.load_workload(name)
    cfg = dict(cells.load_config(wl["config"]),
               stages=[[8, 1, 1], [16, 2, 2]], image=32, num_classes=10,
               limits=TRAIN_LIMITS)
    wl = dict(wl, traffic=dict(wl["traffic"], batch=16, calibrate_steps=2))
    return _cell(wl, cfg, seed, seconds)


def serve_cell(name: str, tmp_path, seed: int = 2 ** 31 + 77,
               seconds: float = 2.0):
    wl = cells.load_workload(name)
    cfg = dict(cells.load_config(wl["config"]), n_layer=2, n_embd=64,
               n_head=4, n_positions=128, vocab_size=256,
               limits=SERVE_LIMITS)
    tr = dict(wl["traffic"], ramp_s=1,
              prompt_len={"median": 12, "sigma": 0.5, "lo": 4, "hi": 32},
              output_len={"median": 8, "sigma": 0.4, "lo": 4, "hi": 16},
              prompt_buckets=[16, 32], drain_s=10, check_requests=3)
    if tr["arrivals"]["kind"] == "poisson":
        tr["arrivals"] = dict(tr["arrivals"], rate=8.0)
    else:
        tr["arrivals"] = dict(tr["arrivals"], clients=4, per_client=40)
    wl = dict(wl, traffic=tr)
    cell = _cell(wl, cfg, seed, seconds)
    cell.workload_file = str(tmp_path / "workload.json")
    with open(cell.workload_file, "w", encoding="utf-8") as f:
        json.dump(wl, f)
    return cell


def _cell(wl, cfg, seed, seconds):
    cell = run.Cell(wl, cfg, seed, seconds, False, time.monotonic())
    # what the look for a chip would have filled in
    cell.device = {"platform": "cpu", "kind": "cpu", "count": wl["chips"]}
    cell.peaks = peaks.peaks_for("TPU v5 lite")
    return cell
