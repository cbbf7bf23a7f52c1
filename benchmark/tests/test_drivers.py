"""Every driver end to end at tiny sizes on the CPU: the command refuses
for want of a TPU; past that look, each driver's run comes out correct;
with the timed path broken underneath it comes out not correct; and the
control (the reference in the precision below the stated one) reads far
from what the sound program reads."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import run
import tiny
from harness import cells, device

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "workloads")))
TRAIN = [c for c in CELLS if cells.load_workload(c)["driver"].startswith(
    "train")]
SERVE = [c for c in CELLS if c not in TRAIN]


@pytest.mark.parametrize("name", CELLS)
def test_the_command_refuses_without_a_tpu(name):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", name,
         "--seed", str(2 ** 31 + 9), "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not any(line.startswith("{") and '"correct"' in line
                   for line in p.stdout.splitlines())


def test_benchmark_json_matches_the_files():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        wl = cells.load_workload(w["name"])
        assert (wl["config"], wl["traffic_name"], wl["chips"], wl["why"]) \
            == (w["config"], w["traffic"], w["chips"], w["why"])
        reported = {m["name"] for m in bench["end_to_end"]
                    if w["name"] in m.get("workloads", [w["name"]])}
        assert reported == set(wl["end_to_end"])
        layer = {m["name"] for m in cells.layer_metrics_for(wl)}
        listed = {m["name"] for m in bench["per_layer"]
                  if w["name"] in m.get("workloads", [w["name"]])
                  and m["moves"] in wl["end_to_end"]}
        assert layer == listed
    for c in bench["configs"]:
        cfg = cells.load_config(c["name"])
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
    for m in bench["per_layer"]:
        f = cells.load_json("layer_metrics", m["name"] + ".json")
        assert {k: f[k] for k in ("unit", "better", "source", "layer",
                                  "moves")} \
            == {k: m[k] for k in ("unit", "better", "source", "layer",
                                  "moves")}
        assert f.get("workloads") == m.get("workloads")


@pytest.mark.parametrize("name", TRAIN)
def test_training_driver_runs_and_is_correct(name):
    if jax.device_count() < cells.load_workload(name)["chips"]:
        pytest.skip("needs four virtual devices")
    line = run.execute(tiny.train_cell(name))
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"train_items_s", "setup_s"}
    assert line["device"]["count"] == cells.load_workload(name)["chips"]


def test_a_step_that_returns_its_state_unchanged_is_not_correct():
    cell = tiny.train_cell(TRAIN[-1])
    driver = cells.load_module("drivers", cell.workload["driver"])
    common = cells.load_module("drivers", "train_common")

    def make_step(cell, net):
        real = driver.make_step(cell, net)
        calls = []

        def step(ds):
            calls.append(1)
            if len(calls) == 1:         # the first step is sound ...
                return real(ds)
            net._scoreArr = jnp.asarray(3.0)    # ... the rest do nothing
        return step
    run.attach(cell)
    outcome = common.run(cell, make_step)
    assert run.judge(outcome["compared"]) is False
    bad = {c["name"] for c in outcome["compared"]
           if not c["value"] <= c["limit"]}
    assert "delta_norm_gap" in bad


def test_resnet_control_reads_far_from_the_sound_program():
    """The control at a size a test run can hold: float8 inputs and
    weights move the batch variances several times as far from the float32
    reference as the bfloat16 program does."""
    cell = tiny.train_cell(TRAIN[-1])
    cfg = dict(cell.config, stages=[[8, 2, 1], [16, 2, 2], [32, 2, 2]],
               image=64)
    ref = cells.load_module("references", cfg["family"])
    fam = cells.load_module("configs", cfg["family"])
    key = device.seed_key(2 ** 31 + 5)
    w = ref.make_weights(cfg, jax.random.fold_in(key, 1))
    batch = ref.make_batches(cfg, jax.random.fold_in(key, 2), 1, 64)
    net = fam.build(cfg, w)
    net.fit(fam.dataset(*batch[0]))
    want = ref.train_steps(cfg, w, batch)["batch_stats"]
    ctl = ref.train_steps(cfg, w, batch, quant=True)["batch_stats"]
    sound = ref.batch_var_err(fam.batch_stats(net, cfg, w), want)
    low = ref.batch_var_err(ctl, want)
    assert low > 3 * sound, (low, sound)


@pytest.mark.parametrize("name", SERVE)
def test_serving_driver_runs_and_is_correct(name, tmp_path):
    cell = tiny.serve_cell(name, tmp_path)
    cell.control = True
    line = run.execute(cell)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 5
    assert set(line["metrics"]) == set(cell.workload["end_to_end"])


def test_a_token_altered_where_it_is_produced_is_not_correct(tmp_path,
                                                             monkeypatch):
    from deeplearning4j_tpu.remote import ContinuousBatcher
    real = ContinuousBatcher._emit

    def emit(self, seq, tok):
        # every fifth token of a sequence is replaced by its neighbour id
        if len(seq.emitted) % 5 == 4:
            tok = (tok + 1) % self.lm.config.vocabSize
        return real(self, seq, tok)
    monkeypatch.setattr(ContinuousBatcher, "_emit", emit)
    line = run.execute(tiny.serve_cell(SERVE[0], tmp_path))
    assert line["correct"] is False


def test_gpt2_control_reads_above_the_sound_program():
    """At every position of one sequence the float32 reference's own best
    token has no gap; bfloat16, teacher-forced on the same tokens, puts
    another token first at some positions, and ``served_gaps`` reads
    that."""
    cfg = dict(cells.load_config("gpt2_xl"), n_layer=4, n_embd=128,
               n_head=4, n_positions=256)
    ref = cells.load_module("references", cfg["family"])
    w = ref.make_weights(cfg, device.seed_key(2 ** 31 + 5))
    seq = [int(t) for t in jax.random.randint(
        jax.random.PRNGKey(0), (256,), 0, cfg["vocab_size"])]
    best = [int(t) for t in jnp.argmax(ref.logits(cfg, w, seq, 0), axis=-1)]
    # one request per position: a prompt and the one token served after it
    sound, low = [], []
    for n in (64, 128, 192, 256):
        g = ref.served_gaps(cfg, w, seq[:n], [best[n - 1]], control=True)
        sound += g["served"]
        low += g["control"]
    assert max(sound) == 0.0 and min(low) >= 0.0
    full = ref.logits(cfg, w, seq, 0)
    half = ref.logits(cfg, w, seq, 0, jnp.bfloat16)
    rows = jnp.arange(len(seq))
    gap = jnp.max(full, axis=-1) - full[rows, jnp.argmax(half, axis=-1)]
    assert float(jnp.max(gap)) > 0.0
