"""The comparison that decides ``correct``, driven end to end on the CPU
at a size a test run holds: the harness's look for a chip is skipped and
everything else of a run happens. A sound run passes; the float8 control
and each fault a served cell can have, planted under the timed path,
fail."""
from __future__ import annotations

import copy
import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import faults  # noqa: E402
from bench import run as bench_run  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
SEED = 2**31 + 977

# The widest gap sound runs of this tiny bf16 model read on the CPU is
# about 2e-3; the float8 control reads 4e-2 to 9e-2; a planted fault
# reads 1e-1 or more.
TINY_LIMIT = 0.02


def tiny_cell(mesh=None, closed=False):
    config = json.loads((ROOT / "bench/configs/smollm-135m.json").read_text())
    config.update(hidden_size=64, intermediate_size=256,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                  num_hidden_layers=2, vocab_size=256)
    config["serve"].update(batch_slots=8, max_len=64)
    if mesh:
        config["serve"]["mesh"] = list(mesh)
        config["chips"] = mesh[0] * mesh[1]
    # The committed sample size stands: only the limit is the tiny
    # model's.
    config["correct"]["max_logit_gap"] = TINY_LIMIT
    traffic = json.loads(
        (ROOT / "bench/traffic/chat_smollm.json").read_text())
    traffic.update(
        arrival={"rate_per_s": 20.0},
        prompt_len={"dist": "lognormal", "median": 12, "sigma": 0.5,
                    "min": 4, "max": 40},
        output_len={"dist": "lognormal", "median": 8, "sigma": 0.5,
                    "min": 4, "max": 16},
        warm_in_s=0.5, drain_s=30.0)
    if closed:  # every slot busy: one client per slot
        traffic.update(loop="closed", clients=8, stagger_s=0.2)
    return {"workload": {"name": "tiny"}, "config": config,
            "traffic": traffic, "end_to_end": BENCH["end_to_end"],
            "per_layer": BENCH["per_layer"]}


def run_tiny(cell=None):
    import jax

    cell = cell or tiny_cell()
    return bench_run.run_cell(cell, SEED, 2.0, False, jax.devices())


@pytest.mark.parametrize("closed", [False, True])
def test_sound_run_is_correct_and_reports_its_metrics(closed):
    res = run_tiny(tiny_cell(closed=closed))
    assert res["correct"] is True
    assert res["checks"]["logit_gap"]["value"] <= TINY_LIMIT
    assert res["failed"] == 0 and res["attempted"] > 0
    names = {m["name"] for m in BENCH["end_to_end"]}
    assert set(res["metrics"]) == names
    assert list(res)[-1] == "checks"


def test_float8_control_fails_the_limit():
    import jax

    cell = tiny_cell()
    eng = bench_run.Engine(cell, SEED, jax.devices())
    run = eng.serve(cell["traffic"], 2.0, False)
    eng.close()
    ok = bench_run.check(cell, run, SEED, control=True)
    assert ok["gap"] <= TINY_LIMIT < ok["control_gap"]


def test_fault_step_returns_its_state_unchanged(monkeypatch):
    faults.plant("state_unchanged", monkeypatch.setattr)
    assert run_tiny()["correct"] is False


def test_fault_half_the_batch_left_out(monkeypatch):
    faults.plant("half_batch", monkeypatch.setattr)
    assert run_tiny(tiny_cell(closed=True))["correct"] is False


def test_fault_token_altered_where_produced(monkeypatch):
    faults.plant("token_altered", monkeypatch.setattr)
    assert run_tiny()["correct"] is False


@pytest.mark.mesh
def test_fault_exchange_between_chips_left_out(monkeypatch, cpu_mesh):
    import jax

    sound = run_tiny(tiny_cell(mesh=(1, 2)))
    assert sound["correct"] is True
    faults.plant("no_model_allreduce", monkeypatch.setattr)
    assert run_tiny(tiny_cell(mesh=(1, 2)))["correct"] is False


def test_sample_takes_the_longest_and_enough_tokens():
    from bench.record import Req

    reqs = [Req(idx=i, sched=0.0, prompt_len=10, max_new=5 + i,
                done=True) for i in range(40)]
    reqs.append(Req(idx=99, sched=0.0, prompt_len=10, max_new=300,
                    done=False))
    picked = bench_run.sample(reqs, 16, SEED)
    assert picked[0].idx == 39 and len(picked) == 16
    assert len({r.idx for r in picked}) == 16
    assert all(r.done for r in picked)
    assert [r.idx for r in picked] == [
        r.idx for r in bench_run.sample(copy.deepcopy(reqs), 16, SEED)]
    # Fewer finished than asked for: all of them, and the check fails.
    assert len(bench_run.sample(reqs[:5], 16, SEED)) == 5


def test_committed_samples_catch_half_the_batch_left_out():
    """With half the slots left out, a sample misses the fault only when
    every request in it sat in a sound slot: one chance in
    2**sample_requests."""
    for cell in BENCH["workloads"]:
        conf = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
        n = json.loads((ROOT / conf["file"]).read_text())[
            "correct"]["sample_requests"]
        assert 0.5 ** n < 1e-4, (cell["name"], n)
