"""The tensor-parallel cell ``mistral-nemo-12b-tp4.chat`` on the CPU: its
configuration against the program's own, its memory reckoned per chip,
a tiny cell of the same shape ratios served on a forced (1, 4) mesh and
checked against the plain reference, and the reader of the collectives'
share of device time."""
from __future__ import annotations

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import engine, faults, trace, weights  # noqa: E402
from bench import run as bench_run  # noqa: E402
from bench.metrics import collective_frac  # noqa: E402
from bench.record import Req, Run  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "mistral-nemo-12b-tp4.chat"
CONF = next(c for c in BENCH["configs"] if c["name"] == "mistral-nemo-12b-tp4")
CONFIG = json.loads((ROOT / CONF["file"]).read_text())
TRAFFIC = json.loads((ROOT / "bench/traffic/chat_nemo_tp4.json").read_text())
SEED = 2**31 + 1531
GIB = 2**30

# Sound runs of the tiny cell below read gaps of 0 to 0.03 on the CPU
# (on the mesh and on one device alike, over five seeds); without the
# model-axis all-reduce they read 4.9 to 6.0.
TINY_LIMIT = 0.1


# ---------------------------------------------------------- configuration
def test_config_is_the_published_model_whole():
    from repro.configs import get_config

    assert engine.arch_config(CONFIG) == get_config("mistral-nemo-12b")
    assert CONFIG["num_hidden_layers"] == 40
    assert CONFIG["reduced"] == [] == CONF["reduced"]
    assert "published" not in CONFIG
    mesh = CONFIG["serve"]["mesh"]
    assert CONFIG["chips"] == mesh[0] * mesh[1] == 4
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == CONFIG["chips"]
    assert cell["traffic"] == "chat_nemo_tp4"


def _per_chip_bytes(tree, specs, mesh) -> int:
    """Bytes one chip holds of ``tree`` laid out by ``specs``."""
    import jax
    import numpy as np

    def one(a, spec):
        split = 1
        for ax in spec:
            for name in (ax if isinstance(ax, tuple) else (ax,)):
                if name is not None:
                    split *= mesh.shape[name]
        return int(np.prod(a.shape)) * a.dtype.itemsize // split

    return sum(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        one, tree, specs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))))


@pytest.mark.mesh
def test_reckoned_bytes_per_chip_fit_one_v5e(cpu_mesh):
    """The chip's share of the weights, plus two copies of its share of
    the pool (the decode step's input and output), under 16 GiB."""
    import jax

    from repro.models import model as model_lib
    from repro.parallel import sharding as shard_lib
    from repro.runtime.paging import blocks_needed

    cfg = engine.arch_config(CONFIG)
    sc = CONFIG["serve"]
    mesh = shard_lib.serve_mesh(tuple(sc["mesh"]))
    params = weights.abstract(CONFIG)
    w = _per_chip_bytes(params, shard_lib.serve_param_specs(params, mesh),
                        mesh)
    blocks = sc["batch_slots"] * blocks_needed(sc["max_len"],
                                               sc["kv_block_size"]) + 1
    pool = jax.eval_shape(lambda: model_lib.init_paged_caches(
        cfg, sc["batch_slots"], blocks, sc["kv_block_size"]))
    p = _per_chip_bytes(pool, shard_lib.serve_paged_cache_specs(cfg), mesh)
    # 40 layers x 272.6 M / 4 + embedding and head whole, in bf16.
    assert w == pytest.approx(8.14e9, rel=0.01)
    # 4,097 blocks x 16 rows x 40 layers x 2 of 8 KV heads x 128 x 2 x 2 B.
    assert p == pytest.approx(2.685e9, rel=0.01)
    assert w + 2 * p < 16 * GIB


# ----------------------------------------------------- tiny tp4 cell, CPU
def tiny_tp4_cell():
    """Nemo's ratios at a size a test run holds: 32 query heads and 8 KV
    heads (2 a shard, group 4), head_dim x heads (256) != hidden (96),
    served on a (1, 4) mesh with the committed sample size."""
    config = json.loads(json.dumps(CONFIG))
    config.update(hidden_size=96, intermediate_size=512, head_dim=8,
                  num_hidden_layers=2, vocab_size=256)
    config["serve"].update(batch_slots=8, max_len=64)
    config["correct"]["max_logit_gap"] = TINY_LIMIT
    traffic = dict(
        TRAFFIC, arrival={"rate_per_s": 20.0},
        prompt_len={"dist": "lognormal", "median": 12, "sigma": 0.5,
                    "min": 4, "max": 40},
        output_len={"dist": "lognormal", "median": 8, "sigma": 0.5,
                    "min": 4, "max": 16},
        warm_in_s=0.5, drain_s=30.0)
    return {"workload": {"name": "tiny-tp4"}, "config": config,
            "traffic": traffic, "end_to_end": BENCH["end_to_end"],
            "per_layer": BENCH["per_layer"]}


def _run_tiny():
    import jax

    return bench_run.run_cell(tiny_tp4_cell(), SEED, 2.0, False,
                              jax.devices())


@pytest.mark.mesh
def test_tiny_tp4_cell_is_correct(cpu_mesh):
    res = _run_tiny()
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["checks"]["checked_requests"]["value"] == 16


@pytest.mark.mesh
def test_tiny_tp4_cell_without_the_model_allreduce_is_not(cpu_mesh,
                                                           monkeypatch):
    faults.plant("no_model_allreduce", monkeypatch.setattr)
    res = _run_tiny()
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > TINY_LIMIT


# ------------------------------------------------------- collective_frac
def _ev(name, s, e):
    """A device op event as ``trace.load`` gives it: the instruction's
    short name and its whole text."""
    return (trace.short_name(name), float(s), float(e), name)


def _traced_run(devices=None, tr=None):
    if tr is None:
        tr = trace.reduce({"devices": devices, "host": []}, window_s=1e-6,
                          kernels=collective_frac.KERNELS)
    return Run(reqs=[Req(idx=0, sched=0.0, prompt_len=8, max_new=1)],
               t_open=0.0, t_close=1.0, deadline=1.0, setup_s=1.0,
               model=CONFIG, batch_slots=32, tp=4,
               device_kind="TPU v5 lite", delta={}, trace=tr,
               t_trace=(0.0, 1.0))


def test_collective_frac_is_collective_time_over_busy_time():
    ar = ("%psum.40 = bf16[32,1,5120]{2,0,1:T(8,128)(2,1)S(1)} "
          "all-reduce(%fusion.122), channel_id=1, replica_groups={{0,1,2,3}}")
    reader = ("%fusion.9 = bf16[32,1,5120]{2,0,1} fusion(bf16[32,1,5120] "
              "%psum.40), kind=kLoop, calls=%fused_computation.9")
    start = ("%all-gather-start.2 = (f32[2,4], f32[8,4]) "
             "all-gather-start(f32[2,4] %x), dimensions={0}")
    done = ("%all-gather-done.2 = f32[8,4] "
            "all-gather-done((f32[2,4], f32[8,4]) %all-gather-start.2)")
    run = _traced_run({
        # Chip 0: busy 0..1000 ns inside a loop; collectives 100 + 50.
        "/device:TPU:0": [_ev("while.3", 0, 1000), _ev(reader, 0, 300),
                          _ev(ar, 300, 400), _ev(start, 400, 420),
                          _ev(done, 600, 630), _ev("%copy.1 = copy()",
                                                   700, 1000)],
        # Chip 1: busy 600 ns, 200 of them in the all-reduce.
        "/device:TPU:1": [_ev(reader, 0, 400), _ev(ar, 400, 600)],
    })
    collective = (100 + 20 + 30) + 200
    busy = 1000 + 600
    assert collective_frac.compute(run) == pytest.approx(collective / busy)


def test_collective_frac_reports_nothing_without_collectives():
    run = _traced_run({"/device:TPU:0": [
        _ev("%fusion.9 = bf16[8] fusion(bf16[8] %psum.40)", 0, 100),
        _ev("%copy-start.1 = (s32[8], s32[8]) copy-start(s32[8] %a)",
            100, 200)]})
    assert collective_frac.compute(run) is None
    run.trace = None
    assert collective_frac.compute(run) is None


def test_collective_frac_on_the_recorded_one_chip_trace_is_none():
    ev = trace.load(str(ROOT / "tests/bench/data/smollm_decode.xplane.pb"))
    tr = trace.reduce(ev, window_s=0.1, kernels=collective_frac.KERNELS)
    assert tr["kernel_s"]["collective"] == 0.0
    assert collective_frac.compute(_traced_run(tr=tr)) is None
