"""The per-layer metrics read from the engine's host-time and KV counters
(``ServeMetrics`` fields fed by ``repro.runtime.metrics.span``)."""
from __future__ import annotations

import importlib
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.record import Run  # noqa: E402

SMOLLM = json.loads((ROOT / "bench/configs/smollm-135m.json").read_text())
NAMES = ("queue_wait_ms", "kv_unused_frac", "readback_sample_ms",
         "step_host_ms")
DELTA = {"ticks": 100.0, "admitted": 10.0, "queue_wait_s": 2.0,
         "kv_live_row_ticks": 100 * 256.0,
         "kv_pool_row_ticks": 100 * 64 * 16.0, "step_s": 8.0,
         "prefill_s": 1.0, "decode_wait_s": 3.0, "readback_s": 2.0,
         "sample_s": 0.5}


def _run(delta=DELTA):
    return Run(reqs=[], t_open=0.0, t_close=10.0, deadline=15.0,
               setup_s=1.0, model=SMOLLM, batch_slots=4, tp=1,
               device_kind="TPU v5 lite", delta=dict(delta))


def _compute(name, run):
    return importlib.import_module(f"bench.metrics.{name}").compute(run)


@pytest.mark.parametrize("name, want", [
    ("queue_wait_ms", 1e3 * 2.0 / 10),
    # 256 live rows a tick of a 64 x 16-row pool.
    ("kv_unused_frac", 1 - 256 / 1024),
    ("readback_sample_ms", 1e3 * 2.5 / 100),
    ("step_host_ms", 1e3 * (8.0 - 1.0 - 3.0 - 2.0 - 0.5) / 100),
])
def test_engine_metric_on_a_synthetic_run(name, want):
    assert _compute(name, _run()) == pytest.approx(want)


@pytest.mark.parametrize("name, zero", [
    ("queue_wait_ms", {"admitted": 0.0}),
    ("kv_unused_frac", {"kv_pool_row_ticks": 0.0}),
    ("readback_sample_ms", {"ticks": 0.0}),
    ("step_host_ms", {"ticks": 0.0}),
])
def test_engine_metric_is_none_without_a_denominator(name, zero):
    assert _compute(name, _run(dict(DELTA, **zero))) is None


@pytest.mark.parametrize("name", NAMES)
def test_engine_metric_is_none_for_a_program_without_spans(name):
    # A program that keeps none of the new counters.
    old = {k: v for k, v in DELTA.items()
           if k in ("ticks", "admitted", "prefill_s")}
    assert _compute(name, _run(old)) is None


@pytest.mark.parametrize("name", NAMES)
def test_engine_metric_reads_counters_the_engine_keeps(name):
    # The harness's delta holds every ServeMetrics field; each counter a
    # metric reads must be one of them.
    from repro.runtime.metrics import ServeMetrics

    def reads(key):
        try:
            return _compute(name, _run(
                {k: v for k, v in DELTA.items() if k != key})) is None
        except KeyError:
            return True

    read = {k for k in DELTA if reads(k)}
    assert read and read <= set(ServeMetrics().as_dict())


@pytest.mark.parametrize("name", NAMES)
def test_engine_metric_matches_its_benchmark_entry(name):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(e for e in spec["per_layer"] if e["name"] == name)
    mod = importlib.import_module(f"bench.metrics.{name}")
    assert (mod.NAME, mod.UNIT, mod.SOURCE, mod.LAYER, mod.MOVES) == (
        entry["name"], entry["unit"], entry["source"], entry["layer"],
        entry["moves"])
    assert entry["workloads"] == [w["name"] for w in spec["workloads"]]
