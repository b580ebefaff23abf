"""The benchmark's yardstick: trace reduction, work counts, peaks."""
from __future__ import annotations

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace, work  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"
SMOLLM = json.loads((ROOT / "bench/configs/smollm-135m.json").read_text())


def _events(*spans):
    """A device plane's op events from (name, start_ns, end_ns)."""
    return [(n, float(s), float(e), n) for n, s, e in spans]


def test_short_names():
    assert trace.short_name(
        "%paged_gqa_decode_attn.7 = bf16[128,3,3,64] custom-call(%a)"
    ) == "paged_gqa_decode_attn.7"
    assert trace.CONTAINER.match("while.3")
    assert not trace.CONTAINER.match("fusion.1")


def test_union_counts_overlap_once():
    u = trace.union([(0, 10), (5, 15), (20, 30), (30, 31), (40, 40)])
    assert u == [(0, 15), (20, 31)]
    assert trace.covered_ns([(0, 10), (5, 15), (20, 30)]) == 25
    # A span nested inside another adds nothing.
    assert trace.covered_ns([(0, 100), (10, 20), (30, 40)]) == 100


def test_gaps_longest_first_and_idle_is_one_minus_busy():
    g = trace.gaps([(0, 10), (12, 20), (50, 60)])
    assert g == [(20, 50), (10, 12)]
    assert trace.idle_frac(0.25, 1.0) == pytest.approx(0.75)


def test_reduce_averages_chips_and_sums_kernels():
    events = {
        "devices": {
            "/device:TPU:0": _events(("while.3", 0, 1000),
                                     ("fusion.1", 0, 400),
                                     ("paged_gqa_decode_attn.7", 300, 600),
                                     ("copy.2", 800, 1000)),
            "/device:TPU:1": _events(("fusion.1", 0, 200)),
        },
        "host": [("PjitFunction(step)", 550, 900), ("sleep", 600, 700)],
    }
    r = trace.reduce(events, window_s=1e-6,
                     kernels={"attn": r"paged_gqa", "none": r"nope"})
    # Chip 0 busy 1000 ns (inside its while loop), chip 1 200 ns.
    assert r["busy_s"] == pytest.approx((1000e-9 + 200e-9) / 2)
    assert r["chips"] == 2
    assert r["kernel_s"]["attn"] == pytest.approx(300e-9 / 2)
    assert r["kernel_s"]["none"] == 0.0
    # The while loop holds the other ops: not an op of its own.
    assert [n for n, _ in r["device_ops"]] == [
        "fusion.1", "paged_gqa_decode_attn.7", "copy.2"]
    assert r["idle_gaps"] == []
    del events["devices"]["/device:TPU:0"][0]
    r = trace.reduce(events, window_s=1e-6, kernels={})
    # Without it, chip 0's one idle gap (600..800) is covered mostly by
    # the jit call.
    assert r["idle_gaps"] == [["PjitFunction(step)", pytest.approx(200e-9)]]


def test_recorded_chip_trace_has_device_plane_and_kernels():
    path = DATA / "smollm_decode.xplane.pb"
    ev = trace.load(str(path))
    assert list(ev["devices"]) == ["/device:TPU:0"]
    names = {n for n, *_ in ev["devices"]["/device:TPU:0"]}
    assert any(n.startswith("paged_gqa_decode_attn") for n in names)
    evs = ev["devices"]["/device:TPU:0"]
    assert trace.kernel_seconds(evs, r"paged_gqa") > 0
    r = trace.reduce(ev, window_s=0.1, kernels={"attn": r"paged_gqa"})
    assert 0 < r["busy_s"]
    assert r["kernel_s"]["attn"] > 0


def test_work_counts_smollm_by_hand():
    m = SMOLLM
    # One layer: q 576x576, k and v 576x192, o 576x576, MLP 3 x 576x1536.
    per_layer = 331_776 + 2 * 110_592 + 331_776 + 3 * 884_736
    assert work.layer_matmul_params(m) == per_layer == 3_538_944
    # Tied embedding: 30 layers + one 49,152 x 576 table = 134.5 M.
    assert work.param_count(m) == 30 * per_layer + 28_311_552
    # KV row: 2 (k, v) x 3 heads x 64 x 2 bytes per layer.
    assert work.kv_row_bytes(m) == 768
    assert work.decode_attn_bytes(m, 100) == 100 * 768 * 30
    # A token at context 100: weights twice, head twice, attention
    # 4 x 9 heads x 64 x 100 per layer.
    head = 576 * 49_152
    assert work.decode_token_flops(m, 100) == (
        2 * 30 * per_layer + 2 * head + 30 * 4 * 9 * 64 * 100)
    # Causal prefill of 3 tokens attends over 1 + 2 + 3 rows.
    assert work.prefill_flops(m, 3) == (
        2 * 30 * per_layer * 3 + 2 * head + 30 * 4 * 9 * 64 * 6)


def test_peaks_known_and_unknown_kind():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v9 imaginary")
