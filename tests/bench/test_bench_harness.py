"""The benchmark harness on the CPU: traffic, metric arithmetic, discovery
by name, and refusal without a chip."""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import loadgen  # noqa: E402
from bench import run as bench_run  # noqa: E402
from bench.record import Req, Run  # noqa: E402

CHAT = json.loads((ROOT / "bench/traffic/chat_smollm.json").read_text())
AGENT = json.loads((ROOT / "bench/traffic/agent_closed64.json").read_text())
BIG_SEED = 2**31 + 12345


# ----------------------------------------------------------------- traffic
def test_schedule_is_a_function_of_the_seed():
    a = loadgen.schedule(CHAT, 30.0, BIG_SEED)
    b = loadgen.schedule(CHAT, 30.0, BIG_SEED)
    c = loadgen.schedule(CHAT, 30.0, BIG_SEED + 1)
    assert a == b
    assert a != c
    assert np.array_equal(loadgen.prompt(BIG_SEED, 7, 40, 1000),
                          loadgen.prompt(BIG_SEED, 7, 40, 1000))
    assert not np.array_equal(loadgen.prompt(BIG_SEED, 7, 40, 1000),
                              loadgen.prompt(BIG_SEED, 8, 40, 1000))


def test_every_seed_gets_the_same_work_in_another_order():
    n = 2000
    rng1, rng2 = np.random.default_rng(1), np.random.default_rng(2)
    a = loadgen.lengths(CHAT["prompt_len"], n, rng1)
    b = loadgen.lengths(CHAT["prompt_len"], n, rng2)
    assert sorted(a) == sorted(b) and list(a) != list(b)
    g1 = loadgen.gaps(CHAT["arrival"], n, rng1)
    g2 = loadgen.gaps(CHAT["arrival"], n, rng2)
    assert np.allclose(np.sort(g1), np.sort(g2))


@pytest.mark.parametrize("spec_key", ["prompt_len", "output_len"])
def test_lengths_follow_the_distribution_and_clip(spec_key):
    spec = CHAT[spec_key]
    x = loadgen.lengths(spec, 4000, np.random.default_rng(0))
    assert x.min() >= spec["min"] and x.max() <= spec["max"]
    # Stratified quantiles: the sample median is the distribution's.
    assert np.median(x) == pytest.approx(spec["median"], rel=0.01)
    # The clip is reached exactly as often as the tail beyond it holds.
    from statistics import NormalDist
    tail = 1 - NormalDist().cdf(np.log(spec["max"] / spec["median"])
                                / spec["sigma"])
    assert np.mean(x == spec["max"]) == pytest.approx(tail, abs=0.005)


def test_arrivals_poisson_rate_and_bursty_cv():
    n = 4000
    g = loadgen.gaps({"rate_per_s": 20.0}, n, np.random.default_rng(0))
    assert np.mean(g) == pytest.approx(1 / 20.0, rel=0.01)
    # Exponential gaps: standard deviation equal to the mean.
    assert np.std(g) / np.mean(g) == pytest.approx(1.0, rel=0.05)


def test_open_schedule_covers_warm_in_and_window_only():
    plans = loadgen.schedule(CHAT, 20.0, 5)
    span = CHAT["warm_in_s"] + 20.0
    assert all(0 <= p.t < span for p in plans)
    rate = CHAT["arrival"]["rate_per_s"]
    assert len(plans) == pytest.approx(rate * span, rel=0.05)


def test_every_seed_sends_the_window_the_same_requests():
    def window(seed):
        plans = loadgen.schedule(CHAT, 51.0, seed)
        w = [p for p in plans if p.t >= CHAT["warm_in_s"]]
        return (sorted(p.prompt_len for p in w), sorted(p.max_new for p in w),
                [p.t for p in w])

    a, b = window(BIG_SEED), window(BIG_SEED + 7)
    assert len(a[2]) == round(CHAT["arrival"]["rate_per_s"] * 51.0)
    assert a[:2] == b[:2] and a[2] != b[2]
    assert CHAT["warm_in_s"] <= min(a[2]) and max(a[2]) < CHAT["warm_in_s"] + 51


def test_closed_schedule_gives_every_client_a_queue():
    plans = loadgen.schedule(AGENT, 30.0, 5)
    clients = {p.client for p in plans}
    assert clients == set(range(AGENT["clients"]))
    assert loadgen.client_start(AGENT, AGENT["clients"] - 1) < AGENT[
        "stagger_s"]


# ------------------------------------------------------------------ client
class RequestCancelled(Exception):
    pass


class _FakeHandle:
    END = object()

    def __init__(self, uid, n):
        import queue
        self.uid, self.n, self.q, self.err = uid, n, queue.Queue(), None

    def stream(self, timeout=None):
        import queue
        while True:
            try:
                tok = self.q.get(timeout=timeout)
            except queue.Empty:
                raise TimeoutError from None
            if tok is self.END:
                if self.err is not None:
                    raise self.err
                return
            yield tok


class _FakeServer:
    """Gives every live request one token each TICK seconds."""

    TICK = 0.01

    def __init__(self):
        import threading
        self.live, self.lock = {}, threading.Lock()
        self.stop = threading.Event()
        self.thread = threading.Thread(target=self._tick, daemon=True)
        self.thread.start()

    def _tick(self):
        import time
        while not self.stop.wait(self.TICK):
            with self.lock:
                for uid, (h, k) in list(self.live.items()):
                    h.q.put(k)
                    if k + 1 == h.n:
                        h.q.put(h.END)
                        del self.live[uid]
                    else:
                        self.live[uid] = (h, k + 1)
            time.sleep(0)

    def submit(self, toks, max_new, uid):
        h = _FakeHandle(uid, max_new)
        with self.lock:
            self.live[uid] = (h, 0)
        return h

    def cancel(self, h):
        with self.lock:
            if h.uid in self.live:
                del self.live[h.uid]
                h.err = RequestCancelled()
                h.q.put(h.END)
                return True
        return False


def test_client_sends_on_time_and_stamps_every_token():
    import time
    srv = _FakeServer()
    plans = [loadgen.Planned(i, 0.02 * i, 4, 5) for i in range(10)]
    t0 = time.perf_counter()
    client = bench_run.Client(srv, BIG_SEED, 100).start(
        {"loop": "open"}, plans, t0, t0 + 1.0)
    time.sleep(0.5)
    assert client.finish(5.0) == 0
    srv.stop.set()
    assert [r.idx for r in client.reqs] == list(range(10))
    for r, p in zip(client.reqs, plans):
        assert r.sched == pytest.approx(t0 + p.t)
        assert 0 <= r.sent - r.sched < 0.05
        assert r.done and r.tokens == list(range(5))
        assert r.times == sorted(r.times) and r.first > r.sent


def test_closed_client_sends_the_next_request_when_the_last_ends():
    import time
    srv = _FakeServer()
    traffic = {"loop": "closed", "clients": 2, "stagger_s": 0.0}
    plans = [loadgen.Planned(i, -1.0, 4, 3, client=i % 2)
             for i in range(40)]
    t0 = time.perf_counter()
    client = bench_run.Client(srv, BIG_SEED, 100).start(
        traffic, plans, t0, t0 + 0.4)
    time.sleep(0.6)
    client.finish(5.0)
    srv.stop.set()
    for c in (0, 1):
        mine = [r for r in client.reqs if r.idx % 2 == c]
        assert len(mine) >= 4
        assert all(r.sched < t0 + 0.4 for r in mine)
        for a, b in zip(mine, mine[1:]):
            # Sent in the pass that took the last token of the one before.
            assert 0 <= b.sched - a.times[-1] < 0.05


def test_client_finish_cancels_what_is_in_flight():
    import time
    srv = _FakeServer()
    plans = [loadgen.Planned(i, 0.0, 4, 10_000) for i in range(3)]
    t0 = time.perf_counter()
    client = bench_run.Client(srv, BIG_SEED, 100).start(
        {"loop": "open"}, plans, t0, t0 + 1.0)
    time.sleep(0.1)
    assert client.finish(5.0) == 3
    srv.stop.set()
    assert all(r.cancelled and r.error is None and not r.done
               and r.tokens for r in client.reqs)


# ------------------------------------------------------- metric arithmetic
def _run(reqs, **kw):
    base = dict(reqs=reqs, t_open=10.0, t_close=20.0, deadline=25.0,
                setup_s=3.0,
                model=json.loads(
                    (ROOT / "bench/configs/smollm-135m.json").read_text()),
                batch_slots=4, tp=1, device_kind="TPU v5 lite",
                delta={"ticks": 100.0, "decode_tokens": 300.0,
                       "admitted": 10.0, "prefill_s": 0.5, "decode_s": 2.0})
    base.update(kw)
    return Run(**base)


def _req(idx, sched, times, done=True, error=None, n=None):
    r = Req(idx=idx, sched=sched, prompt_len=8, max_new=n or len(times),
            sent=sched + 0.001, times=list(times),
            tokens=list(range(len(times))), error=error, done=done)
    return r


def _metric(name, run):
    entry = next(m for m in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["end_to_end"]
        + json.loads((ROOT / "BENCHMARK.json").read_text())["per_layer"]
        if m["name"] == name)
    return bench_run.metric_module(entry).compute(run)


def test_rate_is_over_the_whole_window():
    # 30 tokens inside the window of 10 s, 5 outside it.
    reqs = [_req(0, 9.0, [9.5] * 5 + [11.0 + 0.1 * i for i in range(30)])]
    assert _metric("out_tok_per_s", _run(reqs)) == pytest.approx(3.0)


def test_tail_is_over_all_requests_and_failures_count_as_the_deadline():
    ok = [_req(i, 10.0 + i * 0.1, [10.1 + i * 0.1, 10.2 + i * 0.1])
          for i in range(19)]
    failed = _req(99, 12.0, [], done=False, error="RequestFailed")
    run = _run(ok + [failed])
    ttfts = [0.1] * 19 + [25.0 - 12.0]
    assert _metric("ttft_p95_s", run) == pytest.approx(
        float(np.percentile(ttfts, 95)))
    # A request with no first token by the deadline is the deadline too.
    stuck = _req(98, 13.0, [], done=False)
    assert run.ttft(stuck) == pytest.approx(12.0)
    # Cut by the run at the drain deadline after its first token: its
    # TTFT stands.
    cut = _req(96, 12.0, [12.2], done=False)
    cut.cancelled = True
    assert run.ttft(cut) == pytest.approx(0.2)
    # A request due before the window is not counted.
    early = _req(97, 9.0, [30.0])
    assert _metric("ttft_p95_s", _run(ok + [failed, early])) == \
        _metric("ttft_p95_s", run)


def _paced(gaps, start=10.0):
    return list(start + np.cumsum([0.0] + list(gaps)))


def test_a_stall_in_the_window_moves_the_itl_tail():
    steady = [0.02] * 400
    calm = _run([_req(i, 10.0, _paced(steady)) for i in range(4)])
    assert _metric("itl_p95_s", calm) == pytest.approx(0.02)
    # Every 10th tick stalls 0.3 s for every request: a tenth of the gaps.
    stalled = [0.3 if k % 10 == 9 else 0.02 for k in range(400)]
    hit = _run([_req(i, 10.0, _paced(stalled)) for i in range(4)])
    assert _metric("itl_p95_s", hit) == pytest.approx(0.3)
    # The same stalls before the window opens do not count.
    early = _run([_req(i, 1.0, _paced(stalled[:40], start=1.0)
                       + _paced(steady, start=10.0)) for i in range(4)])
    assert _metric("itl_p95_s", early) == pytest.approx(0.02)


def test_counter_ratios():
    run = _run([])
    assert _metric("slot_occupancy", run) == pytest.approx(300 / (100 * 4))
    assert _metric("decode_call_ms", run) == pytest.approx(20.0)
    assert _metric("prefill_call_ms", run) == pytest.approx(50.0)
    # Trace readers return nothing without a trace: no zero shares.
    for name in ("mfu", "paged_attn_roofline", "device_idle_frac"):
        assert _metric(name, run) is None


def test_trace_readers_with_a_reduced_trace():
    # One request decoding 10 tokens inside the traced part.
    r = _req(0, 10.0, [10.0 + 0.01 * k for k in range(11)])
    run = _run([r], trace={"window_s": 1.0, "busy_s": 0.25, "chips": 1,
                           "kernel_s": {"paged_attn": 1e-3}},
               t_trace=(10.0, 11.0))
    assert _metric("device_idle_frac", run) == pytest.approx(0.75)
    from bench import work
    m = run.model
    rows = sum(8 + j for j in range(1, 11))
    want = rows * work.kv_row_bytes(m) * 30 / 819e9 / 1e-3 * 100
    assert _metric("paged_attn_roofline", run) == pytest.approx(want)
    flops = work.prefill_flops(m, 8) + sum(
        work.decode_token_flops(m, 8 + j) for j in range(1, 11))
    assert _metric("mfu", run) == pytest.approx(100 * flops / 197e12)


# ------------------------------------------------------------- discovery
def _copy_bench(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")


def test_new_metric_config_and_traffic_files_are_found_by_name(tmp_path):
    _copy_bench(tmp_path)
    (tmp_path / "bench/metrics/admitted_per_s.py").write_text(
        'NAME, UNIT, SOURCE = "admitted_per_s", "1/s", "program_counter"\n'
        'LAYER, MOVES = "scheduler", "ttft_p95_s"\n'
        "def compute(run):\n"
        "    return run.delta['admitted'] / run.seconds\n")
    cfg = json.loads((ROOT / "bench/configs/smollm-135m.json").read_text())
    cfg["serve"]["batch_slots"] = 64
    (tmp_path / "bench/configs/smollm-135m-b64.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench/traffic/burst.json").write_text(json.dumps(
        dict(CHAT, arrival={"rate_per_s": 5.0})))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "smollm-135m-b64",
                             "source": cfg["source"],
                             "file": "bench/configs/smollm-135m-b64.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "smollm-135m-b64.burst",
                               "config": "smollm-135m-b64",
                               "traffic": "burst", "chips": 1, "why": "t"})
    bench["per_layer"].append({"name": "admitted_per_s", "unit": "1/s",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "scheduler", "moves": "ttft_p95_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    code = (
        "import json\n"
        "from bench import run\n"
        "c = run.load_cell('smollm-135m-b64.burst')\n"
        "mods = [run.metric_module(m).NAME for m in c['per_layer']]\n"
        "print(json.dumps([c['config']['serve']['batch_slots'],\n"
        "                  c['traffic']['arrival']['rate_per_s'], mods]))\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                         capture_output=True, text=True, timeout=60,
                         env=dict(os.environ, PYTHONPATH=str(tmp_path)))
    assert out.returncode == 0, out.stderr
    slots, rate, mods = json.loads(out.stdout.strip().splitlines()[-1])
    assert (slots, rate) == (64, 5.0)
    assert "admitted_per_s" in mods


def test_harness_names_no_cell_config_or_metric():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = ([w["name"] for w in bench["workloads"]]
             + [c["name"] for c in bench["configs"]]
             + [w["traffic"] for w in bench["workloads"]]
             + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]])
    for f in ("run.py", "loadgen.py", "record.py", "engine.py", "trace.py",
              "work.py", "reference.py", "weights.py", "calibrate.py"):
        text = (ROOT / "bench" / f).read_text()
        for n in names:
            assert f'"{n}"' not in text and f"'{n}'" not in text, (f, n)


# ------------------------------------------------------------ no chip
def _run_cli(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "smollm-135m.chat",
         "--seed", str(BIG_SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=180, env=env)


def test_run_exits_nonzero_without_a_tpu(tmp_path):
    cache = tmp_path / "cache"
    out = _run_cli(ROOT, {"JAX_COMPILATION_CACHE_DIR": str(cache)})
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"correct"' not in out.stdout


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    _copy_bench(tmp_path)
    out = _run_cli(tmp_path, {"JAX_COMPILATION_CACHE_DIR":
                              str(tmp_path / "cache")})
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
