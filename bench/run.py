"""One run of one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration and its traffic mix are found by name:
``BENCHMARK.json`` names the cell's configuration file and traffic mix
(``bench/traffic/<mix>.json``), and each metric is read by
``bench/metrics/<metric>.py``. Nothing here knows a cell by name.

A run: weights made on the device from the seed; the engine built
through ``AsyncServer``; every program the cell's traffic uses warmed
(the end of that is ``setup_s``); traffic from ``bench/loadgen.py``
through ``AsyncServer.submit`` and ``Submission.stream``, every token
timed on the client's clock; ``warm_in_s`` of traffic before the window
opens, then ``--seconds`` measured; with ``--trace 1`` a profiler trace
of the first seconds of the window. After the window: the device's peak
memory, then the program's state freed and a sample of the finished
requests compared with the plain reference (``bench/reference.py``),
which decides ``correct``.

Standard error carries the details; the last line of standard output is
one JSON object. Without a TPU, or with fewer chips than the cell asks
for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
TRACE_SECONDS = 5.0  # traced part of the window, from its opening
JOIN_SECONDS = 60.0  # bound on every wait after the drain deadline
WARM_IDX = 1 << 40  # request ids of warm-up; traffic's count up from 0


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


# ----------------------------------------------------------------- cells
def load_cell(name: str, root: pathlib.Path = ROOT) -> dict:
    """The cell's entry, configuration file, traffic file and metrics."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    wl = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{wl['traffic']}.json").read_text())

    def mine(metric):
        return name in metric.get("workloads", [name])

    return {"workload": wl, "config": config, "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def metric_module(entry: dict):
    """``bench/metrics/<name>.py``, checked against its BENCHMARK entry."""
    mod = importlib.import_module(f"bench.metrics.{entry['name']}")
    for key in ("unit", "source"):
        if getattr(mod, key.upper()) != entry[key]:
            raise ValueError(f"metric {entry['name']}: {key} "
                             f"{getattr(mod, key.upper())!r} in its module, "
                             f"{entry[key]!r} in BENCHMARK.json")
    return mod


# ------------------------------------------------------------ compilation
def use_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else ``<checkout>/.jax_cache``. Every program is kept, so a
    second run of a cell compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Times at which JAX compiled or loaded a program, and cache misses."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax

        self.compiles, self.misses = [], []
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, duration, **_):
        if event == self.COMPILE:
            self.compiles.append(time.perf_counter())

    def _ev(self, event, **_):
        if event == self.MISS:
            self.misses.append(time.perf_counter())

    @staticmethod
    def between(ts, a, b) -> int:
        return sum(a <= t < b for t in ts)


# ------------------------------------------------------------------ client
class Client:
    """The load: one thread that sends every planned request through
    ``AsyncServer.submit`` when it is due and reads the tokens of every
    request in flight through ``Submission.stream``.

    Each pass of the thread sends what is due, then takes the tokens
    that have arrived for each request in flight, stamping each on the
    client's clock as it is taken; then it sleeps until the next send is
    due or ``POLL_S`` has passed. One thread, so the load does not
    compete with itself for the interpreter: a stamp is at most a pass
    late. Open loop: request ``p`` is due at ``t0 + p.t``. Closed loop:
    each client sends its next request in the pass that sees its last
    one end, and none after ``t_close``."""

    POLL_S = 0.002

    def __init__(self, asrv, seed: int, vocab: int):
        self.asrv, self.seed, self.vocab = asrv, seed, vocab
        self.reqs: list = []  # every request sent, in order of sending
        self.live: dict = {}  # idx -> (handle, Req, client)
        self.passes = 0
        self.busy_s = 0.0  # time spent in passes
        self._cut = threading.Event()
        self._thread = None

    # -- called from other threads
    def start(self, traffic: dict, plans, t0: float,
              t_close: float) -> "Client":
        self._thread = threading.Thread(
            target=self._loop, args=(traffic, plans, t0, t_close),
            daemon=True)
        self._thread.start()
        return self

    def finish(self, timeout: float) -> int:
        """Cancel every request still in flight (they count as
        unfinished, not as failed), collect their ends and stop the
        thread. Returns how many were cancelled."""
        self._cut.set()
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise RuntimeError(f"client still has {len(self.live)} "
                               f"requests in flight after {timeout} s")
        return sum(r.cancelled for r in self.reqs)

    def run_now(self, plans, timeout: float) -> list:
        """Send ``plans`` at once, wait until each has ended; returns
        their records."""
        t0 = time.perf_counter()
        self.start({"loop": "open"}, plans, t0, float("inf"))
        end = t0 + timeout
        while time.perf_counter() < end and (
                len(self.reqs) < len(plans) or self.live):
            time.sleep(0.01)
        self.finish(JOIN_SECONDS)
        return list(self.reqs)

    # -- the client's thread
    def _send(self, plan, sched: float, client: int = 0):
        from bench import loadgen
        from bench.record import Req

        toks = loadgen.prompt(self.seed, plan.idx, plan.prompt_len,
                              self.vocab)
        r = Req(idx=plan.idx, sched=sched, prompt_len=len(toks),
                max_new=plan.max_new, prompt=toks)
        self.reqs.append(r)
        try:
            h = self.asrv.submit(toks, max_new=plan.max_new, uid=plan.idx)
        except Exception as e:  # noqa: BLE001 - a refused request is a miss
            r.sent, r.error = time.perf_counter(), f"{type(e).__name__}: {e}"
            return r
        r.sent = time.perf_counter()
        self.live[plan.idx] = (h, r, client)
        return r

    def _take(self, cut: bool) -> list:
        """Tokens that have arrived, for every request in flight; returns
        the clients whose request ended in this pass."""
        ended = []
        for idx, (h, r, client) in list(self.live.items()):
            try:
                for tok in h.stream(timeout=0):
                    r.times.append(time.perf_counter())
                    r.tokens.append(int(tok))
            except TimeoutError:  # in flight, no token waiting
                continue
            except Exception as e:  # noqa: BLE001 - recorded as it is
                if cut and type(e).__name__ == "RequestCancelled":
                    r.cancelled = True
                else:
                    r.error = f"{type(e).__name__}: {e}"
            else:
                r.done = len(r.tokens) == r.max_new
            del self.live[idx]
            ended.append(client)
        return ended

    def _loop(self, traffic: dict, plans, t0: float, t_close: float):
        import heapq

        from bench import loadgen

        closed = traffic["loop"] == "closed"
        if closed:
            queues: dict = {}
            for p in plans:
                queues.setdefault(p.client, []).append(p)
            # (when the client is free, client): its next request is due.
            free = [(t0 + loadgen.client_start(traffic, c), c)
                    for c in sorted(queues)]
            heapq.heapify(free)
            sent = dict.fromkeys(queues, 0)
        else:
            due = sorted(plans, key=lambda p: p.t)
        i = 0

        def send_due(now):
            nonlocal i
            if closed:
                while free and free[0][0] <= now:
                    c = heapq.heappop(free)[1]
                    if now < t_close:
                        q = queues[c]
                        self._send(q[sent[c] % len(q)], now, c)
                        sent[c] += 1
            else:
                while i < len(due) and t0 + due[i].t <= now:
                    self._send(due[i], t0 + due[i].t)
                    i += 1

        cut = False
        while True:
            a = time.perf_counter()
            if self._cut.is_set() and not cut:
                cut = True
                for h, _, _ in self.live.values():
                    self.asrv.cancel(h)
            if not cut:
                send_due(a)
            ended = self._take(cut)
            if closed and ended and not cut:
                now = time.perf_counter()
                for c in ended:
                    heapq.heappush(free, (now, c))
                send_due(now)
            b = time.perf_counter()
            self.passes += 1
            self.busy_s += b - a
            if cut and not self.live:
                return
            nxt = b + self.POLL_S
            if not cut:
                if closed and free:
                    nxt = min(nxt, free[0][0])
                elif not closed and i < len(due):
                    nxt = min(nxt, t0 + due[i].t)
            time.sleep(max(0.0, nxt - time.perf_counter()))


def warm_plans(traffic: dict, buckets, max_new: int = 2):
    """One short request per prefill bucket that the mix's prompts reach:
    the prefill, pool-insert and decode programs of the cell, no other."""
    from bench import loadgen

    lo, hi = loadgen.length_range(traffic["prompt_len"])
    lens, prev = [], 0
    for b in buckets:
        if b >= lo and prev < hi:
            lens.append(min(b, hi))
        prev = b
    return [loadgen.Planned(WARM_IDX + i, 0.0, n, max_new)
            for i, n in enumerate(lens)]


# --------------------------------------------------------------- checking
def sample(reqs, n: int, seed: int):
    """``n`` finished requests drawn from the seed, the longest first:
    requests, not tokens, so the sample spreads over the slots."""
    import numpy as np

    done = [r for r in reqs if r.done and r.error is None]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_len + r.max_new, r.idx))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 4]).permutation(len(rest))
    return [longest] + [rest[i] for i in order[:n - 1]]


def reference_gaps(config, seed, picked, pad_len, pad_out, control=False):
    """Widest logit gap of the served tokens (and of the control's) over
    the picked requests, with freshly made weights."""
    from bench import reference, weights
    from bench.engine import param_shardings

    shard = param_shardings(config, weights.abstract(config))
    params = weights.make(config, seed, shard)
    worst = ctrl = 0.0
    n = 0
    for r in picked:
        g, c = reference.logit_gaps(params, config, r.prompt, r.tokens,
                                    pad_len=pad_len, pad_out=pad_out,
                                    control=control)
        worst, ctrl = max(worst, float(g.max())), max(ctrl, float(c.max()))
        n += len(g)
    return worst, ctrl, n


def free_device() -> None:
    import gc

    import jax

    gc.collect()
    for a in jax.live_arrays():
        a.delete()


# ------------------------------------------------------------------- main
def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def check_devices(chips: int):
    """The TPUs this run uses; exits non-zero without them."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: jax.devices()[0].platform is "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"the cell asks for {chips} chips, "
                         f"found {len(devs)}")
    return devs


class GcPauses:
    """How long the interpreter's cyclic garbage collector held every
    thread, per collection: a pause stalls the engine and the load
    alike."""

    def __init__(self):
        import gc

        self.pauses = []  # (start, seconds, generation)
        self._t = None
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            t = time.perf_counter()
            self.pauses.append((self._t, t - self._t, info["generation"]))
            self._t = None

    def summary(self, a: float, b: float) -> str:
        ps = [p for p in self.pauses if a <= p[0] < b]
        full = [p[1] for p in ps if p[2] == 2]
        return (f"{len(ps)} collections, {sum(p[1] for p in ps):.4f} s in "
                f"all, longest {max((p[1] for p in ps), default=0.0):.4f} "
                f"s; {len(full)} of the oldest generation")


class Engine:
    """The system under test for one seed: weights made on the device,
    the engine built through ``AsyncServer`` and every program the mix
    uses warmed. ``setup_s`` runs from process start (or from ``t_start``)
    to the end of the warm-up."""

    def __init__(self, cell: dict, seed: int, devices, t_start=None):
        from bench import engine, weights
        from repro.runtime.paging import pick_bucket, resolve_buckets
        from repro.runtime.server import AsyncServer

        t_start = T_START if t_start is None else t_start
        config, traffic = cell["config"], cell["traffic"]
        self.cell, self.seed, self.devices = cell, seed, devices
        phases = [("start", t_start), ("devices", time.perf_counter())]
        cfg = engine.arch_config(config)
        self.serve_cfg = engine.serve_config(config)
        abstract = weights.abstract(config)
        engine.check_layout(cfg, abstract)
        self.counter = CompileCounter()
        params = weights.make(config, seed,
                              engine.param_shardings(config, abstract))
        import jax
        jax.block_until_ready(params)
        phases.append(("weights", time.perf_counter()))
        self.asrv = AsyncServer(cfg, params, self.serve_cfg)
        del params
        phases.append(("engine", time.perf_counter()))
        self.vocab = config["vocab_size"]
        buckets = resolve_buckets(self.serve_cfg.prefill_buckets,
                                  self.serve_cfg.max_len)
        warm = warm_plans(traffic, buckets)
        log(f"warm-up: prefill buckets "
            f"{[pick_bucket(p.prompt_len, buckets) for p in warm]} "
            f"of {list(buckets)}")
        wreqs = Client(self.asrv, seed, self.vocab).run_now(
            warm, JOIN_SECONDS * 10)
        bad = [r.error for r in wreqs if not r.done]
        if bad or len(wreqs) < len(warm):
            raise RuntimeError(f"warm-up requests failed: {bad}")
        phases.append(("warm-up", time.perf_counter()))
        self.setup_s = phases[-1][1] - t_start
        self.gc = GcPauses()
        log(f"setup_s {self.setup_s:.3f} (programs compiled or loaded: "
            f"{len(self.counter.compiles)}, cache misses: "
            f"{len(self.counter.misses)}); by phase: " + ", ".join(
                f"{b[0]} {b[1] - a[1]:.3f} s"
                for a, b in zip(phases, phases[1:])))

    def serve(self, traffic: dict, seconds: float, trace: bool) -> dict:
        """Warm-in, the window and the drain of one traffic mix. Returns
        the run record; leaves the engine idle."""
        import jax

        from bench import loadgen
        from bench import trace as trace_lib
        from bench.record import Run

        asrv, config = self.asrv, self.cell["config"]
        plans = loadgen.schedule(traffic, seconds, self.seed)

        def snap():
            return dict(asrv.metrics.as_dict())

        t0 = time.perf_counter()
        t_open = t0 + float(traffic["warm_in_s"])
        t_close = t_open + seconds
        client = Client(asrv, self.seed, self.vocab).start(
            traffic, plans, t0, t_close)
        time.sleep(max(0.0, t_open - time.perf_counter()))
        m_open = snap()
        tr = t_trace = log_dir = None
        if trace:
            import tempfile
            log_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            ts0 = time.perf_counter()
            time.sleep(max(0.0, min(TRACE_SECONDS, t_close - ts0)))
            ts1 = time.perf_counter()
            jax.profiler.stop_trace()
            t_trace = (ts0, ts1)
        t_mid = t_open + seconds / 2
        time.sleep(max(0.0, t_mid - time.perf_counter()))
        m_mid = snap()
        time.sleep(max(0.0, t_close - time.perf_counter()))
        m_close = snap()
        reqs = client.reqs
        deadline = t_close + float(traffic["drain_s"])
        window = [r for r in list(reqs) if t_open <= r.sched < t_close]
        need_all = traffic["loop"] == "open"
        while time.perf_counter() < deadline:
            if all(r.error is not None or r.done
                   or (r.first is not None and not need_all)
                   for r in window):
                break
            time.sleep(0.05)
        deadline = min(deadline, time.perf_counter())
        cancelled = client.finish(JOIN_SECONDS)
        log(f"window: {seconds:.3f} s, {len(window)} requests due in it, "
            f"{len(reqs)} sent in all; {cancelled} cancelled "
            f"{deadline - t_close:.3f} s after the close")
        log(f"compiles inside the window: "
            f"{CompileCounter.between(self.counter.compiles, t_open, t_close)}"
            f" (cache misses "
            f"{CompileCounter.between(self.counter.misses, t_open, t_close)})")
        log(f"client: {client.passes} passes, busy {client.busy_s:.3f} s of "
            f"{deadline - t0:.3f}; gc from traffic start to the close: "
            f"{self.gc.summary(t0, t_close)}")
        self._log_steadiness(reqs, (t_open, t_mid, t_close),
                             (m_open, m_mid, m_close))
        if trace:
            kernels = {}
            for e in self.cell["per_layer"]:
                kernels.update(getattr(metric_module(e), "KERNELS", {}))
            tr = trace_lib.reduce(
                trace_lib.load(trace_lib.find_xplane(log_dir)),
                t_trace[1] - t_trace[0], kernels)
            import shutil
            shutil.rmtree(log_dir, ignore_errors=True)
        mesh = config["serve"].get("mesh") or (1, 1)
        return Run(
            reqs=reqs, t_open=t_open, t_close=t_close, deadline=deadline,
            setup_s=self.setup_s, model=config,
            batch_slots=self.serve_cfg.batch_slots,
            tp=mesh[1], device_kind=self.devices[0].device_kind,
            delta={k: m_close[k] - m_open[k] for k in m_open},
            trace=tr, t_trace=t_trace)

    def _log_steadiness(self, reqs, ts, ms) -> None:
        """Each half of the window: tokens per second reaching clients
        and live slots per tick; at its open, middle and close: requests
        holding KV rows and the rows they hold, against the pool."""
        sc = self.serve_cfg
        for (a, b), (ma, mb) in zip(zip(ts, ts[1:]), zip(ms, ms[1:])):
            n = sum(a <= t < b for r in reqs for t in r.times)
            ticks = mb["ticks"] - ma["ticks"]
            occ = ((mb["decode_tokens"] - ma["decode_tokens"])
                   / (ticks * sc.batch_slots) if ticks else 0.0)
            log(f"window {a - ts[0]:.1f}-{b - ts[0]:.1f} s: "
                f"{n / (b - a):.3f} tokens/s, slot occupancy {occ:.4f}")
        pool = sc.batch_slots * sc.max_len
        for t in ts:
            live = [r for r in reqs if r.first is not None and r.first <= t
                    and not (r.times[-1] <= t and (r.done or r.cancelled
                                                    or r.error))]
            rows = sum(r.prompt_len + sum(x <= t for x in r.times) - 1
                       for r in live)
            log(f"at {t - ts[0]:.1f} s: {len(live)} requests hold "
                f"{rows} KV rows of the pool's {pool} "
                f"({100.0 * rows / pool:.2f}%)")

    def peak_bytes(self) -> int:
        """Peak device memory on the fullest of the cell's chips."""
        used = self.devices[:self.cell["config"].get("chips", 1)]
        return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in used))

    def close(self) -> None:
        self.asrv.shutdown(drain=True, timeout=JOIN_SECONDS)
        self.asrv = None


def check(cell: dict, run, seed: int, control: bool = False) -> dict:
    """The comparison that decides ``correct``, once the program's state
    is freed: the widest logit gap over every served token of
    ``sample_requests`` finished requests drawn from the seed (and, with
    ``control``, the float8 control's on the same tokens)."""
    from bench import loadgen

    config, traffic = cell["config"], cell["traffic"]
    chk = config["correct"]
    free_device()
    picked = sample(run.reqs, chk["sample_requests"], seed)
    t_ref = time.perf_counter()
    _, max_out = loadgen.length_range(traffic["output_len"])
    gap, ctrl, n = reference_gaps(config, seed, picked,
                                  config["serve"]["max_len"], max_out,
                                  control=control)
    log(f"reference: {len(picked)} requests, {n} served tokens, "
        f"{time.perf_counter() - t_ref:.3f} s")
    window = run.in_window()
    failed = sum(r.error is not None for r in window)
    return {"gap": gap, "control_gap": ctrl, "checked": len(picked),
            "tokens": n, "failed": failed, "attempted": len(window),
            "correct": (gap <= chk["max_logit_gap"] and failed == 0
                        and len(picked) >= chk["sample_requests"])}


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             devices, t_start=None, control: bool = False) -> dict:
    """One run of ``cell`` on ``devices``: its result object. With
    ``control`` (calibration only) the float8 control's widest gap on the
    same tokens is logged as well."""
    d0 = devices[0]
    eng = Engine(cell, seed, devices, t_start)
    run = eng.serve(cell["traffic"], seconds, trace)
    eng.close()
    peak = eng.peak_bytes()
    del eng
    ok = check(cell, run, seed, control=control)
    if control:
        log(f"float8 control logit_gap: {ok['control_gap']}")
    chk = cell["config"]["correct"]

    names = cell["per_layer"] if trace else cell["end_to_end"]
    metrics = {}
    for e in names:
        v = metric_module(e).compute(run)
        if v is not None:
            metrics[e["name"]] = {"value": v, "unit": e["unit"]}
    for e in cell["per_layer"] if not trace else cell["end_to_end"]:
        v = metric_module(e).compute(run)
        if v is not None:
            log(f"(not reported in this run) {e['name']}: {v} {e['unit']}")
    lags = [r.sent - r.sched for r in run.in_window() if r.sent is not None]
    log(f"generator lag: max {max(lags, default=0.0):.6f} s over "
        f"{len(lags)} sends")
    d = run.delta
    log(f"window counts: ticks {d['ticks']:.0f}, decode_tokens "
        f"{d['decode_tokens']:.0f}, admitted {d['admitted']:.0f}, "
        f"prefill_tokens {d['prefill_tokens']:.0f}, decode_s "
        f"{d['decode_s']:.3f}, prefill_s {d['prefill_s']:.3f}, "
        f"batch_slots {run.batch_slots}")
    for k, v in metrics.items():
        log(f"{k}: {v['value']} {v['unit']}")
    result = {
        "correct": bool(ok["correct"]),
        "attempted": ok["attempted"],
        "failed": ok["failed"],
        "metrics": metrics,
        "device": {"platform": d0.platform, "kind": d0.device_kind,
                   "count": len(devices), "memory_peak_bytes": peak},
    }
    if trace:
        tr = run.trace
        result["device"].update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    result["checks"] = {
        "logit_gap": {"value": ok["gap"], "limit": chk["max_logit_gap"]},
        "failed_requests": {"value": ok["failed"], "limit": 0},
        "checked_requests": {"value": ok["checked"],
                             "limit": chk["sample_requests"]},
    }
    if control:
        result["checks"]["control_gap"] = {"value": ok["control_gap"],
                                           "limit": chk["max_logit_gap"]}
    log(f"check logit_gap: {ok['gap']} (limit {chk['max_logit_gap']}; "
        "widest gap of a served token's reference logit below the "
        "reference's best)")
    log(f"check failed_requests: {ok['failed']} (limit 0)")
    log(f"check checked_requests: {ok['checked']} (limit "
        f"{chk['sample_requests']}, at least; {ok['tokens']} served tokens)")
    return result


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    cell = load_cell(args.workload)
    cache = use_cache()
    devices = check_devices(cell["config"].get("chips", 1))
    log(f"device: platform={devices[0].platform} "
        f"kind={devices[0].device_kind} count={len(devices)}; "
        f"compile cache {cache}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
