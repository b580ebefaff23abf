"""Measurements that set a cell's fixed numbers, made once when the cell
is defined; the benchmark's own runs never call this.

    python3 bench/calibrate.py sweep --workload <cell> --seed <n> \
        --rates 10,20,30 --seconds 20
        One engine, the cell's traffic at each open-loop rate in turn:
        the end-to-end metrics and counters per rate, to find the knee.
    python3 bench/calibrate.py check --workload <cell> --seeds 1,2,3 \
        --seconds 51 [--fault half_batch]
        Per seed, in a process of its own: a whole run of the cell, then
        the widest logit gap of the served tokens and of the float8
        control on the same tokens. The first sets the lower reading of
        the cell's limit, the second the upper one. With ``--fault`` a
        fault of ``bench/faults.py`` is planted under the timed path.
    python3 bench/calibrate.py sample-trace --workload <cell> --seed <n> \
        --out <file> --seconds 0.2
        A short profiler trace of the cell's decode, for the tests of
        bench/trace.py.

Lines of results go to standard output as JSON objects.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("mode", choices=("sweep", "check", "sample-trace"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default="")
    ap.add_argument("--fault", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import run as R

    cell = R.load_cell(args.workload)
    if args.mode != "check":
        # ``check`` runs each seed in a process of its own, which needs
        # the chip: this process must not touch it.
        R.use_cache()
        devices = R.check_devices(cell["config"].get("chips", 1))

    if args.mode == "sweep":
        eng = R.Engine(cell, args.seed, devices)
        for rate in [float(x) for x in args.rates.split(",")]:
            traffic = copy.deepcopy(cell["traffic"])
            traffic["arrival"]["rate_per_s"] = rate
            run = eng.serve(traffic, args.seconds, False)
            row = {"rate_per_s": rate}
            for e in cell["end_to_end"] + cell["per_layer"]:
                v = R.metric_module(e).compute(run)
                if v is not None:
                    row[e["name"]] = v
            w = run.in_window()
            row["due"] = len(w)
            row["no_first_token"] = sum(r.first is None for r in w)
            row["unfinished"] = sum(not r.done for r in w)
            print(json.dumps(row), flush=True)
        eng.close()
    elif args.mode == "check":
        import subprocess

        for seed in [int(x) for x in args.seeds.split(",")]:
            # A process per seed, as the benchmark's runs have: the
            # device's peak and the compile cache start afresh.
            code = (f"import sys; sys.path[:0] = {[str(ROOT), str(ROOT / 'src')]!r}\n"
                    "from bench import calibrate\n"
                    f"calibrate.check_one({args.workload!r}, {seed}, "
                    f"{args.seconds}, {args.fault!r})\n")
            out = subprocess.run([sys.executable, "-c", code],
                                 stdout=subprocess.PIPE, text=True)
            print(out.stdout.strip().splitlines()[-1] if out.stdout.strip()
                  else json.dumps({"seed": seed, "rc": out.returncode}),
                  flush=True)
    else:
        import shutil
        import tempfile

        import jax

        from bench import loadgen
        from bench import trace as trace_lib

        eng = R.Engine(cell, args.seed, devices)
        traffic = copy.deepcopy(cell["traffic"])
        traffic["warm_in_s"] = 0.0
        plans = loadgen.schedule(traffic, 2.0, args.seed)
        client = R.Client(eng.asrv, args.seed, eng.vocab).start(
            traffic, plans[:8], time.perf_counter(), float("inf"))
        time.sleep(1.0)
        log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        time.sleep(args.seconds)
        jax.profiler.stop_trace()
        client.finish(60)
        eng.close()
        src = trace_lib.find_xplane(log_dir)
        out = pathlib.Path(args.out)
        out.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(src, out)
        ev = trace_lib.load(str(out))
        names = {}
        for evs in ev["devices"].values():
            for name, s, e, text in evs:
                names[name] = names.get(name, 0.0) + (e - s) / 1e9
        print(json.dumps({"file": str(out), "bytes": out.stat().st_size,
                          "planes": sorted(ev["devices"]),
                          "top_ops": sorted(names.items(),
                                            key=lambda kv: -kv[1])[:40]}))
        texts = sorted({t for evs in ev["devices"].values()
                        for _, _, _, t in evs if "custom" in t.lower()
                        or "kernel" in t.lower()})[:40]
        print(json.dumps({"kernel_texts": texts}))
    return 0


def check_one(workload: str, seed: int, seconds: float, fault: str) -> None:
    """One run of ``workload`` with the float8 control, and ``fault``
    planted if named; prints one JSON line."""
    from bench import faults
    from bench import run as R

    cell = R.load_cell(workload)
    R.use_cache()
    devices = R.check_devices(cell["config"].get("chips", 1))
    if fault:
        faults.plant(fault)
    res = R.run_cell(cell, seed, seconds, False, devices, T_START,
                     control=True)
    print(json.dumps({"seed": seed, "fault": fault or None,
                      "correct": res["correct"], "checks": res["checks"],
                      "metrics": {k: v["value"]
                                  for k, v in res["metrics"].items()},
                      "peak": res["device"]["memory_peak_bytes"]}),
          flush=True)


if __name__ == "__main__":
    sys.exit(main())
