"""Bring-up check: the serving engine on a TPU, through its entry points.

    python chip_smoke.py              # one chip: phases (a)-(e)
    python chip_smoke.py --chips 4    # four chips: the SPMD mesh phase only

One chip, smollm-135m at its published width (30 layers, d_model 576,
vocab 49,152, bf16, random weights from --seed):

  (a) the first device is a TPU, or the script exits non-zero;
  (b) every served Pallas kernel runs compiled at served widths and
      matches its ``kernels/ref.py`` oracle; its HLO holds the kernel;
  (c) ``repro.launch.serve.main`` serves a mixed workload with paged
      decode attention and the fused SparCE GLU MLP;
  (d) the same engine on the plain path (gather attention, SparCE off)
      serves the same params and prompts: prefill and first-decode
      logits agree within LOGITS_RTOL, and the share of identical greedy
      tokens is printed;
  (e) an open-loop run through ``AsyncServer`` (``--open-loop``).

Four chips (``--chips 4``), one process over the whole host: smollm-135m
on a (4, 1) mesh and mistral-nemo-12b (published widths, 4 of 40 layers)
on a (1, 4) mesh, each compared with the one-chip run of the same params
and traffic under the checks of (c) and (d).

Every request must complete. Lines before the last are labeled: host
wall-clock seconds, compile seconds (JAX's trace, lowering and backend
compile events, host clock), device peak bytes (``memory_stats``) and
the engine's skip counters. The last line is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``. There is no
CPU path; ``tests/test_chip_smoke.py`` rehearses the phase functions on
the CPU at reduced size.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.kernels import ref as kref  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.models import model as model_lib  # noqa: E402
from repro.runtime.server import Server  # noqa: E402

# Kernel vs oracle, normwise relative error. Operands are bf16 with f32
# accumulation; one bf16 rounding of an output is 2**-8 = 3.9e-3, and
# kernel and oracle may round an intermediate differently once more.
KERNEL_RTOL = 1e-2
# Engine vs engine logits, normwise relative error. The two paths round
# 30 bf16 layers in different places: on the CPU backend, random-weight
# stacks of 30 bf16 layers at widths 128 and 256 diverged by 1.6e-2 to
# 2.1e-2 between the fused/paged and the plain path. A misrouted block,
# row or mask is an O(1) error.
LOGITS_RTOL = 5e-2

# (c): the served path under test.
SERVE_ARGV = [
    "--arch", "smollm-135m", "--batch-slots", "8", "--requests", "12",
    "--mixed", "--prompt-len", "32", "--max-new", "16",
    "--kv-block-size", "16", "--attn-kernel", "paged",
    "--sparce-gate-threshold", "0", "--sparce-mode", "fused",
]
# (d): the plain path it is compared with.
PLAIN_ARGV = [
    "--arch", "smollm-135m", "--batch-slots", "8", "--requests", "12",
    "--mixed", "--prompt-len", "32", "--max-new", "16",
    "--kv-block-size", "16", "--attn-kernel", "gather",
]
# (e): a few requests through AsyncServer.
OPEN_LOOP_ARGV = [
    "--arch", "smollm-135m", "--batch-slots", "8", "--requests", "4",
    "--mixed", "--prompt-len", "32", "--max-new", "8",
    "--kv-block-size", "16", "--attn-kernel", "paged",
    "--sparce-gate-threshold", "0", "--sparce-mode", "fused",
    "--open-loop", "--arrival-rate", "4",
]
# --chips 4: (arch, layers kept or None for all, mesh).
MESH_RUNS = (
    ("smollm-135m", None, (4, 1)),
    ("mistral-nemo-12b", 4, (1, 4)),
)


class _CompileClock:
    """Sums JAX's compile-event durations (host clock)."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._on_event)


class _Phase:
    """Prints a phase's host wall time, compile time and device peak."""

    def __init__(self, name: str, clock: _CompileClock):
        self.name, self.clock = name, clock

    def __enter__(self):
        print(f"[{self.name}] start", flush=True)
        self.t0, self.c0 = time.perf_counter(), self.clock.seconds
        return self

    def __exit__(self, *exc):
        if exc[0] is not None:
            return False
        stats = jax.devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use", "not reported")
        print(f"[{self.name}] ok: host wall {time.perf_counter() - self.t0:.3f}"
              f" s, compile {self.clock.seconds - self.c0:.3f} s (host "
              f"clock), device 0 peak_bytes_in_use so far {peak}",
              flush=True)
        return False


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


def _check_close(name: str, got, want, rtol: float) -> None:
    err = rel_err(got, want)
    print(f"  {name}: normwise rel err {err:.3e} (tolerance {rtol:.0e})")
    if not err <= rtol:
        raise AssertionError(f"{name}: rel err {err:.3e} > {rtol:.0e}")


# ------------------------------------------------------------ (a) device
def check_device(n_chips: int):
    """The first device is a TPU and ``n_chips`` are visible."""
    devs = jax.devices()
    d = devs[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}")
    if d.platform != "tpu":
        raise SystemExit(f"no TPU: jax.devices()[0].platform is "
                         f"{d.platform!r}")
    if len(devs) < n_chips:
        raise SystemExit(f"--chips {n_chips} needs {n_chips} devices, "
                         f"found {len(devs)}")
    return d


# ----------------------------------------------------------- (b) kernels
def _compiled(name: str, fn, *args):
    """Run ``fn`` compiled; where kernels compile, its HLO holds one."""
    compiled = jax.jit(fn).lower(*args).compile()
    if not kops.interpret_mode() and "tpu_custom_call" not in compiled.as_text():
        raise AssertionError(f"{name}: no tpu_custom_call in its HLO")
    return compiled(*args)


def check_kernels(gqa_cfg, mla_cfg, *, seed: int = 0) -> None:
    """Each served kernel at ``gqa_cfg``/``mla_cfg`` widths against its
    oracle. MLP inputs have dead rows, so the fetch-skip path runs."""
    dt = jnp.bfloat16 if gqa_cfg.dtype == "bfloat16" else jnp.float32
    k, f = gqa_cfg.d_model, gqa_cfg.d_ff
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def rnd(shape, scale=1.0, dtype=dt):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    m, bm, bf = 16, 8, 128
    live = (jnp.arange(m) < 8)[:, None]  # rows 8..15: dead slots
    x = jnp.where(live, rnd((m, k)), 0).astype(dt)
    w_gate, w_in = rnd((k, f), k ** -0.5), rnd((k, f), k ** -0.5)
    w_out = rnd((f, k), f ** -0.5)

    y, bmp = _compiled(
        "sparce_glu_mlp_fused",
        lambda *a: kops.sparce_glu_mlp_fused(
            *a, block_m=bm, block_f=bf, act="silu", tau=0.0),
        x, w_gate, w_in, w_out)
    y_ref, bits_ref = kref.glu_mlp_ref(
        x, w_gate, w_in, w_out, act="silu", tau=0.0, block_m=bm,
        block_f=bf)
    _check_close("sparce_glu_mlp_fused y", y, y_ref, KERNEL_RTOL)
    np.testing.assert_array_equal(np.asarray(bmp.bits), np.asarray(bits_ref))
    print(f"  sparce_glu_mlp_fused: {int(bmp.bits.sum())}/{bmp.bits.size} "
          "dead tiles, bitmap equal to the oracle's")

    y, bmp = _compiled(
        "sparce_mlp_fused",
        lambda *a: kops.sparce_mlp_fused(*a, block_m=bm, block_f=bf,
                                         act="relu"),
        x, w_in, w_out)
    a_ref, bits_ref = kref.relu_bitmap_ref(jnp.dot(x, w_in), (bm, bf))
    y_ref = kref.sparce_gemm_ref(
        a_ref, w_out, bits_lhs=bits_ref, block_m=bm, block_k=bf,
        block_n=128)
    _check_close("sparce_mlp_fused y", y, y_ref, KERNEL_RTOL)
    np.testing.assert_array_equal(np.asarray(bmp.bits), np.asarray(bits_ref))

    h = rnd((m, f))
    a, bmp = _compiled("relu_bitmap",
                       lambda v: kops.relu_with_bitmap(v, (bm, bf)), h)
    a_ref, bits_ref = kref.relu_bitmap_ref(h, (bm, bf))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(a_ref))
    np.testing.assert_array_equal(np.asarray(bmp.bits), np.asarray(bits_ref))
    print("  relu_bitmap: output and bitmap equal to the oracle's")

    # Paged decode: 8 slots, one dead, ragged lengths over a shuffled pool.
    B, bs, max_blocks = 8, 16, 8
    nb = B * max_blocks + 1
    rng = np.random.default_rng(seed)
    tables = jnp.asarray(
        rng.permutation(np.arange(1, nb)).reshape(B, max_blocks), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, bs * max_blocks, B), jnp.int32)
    lengths = lengths.at[3].set(0)
    kv, hd = gqa_cfg.num_kv_heads, gqa_cfg.resolved_head_dim
    g = gqa_cfg.num_heads // kv
    q = rnd((B, kv, g, hd))
    k_pool, v_pool = rnd((nb, bs, kv, hd)), rnd((nb, bs, kv, hd))
    o = _compiled(
        "paged_decode_attn",
        lambda *a: kops.paged_decode_attn(*a, scale=hd ** -0.5),
        q, k_pool, v_pool, tables, lengths)
    o_ref = kref.paged_gqa_decode_attn_ref(
        q, k_pool, v_pool, tables, lengths, scale=hd ** -0.5)
    live_rows = np.asarray(lengths) > 0
    _check_close("paged_decode_attn (GQA)", np.asarray(o)[live_rows],
                 np.asarray(o_ref)[live_rows], KERNEL_RTOL)

    mla = mla_cfg.mla
    h_, r, rope = mla_cfg.num_heads, mla.kv_lora_rank, mla.qk_rope_dim
    scale = (mla.qk_nope_dim + rope) ** -0.5
    q_lat, q_rope = rnd((B, h_, r), r ** -0.5), rnd((B, h_, rope))
    ckv, kr = rnd((nb, bs, r)), rnd((nb, bs, rope))
    o = _compiled(
        "paged_mla_decode_attn",
        lambda *a: kops.paged_mla_decode_attn(*a, scale=scale),
        q_lat, q_rope, ckv, kr, tables, lengths)
    o_ref = kref.paged_mla_decode_attn_ref(
        q_lat, q_rope, ckv, kr, tables, lengths, scale=scale)
    _check_close("paged_mla_decode_attn", np.asarray(o)[live_rows],
                 np.asarray(o_ref)[live_rows], KERNEL_RTOL)


# ----------------------------------------------------- (c), (e) serving
def _check_complete(done, n: int) -> None:
    if len(done) != n:
        raise AssertionError(f"{len(done)} of {n} requests completed")
    for r in done:
        if r.error is not None or r.out is None or len(r.out) != r.max_new:
            raise AssertionError(
                f"request uid={r.uid} did not complete: error={r.error!r}, "
                f"{0 if r.out is None else len(r.out)}/{r.max_new} tokens")


def serve_run(argv) -> list:
    """``repro.launch.serve.main`` end to end; every request completes."""
    args = serve.parse_args(argv)
    done = serve.main(argv)
    _check_complete(done, args.requests)
    print(f"  {len(done)}/{args.requests} requests completed")
    return done


# ------------------------------------------------- (d) path comparison
class _TapServer(Server):
    """The engine, unchanged, recording each request's prefill logits
    and its first decode-tick logits."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.prefill_logits, self.decode_logits = {}, {}
        self._in_tick = False

    def _prefill_one(self, r, slot, caches, block_ids=None):
        last, caches = super()._prefill_one(r, slot, caches, block_ids)
        self.prefill_logits[r.uid] = np.asarray(last[0, 0], np.float32)
        return last, caches

    def _decode_tick(self):
        self._in_tick = True
        try:
            return super()._decode_tick()
        finally:
            self._in_tick = False

    def _sample(self, logits):
        if self._in_tick:  # a decode tick: (B, 1, V)
            last = np.asarray(logits[:, 0], np.float32)
            for i, s in enumerate(self._st.slots):
                if s is not None:
                    self.decode_logits.setdefault(s.req.uid, last[i])
        return super()._sample(logits)


def _serve_tapped(cfg, params, serve_cfg, reqs):
    srv = _TapServer(cfg, params, serve_cfg)
    done = srv.generate([dataclasses.replace(r) for r in reqs])
    _check_complete(done, len(reqs))
    m = srv.metrics
    print(f"  served {len(done)} requests, {int(m.decode_tokens)} decode "
          f"tokens in {int(m.ticks)} ticks; skip counters: MLP tile-dots "
          f"skipped {m.skipped_tile_dots:.0f}/{m.total_tile_dots:.0f}, "
          f"attention pool blocks fetched {int(m.attn_blocks_fetched)}/"
          f"{int(m.attn_blocks_total)}")
    return srv, {r.uid: np.asarray(r.out) for r in done}


def compare_engines(name: str, test, ref) -> float:
    """Logits and greedy tokens of two tapped runs of the same traffic.
    Returns the share of identical greedy tokens."""
    (srv_t, out_t), (srv_r, out_r) = test, ref
    for uid in sorted(out_r):
        _check_close(f"{name} uid={uid} prefill logits",
                     srv_t.prefill_logits[uid], srv_r.prefill_logits[uid],
                     LOGITS_RTOL)
    same_first = [u for u in sorted(out_r) if out_t[u][0] == out_r[u][0]]
    for uid in same_first:
        if uid in srv_r.decode_logits:
            _check_close(f"{name} uid={uid} first-decode logits",
                         srv_t.decode_logits[uid], srv_r.decode_logits[uid],
                         LOGITS_RTOL)
    n = sum(len(v) for v in out_r.values())
    same = sum(int(np.sum(out_t[u] == out_r[u])) for u in out_r)
    print(f"  {name}: identical greedy tokens {same}/{n} = {same / n:.4f}; "
          f"first tokens equal for {len(same_first)}/{len(out_r)} requests")
    return same / n


def compare_plain(argv, plain_argv) -> float:
    """The engine of ``argv`` against the plain path of ``plain_argv``
    on the same params and prompts."""
    args, plain_args = serve.parse_args(argv), serve.parse_args(plain_argv)
    cfg, serve_cfg = serve.build_config(args)
    plain_cfg, plain_serve_cfg = serve.build_config(plain_args)
    params = model_lib.init_params(cfg, jax.random.PRNGKey(args.seed))
    reqs = serve.make_requests(args, cfg)
    test = _serve_tapped(cfg, params, serve_cfg, reqs)
    ref = _serve_tapped(plain_cfg, params, plain_serve_cfg, reqs)
    return compare_engines("sparce+paged vs plain", test, ref)


# ---------------------------------------------------------- --chips 4
def mesh_run(argv, num_layers, mesh) -> float:
    """The engine of ``argv`` on ``mesh`` against the one-chip engine
    with the same config, params and traffic."""
    args = serve.parse_args(list(argv) + ["--mesh", f"{mesh[0]},{mesh[1]}"])
    cfg, serve_cfg = serve.build_config(args)
    if num_layers is not None:
        cfg = dataclasses.replace(cfg, num_layers=num_layers)
    params = model_lib.init_params(cfg, jax.random.PRNGKey(args.seed))
    reqs = serve.make_requests(args, cfg)
    print(f"  {cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
          f"mesh (data, model) = {mesh}")
    one_chip = _serve_tapped(cfg, params,
                             dataclasses.replace(serve_cfg, mesh=None), reqs)
    sharded = _serve_tapped(cfg, params, serve_cfg, reqs)
    return compare_engines(f"mesh {mesh} vs one chip", sharded, one_chip)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache

    clock = _CompileClock()
    with _Phase("a: device", clock):
        dev = check_device(args.chips)
    print(f"compile cache: {use_compile_cache()}")
    if args.chips == 4:
        for arch, layers, mesh in MESH_RUNS:
            with _Phase(f"mesh {arch} {mesh}", clock):
                mesh_run(SERVE_ARGV + ["--arch", arch], layers, mesh)
    else:
        with _Phase("b: kernels", clock):
            check_kernels(get_config("smollm-135m"),
                          get_config("deepseek-v3-671b"))
        with _Phase("c: serve", clock):
            serve_run(SERVE_ARGV)
        with _Phase("d: plain-path comparison", clock):
            compare_plain(SERVE_ARGV, PLAIN_ARGV)
        with _Phase("e: open loop", clock):
            serve_run(OPEN_LOOP_ARGV)
    clock.close()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
