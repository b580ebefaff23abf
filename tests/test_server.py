"""Serving: batched generate, decode/prefill consistency, audio path.

Engine-level tests run on the shared deterministic harness
(tests/serving_harness.py): seeded traffic + cache-free greedy oracle.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as model_lib
from repro.runtime.server import Request, ServeConfig, Server
from serving_harness import Traffic, make_traffic, run_and_check


def _setup(arch, dropless_moe=False):
    cfg = get_config(arch).reduced()
    if dropless_moe and cfg.moe is not None:
        # Capacity-factor MoE drops over-capacity assignments, which makes
        # outputs BATCH-DEPENDENT by design (a 12-token pass may drop an
        # assignment that a 1-token decode keeps). The decode-consistency
        # invariant is exact only in the drop-free regime, so tests pin a
        # capacity factor that covers the worst-case load.
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=16.0))
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.mark.parametrize("arch", [
    "smollm-135m", "mamba2-2.7b", "zamba2-7b",
    # deepseek exercises the MLA ABSORBED decode (attention in latent
    # space) against the decompressed full-forward path -- the two
    # formulations are algebraically equal but share no code.
    "deepseek-v3-671b",
    "musicgen-large",
])
def test_decode_matches_full_forward(arch):
    """Token-by-token decode must reproduce the full-sequence logits --
    the KV/SSM cache path is exact, not approximate. (MoE runs dropless
    here: capacity drops are batch-dependent by design, see _setup.)"""
    cfg, params = _setup(arch, dropless_moe=True)
    S = 12
    if cfg.frontend == "codes":
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (1, cfg.num_codebooks, S), 0,
            cfg.vocab_size)
        tok_at = lambda t: tokens[:, :, t:t + 1]
        tok_pre = tokens[:, :, : S - 4]
    else:
        tokens = jax.random.randint(jax.random.PRNGKey(1), (1, S), 0,
                                    cfg.vocab_size)
        tok_at = lambda t: tokens[:, t:t + 1]
        tok_pre = tokens[:, : S - 4]
    full_logits, _, _ = model_lib.forward(params, cfg, {"tokens": tokens})
    # prefill first S-4 tokens, decode the rest one at a time
    pre = S - 4
    logits_p, caches = model_lib.prefill(
        params, cfg, {"tokens": tok_pre}, max_len=S + 8)
    outs = [logits_p[:, -1]]
    for t in range(pre, S):
        lg, caches = model_lib.decode_step(params, cfg, tok_at(t), caches)
        outs.append(lg[:, -1] if cfg.frontend != "codes" else lg[:, 0])
    stepwise = jnp.stack(outs[:-1], axis=1)  # predictions at pre-1..S-2
    np.testing.assert_allclose(
        np.asarray(stepwise, np.float32),
        np.asarray(full_logits[:, pre - 1:S - 1], np.float32),
        rtol=2e-2, atol=2e-2)


def test_server_generates_batched():
    """Seeded mixed traffic, more requests than slots: every request
    reproduces the cache-free oracle exactly (harness contract)."""
    cfg, params = _setup("smollm-135m")
    reqs = make_traffic(cfg, Traffic(n_requests=6, prompt_lens=(4, 9),
                                     max_new=(6, 6), seed=1))
    done, metrics, _ = run_and_check(
        cfg, params, ServeConfig(batch_slots=4, max_len=64), reqs)
    for r in done:
        assert r.out is not None and len(r.out) == 6
        assert all(0 <= int(t) < cfg.vocab_size for t in r.out)
    assert metrics["decode_tokens"] > 0


def test_server_greedy_deterministic():
    cfg, params = _setup("smollm-135m")
    srv = Server(cfg, params, ServeConfig(batch_slots=2, max_len=64))
    r1 = srv.generate([Request(uid=0, prompt=np.array([1, 2, 3]), max_new=5)])
    r2 = srv.generate([Request(uid=1, prompt=np.array([1, 2, 3]), max_new=5)])
    np.testing.assert_array_equal(r1[0].out, r2[0].out)


def test_server_audio_codebooks():
    """Codebook-stream serving matches the codes-frontend oracle."""
    cfg, params = _setup("musicgen-large")
    reqs = make_traffic(cfg, Traffic(n_requests=1, prompt_lens=(5, 5),
                                     max_new=(4, 4), seed=2))
    done, _, _ = run_and_check(
        cfg, params, ServeConfig(batch_slots=2, max_len=32), reqs)
    assert done[0].out.shape == (4, cfg.num_codebooks)


# --------------------------------------------- continuous-batching engine
def test_mixed_budgets_no_wasted_decode_ticks():
    """6 requests, max_new in {2, 32}, 4 slots: decode work counts only
    live slots. The engine spends exactly sum(max_new) - R decode tokens
    (one token per request comes from prefill logits), strictly fewer
    than the fixed-slot schedule batch*max(max_new) per wave."""
    cfg, params = _setup("smollm-135m")
    budgets = [2, 32, 2, 32, 2, 32]
    srv = Server(cfg, params, ServeConfig(batch_slots=4, max_len=64))
    reqs = [Request(uid=i, prompt=np.arange(4 + i) % cfg.vocab_size,
                    max_new=b) for i, b in enumerate(budgets)]
    done = srv.generate(reqs)
    assert len(done) == 6
    for r in done:
        assert len(r.out) == r.max_new
    assert srv.metrics["decode_tokens"] == sum(budgets) - len(budgets)
    # Seed engine: two waves of [2,32,2,32] and [2,32], each decoding
    # every slot to the wave max -> 4*32 + 2*32 tokens.
    assert srv.metrics["decode_tokens"] < 4 * 32 + 2 * 32
    # The long requests bound the tick count; short ones ride along.
    # (The last backfilled 32-budget request starts one tick late.)
    assert srv.metrics["ticks"] == 32


def test_eos_frees_slot_and_queue_backfills():
    """A request hitting EOS releases its slot immediately and a queued
    request is admitted into it (more admissions than slots, in one
    generate call, with long-budget requests still running)."""
    cfg, params = _setup("smollm-135m")
    # Learn the greedy continuation for this prompt, then replay with
    # eos_id set to the second generated token.
    probe = Server(cfg, params, ServeConfig(batch_slots=1, max_len=64))
    seq = probe.generate(
        [Request(uid=0, prompt=np.array([1, 2, 3]), max_new=6)])[0].out
    eos = int(seq[1])
    srv = Server(cfg, params, ServeConfig(batch_slots=2, max_len=64))
    reqs = [
        Request(uid=0, prompt=np.array([1, 2, 3]), max_new=6, eos_id=eos),
        Request(uid=1, prompt=np.array([9, 8, 7, 6]), max_new=6),
        Request(uid=2, prompt=np.array([4, 5]), max_new=6),
    ]
    done = srv.generate(reqs)
    by_uid = {r.uid: r for r in done}
    # EOS request stopped early (eos token included), others ran out
    # their budgets.
    assert len(by_uid[0].out) == 2 and int(by_uid[0].out[-1]) == eos
    assert len(by_uid[1].out) == 6 and len(by_uid[2].out) == 6
    # All three were served by 2 slots in one call => slot reuse.
    assert srv.metrics["admitted"] == 3
    assert srv.metrics["completed"] == 3
    # uid=2 backfilled the freed slot: the total decode work is less
    # than three full budgets would cost.
    assert srv.metrics["decode_tokens"] == (2 - 1) + (6 - 1) + (6 - 1)


def test_greedy_matches_full_forward_rollout():
    """Greedy continuous-batching output == token-by-token argmax over
    the full-sequence forward (no cache): the engine is exact. (The
    harness oracle IS that rollout.)"""
    cfg, params = _setup("smollm-135m")
    run_and_check(
        cfg, params, ServeConfig(batch_slots=3, max_len=64),
        [Request(uid=0, prompt=np.array([1, 2, 3, 4]), max_new=5)])


def test_greedy_outputs_independent_of_batch_composition():
    """The same request yields identical greedy tokens whether it is
    served alone or alongside other in-flight requests -- per-slot cache
    isolation in the shared buffer (and, paged, in the shared pool)."""
    cfg, params = _setup("smollm-135m")
    solo = Server(cfg, params, ServeConfig(batch_slots=1, max_len=64))
    alone = solo.generate(
        [Request(uid=0, prompt=np.array([5, 6, 7]), max_new=6)])[0].out
    done, _, _ = run_and_check(
        cfg, params, ServeConfig(batch_slots=3, max_len=64), [
            Request(uid=0, prompt=np.array([5, 6, 7]), max_new=6),
            Request(uid=1, prompt=np.array([11, 12]), max_new=2),
            Request(uid=2, prompt=np.array([3, 1, 4, 1, 5]), max_new=4),
        ])
    mixed = {r.uid: r.out for r in done}[0]
    np.testing.assert_array_equal(alone, mixed)


def test_serving_sparsity_skips_dead_slot_tiles():
    """With the SparCE path on, freed slots' zeroed activation rows are
    skipped tile work: mlp_skip_fraction > 0 once slots go idle, and
    outputs are unchanged vs. the dense engine."""
    import dataclasses as dc

    from repro.core.sparse_ops import SparsityConfig

    cfg = get_config("smollm-135m").reduced()
    cfg = dc.replace(cfg, mlp_act="relu")  # the paper's sparsity source
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    reqs = lambda: [
        Request(uid=0, prompt=np.array([1, 2, 3]), max_new=2),
        Request(uid=1, prompt=np.array([4, 5, 6]), max_new=10),
    ]
    dense = Server(cfg, params, ServeConfig(batch_slots=2, max_len=64))
    d_out = {r.uid: r.out for r in dense.generate(reqs())}
    scfg = SparsityConfig(enabled=True, mode="reference",
                          block_m=1, block_k=128)
    sp = Server(cfg, params, ServeConfig(batch_slots=2, max_len=64,
                                         sparsity=scfg))
    s_out = {r.uid: r.out for r in sp.generate(reqs())}
    for uid in d_out:
        np.testing.assert_array_equal(d_out[uid], s_out[uid])
    # uid=0 finishes after 1 tick; the following 8 ticks run with a dead
    # slot whose rows are all-zero tiles.
    assert sp.metrics["total_tile_dots"] > 0
    assert sp.metrics["mlp_skip_fraction"] > 0.2


def test_overlong_requests_rejected_before_any_admission():
    cfg, params = _setup("smollm-135m")
    srv = Server(cfg, params, ServeConfig(batch_slots=2, max_len=16))
    with pytest.raises(ValueError, match="do not fit"):
        srv.generate([Request(uid=0, prompt=np.arange(40), max_new=4)])
    # Budget overflow is caught too (decode writes would clamp onto the
    # last cache row), and BEFORE any compute is spent on earlier
    # requests in the same call.
    with pytest.raises(ValueError, match="uid=1"):
        srv.generate([
            Request(uid=0, prompt=np.arange(4), max_new=4),
            Request(uid=1, prompt=np.arange(12), max_new=8),
        ])
    assert srv.metrics["admitted"] == 0


def test_per_request_stats_populated():
    cfg, params = _setup("smollm-135m")
    srv = Server(cfg, params, ServeConfig(batch_slots=2, max_len=64))
    done = srv.generate(
        [Request(uid=0, prompt=np.array([1, 2, 3]), max_new=4)])
    s = done[0].stats
    assert s["tokens"] == 4 and s["decode_ticks"] == 3
    assert s["latency_s"] >= s["ttft_s"] >= 0


# ---------------------------------------------- engine spans and counters
def _served(n_requests=5, seed=4):
    """A finished run of seeded traffic (no EOS) through two slots."""
    cfg, params = _setup("smollm-135m")
    reqs = make_traffic(cfg, Traffic(n_requests=n_requests,
                                     prompt_lens=(3, 9), max_new=(1, 6),
                                     seed=seed))
    srv = Server(cfg, params, ServeConfig(batch_slots=2, max_len=32))
    srv.generate(reqs)
    return srv, reqs


def test_engine_host_counters_nest_and_count_live_rows():
    srv, reqs = _served()
    m = srv.metrics
    tol = 1e-6  # float sums of nested perf_counter intervals
    assert m.step_s > 0 and m.decode_wait_s > 0 and m.readback_s > 0
    assert m.step_s + tol >= (m.admit_s + m.decode_prep_s + m.decode_s
                              + m.sample_s + m.emit_s)
    assert m.decode_s + tol >= m.decode_wait_s + m.readback_s
    assert m.admit_s + tol >= m.prefill_s > 0
    assert m.queue_wait_s >= 0
    # A request with a p-token prompt and n tokens is live in n - 1
    # decode ticks, holding p, p + 1, ..., p + n - 2 rows in them.
    rows = 0
    for r in reqs:
        p, n = len(r.prompt), r.max_new
        rows += sum(p + j for j in range(n - 1))
    assert m.kv_live_row_ticks == rows
    assert m.ticks > 0
    assert m.kv_pool_row_ticks == (
        m.ticks * m.kv_pool_blocks * m.kv_block_size) > 0


def test_step_programs_have_stable_names():
    import re

    srv, _ = _served(n_requests=1)
    st, B = srv._st, srv.sc.batch_slots

    def module(lowered):
        return re.search(r"module @(\w+)", lowered.as_text()).group(1)

    assert module(srv._decode.lower(
        srv.params, jnp.zeros((B, 1), jnp.int32), st.caches,
        jnp.zeros((B,), jnp.float32), jnp.asarray(st.tables),
    )) == "jit_serve_decode"
    assert module(srv._prefill.lower(
        srv.params, {"tokens": jnp.zeros((1, 8), jnp.int32),
                     "advance": jnp.asarray([8], jnp.int32)},
    )) == "jit_serve_prefill"
    assert module(srv._sample_ids.lower(
        jnp.zeros((B, 1, srv.cfg.vocab_size), jnp.float32),
    )) == "jit_serve_sample"


def test_profiler_trace_holds_engine_spans(tmp_path):
    import pathlib
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from bench import trace

    cfg, params = _setup("smollm-135m")
    reqs = make_traffic(cfg, Traffic(n_requests=2, prompt_lens=(4, 6),
                                     max_new=(3, 3), seed=5))
    srv = Server(cfg, params, ServeConfig(batch_slots=2, max_len=32))
    jax.profiler.start_trace(str(tmp_path))
    try:
        srv.generate(reqs)
    finally:
        jax.profiler.stop_trace()
    host = trace.load(trace.find_xplane(str(tmp_path)))["host"]
    names = {n for n, _, _ in host}
    assert {"serve.step", "serve.admit", "serve.prefill",
            "serve.decode.prep", "serve.decode", "serve.decode.wait",
            "serve.decode.readback", "serve.sample",
            "serve.emit"} <= names
    assert sum(n == "serve.prefill" for n, _, _ in host) == 2


# ------------------------------------------------------------- sampling
@pytest.fixture(scope="module")
def greedy_server():
    cfg, params = _setup("smollm-135m")
    return Server(cfg, params, ServeConfig(batch_slots=2, max_len=32))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", [(5, 1, 300), (3, 1, 4, 300), (1, 1, 300)],
                         ids=["tick", "tick_codes", "admission"])
def test_device_sample_matches_host_argmax(greedy_server, dtype, shape):
    """Greedy ids chosen on the device equal np.argmax over the same
    logits, the first index winning each tie: small integer values make
    ties common, row 0 is all ties and row -1 ties at its ends."""
    rng = np.random.default_rng(7)
    x = rng.integers(-3, 4, shape).astype(np.float32)
    x[0] = 1.0
    x[-1, ..., 0] = x[-1, ..., -1] = 9.0
    logits = jnp.asarray(x, dtype)
    ids = greedy_server._sample(logits)
    want = np.argmax(np.asarray(logits, np.float32)[:, -1], axis=-1)
    assert ids.dtype == np.int32 and isinstance(ids, np.ndarray)
    np.testing.assert_array_equal(ids, want)
    assert (ids[0] == 0).all() and (ids[-1] == 0).all()


class _HostSampling(Server):
    """Sampling as it was before greedy moved to the device: the chosen
    row(s) of logits copied to the host, then Gumbel-max in float64 on
    the engine's seeded stream, (V,) at an admission, (B, V) a tick."""

    def _sample(self, logits):
        last = np.asarray(logits, np.float32)[:, -1]
        admission = last.shape[0] != self.sc.batch_slots
        if admission:
            last = last[0]
        z = last.astype(np.float64) / self.sc.temperature
        u = self._rng.random(z.shape)
        g = -np.log(-np.log(np.clip(u, 1e-12, 1.0)))
        out = np.argmax(z + g, axis=-1)
        return out[None] if admission else out


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_sampling_path_and_device_sample_ticks(temperature):
    """Greedy ticks choose on the device and reproduce the cache-free
    argmax oracle; a temperature keeps the host Gumbel-max draw,
    token for token, and no tick counts as sampled on the device."""
    cfg, params = _setup("smollm-135m")
    reqs = make_traffic(cfg, Traffic(n_requests=5, prompt_lens=(3, 9),
                                     max_new=(2, 7), seed=11))
    sc = ServeConfig(batch_slots=3, max_len=32, temperature=temperature,
                     seed=5)
    if temperature <= 0:
        _, m, _ = run_and_check(cfg, params, sc, reqs)
        assert m.device_sample_ticks == m.ticks > 0
        return
    srv = Server(cfg, params, sc)
    got = {r.uid: r.out for r in srv.generate(
        [dataclasses.replace(r) for r in reqs])}
    host = _HostSampling(cfg, params, sc)
    want = {r.uid: r.out for r in host.generate(
        [dataclasses.replace(r) for r in reqs])}
    assert got.keys() == want.keys()
    for uid in want:
        np.testing.assert_array_equal(got[uid], want[uid])
    assert srv.metrics.ticks > 0 and srv.metrics.device_sample_ticks == 0
