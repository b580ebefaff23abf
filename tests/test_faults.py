"""Seeded fault injection: containment, recovery, and engine survival.

The fault harness's contract has three parts, all asserted here:

  1. **Determinism** -- every injection decision is a pure function of
     ``(plan.seed, site, uid, progress counter)``, so a sweep replays
     identically and tests can assert EXACT failed-request sets.
  2. **Containment** -- a request-scoped fault (poison prompt, step
     fault, allocator exhaustion) fails exactly the targeted request:
     its blocks/commitment roll back, the allocator's structural check
     and the engine's ledger pass, and every OTHER request finishes
     with the same tokens as a fault-free run.
  3. **Classification** -- anything NOT request-scoped kills the engine
     loudly (EngineFatal) instead of limping on with corrupt state.

The sweep reuses ONE engine (fresh ``start_engine`` per run, jit cache
shared) so 4 fault kinds x 5 seeds stay cheap.
"""
import dataclasses
import threading

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as model_lib
from repro.core.sparse_ops import SparsityConfig
from repro.kernels import ops as kops
from repro.runtime.errors import (
    DeviceProgramError, EngineFatal, RequestCancelled, RequestFailed,
    ServerOverloaded, is_request_scoped,
)
from repro.runtime.faults import (
    FaultInjector, FaultPlan, InjectedFault, poison_uids_for,
)
from repro.runtime.server import (
    AsyncServer, Request, ServeConfig, Server,
)
from serving_harness import Traffic, make_traffic


@pytest.fixture(scope="module", autouse=True)
def _release_jit_cache():
    """Free this module's compiled step functions at teardown.

    The sweep deliberately shares one engine's jit cache across 20 runs;
    without dropping it afterwards the executables stay live for the rest
    of the pytest process and push later modules' XLA compiles into the
    process's resource ceiling.
    """
    yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def model():
    cfg = get_config("smollm-135m").reduced()
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def sweep_server(model):
    """One engine for the whole sweep: the fault plan swaps per run
    (``sc`` is a frozen dataclass -- replace, don't mutate), the jit
    cache carries over."""
    cfg, params = model
    return Server(cfg, params, ServeConfig(
        batch_slots=2, max_len=64, kv_block_size=4))


def _requests(cfg, seed):
    return [
        Request(uid=r.uid, prompt=r.prompt, max_new=r.max_new)
        for r in make_traffic(cfg, Traffic(
            n_requests=6, prompt_lens=(2, 10), max_new=(2, 5),
            seed=seed))
    ]


# ------------------------------------------------------------- host pieces
def test_fault_plan_validation():
    for bad in (dict(poison_rate=-0.1), dict(step_fault_rate=1.5),
                dict(alloc_exhaust_rate=2.0),
                dict(transient_alloc_rate=-1.0)):
        with pytest.raises(ValueError, match="must be in"):
            FaultPlan(**bad)
    with pytest.raises(ValueError, match="transient_fails"):
        FaultPlan(transient_fails=0)
    with pytest.raises(ValueError, match="max_faults"):
        FaultPlan(max_faults=-1)
    # poison_uids freezes whatever iterable the caller passed.
    assert FaultPlan(poison_uids=[3, 1]).poison_uids == frozenset({1, 3})


def test_injector_decisions_are_call_order_independent():
    """The same plan must fire the same faults whatever order the
    engine visits requests in -- decisions key on (seed, site, uid,
    counter), never on call history."""
    plan = FaultPlan(seed=42, poison_rate=0.3, step_fault_rate=0.1)
    fwd, rev = FaultInjector(plan), FaultInjector(plan)
    uids = list(range(30))
    poison_fwd = {u for u in uids if fwd.poison(u)}
    poison_rev = {u for u in reversed(uids) if rev.poison(u)}
    assert poison_fwd == poison_rev and poison_fwd  # non-empty at 0.3
    grid = [(u, t) for u in range(10) for t in range(10)]
    step_fwd = {k for k in grid if fwd.step_fault_one(*k)}
    step_rev = {k for k in reversed(grid) if rev.step_fault_one(*k)}
    assert step_fwd == step_rev and step_fwd
    # Replays of the whole sweep agree event-for-event as sets.
    assert set(fwd.events) == set(rev.events)


def test_alloc_gate_transient_then_success():
    plan = FaultPlan(seed=0, transient_alloc_rate=1.0, transient_fails=2)
    inj = FaultInjector(plan)
    for attempt in range(2):  # first transient_fails attempts raise
        with pytest.raises(InjectedFault) as ei:
            inj.alloc_gate(uid=5, growth_idx=0, attempt=attempt)
        assert ei.value.transient
    inj.alloc_gate(uid=5, growth_idx=0, attempt=2)  # then clears
    assert inj.injected == 1  # one EVENT however many retries
    assert inj.failed_uids() == frozenset()  # transients aren't failures


def test_max_faults_bounds_the_storm():
    plan = FaultPlan(seed=1, poison_rate=1.0, max_faults=2)
    inj = FaultInjector(plan)
    assert sum(inj.poison(u) for u in range(10)) == 2


def test_poison_uids_for_is_seeded():
    a = poison_uids_for(40, 0.2, seed=7)
    assert a == poison_uids_for(40, 0.2, seed=7)
    assert len(a) == 8 and all(0 <= u < 40 for u in a)
    assert a != poison_uids_for(40, 0.2, seed=8)
    assert poison_uids_for(40, 0.0, seed=7) == frozenset()


def test_is_request_scoped_classification():
    assert is_request_scoped(InjectedFault("poison", 0))
    assert is_request_scoped(ValueError("bad request shape"))
    assert is_request_scoped(FloatingPointError("nan"))
    assert not is_request_scoped(RuntimeError("pool corrupt"))
    assert not is_request_scoped(jax.errors.JaxRuntimeError("device lost"))
    assert not is_request_scoped(DeviceProgramError("decode program"))
    assert not is_request_scoped(EngineFatal("dead"))
    assert not is_request_scoped(KeyboardInterrupt())


# ------------------------------------------------------------- THE sweep
_KINDS = {
    "poison": dict(poison_rate=0.25),
    "step": dict(step_fault_rate=0.04),
    "exhaust": dict(alloc_exhaust_rate=0.12),
    "transient": dict(transient_alloc_rate=0.3),
}
_SEEDS = [0, 1, 2, 3, 4]


def _run_with_plan(srv, cfg, seed, plan):
    srv.sc = dataclasses.replace(srv.sc, faults=plan)
    done = srv.generate(_requests(cfg, seed))
    return done, list(srv.failed)


@pytest.mark.parametrize("kind", sorted(_KINDS))
def test_fault_sweep_preserves_invariants(kind, model, sweep_server):
    """5 seeds x each fault kind: the engine survives every injected
    fault, fails EXACTLY the requests the seeded schedule targets,
    keeps every survivor token-identical to a fault-free run, and ends
    each run with the pool drained and the commitment ledger at zero."""
    cfg, _ = model
    srv = sweep_server
    fired_anywhere = 0
    for seed in _SEEDS:
        # Fault-free reference run for this seed's traffic (engine vs
        # engine: fault ISOLATION means survivors can't tell).
        ref, ref_failed = _run_with_plan(srv, cfg, seed, None)
        assert not ref_failed and len(ref) == 6
        want = {r.uid: np.asarray(r.out) for r in ref}

        done, failed = _run_with_plan(
            srv, cfg, seed, FaultPlan(seed=seed, **_KINDS[kind]))
        inj = srv._inj
        fired_anywhere += inj.injected
        # Accounting: every request ends exactly once.
        assert len(done) + len(failed) == 6
        # EXACT failed set, straight from the seeded schedule.
        assert {r.uid for r in failed} == set(inj.failed_uids())
        if kind == "transient":
            # transient_fails=2 <= the engine's retry budget: every
            # transient recovers invisibly.
            assert not failed
            assert srv.metrics.faults_recovered >= inj.injected
        for r in failed:
            assert isinstance(r.error, RequestFailed)
            assert isinstance(r.error.__cause__, InjectedFault)
        # Survivor parity: fault isolation, token for token.
        for r in done:
            np.testing.assert_array_equal(
                np.asarray(r.out), want[r.uid],
                err_msg=f"kind={kind} seed={seed} uid={r.uid}: a "
                        "fault leaked into a survivor's tokens")
        # Pool invariants re-proven at drain: nothing leaked, nothing
        # double-freed, ledger at zero.
        for a in srv._st.allocs:
            a.check(expect_reserved=0)
            assert a.in_use == 0 and a.reserved == 0
    assert fired_anywhere > 0, (
        f"kind={kind}: no fault fired across seeds {_SEEDS}; the "
        "sweep is vacuous -- raise the rate")


# ----------------------------------------------------- engine-fatal path
def test_non_request_scoped_error_is_engine_fatal(model):
    """A RuntimeError out of the engine step is NOT contained: every
    outstanding handle fails with EngineFatal chaining the cause, and
    further submits are refused."""
    cfg, params = model
    asrv = AsyncServer(cfg, params,
                       ServeConfig(batch_slots=1, max_len=32),
                       start=False)
    h = asrv.submit(np.array([1, 2]), max_new=4)

    def doomed_step():
        raise RuntimeError("pool metadata corrupt")

    asrv._srv.step = doomed_step
    asrv.start()
    with pytest.raises(EngineFatal, match="engine thread died"):
        h.result(timeout=120)
    with pytest.raises(EngineFatal):
        asrv.drain(timeout=120)
    try:
        h.result(timeout=1)
    except EngineFatal as e:
        assert isinstance(e.__cause__, RuntimeError)
        assert "pool metadata corrupt" in str(e)
    with pytest.raises(RuntimeError, match="shut down"):
        asrv.submit(np.array([3]), max_new=1)
    asrv.shutdown(drain=False, timeout=60)


def test_kernel_lowering_error_fails_the_run(model, monkeypatch):
    """A kernel the compiler refuses is engine-fatal, not one failed
    request. On the CPU backend a compiled Pallas kernel is refused at
    lowering with a ValueError -- the type the chip's compiler raises
    for a block it cannot tile -- which a request-scoped classifier
    would read as a malformed prompt and keep serving."""
    cfg, params = model
    monkeypatch.setattr(kops, "interpret_mode", lambda: False)
    srv = Server(cfg, params, ServeConfig(
        batch_slots=2, max_len=32, kv_block_size=4, attn_kernel="paged",
        sparsity=SparsityConfig(enabled=True, mode="kernel", block_m=1)))
    with pytest.raises(DeviceProgramError, match="prefill") as ei:
        srv.generate(_requests(cfg, 0))
    assert isinstance(ei.value.__cause__, ValueError)
    assert not is_request_scoped(ei.value)
    assert not srv.failed


# ------------------------------------------------------------- the hammer
def test_async_hammer_submit_cancel_shutdown(model):
    """Multi-threaded submit/cancel against a bounded queue with step
    faults injected: every handle resolves to exactly one typed
    outcome, the accounting adds up, and the drained pool holds
    nothing."""
    cfg, params = model
    asrv = AsyncServer(cfg, params, ServeConfig(
        batch_slots=2, max_len=64, kv_block_size=8, max_queue_depth=16,
        faults=FaultPlan(seed=3, step_fault_rate=0.02)))
    handles, sheds = [], [0]
    lock = threading.Lock()

    def client(tid):
        rng = np.random.default_rng(tid)
        for _ in range(6):
            prompt = rng.integers(
                0, cfg.vocab_size, int(rng.integers(2, 8)))
            try:
                h = asrv.submit(prompt,
                                max_new=int(rng.integers(1, 5)))
            except ServerOverloaded:
                with lock:
                    sheds[0] += 1
                continue
            if rng.random() < 0.3:
                asrv.cancel(h)
            with lock:
                handles.append(h)

    threads = [threading.Thread(target=client, args=(t,))
               for t in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
        assert not t.is_alive()

    outcomes = {"done": 0, "cancelled": 0, "failed": 0}
    for h in handles:
        try:
            r = h.result(timeout=300)
            assert len(np.asarray(r.out)) == r.max_new
            outcomes["done"] += 1
        except RequestCancelled:
            outcomes["cancelled"] += 1
        except RequestFailed:
            outcomes["failed"] += 1
    asrv.drain(timeout=300)
    m = asrv.metrics
    assert outcomes["done"] == m.completed
    assert outcomes["cancelled"] == m.cancelled
    assert outcomes["failed"] == m.failed_requests
    assert sheds[0] == m.shed_requests
    assert sum(outcomes.values()) == len(handles) == 18 - sheds[0]
    for a in asrv._srv._st.allocs:
        a.check(expect_reserved=0)
        assert a.in_use == 0 and a.reserved == 0
    asrv.shutdown(timeout=60)
