"""Continuous-batching dispatch scheduler differential suite (PR 10).

The AdaptiveDispatchScheduler is the one serving dispatch path; the
contracts under test:

- merged rows are BIT-identical to solo execution across bucket shapes,
  engines (fused and one-partition turbo + blockmax on the interpret-mode
  CPU mesh, a stub), and under injected device faults (PR 5 containment
  semantics);
- SLA tiers: an interactive query never waits past its budget behind a
  deep bulk backlog (the interactive deadline triggers the flush, bulk
  rides the pad slack);
- double buffering: a second batch dispatches while the first batch's
  waiter is still demuxing (slot-1 held), and does NOT with one slot;
- a poisoned batch is retried solo per query, on a stub and on a real
  Turbo engine whose merged dispatch fails;
- `ES_TPU_COALESCE_US=0` disables batching.
"""

import threading
import time

import numpy as np
import pytest

from elasticsearch_tpu.common import faults, metrics
from elasticsearch_tpu.common.errors import DeviceFaultError
from elasticsearch_tpu.threadpool import ThreadPool, tier_for_request
from elasticsearch_tpu.threadpool.scheduler import (
    DEFAULT_BUCKETS, TIER_BULK, TIER_INTERACTIVE, AdaptiveDispatchScheduler,
    _Lane, _parse_buckets, _Waiter, activate_tier, current_tier,
    default_scheduler,
)

pytestmark = [pytest.mark.multidevice]

WORDS = ["alpha", "beta", "gamma", "delta", "epsilon", "zeta", "eta",
         "theta", "iota", "kappa", "lam", "mu", "nu", "xi", "omicron", "pi"]

QUERIES = [["alpha"], ["beta", "gamma"], ["delta"], ["pi", "omicron"],
           ["mu", "nu", "xi"], ["kappa"], ["theta", "iota"], ["zeta", "eta"]]


def _build_index(monkeypatch, *, turbo: bool, uuid: str,
                 one_segment: bool = False):
    from elasticsearch_tpu.cluster.state import IndexMetadata
    from elasticsearch_tpu.common.settings import Settings
    from elasticsearch_tpu.index.index_service import IndexService

    if turbo:
        monkeypatch.setenv("ES_TPU_FORCE_TURBO", "1")
        monkeypatch.setenv("ES_TPU_TURBO_COLD_DF", "8")
    meta = IndexMetadata(
        index="sched_" + uuid, uuid=uuid, settings=Settings({}),
        mappings={"properties": {"body": {"type": "text"}}})
    svc = IndexService(meta)
    rng = np.random.default_rng(99)
    for i in range(320):
        words = rng.choice(WORDS, size=int(rng.integers(3, 16)))
        svc.index_doc(str(i), {"body": " ".join(words)})
        if i == 140 and not one_segment:
            svc.refresh()
    for i in range(0, 50, 9):
        svc.delete_doc(str(i))
    svc.refresh()
    return svc


def _concurrent_sched(sched, eng, queries, k=10, tiers=None, fault_logs=None):
    """Each query on its own thread, all released together; returns
    (results, errors) aligned with `queries`."""
    results = [None] * len(queries)
    errors = [None] * len(queries)
    barrier = threading.Barrier(len(queries))

    def worker(i, q):
        try:
            barrier.wait(timeout=10)
            results[i] = sched.dispatch(
                eng, [q], k,
                tier=tiers[i] if tiers else None,
                fault_log=fault_logs[i] if fault_logs else None)
        except BaseException as e:  # noqa: BLE001 — asserted by callers
            errors[i] = e

    threads = [threading.Thread(target=worker, args=(i, q))
               for i, q in enumerate(queries)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    return results, errors


def _assert_rows_equal(got, want, ctx):
    gs, gp, go = got
    ws, wp, wo = want
    assert np.array_equal(gs, ws), ctx
    assert np.array_equal(gp, wp), ctx
    assert np.array_equal(go, wo), ctx


class _StubEngine:
    """search_many stub: deterministic per-query rows."""

    kind = "stub"

    def __init__(self):
        self.calls = []

    def search_many(self, batches, k=10, check=None):
        qs = batches[0]
        self.calls.append(len(qs))
        out_s = np.zeros((len(qs), k), np.float32)
        out_p = np.zeros((len(qs), k), np.int32)
        out_o = np.zeros((len(qs), k), np.int32)
        for i, q in enumerate(qs):
            out_s[i, 0] = float(len(q[0])) + 1.0
            out_o[i, 0] = len(q[0])
        return [(out_s, out_p, out_o)]


class _Poisoned:
    """An engine whose device dispatch fails as `fails(queries)` says (a
    merged batch, a poison term, always); what it does answer are the
    wrapped engine's own rows."""

    def __init__(self, inner, fails):
        self.inner = inner
        self.fails = fails
        self.calls = []

    def search_many(self, batches, k=10, check=None):
        qs = batches[0]
        self.calls.append(len(qs))
        if self.fails(qs):
            raise DeviceFaultError(f"poisoned dispatch of {qs}",
                                   site="turbo_sweep")
        return self.inner.search_many(batches, k=k)


@pytest.fixture(scope="module")
def turbo_eng():
    """One fused Turbo engine (two partitions) for the module's
    containment tests."""
    with pytest.MonkeyPatch.context() as mp:   # the knobs: build only
        svc = _build_index(mp, turbo=True, uuid="u_sc_mod")
        eng = svc.serving.snapshot().engine("body")
    try:
        assert eng.kind == "turbo"
        yield eng
    finally:
        svc.close()


@pytest.fixture(params=["stub", "turbo"])
def inner_eng(request):
    """The engine under a `_Poisoned` wrapper: the stub, or the real one."""
    if request.param == "stub":
        return _StubEngine()
    return request.getfixturevalue("turbo_eng")


# ---------------------------------------------------------------------------
# knob parsing + SLA-tier classification and propagation
# ---------------------------------------------------------------------------


def test_parse_buckets_knob():
    assert _parse_buckets("1,4,16,64,256") == (1, 4, 16, 64, 256)
    assert _parse_buckets(" 16, 4 ,4,1 ") == (1, 4, 16)     # dedup + sort
    assert _parse_buckets("8") == (8,)
    # malformed / empty / non-positive specs fall back to the default
    assert _parse_buckets("banana") == DEFAULT_BUCKETS
    assert _parse_buckets("") == DEFAULT_BUCKETS
    assert _parse_buckets("0,-4") == DEFAULT_BUCKETS
    assert _parse_buckets("-4,0,2") == (2,)                 # keeps positives


def test_tier_for_request_classification():
    assert tier_for_request("POST", "/idx/_search") == TIER_INTERACTIVE
    assert tier_for_request("GET", "/idx/_doc/1") == TIER_INTERACTIVE
    assert tier_for_request("GET", "/idx/_mget") == TIER_INTERACTIVE
    # batch/scan-shaped search endpoints default to bulk
    assert tier_for_request("POST", "/_msearch") == TIER_BULK
    assert tier_for_request("POST", "/_search/scroll") == TIER_BULK
    assert tier_for_request("POST", "/idx/_async_search") == TIER_BULK
    assert tier_for_request("GET", "/idx/_rank_eval") == TIER_BULK
    # non-search stages are bulk
    assert tier_for_request("POST", "/idx/_bulk") == TIER_BULK
    assert tier_for_request("GET", "/_cluster/health") == TIER_BULK
    # an explicit sla param always wins; junk values are ignored
    assert tier_for_request("POST", "/idx/_search",
                            {"sla": "bulk"}) == TIER_BULK
    assert tier_for_request("POST", "/idx/_bulk",
                            {"sla": "interactive"}) == TIER_INTERACTIVE
    assert tier_for_request("POST", "/idx/_search",
                            {"sla": "platinum"}) == TIER_INTERACTIVE


def test_tier_context_rides_pool_submissions():
    assert current_tier() == TIER_INTERACTIVE        # safe default
    with activate_tier(TIER_BULK):
        assert current_tier() == TIER_BULK
        with activate_tier(None):                    # unknown: passthrough
            assert current_tier() == TIER_BULK
        with activate_tier(TIER_INTERACTIVE):
            assert current_tier() == TIER_INTERACTIVE
        assert current_tier() == TIER_BULK
    assert current_tier() == TIER_INTERACTIVE

    # the submitter's tier crosses the executor thread hop like the trace
    pool = ThreadPool(sizes={"search": 1})
    try:
        with activate_tier(TIER_BULK):
            task = pool.submit("search", current_tier)
        assert task.get(timeout=10) == TIER_BULK
        assert pool.submit("search", current_tier).get(timeout=10) \
            == TIER_INTERACTIVE
    finally:
        pool.shutdown()


# ---------------------------------------------------------------------------
# bucket selection (white-box: the flush decision function)
# ---------------------------------------------------------------------------


def _waiter(nq, tier, age, now):
    w = _Waiter([["q"]] * nq, tier)
    w.enqueued = now - age
    return w


def test_build_batch_flush_rules():
    sched = AdaptiveDispatchScheduler(buckets=(1, 4, 16),
                                      interactive_us=1000.0,
                                      bulk_us=8000.0)
    lane = _Lane(object(), 10, ("e", 10), inflight=2)
    now = time.monotonic()

    # nothing due, top bucket not full: keep waiting
    lane.queue = [_waiter(1, TIER_BULK, 0.001, now)]
    batch, depth = sched._build_batch(lane, now)
    assert batch is None and depth == 1 and len(lane.queue) == 1

    # one interactive past its 1ms budget flushes alone in bucket 1; the
    # not-yet-due bulk waiter stays parked (no slack in a 1-wide bucket)
    lane.queue = [_waiter(1, TIER_BULK, 0.001, now),
                  _waiter(1, TIER_INTERACTIVE, 0.002, now)]
    batch, depth = sched._build_batch(lane, now)
    assert depth == 2 and batch.bucket == 1
    assert [w.tier for w in batch.waiters] == [TIER_INTERACTIVE]
    assert [w.tier for w in lane.queue] == [TIER_BULK]

    # a 2-query due waiter needs bucket 4; parked bulk singles back-fill
    # the pad slack FIFO instead of widening the bucket
    lane.queue = [_waiter(1, TIER_BULK, 0.001, now),
                  _waiter(1, TIER_BULK, 0.0005, now),
                  _waiter(1, TIER_BULK, 0.0001, now),
                  _waiter(2, TIER_INTERACTIVE, 0.002, now)]
    batch, depth = sched._build_batch(lane, now)
    assert depth == 5 and batch.bucket == 4
    assert len(batch.queries) == 4                  # 2 due + 2 riders
    assert batch.waiters[0].tier == TIER_INTERACTIVE
    assert len(lane.queue) == 1                     # third bulk overflows

    # top bucket full flushes everything even with nothing due
    lane.queue = [_waiter(4, TIER_BULK, 0.0001, now) for _ in range(4)]
    batch, depth = sched._build_batch(lane, now)
    assert depth == 16 and batch.bucket == 16
    assert len(batch.queries) == 16 and not lane.queue

    # due backlog wider than the top bucket: flush caps at the ladder top
    # and the overflow stays due for an immediate next flush
    lane.queue = [_waiter(4, TIER_INTERACTIVE, 0.01, now) for _ in range(5)]
    batch, depth = sched._build_batch(lane, now)
    assert depth == 20 and batch.bucket == 16
    assert len(batch.queries) == 16 and len(lane.queue) == 1


def test_a_waiter_wider_than_the_ladder_goes_alone():
    """A ladder that autotuned down to (1,) still has to dispatch an
    `_msearch` of four bodies: the waiter fits no bucket, so it flushes
    alone at its own width (it used to wait for ever behind flushes of
    no waiter at all)."""
    sched = AdaptiveDispatchScheduler(buckets=(1,), interactive_us=1000.0,
                                      bulk_us=8000.0)
    lane = _Lane(object(), 10, ("e", 10), inflight=2)
    now = time.monotonic()
    lane.queue = [_waiter(4, TIER_BULK, 0.01, now),
                  _waiter(1, TIER_BULK, 0.01, now)]
    batch, depth = sched._build_batch(lane, now)
    assert depth == 5 and batch.bucket == 4
    assert [len(w.queries) for w in batch.waiters] == [4]
    assert [len(w.queries) for w in lane.queue] == [1]
    batch, _ = sched._build_batch(lane, now)
    assert batch.bucket == 1 and not lane.queue


# ---------------------------------------------------------------------------
# bit-identity with solo execution (real engines, interpret mode)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["turbo", "turbo_solo", "blockmax", "stub"])
def test_scheduled_rows_bit_identical_to_solo(monkeypatch, kind):
    """Eight concurrent singles merged by the scheduler read the rows
    solo execution reads, on every engine kind: the fused two-partition
    Turbo engine, the one-partition one, BlockMax, and a stub whose rows
    differ by query (the demux offsets alone)."""
    monkeypatch.setenv("ES_TPU_COALESCE_US", "300000")
    svc = None if kind == "stub" else _build_index(
        monkeypatch, turbo=kind != "blockmax", uuid="u_sc1" + kind,
        one_segment=kind == "turbo_solo")
    try:
        if svc is None:
            eng = _StubEngine()
        else:
            eng = svc.serving.snapshot().engine("body")
            assert eng.kind == ("blockmax" if kind == "blockmax" else "turbo")
            assert len(getattr(eng, "turbos", [None])) == \
                (2 if kind == "turbo" else 1)
        solo = [eng.search_many([[q]], k=10)[0] for q in QUERIES]

        # generous budgets + a ladder topping at len(QUERIES): all eight
        # concurrent singles merge into exactly ONE bucket-8 flush
        sched = AdaptiveDispatchScheduler(buckets=(len(QUERIES),),
                                          interactive_us=400000.0,
                                          bulk_us=400000.0)
        results, errors = _concurrent_sched(sched, eng, QUERIES)
        assert errors == [None] * len(QUERIES)
        for q, got, want in zip(QUERIES, results, solo):
            _assert_rows_equal(got, want, f"merged {q}")
        st = sched.stats()
        assert st["sched_dispatches"] == 1
        assert st["sched_queries"] == len(QUERIES)
        assert st["largest_batch"] == len(QUERIES)
        assert st["bucket_counts"] == {str(len(QUERIES)): 1}

        # zero budgets: every waiter is due on arrival, so flushes split
        # across small buckets of the default ladder — still bit-identical
        sched0 = AdaptiveDispatchScheduler(buckets=DEFAULT_BUCKETS,
                                           interactive_us=0.0, bulk_us=0.0)
        results0, errors0 = _concurrent_sched(sched0, eng, QUERIES)
        assert errors0 == [None] * len(QUERIES)
        for q, got, want in zip(QUERIES, results0, solo):
            _assert_rows_equal(got, want, f"split {q}")
        st0 = sched0.stats()
        assert st0["sched_queries"] == len(QUERIES)
        assert 1 <= st0["sched_dispatches"] <= len(QUERIES)
    finally:
        if svc is not None:
            svc.close()


def test_scheduler_primes_engine_bucket_shapes(monkeypatch):
    monkeypatch.setenv("ES_TPU_COALESCE_US", "300000")
    svc = _build_index(monkeypatch, turbo=True, uuid="u_sc_prime")
    try:
        eng = svc.serving.snapshot().engine("body")
        base = set(eng.qc_sizes)
        pad_before = metrics.summary("coalesce_pad_ratio")["count"]
        sched = AdaptiveDispatchScheduler(buckets=(1, 4, 16, 64),
                                          interactive_us=0.0, bulk_us=0.0)
        got = sched.dispatch(eng, [QUERIES[0]], 10)
        # the ladder lands in the engine's compiled-width cache, rounded
        # up to ROWS_PER_STEP multiples like the constructor's qc_sizes
        assert {8, 16, 64} <= set(eng.qc_sizes)
        assert set(eng.qc_sizes) >= base
        assert list(eng.qc_sizes) == sorted(set(eng.qc_sizes))
        # pad-waste is recorded at the device-dispatch site for the
        # scheduler path too (the engine now exposes qc_sizes)
        assert metrics.summary("coalesce_pad_ratio")["count"] > pad_before
        _assert_rows_equal(got, eng.search_many([[QUERIES[0]]], k=10)[0],
                           "primed")
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# SLA tiers: interactive latency under a deep bulk backlog
# ---------------------------------------------------------------------------


def test_interactive_budget_flushes_past_parked_bulk():
    eng = _StubEngine()
    # bulk may wait 10s; interactive must flush within ~8ms
    sched = AdaptiveDispatchScheduler(buckets=(4,),
                                      interactive_us=8000.0,
                                      bulk_us=10_000_000.0, inflight=2)
    results = [None] * 4
    done = [threading.Event() for _ in range(4)]

    def run(i, tier):
        results[i] = sched.dispatch(eng, [[f"q{i}"]], 10, tier=tier)
        done[i].set()

    threads = [threading.Thread(target=run, args=(i, TIER_BULK))
               for i in range(3)]
    for t in threads:
        t.start()
    time.sleep(0.1)
    assert eng.calls == []                  # bulk parked, nothing flushed
    t0 = time.monotonic()
    run(3, TIER_INTERACTIVE)
    interactive_wait = time.monotonic() - t0
    # the interactive deadline triggered the flush, and the parked bulk
    # waiters rode the pad slack of its bucket instead of waiting out
    # their own 10s budget
    assert interactive_wait < 2.0
    for i in range(3):
        assert done[i].wait(5), f"bulk waiter {i} still parked"
    assert eng.calls == [4]                 # ONE merged bucket-4 flush
    for i in range(4):
        assert float(results[i][0][0, 0]) == len(f"q{i}") + 1.0
    st = sched.stats()
    assert st["tiers"][TIER_INTERACTIVE]["dispatches"] == 1
    assert st["tiers"][TIER_BULK]["dispatches"] == 3
    assert st["bucket_counts"] == {"4": 1}


# ---------------------------------------------------------------------------
# double buffering: two in-flight slots overlap demux with the next sweep
# ---------------------------------------------------------------------------


def _blocked_waiter(sched, eng):
    """Dispatch one query whose boundary check parks: returns (thread,
    parked_event, release_event, result_box). The entry check is call 1;
    the boundary check (call 2) blocks — the waiter holds its batch's
    in-flight slot until released."""
    parked = threading.Event()
    release = threading.Event()
    box = {}
    calls = {"n": 0}

    def check():
        calls["n"] += 1
        if calls["n"] == 2:
            parked.set()
            assert release.wait(20)

    def run():
        box["rows"] = sched.dispatch(eng, [["aa"]], 10, check=check)

    t = threading.Thread(target=run)
    t.start()
    return t, parked, release, box


def test_double_buffer_dispatches_while_demux_in_flight():
    eng = _StubEngine()
    sched = AdaptiveDispatchScheduler(buckets=(1,), interactive_us=0.0,
                                      bulk_us=0.0, inflight=2)
    t_a, parked, release, box = _blocked_waiter(sched, eng)
    assert parked.wait(10)                  # batch A done, slot 1 held
    assert sched.stats()["inflight"] == 1
    # batch B dispatches and completes on slot 2 while A is still demuxing
    rows_b = sched.dispatch(eng, [["bbb"]], 10)
    assert float(rows_b[0][0, 0]) == 4.0
    assert t_a.is_alive()
    st = sched.stats()
    assert st["max_inflight"] == 2          # the overlap was real
    release.set()
    t_a.join(timeout=10)
    assert not t_a.is_alive()
    assert float(box["rows"][0][0, 0]) == 3.0
    assert sched.stats()["inflight"] == 0


def test_single_slot_serializes_behind_unconsumed_batch():
    eng = _StubEngine()
    sched = AdaptiveDispatchScheduler(buckets=(1,), interactive_us=0.0,
                                      bulk_us=0.0, inflight=1)
    t_a, parked, release, box = _blocked_waiter(sched, eng)
    assert parked.wait(10)
    done_b = threading.Event()
    rows = {}

    def run_b():
        rows["b"] = sched.dispatch(eng, [["bbb"]], 10)
        done_b.set()

    t_b = threading.Thread(target=run_b)
    t_b.start()
    # with ONE slot, B's device dispatch must wait for A's consume
    assert not done_b.wait(0.4)
    assert eng.calls == [1]
    release.set()
    assert done_b.wait(10)
    t_a.join(timeout=10)
    t_b.join(timeout=10)
    assert eng.calls == [1, 1]
    assert float(rows["b"][0][0, 0]) == 4.0
    assert sched.stats()["max_inflight"] == 1


# ---------------------------------------------------------------------------
# poison-batch containment: a failed merged dispatch is retried solo, on a
# stub and on a real Turbo engine (`inner_eng`)
# ---------------------------------------------------------------------------

POISON_QUERIES = [["alpha"], ["beta", "gamma"], ["delta"]]


def _poison_sched():
    return AdaptiveDispatchScheduler(buckets=(len(POISON_QUERIES),),
                                     interactive_us=400000.0,
                                     bulk_us=400000.0)


def test_poison_batch_retries_each_waiter_solo(inner_eng):
    eng = _Poisoned(inner_eng, lambda qs: len(qs) > 1)
    solo = [inner_eng.search_many([[q]], k=10)[0] for q in POISON_QUERIES]
    sched = _poison_sched()
    results, errors = _concurrent_sched(sched, eng, POISON_QUERIES)
    assert errors == [None, None, None]
    for q, got, want in zip(POISON_QUERIES, results, solo):
        _assert_rows_equal(got, want, f"solo retry {q}")
    assert sched.stats()["sched_batch_retries"] == 1
    # one failed merged dispatch + one solo retry per waiter
    assert sorted(eng.calls) == [1, 1, 1, 3]


def test_poison_query_error_isolated_to_its_waiter(inner_eng):
    # the poison term kills merged AND its own solo retry; peers succeed
    eng = _Poisoned(inner_eng, lambda qs: any("beta" in q for q in qs))
    solo = [inner_eng.search_many([[q]], k=10)[0] for q in POISON_QUERIES]
    sched = _poison_sched()
    results, errors = _concurrent_sched(sched, eng, POISON_QUERIES)
    for i, (r, e) in enumerate(zip(results, errors)):
        if "beta" in POISON_QUERIES[i]:
            assert isinstance(e, DeviceFaultError) and r is None
        else:
            assert e is None
            _assert_rows_equal(r, solo[i], f"peer {POISON_QUERIES[i]}")
    assert sched.stats()["sched_batch_retries"] == 1


def test_all_retries_failing_surfaces_original_error(inner_eng):
    eng = _Poisoned(inner_eng, lambda qs: True)
    sched = _poison_sched()
    results, errors = _concurrent_sched(sched, eng, POISON_QUERIES)
    assert results == [None, None, None]
    assert all(isinstance(e, DeviceFaultError) for e in errors)
    # every waiter sees the ORIGINAL (merged) failure, not its retry's
    assert all("alpha" in str(e) and "delta" in str(e) for e in errors)
    assert sorted(eng.calls) == [1, 1, 1, 3]


@pytest.mark.faults
def test_scheduler_contains_injected_device_fault(monkeypatch):
    """ES_TPU_FAULTS-style device faults under a merged scheduler
    dispatch: the serving engine's fused dispatch faults AND any
    per-partition turbo_sweep fallback faults too, so PR 5 containment
    re-scores the work through the host tier — rows stay bit-identical
    and the FaultRecords are ferried to EVERY waiter's fault_log."""
    monkeypatch.setenv("ES_TPU_COALESCE_US", "300000")
    svc = _build_index(monkeypatch, turbo=True, uuid="u_sc_flt")
    try:
        eng = svc.serving.snapshot().engine("body")
        queries = QUERIES[:4]
        solo = [eng.search_many([[q]], k=10)[0] for q in queries]
        sched = AdaptiveDispatchScheduler(buckets=(4,),
                                          interactive_us=400000.0,
                                          bulk_us=400000.0)
        flogs = [[] for _ in queries]
        with faults.inject("fused_dispatch:raise@1;turbo_sweep:raisexinf"):
            results, errors = _concurrent_sched(sched, eng, queries,
                                                fault_logs=flogs)
        assert errors == [None] * len(queries)
        for q, got, want in zip(queries, results, solo):
            _assert_rows_equal(got, want, f"fault-contained {q}")
        for flog in flogs:
            assert flog, "fault records must reach every waiter"
            assert all(f.site in ("fused_dispatch", "turbo_sweep")
                       for f in flog)
        # contained, not retried: the engine absorbed the fault in-dispatch
        assert sched.stats()["sched_batch_retries"] == 0
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# the window-0 kill switch
# ---------------------------------------------------------------------------


def test_window_zero_disables_batching_entirely(monkeypatch):
    eng = _StubEngine()
    monkeypatch.setenv("ES_TPU_COALESCE_US", "0")
    sched = AdaptiveDispatchScheduler(buckets=(8,))
    before = sched.stats()
    out = sched.dispatch(eng, [["a"]], 10)
    assert float(out[0][0, 0]) == 2.0
    st = sched.stats()
    assert st["direct_dispatches"] == before["direct_dispatches"] + 1
    assert st["sched_dispatches"] == before["sched_dispatches"]
    assert st["lanes"] == 0                 # no lane thread was started
    assert eng.calls == [1]


# ---------------------------------------------------------------------------
# serving path end to end through the adaptive scheduler
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("turbo", [True, False], ids=["turbo", "blockmax"])
def test_serving_path_batches_through_scheduler(monkeypatch, turbo):
    """End to end through ServingContext.try_search: concurrent
    REST-level singles return the same responses as solo execution and
    the process-default scheduler reports the merged device dispatches,
    whichever engine the snapshot selected."""
    svc = _build_index(monkeypatch, turbo=turbo, uuid="u_sc_e2e" + str(turbo))
    try:
        assert svc.serving.snapshot().engine("body").kind == \
            ("turbo" if turbo else "blockmax")
        bodies = [{"query": {"match": {"body": " ".join(q)}}}
                  for q in QUERIES]
        monkeypatch.setenv("ES_TPU_COALESCE_US", "0")
        want = [svc.serving.try_search(b, "query_then_fetch")
                for b in bodies]
        assert all(w is not None for w in want)

        monkeypatch.setenv("ES_TPU_COALESCE_US", "300000")
        monkeypatch.setenv("ES_TPU_SCHED_BUCKETS", str(len(bodies)))
        monkeypatch.setenv("ES_TPU_SCHED_INTERACTIVE_US", "300000")
        monkeypatch.setenv("ES_TPU_SCHED_BULK_US", "300000")
        before = default_scheduler().stats()
        got = [None] * len(bodies)
        errors = []
        barrier = threading.Barrier(len(bodies))

        def worker(i, b):
            try:
                barrier.wait(timeout=10)
                got[i] = svc.serving.try_search(b, "query_then_fetch")
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(i, b))
                   for i, b in enumerate(bodies)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors
        after = default_scheduler().stats()
        flushes = after["sched_dispatches"] - before["sched_dispatches"]
        merged = after["sched_queries"] - before["sched_queries"]
        assert merged == len(bodies)
        assert 1 <= flushes < len(bodies)   # real merging happened
        # no explicit tier: serving threads default to interactive
        assert after["tiers"][TIER_INTERACTIVE]["dispatches"] \
            - before["tiers"][TIER_INTERACTIVE]["dispatches"] == len(bodies)
        for b, g, w in zip(bodies, got, want):
            assert g is not None, b
            assert [h["_id"] for h in g["hits"]["hits"]] == \
                [h["_id"] for h in w["hits"]["hits"]], b
            assert [h["_score"] for h in g["hits"]["hits"]] == \
                [h["_score"] for h in w["hits"]["hits"]], b
            assert g["hits"]["total"] == w["hits"]["total"], b
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# bucket-ladder autotune (PR 16): knob unset -> ladder derived from the
# observed flush-time demand histograms
# ---------------------------------------------------------------------------


def test_derive_ladder_from_synthetic_histograms():
    from elasticsearch_tpu.threadpool.scheduler import _derive_ladder

    depth = {"count": 500, "p50": 4, "p90": 32, "p99": 64, "max": 200}
    # rungs at the depth percentiles + rounded-up max, anchored at 1
    assert _derive_ladder(depth, None) == (1, 4, 32, 64, 256)
    # low pad waste: no densification
    assert _derive_ladder(depth, {"count": 500, "p90": 0.1}) == \
        (1, 4, 32, 64, 256)
    # persistent pad waste adds geometric midpoints into the wide gaps
    assert _derive_ladder(depth, {"count": 500, "p90": 0.6}) == \
        (1, 2, 4, 16, 32, 64, 128, 256)
    # the cap bounds the largest compiled shape
    assert _derive_ladder({"count": 100, "p50": 1024, "p90": 2048,
                           "p99": 4096, "max": 4000}, None)[-1] == 512


def test_autotune_ladder_pins_synthetic_trace(monkeypatch):
    """Knob unset: the ladder stays at DEFAULT_BUCKETS until enough
    flushes are observed, then pins to the demand-derived rungs for a
    bimodal synthetic trace (singles + ~48-deep bursts) and caches."""
    monkeypatch.delenv("ES_TPU_SCHED_BUCKETS", raising=False)
    metrics.reset_for_tests()
    sched = AdaptiveDispatchScheduler()
    assert sched.ladder() == DEFAULT_BUCKETS      # under-observed
    for _ in range(100):
        metrics.observe("sched_queue_depth", 1)
    for _ in range(40):
        metrics.observe("sched_queue_depth", 48)
    lad = sched.ladder()
    assert lad == (1, 64)        # p50 bucket bound 1, burst bound 64
    assert sched.ladder() is lad or sched.ladder() == lad   # cached
    st = sched.stats()
    assert st["bucket_source"] == "auto"
    assert st["buckets"] == [1, 64]
    # an explicit knob immediately overrides the autotuner
    monkeypatch.setenv("ES_TPU_SCHED_BUCKETS", "2,8")
    assert sched.ladder() == (2, 8)
    assert sched.stats()["bucket_source"] == "knob"


def test_prime_reprimes_on_ladder_change():
    """The primed-ladder guard: an unchanged ladder never re-primes, a
    changed one pushes the new rungs into the engine's compiled widths
    before any flush can use them."""

    class _Eng:
        def __init__(self):
            self.calls = []

        def extend_qc_sizes(self, sizes):
            self.calls.append(tuple(sizes))

    sched = AdaptiveDispatchScheduler(buckets=(1, 4))
    e = _Eng()
    sched._prime_engine(e)
    sched._prime_engine(e)                        # no ladder change
    assert e.calls == [(1, 4)]
    sched._buckets = (1, 4, 32)                   # ladder re-derived
    sched._prime_engine(e)
    assert e.calls == [(1, 4), (1, 4, 32)]
