"""Cancellation differential suite (PR 11).

The task plane's boundary-only cancellation contract, verified
differentially against never-cancelled references:

- a cancelled task parked inside a scheduler lane raises
  TaskCancelledError at the flush boundary — it never fails the batch;
- the co-batched peers of a cancelled waiter, cancelled while parked or
  while its batch is on the device, produce rows BIT-identical to solo
  execution;
- re-running the cancelled query under a fresh task matches the
  never-cancelled reference exactly;
- a mixed round — injected ES_TPU_FAULTS device faults + a mid-park
  cancel — stays green: the fault is contained (PR 5), the cancel kills
  exactly one waiter, everyone else is bit-identical.
"""

import threading
import time

import numpy as np
import pytest

from elasticsearch_tpu.common import faults
from elasticsearch_tpu.tasks import TaskCancelledError, TaskManager
from elasticsearch_tpu.tasks import task_manager as _taskmgr
from elasticsearch_tpu.threadpool.scheduler import AdaptiveDispatchScheduler

pytestmark = [pytest.mark.multidevice]

WORDS = ["alpha", "beta", "gamma", "delta", "epsilon", "zeta", "eta",
         "theta", "iota", "kappa", "lam", "mu", "nu", "xi", "omicron", "pi"]

QUERIES = [["alpha"], ["beta", "gamma"], ["delta"], ["pi", "omicron"]]


@pytest.fixture(scope="module")
def svc():
    import os

    from elasticsearch_tpu.cluster.state import IndexMetadata
    from elasticsearch_tpu.common.settings import Settings
    from elasticsearch_tpu.index.index_service import IndexService

    os.environ["ES_TPU_FORCE_TURBO"] = "1"
    os.environ["ES_TPU_TURBO_COLD_DF"] = "8"
    try:
        meta = IndexMetadata(
            index="cdiff", uuid="u_cdiff", settings=Settings({}),
            mappings={"properties": {"body": {"type": "text"}}})
        svc = IndexService(meta)
        rng = np.random.default_rng(17)
        for i in range(280):
            words = rng.choice(WORDS, size=int(rng.integers(3, 14)))
            svc.index_doc(str(i), {"body": " ".join(words)})
            if i == 130:
                svc.refresh()
        svc.refresh()
        yield svc
        svc.close()
    finally:
        os.environ.pop("ES_TPU_FORCE_TURBO", None)
        os.environ.pop("ES_TPU_TURBO_COLD_DF", None)


@pytest.fixture(scope="module")
def eng(svc):
    return svc.serving.snapshot().engine("body")


@pytest.fixture(scope="module")
def solo(eng):
    return [eng.search_many([[q]], k=10)[0] for q in QUERIES]


def _rows_equal(got, want, label):
    gs, gp, go = got
    ws, wp, wo = want
    assert np.array_equal(np.asarray(gs), np.asarray(ws)), f"{label}: scores"
    assert np.array_equal(np.asarray(gp), np.asarray(wp)), f"{label}: parts"
    assert np.array_equal(np.asarray(go), np.asarray(wo)), f"{label}: ords"


def _run_round(dispatcher, eng, tm, cancel_idx=None, cancel_delay_s=0.05,
               k=10, canceller=None):
    """All QUERIES on their own threads under registered tasks, released
    together; optionally cancel one task after it parks, or hand the
    tasks to `canceller`, which cancels when it sees fit. Returns
    (results, errors, tasks) aligned with QUERIES."""
    n = len(QUERIES)
    results, errors = [None] * n, [None] * n
    tasks = [tm.register("indices:data/read/search", f"q{i}")
             for i in range(n)]
    barrier = threading.Barrier(n + (1 if cancel_idx is not None else 0))

    def worker(i):
        try:
            with _taskmgr.activate(tasks[i]):
                barrier.wait(timeout=10)
                results[i] = dispatcher.dispatch(eng, [QUERIES[i]], k)
        except BaseException as e:  # noqa: BLE001 — asserted by callers
            errors[i] = e
        finally:
            tm.unregister(tasks[i])

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    if cancel_idx is not None:
        barrier.wait(timeout=10)
        time.sleep(cancel_delay_s)      # let the waiters park in the lane
        tm.cancel(tasks[cancel_idx].id, "differential test")
    if canceller is not None:
        canceller(tasks)
    for t in threads:
        t.join(timeout=60)
    return results, errors, tasks


def _window_sched():
    # a wide flush budget AND a bucket the round can't fill, so every
    # waiter genuinely parks long enough for the canceller to fire
    return AdaptiveDispatchScheduler(buckets=(8,), interactive_us=250000.0,
                                     bulk_us=250000.0)


def test_precancelled_task_refused_at_dispatch_entry(eng, monkeypatch):
    monkeypatch.setenv("ES_TPU_COALESCE_US", "250000")
    tm = TaskManager("n")
    t = tm.register("indices:data/read/search", "pre")
    t.cancel("before dispatch")
    sched = _window_sched()
    with _taskmgr.activate(t):
        with pytest.raises(TaskCancelledError):
            sched.dispatch(eng, [QUERIES[0]], 10)
    assert sched.stats()["sched_dispatches"] == 0
    assert sched.stats()["direct_dispatches"] == 0


def test_cancel_parked_scheduler_waiter_spares_peers(eng, solo, monkeypatch):
    monkeypatch.setenv("ES_TPU_COALESCE_US", "250000")
    tm = TaskManager("n")
    results, errors, _ = _run_round(_window_sched(), eng, tm, cancel_idx=2)
    assert isinstance(errors[2], TaskCancelledError)
    assert results[2] is None
    for i in (0, 1, 3):
        assert errors[i] is None, f"peer {i} must survive the cancel"
        _rows_equal(results[i], solo[i], f"peer {i}")
    st = tm.stats()
    # `completed` counts every unregister; `cancelled` is the subset
    assert st["cancelled"] == 1 and st["completed"] == 4
    assert st["current"] == {}


def test_cancel_in_flight_batch_member(eng, solo, monkeypatch):
    """A cancel that lands AFTER the flush, while the merged batch is on
    the device: the batch runs to its end, the cancelled member alone
    raises at its boundary, the peers read their solo rows."""
    monkeypatch.setenv("ES_TPU_COALESCE_US", "250000")
    tm = TaskManager("n")
    on_device, cancelled = threading.Event(), threading.Event()

    class _Held:
        """`eng`, its dispatch held on the device until the cancel lands."""

        def search_many(self, batches, k=10, check=None):
            on_device.set()
            assert cancelled.wait(20)
            return eng.search_many(batches, k=k)

    # a bucket the round fills exactly: the flush is immediate
    sched = AdaptiveDispatchScheduler(buckets=(len(QUERIES),),
                                      interactive_us=250000.0,
                                      bulk_us=250000.0)

    def cancel_on_device(tasks):
        assert on_device.wait(20)
        tm.cancel(tasks[1].id, "differential test")
        cancelled.set()

    results, errors, _ = _run_round(sched, _Held(), tm,
                                    canceller=cancel_on_device)
    st = sched.stats()
    assert st["sched_dispatches"] == 1 and st["sched_batch_retries"] == 0
    assert isinstance(errors[1], TaskCancelledError)
    assert results[1] is None
    for i in (0, 2, 3):
        assert errors[i] is None
        _rows_equal(results[i], solo[i], f"in-flight peer {i}")


def test_rerun_after_cancel_matches_never_cancelled_reference(
        eng, solo, monkeypatch):
    monkeypatch.setenv("ES_TPU_COALESCE_US", "250000")
    tm = TaskManager("n")
    sched = _window_sched()
    _, errors, _ = _run_round(sched, eng, tm, cancel_idx=0)
    assert isinstance(errors[0], TaskCancelledError)
    # identical re-run under a fresh task: bit-identical to the quiet
    # reference — a cancel must leave no residue in the lane state
    t = tm.register("indices:data/read/search", "rerun")
    with _taskmgr.activate(t):
        got = sched.dispatch(eng, [QUERIES[0]], 10)
    tm.unregister(t)
    _rows_equal(got, solo[0], "rerun")


@pytest.mark.faults
def test_mixed_cancel_and_device_fault_round_green(eng, solo, monkeypatch):
    """One injected fused-dispatch fault (contained by PR 5 host
    re-score) AND one mid-park cancel in the same round: the cancelled
    waiter dies alone, every survivor is bit-identical."""
    monkeypatch.setenv("ES_TPU_COALESCE_US", "250000")
    tm = TaskManager("n")
    with faults.inject("fused_dispatch:raise@1;turbo_sweep:raisexinf"):
        results, errors, _ = _run_round(_window_sched(), eng, tm,
                                        cancel_idx=3)
    assert isinstance(errors[3], TaskCancelledError)
    for i in (0, 1, 2):
        assert errors[i] is None, f"fault leaked to waiter {i}: {errors[i]}"
        _rows_equal(results[i], solo[i], f"chaos survivor {i}")


def test_unrelated_cancel_leaves_search_bit_identical(svc):
    """End-to-end no-cancel purity: a search running while an UNRELATED
    task is cancelled returns exactly what a quiet run returns."""
    body = {"query": {"match": {"body": "alpha"}}, "size": 10,
            "track_total_hits": True}
    quiet = svc.search(body)
    tm = TaskManager("n")
    victim = tm.register("indices:data/read/search", "unrelated")
    tm.cancel(victim.id, "noise")
    noisy = svc.search(body)
    assert noisy["hits"] == quiet["hits"]
    assert noisy["_shards"] == quiet["_shards"]
