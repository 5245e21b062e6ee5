"""Device-fault containment differential suite (PR 5).

Deterministic faults (common/faults.py) are injected at every named
dispatch site and the contract is BIT-identity with the no-fault host
reference: containment re-scores the faulted partition/query through the
exact host tier (the same `_exact_merge` route the certificate path
lands in), so a fault changes counters and `_shards` accounting — never
results.

Also pins the circuit-breaker lifecycle (K consecutive faults open ->
zero device dispatches while open -> half-open probe -> closed), the
scheduler's merged dispatch under a fault, and the serving-level
`allow_partial_search_results` / `timeout` semantics.

Runs on the host-simulated 8-device CPU mesh from tests/conftest.py
(interpret mode, ES_TPU_FORCE_TURBO=1 where the REST path is involved).
"""

import logging
import time

import numpy as np
import pytest

from elasticsearch_tpu.common import faults
from elasticsearch_tpu.common.errors import (
    DeviceFaultError, HbmOomError, SearchPhaseExecutionError,
)
from elasticsearch_tpu.common.faults import FaultSpecError
from elasticsearch_tpu.common.health import EngineHealth, node_health_stats
from elasticsearch_tpu.index.segment import build_field_postings
from elasticsearch_tpu.parallel.spmd import build_stacked_bm25
from elasticsearch_tpu.parallel.turbo import TurboBM25

pytestmark = [pytest.mark.faults, pytest.mark.multidevice]


class _Seg:
    def __init__(self, n_docs, fp):
        self.n_docs = n_docs
        self.postings = {"body": fp}
        self.vectors = {}


def _pcorpus(n_docs, vocab, seed):
    rng = np.random.default_rng(seed)
    probs = 1.0 / np.arange(1, vocab + 1) ** 1.1
    probs /= probs.sum()
    lens = rng.integers(4, 24, size=n_docs).astype(np.int64)
    tokens = rng.choice(vocab, size=int(lens.sum()), p=probs).astype(np.int64)
    tok_docs = np.repeat(np.arange(n_docs, dtype=np.int64), lens)
    bounds = np.concatenate([[0], np.cumsum(lens)])
    tok_pos = (np.arange(len(tokens), dtype=np.int64)
               - np.repeat(bounds[:-1], lens))
    return build_field_postings("body", lens, tok_docs, tokens,
                                [f"t{i}" for i in range(vocab)],
                                token_pos=tok_pos)


def _turbo(fp, n_docs, cold_df=5, hbm=64 << 20):
    stacked = build_stacked_bm25([_Seg(n_docs, fp)], "body", serve_only=True)
    return TurboBM25(stacked, hbm_budget_bytes=hbm, cold_df=cold_df)


def _engine(parts, mesh=True):
    from elasticsearch_tpu.search.serving import TurboEngine, _turbo_mesh

    turbos = [_turbo(fp, n) for n, fp in parts]
    return TurboEngine(turbos,
                       mesh=_turbo_mesh(len(turbos)) if mesh else None)


def _host_many(eng, batch, k):
    per = [t.search_many_host([batch], k=k)[0] for t in eng.turbos]
    return eng._merge3(per, len(batch), k)


def _host_bool(eng, specs, k):
    per = [t.search_bool_host(specs, k=k) for t in eng.turbos]
    return eng._merge3(per, len(specs), k)


def _assert_rows_equal(got, want, ctx):
    for g, w, name in zip(got, want, ("scores", "parts", "ords")):
        assert np.array_equal(np.asarray(g), np.asarray(w)), (ctx, name)


BATCH = [["t1", "t3"], ["t2", "t5"], ["t0", "t7"], ["t4", "t1"],
         ["t6", "t2"]]
SPECS = [
    {"must": [("t1", 1.0)], "should": [("t3", 1.0)]},
    {"must": [("t0", 1.0), ("t2", 1.5)]},
    {"must": [("t4", 1.0)], "filter": ["t1"]},
]
K = 10


# ---------------------------------------------------------------------------
# spec parsing
# ---------------------------------------------------------------------------


def test_parse_spec_full_grammar():
    cl = faults.parse_spec(
        "turbo_sweep#1:raise@2x3;fused_dispatch:oom~0.5;"
        "merge_kernel:hang=0.01;column_upload:raisexinf")
    assert [(c.site, c.part, c.mode) for c in cl] == [
        ("turbo_sweep", 1, "raise"), ("fused_dispatch", None, "oom"),
        ("merge_kernel", None, "hang"), ("column_upload", None, "raise")]
    assert (cl[0].nth, cl[0].count) == (2, 3)
    assert cl[1].prob == 0.5 and cl[1].rng is not None
    assert cl[2].arg == 0.01
    assert cl[3].count == float("inf")


@pytest.mark.parametrize("bad", [
    "not_a_site:raise",          # unknown site
    "turbo_sweep:explode",       # unknown mode
    "turbo_sweep#x:raise",       # bad partition
    "turbo_sweep",               # missing mode
    "turbo_sweep:raise@zz",      # bad nth
])
def test_parse_spec_rejects(bad):
    with pytest.raises(FaultSpecError):
        faults.parse_spec(bad)


def test_fault_point_nth_count_and_part_scope():
    with faults.inject("turbo_sweep#1:raise@2x2"):
        faults.fault_point("turbo_sweep", 0)      # wrong partition: never
        faults.fault_point("merge_kernel", 1)     # wrong site: never
        faults.fault_point("turbo_sweep", 1)      # call 1 < nth
        for _ in range(2):                        # calls 2, 3 fire (x2)
            with pytest.raises(DeviceFaultError) as ei:
                faults.fault_point("turbo_sweep", 1)
            assert ei.value.site == "turbo_sweep" and ei.value.part == 1
        faults.fault_point("turbo_sweep", 1)      # count exhausted
    faults.fault_point("turbo_sweep", 1)          # restored on exit


def test_oom_mode_and_device_error_translation():
    with faults.inject("turbo_sweep:oom"):
        with pytest.raises(HbmOomError):
            faults.fault_point("turbo_sweep")
    with pytest.raises(HbmOomError):
        with faults.device_errors("turbo_sweep", 2):
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory on chip")
    with pytest.raises(ValueError):               # non-device errors pass
        with faults.device_errors("turbo_sweep"):
            raise ValueError("not a device problem")


# ---------------------------------------------------------------------------
# engine-level differentials: fault at every site, results bit-identical
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def eng2():
    """Warm 2-partition fused engine for sites that fire post-build."""
    eng = _engine([(900, _pcorpus(900, 40, 1)), (1300, _pcorpus(1300, 32, 2))])
    eng.search_many([BATCH], k=K)      # build columns, compile dispatch
    return eng


def test_solo_sweep_fault_bit_identical():
    eng = _engine([(700, _pcorpus(700, 40, 7))], mesh=False)
    want = _host_many(eng, BATCH, K)
    for spec in ("turbo_sweep:raise@1", "turbo_sweep:oom@1"):
        flog = []
        with faults.inject(spec):
            got = eng.search_many([BATCH], k=K, fault_log=flog)[0]
        _assert_rows_equal(got, want, spec)
        assert flog and flog[0].partition == 0 and flog[0].recovered
    assert eng.stats["health_device_faults"] >= 2


def test_fused_dispatch_fault_bit_identical(eng2):
    want = _host_many(eng2, BATCH, K)
    flog = []
    with faults.inject("fused_dispatch:raise@1"):
        got = eng2.search_many([BATCH], k=K, fault_log=flog)[0]
    _assert_rows_equal(got, want, "fused_dispatch")
    assert any(f.site == "fused_dispatch" for f in flog)


def test_partition_column_fault_isolated():
    # FRESH engine: the fault must fire during the first column build
    eng = _engine([(600, _pcorpus(600, 40, 3)), (800, _pcorpus(800, 32, 4))])
    want = _host_many(eng, BATCH, K)
    flog = []
    with faults.inject("column_upload#1:raise@1"):
        got = eng.search_many([BATCH], k=K, fault_log=flog)[0]
    _assert_rows_equal(got, want, "column_upload#1")
    assert any(f.partition == 1 for f in flog)
    # the faulted partition recovers: a clean retry serves device-side
    # again off the rebuilt cache and still matches
    _assert_rows_equal(eng.search_many([BATCH], k=K)[0], want, "recovered")


def test_bool_and_phrase_under_partition_fault():
    eng = _engine([(600, _pcorpus(600, 40, 5)), (800, _pcorpus(800, 32, 6))])
    want = _host_bool(eng, SPECS, K)
    with faults.inject("column_upload#0:raise@1"):
        got = eng.search_bool(SPECS, k=K)
    _assert_rows_equal(got, want, "bool under column fault")
    phrases = [["t0", "t1"], ["t2", "t0"]]
    want_p = _host_bool(
        eng, [{"phrases": [(p, 0, 1.0)]} for p in phrases], K)
    with faults.inject("turbo_sweep:raisexinf"):
        got_p = eng.search_phrase(phrases, k=K, slop=0)
    _assert_rows_equal(got_p, want_p, "phrase under sweep fault")


def test_merge_kernel_fault_degrades_to_host_merge(eng2):
    want = _host_many(eng2, BATCH, K)
    h0 = eng2.merge_stats["merge_host"]
    flog = []
    with faults.inject("merge_kernel:raise@1"):
        got = eng2.search_many([BATCH], k=K, fault_log=flog)[0]
    _assert_rows_equal(got, want, "merge_kernel")
    assert eng2.merge_stats["merge_host"] == h0 + 1
    assert any(f.site == "merge_kernel" for f in flog)


def test_blockmax_fault_point_raises():
    # the BlockMax engine has no internal host tier: its fault surface
    # raises (serving catches it, records the fault on the engine's
    # circuit, and falls back to the dense executor)
    with faults.inject("blockmax_pass:raise@1"):
        with pytest.raises(DeviceFaultError):
            faults.fault_point("blockmax_pass")


# ---------------------------------------------------------------------------
# circuit breaker lifecycle
# ---------------------------------------------------------------------------


def test_circuit_opens_after_trip_n_and_probe_restores():
    eng = _engine([(700, _pcorpus(700, 40, 9))], mesh=False)
    eng.health = EngineHealth("turbo", trip_n=2, backoff_ms=40)
    t = eng.turbos[0]
    want = _host_many(eng, BATCH, K)
    eng.search_many([BATCH], k=K)                      # warm, clean
    with faults.inject("turbo_sweep:raisexinf"):
        for i in range(2):                             # trip the breaker
            _assert_rows_equal(eng.search_many([BATCH], k=K)[0], want,
                               f"contained fault {i}")
        assert eng.health.state == "open"
        d0 = t.stats["dispatches"]
        # while open: host tier serves, ZERO device dispatches
        _assert_rows_equal(eng.search_many([BATCH], k=K)[0], want, "open")
        assert t.stats["dispatches"] == d0
        assert eng.health.counters["fallback_queries"] >= len(BATCH)
    time.sleep(0.06)                                   # past backoff
    _assert_rows_equal(eng.search_many([BATCH], k=K)[0], want, "probe")
    assert eng.health.state == "closed"
    c = eng.health.counters
    assert c["circuit_opens"] == 1
    assert c["probes"] == 1 and c["probe_successes"] == 1
    trans = list(eng.health._transitions)
    assert trans == ["closed->open", "open->half_open",
                     "half_open->closed"]


def test_failed_probe_reopens_with_exponential_backoff():
    h = EngineHealth("x", trip_n=1, backoff_ms=10)
    h.record_fault(DeviceFaultError("boom"))
    assert h.state == "open" and h.backoff_ms == 10
    for i in range(1, 8):
        h._retry_at = 0.0                  # make the probe due now
        assert h.allow_device()            # half-open probe admitted
        assert not h.allow_device()        # only ONE probe in flight
        h.record_fault(DeviceFaultError("boom"))
        assert h.state == "open"
        assert h.backoff_ms == min(10 * 2 ** i, 320)
    assert h.counters["circuit_reopens"] == 7
    h._retry_at = 0.0
    assert h.allow_device()
    h.record_success()
    assert h.state == "closed" and h.backoff_ms == 10


def test_health_visible_in_node_stats_and_handler():
    h = EngineHealth("visible_test", trip_n=1, backoff_ms=10)
    h.record_fault(DeviceFaultError("boom"))
    node = node_health_stats()
    mine = [e for e in node["engines"] if e["name"] == "visible_test"]
    assert mine and mine[0]["state"] == "open"
    assert node["open_circuits"] >= 1
    assert node["device_faults"] >= 1
    from elasticsearch_tpu.rest.handlers import (
        _default_scheduler_stats, _tpu_health_stats,
    )

    full = _tpu_health_stats()
    for key in ("engines", "open_circuits", "device_faults",
                "fastpath_reject_error", "shard_fault_recoveries"):
        assert key in full
    # a poisoned batch's solo retries are the scheduler's section's
    assert "sched_batch_retries" in _default_scheduler_stats()


# ---------------------------------------------------------------------------
# serving path: _shards accounting, allow_partial_search_results, timeout
# ---------------------------------------------------------------------------

WORDS = ["alpha", "beta", "gamma", "delta", "epsilon", "zeta", "eta",
         "theta", "iota", "kappa", "lam", "mu"]


@pytest.fixture()
def turbo_svc(monkeypatch):
    from elasticsearch_tpu.cluster.state import IndexMetadata
    from elasticsearch_tpu.common.settings import Settings
    from elasticsearch_tpu.index.index_service import IndexService

    monkeypatch.setenv("ES_TPU_FORCE_TURBO", "1")
    monkeypatch.setenv("ES_TPU_TURBO_COLD_DF", "8")
    meta = IndexMetadata(
        index="faults_t", uuid="u_faults", settings=Settings({}),
        mappings={"properties": {"body": {"type": "text"}}})
    svc = IndexService(meta)
    rng = np.random.default_rng(21)
    for i in range(260):
        words = rng.choice(WORDS, size=int(rng.integers(3, 14)))
        svc.index_doc(str(i), {"body": " ".join(words)})
        if i == 120:
            svc.refresh()          # two segments -> two partitions
    svc.refresh()
    yield svc
    svc.close()


def _hits(resp):
    return [(h["_id"], h["_score"]) for h in resp["hits"]["hits"]]


def test_apsr_false_turns_fault_into_request_error(turbo_svc):
    body = {"query": {"match": {"body": "alpha beta"}},
            "allow_partial_search_results": False}
    with faults.inject("column_upload:raise@1"):
        with pytest.raises(SearchPhaseExecutionError) as ei:
            turbo_svc.search(body)
    assert "allow_partial_search_results" in str(ei.value)


def test_recovered_fault_reported_in_shards(turbo_svc):
    from elasticsearch_tpu.search.serving import serving_fault_stats

    body = {"query": {"match": {"body": "alpha beta"}}}
    # clean fast-path reference via try_search (bypasses the request
    # cache); the faulted run must match it BITWISE — the host tier
    # rescores the faulted partition through the same exact route
    want = turbo_svc.serving.try_search(body, "query_then_fetch")
    r0 = serving_fault_stats()["shard_fault_recoveries"]
    with faults.inject("column_upload#0:raise@1"):
        got = turbo_svc.search(body)
    fails = got["_shards"].get("failures")
    assert fails and fails[0]["status"] == "recovered"
    assert fails[0]["reason"]["site"] == "column_upload"
    assert _hits(got) == _hits(want)
    assert serving_fault_stats()["shard_fault_recoveries"] > r0
    # clean retry: no failures reported, identical hits
    clean = turbo_svc.search(dict(body, size=11))
    assert "failures" not in clean["_shards"]
    assert clean["_shards"]["failed"] == 0


def test_timeout_yields_timed_out_partial(turbo_svc, monkeypatch):
    from elasticsearch_tpu.search.serving import serving_fault_stats

    monkeypatch.setenv("ES_TPU_COALESCE_US", "0")
    turbo_svc.search({"query": {"match": {"body": "alpha"}}})  # warm
    body = {"query": {"match": {"body": "alpha beta"}},
            "timeout": "5ms"}
    spec = ("turbo_sweep:hang=0.08;fused_dispatch:hang=0.08;"
            "column_upload:hang=0.08")
    with faults.inject(spec):
        resp = turbo_svc.search(body)
    assert resp["timed_out"] is True
    # no timeout -> same request completes normally
    resp2 = turbo_svc.search({"query": {"match": {"body": "alpha beta"}}})
    assert resp2["timed_out"] is False and resp2["hits"]["hits"]


def test_reject_errors_counted_and_logged_once(caplog):
    from elasticsearch_tpu.search import serving as sv

    class _BoomMapper:
        def __getattr__(self, name):
            raise RuntimeError("mapper exploded")

    n0 = sv.serving_fault_stats()["fastpath_reject_error"]
    with caplog.at_level(logging.WARNING, logger="search.serving"):
        for _ in range(3):
            assert sv.extract_plan({"query": {"match": {"body": "x"}}},
                                   _BoomMapper()) is None
    assert sv.serving_fault_stats()["fastpath_reject_error"] == n0 + 3
    hits = [r for r in caplog.records if "RuntimeError" in r.getMessage()]
    assert len(hits) == 1      # first occurrence logged, rest counted


def test_scheduled_turbo_fault_bit_identical(monkeypatch):
    """Real engine through the scheduler under a one-shot fault: the
    merged dispatch contains the fault internally; rows stay identical
    to the solo host reference."""
    from elasticsearch_tpu.threadpool.scheduler import (
        AdaptiveDispatchScheduler,
    )
    from test_scheduler import _concurrent_sched

    monkeypatch.setenv("ES_TPU_COALESCE_US", "200000")
    eng = _engine([(700, _pcorpus(700, 40, 11))], mesh=False)
    eng.search_many([BATCH], k=K)              # warm columns
    sched = AdaptiveDispatchScheduler(buckets=(len(BATCH),),
                                      interactive_us=200000.0)
    want = _host_many(eng, BATCH, K)
    with faults.inject("turbo_sweep:raise@1"):
        results, errors = _concurrent_sched(sched, eng, BATCH)
    assert errors == [None] * len(BATCH)
    st = sched.stats()
    assert st["sched_dispatches"] == 1 and st["sched_batch_retries"] == 0
    for qi, r in enumerate(results):
        for j, name in enumerate(("scores", "parts", "ords")):
            assert np.array_equal(np.asarray(r[j][0]),
                                  np.asarray(want[j][qi])), (qi, name)
