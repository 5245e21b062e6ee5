"""Eager sparse impact slice differential suite (PR 17).

Cold terms (df < COLD_DF) no longer fork to the `_cold_contrib` host walk
on the serving path: at column-upload time each cold query term gets an
eagerly-scored sparse slice — packed ``doc << 8 | impact`` granules with a
per-term uint8 quantization scale — and `kernels.sparse_gather` scatters
them into a dense per-tile accumulator on device. The contract: the device
contribution plus its tracked error bound (`slack`, the cold twin of the
`e_q` certificate arithmetic) is a true upper bound, so the bound-pruned
survivor set is a SUPERSET of the host path's, every survivor is exact
host rescored, and top-k stays BIT-identical to the host reference on
every route — solo, fused S > 1, bool with cold clauses, the host A/B
(`ES_TPU_SPARSE=0`), certificate fallback, injected `sparse_gather`
faults, and an HBM scrub cycle repairing a corrupted slice pool.

PR 28: a dispatch chunk's gathers are planned and launched behind the
chunk's sweep, before anything waits for the sweep, and `_finish_query`
only collects them. The second half of this file holds that path to the
same bits at batch widths 1, 4 and 16, its two fault points to one
(partition, query), a pool smaller than a chunk's cold terms to zero
fallbacks, its two counters against `sparse_queries`, and a faulted
fused sweep to leaving no gather in flight.

PR 35: a gather serves a GROUP of queries of one (partition, chunk): one
plan, one upload, one program, one fetch. The same tests, re-stated for
groups: a fault costs its group's pairs and no other; a chunk wider than
the pool, or than the last rung of the program's ladder, splits into
groups; a group of N answers N groups of one, bit for bit.

Runs on the host-simulated 8-device CPU mesh from tests/conftest.py
(Pallas kernels interpret on CPU)."""

import numpy as np
import pytest

from elasticsearch_tpu.common import faults, integrity
from elasticsearch_tpu.common.settings import knob
from elasticsearch_tpu.common.errors import DeviceFaultError
from elasticsearch_tpu.parallel import turbo as turbo_mod
from elasticsearch_tpu.parallel.turbo import (
    SPARSE_GRAN, _SPARSE_RUNGS, _sparse_widths,
)

from test_turbo_bitset import _pcorpus, _turbo, _fused, _assert_identical

pytestmark = pytest.mark.multidevice

K = 10
# _pcorpus(3000, 40, 7) dfs run ~2886 down to ~173; cold_df=800 leaves
# terms t8.. cold, t0..t7 colized — queries below straddle the boundary
COLD_DF = 800


def _queries():
    qs = [[(f"t{i}", 1.0), (f"t{i + 11}", 0.7)] for i in range(0, 20, 3)]
    qs.append([("t30", 1.0), ("t35", 1.0)])            # cold-only
    qs.append([("t31", 2.0)])                          # single cold term
    qs.append([("t0", 1.0), ("t25", 1.0), ("t38", 0.5)])   # mixed
    qs.append([("t1", 1.0), ("t2", 0.5)])              # colized-only
    qs.append([("absent", 1.0), ("t33", 1.0)])         # unknown + cold
    return qs


def test_sparse_solo_bit_identical():
    t = _turbo(_pcorpus(3000, 40, 7), 3000, cold_df=COLD_DF)
    qs = _queries()
    got = t.search_many([qs], k=K)[0]
    want = t.search_many_host([qs], k=K)[0]
    _assert_identical(got, want, "sparse solo vs host")
    assert t.stats["cold_queries"] == 0, "host cold fork still serving"
    assert t.stats["sparse_queries"] > 0, "sparse route never engaged"
    assert t.stats["sparse_slices"] > 0, "no slices built"
    assert t.stats["sparse_fallbacks"] == 0
    assert t.stats["sparse_bytes"] > 0
    assert t._sp_pool is not None and t._sp_host is not None
    # every resident slice is granule-aligned on a declared ladder rung
    widths = _sparse_widths()
    for g0, n_g, w, sscale, _pairs in t._sp_of.values():
        assert w in widths and w == n_g * SPARSE_GRAN and sscale > 0


def test_sparse_off_ab_identical(monkeypatch):
    """ES_TPU_SPARSE=0 restores the host cold fork verbatim — same bits,
    today's counters."""
    fp = _pcorpus(3000, 40, 7)
    qs = _queries()
    on = _turbo(fp, 3000, cold_df=COLD_DF)
    got_on = on.search_many([qs], k=K)[0]
    monkeypatch.setenv("ES_TPU_SPARSE", "0")
    off = _turbo(fp, 3000, cold_df=COLD_DF)
    got_off = off.search_many([qs], k=K)[0]
    _assert_identical(got_on, got_off, "sparse on vs off A/B")
    _assert_identical(got_off, off.search_many_host([qs], k=K)[0],
                      "sparse off vs host")
    assert off.stats["cold_queries"] > 0
    assert off.stats["sparse_queries"] == 0
    assert off.stats["sparse_slices"] == 0 and off.stats["sparse_bytes"] == 0
    assert off._sp_pool is None, "slices built despite ES_TPU_SPARSE=0"


def test_sparse_bool_bit_identical():
    """Bool route: cold SHOULD terms score via the sparse tier; cold
    must/must_not clauses keep their exact host routing — all specs stay
    bit-identical to search_bool_host."""
    t = _turbo(_pcorpus(3000, 40, 7), 3000, cold_df=COLD_DF)
    specs = [
        {"must": [("t1", 1.0)], "should": [("t30", 1.0), ("t35", 0.5)]},
        {"must": [("t25", 1.0), ("t3", 1.0)], "must_not": ["t33"]},
        {"filter": ["t4"], "should": [("t38", 1.0)]},
        {"must": [("t2", 1.0)], "should": [("t8", 1.0), ("t31", 1.0)]},
        {"should": [("t28", 1.0), ("t36", 2.0)]},      # all-cold scoring
        {"must": [("t34", 1.0)], "must_not": ["t0"]},  # cold must
    ]
    got = t.search_bool(specs, k=K)
    want = t.search_bool_host(specs, k=K)
    _assert_identical(got, want, "sparse bool vs host")
    assert t.stats["sparse_queries"] > 0, "bool cold side never sparse"
    assert t.stats["cold_queries"] == 0


def test_sparse_fused_primed_shape_retraces_nothing():
    """Once a batch width is primed through `extend_qc_sizes` and a warm
    pass has built the cold terms' slices, a fused disjunctive dispatch
    of that width traces no program (`tpu_compile.retraces` stands
    still), with the sparse tier serving the cold side."""
    from elasticsearch_tpu.common import hbm_ledger

    eng = _fused([(1500, _pcorpus(1500, 40, 1)),
                  (900, _pcorpus(900, 56, 2))], cold_df=300)
    qs = [[("t1", 1.0), ("t20", 1.0)], [("t25", 1.0), ("t30", 0.5)],
          [("t2", 1.0)], [("t28", 1.0), ("t31", 1.0), ("t3", 0.2)]]
    eng.extend_qc_sizes([len(qs)])
    eng._fused()
    eng.extend_qc_sizes([len(qs)])       # the lazily built fused dispatcher
    want = eng.search_many([qs], k=K)[0]     # warm pass builds the slices
    r0 = hbm_ledger.compile_stats()["retraces"]
    got = eng.search_many([qs], k=K)[0]
    assert hbm_ledger.compile_stats()["retraces"] == r0
    assert eng.stats["sparse_queries"] > 0 and eng.stats["cold_queries"] == 0
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))


def test_sparse_fused_bit_identical():
    """S=3 fused dispatch (different sizes, vocabularies, df spectra,
    therefore different per-partition slice pools) against each
    partition's host route, plus the ledger == hbm_bytes cross-check."""
    eng = _fused([(1500, _pcorpus(1500, 40, 1)),
                  (900, _pcorpus(900, 56, 2)),
                  (2100, _pcorpus(2100, 32, 3))], cold_df=300)
    st = eng._fused()
    qs = [[("t1", 1.0), ("t20", 1.0)], [("t25", 1.0), ("t30", 0.5)],
          [("t2", 1.0)], [("t28", 1.0), ("t31", 1.0), ("t3", 0.2)]]
    per = st.search_many([qs], k=K)
    for si, t in enumerate(st.turbos):
        _assert_identical(per[si][0], t.search_many_host([qs], k=K)[0],
                          f"fused partition {si} vs host")
    assert sum(t.stats["sparse_queries"] for t in st.turbos) > 0
    assert all(t.stats["cold_queries"] == 0 for t in st.turbos)
    # ledger cross-check: the slice pool is a ledgered region, and each
    # engine's ledgered occupancy stays byte-identical to hbm_bytes()
    for t in st.turbos:
        assert t._hbm.total_bytes() == t.hbm_bytes()
        if t._sp_pool is not None:
            assert t._sp_pool.nbytes > 0
    assert eng.hbm_bytes() == (sum(t.hbm_bytes() for t in st.turbos)
                               + st.hbm_bytes())


def test_sparse_widths_ladder(monkeypatch):
    """A custom ES_TPU_SPARSE_WIDTHS ladder is honored (rounded up to
    granule multiples) and stays bit-identical; a term above the top rung
    falls back to the exact host walk."""
    monkeypatch.setenv("ES_TPU_SPARSE_WIDTHS", "1024,2048")
    assert _sparse_widths() == (1024, 2048)
    fp = _pcorpus(3000, 40, 7)
    t = _turbo(fp, 3000, cold_df=2500)   # t2 (df~1892) cold, > 1024 rung
    qs = [[("t2", 1.0), ("t30", 1.0)], [("t35", 1.0), ("t38", 1.0)]]
    got = t.search_many([qs], k=K)[0]
    _assert_identical(got, t.search_many_host([qs], k=K)[0],
                      "custom ladder vs host")
    assert all(sl[2] in (1024, 2048) for sl in t._sp_of.values())
    # df above the ladder: the whole batch host-falls-back, still counted
    monkeypatch.setenv("ES_TPU_SPARSE_WIDTHS", "1024")
    t2 = _turbo(fp, 3000, cold_df=2500)
    got2 = t2.search_many([qs[:1]], k=K)[0]
    _assert_identical(got2, t2.search_many_host([qs[:1]], k=K)[0],
                      "over-ladder fallback vs host")
    assert t2.stats["sparse_fallbacks"] > 0


def test_sparse_certificate_fallback():
    """force_cert_fail (the bool-path certificate test hook) discards the
    device collection on specs whose cold SHOULD side went through the
    sparse tier; the exact fallback still agrees bit-for-bit."""
    t = _turbo(_pcorpus(2200, 40, 9), 2200, cold_df=600)
    specs = [{"must": [("t0", 1.0)], "should": [("t30", 1.0)]},
             {"must": [("t2", 1.0)], "should": [("t25", 1.0),
                                                ("t33", 0.5)]}]
    want = t.search_bool_host(specs, k=K)
    fb0 = t.stats["fallbacks"]
    try:
        t.force_cert_fail = True
        got = t.search_bool(specs, k=K)
    finally:
        t.force_cert_fail = False
    _assert_identical(got, want, "cert-fail vs host")
    assert t.stats["fallbacks"] > fb0
    assert t.stats["sparse_queries"] > 0


@pytest.mark.faults
def test_sparse_fault_contained_per_partition():
    """An injected sparse_gather fault on one partition host-scores that
    partition's cold side only — results stay bit-identical, the fallback
    is counted, and a clean retry serves the device route again."""
    eng = _fused([(700, _pcorpus(700, 40, 12)),
                  (900, _pcorpus(900, 32, 13))], cold_df=250)
    qs = [[("t20", 1.0), ("t25", 1.0)], [("t1", 1.0), ("t28", 0.5)]]
    want = eng._merge3([t.search_many_host([qs], k=K)[0]
                        for t in eng.turbos], len(qs), K)
    fb0 = eng.turbos[1].stats["sparse_fallbacks"]
    with faults.inject("sparse_gather#1:raise@1"):
        got = eng.search_many([qs], k=K)[0]
    for g, w, name in zip(got, want, ("scores", "parts", "ords")):
        assert np.array_equal(np.asarray(g), np.asarray(w)), name
    assert eng.turbos[1].stats["sparse_fallbacks"] > fb0, \
        "faulted partition never fell back"
    clean = eng.search_many([qs], k=K)[0]
    for g, w, name in zip(clean, want, ("scores", "parts", "ords")):
        assert np.array_equal(np.asarray(g), np.asarray(w)), name


@pytest.mark.faults
def test_sparse_scrub_bitflip_repair():
    """PR-15 integrity plane over the slice pool: an injected hbm_region
    flip on sparse_pool is detected by the scrubber, repaired from the
    host mirror, and the repaired engine answers bit-identically."""
    fp = _pcorpus(1400, 36, 14)
    qs = [[("t20", 1.0), ("t25", 1.0)], [("t1", 1.0), ("t28", 0.5)]]
    control = _turbo(fp, 1400, cold_df=300)
    want = control.search_many([qs], k=K)[0]
    _assert_identical(want, control.search_many_host([qs], k=K)[0],
                      "control")

    integrity.reset_scrub_for_tests()      # only the engine below scrubs
    t = _turbo(fp, 1400, cold_df=300)
    t.search_many([qs], k=K)               # builds slices, registers region
    assert t._sp_pool is not None

    def cycle():
        return [integrity.scrub_once()
                for _ in range(integrity.scrub_registry_size())]

    cycle()                                # baseline pass: all clean
    m0 = integrity.integrity_stats()["scrub_mismatches"]
    with faults.inject("hbm_region#sparse_pool:raise@1x1"):
        results = cycle()
    hit = [r for r in results if r and r["result"] == "mismatch"]
    assert len(hit) == 1 and hit[0]["region"].endswith(".sparse_pool")
    st = integrity.integrity_stats()
    assert st["scrub_mismatches"] == m0 + 1
    assert st["scrub_repairs"] >= 1
    _assert_identical(t.search_many([qs], k=K)[0], want,
                      "repaired sparse engine vs control")
    cycle()                                # repair re-baselined the region
    assert integrity.integrity_stats()["scrub_mismatches"] == m0 + 1


def test_sparse_prewarm_and_hot_terms():
    """The relocation warm-handoff surface: sparse_hot_terms reports the
    resident slice set; prewarm_sparse rebuilds it on a cold engine so
    the first query after a move needs no slice build."""
    fp = _pcorpus(2000, 40, 15)
    src = _turbo(fp, 2000, cold_df=400)
    qs = [[("t20", 1.0), ("t30", 1.0)], [("t25", 1.0)]]
    src.search_many([qs], k=K)
    hot = src.sparse_hot_terms()
    assert hot, "no slices resident after cold-term traffic"

    dst = _turbo(fp, 2000, cold_df=400)
    n = dst.prewarm_sparse(hot)
    assert n == len(hot)
    assert dst.sparse_hot_terms() == hot
    s0 = dst.stats["sparse_slices"]
    got = dst.search_many([qs], k=K)[0]
    _assert_identical(got, src.search_many_host([qs], k=K)[0],
                      "prewarmed vs host")
    assert dst.stats["sparse_slices"] == s0, "prewarmed slices rebuilt"
    # colized terms never slice; unknown terms are ignored
    assert dst.prewarm_sparse(["t0", "absent"]) == 0


def test_sparse_knob_defaults():
    assert bool(knob("ES_TPU_SPARSE")) is True
    assert _sparse_widths() == (1024, 4096, 16384)


# --------------------------------------------------------------------------
# PR 28: the gathers go out with the sweep and are collected in finish
# --------------------------------------------------------------------------

_SPARSE_KEYS = ("sparse_queries", "sparse_fallbacks",
                "sparse_gather_launches", "sparse_gather_overlapped",
                "sparse_slices", "cold_queries")


def _snap(turbos):
    return [{key: t.stats[key] for key in _SPARSE_KEYS} for t in turbos]


def _rise(turbos, before):
    return [{key: t.stats[key] - b[key] for key in _SPARSE_KEYS}
            for t, b in zip(turbos, before)]


def _wide_queries(width):
    """`width` queries: neighbours share a cold term (t20..t39 are cold on
    every corpus below), every fourth has no cold term at all."""
    qs = []
    for i in range(width):
        if i % 4 == 3:
            qs.append([("t1", 1.0), ("t2", 0.5)])
        else:
            qs.append([(f"t{i % 3}", 1.0), (f"t{20 + i % 16}", 1.0),
                       (f"t{21 + i % 16}", 0.7)])
    return qs


def _wide_specs(width):
    specs = []
    for i in range(width):
        if i % 4 == 3:
            specs.append({"must": [("t1", 1.0)], "should": [("t2", 0.5)]})
        else:
            specs.append({"must": [(f"t{i % 3}", 1.0)],
                          "should": [(f"t{20 + i % 16}", 1.0),
                                     (f"t{21 + i % 16}", 0.7)]})
    return specs


@pytest.fixture(scope="module")
def solo_engine():
    return _turbo(_pcorpus(3000, 40, 7), 3000, cold_df=COLD_DF)


@pytest.fixture(scope="module")
def fused_engine():
    return _fused([(1500, _pcorpus(1500, 40, 1)),
                   (900, _pcorpus(900, 56, 2)),
                   (2100, _pcorpus(2100, 40, 3))], cold_df=300)


@pytest.mark.parametrize("width", [1, 4, 16, 72])
@pytest.mark.parametrize("route", ["solo", "fused", "bool"])
def test_hoisted_gather_bit_identical_and_counted(solo_engine, fused_engine,
                                                  route, width):
    """(a) + (d): every width and route answers the host enumeration's
    bits; on the match routes a (partition, chunk)'s cold sides are ONE
    group, so one program, launched before the dispatch waited
    (`overlapped` == `sparse_queries`, one launch), with the queries that
    have no cold term lying between its members; on the bool route the
    same three steps run back to back inside finish, a group of one a
    query."""
    cold_pairs = sum(1 for i in range(width) if i % 4 != 3)
    if route == "fused":
        st = fused_engine._fused()
        turbos = st.turbos
        qs = _wide_queries(width)
        before = _snap(turbos)
        per = st.search_many([qs], k=K)
        for si, t in enumerate(turbos):
            _assert_identical(per[si][0],
                              t.search_many_host([qs], k=K)[0],
                              f"fused partition {si}, width {width}")
    else:
        turbos = [solo_engine]
        before = _snap(turbos)
        if route == "solo":
            qs = _wide_queries(width)
            _assert_identical(solo_engine.search_many([qs], k=K)[0],
                              solo_engine.search_many_host([qs], k=K)[0],
                              f"solo, width {width}")
        else:
            specs = _wide_specs(width)
            _assert_identical(solo_engine.search_bool(specs, k=K),
                              solo_engine.search_bool_host(specs, k=K),
                              f"bool, width {width}")
    for t, d in zip(turbos, _rise(turbos, before)):
        assert d["sparse_queries"] == cold_pairs, d
        assert d["sparse_gather_launches"] == \
            (cold_pairs if route == "bool" else 1), d
        assert d["sparse_gather_overlapped"] == \
            (0 if route == "bool" else cold_pairs), d
        assert d["sparse_fallbacks"] == 0 and d["cold_queries"] == 0, d
        assert t._sp_inflight == 0


@pytest.mark.faults
@pytest.mark.parametrize("where, nth, cap", [
    ("launch", 1, None), ("collect", 2, None),
    ("launch", 2, 3), ("collect", 4, 3)])
def test_gather_fault_contained_to_its_group_and_counted_once(where, nth,
                                                              cap):
    """(b) `sparse_gather` fires once before a group's launch and once
    before its fetch. Two cold queries on partition 1 are one group:
    call 1 its launch, call 2 its fetch, and a fault at either host-scores
    both pairs, once each. With room for two slices in the pool (`cap` 3:
    the queries' three cold terms cannot be resident at once) they are two
    groups of one: calls 1, 2 the launches and 3, 4 the fetches, and the
    faulted group's pair alone is host-scored."""
    eng = _fused([(700, _pcorpus(700, 40, 12)),
                  (900, _pcorpus(900, 32, 13))], cold_df=250)
    if cap:
        eng.turbos[1]._sp_cap = cap
    qs = [[("t20", 1.0), ("t25", 1.0)], [("t1", 1.0), ("t28", 0.5)]]
    want = eng._merge3([t.search_many_host([qs], k=K)[0]
                        for t in eng.turbos], len(qs), K)
    before = _snap(eng.turbos)
    with faults.inject(f"sparse_gather#1:raise@{nth}"):
        got = eng.search_many([qs], k=K)[0]
    for g, w, name in zip(got, want, ("scores", "parts", "ords")):
        assert np.array_equal(np.asarray(g), np.asarray(w)), name
    d0, d1 = _rise(eng.turbos, before)
    groups, lost = (2, 1) if cap else (1, 2)
    assert d0["sparse_fallbacks"] == 0 and d1["sparse_fallbacks"] == lost
    assert d0["sparse_queries"] == 2 and d1["sparse_queries"] == 2
    assert d0["sparse_gather_launches"] == 1
    # a fault before the launch costs the launch; one at the fetch does not
    assert d1["sparse_gather_launches"] == \
        groups - (1 if where == "launch" else 0)
    # cold sides collected from a program that went out early
    assert d1["sparse_gather_overlapped"] == \
        2 - (lost if where == "launch" else 0)
    assert all(t._sp_inflight == 0 for t in eng.turbos)


@pytest.mark.faults
@pytest.mark.parametrize("where", ["program", "fetch"])
def test_gather_device_error_contained(monkeypatch, where):
    """(b) an organic device error, raised by the program's call or by
    the fetch of its result, is contained like the injected one: the
    first group's two pairs are host-scored, the second group (the pool
    holds two slices, the third query's term is a third) is served."""
    t = _turbo(_pcorpus(3000, 40, 7), 3000, cold_df=COLD_DF)
    t._sp_cap = 3
    qs = [[("t0", 1.0), ("t30", 1.0)], [("t1", 1.0), ("t35", 1.0)],
          [("t2", 1.0), ("t38", 1.0)]]
    want = t.search_many_host([qs], k=K)[0]
    t.search_many([qs], k=K)                   # the pool in place
    real = turbo_mod.sparse_gather
    calls = []

    class _Lost:
        def copy_to_host_async(self):
            pass

        def __array__(self, *a, **kw):
            raise RuntimeError("INTERNAL: device lost")

    def flaky(desc, pool, **kw):
        calls.append(1)
        if len(calls) == 1:
            if where == "program":
                raise RuntimeError("INTERNAL: launch failed")
            return _Lost()
        return real(desc, pool, **kw)

    monkeypatch.setattr(turbo_mod, "sparse_gather", flaky)
    before = _snap([t])
    _assert_identical(t.search_many([qs], k=K)[0], want, where)
    (d,) = _rise([t], before)
    assert len(calls) == 2
    assert d["sparse_fallbacks"] == 2 and d["sparse_queries"] == 3
    assert t._sp_inflight == 0


@pytest.mark.parametrize("route", ["solo", "fused"])
def test_chunk_wider_than_the_pool_is_served_without_fallback(route):
    """(c) a chunk whose cold terms cannot all be resident at once (room
    for three one-granule slices, sixteen distinct cold terms) splits
    into groups of the queries whose slices can: every group's gather is
    issued before the next group's slice build recycles its granules, so
    the sparse tier serves all of them."""
    if route == "solo":
        turbos = [_turbo(_pcorpus(3000, 40, 7), 3000, cold_df=COLD_DF)]
    else:
        eng = _fused([(1500, _pcorpus(1500, 40, 1)),
                      (2100, _pcorpus(2100, 40, 3))], cold_df=300)
        turbos = eng._fused().turbos
    for t in turbos:
        t._sp_cap = 4                   # granule 0 is the reserved zero one
    qs = [q for q in _wide_queries(16) if len(q) == 3]
    distinct = {term for q in qs for term, _ in q[1:]}
    assert len(distinct) > 3
    before = _snap(turbos)
    if route == "solo":
        got = [turbos[0].search_many([qs], k=K)]
    else:
        got = eng._fused().search_many([qs], k=K)
    for si, t in enumerate(turbos):
        _assert_identical(got[si][0], t.search_many_host([qs], k=K)[0],
                          f"{route} partition {si}")
        assert t._sp_pool.shape[0] == 4
        assert len(t._sp_of) <= 3
    for d in _rise(turbos, before):
        assert d["sparse_fallbacks"] == 0, d
        assert d["sparse_queries"] == len(qs)
        assert d["sparse_gather_overlapped"] == len(qs)
        assert 1 < d["sparse_gather_launches"] < len(qs), d
        assert d["sparse_slices"] >= len(distinct) > 3, "nothing recycled"


@pytest.mark.faults
@pytest.mark.parametrize("where", ["launch", "fetch"])
def test_fused_sweep_fault_leaves_no_gather_in_flight(monkeypatch, where):
    """(e) a fused sweep that faults at its launch never starts the
    chunk's gathers; one whose fault surfaces at the fetch has started
    them, and the chunk's host scoring drops every one."""
    eng = _fused([(700, _pcorpus(700, 40, 12)),
                  (900, _pcorpus(900, 32, 13))], cold_df=250)
    st = eng._fused()
    qs = [[("t20", 1.0), ("t25", 1.0)], [("t1", 1.0), ("t28", 0.5)]]
    want = [t.search_many_host([qs], k=K)[0] for t in st.turbos]
    st.search_many([qs], k=K)                  # slices and pools in place
    before = _snap(st.turbos)
    log = []
    if where == "launch":
        with faults.inject("fused_dispatch:raise@1"):
            per = st.search_many([qs], k=K, fault_log=log)
    else:
        real = st._dispatch_disj

        class _Lost:
            def __array__(self, *a, **kw):
                raise DeviceFaultError("async fault", site="fused_dispatch")

        def lost(*args, **kw):
            _packed, gathers = real(*args, **kw)
            return _Lost(), gathers

        monkeypatch.setattr(st, "_dispatch_disj", lost)
        per = st.search_many([qs], k=K, fault_log=log)
    for si, t in enumerate(st.turbos):
        _assert_identical(per[si][0], want[si], f"partition {si}")
        assert t._sp_inflight == 0
    assert [r.site for r in log] == ["fused_dispatch"]
    for d in _rise(st.turbos, before):
        assert d["sparse_gather_launches"] == (0 if where == "launch" else 1)
        # host-scored chunk: no finish ran, so nothing was collected
        assert d["sparse_queries"] == 0 and d["sparse_fallbacks"] == 0
        assert d["sparse_gather_overlapped"] == 0


def test_cancel_between_launch_and_finish_drops_the_gathers(solo_engine):
    """A cooperative cancel (`check`) raising after the gathers went out
    unwinds through search_many with none left in flight."""
    class _Cancelled(Exception):
        pass

    n = []

    def check():
        n.append(1)
        if len(n) == 2:                 # 1: before the sweep, 2: before finish
            raise _Cancelled()

    qs = _wide_queries(4)
    l0 = solo_engine.stats["sparse_gather_launches"]
    with pytest.raises(_Cancelled):
        solo_engine.search_many([qs], k=K, check=check)
    assert solo_engine.stats["sparse_gather_launches"] == l0 + 1
    assert solo_engine._sp_inflight == 0


def test_every_gather_shape_is_instantiated_when_the_pool_is_full_size():
    """The gather program's shape is (rung = steps and result chunks, pool
    size, tiles). When the pool reaches its cap, its final size, every
    rung is instantiated: a dispatch at a rung traffic has not reached yet
    builds nothing. (Below the cap the pool still doubles, and programs
    for a passing size are left to the traffic that needs them.)"""
    from elasticsearch_tpu.parallel.kernels import TILE

    program = turbo_mod.sparse_gather
    t = _turbo(_pcorpus(3000, 40, 7), 3000, cold_df=COLD_DF)
    t._sp_cap = 37                       # no other test's pool size
    t.search_many([[[("t0", 1.0), ("t30", 1.0)]]], k=K)
    assert t._sp_pool.shape[0] == 37
    size0 = program._cache_size()
    for ns, nc in _SPARSE_RUNGS:
        program(turbo_mod._gather_desc(ns), t._sp_pool, n_chunks=nc,
                n_tiles=t.Dp // TILE)
    assert program._cache_size() == size0
    # the ladder is short, rises in both members, and its last rung's
    # step words fit the chip's scalar memory (1 MB) four times over
    assert len(_SPARSE_RUNGS) <= 4
    assert all(a[0] < b[0] and a[1] < b[1]
               for a, b in zip(_SPARSE_RUNGS, _SPARSE_RUNGS[1:]))
    assert 4 * 4 * _SPARSE_RUNGS[-1][0] <= (1 << 20) // 4
    assert _SPARSE_RUNGS[-1][1] >= turbo_mod._SPARSE_QUERY_CHUNKS


@pytest.mark.parametrize("route", ["solo", "fused"])
def test_chunk_with_more_steps_than_a_program_splits_into_groups(
        monkeypatch, route):
    """A ladder whose last rung holds 24 steps and 4 result chunks: a
    query's two one-chunk slices are 8 steps here (each meets one tile:
    a scatter and a pick step), so a 16-wide chunk's twelve cold sides
    go out as groups of two or three, each at the rung that holds it,
    with the same bits and no fallback; a ladder too short for one query
    host-scores every pair."""
    if route == "solo":
        turbos = [_turbo(_pcorpus(3000, 40, 7), 3000, cold_df=COLD_DF)]
        search = turbos[0].search_many
    else:
        st = _three_parts()._fused()
        turbos, search = st.turbos, st.search_many
    qs = _wide_queries(16)
    want = [t.search_many_host([qs], k=K)[0] for t in turbos]

    def run():
        got = search([qs], k=K)
        return got if route == "fused" else [got]

    monkeypatch.setattr(turbo_mod, "_SPARSE_RUNGS", ((8, 2), (24, 4)))
    before = _snap(turbos)
    for si, got in enumerate(run()):
        _assert_identical(got[0], want[si], f"{route} partition {si}")
    for t, d in zip(turbos, _rise(turbos, before)):
        assert d["sparse_fallbacks"] == 0 and d["sparse_queries"] == 12, d
        assert d["sparse_gather_launches"] == 6, d     # 4 chunks a program
        assert d["sparse_gather_overlapped"] == 12 and t._sp_inflight == 0
    monkeypatch.setattr(turbo_mod, "_SPARSE_RUNGS", ((2, 1),))
    before = _snap(turbos)
    for si, got in enumerate(run()):
        _assert_identical(got[0], want[si], f"{route} partition {si}, host")
    for t, d in zip(turbos, _rise(turbos, before)):
        assert d["sparse_fallbacks"] == 12, d
        assert d["sparse_gather_launches"] == 0 and t._sp_inflight == 0


def test_a_group_reads_what_its_queries_read_alone(solo_engine):
    """One program over five queries (shared cold terms, different
    weights, one doc in several of a query's slices) gives every query
    the totals, the slack and the enumeration its own group of one gives:
    an accumulator a query, never shared."""
    t = solo_engine
    colds = []
    for i, boost in enumerate((1.0, 0.3, 2.0, 0.7, 1.1)):
        terms = [(f"t{20 + i}", boost), (f"t{21 + i}", 1.0),
                 ("t30", 0.5 + i)]
        colds.append((i, [(tm, b, t._term(tm)) for tm, b in terms]))
    l0 = t.stats["sparse_gather_launches"]
    grouped = t._start_gathers(colds, False)
    assert t.stats["sparse_gather_launches"] == l0 + 1
    assert len({id(h.group) for h in grouped.values()}) == 1
    for qi, cold in colds:
        alone = t._start_gathers([(qi, cold)], False)[qi]
        for got, want in zip(t._collect_gather(grouped[qi]),
                             t._collect_gather(alone)):
            assert np.array_equal(got, want), qi
        assert not grouped[qi].host and not alone.host
    assert t.stats["sparse_gather_launches"] == l0 + 6
    assert t._sp_inflight == 0


def test_gather_counters_ride_the_node_stats(solo_engine):
    from elasticsearch_tpu.search.serving import turbo_node_stats

    before = turbo_node_stats()
    assert {"sparse_gather_launches", "sparse_gather_overlapped"} \
        <= set(before)
    solo_engine.search_many([_wide_queries(4)], k=K)
    after = turbo_node_stats()
    rise = {key: after[key] - before[key] for key in (
        "sparse_queries", "sparse_gather_launches",
        "sparse_gather_overlapped", "sparse_fallbacks")}
    assert rise == {"sparse_queries": 3, "sparse_gather_launches": 1,
                    "sparse_gather_overlapped": 3, "sparse_fallbacks": 0}


# ---------------------------------------------------------------------------
# PR 31: the finish runs once a (partition, chunk); containment stays per
# (partition, query), and the two finish counters say who left the bulk path
# ---------------------------------------------------------------------------

_FINISH_KEYS = ("finish_bulk_pairs", "finish_pair_fallbacks", "fallbacks")


def _finish_snap(turbos):
    return [{key: t.stats[key] for key in _FINISH_KEYS + _SPARSE_KEYS}
            for t in turbos]


def _finish_rise(turbos, before):
    return [{key: t.stats[key] - b[key] for key in b}
            for t, b in zip(turbos, before)]


def _three_parts(**kw):
    return _fused([(1500, _pcorpus(1500, 40, 1)),
                   (900, _pcorpus(900, 56, 2)),
                   (2100, _pcorpus(2100, 40, 3))], cold_df=300, **kw)


@pytest.mark.faults
@pytest.mark.parametrize("where", ["launch", "collect"])
def test_gather_fault_in_a_wide_chunk_host_scores_its_group(where):
    """A 16-wide chunk has 12 cold pairs a partition. Partition 1's pool
    holds three one-granule slices, so its twelve go out as several
    groups: `sparse_gather` fires once at each group's launch, then once
    at each group's fetch. Whichever call faults, the pairs of THAT group
    are host-scored and leave the chunk-wide finish, once each; every
    other group, and every other partition, is served."""
    eng = _three_parts()
    eng.turbos[1]._sp_cap = 4
    qs = _wide_queries(16)
    want = eng._merge3([t.search_many_host([qs], k=K)[0]
                        for t in eng.turbos], len(qs), K)
    eng.search_many([qs], k=K)               # pools in place
    sizes = []
    real = eng.turbos[1]._launch_gather

    def launch(g):
        sizes.append(len(g.members))
        real(g)

    eng.turbos[1]._launch_gather = launch
    before = _finish_snap(eng.turbos)
    eng.search_many([qs], k=K)
    groups = [n for n in sizes if n]
    assert sum(groups) == 12 and len(groups) > 2
    assert _finish_rise(eng.turbos, before)[1]["sparse_gather_launches"] \
        == len(groups)
    hit_group = 2                            # the third group's calls
    before = _finish_snap(eng.turbos)
    nth = hit_group + 1 + (0 if where == "launch" else len(groups))
    with faults.inject(f"sparse_gather#1:raise@{nth}"):
        got = eng.search_many([qs], k=K)[0]
    for g, w, name in zip(got, want, ("scores", "parts", "ords")):
        assert np.array_equal(np.asarray(g), np.asarray(w)), name
    for si, d in enumerate(_finish_rise(eng.turbos, before)):
        hit = groups[hit_group] if si == 1 else 0
        assert d["sparse_fallbacks"] == hit, (si, d)
        assert d["finish_pair_fallbacks"] == hit + d["fallbacks"], (si, d)
        assert d["finish_bulk_pairs"] == 16 - d["finish_pair_fallbacks"]
        assert d["sparse_queries"] == 12
        assert d["sparse_gather_launches"] == \
            (len(groups) if si == 1 else 1) \
            - (1 if hit and where == "launch" else 0)
    assert all(t._sp_inflight == 0 for t in eng.turbos)


def test_one_failed_certificate_leaves_the_rest_of_the_chunk_in_bulk(
        monkeypatch):
    """The pick's bound for query 5 of 16 is rigged past any score: its
    certificate fails and it alone takes `dispatch.cert_fallback`; the
    other 15 stay on the chunk-wide path; all 16 answer the host's bits."""
    t = _turbo(_pcorpus(3000, 40, 7), 3000, cold_df=COLD_DF)
    qs = _wide_queries(16)
    want = t.search_many_host([qs], k=K)[0]
    _assert_identical(t.search_many([qs], k=K)[0], want, "sound bound")
    real = turbo_mod._pick_rows

    def rigged(rm, rr, *, n_rows):
        return real(rm, rr, n_rows=n_rows).at[5, n_rows].set(1e9)

    monkeypatch.setattr(turbo_mod, "_pick_rows", rigged)
    before = _finish_snap([t])
    _assert_identical(t.search_many([qs], k=K)[0], want, "rigged bound")
    (d,) = _finish_rise([t], before)
    assert d["fallbacks"] == 1 and d["finish_pair_fallbacks"] == 1, d
    assert d["finish_bulk_pairs"] == 15 and d["sparse_fallbacks"] == 0, d


@pytest.mark.parametrize("route", ["solo", "fused"])
def test_cancel_between_chunks_leaves_no_gather_in_flight(route):
    """20 queries at width 8 are three chunks, all swept and their 15
    cold sides launched, a group a chunk, before the first finish. A
    cancel after the first chunk's finish unwinds with the other two
    chunks' groups dropped."""
    class _Cancelled(Exception):
        pass

    if route == "solo":
        turbos = [_turbo(_pcorpus(3000, 40, 7), 3000, cold_df=COLD_DF,
                         qc_sizes=(8,))]
        search = turbos[0].search_many
    else:
        st = _three_parts(qc_sizes=(8,))._fused()
        turbos, search = st.turbos, st.search_many
    qs = _wide_queries(20)
    search([qs], k=K)                        # slices and pools in place
    n = []

    def check():
        n.append(1)
        if len(n) == 5:     # 1-3: before each sweep, 4-6: before each finish
            raise _Cancelled()

    before = _finish_snap(turbos)
    with pytest.raises(_Cancelled):
        search([qs], k=K, check=check)
    for t, d in zip(turbos, _finish_rise(turbos, before)):
        assert d["sparse_gather_launches"] == 3, d
        assert d["sparse_gather_overlapped"] == 6, d      # chunk 1's own
        assert d["finish_bulk_pairs"] + d["finish_pair_fallbacks"] == 8
        assert t._sp_inflight == 0


def test_an_evicted_column_rebuilds_its_host_index():
    """32 slots, 36 terms past cold_df: a batch of 32 other terms evicts
    the first call's four columns, the third call rebuilds them (other
    slots, maybe) and with them the rows' span ends and the survivors'
    look-up (`_col_cnt`, `_col_bits`): the same bits before and after."""
    fp = _pcorpus(3000, 60, 7)
    by_df = sorted(range(60), key=lambda i: -int(fp.doc_freq[fp.ord(f"t{i}")]))
    cold_df = int(fp.doc_freq[fp.ord(f"t{by_df[35]}")])
    t = _turbo(fp, 3000, cold_df=cold_df, hbm=1)
    assert t.Hp == 32
    hot = [f"t{i}" for i in by_df[:36]]
    cold = [f"t{i}" for i in by_df[36:]]
    qs1 = [[(hot[0], 1.0), (hot[1], 0.5), (cold[0], 1.0)],
           [(hot[2], 1.0), (hot[3], 1.0), (cold[1], 0.7), (cold[2], 1.0)]]
    qs2 = [[(hot[i], 1.0), (hot[i + 1], 1.0), (cold[i % 20], 1.0)]
           for i in range(4, 36, 2)]
    want1 = t.search_many_host([qs1], k=K)[0]
    first = t.search_many([qs1], k=K)[0]
    _assert_identical(first, want1, "before the eviction")
    _assert_identical(t.search_many([qs2], k=K)[0],
                      t.search_many_host([qs2], k=K)[0], "the evicting batch")
    assert not any(h in t._slot_of for h in hot[:4])
    builds = t.stats["builds"]
    _assert_identical(t.search_many([qs1], k=K)[0], first, "after the rebuild")
    assert t.stats["builds"] == builds + 4
    for h in hot[:4]:
        info, slot = t._term(h), t._slot_of[h]
        assert t._col_cnt[slot, -1] == info.df
        assert int(np.bitwise_count(t._col_bits[slot]).sum()) == info.df


def test_a_wide_chunk_is_one_observation_a_step_and_counts_its_pairs():
    """One engine call over a 16-query chunk on three partitions: ONE
    observation each of `dispatch.finish`, `dispatch.rescore` and
    `dispatch.sparse_gather` (48 pairs inside them), rescore and the
    collect under finish, and partitions x queries pairs on the
    chunk-wide path, node-wide too."""
    from elasticsearch_tpu.common import metrics
    from elasticsearch_tpu.search.serving import turbo_node_stats

    st = _three_parts()._fused()
    qs = _wide_queries(16)
    st.search_many([qs], k=K)                # slices and pools in place
    names = ("dispatch.finish", "dispatch.rescore", "dispatch.sparse_gather")
    count0 = {n: metrics.summary(n)["count"] for n in names}
    total0 = {n: metrics.raw_dump(n)["total"] for n in names}
    node0 = turbo_node_stats()
    before = _finish_snap(st.turbos)
    st.search_many([qs], k=K)
    took = {n: metrics.raw_dump(n)["total"] - total0[n] for n in names}
    for n in names:
        assert metrics.summary(n)["count"] == count0[n] + 1, n
        assert took[n] > 0, n
    assert took["dispatch.rescore"] + took["dispatch.sparse_gather"] \
        <= took["dispatch.finish"]
    rise = _finish_rise(st.turbos, before)
    left = sum(d["finish_pair_fallbacks"] for d in rise)
    assert sum(d["finish_bulk_pairs"] for d in rise) == 3 * 16 - left
    assert left == sum(d["fallbacks"] for d in rise)
    node1 = turbo_node_stats()
    assert node1["finish_bulk_pairs"] - node0["finish_bulk_pairs"] \
        == 3 * 16 - left
    assert node1["finish_pair_fallbacks"] - node0["finish_pair_fallbacks"] \
        == left


def test_finish_bulk_pct_is_declared_for_the_bm25_cells_and_reads_a_share():
    """The benchmark's `finish_bulk_pct.search`: the manifest declares it
    in the traced runs of the cells whose device counter is the fused
    turbo's, and its reader gives 100 x bulk / (bulk + left), 0 where the
    program's stats lack the counters (the parent commit's do)."""
    from types import SimpleNamespace

    from benchmark.manifest import ROOT, Manifest, load_kind

    m = Manifest(ROOT)
    name = "finish_bulk_pct.search"
    for cell in m.cell_names():
        declared = name in {x["name"] for x in m.declared(cell, 1)}
        c = m.cell(cell)          # (the `match` route's cells: the bool
        #                           cell's finish is `_finish_bool_chunk`)
        assert declared == (c.config["device_counter"].startswith(
            "tpu_turbo.") and c.traffic["request"]["kind"] == "match"), cell
        assert name not in {x["name"] for x in m.declared(cell, 0)}
    spec = m.metric_spec(name)
    read = load_kind(m.dir, "reader", spec["kind"]).read

    def window(b0, l0, b1, l1):
        return SimpleNamespace(
            stats_before={"tpu_turbo": {"finish_bulk_pairs": b0,
                                        "finish_pair_fallbacks": l0}},
            stats_after={"tpu_turbo": {"finish_bulk_pairs": b1,
                                       "finish_pair_fallbacks": l1}})

    assert read(spec, window(10, 1, 10 + 750, 1 + 18)) == \
        pytest.approx(100 * 750 / 768)
    assert read(spec, window(5, 0, 5, 0)) == 0.0
    bare = SimpleNamespace(stats_before={"tpu_turbo": {}},
                           stats_after={"tpu_turbo": {}})
    assert read(spec, bare) == 0.0


@pytest.mark.parametrize("name, numerator, denominator", [
    ("gather_queries_per_launch.search", "sparse_queries",
     "sparse_gather_launches"),
    ("slices_per_pass.search", "sparse_slices", "sparse_slice_passes"),
])
def test_a_counter_ratio_is_declared_for_the_bm25_match_cells_and_reads_a_ratio(
        name, numerator, denominator):
    """The benchmark's two ratios of the sparse tier's own counters,
    `gather_queries_per_launch.search` (PR 35: cold sides served over
    gather programs launched; a tree that launches a program a (partition,
    query) reads 1.0) and `slices_per_pass.search` (PR 39: slices built
    over the build passes that placed one): declared in the traced runs
    of the `match` cells whose device counter is the fused turbo's; the
    reader gives the rise of the numerator over the rise of the
    denominator, 0.0 where the denominator did not move or the stats
    lack it (PR 39's parent has no `sparse_slice_passes`: its traced run
    prints a line, not a KeyError)."""
    from types import SimpleNamespace

    from benchmark.manifest import ROOT, Manifest, load_kind
    from elasticsearch_tpu.search.serving import turbo_node_stats

    m = Manifest(ROOT)
    for cell in m.cell_names():
        declared = name in {x["name"] for x in m.declared(cell, 1)}
        c = m.cell(cell)          # (the `match` route's cells: the bool
        #                           cell's finish is `_finish_bool_chunk`)
        assert declared == (c.config["device_counter"].startswith(
            "tpu_turbo.") and c.traffic["request"]["kind"] == "match"), cell
        assert name not in {x["name"] for x in m.declared(cell, 0)}
    spec = m.metric_spec(name)
    assert spec["kind"] == "counter_share" and spec["scale"] == 1
    assert spec["numerator"] == [f"tpu_turbo.{numerator}"]
    assert spec["denominator"] == [f"tpu_turbo.{denominator}"]
    assert {numerator, denominator} <= set(turbo_node_stats())
    read = load_kind(m.dir, "reader", spec["kind"]).read

    def window(n0, d0, n1, d1):
        return SimpleNamespace(
            stats_before={"tpu_turbo": {numerator: n0, denominator: d0}},
            stats_after={"tpu_turbo": {numerator: n1, denominator: d1}})

    assert read(spec, window(7, 7, 7 + 768, 7 + 9)) == \
        pytest.approx(768 / 9)
    assert read(spec, window(7, 7, 7 + 768, 7 + 768)) == 1.0
    assert read(spec, window(5, 5, 5, 5)) == 0.0
    bare = SimpleNamespace(stats_before={"tpu_turbo": {}},
                           stats_after={"tpu_turbo": {}})
    assert read(spec, bare) == 0.0
    lacks = SimpleNamespace(stats_before={"tpu_turbo": {numerator: 9000}},
                            stats_after={"tpu_turbo": {numerator: 11100}})
    assert read(spec, lacks) == 0.0


def test_the_metric_reads_the_engines_own_counters(solo_engine):
    """What the metric divides, on a live engine: a 16-wide chunk's twelve
    cold sides over its one program."""
    before = _snap([solo_engine])
    solo_engine.search_many([_wide_queries(16)], k=K)
    (d,) = _rise([solo_engine], before)
    assert d["sparse_queries"] / d["sparse_gather_launches"] == 12


def test_a_call_leaves_nothing_to_the_cycle_collector(solo_engine):
    """A group holds its queries only until its launch and a query its
    group after it: no reference cycle, so a call's handles and result
    blocks go when the call returns. (With a cycle every 256-query call
    left 768 handles to the collector, and a closed loop's window ran
    twice the full collections.)"""
    import gc

    qs = _wide_queries(16)
    solo_engine.search_many([qs], k=K)
    handles = solo_engine._start_gathers(
        [(0, [(tm, b, solo_engine._term(tm)) for tm, b in qs[0][1:]])], False)
    assert handles[0].group.launched and not handles[0].group.members
    solo_engine._discard_gather(handles[0])
    del handles
    gc.collect()
    gc.disable()
    try:
        solo_engine.search_many([qs], k=K)
        assert gc.collect() == 0
    finally:
        gc.enable()


# ---- PR 39: a call's missing slices are built in ONE pass ----
#
# `_build_slices` allocates, packs and uploads all the slices of one call
# together. What it replaced, the build a term at a time, lives on here as
# the oracle the pass is held to byte for byte: the same runs, the same
# victims in the same order, the same granules on both sides of the pool,
# the same `_sp_of` tuples (step pairs included), the same books.

_SPECIAL_DFS = (1, 2, 100, 1023, 1024, 1025, 3000, 4096, 4097, 5000, 16384)
_N_DOCS = 40_000           # three tiles of 16,384 docs: real step pairs
_N_SMALL = 400


@pytest.fixture(scope="module")
def df_corpus():
    """A partition whose terms have the document frequencies the cases
    name: `d<df>` for each of `_SPECIAL_DFS`, `r000`..`r399` of 1..600,
    and `zall` in every document (the one colized term)."""
    from elasticsearch_tpu.index.segment import build_field_postings

    rng = np.random.default_rng(39)
    dfs = {f"d{df:05d}": df for df in _SPECIAL_DFS}
    dfs.update((f"r{i:03d}", int(df)) for i, df in
               enumerate(rng.integers(1, 601, _N_SMALL)))
    dfs["zall"] = _N_DOCS
    names = sorted(dfs)
    docs_l, terms_l = [], []
    for o, name in enumerate(names):
        docs = np.sort(rng.choice(_N_DOCS, dfs[name], replace=False))
        docs = np.repeat(docs, rng.integers(1, 4, len(docs)))   # tf 1..3
        docs_l.append(docs)
        terms_l.append(np.full(len(docs), o, np.int64))
    tok_docs, tok_terms = np.concatenate(docs_l), np.concatenate(terms_l)
    order = np.argsort(tok_docs, kind="stable")
    lens = np.bincount(tok_docs, minlength=_N_DOCS).astype(np.int64)
    return build_field_postings("body", lens, tok_docs[order],
                                tok_terms[order], names), dfs


def _term_at_a_time(self, need, protect):
    """The oracle: `TurboBM25._build_slices` as it stood before the pass
    (PR 38's tree), with its `_sp_alloc`, `_sp_evict` and `_slice_pairs`."""
    from elasticsearch_tpu.parallel.turbo import (
        SPARSE_IMP_MAX, TILE, _SPARSE_UP_BUCKETS, jnp, sparse_pool_update)

    def evict(term):
        g0, n_g, w, _, _ = self._sp_of.pop(term)
        self._sp_lru.pop(term, None)
        self._sp_free.setdefault(n_g, []).append(g0)
        self.stats["sparse_bytes"] -= w * 4

    def alloc(n_g):
        free = self._sp_free.get(n_g)
        if free:
            return free.pop()
        cur = 0 if self._sp_pool is None else self._sp_pool.shape[0]
        if self._sp_next + n_g > cur and cur < self._sp_cap:
            self._sp_grow(min(self._sp_cap,
                              max(cur * 2, self._sp_next + n_g, 64)))
            cur = self._sp_pool.shape[0]
        if self._sp_next + n_g <= cur:
            g0 = self._sp_next
            self._sp_next += n_g
            return g0
        for t in sorted(self._sp_lru, key=self._sp_lru.get):
            if t in protect or t not in self._sp_of:
                continue
            evict(t)
            free = self._sp_free.get(n_g)
            if free:
                return free.pop()
        return -1

    def slice_pairs(docs):
        key = docs // TILE
        if len(docs) <= SPARSE_GRAN:
            return np.flatnonzero(np.bincount(key))
        key += np.arange(len(docs)) // SPARSE_GRAN << 16
        new = np.ones(len(key), bool)
        np.not_equal(key[1:], key[:-1], out=new[1:])
        return key[new]

    fp = self.fp
    idx_l, upd_l = [], []
    fits = True
    try:
        for t, info, w in need:
            n_g = w // SPARSE_GRAN
            g0 = alloc(n_g)
            if g0 < 0:
                fits = False
                break
            lo = int(fp.post_start[info.ord])
            hi = int(fp.post_start[info.ord + 1])
            docs = np.asarray(fp.post_doc[lo:hi], np.int64)
            lanes = self._host_scores[
                info.row_start: info.row_start + info.n_rows
            ].ravel()[: hi - lo].astype(np.float64)
            sscale = max(float(info.smax), 1e-9) / SPARSE_IMP_MAX
            q = np.clip(np.rint(lanes / sscale),
                        1, SPARSE_IMP_MAX).astype(np.int64)
            buf = np.zeros(w, np.int64)
            buf[: hi - lo] = (docs << 8) | q
            gran = buf.astype(np.int32).reshape(
                n_g, SPARSE_GRAN // 128, 128)
            self._sp_host[g0: g0 + n_g] = gran
            self._sp_of[t] = (g0, n_g, w, sscale, slice_pairs(docs))
            self._sp_lru[t] = self._tick
            idx_l.append(np.arange(g0, g0 + n_g, dtype=np.int32))
            upd_l.append(gran)
            self.stats["sparse_slices"] += 1
            self.stats["sparse_bytes"] += w * 4
        if not idx_l:
            return False
        idx = np.concatenate(idx_l)
        upd = np.concatenate(upd_l, axis=0)
        nb = next((b for b in _SPARSE_UP_BUCKETS if b >= len(idx)),
                  -(-len(idx) // _SPARSE_UP_BUCKETS[-1])
                  * _SPARSE_UP_BUCKETS[-1])
        pad = nb - len(idx)
        idx = np.concatenate([idx, np.zeros(pad, np.int32)])
        upd = np.concatenate(
            [upd, np.zeros((pad, SPARSE_GRAN // 128, 128), np.int32)])
        with faults.device_errors("sparse_gather", self.part_id):
            self._sp_pool = sparse_pool_update(
                self._sp_pool, jnp.asarray(idx), jnp.asarray(upd))
    except DeviceFaultError:
        self._reset_sparse()
        raise
    return fits


def _assert_same_pool(a, b, label):
    """Engine `a` (the pass) against engine `b` (the oracle): everything
    the sparse tier keeps, and mirror == device on both."""
    assert (a._sp_host is None) == (b._sp_host is None), label
    if a._sp_host is not None:
        assert a._sp_host.dtype == b._sp_host.dtype
        assert np.array_equal(a._sp_host, b._sp_host), f"{label}: mirror"
        for e in (a, b):
            assert np.array_equal(np.asarray(e._sp_pool), e._sp_host), \
                f"{label}: mirror != device"
    assert list(a._sp_of) == list(b._sp_of), f"{label}: resident terms"
    for t, (g0, n_g, w, sscale, pairs) in a._sp_of.items():
        g0_b, n_g_b, w_b, sscale_b, pairs_b = b._sp_of[t]
        assert (g0, n_g, w, sscale) == (g0_b, n_g_b, w_b, sscale_b), (label, t)
        assert [type(x) for x in (g0, n_g, w, sscale)] == \
            [int, int, int, float], (label, t)
        # (its own array: a view would keep its whole call's pairs alive
        # for as long as this one slice stays resident)
        assert pairs.dtype == pairs_b.dtype and pairs.base is None
        assert np.array_equal(pairs, pairs_b), f"{label}: pairs of {t}"
    assert list(a._sp_lru.items()) == list(b._sp_lru.items()), f"{label}: LRU"
    assert a._sp_free == b._sp_free, f"{label}: free lists"
    assert a._sp_next == b._sp_next, f"{label}: bump pointer"
    for key in ("sparse_slices", "sparse_bytes"):
        assert a.stats[key] == b.stats[key], (label, key)
    assert a.stats["sparse_bytes"] == 4 * sum(
        w for _g0, _n, w, _s, _p in a._sp_of.values()), label


def _small(lo, hi):
    return [f"r{i:03d}" for i in range(lo, hi)]


# a case = (pool cap in granules or None, [(terms, keep, fits expected)]);
# each step is one `_ensure_sparse` call on both engines
_PASS_CASES = {
    "one term": (None, [(["r007"], (), True)]),
    "df 1": (None, [(["d00001"], (), True)]),
    "df exactly a ladder rung": (None, [
        (["d01024"], (), True), (["d04096", "d16384", "d01023"], (), True)]),
    "multi-granule slices": (None, [
        (["d01025", "d03000", "r001", "d04097", "d05000"], (), True)]),
    "several hundred terms in one call": (None, [
        (_small(0, _N_SMALL) + [f"d{df:05d}" for df in _SPECIAL_DFS],
         (), True)]),
    "a pool at its cap, victims taken": (24, [
        (_small(0, 12) + ["d03000"], (), True),            # 12 + 4 of 23
        (_small(4, 8), (), True),                          # ticks only
        (_small(12, 22), (), True),      # 7 by the bump, 3 evict r000..
        (["d01025", "r002", "d04097"], (), False),  # a 4-run: evicts across
        #   widths down to d03000; a 16-run: every victim goes, none frees one
        (_small(0, 4) + _small(30, 40), (), True)]),
    "pool pressure with everything protected": (4, [
        (_small(0, 6), (), False),
        (["d03000"], (), False),         # no run of four can ever be had
        (_small(6, 8), (), True)]),
    "keep honoured": (4, [
        (_small(0, 3), (), True),
        (["r010"], ("r000",), True),     # r000 is the oldest and kept
        (["r011", "r012"], ("r010", "r002"), False)]),
}


@pytest.mark.parametrize("case", list(_PASS_CASES))
def test_the_one_pass_build_is_the_per_term_build_byte_for_byte(
        df_corpus, case):
    from types import MethodType

    from elasticsearch_tpu.common import metrics

    fp, dfs = df_corpus
    cap, steps = _PASS_CASES[case]
    a = _turbo(fp, _N_DOCS, cold_df=20_000)
    b = _turbo(fp, _N_DOCS, cold_df=20_000)
    b._build_slices = MethodType(_term_at_a_time, b)
    if cap is not None:
        a._sp_cap = b._sp_cap = cap
    for si, (terms, keep, fits) in enumerate(steps):
        label = f"{case}, call {si}"
        pairs = [(t, a._term(t)) for t in terms]
        assert all(info.df == dfs[t] for t, info in pairs)
        node0 = turbo_mod.node_sparse_stats()
        seen0 = metrics.summary("sparse_slice_width")
        built0 = a.stats["sparse_slices"]
        got = a._ensure_sparse(pairs, keep=keep)
        node1 = turbo_mod.node_sparse_stats()
        seen1 = metrics.summary("sparse_slice_width")
        assert got == b._ensure_sparse(
            [(t, b._term(t)) for t in terms], keep=keep), label
        assert got == fits, label
        _assert_same_pool(a, b, label)
        # the books once a call: the node's counters and the histogram
        # rise by what the pass placed, and one pass is counted for it
        built = a.stats["sparse_slices"] - built0
        assert node1["sparse_slices"] - node0["sparse_slices"] == built
        assert (node1["sparse_slice_passes"]
                - node0["sparse_slice_passes"]) == (1 if built else 0), label
        assert ((seen1 or {}).get("count", 0)
                - (seen0 or {}).get("count", 0)) == built, label
        if fits:
            assert all(t in a._sp_of for t in terms), label
    assert a.stats["sparse_slices"] > 0


@pytest.mark.faults
def test_a_faulted_upload_of_the_one_pass_build_drops_every_slice(
        df_corpus, monkeypatch):
    """The pass and the oracle under the same faulted `sparse_pool_update`:
    both raise `DeviceFaultError`, both end with an empty tier whose
    mirror is the device's bytes, and both rebuild the same pool after."""
    from types import MethodType

    fp, _dfs = df_corpus
    a = _turbo(fp, _N_DOCS, cold_df=20_000)
    b = _turbo(fp, _N_DOCS, cold_df=20_000)
    b._build_slices = MethodType(_term_at_a_time, b)
    first = _small(0, 20) + ["d03000"]
    for e in (a, b):
        assert e._ensure_sparse([(t, e._term(t)) for t in first])
    real = turbo_mod.sparse_pool_update

    def lost(pool, idx, upd):
        raise RuntimeError("INTERNAL: device lost")

    second = _small(15, 40) + ["d05000"]
    monkeypatch.setattr(turbo_mod, "sparse_pool_update", lost)
    for e in (a, b):
        with pytest.raises(DeviceFaultError):
            e._ensure_sparse([(t, e._term(t)) for t in second])
        assert not e._sp_of and not e._sp_lru and not e._sp_free
        assert e._sp_next == 1 and e.stats["sparse_bytes"] == 0
        assert not e._sp_host.any()
    _assert_same_pool(a, b, "after the fault")
    monkeypatch.setattr(turbo_mod, "sparse_pool_update", real)
    for e in (a, b):
        assert e._ensure_sparse([(t, e._term(t)) for t in second])
    _assert_same_pool(a, b, "rebuilt")
    assert set(second) == set(a._sp_of)
