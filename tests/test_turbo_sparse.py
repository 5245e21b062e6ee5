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


def test_gather_queries_per_launch_is_declared_for_the_bm25_cells_and_reads_a_ratio():
    """The benchmark's `gather_queries_per_launch.search`: declared in the
    traced runs of the cells whose device counter is the fused turbo's;
    its reader gives the rise of `sparse_queries` over the rise of
    `sparse_gather_launches` (both counters older than the group gather:
    a tree that launches a program a (partition, query) reads 1.0), 0
    where no gather was launched or the stats lack the counters."""
    from types import SimpleNamespace

    from benchmark.manifest import ROOT, Manifest, load_kind

    m = Manifest(ROOT)
    name = "gather_queries_per_launch.search"
    for cell in m.cell_names():
        declared = name in {x["name"] for x in m.declared(cell, 1)}
        c = m.cell(cell)          # (the `match` route's cells: the bool
        #                           cell's finish is `_finish_bool_chunk`)
        assert declared == (c.config["device_counter"].startswith(
            "tpu_turbo.") and c.traffic["request"]["kind"] == "match"), cell
        assert name not in {x["name"] for x in m.declared(cell, 0)}
    spec = m.metric_spec(name)
    assert spec["kind"] == "counter_share" and spec["scale"] == 1
    read = load_kind(m.dir, "reader", spec["kind"]).read

    def window(q0, l0, q1, l1):
        return SimpleNamespace(
            stats_before={"tpu_turbo": {"sparse_queries": q0,
                                        "sparse_gather_launches": l0}},
            stats_after={"tpu_turbo": {"sparse_queries": q1,
                                       "sparse_gather_launches": l1}})

    assert read(spec, window(7, 7, 7 + 768, 7 + 9)) == \
        pytest.approx(768 / 9)
    assert read(spec, window(7, 7, 7 + 768, 7 + 768)) == 1.0
    assert read(spec, window(5, 5, 5, 5)) == 0.0
    bare = SimpleNamespace(stats_before={"tpu_turbo": {}},
                           stats_after={"tpu_turbo": {}})
    assert read(spec, bare) == 0.0


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
