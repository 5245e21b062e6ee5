"""Device bitset-intersection differential suite (PR 16).

The bool path's match sets are packed 32-docs-per-lane into uint32
columns next to the int8 impact columns; conjunction masks come from a
blockwise AND / AND-NOT Pallas kernel and the sweep skips chunks whose
intersected mask is all-zero. The contract is unchanged from the dense
coverage-matmul engine it replaces: the device mask is a SUPERSET of
the true match set (clauses beyond the kernel fan-in are dropped from
the mask only) and the exact host rescore re-tests every clause, so
top-k stays BIT-identical to `search_bool_host` on every route — solo,
fused S > 1, split flushes, the dense engine (ES_TPU_BITSET=0), the
galloping host fallback, injected `bitset_intersect` faults, and an
HBM scrub cycle that repairs a corrupted bitset region.

Runs on the host-simulated 8-device CPU mesh from tests/conftest.py
(Pallas kernels interpret on CPU)."""

import numpy as np
import pytest

from elasticsearch_tpu.common import faults, integrity
from elasticsearch_tpu.index.segment import build_field_postings
from elasticsearch_tpu.parallel.spmd import build_stacked_bm25
from elasticsearch_tpu.parallel.turbo import TurboBM25, _intersect_sorted

pytestmark = pytest.mark.multidevice


class _Seg:
    def __init__(self, n_docs, fp):
        self.n_docs = n_docs
        self.postings = {"body": fp}
        self.vectors = {}


def _pcorpus(n_docs, vocab, seed, reps=1):
    """`reps` copies of `n_docs` random docs, one after the other."""
    rng = np.random.default_rng(seed)
    probs = 1.0 / np.arange(1, vocab + 1) ** 1.1
    probs /= probs.sum()
    lens = rng.integers(4, 24, size=n_docs).astype(np.int64)
    tokens = rng.choice(vocab, size=int(lens.sum()), p=probs).astype(np.int64)
    lens, tokens = np.tile(lens, reps), np.tile(tokens, reps)
    tok_docs = np.repeat(np.arange(n_docs * reps, dtype=np.int64), lens)
    bounds = np.concatenate([[0], np.cumsum(lens)])
    tok_pos = (np.arange(len(tokens), dtype=np.int64)
               - np.repeat(bounds[:-1], lens))
    return build_field_postings("body", lens, tok_docs, tokens,
                                [f"t{i}" for i in range(vocab)],
                                token_pos=tok_pos)


def _turbo(fp, n_docs, cold_df=5, hbm=64 << 20, **kw):
    stacked = build_stacked_bm25([_Seg(n_docs, fp)], "body", serve_only=True)
    return TurboBM25(stacked, hbm_budget_bytes=hbm, cold_df=cold_df, **kw)


def _fused(parts, **kw):
    from elasticsearch_tpu.search.serving import TurboEngine, _turbo_mesh

    turbos = [_turbo(fp, n, **kw) for n, fp in parts]
    return TurboEngine(turbos, mesh=_turbo_mesh(len(turbos)))


def _assert_identical(a, b, label):
    (sa, da), (sb, db) = a, b
    assert np.array_equal(np.asarray(da), np.asarray(db)), \
        f"{label}: doc ids differ"
    assert np.array_equal(np.asarray(sa), np.asarray(sb)), \
        f"{label}: scores differ (not bit-identical)"


# every clause kind the intersect kernel has to represent, plus fan-in
# overflow (>8 required, >4 must_not -> subset-AND superset masks)
SPECS = [
    {"must": [("t1", 1.0), ("t3", 1.0)], "should": [("t5", 1.0)]},
    {"must": [("t0", 1.0)], "must_not": ["t2"],
     "should": [("t7", 1.0), ("t9", 0.5)]},
    {"filter": ["t4"], "should": [("t1", 1.0)]},
    {"must": [("t2", 1.0), ("t6", 2.0)], "must_not": ["t1", "t3"],
     "should": [("t0", 1.0)]},
    {"must": [("t5", 1.0)], "should": [("t8", 1.0), ("t10", 1.0)]},
    {"must": [(f"t{i}", 1.0) for i in range(10)]},          # > BITSET_CLAUSES
    {"must": [("t0", 1.0)],
     "must_not": [f"t{i}" for i in range(1, 8)]},           # > BITSET_NEGS
    {"must": [("t1", 1.0)], "filter": ["t0", "t2"], "must_not": ["t30"]},
    {"must": [("absent", 1.0), ("t1", 1.0)]},               # unmatchable
    {"should": [("t3", 1.0), ("t7", 2.0)]},                 # no required
]
K = 10


def test_bitset_solo_bit_identical(monkeypatch):
    monkeypatch.setenv("ES_TPU_BITSET", "1")
    monkeypatch.setenv("ES_TPU_BITSET_HOST_DF", "0")
    t = _turbo(_pcorpus(2500, 40, 7), 2500)
    got = t.search_bool(SPECS, k=K)
    want = t.search_bool_host(SPECS, k=K)
    _assert_identical(got, want, "solo bitset vs host")
    assert t.stats["bool_device"] > 0, "device route never engaged"
    assert t.stats["bitset_packs"] > 0, "bitsets never packed"
    assert t.stats["bitset_blocks_skipped"] > 0, "no chunk ever skipped"
    assert (t.stats["bitset_bytes"]
            == t.bits.nbytes + t.live_bits.nbytes > 0)


def test_bitset_dense_ab_identical(monkeypatch):
    """ES_TPU_BITSET=0 keeps the dense coverage-matmul sweep selectable,
    and both engines give the same bits."""
    monkeypatch.setenv("ES_TPU_BITSET_HOST_DF", "0")
    fp = _pcorpus(1800, 36, 8)
    monkeypatch.setenv("ES_TPU_BITSET", "0")
    dense = _turbo(fp, 1800)
    got_dense = dense.search_bool(SPECS, k=K)
    assert dense.bits is None, "dense engine packed bitsets anyway"
    assert dense.stats["bitset_packs"] == 0
    monkeypatch.setenv("ES_TPU_BITSET", "1")
    bits = _turbo(fp, 1800)
    got_bits = bits.search_bool(SPECS, k=K)
    _assert_identical(got_bits, got_dense, "bitset vs dense A/B")
    _assert_identical(got_bits, bits.search_bool_host(SPECS, k=K),
                      "bitset vs host")


def test_bitset_split_flushes(monkeypatch):
    """qc_sizes=(8,) forces one search_bool call through several device
    chunks; every flush runs the intersect + masked sweep and the
    concatenated result stays bit-identical."""
    monkeypatch.setenv("ES_TPU_BITSET", "1")
    monkeypatch.setenv("ES_TPU_BITSET_HOST_DF", "0")
    rng = np.random.default_rng(5)
    specs = list(SPECS)
    for _ in range(20):
        a, b, c = rng.choice(30, size=3, replace=False)
        specs.append({"must": [(f"t{a}", 1.0)], "should": [(f"t{b}", 1.0)],
                      "must_not": [f"t{c}"]})
    t = _turbo(_pcorpus(2200, 40, 9), 2200, qc_sizes=(8,))
    got = t.search_bool(specs, k=K)
    _assert_identical(got, t.search_bool_host(specs, k=K),
                      "split flushes vs host")
    assert t.stats["bool_device"] > 8, "batch did not split across flushes"


def test_bitset_fused_bit_identical(monkeypatch):
    """S=3 fused dispatch (different sizes, vocabularies, and therefore
    per-partition Hp/bitset shapes) against each partition's host
    route."""
    monkeypatch.setenv("ES_TPU_BITSET", "1")
    monkeypatch.setenv("ES_TPU_BITSET_HOST_DF", "0")
    eng = _fused([(1500, _pcorpus(1500, 40, 1)),
                  (900, _pcorpus(900, 56, 2)),
                  (2100, _pcorpus(2100, 32, 3))])
    st = eng._fused()
    per = st.search_bool(SPECS, k=K)
    for si, t in enumerate(st.turbos):
        _assert_identical(per[si], t.search_bool_host(SPECS, k=K),
                          f"fused partition {si} vs host")
    assert st.bits is not None, "fused bitsets never stacked"
    assert sum(t.stats["bitset_blocks_skipped"] for t in st.turbos) > 0
    # ledger cross-check: with the bitset regions packed, each engine's
    # ledgered occupancy stays byte-identical to its hbm_bytes(), and the
    # facade total covers the per-partition and fused caches exactly
    for t in st.turbos:
        assert t._hbm.total_bytes() == t.hbm_bytes()
        assert t.bits.nbytes > 0
    assert st._hbm.total_bytes() == st.hbm_bytes()
    assert eng.hbm_bytes() == (sum(t.hbm_bytes() for t in st.turbos)
                               + st.hbm_bytes())


def test_bitset_fused_primed_shape_retraces_nothing(monkeypatch):
    """Once a batch width is primed through `extend_qc_sizes` and warmed,
    a fused bool dispatch of that width traces no program
    (`tpu_compile.retraces` stands still): what the scheduler's ladder
    priming promises the served path."""
    from elasticsearch_tpu.common import hbm_ledger

    monkeypatch.setenv("ES_TPU_BITSET", "1")
    monkeypatch.setenv("ES_TPU_BITSET_HOST_DF", "0")
    eng = _fused([(1500, _pcorpus(1500, 40, 1)),
                  (900, _pcorpus(900, 56, 2))])
    eng.extend_qc_sizes([len(SPECS)])
    eng._fused()
    eng.extend_qc_sizes([len(SPECS)])    # the lazily built fused dispatcher
    want = eng.search_bool(SPECS, k=K)   # warm pass: packs, first trace
    r0 = hbm_ledger.compile_stats()["retraces"]
    got = eng.search_bool(SPECS, k=K)
    assert hbm_ledger.compile_stats()["retraces"] == r0
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))


def test_bitset_fused_after_disjunctive_traffic(monkeypatch):
    """The order a served index sees: match traffic builds and syncs the
    columns first, the first bool packs the bitsets later. The fused
    stack must pick the bitsets up although its columns are in sync
    (it used to skip them and raise TypeError on `bits[i]` — which the
    serving layer contained by answering every bool from the dense
    executor)."""
    monkeypatch.setenv("ES_TPU_BITSET", "1")
    monkeypatch.setenv("ES_TPU_BITSET_HOST_DF", "0")
    eng = _fused([(1500, _pcorpus(1500, 40, 1)),
                  (900, _pcorpus(900, 56, 2))])
    st = eng._fused()
    terms = sorted({t for spec in SPECS
                    for t, _ in spec.get("must", []) + spec.get("should", [])}
                   | {t for spec in SPECS
                      for t in spec.get("filter", []) + spec.get("must_not", [])})
    st.search_many([[[(t, 1.0)] for t in terms]], k=K)
    assert st.bits is None
    per = st.search_bool(SPECS, k=K)
    for si, t in enumerate(st.turbos):
        _assert_identical(per[si], t.search_bool_host(SPECS, k=K),
                          f"fused partition {si} vs host")
    assert st.bits is not None


def test_bitset_gallop_host_fallback(monkeypatch):
    """A threshold above every df routes every bool query to the
    galloping host intersection — same bits, counter moves."""
    monkeypatch.setenv("ES_TPU_BITSET", "1")
    monkeypatch.setenv("ES_TPU_BITSET_HOST_DF", str(1 << 30))
    t = _turbo(_pcorpus(1600, 40, 10), 1600)
    got = t.search_bool(SPECS, k=K)
    _assert_identical(got, t.search_bool_host(SPECS, k=K),
                      "galloped vs host")
    assert t.stats["bitset_gallop"] > 0, "gallop route never engaged"
    assert t.stats["bitset_blocks_skipped"] == 0, \
        "device sweep ran despite gallop threshold"


def test_intersect_sorted_matches_numpy():
    rng = np.random.default_rng(11)
    for na, nb in [(3, 4000), (200, 250), (0, 50), (70, 0), (1, 1)]:
        a = np.unique(rng.integers(0, 10000, size=na).astype(np.int64))
        b = np.unique(rng.integers(0, 10000, size=nb).astype(np.int64))
        got = _intersect_sorted(a, b)
        want = np.intersect1d(a, b)
        assert np.array_equal(np.sort(got), want), (na, nb)


@pytest.mark.faults
def test_bitset_fault_contained_per_partition(monkeypatch):
    """An injected bitset_intersect fault on one partition host-scores
    that partition only — results stay bit-identical and the fault is
    attributed to the faulted partition."""
    monkeypatch.setenv("ES_TPU_BITSET", "1")
    monkeypatch.setenv("ES_TPU_BITSET_HOST_DF", "0")
    eng = _fused([(700, _pcorpus(700, 40, 12)),
                  (900, _pcorpus(900, 32, 13))])
    want = eng._merge3([t.search_bool_host(SPECS, k=K)
                        for t in eng.turbos], len(SPECS), K)
    flog = []
    with faults.inject("bitset_intersect#1:raise@1"):
        got = eng.search_bool(SPECS, k=K, fault_log=flog)
    for g, w, name in zip(got, want, ("scores", "parts", "ords")):
        assert np.array_equal(np.asarray(g), np.asarray(w)), name
    assert any(f.site == "bitset_intersect" and f.partition == 1
               for f in flog)
    # the faulted partition recovers: a clean retry packs and serves the
    # device bitset route again, still bit-identical
    clean = eng.search_bool(SPECS, k=K)
    for g, w, name in zip(clean, want, ("scores", "parts", "ords")):
        assert np.array_equal(np.asarray(g), np.asarray(w)), name


@pytest.mark.faults
def test_bitset_scrub_bitflip_repair(monkeypatch):
    """PR-15 integrity plane over the new region: an injected hbm_region
    flip on cols_bits is detected by the scrubber, repaired by re-packing
    from the (separately scrubbed) column cache, and the repaired engine
    answers bit-identically."""
    monkeypatch.setenv("ES_TPU_BITSET", "1")
    monkeypatch.setenv("ES_TPU_BITSET_HOST_DF", "0")
    fp = _pcorpus(1400, 36, 14)
    control = _turbo(fp, 1400)
    want = control.search_bool(SPECS, k=K)
    _assert_identical(want, control.search_bool_host(SPECS, k=K), "control")

    integrity.reset_scrub_for_tests()      # only the engine below scrubs
    t = _turbo(fp, 1400)
    t.search_bool(SPECS, k=K)              # packs bits, registers region
    assert t.bits is not None

    def cycle():
        return [integrity.scrub_once()
                for _ in range(integrity.scrub_registry_size())]

    cycle()                                # baseline pass: all clean
    m0 = integrity.integrity_stats()["scrub_mismatches"]
    with faults.inject("hbm_region#cols_bits:raise@1x1"):
        results = cycle()
    hit = [r for r in results if r and r["result"] == "mismatch"]
    assert len(hit) == 1 and hit[0]["region"].endswith(".cols_bits")
    st = integrity.integrity_stats()
    assert st["scrub_mismatches"] == m0 + 1
    assert st["scrub_repairs"] >= 1
    _assert_identical(t.search_bool(SPECS, k=K), want,
                      "repaired bitset engine vs control")
    # next cycle is clean again (the repair re-baselined the region)
    cycle()
    assert integrity.integrity_stats()["scrub_mismatches"] == m0 + 1
