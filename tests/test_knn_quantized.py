"""Quantized sharded kNN differential suite (PR 19).

The KnnEngine first pass scores per-row int8 quantized vectors with the
`knn_int8_window_topc` Pallas kernel, carrying a tracked quantization
bound so the candidate set is a provable SUPERSET of the true top-k;
survivors are exact-rescored on device (bf16 gemm, same arithmetic as
the `knn_top_k` f32 reference) and merged with the deterministic
(score desc, partition asc, doc asc) tie-break. The contract: top-k is
BIT-identical to the f32 brute-force reference on every route — solo,
fused S > 1 over the ICI mesh, filtered, the `ES_TPU_KNN_INT8=0` dense
A/B, and IVF at nprobe=0. IVF coarse pruning trades exactness for
probes: recall@10 must stay >= 0.99 at the documented probe count.

Fault plane: an injected `knn_score` fault on one partition is contained
to that partition (peers still serve from device, the failed partition
falls back to the exact host path); an `hbm_region` flip on the int8
shard pool is detected by the scrubber, repaired from the host mirror,
and the repaired engine answers bit-identically.

Runs on the host-simulated 8-device CPU mesh from tests/conftest.py
(Pallas kernels interpret on CPU)."""

import numpy as np
import pytest

from elasticsearch_tpu.common import faults, integrity
from elasticsearch_tpu.index.segment import VectorColumn
from elasticsearch_tpu.parallel import knn as knn_mod
from elasticsearch_tpu.parallel.knn import KnnEngine, KnnWork
from elasticsearch_tpu.parallel.spmd import make_mesh

pytestmark = pytest.mark.multidevice

K = 10
DIMS = 48


def _cols(sizes, dims=DIMS, similarity="cosine", seed=7, unit=False):
    rng = np.random.default_rng(seed)
    cols = []
    for n in sizes:
        v = rng.standard_normal((n, dims)).astype(np.float32)
        if unit:
            v /= np.maximum(np.linalg.norm(v, axis=1), 1e-20)[:, None]
        cols.append(VectorColumn(
            vectors=v, norms=np.linalg.norm(v, axis=1).astype(np.float32),
            exists=rng.random(n) > 0.04, dims=dims, similarity=similarity))
    return cols


def _queries(nq, dims=DIMS, seed=3, unit=False):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((nq, dims)).astype(np.float32)
    if unit:
        q /= np.maximum(np.linalg.norm(q, axis=1), 1e-20)[:, None]
    return q


def _reference(cols, qs, k, similarity, masks=None):
    """f32 brute force: `knn_top_k` per partition (rows pre-normalized
    for cosine, exactly as the engine stores them) + the deterministic
    (score desc, partition asc, ord asc) merge. s <= 0 marks empty."""
    import jax.numpy as jnp

    from elasticsearch_tpu.ops.knn import knn_top_k

    nq = len(qs)
    per = []
    for pi, col in enumerate(cols):
        v = col.vectors
        if similarity == "cosine":
            v = v / np.maximum(col.norms, 1e-20)[:, None]
        mask = np.ones(len(v), bool) if masks is None else masks[pi]
        ts, to, ok = knn_top_k(
            jnp.asarray(qs), jnp.asarray(v).astype(jnp.bfloat16),
            jnp.asarray(col.norms), jnp.asarray(col.exists),
            jnp.asarray(mask), similarity=similarity, k=k)
        ts, to = np.asarray(ts), np.asarray(to)
        per.append((np.where(np.asarray(ok), ts, 0.0), to))
    ws = np.zeros((nq, k), np.float32)
    wp = np.zeros((nq, k), np.int32)
    wo = np.zeros((nq, k), np.int32)
    for qi in range(nq):
        rows = [(rs[qi, j], pi, ro[qi, j])
                for pi, (rs, ro) in enumerate(per)
                for j in range(k) if rs[qi, j] > 0]
        rows.sort(key=lambda r: (-r[0], r[1], r[2]))
        for j, (sv, pv, ov) in enumerate(rows[:k]):
            ws[qi, j], wp[qi, j], wo[qi, j] = sv, pv, ov
    return ws, wp, wo


def _assert_identical(got, want, label, ulps=1):
    """Ids, partitions and order exactly; scores to 1 ulp of f32. The
    engine's rescore is one [Q, Q*C] bf16 gemm and the reference's a
    [Q, n] one: whether their f32 accumulations round alike is a property
    of the XLA backend's gemm tiling, not of the engine (1 ulp on the
    dot; l2_norm's d2 -> sqrt -> 1/(1+x) carries it to 2)."""
    gs, gp, go = got
    ws, wp, wo = want
    gs = np.asarray(gs)
    assert np.all(np.abs(gs - ws) <= ulps * np.spacing(np.abs(ws))), \
        f"{label}: scores differ"
    assert np.array_equal(np.asarray(gp), wp), f"{label}: partitions differ"
    assert np.array_equal(np.asarray(go), wo), f"{label}: ords differ"


@pytest.mark.parametrize("similarity", ["cosine", "dot_product", "l2_norm"])
def test_int8_solo_bit_identical(similarity):
    unit = similarity == "dot_product"      # ES contract: unit vectors
    cols = _cols([3000], similarity=similarity, unit=unit)
    qs = _queries(20, unit=unit)
    eng = KnnEngine(cols)
    knn_mod.reset_for_tests()
    got = eng.search_many([[KnnWork(q) for q in qs]], k=K)[0]
    want = _reference(cols, qs, K, similarity)
    _assert_identical(got, want, f"solo {similarity}",
                      ulps=2 if similarity == "l2_norm" else 1)
    st = knn_mod.knn_node_stats()
    assert st["knn_int8_dispatches"] > 0, "int8 route never engaged"
    assert st["knn_host_fallbacks"] == 0
    assert st["knn_rescore_docs"] > 0


def test_int8_fused_sharded_bit_identical():
    """S=3 over a 4-way ICI mesh, query count straddling two qc rungs."""
    cols = _cols([2500, 1800, 2100], seed=17)
    qs = _queries(40, seed=5)
    eng = KnnEngine(cols, mesh=make_mesh(4, dp=1))
    assert eng._fused, "mesh engine did not take the fused route"
    got = eng.search_many([[KnnWork(q) for q in qs]], k=K)[0]
    _assert_identical(got, _reference(cols, qs, K, "cosine"), "fused S=3")


def test_int8_fused_primed_shape_retraces_nothing():
    """Once a batch rung is primed through `extend_qc_sizes` and warmed,
    a fused kNN dispatch inside that rung traces no program
    (`tpu_compile.retraces` stands still) and still takes the int8 pass."""
    from elasticsearch_tpu.common import hbm_ledger

    cols = _cols([2500, 1800, 2100], seed=17)
    works = [KnnWork(q) for q in _queries(24, seed=5)]
    eng = KnnEngine(cols, mesh=make_mesh(4, dp=1))
    eng.extend_qc_sizes([32])
    want = eng.search_many([works], k=K)[0]      # warm pass: first trace
    r0 = hbm_ledger.compile_stats()["retraces"]
    knn_mod.reset_for_tests()
    got = eng.search_many([works], k=K)[0]
    assert hbm_ledger.compile_stats()["retraces"] == r0
    assert knn_mod.knn_node_stats()["knn_int8_dispatches"] > 0
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))


def test_int8_off_ab_identical(monkeypatch):
    """ES_TPU_KNN_INT8=0 serves the same bits through the dense f32
    route with zero int8 dispatches."""
    cols = _cols([2200, 1600], seed=23)
    qs = _queries(16, seed=9)
    on = KnnEngine(cols)
    got_on = on.search_many([[KnnWork(q) for q in qs]], k=K)[0]
    monkeypatch.setenv("ES_TPU_KNN_INT8", "0")
    knn_mod.reset_for_tests()
    off = KnnEngine(cols)
    got_off = off.search_many([[KnnWork(q) for q in qs]], k=K)[0]
    _assert_identical(got_on, got_off, "int8 on vs off A/B")
    _assert_identical(got_off, _reference(cols, qs, K, "cosine"),
                      "int8 off vs reference")
    st = knn_mod.knn_node_stats()
    assert st["knn_int8_dispatches"] == 0, "int8 dispatched despite knob"
    assert st["knn_queries"] > 0


def test_filtered_bit_identical():
    """Per-partition filter masks (the BM25 candidate mask shape used by
    hybrid fusion) constrain the int8 pass and the reference equally."""
    cols = _cols([2400, 1900], seed=29)
    qs = _queries(12, seed=13)
    rng = np.random.default_rng(41)
    masks = [rng.random(len(c.vectors)) > 0.6 for c in cols]
    eng = KnnEngine(cols)
    works = [KnnWork(q, filters=masks) for q in qs]
    got = eng.search_many([works], k=K)[0]
    _assert_identical(got, _reference(cols, qs, K, "cosine", masks=masks),
                      "filtered")


def test_ivf_nprobe_zero_exact_and_recall(monkeypatch):
    """IVF builds at n >= 4096: nprobe=0 stays bit-exact; at the
    documented probe count recall@10 >= 0.99."""
    cols = _cols([9000], seed=37)
    qs = _queries(32, seed=19)
    eng = KnnEngine(cols)
    assert eng._cent_host[0].shape[0] > 1, "IVF never built at n=9000"
    want = _reference(cols, qs, K, "cosine")
    _assert_identical(eng.search_many([[KnnWork(q) for q in qs]], k=K)[0],
                      want, "ivf nprobe=0")

    monkeypatch.setenv("ES_TPU_KNN_NPROBE", "24")
    got = eng.search_many([[KnnWork(q) for q in qs]], k=K)[0]
    hits = total = 0
    for qi in range(len(qs)):
        truth = {(p, o) for s, p, o in
                 zip(want[0][qi], want[1][qi], want[2][qi]) if s > 0}
        found = {(p, o) for s, p, o in
                 zip(np.asarray(got[0])[qi], np.asarray(got[1])[qi],
                     np.asarray(got[2])[qi]) if s > 0}
        hits += len(truth & found)
        total += len(truth)
    assert total > 0 and hits / total >= 0.99, \
        f"IVF recall@10 {hits / total:.4f} < 0.99 at nprobe=24"


@pytest.mark.faults
def test_knn_score_fault_contained_per_partition():
    """An injected knn_score fault on partition 1 is contained: the
    fault log names only partition 1, peers keep serving, and the host
    fallback stays correctness-equal to the exact reference."""
    cols = _cols([1500, 1200, 1400], seed=43)
    qs = _queries(8, seed=21)
    eng = KnnEngine(cols)          # solo route: per-partition dispatch
    works = [[KnnWork(q) for q in qs]]
    want = _reference(cols, qs, K, "cosine")
    knn_mod.reset_for_tests()
    flog = []
    with faults.inject("knn_score#1:raise@1"):
        s, p, o = eng.search_many(works, k=K, fault_log=flog)[0]
    assert flog, "fault not surfaced in the fault log"
    assert all(r.partition == 1 for r in flog), \
        f"fault leaked beyond partition 1: {flog}"
    assert all(r.site == "knn_score" and r.recovered for r in flog)
    s, p, o = np.asarray(s), np.asarray(p), np.asarray(o)
    ws, wp, wo = want
    # host fallback is exact-f64 while the reference rounds rows to
    # bf16: correctness-equal to bf16 row precision, not bitwise
    assert np.allclose(s, ws, rtol=5e-3, atol=5e-3)
    overlap = np.mean([
        len({(a, b) for a, b in zip(p[i], o[i])}
            & {(a, b) for a, b in zip(wp[i], wo[i])}) / K
        for i in range(len(qs))])
    assert overlap >= 0.95, f"top-{K} overlap {overlap:.3f} after fault"
    # untouched partitions still answered on device
    eng2 = KnnEngine(cols)
    _assert_identical(eng2.search_many(works, k=K)[0], want,
                      "engine after clean rebuild")


@pytest.mark.faults
def test_knn_scrub_bitflip_repair():
    """An injected hbm_region flip on the int8 shard pool is detected by
    the scrubber, repaired from the host mirror, and the repaired engine
    answers bit-identically."""
    cols = _cols([1800, 1300], seed=47)
    qs = _queries(10, seed=25)
    works = [[KnnWork(q) for q in qs]]
    want = _reference(cols, qs, K, "cosine")

    integrity.reset_scrub_for_tests()      # only the engine below scrubs
    eng = KnnEngine(cols)
    _assert_identical(eng.search_many(works, k=K)[0], want, "pre-flip")

    def cycle():
        return [integrity.scrub_once()
                for _ in range(integrity.scrub_registry_size())]

    cycle()                                # baseline pass: all clean
    m0 = integrity.integrity_stats()["scrub_mismatches"]
    with faults.inject("hbm_region#knn_shards:raise@1x1"):
        results = cycle()
    hit = [r for r in results if r and r["result"] == "mismatch"]
    assert len(hit) == 1 and hit[0]["region"].endswith(".knn_shards")
    st = integrity.integrity_stats()
    assert st["scrub_mismatches"] == m0 + 1
    assert st["scrub_repairs"] >= 1
    _assert_identical(eng.search_many(works, k=K)[0], want,
                      "repaired engine vs reference")
    cycle()                                # repair re-baselined the region
    assert integrity.integrity_stats()["scrub_mismatches"] == m0 + 1


def test_ledger_matches_engine_bytes():
    cols = _cols([2000, 1500], seed=53)
    eng = KnnEngine(cols, mesh=make_mesh(2, dp=1))
    eng.search_many([[KnnWork(q) for q in _queries(4)]], k=K)
    assert eng._hbm.total_bytes() == eng.hbm_bytes()
    st = eng.stats()
    assert st["hbm_bytes"] == eng.hbm_bytes()
    assert st["partitions"] == 2 and st["fused"]
    node = knn_mod.knn_node_stats()
    assert node["engines"] >= 1
    assert node["hbm_bytes"] >= eng.hbm_bytes()


# --------------------------------------------------------------------------
# the dense route's mask stays on the device (PR 46)
# --------------------------------------------------------------------------

def _parent_dense(cols, lives, i, chunk, QC, k):
    """The dense route as the parent built it: `knn_scores` + a HOST-built
    [QC, n] mask (exists & live & the query's filter; pad rows all False)
    + `top_k`, invalid slots zeroed. From the columns, not the engine's
    device state. Returns the chunk's rows."""
    import jax
    import jax.numpy as jnp

    from elasticsearch_tpu.ops.knn import knn_scores

    col = cols[i]
    n = len(col.vectors)
    if n == 0:
        return (np.zeros((len(chunk), k), np.float32),
                np.zeros((len(chunk), k), np.int32))
    v = col.vectors.astype(np.float32)
    if col.similarity == "cosine":
        v = v / np.maximum(col.norms, 1e-20)[:, None]
    ok = np.asarray(col.exists, bool)
    if lives is not None and lives[i] is not None:
        ok = ok & lives[i]
    qf = np.zeros((QC, v.shape[1]), np.float32)
    qmask = np.zeros((QC, n), bool)
    for j, wk in enumerate(chunk):
        qf[j] = wk.vector
        m = ok
        if wk.filters is not None and wk.filters[i] is not None:
            m = m & np.asarray(wk.filters[i], bool)
        qmask[j] = m
    sc = knn_scores(jnp.asarray(qf), jnp.asarray(v).astype(jnp.bfloat16),
                    jnp.asarray(col.norms), jnp.asarray(col.exists),
                    similarity=col.similarity)
    sc = jnp.where(jnp.asarray(qmask), sc, -jnp.inf)
    ts, to = jax.lax.top_k(sc, k)
    valid = np.asarray(ts) > -np.inf
    return (np.where(valid, np.asarray(ts), 0.0)[:len(chunk)],
            np.where(valid, np.asarray(to), 0)[:len(chunk)])


def _never_certified(monkeypatch):
    """Every (query, partition) pair of the int8 pass reads uncertified,
    so the dense re-run decides every score `search_many` returns."""
    real = knn_mod._rescore_program

    def program(*a, **kw):
        ts, to, cert = real(*a, **kw)
        return ts, to, np.zeros(np.asarray(cert).shape, bool)

    monkeypatch.setattr(knn_mod, "_rescore_program", program)


# case -> (partition sizes, queries, which queries filter, deletes)
_DENSE_CASES = {
    "unfiltered": ([640, 520], 8, "none", None),
    "all_filtered": ([640, 520], 8, "all", None),
    "mixed_filters": ([640, 520], 8, "odd", None),
    "short_chunk_pad_rows": ([640, 520], 3, "odd", None),
    "empty_partition": ([0, 600], 8, "odd", None),
    "n_not_multiple_of_8": ([613, 1027], 8, "odd", None),
    "set_live_mirror_first": ([640, 520], 8, "odd", "after_mirror"),
    "set_live_before_mirror": ([640, 520], 8, "none", "before_mirror"),
}


@pytest.mark.parametrize("similarity", ["cosine", "dot_product", "l2_norm"])
@pytest.mark.parametrize("case", sorted(_DENSE_CASES))
def test_dense_route_matches_host_mask_construction(case, similarity,
                                                    monkeypatch):
    """`_dense_chunk`, the uncertified re-run through `search_many` and
    the ES_TPU_KNN_INT8=0 A/B route answer bit for bit what the parent's
    construction answers: the resident `ok` row and the bit-packed filters
    are the same mask as the host-built [QC, n] bool array."""
    import jax.numpy as jnp

    sizes, nq, who, deletes = _DENSE_CASES[case]
    unit = similarity == "dot_product"
    cols = _cols(sizes, similarity=similarity, seed=71, unit=unit)
    qs = _queries(nq, seed=31, unit=unit)
    rng = np.random.default_rng(83)
    works = []
    for j, q in enumerate(qs):
        filtered = who == "all" or (who == "odd" and j % 2 == 1)
        # a query may filter one partition and leave the other alone
        flt = [rng.random(n) > 0.5 if (j + i) % 3 else None
               for i, n in enumerate(sizes)] if filtered else None
        works.append(KnnWork(q, filters=flt))
    eng = KnnEngine(cols, qc_sizes=(8,))
    QC = 8
    qfd = np.zeros((QC, DIMS), np.float32)
    qfd[:nq] = qs
    qfd = jnp.asarray(qfd)
    lives = None
    if deletes:
        if deletes == "after_mirror":
            for i in range(len(sizes)):
                eng._ensure_dense(i)
        # delete what the live corpus ranks first, so the answer must move
        lives = []
        for i, n in enumerate(sizes):
            _, top = _parent_dense(cols, None, i, works, QC, K)
            live = np.ones(n, bool)
            live[np.unique(top[:, :3])] = False
            lives.append(live)
            eng.set_live(i, live)
    want = [_parent_dense(cols, lives, i, works, QC, K)
            for i in range(len(sizes))]
    for i in range(len(sizes)):
        gs, go = eng._dense_chunk(i, qfd, works, K)
        assert np.array_equal(gs, want[i][0]), f"{case}: scores, part {i}"
        assert np.array_equal(go, want[i][1]), f"{case}: ords, part {i}"
    if deletes:
        assert all((np.take_along_axis(~lives[i][None, :], want[i][1], 1)
                    & (want[i][0] > 0)).sum() == 0 for i in range(len(sizes)))
    merged = eng._merge(np.stack([w[0] for w in want]),
                        np.stack([w[1] for w in want]), K)
    with monkeypatch.context() as mp:
        _never_certified(mp)
        knn_mod.reset_for_tests()
        got = eng.search_many([works], k=K)[0]
        live_parts = sum(1 for n in sizes if n)
        assert knn_mod.knn_node_stats()["knn_uncertified"] == nq * live_parts
    for g, w in zip(got, merged):
        assert np.array_equal(np.asarray(g), np.asarray(w)), \
            f"{case}: re-run through search_many"
    monkeypatch.setenv("ES_TPU_KNN_INT8", "0")
    got = eng.search_many([works], k=K)[0]
    for g, w in zip(got, merged):
        assert np.array_equal(np.asarray(g), np.asarray(w)), \
            f"{case}: ES_TPU_KNN_INT8=0 route"


def test_dense_route_uploads_no_mask_or_bits_only(monkeypatch):
    """The mechanism: an unfiltered re-run hands the device nothing of
    size QC x n (no mask operand at all); a filtered one hands it the
    filters as bits, QC x `_filter_bits_width(n)` bytes whatever the mix.
    The resident `ok` row is in the HBM ledger's count."""
    n, QC = 1500, 8
    cols = _cols([n], seed=89)
    eng = KnnEngine(cols, qc_sizes=(QC,))
    seen = []
    real = knn_mod._dense_topk

    def spy(*a, **kw):
        seen.append([(getattr(x, "shape", None), getattr(x, "dtype", None))
                     for x in a])
        return real(*a, **kw)

    monkeypatch.setattr(knn_mod, "_dense_topk", spy)
    _never_certified(monkeypatch)
    qs = _queries(5, seed=37)

    knn_mod.reset_for_tests()
    eng.search_many([[KnnWork(q) for q in qs]], k=K)
    st = knn_mod.knn_node_stats()
    assert st["knn_dense_mask_free"] == 1 and st["knn_dense_masked"] == 0
    assert st["knn_dense_mask_bytes"] == 0
    assert len(seen) == 1 and seen[0][4][0] is None, "a mask operand went up"
    assert all(shape != (QC, n) for shape, _ in seen[0])
    assert seen[0][3] == ((n,), np.dtype(bool)), "ok row not the resident one"

    seen.clear()
    flt = np.random.default_rng(97).random(n) > 0.5
    works = [KnnWork(q, filters=[flt] if j == 2 else None)
             for j, q in enumerate(qs)]
    eng.search_many([works], k=K)
    st = knn_mod.knn_node_stats()
    nb = knn_mod._filter_bits_width(n)
    assert nb == 256 and nb * 8 >= n
    assert st["knn_dense_mask_free"] == 1 and st["knn_dense_masked"] == 1
    assert st["knn_dense_mask_bytes"] == QC * nb
    assert seen[0][4] == ((QC, nb), np.dtype(np.uint8))
    assert all(shape != (QC, n) for shape, _ in seen[0])
    assert eng._hbm.total_bytes() == eng.hbm_bytes()
    assert eng._mirror_bytes() == n * (DIMS * 2 + 4 + 1)


class TestServingFastPath:
    """REST-level knn bodies through IndexService: the quantized fast
    path (forced eligible via ES_TPU_FORCE_KNN) must match _search_dense
    — ids exactly, scores to f32 tolerance."""

    @pytest.fixture()
    def svc(self, monkeypatch):
        monkeypatch.setenv("ES_TPU_FORCE_KNN", "1")
        from elasticsearch_tpu.cluster.state import IndexMetadata
        from elasticsearch_tpu.common.settings import Settings
        from elasticsearch_tpu.index.index_service import IndexService

        meta = IndexMetadata(
            index="t", uuid="u1", settings=Settings({}),
            mappings={"properties": {
                "body": {"type": "text"},
                "tag": {"type": "keyword"},
                "vec": {"type": "dense_vector", "dims": 8},
            }})
        svc = IndexService(meta)
        rng = np.random.default_rng(59)
        words = ["alpha", "beta", "gamma", "delta"]
        for i in range(220):
            svc.index_doc(str(i), {
                "body": " ".join(rng.choice(words, size=4)),
                "tag": str(rng.choice(["red", "green"])),
                "vec": [float(x) for x in rng.standard_normal(8)],
            })
        for i in range(0, 40, 9):
            svc.delete_doc(str(i))
        svc.refresh()
        yield svc
        svc.close()

    def _check(self, svc, body):
        fast = svc.serving.try_search(body, "query_then_fetch")
        assert fast is not None, f"knn fast path did not engage: {body}"
        dense = svc._search_dense(body)
        fh, dh = fast["hits"]["hits"], dense["hits"]["hits"]
        assert [h["_id"] for h in fh] == [h["_id"] for h in dh], body
        for a, b in zip(fh, dh):
            assert abs(a["_score"] - b["_score"]) <= \
                2e-4 * abs(b["_score"]) + 2e-4, body

    def test_knn_bodies_match_dense(self, svc):
        qv = [float(x) for x in np.random.default_rng(61).standard_normal(8)]
        for body in [
            {"knn": {"field": "vec", "query_vector": qv, "k": 7}},
            {"knn": {"field": "vec", "query_vector": qv, "k": 12,
                     "filter": {"term": {"tag": "red"}}}, "size": 12},
            {"knn": {"field": "vec", "query_vector": qv, "k": 9,
                     "filter": {"bool": {
                         "must": [{"term": {"tag": "green"}}],
                         "must_not": [{"term": {"body": "alpha"}}]}}}},
        ]:
            self._check(svc, body)

    def test_hybrid_query_plus_knn_stays_dense(self, svc):
        qv = [0.5] * 8
        body = {"query": {"match": {"body": "alpha"}},
                "knn": {"field": "vec", "query_vector": qv, "k": 5}}
        assert svc.serving.try_search(body, "query_then_fetch") is None
        assert svc._search_dense(body) is not None
