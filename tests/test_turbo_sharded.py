"""ICI-sharded TurboBM25 differential suite (PR 4).

With S > 1 partitions on a multi-device mesh, TurboEngine serves every
partition's sweep as ONE fused shard_map dispatch and merges the
per-partition top-ks ON DEVICE (parallel.spmd.merge_partition_topk).
The host route — solo per-partition search_many + TurboEngine._merge3 —
is the reference, and the contract is BIT-identity: merging permutes
the exact per-partition f32 scores, it never recomputes them, so the
two routes must agree to the last bit including the (score desc,
partition asc, ord asc) tie-break.

Runs on the host-simulated 8-device CPU mesh from tests/conftest.py
(Pallas kernels interpret on CPU); the multidevice marker documents the
lane — these tests ARE tier-1.
"""

import threading

import numpy as np
import pytest

from elasticsearch_tpu.index.segment import build_field_postings
from elasticsearch_tpu.parallel.spmd import build_stacked_bm25
from elasticsearch_tpu.parallel.turbo import TurboBM25

pytestmark = pytest.mark.multidevice


class _Seg:
    def __init__(self, n_docs, fp):
        self.n_docs = n_docs
        self.postings = {"body": fp}
        self.vectors = {}


def _pcorpus(n_docs, vocab, seed):
    """Positional Zipf corpus (token_pos = in-doc offset, so adjacent
    pairs are real slop-0 phrase hits)."""
    rng = np.random.default_rng(seed)
    probs = 1.0 / np.arange(1, vocab + 1) ** 1.1
    probs /= probs.sum()
    lens = rng.integers(4, 24, size=n_docs).astype(np.int64)
    tokens = rng.choice(vocab, size=int(lens.sum()), p=probs).astype(np.int64)
    return _corpus_fp(lens, tokens, vocab)


def _corpus_fp(lens, tokens, vocab):
    n_docs = len(lens)
    tok_docs = np.repeat(np.arange(n_docs, dtype=np.int64), lens)
    bounds = np.concatenate([[0], np.cumsum(lens)])
    tok_pos = (np.arange(len(tokens), dtype=np.int64)
               - np.repeat(bounds[:-1], lens))
    names = [f"t{i}" for i in range(vocab)]
    return build_field_postings("body", lens, tok_docs, tokens, names,
                                token_pos=tok_pos)


def _turbo(fp, n_docs, cold_df=5, hbm=64 << 20, **kw):
    stacked = build_stacked_bm25([_Seg(n_docs, fp)], "body", serve_only=True)
    return TurboBM25(stacked, hbm_budget_bytes=hbm, cold_df=cold_df, **kw)


def _fused_engine(parts, cold_df=5, **kw):
    """TurboEngine over S partitions WITH the fused mesh, as
    select_bm25_engine builds it for S > 1."""
    from elasticsearch_tpu.search.serving import TurboEngine, _turbo_mesh

    turbos = [_turbo(fp, n, cold_df=cold_df, **kw) for n, fp in parts]
    return TurboEngine(turbos, mesh=_turbo_mesh(len(turbos)))


@pytest.fixture(scope="module")
def eng3():
    """Three partitions of different sizes AND vocabularies — different
    slot counts (Hp) per partition exercise the weight-axis padding in
    the fused dispatch, and terms absent from the small-vocab partition
    exercise partial term presence."""
    return _fused_engine([(1500, _pcorpus(1500, 40, 1)),
                          (900, _pcorpus(900, 56, 2)),
                          (2100, _pcorpus(2100, 32, 3))])


def _assert_rows_equal(got, want, ctx):
    for g, w, name in zip(got, want, ("scores", "parts", "ords")):
        assert np.array_equal(np.asarray(g), np.asarray(w)), (ctx, name)


def _host_route_many(eng, batch, k):
    per = [t.search_many([batch], k=k)[0] for t in eng.turbos]
    return eng._merge3(per, len(batch), k)


def _host_route_bool(eng, specs, k):
    per = [t.search_bool(specs, k=k) for t in eng.turbos]
    return eng._merge3(per, len(specs), k)


# ---------------------------------------------------------------------------
# the merge kernel against an independent lexicographic reference
# ---------------------------------------------------------------------------


def _ref_merge(scores, ords, k):
    Q, L = scores.shape
    out = (np.zeros((Q, k), np.float32), np.zeros((Q, k), np.int32),
           np.zeros((Q, k), np.int32))
    for qi in range(Q):
        cand = [(float(s), lane // k, int(o))
                for lane, (s, o) in enumerate(zip(scores[qi], ords[qi]))
                if s > 0]
        cand.sort(key=lambda x: (-x[0], x[1], x[2]))
        for j, (s, p, o) in enumerate(cand[:k]):
            out[0][qi, j], out[1][qi, j], out[2][qi, j] = s, p, o
    return out


def test_merge_topk_matches_lexicographic_reference():
    from elasticsearch_tpu.parallel.kernels import merge_topk

    rng = np.random.default_rng(5)
    Q, S, k = 6, 4, 10
    # few distinct score values force heavy cross-partition ties; ords
    # unique per partition lane block (real partitions emit distinct docs)
    scores = rng.choice(np.asarray([0.0, 0.0, 1.5, 2.25, 3.5], np.float32),
                        size=(Q, S * k))
    ords = np.stack([rng.permutation(1000)[:S * k] for _ in range(Q)])
    ords = ords.astype(np.int32)
    got = merge_topk(scores, ords, k=k)
    _assert_rows_equal(got, _ref_merge(scores, ords, k), "merge_topk")


# ---------------------------------------------------------------------------
# fused dispatch + device merge vs solo + host _merge3
# ---------------------------------------------------------------------------


def test_fused_disjunctive_bit_identical_one_dispatch(eng3):
    batch = [["t0", "t1"], ["t3"], [("t2", 2.0), "t5"], ["t7", "t0", "t9"],
             ["t33", "t1"],        # t33 absent from the vocab-32 partition
             ["t90"]]              # absent from EVERY partition
    d0 = {id(t): t.stats["dispatches"] for t in eng3.turbos}
    f0 = eng3.merge_stats["fused_dispatches"]
    m0 = eng3.merge_stats["merge_device"]
    got = eng3.search_many([batch], k=10)[0]
    # one ≤8-query batch -> exactly ONE fused dispatch for all S
    # partitions, merged on device; no per-partition solo dispatches
    assert eng3.merge_stats["fused_dispatches"] - f0 == 1
    assert eng3.merge_stats["merge_device"] - m0 == 1
    assert all(t.stats["dispatches"] == d0[id(t)] for t in eng3.turbos)
    _assert_rows_equal(got, _host_route_many(eng3, batch, 10), "disj")


def test_fused_multi_batch_and_chunking():
    # a single compiled width of 8: the 9-query flat batch (both caller
    # batches aggregate into one flat dispatch stream) splits into two
    # 8-wide chunks -> two fused dispatches, each covering ALL
    # partitions, and still one device merge per caller batch
    eng = _fused_engine([(500, _pcorpus(500, 30, 61)),
                         (400, _pcorpus(400, 30, 67))], qc_sizes=(8,))
    b1 = [[f"t{i}", f"t{(i * 3 + 1) % 20}"] for i in range(7)]
    b2 = [["t2"], ["t4", "t6"]]
    f0 = eng.merge_stats["fused_dispatches"]
    m0 = eng.merge_stats["merge_device"]
    got = eng.search_many([b1, b2], k=7)
    assert eng.merge_stats["fused_dispatches"] - f0 == 2
    assert eng.merge_stats["merge_device"] - m0 == 2
    _assert_rows_equal(got[0], _host_route_many(eng, b1, 7), "b1")
    _assert_rows_equal(got[1], _host_route_many(eng, b2, 7), "b2")


def test_fused_bool_and_phrase_bit_identical(eng3):
    specs = [
        {"must": [("t0", 1.0), ("t1", 1.0)]},
        {"must": [("t2", 1.0)], "must_not": ["t1"]},
        {"should": [("t3", 1.0), ("t4", 2.0)]},
        {"must": [("t0", 1.0)], "filter": ["t5"]},
        {"must": [("t0", 1.0)], "phrases": [(("t0", "t1"), 0, 1.0)]},
        {"phrases": [(["t1", "t0"], 0, 1.0)]},
    ]
    got = eng3.search_bool(specs, k=10)
    _assert_rows_equal(got, _host_route_bool(eng3, specs, 10), "bool")

    phrases = [["t0", "t1"], ["t2", "t0"], ["t1", "t3"]]
    got_p = eng3.search_phrase(phrases, k=5, slop=0)
    per = [t.search_phrase(phrases, k=5, slop=0) for t in eng3.turbos]
    _assert_rows_equal(got_p, eng3._merge3(per, len(phrases), 5), "phrase")


def test_fused_refresh_picks_up_new_columns(eng3):
    """Columns built AFTER the ShardedTurbo uploaded (cols_epoch bump)
    must be re-uploaded before the next fused dispatch."""
    epochs0 = [t.cols_epoch for t in eng3.turbos]
    batch = [["t11", "t13"], ["t12", "t14", "t15"]]
    got = eng3.search_many([batch], k=10)[0]
    _assert_rows_equal(got, _host_route_many(eng3, batch, 10), "refresh")
    # the differential itself is the real check; the epochs moving shows
    # this test actually exercised the refresh path at least once overall
    assert all(t.cols_epoch >= e for t, e in zip(eng3.turbos, epochs0))


def test_fused_certificate_fallback_bit_identical(eng3):
    """force_cert_fail (the bool-path certificate test hook) discards
    the device collection inside the fused path too — the per-partition
    exact host fallback runs and the merge still agrees with the solo
    route (both exact)."""
    specs = [{"must": [("t0", 1.0), ("t6", 1.0)]},
             {"must": [("t1", 1.0)], "should": [("t2", 1.0)]}]
    fb0 = eng3.stats["fallbacks"]
    try:
        for t in eng3.turbos:
            t.force_cert_fail = True
        got = eng3.search_bool(specs, k=10)
        want = _host_route_bool(eng3, specs, 10)
    finally:
        for t in eng3.turbos:
            t.force_cert_fail = False
    _assert_rows_equal(got, want, "cert-fail")
    assert eng3.stats["fallbacks"] > fb0


# ---------------------------------------------------------------------------
# tie-break: equal scores across and within partitions, short partitions
# ---------------------------------------------------------------------------


def test_fused_ties_across_partitions():
    """Two partitions with IDENTICAL corpora: every hit is an exact
    cross-partition score tie; order must be partition asc at equal
    (score, ord) and stay bit-identical to _merge3."""
    fp = _pcorpus(700, 30, 7)
    eng = _fused_engine([(700, fp), (700, fp)])
    batch = [["t0", "t2"], ["t1"], ["t4", "t5"]]
    got = eng.search_many([batch], k=10)[0]
    _assert_rows_equal(got, _host_route_many(eng, batch, 10), "xpart ties")
    s, p, o = got
    for qi in range(len(batch)):
        for j in range(9):
            if s[qi, j] > 0 and s[qi, j] == s[qi, j + 1]:
                assert (p[qi, j], o[qi, j]) < (p[qi, j + 1], o[qi, j + 1])


def test_fused_ties_within_partition():
    """A partition whose second half duplicates its first half: equal
    (score, partition) pairs must order by ord asc."""
    rng = np.random.default_rng(17)
    lens = rng.integers(4, 20, size=400).astype(np.int64)
    toks = rng.choice(25, size=int(lens.sum()),
                      p=(lambda w: w / w.sum())(
                          1.0 / np.arange(1, 26) ** 1.1)).astype(np.int64)
    fp_dup = _corpus_fp(np.concatenate([lens, lens]),
                        np.concatenate([toks, toks]), 25)
    eng = _fused_engine([(800, fp_dup), (600, _pcorpus(600, 25, 19))])
    batch = [["t0", "t1"], ["t3", "t2"]]
    got = eng.search_many([batch], k=10)[0]
    _assert_rows_equal(got, _host_route_many(eng, batch, 10), "inpart ties")


def test_fused_k_exceeds_partition_candidates():
    """A tail term matching only a handful of docs per partition: some
    partitions contribute fewer than k candidates, the merged tail pads
    with (0, 0, 0) exactly as _merge3 does."""
    eng = _fused_engine([(60, _pcorpus(60, 40, 23)),
                         (40, _pcorpus(40, 40, 29)),
                         (50, _pcorpus(50, 40, 31))], cold_df=2)
    batch = [["t38"], ["t39", "t37"], ["t36"]]
    got = eng.search_many([batch], k=10)[0]
    want = _host_route_many(eng, batch, 10)
    _assert_rows_equal(got, want, "short partitions")
    assert np.any(got[0] == 0), "expected padded tail slots"


# ---------------------------------------------------------------------------
# serving selection + lane-key stability for the sharded engine
# ---------------------------------------------------------------------------


def test_select_engine_routes_multi_partition_to_fused_turbo(monkeypatch):
    from elasticsearch_tpu.search.serving import (select_bm25_engine,
                                                  turbo_eligible)

    monkeypatch.setenv("ES_TPU_FORCE_TURBO", "1")
    segs = [_Seg(600, _pcorpus(600, 30, 41)), _Seg(450, _pcorpus(450, 30, 43))]
    from elasticsearch_tpu.parallel import make_mesh

    mesh = make_mesh(2, dp=1)
    assert turbo_eligible(segs, "body", mesh, cold_df=5)
    eng = select_bm25_engine(segs, "body", None, mesh, cold_df=5)
    assert eng.kind == "turbo"
    assert eng.mesh is not None, "S > 1 must get the fused turbo mesh"
    batch = [["t0", "t1"], ["t2"]]
    got = eng.search_many([batch], k=10)[0]
    _assert_rows_equal(got, _host_route_many(eng, batch, 10), "selected")
    assert eng.merge_stats["merge_device"] >= 1


def test_turbo_mesh_env_disable(monkeypatch):
    from elasticsearch_tpu.search.serving import _turbo_mesh

    assert _turbo_mesh(1) is None          # S == 1 never fuses
    assert _turbo_mesh(3) is not None
    monkeypatch.setenv("ES_TPU_TURBO_MESH", "0")
    assert _turbo_mesh(3) is None          # explicit opt-out
    monkeypatch.setenv("ES_TPU_TURBO_MESH", "2")
    m = _turbo_mesh(5)
    assert m is not None and m.devices.size == 2


def test_sharded_engine_scheduler_rows_and_keys(monkeypatch):
    """The scheduler serves the SHARDED TurboEngine with rows
    bit-identical to solo dispatch, and its lane keying stays stable —
    one serial per engine object, distinct across the engine swap a
    snapshot refresh performs."""
    from elasticsearch_tpu.threadpool.scheduler import (
        AdaptiveDispatchScheduler, _engine_key,
    )

    monkeypatch.setenv("ES_TPU_COALESCE_US", "400000")

    eng = _fused_engine([(600, _pcorpus(600, 30, 47)),
                         (500, _pcorpus(500, 30, 53))])
    queries = [["t0", "t1"], ["t2"], ["t1", "t3"], ["t4"]]
    solo = [eng.search_many([[q]], k=10)[0] for q in queries]

    sched = AdaptiveDispatchScheduler(buckets=(len(queries),),
                                      interactive_us=400_000.0)
    results = [None] * len(queries)
    errors = []
    barrier = threading.Barrier(len(queries))

    def worker(i, q):
        try:
            barrier.wait(timeout=10)
            results[i] = sched.dispatch(eng, [q], 10)
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i, q))
               for i, q in enumerate(queries)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors
    for q, got, want in zip(queries, results, solo):
        _assert_rows_equal((got[0][0], got[1][0], got[2][0]),
                           (want[0][0], want[1][0], want[2][0]), q)
    assert sched.stats()["largest_batch"] > 1     # merging happened

    # keying: stable per object, distinct across objects — a refreshed
    # snapshot's NEW engine (even one landing at the same id() after the
    # old is collected) can never join the old engine's lane
    k1, k1b = _engine_key(eng), _engine_key(eng)
    assert k1 == k1b
    eng2 = type(eng)(eng.turbos, mesh=eng.mesh)   # refreshed wrapper
    assert _engine_key(eng2) != k1
    assert _engine_key(eng2) == _engine_key(eng2)


# ---------------------------------------------------------------------------
# PR 31: one finish a (partition, chunk) on the fused path
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def eng_chunk():
    """Partitions 0 and 1 hold the SAME corpus (every hit ties across
    them), partition 2 repeats its first half in its second (ties within),
    lacks the tail of the vocabulary and has every fifth doc deleted."""
    from elasticsearch_tpu.search.serving import TurboEngine, _turbo_mesh

    twin = _pcorpus(1400, 48, 41)
    rng = np.random.default_rng(43)
    lens = rng.integers(4, 20, size=900).astype(np.int64)
    toks = rng.choice(30, size=int(lens.sum()),
                      p=(lambda w: w / w.sum())(
                          1.0 / np.arange(1, 31) ** 1.1)).astype(np.int64)
    dup = _corpus_fp(np.concatenate([lens, lens]),
                     np.concatenate([toks, toks]), 30)
    live = np.ones(1800, bool)
    live[::5] = False
    turbos = [_turbo(twin, 1400, cold_df=120), _turbo(twin, 1400, cold_df=120)]
    stacked = build_stacked_bm25([_Seg(1800, dup)], "body",
                                 live_masks=[live], serve_only=True)
    turbos.append(TurboBM25(stacked, hbm_budget_bytes=64 << 20, cold_df=120))
    return TurboEngine(turbos, mesh=_turbo_mesh(3))


# hot-only, cold-only, mixed, terms partition 2 lacks (t30 and up) or
# nobody has, a repeated term, boosts, a rare term (fewer hits than k)
FUSED_MIX = [
    ["t0", "t1"], ["t40", "t45"], ["t0", "t25", "t46"], ["t1", "zzz"],
    ["zzz"], ["t2", "t2", "t38"], [("t0", 2.0), ("t44", 0.5)],
    [("t3", 0.25), ("t4", 3.0), ("t33", 1.0), ("t20", 1.0)], ["t47"],
    ["t5", "t6", "t7", "t8", "t9", "t27", "t28", "t43"],
]


@pytest.mark.parametrize("k", [10, 60])
@pytest.mark.parametrize("width", [1, 3, 16, 40])
def test_fused_chunk_finish_bit_identical_to_host(eng_chunk, width, k):
    """Every partition of a fused dispatch answers `search_many_host`'s
    bits at widths 1 .. 40, the merged answer is the host route's, and
    partitions x queries pairs are counted between the chunk-wide finish
    and the ways out of it."""
    st = eng_chunk._fused()
    qs = [FUSED_MIX[(i + width) % len(FUSED_MIX)] for i in range(width)]
    keys = ("finish_bulk_pairs", "finish_pair_fallbacks", "fallbacks")
    before = [[t.stats[key] for key in keys] for t in st.turbos]
    per = st.search_many([qs], k=k)
    for si, t in enumerate(st.turbos):
        want_s, want_d = t.search_many_host([qs], k=k)[0]
        got_s, got_d = per[si][0]
        assert np.array_equal(got_d, want_d), (si, width)
        assert np.array_equal(got_s, want_s), (si, width)
        bulk, left, fb = (t.stats[key] - b
                          for key, b in zip(keys, before[si]))
        assert bulk + left == width and left == fb
    _assert_rows_equal(eng_chunk.search_many([qs], k=k)[0],
                       _host_route_many(eng_chunk, qs, k),
                       f"merged, width {width}")


def test_fused_chunk_finish_ties_order_by_partition_then_ord(eng_chunk):
    s, p, o = eng_chunk.search_many([[["t0", "t1"], ["t40", "t45"]]],
                                    k=10)[0]
    ties = 0
    for qi in range(2):
        for j in range(9):
            if s[qi, j] > 0 and s[qi, j] == s[qi, j + 1]:
                ties += 1
                assert (p[qi, j], o[qi, j]) < (p[qi, j + 1], o[qi, j + 1])
    assert ties
