"""The hybrid cell's comparison (request kind `hybrid`, PR 45): what it
has to call wrong, and the one thing it forgives.

At tiny size on the CPU, the program on one side and the plain reference
on the other: both controls (the BM25 side one precision step down, the
vector side one step down) read NOT `correct`; a served list with one
nearest document's addend dropped reads NOT `correct`; a response whose
total forgets the documents only the `knn` section matches reads
`hits_wrong`. And on hand-made answers: two nearest documents tied inside
the band pass whichever of them the served list counts among the `k`
nearest, and the same swap outside the band does not.
"""

import copy
import time

import numpy as np
import pytest

from benchmark import compare, run
from benchmark.manifest import ROOT, load_kind
from benchmark.reference import top_hits

import bench_tiny

SEED = 1556403449
CHIP = {"platform": "tpu", "kind": "TPU v5 lite"}
HYBRID = bench_tiny.cells_where(
    lambda c: c.traffic["request"]["kind"] == "hybrid")
kind = load_kind(bench_tiny.REAL.dir, "request", "hybrid")


def settled(still_s: float = 2.5, limit_s: float = 20.0) -> None:
    """Wait until the serving counters of this PROCESS stand still.
    `host_tier_answers` is read from them, and they are the worker's: a
    search that an earlier file's test abandoned in a `hang` fault (the
    longest is 2 s, tests/test_disruption.py) wakes after its test has
    ended, finds its deadline passed and counts `fastpath_timed_out`,
    inside whichever window is open then."""
    from elasticsearch_tpu.search import serving

    end = time.monotonic() + limit_s
    seen, since = serving.serving_fault_stats(), time.monotonic()
    while time.monotonic() < min(since + still_s, end):
        time.sleep(0.1)
        now = serving.serving_fault_stats()
        if now != seen:
            seen, since = now, time.monotonic()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One tiny node of the hybrid cell, one short window: the cell, the
    searches it answered, the corpus and the node's stats around it."""
    settled()
    mp = pytest.MonkeyPatch()
    bench_tiny.steer_engines(mp, str(tmp_path_factory.mktemp("jax_cache")))
    manifest = bench_tiny.tiny_manifest(str(tmp_path_factory.mktemp("tiny")))
    bench = run.Bench(manifest, HYBRID[0], require_chip=False,
                      out_dir=str(tmp_path_factory.mktemp("out")))
    bench.dev = dict(CHIP, count=1)
    try:
        bench.setup(SEED)
        w = bench.window(SEED, 1.0, 0)
    finally:
        bench.close()
        mp.undo()
    return bench, w


def checked(served, precision=None, change=None) -> dict:
    bench, w = served
    pairs = run.sample_pairs(bench.cell, w.notes["answered"], SEED,
                             bench.parts, precision)
    if change is not None:
        pairs = [(change(copy.deepcopy(resp), ref), ref)
                 for resp, ref in pairs]
    return run.verdict(bench.cell, pairs, bench.stats0, w.stats_before,
                       w.stats_after)


def moved(served) -> dict:
    """The counters behind `host_tier_answers` that rose, by name: what a
    failure of it has to say."""
    bench, w = served
    rose = {c: compare.dotted(w.stats_after, c)
            - compare.dotted(bench.stats0, c)
            for c in compare.ZERO_COUNTERS
            + tuple(bench.cell.config["must_stay"])}
    return {c: n for c, n in rose.items() if n}


def test_the_cell_is_in_the_manifest_and_sound_at_tiny_size(served):
    assert HYBRID == ("msmarco-hybrid.msearch-closed",)
    got = checked(served)
    assert compare.is_correct(got), (moved(served), got)
    assert got["compared"]["value"] >= 24
    assert got["device_dispatches"]["value"] >= 1
    assert got["knn_part_err"]["value"] < 2e-5
    # the sum is worked: nearest documents that also match are served
    bench, w = served
    cfg = bench.cell.config
    assert cfg["must_stay"] == ["tpu_hybrid.hybrid_host"]
    assert "tpu_hybrid.hybrid_device" in cfg["must_rise"]


@pytest.mark.parametrize("precision, by", [("bfloat16", "score_err"),
                                           ("int8", "knn_part_err")])
def test_a_control_one_precision_step_down_is_not_correct(served, precision,
                                                          by):
    """The reference in the program's place with ONE side a step below
    what the configuration states: the BM25 side in bfloat16, the vector
    side in int8. Each is called wrong, by the number that reads its
    side."""
    bench, _ = served
    assert precision in bench.cell.config["precision"]["controls"]
    got = checked(served, precision)
    assert not compare.is_correct(got)
    assert not got[by]["ok"], got
    assert got[by]["value"] >= 10 * got[by]["limit"]
    assert got["host_tier_answers"]["ok"], moved(served)
    assert got["compared"]["ok"]


def test_the_int8_control_would_pass_score_err_alone(served):
    """Why `knn_part_err` exists: the vector addend is a small part of a
    sum, and the sum's relative error hides a vector side in int8."""
    got = checked(served, "int8")
    assert got["score_err"]["value"] < got["knn_part_err"]["value"] / 2


def _drop_one_addend(resp, ref):
    nearest = set(ref["nn"].tolist())
    for h in resp["hits"]["hits"]:
        if int(h["_id"]) in nearest:
            h["_score"] -= float(ref["vec"][int(h["_id"])])
            break
    return resp


def test_a_dropped_addend_is_not_correct(served):
    got = checked(served, change=_drop_one_addend)
    assert not compare.is_correct(got)
    assert not got["score_err"]["ok"] and not got["knn_part_err"]["ok"]


def _forget_knn_only(resp, ref):
    bm25_alone = ref["scores"][ref["nn"]] - ref["vec"][ref["nn"]]
    resp["hits"]["total"]["value"] -= int(np.count_nonzero(bm25_alone <= 0))
    return resp


def test_a_total_without_the_knn_only_documents_is_hits_wrong(served):
    bench, w = served
    pairs = run.sample_pairs(bench.cell, w.notes["answered"], SEED,
                             bench.parts)
    only = sum(int(np.count_nonzero(
        ref["scores"][ref["nn"]] - ref["vec"][ref["nn"]] <= 0))
        for _, ref in pairs)
    assert only > 0, "the tiny corpus has nearest documents no term matches"
    got = checked(served, change=_forget_knn_only)
    assert got["hits_wrong"]["value"] >= 1 and not compare.is_correct(got)
    assert got["score_err"]["ok"] and got["knn_part_err"]["ok"]


# ---- the band, on hand-made answers -------------------------------------

LIMITS = {"score_err": 2e-5, "rank_gap": 2e-5, "order_err": 0.0,
          "knn_part_err": 2e-5}
K, NN_K = 5, 3


def _answer(bm25: np.ndarray, vec: np.ndarray, counted=None) -> dict:
    """The reference's answer over a hand-made corpus; `counted` = the
    rows that carry their addend (default: the NN_K nearest)."""
    nn, near = top_hits(vec, NN_K)
    counted = nn if counted is None else np.asarray(counted)
    s = bm25.astype(np.float64).copy()
    s[counted] += vec[counted].astype(np.float64)
    ords, top = top_hits(s, K)
    return {"scores": s, "ords": ords, "top": top,
            "total": int(np.count_nonzero(s > 0)), "vec": vec, "nn": nn,
            "kth": float(near[-1])}


def _response(ans: dict) -> dict:
    """What a program that computed `ans` exactly would serve."""
    return {"hits": {"total": {"value": ans["total"], "relation": "eq"},
                     "hits": [{"_id": str(int(d)), "_score": float(s)}
                              for d, s in zip(ans["ords"], ans["top"])]}}


def _corpus(tie: float):
    """Twelve rows; rows 4 and 9 are the third and fourth nearest, `tie`
    apart (relative); row 9 matches no term, row 4 does."""
    bm25 = np.asarray([3.0, 0.0, 2.5, 0.0, 1.0, 2.0, 0.0, 1.5, 0.0, 0.0,
                       0.5, 0.0])
    vec = np.full(12, 0.3, np.float32)
    vec[[2, 7]] = 0.95, 0.93
    vec[4] = 0.9
    vec[9] = np.float32(0.9 * (1.0 - tie))
    return bm25, vec


@pytest.mark.parametrize("third", [4, 9])
def test_two_nearest_tied_inside_the_band_pass_either_way(third):
    bm25, vec = _corpus(tie=3e-7)
    ref = _answer(bm25, vec)
    assert ref["nn"].tolist() == [2, 7, 4]
    program = _answer(bm25, vec, counted=[2, 7, third])
    got = kind.numbers([(_response(program), ref)], LIMITS, K)
    assert all(v["ok"] for v in got.values()), got
    assert got["hits_wrong"]["value"] == 0


def test_the_same_swap_outside_the_band_is_not_forgiven():
    bm25, vec = _corpus(tie=1e-3)
    ref = _answer(bm25, vec)
    sound = kind.numbers([(_response(ref), ref)], LIMITS, K)
    assert all(v["ok"] for v in sound.values()), sound
    program = _answer(bm25, vec, counted=[2, 7, 9])
    got = kind.numbers([(_response(program), ref)], LIMITS, K)
    assert not got["score_err"]["ok"] or not got["rank_gap"]["ok"]


def test_inside_the_band_a_wrong_addend_is_still_wrong():
    """The band forgives WHICH of the tied rows carries the addend, not
    its size."""
    bm25, vec = _corpus(tie=3e-7)
    ref = _answer(bm25, vec)
    resp = _response(ref)
    hit = next(h for h in resp["hits"]["hits"] if h["_id"] == "4")
    hit["_score"] -= 0.45           # half its addend
    got = kind.numbers([(resp, ref)], LIMITS, K)
    assert not got["score_err"]["ok"]


def test_the_reference_imports_nothing_of_the_program():
    import ast
    import os

    path = os.path.join(ROOT, "benchmark", "kinds", "request", "hybrid.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    mods = [n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
    mods += [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names]
    assert not [m for m in mods if m and m.startswith("elasticsearch_tpu")]
    with open(path) as f:
        text = f.read()
    # the two kinds are reused by name, not by copy
    assert 'load_kind(_BDIR, "request", "match")' in text
    assert 'load_kind(_BDIR, "request", "knn")' in text
    assert "class BM25Reference" not in text
    assert "class KnnReference" not in text
