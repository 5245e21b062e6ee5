"""The bool cell's comparison, at tiny size on the CPU, with no node: the
plain reference (benchmark/kinds/request/bool.py) answers the mix's own
requests; a served side built from the reference itself is `correct`, and
each of the kind's two controls has to come out not `correct`: the
reference one precision step below what the configuration states
("bfloat16"), and the reference of ANOTHER conjunction, the last required
clause left out ("drop_clause"). Then the configuration's own part of the
verdict: the device bool route and its cold lead must have answered
(`must_rise`), no host intersection may have (`must_stay`).

The request kind compares hit lists under its own `numbers`, so the
shared hit-list tests (test_bench_compare.py, which take every
configuration with a `must_stay` for the sharded one) leave it to these.
"""

import numpy as np
import pytest

from benchmark import compare, run
from benchmark.traffic import Mix

import bench_tiny
from test_bench_compare import _as_response, _stats, _verdict

BOOL = bench_tiny.cells_where(
    lambda c: c.traffic["request"]["kind"] == "bool")
SEED = 1556403449


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    return bench_tiny.tiny_manifest(str(tmp_path_factory.mktemp("tiny")))


@pytest.fixture(scope="module")
def sides(manifest):
    """cell -> (Cell, the mix's first 64 requests, k, their answers by
    precision), the corpus drawn once."""
    made = {}

    def get(cell_name):
        if cell_name not in made:
            cell = manifest.cell(cell_name)
            parts = cell.corpus_kind.make_parts(cell.config, SEED)
            mix = Mix(cell, SEED, parts)
            reqs = [mix.request(i) for i in range(64)]
            k = run.top_k(cell)
            made[cell_name] = (cell, reqs, k, {
                p: cell.request_kind.reference(cell.config, parts, p)
                .answers(reqs, k) for p in (None, "bfloat16",
                                            "drop_clause")})
        return made[cell_name]

    return get


def _pairs(answers, served):
    k = 10
    return [(_as_response(a, k), ref)
            for a, ref in zip(answers[served], answers[None])]


@pytest.mark.parametrize("cell", BOOL)
def test_the_mix_is_the_cycle_the_issue_wrote(sides, cell):
    c, reqs, _k, _ = sides(cell)
    req = c.traffic["request"]
    assert len(req["cycle"]) == 16
    shapes = [r.shape for r in reqs[:16]]
    assert shapes == req["cycle"]
    count = {s: shapes.count(s) for s in set(shapes)}
    assert count == {"AndHighHigh": 2, "AndHighMed": 3, "AndHighLow": 2,
                     "AndMedMed": 1, "And3": 1, "AndHighOrMedMed": 2,
                     "Filter": 1, "MustNot": 1, "Phrase2": 2, "Phrase3": 1}
    # nine of sixteen have a cold required clause (ISSUE 38 counted ten),
    # three need an adjacency column, two carry a cold SHOULD side
    cold = [s for s in shapes if any(
        b in ("Med", "Low") for g in ("must", "filter")
        for b in req["shapes"][s].get(g, ()))]
    assert len(cold) == 9
    assert sum("phrase" in req["shapes"][s] for s in shapes) == 3
    assert sum("should" in req["shapes"][s] for s in shapes) == 2
    vocab = c.config["corpus"]["vocab"]
    for r in reqs:
        flat = [x for g in r.ranks.values() for x in g]
        assert len(set(flat)) == len(flat) and max(flat) < vocab
        for g, bands in req["shapes"][r.shape].items():
            for rank, band in zip(r.ranks[g], bands):
                lo, hi = req["bands"][band]
                assert lo <= rank <= min(hi, vocab - 1)
        body = r.body["query"]
        if "phrase" in r.ranks:
            assert body["match_phrase"]["body"]["slop"] == 0
        else:
            assert set(body["bool"]) == set(r.ranks)


@pytest.mark.parametrize("cell", BOOL)
def test_the_reference_counts_and_scores_a_conjunction(sides, cell):
    """The reference against a second, slower reading of the same
    definition: a document at a time over the raw tokens."""
    c, reqs, k, answers = sides(cell)
    parts = c.corpus_kind.make_parts(c.config, SEED)
    seg = parts[0]
    docs = [set(seg.tokens[seg.bounds[i]: seg.bounds[i + 1]].tolist())
            for i in range(seg.n)]
    checked = 0
    for r, a in zip(reqs, answers[None]):
        if "phrase" in r.ranks:
            p = r.ranks["phrase"]
            n = 0
            for i in range(seg.n):
                t = seg.tokens[seg.bounds[i]: seg.bounds[i + 1]].tolist()
                n += any(t[j: j + len(p)] == p for j in range(len(t)))
        else:
            need = set(r.ranks.get("must", []) + r.ranks.get("filter", []))
            ban = set(r.ranks.get("must_not", []))
            n = sum(1 for d in docs if need <= d and not ban & d)
        in_seg = np.count_nonzero(a["scores"][: seg.n] > 0)
        assert in_seg == n, r.shape
        checked += n > 0
        assert a["total"] == np.count_nonzero(a["scores"] > 0)
    assert checked >= 16


@pytest.mark.parametrize("cell", BOOL)
def test_reference_against_itself_is_correct(sides, cell):
    c, _reqs, k, answers = sides(cell)
    cfg = c.config
    checked = _verdict(c, _pairs(answers, None), k, _stats(cfg),
                       _stats(cfg), _stats(cfg, moved=5))
    assert compare.is_correct(checked), compare.lines(checked)
    assert checked["score_err"]["value"] < 2e-7


@pytest.mark.parametrize("cell", BOOL)
def test_the_bfloat16_control_is_not_correct(sides, cell):
    c, _reqs, k, answers = sides(cell)
    cfg = c.config
    assert cfg["precision"]["control"] == "bfloat16"
    checked = _verdict(c, _pairs(answers, "bfloat16"), k, _stats(cfg),
                       _stats(cfg), _stats(cfg, moved=5))
    assert not compare.is_correct(checked)
    assert checked["score_err"]["value"] > 10 * checked["score_err"]["limit"]


@pytest.mark.parametrize("cell", BOOL)
def test_the_dropped_clause_control_is_not_correct(sides, cell):
    c, _reqs, k, answers = sides(cell)
    cfg = c.config
    assert "drop_clause" in cfg["precision"]["controls"]
    checked = _verdict(c, _pairs(answers, "drop_clause"), k, _stats(cfg),
                       _stats(cfg), _stats(cfg, moved=5))
    assert not compare.is_correct(checked)
    assert not checked["hits_wrong"]["ok"] or not checked["rank_gap"]["ok"]


@pytest.mark.parametrize("cell", BOOL)
def test_a_host_intersection_or_an_idle_bool_route_is_not_correct(sides,
                                                                 cell):
    c, _reqs, k, answers = sides(cell)
    cfg = c.config
    assert cfg["must_rise"] == ["tpu_turbo.bool_device",
                                "tpu_turbo.bool_cold_lead"]
    assert cfg["must_stay"] == ["tpu_turbo.bool_host",
                                "tpu_turbo.bitset_gallop"]
    pairs = _pairs(answers, None)
    for counter in cfg["must_stay"]:
        key = counter.replace(".", "__")
        on_host = _verdict(c, pairs, k, _stats(cfg), _stats(cfg),
                           _stats(cfg, moved=5, **{key: 1}))
        assert on_host["host_tier_answers"]["value"] == 1
        assert not compare.is_correct(on_host)
    for counter in cfg["must_rise"]:
        key = counter.replace(".", "__")
        idle = _verdict(c, pairs, k, _stats(cfg), _stats(cfg),
                        _stats(cfg, moved=5, **{key: 0}))
        assert idle["device_dispatches"]["value"] == 0
        assert not compare.is_correct(idle)
