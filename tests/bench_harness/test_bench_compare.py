"""The comparison that decides `correct`, at tiny size on the CPU, with no
node: the plain reference answers the cell's own requests; a served side
built from the reference itself passes, and one computed one precision
step below what the configuration states (the control) has to fail.

These are the tests of the HIT-LIST comparison: they run on every
configuration of the manifest whose cells' request kind compares hit
lists (`HIT_LIST`, read from the data). A request kind with another
response brings its own (tests/bench_harness/test_bench_toy.py shows
one)."""

import json

import numpy as np
import pytest

from benchmark import compare, run
from benchmark.reference import TOTAL_CAP, bf16_round, top_hits
from benchmark.traffic import Mix

import bench_tiny

# configuration -> the first cell of it whose responses are hit lists
HIT_LIST = {}
for _c in bench_tiny.cells_where(
        lambda c: c.request_kind.numbers is compare.hit_list_numbers):
    HIT_LIST.setdefault(bench_tiny.data_of(_c).config_name, _c)
ONE_CHIP = bench_tiny.data_of(bench_tiny.TWIN).config_name
# the configurations of more than one shard (a configuration may name
# what must and may not have moved for another reason than a merge)
SHARDED = sorted(c for c, cell in HIT_LIST.items()
                 if bench_tiny.data_of(cell).config["index"].get(
                     "shards", 1) > 1)


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    return bench_tiny.tiny_manifest(str(tmp_path_factory.mktemp("tiny")))


def _stats(cfg, moved=0, host=0, **named):
    """Node stats in which the configuration's `device_counter` and
    `must_rise` read `moved`, its `must_stay` 0 and one of the nine
    fallback counters `host`; `named` (dots as `__`) overrides."""
    s = {}

    def put(path, value):
        sec, key = path.split(".")
        s.setdefault(sec, {})[key] = value

    for path in compare.ZERO_COUNTERS + tuple(cfg.get("must_stay", ())):
        put(path, 0)
    put("tpu_health.fastpath_reject_error", host)
    for path in [cfg["device_counter"]] + list(cfg.get("must_rise", ())):
        put(path, moved)
    for name, value in named.items():
        put(name.replace("__", "."), value)
    return s


def _as_response(answer, k):
    """What a server that computed `answer` would send."""
    total = ({"value": TOTAL_CAP, "relation": "gte"}
             if answer["total"] > TOTAL_CAP
             else {"value": answer["total"], "relation": "eq"})
    return {"timed_out": False, "_shards": {"failed": 0},
            "hits": {"total": total, "hits": [
                {"_id": str(int(o)), "_score": float(np.float32(s))}
                for o, s in zip(answer["ords"][:k], answer["top"][:k])]}}


def _served_and_reference(manifest, config, served_precision, n=32):
    cell = manifest.cell(HIT_LIST[config])
    cfg = cell.config
    seed = 1556403449
    parts = cell.corpus_kind.make_parts(cfg, seed)
    mix = Mix(cell, seed, parts)
    reqs = [mix.request(i) for i in range(n)]
    k = run.top_k(cell)

    def answers(precision):
        return cell.request_kind.reference(cfg, parts, precision).answers(
            reqs, k)

    served = [_as_response(a, k) for a in answers(served_precision)]
    return cell, list(zip(served, answers(None))), k


def _verdict(cell, pairs, k, s0, s1, s2):
    return compare.verdict(pairs, cell.request_kind.numbers, cell.config,
                           s0, s1, s2, k)


@pytest.mark.parametrize("config", sorted(HIT_LIST))
def test_reference_against_itself_is_correct(manifest, config):
    cell, pairs, k = _served_and_reference(manifest, config, None)
    cfg = cell.config
    checked = _verdict(cell, pairs, k, _stats(cfg), _stats(cfg),
                       _stats(cfg, moved=5))
    assert compare.is_correct(checked), compare.lines(checked)
    # the float32 the wire carries is all that separates the two sides
    assert checked["score_err"]["value"] < 2e-7


@pytest.mark.parametrize("config", sorted(HIT_LIST))
def test_lower_precision_served_side_is_not_correct(manifest, config):
    cell, pairs, k = _served_and_reference(
        manifest, config, manifest.cell(HIT_LIST[config]).config[
            "precision"]["control"])
    cfg = cell.config
    checked = _verdict(cell, pairs, k, _stats(cfg), _stats(cfg),
                       _stats(cfg, moved=5))
    assert not compare.is_correct(checked)
    # by a wide margin, not by luck: the score gap alone is 10x its limit
    assert checked["score_err"]["value"] > 10 * checked["score_err"]["limit"]


def test_host_tier_answer_or_idle_device_is_not_correct(manifest):
    cell, pairs, k = _served_and_reference(manifest, ONE_CHIP, None)
    cfg = cell.config
    host = _verdict(cell, pairs, k, _stats(cfg), _stats(cfg),
                    _stats(cfg, moved=5, host=1))
    assert not host["host_tier_answers"]["ok"]
    idle = _verdict(cell, pairs, k, _stats(cfg), _stats(cfg, moved=5),
                    _stats(cfg, moved=5))
    assert not idle["device_dispatches"]["ok"]
    few = _verdict(cell, pairs[:3], k, _stats(cfg), _stats(cfg),
                   _stats(cfg, moved=5))
    assert not few["compared"]["ok"]
    assert not compare.is_correct(host) and not compare.is_correct(idle)


@pytest.mark.parametrize("config", SHARDED)
def test_a_host_merge_or_an_unmoved_device_merge_is_not_correct(manifest,
                                                                config):
    """The sharded deployment names more than one thing that must have
    run on the device (`must_rise`) and one that may not have run on the
    host (`must_stay`): the sweep without the device merge, or the host's
    merge in its place, is a host tier like any other."""
    cell, pairs, k = _served_and_reference(manifest, config, None)
    cfg = cell.config
    assert "tpu_turbo.merge_device" in cfg["must_rise"]
    assert cfg["must_stay"] == ["tpu_turbo.merge_host"]
    sound = _verdict(cell, pairs, k, _stats(cfg), _stats(cfg, moved=2),
                     _stats(cfg, moved=7))
    assert compare.is_correct(sound), compare.lines(sound)
    assert sound["device_dispatches"]["value"] == 5
    on_host = _verdict(cell, pairs, k, _stats(cfg), _stats(cfg),
                       _stats(cfg, moved=5, tpu_turbo__merge_host=5))
    assert on_host["host_tier_answers"]["value"] == 5
    assert not compare.is_correct(on_host)
    no_merge = _verdict(cell, pairs, k, _stats(cfg), _stats(cfg),
                        _stats(cfg, moved=5, tpu_turbo__merge_device=0))
    assert no_merge["device_dispatches"]["value"] == 0
    assert not compare.is_correct(no_merge)
    # the one-chip configuration names neither: its verdict is as before
    one, pairs1, _ = _served_and_reference(manifest, ONE_CHIP, None)
    assert "must_rise" not in one.config and "must_stay" not in one.config
    c1 = one.config
    merges = dict(tpu_turbo__merge_device=0, tpu_turbo__merge_host=0)
    same = _verdict(one, pairs1, k, _stats(c1, **merges),
                    _stats(c1, **merges),
                    _stats(c1, moved=5, tpu_turbo__merge_device=0,
                           tpu_turbo__merge_host=5))
    assert compare.is_correct(same)


def test_an_altered_answer_is_caught(manifest):
    cell, pairs, k = _served_and_reference(manifest, ONE_CHIP, None)
    cfg = cell.config

    def verdict_with(change):
        resp = json.loads(json.dumps(pairs[0][0]))
        change(resp["hits"])
        return _verdict(cell, [(resp, pairs[0][1])] + pairs[1:], k,
                        _stats(cfg), _stats(cfg), _stats(cfg, moved=1))

    def swap_id(h):       # a document that is not among the best
        worst = int(np.argmin(pairs[0][1]["scores"]))
        h["hits"][0]["_id"] = str(worst)

    def drop_hit(h):
        h["hits"].pop()

    def wrong_total(h):
        h["total"]["value"] += 1

    def unsorted(h):
        h["hits"][0], h["hits"][-1] = h["hits"][-1], h["hits"][0]

    assert not verdict_with(swap_id)["rank_gap"]["ok"]
    assert not verdict_with(drop_hit)["hits_wrong"]["ok"]
    assert not verdict_with(wrong_total)["hits_wrong"]["ok"]
    assert not verdict_with(unsorted)["order_err"]["ok"]


def test_exact_ties_may_come_in_either_order():
    scores = np.array([0.0, 2.0, 2.0, 1.0])
    ords, top = top_hits(scores, 2)
    assert list(ords) == [1, 2]
    ref = {"scores": scores, "ords": ords, "top": top, "total": 3}
    resp = {"hits": {"total": {"value": 3, "relation": "eq"}, "hits": [
        {"_id": "2", "_score": 2.0}, {"_id": "1", "_score": 2.0}]}}
    one = compare.compare_one(resp, ref, 2)
    assert one == {"score_err": 0.0, "rank_gap": 0.0, "order_err": 0.0,
                   "hits_wrong": 0}


def test_bf16_round_is_round_to_nearest_even():
    x = np.array([1.0, 1.0 + 2 ** -8, 1.0 + 3 * 2 ** -8, -3.1415927],
                 np.float32)
    got = bf16_round(x)
    assert got[0] == 1.0 and got[1] == 1.0          # tie -> even mantissa
    assert got[2] == np.float32(1.0 + 2 ** -6)      # tie -> even (up)
    assert abs(got[3] + 3.140625) < 1e-6
    assert np.all((got.view(np.uint32) & 0xFFFF) == 0)


def test_dotted_takes_keys_that_hold_dots():
    stats = {"tpu_search_latency": {"queue_wait.search": {"count": 3}},
             "tpu_knn": {"knn_queries": 7}}
    assert compare.dotted(stats, "tpu_knn.knn_queries") == 7
    assert compare.dotted(
        stats, "tpu_search_latency.queue_wait.search.count") == 3
    with pytest.raises(KeyError):
        compare.dotted(stats, "tpu_knn.nope")


def test_well_formed_counts_errors_not_slowness():
    ok = {"timed_out": False, "_shards": {"failed": 0},
          "hits": {"total": {"value": 0, "relation": "eq"}, "hits": []}}

    def well_formed(raw, n):
        return compare.responses_of(raw, n, compare.hits_well_formed)

    assert well_formed(json.dumps(ok).encode(), 1) == [ok]
    assert well_formed(
        json.dumps({"responses": [ok, ok]}).encode(), 2) == [ok, ok]
    assert well_formed(json.dumps({"responses": [ok]}).encode(), 2) is None
    assert well_formed(b"{not json", 1) is None
    assert well_formed(json.dumps({"error": "x"}).encode(), 1) is None
    bad = dict(ok, _shards={"failed": 1})
    assert well_formed(json.dumps(bad).encode(), 1) is None
