"""Rehearsal of chip_smoke.py on the CPU mesh (on-chip-measurement §2.1).

The same phase functions the chip run drives, at a tiny size, with the
Pallas kernels in interpret mode and the existing test knobs standing in
for the TPU backend gate — everything except the device requirement. The
knobs and thresholds are steered here, in the test, not by an option of
the script."""

import jax
import pytest

import chip_smoke as cs


@pytest.fixture(scope="module")
def smoke(tmp_path_factory):
    import elasticsearch_tpu.search.aggregations as agg_mod
    from elasticsearch_tpu.__main__ import start_node

    mp = pytest.MonkeyPatch()
    mp.setenv("ES_TPU_FORCE_TURBO", "1")
    mp.setenv("ES_TPU_FORCE_KNN", "1")
    # tiny segments: nothing reaches the real thresholds (df 16384 for a
    # dense column, 512 matches before a conjunction leaves the host,
    # 65536 docs before an aggregation leaves it)
    mp.setenv("ES_TPU_TURBO_COLD_DF", "32")
    mp.setenv("ES_TPU_BITSET_HOST_DF", "0")
    mp.setattr(agg_mod, "AGG_DEVICE_MIN_DOCS", 100)
    # JAX read this variable when it was imported, so this only keeps
    # start_node from pointing the test worker at <checkout>/.jax_cache
    mp.setenv("JAX_COMPILATION_CACHE_DIR",
              str(tmp_path_factory.mktemp("jax_cache")))
    mp.setattr(cs, "BULK_BATCH", 500)
    node, server = start_node(port=0, name="smoke-rehearsal")
    client = cs.Client(node, server)
    vectors, vec_tags = cs.make_vectors(2000, seed=7)
    s = cs.Smoke(client=client, seed=7, index="smoke", vec_index="smoke_vec",
                 corpus=cs.make_corpus(6000, seed=7), vectors=vectors,
                 vec_tags=vec_tags, routing_reason="forced_turbo")
    try:
        cs.run_phase(s, cs.PHASES[0])            # ingest
        yield s
    finally:
        client.close()
        mp.undo()


@pytest.mark.parametrize("phase", cs.PHASES[1:], ids=lambda p: p.name)
def test_phase_on_cpu_mesh(smoke, phase, capsys):
    line = cs.run_phase(smoke, phase)
    assert line["phase"] == phase.name and line["requests"] > 0
    for c in phase.must_increase:
        assert line["counters"][c] > 0
    assert capsys.readouterr().out.count("\n") == 1   # one JSON line


def test_ingest_made_several_segments(smoke):
    stats = smoke.client.node.indices.get("smoke").stats()
    assert stats["segments"]["count"] >= 3


def test_phase_whose_counter_did_not_move_fails_the_run(smoke):
    idle = cs.Phase("idle", lambda s: {"requests": 0},
                    ("tpu_knn.knn_int8_dispatches",))
    with pytest.raises(cs.SmokeFailure, match="did not move"):
        cs.run_phase(smoke, idle)
    with pytest.raises(cs.SmokeFailure, match="did not move"):
        cs.run(smoke, [idle])


def test_fallback_counter_fails_the_phase(smoke):
    from elasticsearch_tpu.search import serving

    def host_answers(s):
        serving._count_serving("fastpath_reject_error")
        return {"requests": 1}

    with pytest.raises(cs.SmokeFailure, match="fastpath_reject_error"):
        cs.run_phase(smoke, cs.Phase("contained", host_answers))
    # later phases are held to the same baseline: put the counter back
    serving._count_serving("fastpath_reject_error", -1)


def test_refuses_to_run_without_a_tpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert cs.main([]) == 2
    out = capsys.readouterr()
    assert out.out == "" and "does not continue" in out.err


def _resp(hits, total=None):
    return {"hits": {"total": {"value": total or len(hits), "relation": "eq"},
                     "hits": [{"_id": i, "_score": s} for i, s in hits]}}


def test_compare_hits_holds_order_and_scores():
    ref = _resp([("a", 3.0), ("b", 2.0), ("c", 1.0)])
    assert cs.compare_hits(_resp([("a", 3.0), ("b", 2.0), ("c", 1.0)]), ref,
                           score_rtol=1e-6) == 0
    with pytest.raises(cs.SmokeFailure, match="no tie"):
        cs.compare_hits(_resp([("b", 3.0), ("a", 2.0), ("c", 1.0)]), ref,
                        score_rtol=1.0)
    with pytest.raises(cs.SmokeFailure, match="score"):
        cs.compare_hits(_resp([("a", 3.001), ("b", 2.0), ("c", 1.0)]), ref,
                        score_rtol=1e-6)
    with pytest.raises(cs.SmokeFailure, match="total"):
        cs.compare_hits(_resp([("a", 3.0), ("b", 2.0), ("c", 1.0)], 4), ref,
                        score_rtol=1e-6)
    with pytest.raises(cs.SmokeFailure, match="hits"):
        cs.compare_hits(_resp([("a", 3.0), ("b", 2.0)], 3), ref,
                        score_rtol=1e-6)


def test_compare_hits_sharded_reference_totals():
    """Over n shards the dense executor sums n capped counts."""
    got = _resp([("a", 3.0)])
    got["hits"]["total"] = {"value": 10000, "relation": "gte"}
    ref = _resp([("a", 3.0)])
    ref["hits"]["total"] = {"value": 40000, "relation": "gte"}
    with pytest.raises(cs.SmokeFailure, match="total"):
        cs.compare_hits(got, ref, score_rtol=1e-6)
    assert cs.compare_hits(got, ref, score_rtol=1e-6, totals="capped") == 0
    # four shards under the cap each, over it together
    ref["hits"]["total"] = {"value": 11235, "relation": "eq"}
    assert cs.compare_hits(got, ref, score_rtol=1e-6, totals="capped") == 0
    ref["hits"]["total"] = {"value": 7, "relation": "eq"}
    with pytest.raises(cs.SmokeFailure, match="total"):
        cs.compare_hits(got, ref, score_rtol=1e-6, totals="capped")
    assert cs.compare_hits(got, ref, score_rtol=1e-6, totals="skip") == 0


def test_compare_hits_lets_near_ties_trade_places():
    ref = _resp([("a", 3.0), ("b", 2.0000001), ("c", 2.0)], 9)
    swapped = _resp([("a", 3.0), ("c", 2.0), ("b", 2.0000001)], 9)
    assert cs.compare_hits(swapped, ref, score_rtol=1e-6) == 2
    # across the top-k boundary: "d" ties with the reference's last hit
    edge = _resp([("a", 3.0), ("b", 2.0000001), ("d", 2.0)], 9)
    assert cs.compare_hits(edge, ref, score_rtol=1e-6) == 1


def test_engine_of_reads_the_device_dispatch_node():
    resp = {"profile": {"shards": [{"searches": [{"query": [
        {"type": "MatchQuery", "description": "..."},
        {"type": "DeviceDispatch",
         "description": "engine=fused_turbo partitions=3"}]}]}]}}
    assert cs.engine_of(resp) == "fused_turbo"
    assert cs.engine_of({"hits": {}}) is None
