"""The aggregation engine's size floor, steered for the harness tests as
`bench_tiny.steer_engines` steers the other engines' thresholds: a tiny
segment reaches none of them, and a cell of the aggregation engine has to
be rehearsed on the CPU on the route it takes on the chip. And
`search.max_buckets` at its default: it is a value of the process, and a
test of the setting that ran before in this worker leaves its own behind
(the logs cell's `HourlyAgg` has 2,112 buckets)."""

import pytest


@pytest.fixture(autouse=True, scope="session")
def _aggregations_on_tiny_segments():
    import elasticsearch_tpu.search.aggregations as aggregations

    mp = pytest.MonkeyPatch()
    mp.setattr(aggregations, "AGG_DEVICE_MIN_DOCS", 1)
    mp.setattr(aggregations, "MAX_BUCKETS", 65536)
    yield
    mp.undo()


@pytest.fixture(scope="session", params=["as_committed", "one_more_appended"])
def grown(request, tmp_path_factory):
    """The manifest a test that holds an accepted cell's place is handed:
    as committed, and with one more cell and one more `per_layer` entry
    appended (`bench_tiny.appended_root`). A test that passes in the
    first case alone counts cells or holds a last place, and the next
    cell's PR, which may not edit it, would fail it."""
    import bench_tiny
    from benchmark.manifest import Manifest

    if request.param == "as_committed":
        return bench_tiny.REAL
    return Manifest(bench_tiny.appended_root(
        str(tmp_path_factory.mktemp("appended"))))
