"""Every cell, rehearsed on the CPU at tiny size: the node is started and
the corpus installed as on the chip, the cell's traffic is driven over
HTTP, and the last line is assembled, compared with the plain reference
and checked against the manifest — for every cell, `--trace` 0 and 1, and
seeds of the kind the driver draws (31 bits; 1556403449 is the one PR 25's
check died on).

There is no device to trace here, so a traced window is handed the small
trace recorded on the chip (benchmark/testdata/), and the device it is
attributed to is the one it was recorded on. Nothing a CPU run measures is
printed or kept.
"""

import json
import os

import numpy as np
import pytest

from benchmark import readers, run, trace, validate
from benchmark.manifest import ROOT, Manifest
from benchmark.traffic import WINDOW, Mix

import bench_tiny

SEEDS = (1556403449, 2147483647, 1073741827, 88172645)
CELLS = bench_tiny.CELLS      # the accepted cells and the held one
RECORDED = {
    c: os.path.join(ROOT, "benchmark", "testdata", f"trace_{c}.json")
    for c in CELLS}
CHIP = {"platform": "tpu", "kind": "TPU v5 lite"}
OPEN = tuple(Manifest(ROOT).cell_names())
SENT = []          # every canonical request a Mix put on the wire


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    return bench_tiny.tiny_manifest(str(tmp_path_factory.mktemp("tiny")))


@pytest.fixture(scope="module")
def benches(manifest, tmp_path_factory):
    """One node per cell, set up once from the first seed."""
    mp = pytest.MonkeyPatch()
    bench_tiny.steer_engines(mp, str(tmp_path_factory.mktemp("jax_cache")))
    made = {}
    request = Mix.request      # under `call` and `msearch` alike
    mp.setattr(Mix, "request",
               lambda self, j: (SENT.append(int(j)), request(self, j))[1])

    def get(cell):
        if cell not in made:
            del SENT[:]
            b = run.Bench(manifest, cell, require_chip=False,
                          out_dir=str(tmp_path_factory.mktemp("out")))
            # the device the recorded trace was taken on
            b.dev = dict(CHIP, count=b.cell.chips)
            b.setup(SEEDS[0])
            b.sent_in_setup = list(SENT)
            made[cell] = b
        return made[cell]

    yield get
    for b in made.values():
        b.close()
    mp.undo()


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("trace_on", (0, 1))
@pytest.mark.parametrize("cell", CELLS)
def test_last_line_carries_every_declared_metric(benches, manifest, cell,
                                                 trace_on, seed, capsys):
    bench = benches(cell)
    events = trace.load_events(RECORDED[cell]) if trace_on else None
    w = bench.window(seed, 1.0, trace_on, events=events)
    line, rc = bench.report(w, trace_on)
    assert rc == 0 and line is not None, capsys.readouterr().err[-2000:]
    assert validate.line_faults(line, manifest, cell, trace_on) == []
    want = [m["name"] for m in manifest.declared(cell, trace_on)]
    assert list(line["metrics"]) == want          # the manifest's own order
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "checked"
    for v in line["checked"].values():
        assert set(v) == {"value", "limit", "ok"}
    if trace_on:
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        assert len(line["breakdown"]["device_ops"]) <= 10
        for name, m in line["metrics"].items():
            if "roofline" in name:
                assert 0 < m["value"] <= 105, (name, m)
    # the numbers compared, each beside its limit, end standard error
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-1].startswith("compared ") and " limit " in err[-1]


@pytest.mark.parametrize("cell", CELLS)
def test_served_answers_agree_with_the_plain_reference(benches, cell):
    """One test per configuration: the cell's own comparison, the program
    on one side and the plain reference on the other, at tiny size."""
    bench = benches(cell)
    w = bench.window(SEEDS[1], 4.0, 0)
    line, rc = bench.report(w, 0)
    assert rc == 0 and line["correct"] is True, line["checked"]
    assert line["checked"]["compared"]["value"] >= 24
    assert line["checked"]["score_err"]["value"] < 2e-6


def test_a_broken_timed_path_comes_out_not_correct(benches, monkeypatch):
    """The rest of a run with the timed path broken underneath: every
    score the engine's exact rescore produces is off by one part in a
    thousand, which no tolerance of the comparison may swallow."""
    from elasticsearch_tpu.parallel import turbo

    bench = benches(CELLS[0])
    sound = turbo.TurboBM25._exact_scores
    monkeypatch.setattr(
        turbo.TurboBM25, "_exact_scores",
        lambda self, qterms, docs: sound(self, qterms, docs)
        * np.float32(1.001))
    w = bench.window(SEEDS[2], 1.5, 0)
    monkeypatch.undo()
    line, rc = bench.report(w, 0)
    assert rc == 0 and line["correct"] is False
    assert not line["checked"]["score_err"]["ok"]
    assert line["checked"]["host_tier_answers"]["ok"]


def test_a_traced_window_without_the_kernel_prints_no_line(benches, capsys):
    """No event of the cell's kernel in the span: a truncated trace or a
    name the reducer does not know. The share is never reported as 0 and
    never silently left out: the run says what it saw and ends non-zero
    with no result line."""
    bench = benches(CELLS[0])
    events = [e for e in trace.load_events(RECORDED[CELLS[0]])
              if "sweep_rowmax" not in trace.own_name(e[2])]
    w = bench.window(SEEDS[3], 1.0, 1, events=events)
    line, rc = bench.report(w, 1)
    assert line is None and rc == 3
    err = capsys.readouterr().err
    assert "metrics lacks sweep_roofline_pct.search" in err
    assert "device events seen" in err
    assert os.path.exists(os.path.join(bench.trace_dir, "seen_names.json"))


def test_refuses_to_run_without_the_chip(manifest, capsys):
    line, rc = run.run_cell(manifest, CELLS[0], SEEDS[0], 1.0, 0)
    assert line is None and rc == 2
    out = capsys.readouterr()
    assert out.out == "" and "does not run without one" in out.err


def test_set_up_warms_what_the_window_uses(benches):
    bench = benches(CELLS[1])
    w = bench.window(SEEDS[3], 1.5, 0)
    spec = bench.manifest.metric_spec("compiles_in_window.search")
    assert readers.read(spec, w) == 0.0


@pytest.mark.parametrize("cell", CELLS)
def test_set_up_sends_no_request_that_a_window_sends(benches, cell):
    """Warm-up, prefill, bucket bursts and lead-in draw from the upper
    half of the mix's pool, every window from the lower: a timed request
    never finds its terms' slices or its filter's mask left by set-up."""
    bench = benches(cell)
    assert bench.sent_in_setup and min(bench.sent_in_setup) >= WINDOW
    del SENT[:]
    w = bench.window(SEEDS[2], 1.5, 0)
    batch = bench.cell.traffic["request"].get("batch", 1)
    assert len(SENT) == batch * w.notes["attempted"] > 0
    assert max(SENT) < WINDOW and len(set(SENT)) == len(SENT)


@pytest.mark.parametrize("cell", OPEN)
def test_every_seed_sends_the_same_work_in_another_order(benches, cell):
    bench = benches(cell)
    c = bench.cell
    scheds = []
    for seed in SEEDS[:3]:
        mix = Mix(c.traffic, c.config, seed, bench.parts)
        scheds.append(mix.window(40.0))
        lead = mix.lead_in(5.0)
        assert not set(lead.index) & set(scheds[-1].index)
    a, b, _ = scheds
    assert sorted(a.index) == sorted(b.index) == list(range(len(a.index)))
    assert list(a.index) != list(b.index)
    assert np.allclose(np.sort(np.diff(a.due, prepend=0)),
                       np.sort(np.diff(b.due, prepend=0)))
    with pytest.raises(ValueError):
        Mix(c.traffic, c.config, SEEDS[0], bench.parts).window(40.0, WINDOW)


def test_knn_filters_follow_the_cycle_and_the_reference_keeps_the_range(
        benches):
    bench = benches(CELLS[1])
    c = bench.cell
    mix = Mix(c.traffic, c.config, SEEDS[0], bench.parts)
    cycle = c.traffic["request"]["filter_cycle"]
    tags = np.concatenate([p.tags for p in bench.parts])
    for j in range(2 * len(cycle)):
        r = mix.request(j)
        f = r.body["knn"].get("filter")
        width = cycle[j % len(cycle)]
        if not width:
            assert f is None and r.tag_lo == -1
            continue
        names = [f["term"]["tag"]] if "term" in f else f["terms"]["tag"]
        assert len(names) == width == r.tag_hi - r.tag_lo
        assert r.tag_lo <= tags[r.doc] < r.tag_hi
        assert names[0] == "g%04d" % r.tag_lo
    assert mix.variants() == [True, False, None]
    assert mix.request(mix.warm(True)).tag_lo >= 0
    assert mix.request(mix.warm(False)).tag_lo == -1
