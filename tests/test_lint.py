"""tpulint lane (PR 7): rule fixtures, seeded regressions, and the
package-wide zero-findings gate.

Each rule gets a detection fixture, a clean twin, and a suppression
check; the seeded-regression tests then simulate exactly the rot each
rule exists to catch (deleting a fault_point, mutating guarded state
outside its lock, a typo'd knob) and assert the finding appears. The
meta-tests pin the baseline to reality: every entry must point at a line
that still exists AND still fire, and the package itself must lint clean.
"""

from __future__ import annotations

from pathlib import Path

import pytest

from tools.tpulint.core import (
    Finding, apply_baseline, lint_paths, lint_sources, load_baseline,
)

pytestmark = pytest.mark.lint

ROOT = Path(__file__).resolve().parents[1]
BASELINE = ROOT / "tools" / "tpulint" / "baseline.txt"


def rules_of(findings):
    return [f.rule for f in findings]


# --------------------------------------------------------------------------
# TPU001 — unguarded dispatch
# --------------------------------------------------------------------------

_SETTINGS_TWIN = (
    "elasticsearch_tpu/common/settings.py",
    '''
def declare_knob(name, type, default, doc):
    pass

declare_knob("ES_TPU_REAL", "int", 1, "a declared knob")
''',
)

_TPU001_PATH = "elasticsearch_tpu/parallel/fixture.py"

_TPU001_BAD = '''
import jax
from elasticsearch_tpu.common import faults

_prog = jax.jit(lambda x: x + 1)

def run(x):
    return _prog(x)
'''

_TPU001_CLEAN = '''
import jax
from elasticsearch_tpu.common import faults

_prog = jax.jit(lambda x: x + 1)

def run(x):
    with faults.device_errors("turbo_sweep", 0):
        return _prog(x)
'''

_TPU001_FAULT_POINT = '''
import jax
from elasticsearch_tpu.common import faults

_prog = jax.jit(lambda x: x + 1)

def run(x):
    faults.fault_point("turbo_sweep", 0)
    return _prog(x)
'''


def test_tpu001_detects_unguarded_dispatch():
    findings = lint_sources([(_TPU001_PATH, _TPU001_BAD)])
    assert rules_of(findings) == ["TPU001"]
    assert "_prog" in findings[0].message


def test_tpu001_clean_twin_passes():
    assert lint_sources([(_TPU001_PATH, _TPU001_CLEAN)]) == []
    assert lint_sources([(_TPU001_PATH, _TPU001_FAULT_POINT)]) == []


def test_tpu001_device_put_flagged_and_jit_def_is_not():
    src = '''
import jax

@jax.jit
def kernel(x):
    return x + 1          # trace-time body: never a dispatch site

def upload(a):
    return jax.device_put(a)
'''
    findings = lint_sources([(_TPU001_PATH, src)])
    assert rules_of(findings) == ["TPU001"]
    assert "device_put" in findings[0].message


def test_tpu001_suppression():
    src = _TPU001_BAD.replace(
        "return _prog(x)", "return _prog(x)  # tpulint: disable=TPU001")
    assert lint_sources([(_TPU001_PATH, src)]) == []


def test_tpu001_only_applies_to_dispatch_layers():
    # the same unguarded call in a non-dispatch layer is not flagged
    assert lint_sources([("elasticsearch_tpu/rest/fixture.py",
                          _TPU001_BAD)]) == []


def test_seeded_regression_deleting_fault_point_is_caught():
    # the ISSUE's canary: remove the fault_point wrapper from a guarded
    # dispatch site and the linter must notice
    broken = _TPU001_FAULT_POINT.replace(
        '    faults.fault_point("turbo_sweep", 0)\n', "")
    assert lint_sources([(_TPU001_PATH, _TPU001_FAULT_POINT)]) == []
    assert rules_of(lint_sources([(_TPU001_PATH, broken)])) == ["TPU001"]


# --------------------------------------------------------------------------
# TPU002 — guarded-by
# --------------------------------------------------------------------------

_TPU002_PATH = "elasticsearch_tpu/common/fixture.py"

_TPU002_CLEAN = '''
import threading

class Box:
    def __init__(self):
        self._lock = threading.Lock()
        self._items = []       # guarded by: _lock
        self.count = 0         # guarded by: _lock

    def push(self, x):
        with self._lock:
            self._items.append(x)
            self.count += 1
'''

_TPU002_BAD = _TPU002_CLEAN + '''
    def rogue(self, x):
        self._items.append(x)
'''


def test_tpu002_detects_unlocked_mutation():
    findings = lint_sources([(_TPU002_PATH, _TPU002_BAD)])
    assert rules_of(findings) == ["TPU002"]
    assert "_items" in findings[0].message


def test_tpu002_clean_twin_passes():
    assert lint_sources([(_TPU002_PATH, _TPU002_CLEAN)]) == []


def test_tpu002_holds_marker_trusts_helper():
    src = _TPU002_CLEAN + '''
    def _push_locked(self, x):  # tpulint: holds=_lock
        self._items.append(x)
'''
    assert lint_sources([(_TPU002_PATH, src)]) == []


def test_tpu002_module_global_and_augassign():
    src = '''
import threading

_LOCK = threading.Lock()
_STATS = {"n": 0}   # guarded by: _LOCK

def good():
    with _LOCK:
        _STATS["n"] += 1

def bad():
    _STATS["n"] += 1
'''
    findings = lint_sources([(_TPU002_PATH, src)])
    assert rules_of(findings) == ["TPU002"]
    assert findings[0].line == src.splitlines().index('    _STATS["n"] += 1',
                                                      8) + 1


def test_tpu002_suppression():
    src = _TPU002_BAD.replace(
        "        self._items.append(x)\n" ,
        "        self._items.append(x)  # tpulint: disable=TPU002\n")
    assert lint_sources([(_TPU002_PATH, src)]) == []


def test_seeded_regression_guarded_mutation_outside_lock_is_caught():
    broken = _TPU002_CLEAN.replace(
        "        with self._lock:\n"
        "            self._items.append(x)\n"
        "            self.count += 1\n",
        "        self._items.append(x)\n"
        "        self.count += 1\n")
    findings = lint_sources([(_TPU002_PATH, broken)])
    assert rules_of(findings) == ["TPU002", "TPU002"]


# --------------------------------------------------------------------------
# TPU003 — knob registry
# --------------------------------------------------------------------------

_TPU003_PATH = "elasticsearch_tpu/common/fixture.py"


def test_tpu003_detects_direct_env_read():
    src = '''
import os
v = os.environ.get("ES_TPU_SECRET_KNOB", "")
w = os.environ["ES_TPU_OTHER"]
x = os.getenv("ES_TPU_THIRD")
'''
    findings = lint_sources([(_TPU003_PATH, src), _SETTINGS_TWIN])
    assert rules_of(findings) == ["TPU003", "TPU003", "TPU003"]


def test_tpu003_knob_call_and_non_es_tpu_env_are_clean():
    src = '''
import os
from elasticsearch_tpu.common.settings import knob

a = knob("ES_TPU_REAL")
b = os.environ.get("HOME")
'''
    assert lint_sources([(_TPU003_PATH, src), _SETTINGS_TWIN]) == []


def test_tpu003_fstring_env_read_flagged():
    src = '''
import os

def read(name):
    return os.environ.get(f"ES_TPU_POOL_{name}_SIZE")
'''
    findings = lint_sources([(_TPU003_PATH, src), _SETTINGS_TWIN])
    assert rules_of(findings) == ["TPU003"]


def test_tpu003_suppression():
    src = 'import os\nv = os.environ.get("ES_TPU_X")  # tpulint: disable=TPU003\n'
    assert lint_sources([(_TPU003_PATH, src), _SETTINGS_TWIN]) == []


def test_seeded_regression_undeclared_knob_is_caught():
    ok = 'from elasticsearch_tpu.common.settings import knob\nv = knob("ES_TPU_REAL")\n'
    typo = ok.replace("ES_TPU_REAL", "ES_TPU_RAEL")
    assert lint_sources([(_TPU003_PATH, ok), _SETTINGS_TWIN]) == []
    findings = lint_sources([(_TPU003_PATH, typo), _SETTINGS_TWIN])
    assert rules_of(findings) == ["TPU003"]
    assert "ES_TPU_RAEL" in findings[0].message


# --------------------------------------------------------------------------
# TPU004 — dtype drift
# --------------------------------------------------------------------------

_TPU004_PATH = "elasticsearch_tpu/ops/scoring.py"


def test_tpu004_detects_literal_mixed_with_narrow_int():
    src = '''
def f(x):
    q = x.astype("int8")
    return q * 0.5
'''
    findings = lint_sources([(_TPU004_PATH, src)])
    assert rules_of(findings) == ["TPU004"]
    assert "`q`" in findings[0].message


def test_tpu004_division_of_narrow_array_flagged():
    src = '''
import jax.numpy as jnp

def f(x):
    h = jnp.zeros((4,), dtype=jnp.bfloat16)
    return h / 2
'''
    findings = lint_sources([(_TPU004_PATH, src)])
    assert rules_of(findings) == ["TPU004"]


def test_tpu004_clean_twin_passes():
    src = '''
import numpy as np

def f(x):
    q = x.astype("int8")
    wide = q.astype(np.float32)
    return wide * 0.5, q * 2
'''
    # explicit astype before float math; int * int literal is exact
    assert lint_sources([(_TPU004_PATH, src)]) == []


def test_tpu004_only_applies_to_kernel_files():
    src = 'def f(x):\n    q = x.astype("int8")\n    return q * 0.5\n'
    assert lint_sources([("elasticsearch_tpu/search/fixture.py", src)]) == []


def test_tpu004_suppression():
    src = '''
def f(x):
    q = x.astype("int8")
    return q * 0.5  # tpulint: disable=TPU004
'''
    assert lint_sources([(_TPU004_PATH, src)]) == []


# --------------------------------------------------------------------------
# TPU005 — counter hygiene
# --------------------------------------------------------------------------

_TPU005_PATH = "elasticsearch_tpu/common/fixture.py"

_TPU005_BAD = '''
class S:
    def __init__(self):
        self.hits = 0
        self.lost = 0

    def record(self):
        self.hits += 1
        self.lost += 1

    def stats(self):
        return {"hits": self.hits}
'''


def test_tpu005_detects_invisible_counter():
    findings = lint_sources([(_TPU005_PATH, _TPU005_BAD)])
    assert rules_of(findings) == ["TPU005"]
    assert "lost" in findings[0].message


def test_tpu005_clean_twin_passes():
    src = _TPU005_BAD.replace('return {"hits": self.hits}',
                              'return {"hits": self.hits, "lost": self.lost}')
    assert lint_sources([(_TPU005_PATH, src)]) == []


def test_tpu005_gauges_and_statless_classes_exempt():
    src = '''
class Gauge:
    def __init__(self):
        self.active = 0

    def enter(self):
        self.active += 1

    def leave(self):
        self.active -= 1

    def stats(self):
        return {}

class NoStats:
    def __init__(self):
        self.n = 0

    def bump(self):
        self.n += 1
'''
    assert lint_sources([(_TPU005_PATH, src)]) == []


def test_tpu005_suppression():
    src = _TPU005_BAD.replace("        self.lost += 1",
                              "        self.lost += 1  # tpulint: disable=TPU005")
    assert lint_sources([(_TPU005_PATH, src)]) == []


# TPU005's histogram-registry pass (PR 9): literal observe() sites must
# name a histogram declared in common/metrics.py, otherwise the metric
# never surfaces in `tpu_search_latency` and raises at runtime.

_METRICS_TWIN = (
    "elasticsearch_tpu/common/metrics.py",
    '''
def declare_histogram(name, kind, doc):
    pass

declare_histogram("device", "ms", "one device dispatch")
declare_histogram("queue_wait.search", "ms", "search pool wait")
''',
)


def test_tpu005_undeclared_observe_detected():
    bad = (_TPU005_PATH, '''
from elasticsearch_tpu.common import metrics

def record(ms):
    metrics.observe("devcie", ms)
''')
    findings = lint_sources([_METRICS_TWIN, bad], select={"TPU005"})
    assert rules_of(findings) == ["TPU005"]
    assert "devcie" in findings[0].message


def test_tpu005_declared_observe_clean():
    ok = (_TPU005_PATH, '''
from elasticsearch_tpu.common import metrics

def record(ms, pool):
    metrics.observe("device", ms)
    # dynamically composed names go through the lenient entry point,
    # which the rule deliberately ignores
    metrics.observe_if_declared(f"queue_wait.{pool}", ms)
''')
    assert lint_sources([_METRICS_TWIN, ok], select={"TPU005"}) == []


@pytest.mark.parametrize("call", ["tracing.phase", "tracing.record",
                                  "tracing.steps.add"])
def test_tpu005_span_primitive_names_are_tied_to_the_registry(call):
    """`tracing.phase("x")` / `tracing.record("x", ms)` /
    `tracing.steps.add("x", ms)` observe under the span's own name (PR 27,
    PR 42): a literal name must be declared; a composed one, and a `phase`
    / `record` / `add` of some other object, are left alone."""
    src = (_TPU005_PATH, f'''
from elasticsearch_tpu.common import tracing

def serve(ms, pool, health, hist):
    with {call}("devcie", 1.0):
        pass
    with {call}("device", 1.0):
        pass
    {call}(f"queue_wait.{{pool}}", ms)
    health.record("not_a_histogram")
    hist.phase("moon")
    seen.add("a_set_member")
    other.steps.add("not_tracing")
''')
    findings = lint_sources([_METRICS_TWIN, src], select={"TPU005"})
    assert rules_of(findings) == ["TPU005"]
    assert "devcie" in findings[0].message and call in findings[0].message


def test_tpu005_observe_pass_needs_registry_in_scope():
    """Without metrics.py in the lint scope there is no declaration set, so
    the rule must stay silent (fixture snippets would otherwise light up)."""
    orphan = (_TPU005_PATH, '''
from elasticsearch_tpu.common import metrics

def record(ms):
    metrics.observe("anything_at_all", ms)
''')
    assert lint_sources([orphan], select={"TPU005"}) == []


# TPU005's gauge-surface pass (PR 12): a file that declares a gauge must
# also surface it — the dotted tail has to appear as a key in some *stats()
# function in the same file, otherwise the gauge scrapes over /_tpu/metrics
# but is invisible in its owning `_nodes/stats` section.

_TPU005_GAUGE_BAD = '''
from elasticsearch_tpu.common import metrics

metrics.declare_gauge("tpu_widget.occupancy_bytes", "bytes resident")

def widget_stats():
    return {"evictions": 0}
'''


def test_tpu005_unsurfaced_gauge_detected():
    findings = lint_sources([(_TPU005_PATH, _TPU005_GAUGE_BAD)],
                            select={"TPU005"})
    assert rules_of(findings) == ["TPU005"]
    assert "tpu_widget.occupancy_bytes" in findings[0].message


def test_tpu005_surfaced_gauge_clean():
    ok = _TPU005_GAUGE_BAD.replace(
        'return {"evictions": 0}',
        'return {"evictions": 0, "occupancy_bytes": 0}')
    assert lint_sources([(_TPU005_PATH, ok)], select={"TPU005"}) == []


def test_tpu005_gauge_pass_exempts_metrics_registry():
    """common/metrics.py holds the central cross-subsystem declarations
    (e.g. scheduler gauges) whose stats() surfaces live elsewhere."""
    registry = ("elasticsearch_tpu/common/metrics.py", _TPU005_GAUGE_BAD)
    assert lint_sources([registry], select={"TPU005"}) == []


# --------------------------------------------------------------------------
# Baseline machinery
# --------------------------------------------------------------------------


def test_baseline_round_trip(tmp_path):
    p = tmp_path / "baseline.txt"
    p.write_text("# comment\n\na/b.py:10: TPU001 legacy tier\n")
    entries = load_baseline(str(p))
    assert entries == {("a/b.py", 10, "TPU001"): "legacy tier"}
    f_known = Finding("TPU001", "a/b.py", 10, 0, "m")
    f_new = Finding("TPU002", "a/b.py", 11, 0, "m")
    fresh, stale = apply_baseline([f_known, f_new], entries)
    assert fresh == [f_new] and stale == []
    fresh, stale = apply_baseline([f_new], entries)
    assert fresh == [f_new] and stale == [("a/b.py", 10, "TPU001")]


def test_baseline_rejects_reasonless_and_garbage(tmp_path):
    p = tmp_path / "baseline.txt"
    p.write_text("a/b.py:10: TPU001\n")
    with pytest.raises(ValueError):
        load_baseline(str(p))
    p.write_text("not a baseline line\n")
    with pytest.raises(ValueError):
        load_baseline(str(p))


# --------------------------------------------------------------------------
# The package-wide gate + baseline meta-tests
# --------------------------------------------------------------------------


def test_package_lints_clean_against_baseline():
    findings = lint_paths(["elasticsearch_tpu"], root=str(ROOT))
    fresh, stale = apply_baseline(findings, load_baseline(str(BASELINE)))
    assert not fresh, "non-baselined findings:\n" + "\n".join(
        f.render() for f in fresh)
    assert not stale, "stale baseline entries (code moved — re-justify " \
        "or drop):\n" + "\n".join(f"{p}:{ln}: {r}" for p, ln, r in stale)


def test_baseline_references_live_lines():
    for (path, line, rule), reason in load_baseline(str(BASELINE)).items():
        src = ROOT / path
        assert src.exists(), f"baseline references missing file {path}"
        n_lines = len(src.read_text().splitlines())
        assert 1 <= line <= n_lines, \
            f"baseline {path}:{line} ({rule}) is past EOF ({n_lines} lines)"
        assert reason.strip(), f"baseline {path}:{line} has no reason"


def test_cli_exits_clean(capsys, monkeypatch):
    from tools.tpulint.__main__ import main

    monkeypatch.chdir(ROOT)
    assert main(["elasticsearch_tpu"]) == 0
    out = capsys.readouterr().out
    assert "OK" in out


# --------------------------------------------------------------------------
# Knob registry semantics (satellite 1)
# --------------------------------------------------------------------------


def test_knob_reads_env_per_call(monkeypatch):
    from elasticsearch_tpu.common.settings import knob

    monkeypatch.delenv("ES_TPU_HEALTH_TRIP_N", raising=False)
    assert knob("ES_TPU_HEALTH_TRIP_N") == 3
    monkeypatch.setenv("ES_TPU_HEALTH_TRIP_N", "5")
    assert knob("ES_TPU_HEALTH_TRIP_N") == 5
    monkeypatch.setenv("ES_TPU_HEALTH_TRIP_N", "junk")
    assert knob("ES_TPU_HEALTH_TRIP_N") == 3      # lenient fallback


def test_knob_flag_semantics(monkeypatch):
    from elasticsearch_tpu.common.settings import knob

    monkeypatch.setenv("ES_TPU_FORCE_TURBO", "1")
    assert knob("ES_TPU_FORCE_TURBO") is True
    monkeypatch.setenv("ES_TPU_FORCE_TURBO", "true")
    assert knob("ES_TPU_FORCE_TURBO") is False    # exactly "1" means on


def test_knob_undeclared_raises():
    from elasticsearch_tpu.common.settings import UndeclaredKnobError, knob

    with pytest.raises(UndeclaredKnobError):
        knob("ES_TPU_NO_SUCH_KNOB")


def test_effective_knobs_reports_source(monkeypatch):
    from elasticsearch_tpu.common.settings import effective_knobs

    monkeypatch.setenv("ES_TPU_FAULTS_SEED", "7")
    monkeypatch.delenv("ES_TPU_HEALTH_TRIP_N", raising=False)
    eff = effective_knobs()
    assert eff["ES_TPU_FAULTS_SEED"]["value"] == 7
    assert eff["ES_TPU_FAULTS_SEED"]["source"] == "env"
    assert eff["ES_TPU_HEALTH_TRIP_N"]["source"] == "default"
    assert eff["ES_TPU_HEALTH_TRIP_N"]["value"] == 3


def test_nodes_stats_exposes_tpu_settings():
    from elasticsearch_tpu.rest.handlers import _tpu_settings_stats

    eff = _tpu_settings_stats()
    assert "ES_TPU_FAULTS" in eff and "ES_TPU_TURBO_HBM" in eff
    for entry in eff.values():
        assert {"value", "default", "type", "source"} <= set(entry)
