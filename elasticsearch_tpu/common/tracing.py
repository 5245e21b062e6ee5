"""Per-request trace contexts for the search flight recorder.

A ``TraceContext`` is born at the REST layer (or at a coordinator entry for
the in-process cluster harness), rides the current thread via a thread-local,
hops threads through ``threadpool.pool`` (tasks capture the submitter's trace
and re-activate it in the worker), and crosses node boundaries as a small
``_trace`` dict inside the shard RPC payload — NEVER inside the search body
itself, which would trip ``extract_plan``'s allowed-key check and silently
kill the Turbo fast path.

Tracing is OFF by default: ``current()`` returns None, every recording site
degrades to one thread-local read, and responses are bit-identical to the
untraced build (differential-tested). It turns on per request when:

- the search body asks for ``profile``,
- ``ES_TPU_TRACE_SAMPLE`` = N samples every Nth search, or
- the target index has any ``index.search.slowlog.threshold.*`` configured
  (slow queries must carry phase attribution when they hit the slowlog).

Completed traces land in a bounded in-memory ring (``ES_TPU_TRACE_RING``);
over-threshold queries additionally append structured records to the slowlog
ring (``ES_TPU_SLOWLOG_RING``) served at ``GET /_tpu/slowlog``.

ONE span primitive serves all three observers: ``phase(name, **meta)``
records its duration into the declared histogram ``name`` (always on), adds
a span to the active context (when one is), and enters a
``jax.profiler.TraceAnnotation("es." + name)`` so that, whenever a profiler
session runs, the span sits on the host plane of the same xplane as the
device ops. Spans carry ``id`` / ``parent`` / ``start_ns`` / ``end_ns`` on
one process-wide clock (``time.monotonic_ns``); the parent is the enclosing
span on the thread or, across a pool / scheduler hop, the span that
submitted the work (``activate(tc, parent)``).
"""

from __future__ import annotations

import gc
import itertools
import threading
import time
import uuid
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from elasticsearch_tpu.common import metrics
from elasticsearch_tpu.common.settings import knob, parse_time_value

_tls = threading.local()
_clock = time.monotonic_ns
_span_ids = itertools.count(1)     # next() is atomic under the GIL


class _ThreadState:
    """What one thread knows about where it is: the active context, the
    span its work descends from across a hop, the open phases, and the
    per-engine-call step accumulator."""

    __slots__ = ("trace", "parent", "base", "stack", "steps")

    def __init__(self):
        self.trace = None          # TraceContext | _Fanout | None
        self.parent = 0            # span id inherited across a hop
        self.base = 0              # stack depth at the last activate()
        self.stack: List["phase"] = []
        self.steps: Optional[Dict[str, float]] = None


def _state() -> _ThreadState:
    st = getattr(_tls, "st", None)
    if st is None:
        st = _tls.st = _ThreadState()
    return st


def self_times(spans: Iterable[dict]) -> Dict[str, float]:
    """Self time by span name (ms): a span's duration minus what its
    children cover (the union of their intervals, clipped to the span), so
    nested spans partition their root instead of double-counting it.
    ``rest_total`` is left out: it envelopes the request, and its own
    remainder is what no span names."""
    spans = list(spans)
    kids: Dict[int, List[Tuple[int, int]]] = {}
    for s in spans:
        if s.get("parent"):
            kids.setdefault(s["parent"], []).append(
                (s["start_ns"], s["end_ns"]))
    out: Dict[str, float] = {}
    for s in spans:
        if s["name"] == "rest_total":
            continue
        lo, hi = s["start_ns"], s["end_ns"]
        covered, edge = 0, lo
        for a, b in sorted(kids.get(s.get("id"), ())):
            a, b = max(a, edge), min(b, hi)
            if b > a:
                covered += b - a
                edge = b
        own = max(0, (hi - lo) - covered) / 1e6
        out[s["name"]] = out.get(s["name"], 0.0) + own
    return {k: round(v, 3) for k, v in out.items()}


class TraceContext:
    """Spans for one search request on one node. Thread-safe: spans arrive
    from pool workers, scheduler lanes and RPC threads
    concurrently."""

    __slots__ = ("trace_id", "opaque_id", "node", "kind", "t0_ns", "spans",
                 "_lock")

    def __init__(self, trace_id: Optional[str] = None,
                 opaque_id: Optional[str] = None,
                 node: str = "", kind: str = "coordinator"):
        self.trace_id = trace_id or uuid.uuid4().hex[:16]
        self.opaque_id = opaque_id
        self.node = node
        self.kind = kind
        self.t0_ns = _clock()
        self._lock = threading.Lock()
        self.spans: List[dict] = []  # guarded by: _lock

    def _link(self, span: dict) -> None:
        with self._lock:
            self.spans.append(span)

    def add_span(self, name: str, duration_ms: float, **meta: Any) -> None:
        """A span measured by the caller, ending now (an RPC attempt with
        its error, a wait that ended on another thread's clock read).
        Sites that also feed a histogram use ``phase`` / ``record``."""
        self._link(_make_span(name, _open_span(self), duration_ms, meta))

    @contextmanager
    def span(self, name: str, **meta: Any):
        t0 = time.monotonic()
        try:
            yield
        finally:
            self.add_span(name, (time.monotonic() - t0) * 1e3, **meta)

    def span_dicts(self) -> List[dict]:
        with self._lock:
            spans = list(self.spans)
        return [dict(s, trace_id=self.trace_id,
                     start_ms=round(
                         max(0, s["start_ns"] - self.t0_ns) / 1e6, 3))
                for s in spans]

    def phase_totals(self) -> Dict[str, float]:
        """Self time by span name (ms), see ``self_times``: the phases of
        ``profile.tpu`` and of a slowlog record stay a partition of the
        request once spans nest."""
        with self._lock:
            return self_times(self.spans)

    def wire(self) -> dict:
        """What crosses the RPC boundary (payload `_trace` key)."""
        return {"trace_id": self.trace_id, "opaque_id": self.opaque_id}

    def to_dict(self) -> dict:
        return {"trace_id": self.trace_id, "opaque_id": self.opaque_id,
                "node": self.node, "kind": self.kind,
                "spans": self.span_dicts()}


def _open_span(tc) -> int:
    """Id of the innermost span open on this thread under ``tc`` (0 when
    ``tc`` is not this thread's active context)."""
    st = getattr(_tls, "st", None)
    if st is None or st.trace is not tc or tc is None:
        return 0
    return st.stack[-1].span_id if len(st.stack) > st.base else st.parent


def _make_span(name: str, parent: int, duration_ms: float,
               meta: dict) -> dict:
    """A span measured by its caller: it ends now."""
    end_ns = _clock()
    span = {"id": next(_span_ids), "parent": parent, "name": name,
            "start_ns": end_ns - int(max(0.0, duration_ms) * 1e6),
            "end_ns": end_ns, "duration_ms": round(duration_ms, 3)}
    if meta:
        span["meta"] = meta
    return span


class _Fanout:
    """The context of work done once on behalf of several requests (one
    scheduler batch): every span recorded under it is one
    record, linked into the context of each traced waiter. A span opened
    at the top of the activation hangs off the span each waiter was in
    when it submitted."""

    __slots__ = ("targets", "trace_id")

    def __init__(self, targets: Sequence[Tuple[TraceContext, int]]):
        self.targets = list(targets)
        self.trace_id = self.targets[0][0].trace_id   # the annotation's stat

    def _link(self, span: dict) -> None:
        for tc, parent in self.targets:
            tc._link(span if span["parent"] or not parent
                     else dict(span, parent=parent))


def fanout(targets: Iterable[Tuple[Optional[TraceContext], int]]):
    """``activate()``-able context over the traced (context, submitting
    span) pairs of one batch; None when no waiter is traced."""
    traced = [(tc, parent) for tc, parent in targets if tc is not None]
    return _Fanout(traced) if traced else None


def current():
    """The thread's active context (a ``TraceContext``; on a batch's
    dispatch thread the fan-out over its traced waiters), or None."""
    st = getattr(_tls, "st", None)
    return st.trace if st is not None else None


def current_span() -> int:
    """Id of the innermost span open on this thread under the active
    context (what a `_Task` / `_Waiter` captures beside the context, so
    the work it submits descends from it), 0 when untraced."""
    return _open_span(current())


def open_phase() -> Optional[str]:
    """Name of the innermost ``phase`` open on this thread, traced or not
    (the jit-build listener files a build under it)."""
    st = getattr(_tls, "st", None)
    return st.stack[-1].name if st is not None and st.stack else None


@contextmanager
def activate(tc, parent: int = 0):
    """Install ``tc`` as the thread's current trace; spans opened inside
    descend from ``parent`` (the submitter's span across a thread hop).
    activate(None) is a no-op pass-through so call sites need no
    branching."""
    if tc is None:
        yield None
        return
    st = _state()
    prev = (st.trace, st.parent, st.base)
    st.trace, st.parent, st.base = tc, int(parent or 0), len(st.stack)
    try:
        yield tc
    finally:
        st.trace, st.parent, st.base = prev


# --- the span primitive -------------------------------------------------------

_ANNOTATION = None     # jax.profiler.TraceAnnotation, resolved on first use


def _annotation_cls():
    """Imported on first use: this module is imported long before anything
    needs jax, and importing jax is not free."""
    global _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation
        _ANNOTATION = TraceAnnotation
    return _ANNOTATION


def name_thread(name: str) -> None:
    """Give the CALLING thread its OS name (15 bytes at most): the
    profiler's host plane files a thread's annotations under a line of
    that name, taken when the thread's first one is recorded, and every
    Python thread is otherwise a line called after the process. For
    threads that carry spans side by side with others (a scheduler lane,
    a hybrid batch's second side): call it first in the thread. Linux
    only; elsewhere, and on any error, nothing happens."""
    try:
        import ctypes

        ctypes.CDLL(None).prctl(15, name.encode()[:15], 0, 0, 0)
    except Exception:   # noqa: BLE001 — a name is never worth a failure
        pass


def annotation(name: str, **stats: Any):
    """A bare profiler annotation ``es.<name>`` (context manager): live
    exactly while a profiler session is, no histogram and no span. For
    what is not a phase of a request: a lane parked on its queue, a
    collection, a program being built."""
    return _annotation_cls()("es." + name, **stats)


class phase:
    """``with tracing.phase("dispatch.prep", batch=n):`` — the one span
    primitive. On exit the duration goes into the declared histogram
    ``name`` (inside ``steps()``, into that call's accumulator instead);
    with a context active a span ``name`` is linked into it; and the
    block runs under the profiler annotation ``es.<name>`` carrying
    ``trace_id`` and the meta as stats. ``.ms`` holds the duration after
    the block. tpulint TPU005 ties literal names to ``declare_histogram``."""

    __slots__ = ("name", "meta", "ms", "span_id", "_st", "_tc", "_parent",
                 "_ann", "_t0")

    def __init__(self, name: str, **meta: Any):
        self.name = name
        self.meta = meta
        self.ms = 0.0
        self.span_id = 0

    def __enter__(self) -> "phase":
        st = self._st = _state()
        tc = self._tc = st.trace
        if tc is not None:
            stack = st.stack
            self._parent = (stack[-1].span_id if len(stack) > st.base
                            else st.parent)
            self.span_id = next(_span_ids)
            ann = _annotation_cls()("es." + self.name, trace_id=tc.trace_id,
                                    **self.meta)
        else:
            ann = _annotation_cls()("es." + self.name, **self.meta)
        st.stack.append(self)
        self._ann = ann
        ann.__enter__()
        self._t0 = _clock()
        return self

    def __exit__(self, et, ev, tb) -> bool:
        t1 = _clock()
        self._ann.__exit__(et, ev, tb)
        st = self._st
        st.stack.pop()
        self.ms = ms = (t1 - self._t0) / 1e6
        steps = st.steps
        if steps is not None and self.name in steps:
            steps[self.name] += ms
        else:
            metrics.observe(self.name, ms)
        tc = self._tc
        if tc is not None:
            span = {"id": self.span_id, "parent": self._parent,
                    "name": self.name, "start_ns": self._t0, "end_ns": t1,
                    "duration_ms": round(ms, 3)}
            if et is not None:
                span["meta"] = dict(self.meta, error=et.__name__)
            elif self.meta:
                span["meta"] = self.meta
            tc._link(span)
        return False


def record(name: str, duration_ms: float, tc=None, parent: int = 0,
           **meta: Any) -> None:
    """A phase measured by the caller, ending now: the histogram ``name``
    (skipped when undeclared: composed names of ad-hoc pools) and a span
    in ``tc`` (default: the thread's context). No annotation: the
    profiler takes no event after the fact."""
    metrics.observe_if_declared(name, duration_ms)
    if tc is None:
        tc, parent = current(), current_span()
    if tc is not None:
        tc._link(_make_span(name, int(parent or 0), duration_ms, meta))


class steps:
    """Per-engine-call accumulator: inside the block a ``phase`` whose
    name is in ``names`` adds its duration here, and on exit every name
    gets ONE histogram observation holding its total inside the call (0.0
    when the step did not run). The step means are then per dispatch,
    children sum under parents, and the top-level steps sum to `device`.
    Nested (a fused engine calling a partition's engine) the outer call
    owns the accumulator. A step timed by its caller's clock reads joins
    through ``steps.add``."""

    __slots__ = ("names", "_st", "_own")

    def __init__(self, names: Sequence[str]):
        self.names = names

    def __enter__(self) -> "steps":
        st = self._st = _state()
        self._own = st.steps is None
        if self._own:
            st.steps = dict.fromkeys(self.names, 0.0)
        return self

    def __exit__(self, et, ev, tb) -> bool:
        if self._own:
            acc, self._st.steps = self._st.steps, None
            for name, ms in acc.items():
                metrics.observe(name, ms)
        return False

    @staticmethod
    def add(name: str, ms: float, start_ns: int = 0, **meta: Any) -> None:
        """A step its caller timed with clock reads: one that runs a QUERY
        at a time inside an engine call (or a request at a time inside an
        `_msearch`'s response loop), where a ``phase`` each would be
        hundreds of annotations a call. ``ms``, the step's total over the
        caller's loop, goes where a ``phase`` of that name would put it
        (the call's accumulator; outside one, the histogram). With
        ``start_ns`` and a context active ONE span is linked, laid from
        there: the caller lays a loop's steps end to end from the loop's
        start, so they partition their parent as phases do. Without it no
        span: a parent step whose children carry the spans. No
        annotation: the loop runs under one of the caller's."""
        st = _state()
        acc = st.steps
        if acc is not None and name in acc:
            acc[name] += ms
        else:
            metrics.observe(name, ms)
        tc = st.trace
        if tc is not None and start_ns:
            span = _make_span(name, _open_span(tc), ms, meta)
            span["start_ns"] = start_ns
            span["end_ns"] = start_ns + int(ms * 1e6)
            tc._link(span)


# --- the interpreter's collector ----------------------------------------------
# `jvm.gc.collectors.{young,old}` of GET /_nodes/stats (old = generation 2;
# the keys an Elasticsearch dashboard already reads). A full collection
# walks everything a 1.1M-document shard keeps on the host, with every
# thread stopped: it is also an annotation `es.gc`.

_GC_NS = [0, 0]        # young, old: collections run under the GIL
_GC_COUNT = [0, 0]
_GC_T0 = [0]
_GC_ANN: List[Any] = []


def _on_gc(when: str, info: dict) -> None:
    if when == "start":
        if info["generation"] == 2:
            ann = annotation("gc", generation=2)
            ann.__enter__()
            _GC_ANN.append(ann)
        _GC_T0[0] = _clock()
        return
    dt = _clock() - _GC_T0[0]
    old = info["generation"] == 2
    _GC_NS[old] += dt
    _GC_COUNT[old] += 1
    if old and _GC_ANN:
        _GC_ANN.pop().__exit__(None, None, None)


def install_gc_hook() -> None:
    """Idempotent; the node process calls it at start."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def gc_stats() -> dict:
    """``jvm.gc.collectors`` of GET /_nodes/stats."""
    return {gen: {"collection_count": _GC_COUNT[i],
                  "collection_time_in_millis": _GC_NS[i] // 1_000_000}
            for i, gen in enumerate(("young", "old"))}


def child_from_wire(wire: Optional[dict], node: str = "",
                    kind: str = "shard") -> Optional[TraceContext]:
    """Data-node side of RPC propagation: rebuild a local context sharing
    the coordinator's trace id (or None when the request is untraced)."""
    if not wire:
        return None
    return TraceContext(trace_id=wire.get("trace_id"),
                        opaque_id=wire.get("opaque_id"),
                        node=node, kind=kind)


# --- sampling ---------------------------------------------------------------

_SAMPLE_LOCK = threading.Lock()
_SAMPLE = {"n": 0}  # guarded by: _SAMPLE_LOCK


def should_sample() -> bool:
    """Every-Nth sampling per ES_TPU_TRACE_SAMPLE (0 = off)."""
    every = knob("ES_TPU_TRACE_SAMPLE")
    if every <= 0:
        return False
    with _SAMPLE_LOCK:
        _SAMPLE["n"] += 1
        return _SAMPLE["n"] % every == 0


# --- flight-recorder ring ---------------------------------------------------

_RING_LOCK = threading.Lock()
_TRACES: deque = deque()  # guarded by: _RING_LOCK


def record_trace(tc: TraceContext) -> None:
    cap = max(1, knob("ES_TPU_TRACE_RING"))
    with _RING_LOCK:
        _TRACES.append(tc.to_dict())
        while len(_TRACES) > cap:
            _TRACES.popleft()


def recent_traces() -> List[dict]:
    with _RING_LOCK:
        return list(_TRACES)


# --- slowlog ----------------------------------------------------------------

_SLOWLOG_LOCK = threading.Lock()
_SLOWLOG: deque = deque()  # guarded by: _SLOWLOG_LOCK
_SLOWLOG_COUNTS = {"query_warn": 0, "query_info": 0,
                   "fetch_warn": 0, "fetch_info": 0}  # guarded by: _SLOWLOG_LOCK

_SLOWLOG_SETTING = "index.search.slowlog.threshold.{phase}.{level}"
_LEVELS = ("warn", "info")  # warn checked first: highest threshold wins


def slowlog_thresholds(settings) -> Dict[str, Dict[str, Optional[float]]]:
    """Effective per-phase thresholds (ms) from an index Settings object —
    {'query': {'warn': ms|None, 'info': ms|None}, 'fetch': {...}}.
    Unset or '-1' means disabled, matching the reference semantics."""
    out: Dict[str, Dict[str, Optional[float]]] = {}
    for phase in ("query", "fetch"):
        per: Dict[str, Optional[float]] = {}
        for level in _LEVELS:
            raw = settings.raw(_SLOWLOG_SETTING.format(phase=phase, level=level))
            ms: Optional[float] = None
            if raw not in (None, "", "-1", -1):
                try:
                    ms = float(raw)  # bare numbers are ms (reference convention)
                except (TypeError, ValueError):
                    try:
                        ms = parse_time_value(str(raw)) * 1000.0
                    except Exception:  # unparseable -> disabled, not fatal
                        ms = None
                if ms is not None and ms < 0:
                    ms = None
            per[level] = ms
        out[phase] = per
    return out


def slowlog_configured(settings) -> bool:
    th = slowlog_thresholds(settings)
    return any(v is not None for per in th.values() for v in per.values())


def slowlog_check(phase: str, took_ms: float,
                  thresholds: Dict[str, Optional[float]]) -> Optional[str]:
    """Highest matching level for one phase timing, or None."""
    for level in _LEVELS:
        ms = thresholds.get(level)
        if ms is not None and took_ms >= ms:
            return level
    return None


def slowlog_record(phase: str, level: str, index: str, took_ms: float,
                   source: Any = None, node: str = "", shard: Any = None,
                   tc: Optional[TraceContext] = None) -> None:
    entry = {
        "phase": phase,
        "level": level,
        "index": index,
        "shard": shard,
        "node": node,
        "took_ms": round(took_ms, 3),
        "source": source,
        "trace_id": tc.trace_id if tc is not None else None,
        "opaque_id": tc.opaque_id if tc is not None else None,
        "phases": tc.phase_totals() if tc is not None else {},
    }
    cap = max(1, knob("ES_TPU_SLOWLOG_RING"))
    key = f"{phase}_{level}"
    with _SLOWLOG_LOCK:
        if key in _SLOWLOG_COUNTS:
            _SLOWLOG_COUNTS[key] += 1
        _SLOWLOG.append(entry)
        while len(_SLOWLOG) > cap:
            _SLOWLOG.popleft()


def slowlog_entries() -> List[dict]:
    with _SLOWLOG_LOCK:
        return list(_SLOWLOG)


def slowlog_stats() -> dict:
    with _SLOWLOG_LOCK:
        return {**_SLOWLOG_COUNTS, "ring_entries": len(_SLOWLOG)}


def reset_for_tests() -> None:
    with _RING_LOCK:
        _TRACES.clear()
    with _SLOWLOG_LOCK:
        _SLOWLOG.clear()
        for k in _SLOWLOG_COUNTS:
            _SLOWLOG_COUNTS[k] = 0
    with _SAMPLE_LOCK:
        _SAMPLE["n"] = 0
    _tls.st = None
    _GC_NS[:] = [0, 0]
    _GC_COUNT[:] = [0, 0]
