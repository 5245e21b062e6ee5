"""The five tpulint rules.

Each rule is a singleton with `name`, `summary` (one line, used by
--list-rules and the README table) and `check(ctx, project)` yielding
`Finding`s. Rules are pure AST + comment-directive analysis: nothing here
imports elasticsearch_tpu, so the linter runs on a broken tree too.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from tools.tpulint.core import (
    FileContext, Finding, Project, dotted_name, dotted_tail, is_jit_decorated,
    is_jitlike_call, JIT_TAILS,
)

# ---------------------------------------------------------------------------
# TPU001 — unguarded device dispatch
# ---------------------------------------------------------------------------


class UnguardedDispatchRule:
    """Every device dispatch must go through the PR 5/6 fault grammar:
    wrapped in `faults.device_dispatch`/`device_errors`, or preceded by a
    `fault_point` in the same function — otherwise an injected or organic
    device fault at that site escapes the containment ladder."""

    name = "TPU001"
    summary = ("jit / shard_map / device_put call sites in search/serving.py, "
               "parallel/*, ops/* must sit inside a named common/faults.py "
               "fault site")

    FAULT_WRAPPERS = frozenset({"device_dispatch", "device_errors"})
    FAULT_POINTS = frozenset({"fault_point", "transport_fault_point"})
    DIRECT_TAILS = frozenset({"device_put"})

    @staticmethod
    def applies(path: str) -> bool:
        return (path.endswith("search/serving.py")
                or "/parallel/" in path or "/ops/" in path)

    def check(self, ctx: FileContext, project: Project) -> List[Finding]:
        if not self.applies(ctx.path):
            return []
        alias_to_module: Dict[str, str] = {}
        imported_from: Dict[str, Tuple[str, str]] = {}
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    alias_to_module[a.asname or a.name.split(".")[0]] = a.name
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    bound = a.asname or a.name
                    imported_from[bound] = (node.module, a.name)
                    alias_to_module.setdefault(bound,
                                               f"{node.module}.{a.name}")
        local_jitted = project.jitted.get(
            Project._module_name(ctx.path), set())
        # self-attributes bound to jitted callables, per class
        class_jitted: Dict[ast.ClassDef, Set[str]] = {}
        for cls in [n for n in ast.walk(ctx.tree)
                    if isinstance(n, ast.ClassDef)]:
            attrs: Set[str] = set()
            for node in ast.walk(cls):
                if isinstance(node, ast.Assign) and is_jitlike_call(node.value):
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Attribute) \
                                and isinstance(tgt.value, ast.Name) \
                                and tgt.value.id == "self":
                            attrs.add(tgt.attr)
            class_jitted[cls] = attrs

        def dispatch_name(call: ast.Call) -> Optional[str]:
            func = call.func
            tail = dotted_tail(func)
            if tail in self.DIRECT_TAILS:
                return dotted_name(func) or tail
            # jax.jit(f)(x): immediate dispatch of a freshly-jitted callable
            if isinstance(func, ast.Call) \
                    and dotted_tail(func.func) in JIT_TAILS:
                return "jit(...)"
            if isinstance(func, ast.Name):
                if func.id in local_jitted:
                    return func.id
                if func.id in imported_from:
                    mod, orig = imported_from[func.id]
                    if orig in project.jitted.get(mod, ()):
                        return f"{mod}.{orig}"
            if isinstance(func, ast.Attribute) \
                    and isinstance(func.value, ast.Name):
                base = func.value.id
                if base == "self":
                    cls = ctx.enclosing_class(call)
                    if cls is not None and func.attr in class_jitted.get(
                            cls, ()):
                        return f"self.{func.attr}"
                mod = alias_to_module.get(base)
                if mod and func.attr in project.jitted.get(mod, ()):
                    return f"{mod}.{func.attr}"
            return None

        def guarded(call: ast.Call) -> bool:
            for anc in ctx.ancestors(call):
                if isinstance(anc, ast.With):
                    for item in anc.items:
                        cexpr = item.context_expr
                        if isinstance(cexpr, ast.Call) and dotted_tail(
                                cexpr.func) in self.FAULT_WRAPPERS:
                            return True
                if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    if is_jit_decorated(anc):
                        return True        # trace-time call, not a dispatch
                    for n in ast.walk(anc):
                        if isinstance(n, ast.Call) \
                                and dotted_tail(n.func) in self.FAULT_POINTS \
                                and n.lineno <= call.lineno:
                            return True    # fault_point guards what follows
            return False

        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dispatch_name(node)
            if name is None or guarded(node):
                continue
            f = ctx.finding(
                self.name, node,
                f"device dispatch `{name}` outside a named fault site — wrap "
                f"in faults.device_dispatch()/device_errors() or precede "
                f"with faults.fault_point() so the PR 5/6 fault grammar "
                f"stays exhaustive")
            if f:
                out.append(f)
        return out


# ---------------------------------------------------------------------------
# TPU002 — guarded-by: annotated shared state mutated outside its lock
# ---------------------------------------------------------------------------

_MUTATORS = frozenset({
    "append", "appendleft", "extend", "add", "update", "insert",
    "pop", "popleft", "popitem", "remove", "discard", "clear", "setdefault",
})


class GuardedByRule:
    """Attributes / module globals annotated `# guarded by: <lock>` on
    their defining assignment may only be mutated inside `with <lock>:`
    (or in a function marked `# tpulint: holds=<lock>`, or `__init__`,
    where the object is not yet shared)."""

    name = "TPU002"
    summary = ("state annotated `# guarded by: <lock>` may only be mutated "
               "under `with <lock>:` (helpers may declare "
               "`# tpulint: holds=<lock>`)")

    def check(self, ctx: FileContext, project: Project) -> List[Finding]:
        if not ctx.guard_notes:
            return []
        # (scope, name) -> lock; scope is the ClassDef for attributes,
        # None for module globals
        guards: Dict[Tuple[Optional[ast.ClassDef], str], str] = {}

        def note_for(node: ast.AST) -> Optional[str]:
            # the annotation may sit on any physical line of a multi-line
            # assignment (typically the last)
            end = getattr(node, "end_lineno", node.lineno) or node.lineno
            for ln in range(node.lineno, end + 1):
                lock = ctx.guard_notes.get(ln)
                if lock is not None:
                    return lock
            return None

        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.Assign, ast.AnnAssign)):
                continue
            lock = note_for(node)
            if lock is None:
                continue
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            cls = ctx.enclosing_class(node)
            for tgt in targets:
                if isinstance(tgt, ast.Attribute) \
                        and isinstance(tgt.value, ast.Name) \
                        and tgt.value.id == "self" and cls is not None:
                    guards[(cls, tgt.attr)] = lock
                elif isinstance(tgt, ast.Name):
                    guards[(cls, tgt.id)] = lock
        if not guards:
            return []

        def base_target(expr: ast.AST) -> Optional[Tuple[str, str]]:
            t = expr
            while isinstance(t, ast.Subscript):
                t = t.value
            if isinstance(t, ast.Attribute) and isinstance(t.value, ast.Name) \
                    and t.value.id == "self":
                return ("self", t.attr)
            if isinstance(t, ast.Name):
                return ("bare", t.id)
            return None

        def lock_for(node: ast.AST, kind: str, name: str) -> Optional[str]:
            if kind == "self":
                cls = ctx.enclosing_class(node)
                return guards.get((cls, name)) if cls is not None else None
            # bare name: module global, or a class-body attribute alias
            cls = ctx.enclosing_class(node)
            return guards.get((cls, name)) or guards.get((None, name))

        def is_guarded(node: ast.AST, lock: str) -> bool:
            fn = ctx.enclosing_function(node)
            if fn is None:
                return True                 # import-time: single-threaded
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and fn.name == "__init__":
                return True                 # not yet shared
            for anc in ctx.ancestors(node):
                if isinstance(anc, ast.With):
                    for item in anc.items:
                        if dotted_tail(item.context_expr) == lock:
                            return True
                if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                        and ctx.held_lock(anc) == lock:
                    return True
            return False

        def emit(node: ast.AST, name: str, lock: str,
                 out: List[Finding]) -> None:
            f = ctx.finding(
                self.name, node,
                f"`{name}` is annotated `# guarded by: {lock}` but is "
                f"mutated outside `with {lock}:` (mark the enclosing helper "
                f"`# tpulint: holds={lock}` if the caller holds it)")
            if f:
                out.append(f)

        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            mutated: List[ast.AST] = []
            if isinstance(node, ast.Assign):
                if note_for(node) is not None:
                    continue                # the annotated definition itself
                for tgt in node.targets:
                    mutated.extend(tgt.elts if isinstance(
                        tgt, (ast.Tuple, ast.List)) else [tgt])
            elif isinstance(node, ast.AugAssign):
                mutated.append(node.target)
            elif isinstance(node, ast.Delete):
                mutated.extend(node.targets)
            elif isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _MUTATORS:
                mutated.append(node.func.value)
            for tgt in mutated:
                hit = base_target(tgt)
                if hit is None:
                    continue
                kind, name = hit
                lock = lock_for(node, kind, name)
                if lock is not None and not is_guarded(node, lock):
                    emit(node, name, lock, out)
        return out


# ---------------------------------------------------------------------------
# TPU003 — ES_TPU_* knobs must go through the common/settings.py registry
# ---------------------------------------------------------------------------


class KnobRegistryRule:
    """`os.environ` reads of ES_TPU_* outside common/settings.py bypass the
    typed knob registry (no declared type/default/doc, invisible to the
    `tpu_settings` stats section); `knob()` calls must name a declared
    knob, which also catches misspellings statically."""

    name = "TPU003"
    summary = ("every ES_TPU_* env read goes through the typed knob registry "
               "in common/settings.py; knob() names must be declared there")

    ENV_GETTERS = frozenset({"os.environ.get", "os.getenv"})
    KNOB_FUNCS = frozenset({"knob"})

    @staticmethod
    def _literal_prefix(node: ast.AST) -> Optional[str]:
        """String-ish first chars of a Constant or f-string, else None."""
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return node.value
        if isinstance(node, ast.JoinedStr) and node.values \
                and isinstance(node.values[0], ast.Constant) \
                and isinstance(node.values[0].value, str):
            return node.values[0].value
        return None

    def check(self, ctx: FileContext, project: Project) -> List[Finding]:
        if ctx.path.endswith("common/settings.py"):
            return []
        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            key: Optional[str] = None
            if isinstance(node, ast.Call):
                fname = dotted_name(node.func)
                if fname in self.ENV_GETTERS and node.args:
                    key = self._literal_prefix(node.args[0])
                elif dotted_tail(node.func) in self.KNOB_FUNCS and node.args:
                    lit = node.args[0]
                    if isinstance(lit, ast.Constant) \
                            and isinstance(lit.value, str) \
                            and lit.value.startswith("ES_TPU") \
                            and lit.value not in project.knob_names:
                        f = ctx.finding(
                            self.name, node,
                            f"knob `{lit.value}` is not declared in the "
                            f"common/settings.py registry (undeclared or "
                            f"misspelled — declare_knob it)")
                        if f:
                            out.append(f)
                    continue
            elif isinstance(node, ast.Subscript) \
                    and dotted_name(node.value) == "os.environ":
                key = self._literal_prefix(node.slice)
            if key is not None and key.startswith("ES_TPU"):
                f = ctx.finding(
                    self.name, node,
                    f"direct os.environ read of `{key}…` — use "
                    f"common.settings.knob() so the knob is typed, "
                    f"documented and visible in `tpu_settings`")
                if f:
                    out.append(f)
        return out


# ---------------------------------------------------------------------------
# TPU004 — dtype drift in the narrow-dtype kernels
# ---------------------------------------------------------------------------

_NARROW_INT = frozenset({"int8", "uint8", "int4", "uint4"})
_NARROW_FLOAT = frozenset({"bfloat16", "float16"})
_ARITH = (ast.Add, ast.Sub, ast.Mult, ast.Div, ast.Pow, ast.Mod,
          ast.FloorDiv)


class DtypeDriftRule:
    """In the int8/bf16 kernels, arithmetic mixing a bare Python literal
    with a narrow-dtype array relies on implicit promotion — exactly what
    silently breaks the bit-identity certificate when jax's promotion
    rules (or a dtype flag) change. Promotions must be explicit astype."""

    name = "TPU004"
    summary = ("in parallel/kernels.py, ops/scoring.py, ops/knn.py: no "
               "arithmetic mixing Python literals with int8/bf16 arrays "
               "without an explicit astype")

    FILES = ("parallel/kernels.py", "ops/scoring.py", "ops/knn.py")

    @classmethod
    def applies(cls, path: str) -> bool:
        return path.endswith(cls.FILES)

    @staticmethod
    def _narrow_kind(expr: ast.AST) -> Optional[str]:
        """'int' / 'float' when expr produces a narrow-dtype array —
        looks for .astype(D)/.view(D)/dtype=D with D in the narrow sets."""
        for node in ast.walk(expr):
            if not isinstance(node, ast.Call):
                continue
            cands: List[ast.AST] = []
            if dotted_tail(node.func) in ("astype", "view") and node.args:
                cands.append(node.args[0])
            cands.extend(kw.value for kw in node.keywords
                         if kw.arg == "dtype")
            for c in cands:
                tail = dotted_tail(c) or (
                    c.value if isinstance(c, ast.Constant)
                    and isinstance(c.value, str) else None)
                if tail in _NARROW_INT:
                    return "int"
                if tail in _NARROW_FLOAT:
                    return "float"
        return None

    def check(self, ctx: FileContext, project: Project) -> List[Finding]:
        if not self.applies(ctx.path):
            return []
        # narrow locals per enclosing function (None = module scope)
        narrow: Dict[Optional[ast.AST], Dict[str, str]] = {}
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Assign):
                continue
            kind = self._narrow_kind(node.value)
            if kind is None:
                continue
            scope = ctx.enclosing_function(node)
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    narrow.setdefault(scope, {})[tgt.id] = kind

        def kind_of(name_node: ast.AST, at: ast.AST) -> Optional[str]:
            if not isinstance(name_node, ast.Name):
                return None
            fn = ctx.enclosing_function(at)
            while True:
                k = narrow.get(fn, {}).get(name_node.id)
                if k is not None:
                    return k
                if fn is None:
                    return None
                fn = ctx.enclosing_function(fn)

        def num_literal(node: ast.AST) -> Optional[type]:
            if isinstance(node, ast.Constant) \
                    and isinstance(node.value, (int, float)) \
                    and not isinstance(node.value, bool):
                return type(node.value)
            # -0.5 parses as UnaryOp(USub, Constant)
            if isinstance(node, ast.UnaryOp) \
                    and isinstance(node.op, (ast.USub, ast.UAdd)):
                return num_literal(node.operand)
            return None

        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.BinOp) \
                    or not isinstance(node.op, _ARITH):
                continue
            for arr, lit in ((node.left, node.right),
                             (node.right, node.left)):
                kind = kind_of(arr, node)
                if kind is None:
                    continue
                lit_t = num_literal(lit)
                if lit_t is None:
                    continue
                bad = (kind == "int" and lit_t is float) \
                    or isinstance(node.op, ast.Div)
                if not bad:
                    continue
                f = ctx.finding(
                    self.name, node,
                    f"arithmetic mixes narrow {kind} array "
                    f"`{arr.id}` with a Python {lit_t.__name__} literal — "
                    f"implicit promotion (f32/f64) breaks the bit-identity "
                    f"certificate; make the intent explicit with .astype()")
                if f:
                    out.append(f)
                break
        return out


# ---------------------------------------------------------------------------
# TPU005 — counters incremented but missing from the stats() surface
# ---------------------------------------------------------------------------


class CounterHygieneRule:
    """A class that exposes `stats()` must surface every counter it
    increments — `_nodes/stats` silently dropping a metric is how
    regressions hide (the counter looks alive in the code, but no
    dashboard or differential test can see it move).

    Same hygiene for the flight-recorder histograms: a literal
    ``metrics.observe("name", …)`` site — and a literal
    ``tracing.phase("name", …)`` / ``tracing.record("name", …)`` /
    ``tracing.steps.add("name", …)`` site, which observes under the span's
    own name — must name a histogram
    declared in common/metrics.py — declared histograms all surface through
    ``search_latency_stats()``, so an undeclared name is a metric that can
    never reach `_nodes/stats` (and raises UndeclaredHistogramError the
    first time the line runs). Dynamically composed names (f-strings,
    ``observe_if_declared``) this rule deliberately ignores.

    And for telemetry gauges (PR 12): a module that calls
    ``declare_gauge("section.tail", …)`` outside the central registry
    (common/metrics.py, whose declarations surface via the Prometheus
    renderer itself) owns that gauge, so the gauge's dotted tail must
    appear as a string in some ``*stats()`` function in the SAME file —
    otherwise the gauge scrapes but never shows in the owning module's
    `_nodes/stats` section."""

    name = "TPU005"
    summary = ("counters a stats()-bearing class increments (`self.x += …`) "
               "must appear in its stats() surface; literal observe(...) / "
               "tracing.phase(...) / tracing.record(...) sites must name a "
               "histogram declared in common/metrics.py; "
               "declare_gauge names outside the registry must surface in a "
               "*stats() function in the declaring file")

    @staticmethod
    def _observes(func: ast.AST) -> Optional[str]:
        """`observe` for any `….observe(` call; `phase` / `record` only as
        `tracing.phase(` / `tracing.record(` (both names are common), and
        `tracing.steps.add(`."""
        tail = dotted_tail(func)
        if tail == "observe":
            return tail
        if not isinstance(func, ast.Attribute):
            return None
        if tail in ("phase", "record") and isinstance(func.value, ast.Name) \
                and func.value.id == "tracing":
            return "tracing." + tail
        if tail == "add" and isinstance(func.value, ast.Attribute) \
                and func.value.attr == "steps" \
                and isinstance(func.value.value, ast.Name) \
                and func.value.value.id == "tracing":
            return "tracing.steps.add"
        return None

    @staticmethod
    def _self_attr(expr: ast.AST) -> Optional[str]:
        t = expr
        while isinstance(t, ast.Subscript):
            t = t.value
        if isinstance(t, ast.Attribute) and isinstance(t.value, ast.Name) \
                and t.value.id == "self":
            return t.attr
        return None

    def check(self, ctx: FileContext, project: Project) -> List[Finding]:
        out: List[Finding] = []
        # histogram registry hygiene (skipped inside the registry itself,
        # and entirely when the lint scope doesn't include metrics.py —
        # fixture snippets must not see every observe() flagged)
        if project.histogram_names \
                and not ctx.path.endswith("common/metrics.py"):
            for node in ast.walk(ctx.tree):
                if isinstance(node, ast.Call) \
                        and self._observes(node.func) \
                        and node.args \
                        and isinstance(node.args[0], ast.Constant) \
                        and isinstance(node.args[0].value, str) \
                        and node.args[0].value not in project.histogram_names:
                    f = ctx.finding(
                        self.name, node,
                        f"{self._observes(node.func)}"
                        f"({node.args[0].value!r}) names a histogram "
                        f"that is not declared in common/metrics.py — it "
                        f"never surfaces in `tpu_search_latency` and raises "
                        f"UndeclaredHistogramError at runtime")
                    if f:
                        out.append(f)
        # gauge-surface hygiene (PR 12): declare_gauge call sites outside
        # the central registry must surface the gauge's dotted tail in a
        # *stats() function in the same file
        if not ctx.path.endswith("common/metrics.py"):
            declared_here = [
                node for node in ast.walk(ctx.tree)
                if isinstance(node, ast.Call)
                and dotted_tail(node.func) == "declare_gauge"
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)]
            if declared_here:
                surfaced: List[str] = []
                for fn in ast.walk(ctx.tree):
                    if isinstance(fn, ast.FunctionDef) \
                            and fn.name.endswith("stats"):
                        for node in ast.walk(fn):
                            if isinstance(node, ast.Constant) \
                                    and isinstance(node.value, str):
                                surfaced.append(node.value)
                for node in declared_here:
                    gname = node.args[0].value
                    tail = gname.rsplit(".", 1)[-1]
                    if any(tail in s for s in surfaced):
                        continue
                    f = ctx.finding(
                        self.name, node,
                        f"declare_gauge({gname!r}) has no matching key in "
                        f"any *stats() function in this file — the gauge "
                        f"scrapes but never surfaces in the owning "
                        f"`_nodes/stats` section")
                    if f:
                        out.append(f)
        for cls in [n for n in ast.walk(ctx.tree)
                    if isinstance(n, ast.ClassDef)]:
            stats_fns = [n for n in cls.body
                         if isinstance(n, ast.FunctionDef)
                         and n.name in ("stats", "flat_stats")]
            if not stats_fns:
                continue
            incremented: Dict[str, ast.AST] = {}
            excluded: Set[str] = set()
            for node in ast.walk(cls):
                if isinstance(node, ast.AugAssign):
                    attr = self._self_attr(node.target)
                    if attr is None:
                        continue
                    if isinstance(node.op, ast.Add):
                        incremented.setdefault(attr, node)
                    else:
                        excluded.add(attr)   # gauges (-=) are not counters
                elif isinstance(node, ast.Assign):
                    fn = ctx.enclosing_function(node)
                    if fn is not None and fn.name == "__init__":
                        continue
                    for tgt in node.targets:
                        attr = self._self_attr(tgt)
                        if attr is not None:
                            excluded.add(attr)   # re-assigned: not monotonic
            if not incremented:
                continue
            surfaced_attrs: Set[str] = set()
            surfaced_strings: List[str] = []
            for sfn in stats_fns:
                for node in ast.walk(sfn):
                    if isinstance(node, ast.Attribute):
                        surfaced_attrs.add(node.attr)
                    elif isinstance(node, ast.Constant) \
                            and isinstance(node.value, str):
                        surfaced_strings.append(node.value)
            for attr, node in sorted(incremented.items()):
                if attr in excluded or attr in surfaced_attrs:
                    continue
                bare = attr.lstrip("_")
                if any(bare and bare in s for s in surfaced_strings):
                    continue
                f = ctx.finding(
                    self.name, node,
                    f"counter `self.{attr}` is incremented but never appears "
                    f"in {cls.name}.stats() — the metric is invisible to "
                    f"`_nodes/stats`")
                if f:
                    out.append(f)
        return out


ALL_RULES = (
    UnguardedDispatchRule(),
    GuardedByRule(),
    KnobRegistryRule(),
    DtypeDriftRule(),
    CounterHygieneRule(),
)

RULE_DOCS = {r.name: r.summary for r in ALL_RULES}
