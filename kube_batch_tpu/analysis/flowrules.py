"""Flow-aware KBT rules (KBT006–KBT010), grounded in the PR 3 device-resident
hot path.  Line-local matching (rules.py, KBT001–005) cannot see these bug
shapes: each rule here consumes the per-module :class:`ModuleContext` the
engine builds (import resolution + symbol table) and, where the bug is a
*sequence* of statements, the intra-procedural def-use walk in dataflow.py.

Rules report (line, col, message) triples; scoping and suppression live in
the engine, exactly like the line-local rules.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from kube_batch_tpu.analysis.dataflow import (
    FlowEvent,
    FlowVisitor,
    ModuleContext,
    call_keyword,
    const_int_tuple,
    walk_function,
)
from kube_batch_tpu.analysis.engine import Rule

# --------------------------------------------------------------------------
# shared jit-detection helpers
# --------------------------------------------------------------------------

_JIT_PATHS = {"jax.jit", "jax.api.jit"}
_PARTIAL_PATHS = {"functools.partial", "functools.partial.partial"}


def _is_jit_expr(node: ast.AST, ctx: ModuleContext) -> Optional[ast.Call]:
    """The ``jax.jit(...)`` call inside ``node``, unwrapping one registry
    wrapper layer (``jitstats.register("n", jax.jit(...))``) and the
    ``functools.partial(jax.jit, ...)`` form.  None when node builds no jit
    wrapper."""
    if not isinstance(node, ast.Call):
        return None
    dotted = ctx.resolve_call(node)
    if dotted in _JIT_PATHS:
        return node
    if dotted in _PARTIAL_PATHS and node.args:
        if ctx.imports.dotted(node.args[0]) in _JIT_PATHS:
            return node
    # one wrapper layer: any call carrying a jax.jit call among its args
    for arg in list(node.args) + [kw.value for kw in node.keywords]:
        if isinstance(arg, ast.Call) and ctx.resolve_call(arg) in _JIT_PATHS:
            return arg
    return None


def _donate_positions(jit_call: ast.Call, ctx: ModuleContext,
                      tree: ast.Module) -> Tuple[int, ...]:
    """donate_argnums of a jax.jit call, resolving a Name argument through
    any single assignment in the module (the resident scatter binds its
    backend-conditional tuple to a local first).  Conditional tuples fold
    may-style — a position that CAN be donated is tracked."""
    kw = call_keyword(jit_call, "donate_argnums")
    if kw is None:
        return ()
    got = const_int_tuple(kw)
    if got is not None:
        return got
    if isinstance(kw, ast.Name):
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == kw.id
                for t in node.targets
            ):
                got = const_int_tuple(node.value)
                if got is not None:
                    return got
    return ()


class _DonationTable:
    """Module symbol table slice for KBT006: which local names are donating
    jitted callables, which zero-arg functions return one, and — ONE call
    level deep through the module's symbol table — which same-module
    helpers donate their own parameters.

    The interprocedural level closes the ROADMAP-standing escape: a helper
    like ``def refresh(dev): return _scatter_fn()(dev, rows, vals)``
    donates its caller's buffer, but only the helper's body carries the
    donating call — a caller reading ``dev`` after ``refresh(dev)`` walked
    clean.  The ``param_donors`` scan marks such helpers so their call
    sites taint arguments exactly like a direct donating call.  One level
    only (a helper calling a helper is out of scope), matching the
    deliberately-bounded depth of the rest of the flow engine."""

    def __init__(self, ctx: ModuleContext):
        self.by_name: Dict[str, Tuple[int, ...]] = {}
        self.factories: Dict[str, Tuple[int, ...]] = {}
        #: helper function name → parameter positions it donates
        self.param_donors: Dict[str, Tuple[int, ...]] = {}
        tree = ctx.tree
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign):
                jit = _is_jit_expr(node.value, ctx)
                if jit is None:
                    continue
                pos = _donate_positions(jit, ctx, tree)
                if not pos:
                    continue
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        self.by_name[t.id] = pos
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    jit = _is_jit_expr(dec, ctx) if isinstance(dec, ast.Call) else None
                    if jit is not None:
                        pos = _donate_positions(jit, ctx, tree)
                        if pos:
                            self.by_name[node.name] = pos
        # factories: functions whose return value is a donating name
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            for sub in ast.walk(node):
                if (isinstance(sub, ast.Return)
                        and isinstance(sub.value, ast.Name)
                        and sub.value.id in self.by_name):
                    self.factories[node.name] = self.by_name[sub.value.id]
        # one-level interprocedural: a function passing its OWN parameter
        # into a donating call at a donated position donates that
        # parameter — including through the factory ``_scatter_fn()(...)``
        # form, which _direct_positions already resolves
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            params = [a.arg for a in node.args.args]
            donated: set = set()
            for sub in ast.walk(node):
                if not isinstance(sub, ast.Call):
                    continue
                for p in self._direct_positions(sub):
                    if (p < len(sub.args)
                            and isinstance(sub.args[p], ast.Name)
                            and sub.args[p].id in params):
                        donated.add(params.index(sub.args[p].id))
            if donated:
                self.param_donors[node.name] = tuple(sorted(donated))

    def _direct_positions(self, call: ast.Call) -> Tuple[int, ...]:
        """Donated positions from the module-level table only (no
        interprocedural step — this is what the one-level scan itself
        consumes, keeping the closure bounded)."""
        f = call.func
        if isinstance(f, ast.Name):
            return self.by_name.get(f.id, ())
        if (isinstance(f, ast.Call) and isinstance(f.func, ast.Name)
                and not f.args):
            return self.factories.get(f.func.id, ())
        return ()

    def call_positions(self, call: ast.Call) -> Tuple[int, ...]:
        """Donated positions of this call site, or () — the direct
        ``scatter(...)`` form, the factory ``_scatter_fn()(...)`` form,
        and same-module helpers that donate their parameters."""
        direct = self._direct_positions(call)
        if direct:
            return direct
        f = call.func
        if isinstance(f, ast.Name):
            return self.param_donors.get(f.id, ())
        return ()


# --------------------------------------------------------------------------
# KBT006 — donated-buffer use after donation
# --------------------------------------------------------------------------


class UseAfterDonationRule(Rule):
    """PR 3 hazard: the resident scatter donates its stale device buffer
    (``donate_argnums``) so XLA writes in place — after the donating call
    the Python binding still *looks* alive, but the buffer is deleted; a
    later read raises (or worse, silently reads garbage on backends that
    alias).  Nothing fails until a real accelerator run.  Tracks
    donate_argnums call sites through the module symbol table (direct
    names, registry-wrapped assigns, factory functions) and flags any read
    of a donated binding that was not rebound first — rebinding to the
    call's result (``dev = scatter(dev, ...)``) is the sanctioned shape."""

    id = "KBT006"
    title = "read of a donated buffer after the donating call"
    scope = ()  # donation is rare; check everywhere it appears

    def check_ctx(self, ctx: ModuleContext):
        table = _DonationTable(ctx)
        if not table.by_name:
            return
        findings: List[Tuple[int, int, str]] = []
        seen: Set[Tuple[int, str]] = set()

        class V(FlowVisitor):
            def on_call(self, ev: FlowEvent, env) -> None:
                call = ev.node
                pos = table.call_positions(call)
                for p in pos:
                    if p < len(call.args) and isinstance(call.args[p], ast.Name):
                        cell = env.get(call.args[p].id)
                        if cell is not None:
                            cell["donated"] = (call.lineno, call.args[p].id)

            def on_load(self, ev: FlowEvent, env) -> None:
                if ev.cell is None or "donated" not in ev.cell:
                    return
                dline, dname = ev.cell["donated"]  # type: ignore[misc]
                key = (ev.node.lineno, ev.name)
                if key in seen:
                    return
                seen.add(key)
                findings.append((
                    ev.node.lineno, ev.node.col_offset,
                    f"`{ev.name}` was donated to the jitted call on line "
                    f"{dline} (donate_argnums) — its buffer no longer "
                    "exists; rebind the name to the call's result before "
                    "any further use",
                ))

        for func in ctx.functions:
            walk_function(func, V())
        yield from findings


# --------------------------------------------------------------------------
# KBT007 — jit retrace hazards
# --------------------------------------------------------------------------


class RetraceHazardRule(Rule):
    """Guards the zero-steady-state-retrace invariant the PR 3 bench proves
    (utils/jitstats counters): a ``jax.jit`` wrapper constructed inside a
    function body gets a fresh cache per call — every cycle recompiles the
    whole solve (the bug parallel/mesh.py's ``_jit_cache`` exists to
    prevent).  Also flags unhashable literals passed in static positions of
    module-known jitted callables (TypeError at runtime, or a per-value
    cache key), shape-derived static args (``len(...)``/``.shape[...]`` —
    per-size specializations; route sizes through the snapshot buckets /
    ``ColumnStore.reserve()``), and jitted functions closing over mutable
    module state (the value is baked at trace time; mutation never
    reaches the compiled code)."""

    id = "KBT007"
    title = "jit retrace hazard"
    scope = ("ops/", "api/", "actions/", "parallel/", "framework/", "cache/")

    MUTABLE_FACTORIES = {"dict", "list", "set", "defaultdict", "deque",
                         "Counter", "OrderedDict"}

    # -- helpers -----------------------------------------------------------
    @staticmethod
    def _body_without_nested_defs(func: ast.AST) -> Iterable[ast.AST]:
        stack = list(func.body)
        while stack:
            node = stack.pop()
            yield node
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda, ast.ClassDef)):
                continue
            stack.extend(ast.iter_child_nodes(node))

    def _memo_names(self, func: ast.AST) -> Set[str]:
        """Names that escape into a memo within this function: stored to a
        subscript/attribute (``_jit_cache[key] = fn``) or declared global
        (the module-global memo the resident scatter uses)."""
        out: Set[str] = set()
        for node in self._body_without_nested_defs(func):
            if isinstance(node, ast.Assign):
                if any(isinstance(t, (ast.Subscript, ast.Attribute))
                       for t in node.targets):
                    if isinstance(node.value, ast.Name):
                        out.add(node.value.id)
            elif isinstance(node, ast.Global):
                out.update(node.names)
        return out

    def _static_positions(self, jit_call: ast.Call) -> Tuple[Tuple[int, ...],
                                                             Tuple[str, ...]]:
        nums = const_int_tuple(call_keyword(jit_call, "static_argnums") or
                               ast.Constant(value=None)) or ()
        names: Tuple[str, ...] = ()
        kw = call_keyword(jit_call, "static_argnames")
        if isinstance(kw, (ast.Tuple, ast.List)):
            names = tuple(e.value for e in kw.elts
                          if isinstance(e, ast.Constant) and isinstance(e.value, str))
        elif isinstance(kw, ast.Constant) and isinstance(kw.value, str):
            names = (kw.value,)
        return nums, names

    @staticmethod
    def _is_lru_cached(func: ast.AST, ctx: ModuleContext) -> bool:
        for dec in func.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            if ctx.imports.dotted(target) in (
                "functools.lru_cache", "functools.cache",
            ):
                return True
        return False

    @staticmethod
    def _unhashable(node: ast.AST) -> str:
        if isinstance(node, (ast.List, ast.ListComp)):
            return "list"
        if isinstance(node, (ast.Dict, ast.DictComp)):
            return "dict"
        if isinstance(node, (ast.Set, ast.SetComp)):
            return "set"
        return ""

    @staticmethod
    def _shape_derived(node: ast.AST) -> bool:
        """len(x) or anything.shape[...] — a per-cycle size reaching a
        static position means one compile per distinct size."""
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id == "len"):
            return True
        for sub in ast.walk(node):
            if isinstance(sub, ast.Attribute) and sub.attr == "shape":
                return True
        return False

    # -- the check ---------------------------------------------------------
    def check_ctx(self, ctx: ModuleContext):
        # (a) jit wrappers built per call inside function bodies
        for func in ctx.functions:
            if self._is_lru_cached(func, ctx):
                continue
            memo = self._memo_names(func)
            for node in self._body_without_nested_defs(func):
                jit: Optional[ast.Call] = None
                bound: Optional[str] = None
                if isinstance(node, ast.Assign):
                    jit = _is_jit_expr(node.value, ctx)
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            bound = t.id
                elif isinstance(node, ast.Expr):
                    jit = _is_jit_expr(node.value, ctx)
                if jit is None:
                    continue
                if bound is not None and bound in memo:
                    continue  # memoized (the mesh _jit_cache pattern)
                yield (jit.lineno, jit.col_offset,
                       "jax.jit wrapper constructed inside a function body "
                       "gets a fresh compile cache per call — every "
                       "invocation retraces; hoist to module level or memo "
                       "it (the parallel/mesh.py _jit_cache pattern)")

        # (b) static-position hazards at call sites of module-known jitted
        # callables
        jitted: Dict[str, Tuple[Tuple[int, ...], Tuple[str, ...]]] = {}
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if isinstance(dec, ast.Call):
                        jit = _is_jit_expr(dec, ctx)
                        if jit is not None:
                            jitted[node.name] = self._static_positions(jit)
                    elif ctx.imports.dotted(dec) in _JIT_PATHS:
                        jitted[node.name] = ((), ())  # bare @jax.jit
            elif isinstance(node, ast.Assign):
                jit = _is_jit_expr(node.value, ctx)
                if jit is not None:
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            jitted[t.id] = self._static_positions(jit)
        for node in ast.walk(ctx.tree):
            if not jitted:
                break
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id in jitted):
                continue
            nums, names = jitted[node.func.id]
            static_args = [
                (node.args[p], f"position {p}") for p in nums
                if p < len(node.args)
            ] + [
                (kw.value, f"`{kw.arg}`") for kw in node.keywords
                if kw.arg in names
            ]
            for arg, where in static_args:
                kind = self._unhashable(arg)
                if kind:
                    yield (arg.lineno, arg.col_offset,
                           f"unhashable {kind} literal passed in static "
                           f"{where} of jitted `{node.func.id}` — jit cache "
                           "keys must hash; pass a tuple/NamedTuple")
                elif self._shape_derived(arg):
                    yield (arg.lineno, arg.col_offset,
                           f"shape-derived value in static {where} of "
                           f"jitted `{node.func.id}` compiles once per "
                           "distinct size; route sizes through the "
                           "snapshot shape buckets (ColumnStore.reserve)")

        # (c) jitted functions closing over mutable module state
        mutable_globals = {
            name for name, value in ctx.module_assigns.items()
            if isinstance(value, (ast.List, ast.Dict, ast.Set, ast.ListComp,
                                  ast.DictComp, ast.SetComp))
            or (isinstance(value, ast.Call) and isinstance(value.func, ast.Name)
                and value.func.id in self.MUTABLE_FACTORIES)
        }
        if not mutable_globals:
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not any(
                (isinstance(dec, ast.Call) and _is_jit_expr(dec, ctx))
                or ctx.imports.dotted(dec) in _JIT_PATHS
                for dec in node.decorator_list
            ):
                continue
            params = {a.arg for a in node.args.args}
            for sub in ast.walk(node):
                if (isinstance(sub, ast.Name)
                        and isinstance(sub.ctx, ast.Load)
                        and sub.id in mutable_globals
                        and sub.id not in params):
                    yield (sub.lineno, sub.col_offset,
                           f"jitted `{node.name}` reads mutable module "
                           f"state `{sub.id}` — the value is baked in at "
                           "trace time and later mutation never reaches "
                           "the compiled code (silent staleness, not a "
                           "retrace)")


# --------------------------------------------------------------------------
# KBT008 — fail-open seam probes in the k8s layer
# --------------------------------------------------------------------------


class FailOpenSeamProbeRule(Rule):
    """ROADMAP follow-on to KBT004: the translate/watch layer probed its
    volume-binder seam with 3-arg ``getattr(binder, "add_pv", lambda..)`` —
    a binder missing the method silently dropped every PV event, the exact
    shape of the round-5 PV fail-open but one layer up.  Now that the seam
    surface is stable (cache/interface.py Protocols + explicit no-op
    fakes), a defaulted getattr probe in k8s/ is a policy decision to fail
    open and must be written down or replaced with a declared method.
    Dispatch-table ``.get()`` probes whose miss silently drops an event are
    the same bug through a dict."""

    id = "KBT008"
    title = "fail-open seam probe (defaulted getattr / dispatch-table get)"
    scope = ("k8s/",)

    DISPATCH_NAMES = ("handlers", "registry", "builders", "dispatch", "hooks")

    def check_ctx(self, ctx: ModuleContext):
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if (isinstance(f, ast.Name) and f.id == "getattr"
                    and len(node.args) == 3):
                default = node.args[2]
                attr = node.args[1]
                attr_txt = (
                    repr(attr.value) if isinstance(attr, ast.Constant) else "?"
                )
                if (isinstance(default, ast.Constant) and default.value is None) \
                        or isinstance(default, ast.Lambda):
                    yield (node.lineno, node.col_offset,
                           f"3-arg getattr probe of {attr_txt} fails open "
                           "when the seam object lacks it (events silently "
                           "dropped); declare the method on the interface "
                           "Protocol with an explicit no-op on fakes, or "
                           "annotate why silent absence is sound")
            elif (isinstance(f, ast.Attribute) and f.attr == "get"
                    and isinstance(f.value, ast.Name)
                    and f.value.id.lower() in self.DISPATCH_NAMES):
                default = node.args[1] if len(node.args) > 1 else None
                if default is None or (
                    isinstance(default, ast.Constant) and default.value is None
                ):
                    yield (node.lineno, node.col_offset,
                           f"dispatch-table `{f.value.id}.get(...)` miss "
                           "returns None and silently drops the event; "
                           "fail closed (raise/log at the seam) or "
                           "annotate the open default")


# --------------------------------------------------------------------------
# KBT009 — telemetry clock outside metrics-feeding expressions
# --------------------------------------------------------------------------

_TELEMETRY_PATHS = {
    "kube_batch_tpu.utils.telemetry.perf_counter",
    "kube_batch_tpu.utils.telemetry",
}


class TelemetryMisuseRule(Rule):
    """ROADMAP follow-on to KBT001: ``telemetry.perf_counter`` is the ONE
    sanctioned wall-clock read in the clock-seamed paths, sanctioned
    precisely because it only feeds latency metrics.  A telemetry value
    reaching *control flow* (a comparison, a loop/if test, a sleep arg)
    smuggles real wall-clock back into scheduling decisions — the exact
    determinism break KBT001 exists to stop, laundered through the
    telemetry seam.  Flow-tracked: bindings are tainted, aliases follow,
    and a binding that is never read at all is a dead wall-clock read."""

    id = "KBT009"
    title = "telemetry clock value outside metrics-feeding expressions"
    scope = ("scheduler.py", "actions/", "cache/", "sim/", "framework/")

    @staticmethod
    def _is_perf_counter(call: ast.Call, ctx: ModuleContext) -> bool:
        dotted = ctx.resolve_call(call)
        if dotted in _TELEMETRY_PATHS or dotted.endswith(
            ".telemetry.perf_counter"
        ):
            return True
        # `from ..utils.telemetry import perf_counter` form
        return dotted.endswith("utils.telemetry.perf_counter")

    def check_ctx(self, ctx: ModuleContext):
        rule = self
        findings: List[Tuple[int, int, str]] = []
        seen: Set[int] = set()

        def flag(node: ast.AST, msg: str) -> None:
            if node.lineno in seen:
                return
            seen.add(node.lineno)
            findings.append((node.lineno, node.col_offset, msg))

        class V(FlowVisitor):
            def __init__(self) -> None:
                # dead-read tracking is keyed by BIND SITE and marked by
                # NAME, not by cell identity: branch joins replace cells
                # with union copies and the two-pass loop walk rebinds, so
                # a cell-held counter misses legitimate post-join /
                # loop-carried reads (review finding, PR 4)
                self.bind_nodes: Dict[int, ast.AST] = {}   # id(node) → node
                self.bind_used: Dict[int, bool] = {}
                self.binds_by_name: Dict[str, List[int]] = {}

            def on_call(self, ev: FlowEvent, env) -> None:
                call = ev.node
                if not rule._is_perf_counter(call, ctx):
                    return
                if "compare" in ev.where or "test" in ev.where:
                    flag(call,
                         "telemetry.perf_counter() used directly in control "
                         "flow — pacing/timeout decisions belong to the "
                         "injected clock (Scheduler.clock / sim "
                         "VirtualClock); the telemetry seam is for latency "
                         "metrics only")

            def on_bind(self, ev: FlowEvent, env, value) -> None:
                if (isinstance(value, ast.Call)
                        and rule._is_perf_counter(value, ctx)
                        and ev.cell is not None):
                    ev.cell["telemetry"] = value.lineno
                    key = id(ev.node)
                    self.bind_nodes[key] = ev.node
                    self.bind_used.setdefault(key, False)
                    self.binds_by_name.setdefault(ev.name, []).append(key)

            def on_load(self, ev: FlowEvent, env) -> None:
                for key in self.binds_by_name.get(ev.name, ()):
                    self.bind_used[key] = True
                cell = ev.cell
                if cell is None or "telemetry" not in cell:
                    return
                if "compare" in ev.where or "test" in ev.where:
                    flag(ev.node,
                         f"telemetry clock value `{ev.name}` reaches a "
                         "comparison/branch — wall clock is steering "
                         "scheduling control flow; use the injected clock "
                         "for pacing, telemetry for metrics spans only")

        for func in ctx.functions:
            v = V()
            walk_function(func, v)
            for key, used in v.bind_used.items():
                if not used:
                    flag(v.bind_nodes[key],
                         "telemetry.perf_counter() bound but never read — "
                         "a dead wall-clock read in a clock-seamed path; "
                         "delete it or feed it to a metrics expression")
        yield from findings


# --------------------------------------------------------------------------
# KBT010 — host-device sync on resident values in the action layer
# --------------------------------------------------------------------------

#: calls whose results live on device: THE call of a program looked up in
#: parallel/mesh.py's table (every dispatch site's shape: solves, the
#: fit-error histograms, the what-if probe, the enqueue gate — device
#: values until the site's one sanctioned readback), the one-device
#: programs of ops/ called directly, and the resident swap
_DEVICE_SOURCES = {
    "kube_batch_tpu.parallel.mesh.call",
    "kube_batch_tpu.ops.assignment.allocate_solve",
    "kube_batch_tpu.ops.assignment.allocate_topk_solve",
    "kube_batch_tpu.ops.assignment.warm_allocate_solve",
    "kube_batch_tpu.ops.assignment.failure_histogram_solve",
    "kube_batch_tpu.ops.assignment.failure_histogram_bucket_solve",
    "kube_batch_tpu.ops.eviction.evict_solve",
    "kube_batch_tpu.ops.probe.probe_solve",
    "kube_batch_tpu.api.columns.resident_snap",
    "kube_batch_tpu.ops.admission.enqueue_gate_solve",
    "jax.device_put",
}
#: local-name fallbacks for intra-module dispatch helpers: direct calls
#: (`..._solve(...)`) and the jitted-fn factory form the resident scatters
#: use (`_swap_scatter_fn()(devs, ...)`, `_mesh_shard_scatter_fn(mesh)(devs, ...)`)
_DEVICE_SOURCE_SUFFIXES = ("_solve", "solve_dispatch")
_DEVICE_FACTORY_SUFFIXES = ("_scatter_fn", "_gate_fn")


class ResidentSyncRule(Rule):
    """Guards the PR 3 cycle budget at its weakest point: the action layer
    holds BOTH host-backed snapshots (cheap numpy reads) and device-resident
    solve results (each read = a blocking transfer).  KBT005 can't tell
    them apart — this rule can: solve dispatches and resident swaps taint
    their results "device", aliases follow, and a ``np.asarray``/
    ``.item()``/``jax.device_get``/``float()`` on a tainted value is a
    host-device sync.  The sanctioned choke points (the allocate action's
    ONE blocking ``device_get`` and the post-replay histogram readback)
    carry ``# kbt: allow[KBT010]`` annotations — everything else is a new
    stall on the <1s/50k-pod path."""

    id = "KBT010"
    title = "host-device sync on a device-resident value"
    # serve/ joined the scope with the query plane (PR 8): probe results
    # are device-resident until the micro-batcher's one sanctioned
    # per-window readback (serve/plane.py carries the allow annotation)
    scope = ("actions/", "api/resident.py", "serve/")

    SYNC_ATTRS = {"item", "tolist", "block_until_ready"}

    @staticmethod
    def _is_device_source(call: ast.Call, ctx: ModuleContext) -> bool:
        dotted = ctx.resolve_call(call)
        if dotted in _DEVICE_SOURCES:
            return True
        f = call.func
        if isinstance(f, ast.Name):
            return f.id.endswith(_DEVICE_SOURCE_SUFFIXES) or f.id == "resident_snap"
        # the factory form: `_scatter_fn()(dev, ...)` / `_mesh_shard_
        # scatter_fn(mesh)(dev, ...)` — the inner call returns a jitted
        # device fn, so the outer call's result is device-resident
        if (isinstance(f, ast.Call) and isinstance(f.func, ast.Name)
                and f.func.id.endswith(_DEVICE_FACTORY_SUFFIXES)):
            return True
        return False

    @staticmethod
    def _base_name(node: ast.AST) -> str:
        while isinstance(node, (ast.Attribute, ast.Subscript)):
            node = node.value
        if isinstance(node, ast.Name):
            return node.id
        return ""

    def check_ctx(self, ctx: ModuleContext):
        rule = self
        findings: List[Tuple[int, int, str]] = []
        seen: Set[int] = set()

        def flag(node: ast.AST, msg: str) -> None:
            if node.lineno in seen:
                return
            seen.add(node.lineno)
            findings.append((node.lineno, node.col_offset, msg))

        def tainted(env, expr: ast.AST) -> bool:
            for sub in ast.walk(expr):
                name = rule._base_name(sub) if isinstance(
                    sub, (ast.Name, ast.Attribute, ast.Subscript)) else ""
                if name:
                    cell = env.get(name)
                    if cell is not None and "device" in cell:
                        return True
            return False

        class V(FlowVisitor):
            def on_call(self, ev: FlowEvent, env) -> None:
                call = ev.node
                dotted = ctx.resolve_call(call)
                f = call.func
                # syncs ------------------------------------------------
                if dotted == "jax.device_get":
                    flag(call,
                         "jax.device_get blocks on the device pipeline; "
                         "the action layer gets ONE sanctioned readback "
                         "per cycle — annotate the choke point or batch "
                         "this into it")
                    return
                if dotted in ("numpy.asarray", "numpy.array") and call.args:
                    if tainted(env, call.args[0]):
                        flag(call,
                             "np.asarray on a device-resident value forces "
                             "a blocking transfer outside the sanctioned "
                             "readback; keep it on device or fold it into "
                             "the cycle's choke point")
                    return
                if (isinstance(f, ast.Attribute)
                        and f.attr in rule.SYNC_ATTRS
                        and tainted(env, f.value)):
                    flag(call,
                         f"`.{f.attr}()` on a device-resident value is a "
                         "blocking host-device sync in the action layer; "
                         "batch it into the sanctioned readback")
                    return
                if (isinstance(f, ast.Name) and f.id in ("float", "int")
                        and call.args and tainted(env, call.args[0])):
                    flag(call,
                         f"`{f.id}()` on a device-resident value "
                         "materializes it on host; read it back through "
                         "the sanctioned choke point")

            def on_bind(self, ev: FlowEvent, env, value) -> None:
                if (isinstance(value, ast.Call)
                        and rule._is_device_source(value, ctx)
                        and ev.cell is not None):
                    # device_get results are host values — never a source
                    if ctx.resolve_call(value) != "jax.device_get":
                        ev.cell["device"] = value.lineno

        for func in ctx.functions:
            walk_function(func, V())
        yield from findings


FLOW_RULES = (
    UseAfterDonationRule(),
    RetraceHazardRule(),
    FailOpenSeamProbeRule(),
    TelemetryMisuseRule(),
    ResidentSyncRule(),
)
