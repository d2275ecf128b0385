"""kbt-check static analyzer: fixture-driven good/bad snippets per rule,
suppression contract, CLI, and the tier-1 self-enforcement check that keeps
the whole package clean."""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

from kube_batch_tpu.analysis import check_source, run_paths
from kube_batch_tpu.analysis.rules import RULES_BY_ID


def findings_for(src: str, relpath: str):
    return check_source(textwrap.dedent(src), relpath)


def rule_ids(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# KBT001 — wall clock outside the Clock seam
# ---------------------------------------------------------------------------


class TestKBT001:
    BAD = """
    import time

    def pace():
        time.sleep(1.0)
        return time.monotonic()
    """

    def test_bad_snippet_triggers_exactly_kbt001(self):
        findings = findings_for(self.BAD, "actions/x.py")
        assert rule_ids(findings) == ["KBT001"]
        assert len(findings) == 2

    def test_from_import_alias_is_caught(self):
        findings = findings_for(
            "from time import sleep as zzz\ndef f():\n    zzz(1)\n",
            "sim/x.py",
        )
        assert rule_ids(findings) == ["KBT001"]

    def test_datetime_now_is_caught(self):
        findings = findings_for(
            "import datetime\ndef f():\n    return datetime.datetime.now()\n",
            "cache/x.py",
        )
        assert rule_ids(findings) == ["KBT001"]

    def test_injected_clock_is_the_sanctioned_path(self):
        good = """
        class S:
            def pace(self):
                t = self.clock.monotonic()
                self.clock.sleep(1.0)
                return t
        """
        assert findings_for(good, "scheduler.py") == []

    def test_out_of_scope_paths_unflagged(self):
        # cmd/ owns real wall-clock concerns (leases, rate limits)
        assert findings_for(self.BAD, "cmd/x.py") == []

    def test_annotation_suppresses(self):
        src = """
        import time

        def f():
            # kbt: allow[KBT001] measures real compute for the bench
            return time.perf_counter()
        """
        assert findings_for(src, "actions/x.py") == []


# ---------------------------------------------------------------------------
# KBT002 — blocking call under a lock
# ---------------------------------------------------------------------------


class TestKBT002:
    def test_sleep_under_lock_triggers(self):
        src = """
        import time

        def take(self):
            with self._lock:
                time.sleep(0.1)
        """
        # KBT002 everywhere; out of KBT001 scope so only the lock rule fires
        findings = findings_for(src, "cmd/server.py")
        assert rule_ids(findings) == ["KBT002"]

    def test_future_result_and_queue_get_under_lock_trigger(self):
        src = """
        def drain(self):
            with self._lock:
                self.future.result()
                item = work_queue.get()
        """
        findings = findings_for(src, "k8s/x.py")
        assert len(findings) == 2 and rule_ids(findings) == ["KBT002"]

    def test_tokenbucket_pattern_is_clean(self):
        src = """
        def take(self):
            with self._lock:
                self._tokens -= 1.0
                wait = max(0.0, -self._tokens / self._qps)
            if wait:
                self._time.sleep(wait)
        """
        assert findings_for(src, "cmd/server.py") == []

    def test_dict_get_under_lock_is_not_blocking(self):
        src = """
        def read(self):
            with self._lock:
                return self.index.get("k")
        """
        assert findings_for(src, "k8s/x.py") == []

    def test_nested_def_body_is_not_under_the_lock(self):
        src = """
        import time

        def sched(self):
            with self._lock:
                def later():
                    time.sleep(1)
                return later
        """
        assert findings_for(src, "cmd/x.py") == []

    def test_non_lock_with_is_ignored(self):
        src = """
        import time

        def f():
            with open("x") as fh:
                time.sleep(1)
                return fh
        """
        assert findings_for(src, "cmd/x.py") == []


# ---------------------------------------------------------------------------
# KBT003 — module-level mutable state in actions/framework
# ---------------------------------------------------------------------------


class TestKBT003:
    def test_module_dict_and_global_write_trigger(self):
        src = """
        last_host_discards = {}

        def execute(ssn):
            global cycle_count
            cycle_count = 1
        """
        findings = findings_for(src, "actions/x.py")
        assert rule_ids(findings) == ["KBT003"]
        assert len(findings) == 2

    def test_constants_and_dunders_are_fine(self):
        src = """
        OVERCOMMIT = {"cpu": 1.2}
        __all__ = ["execute"]
        logger = get_logger("x")
        """
        assert findings_for(src, "framework/x.py") == []

    def test_annotated_registry_is_fine(self):
        src = """
        # kbt: allow[KBT003] import-time registry, read-only after import
        _builders = {}
        """
        assert findings_for(src, "framework/x.py") == []

    def test_out_of_scope_module_state_unflagged(self):
        assert findings_for("cache = {}\n", "plugins/x.py") == []


# ---------------------------------------------------------------------------
# KBT004 — translate-layer fail-open defaults
# ---------------------------------------------------------------------------


class TestKBT004:
    def test_none_fallback_in_value_function_triggers(self):
        src = """
        def node_from(spec):
            if spec.get("kind") == "node":
                return spec["name"]
            return None
        """
        findings = findings_for(src, "k8s/translate.py")
        assert rule_ids(findings) == ["KBT004"]

    def test_empty_collection_fallback_triggers(self):
        src = """
        def terms_from(spec):
            if "terms" in spec:
                return list(spec["terms"])
            return []
        """
        assert rule_ids(findings_for(src, "k8s/translate.py")) == ["KBT004"]

    def test_procedures_with_bare_returns_are_fine(self):
        src = """
        def apply(cache, obj):
            if obj is None:
                return
            cache.add(obj)
        """
        assert findings_for(src, "k8s/translate.py") == []

    def test_fail_closed_sentinel_is_fine(self):
        src = """
        SENTINEL = "__restricted__"

        def node_from(spec):
            if spec.get("kind") == "node":
                return spec["name"]
            return SENTINEL
        """
        assert findings_for(src, "k8s/translate.py") == []

    def test_annotated_default_is_fine(self):
        src = """
        def owner_of(meta):
            for ref in meta.get("ownerReferences") or []:
                return ref["uid"]
            # kbt: allow[KBT004] ownerless pods are a valid spec state
            return None
        """
        assert findings_for(src, "k8s/translate.py") == []

    def test_out_of_scope_none_returns_unflagged(self):
        src = "def f(x):\n    if x:\n        return x\n    return None\n"
        assert findings_for(src, "cache/x.py") == []


# ---------------------------------------------------------------------------
# KBT005 — host-device sync in ops/
# ---------------------------------------------------------------------------


class TestKBT005:
    def test_sync_calls_trigger(self):
        src = """
        import numpy as np

        def solve(x):
            y = np.asarray(x)
            x.block_until_ready()
            return float(y)
        """
        findings = findings_for(src, "ops/x.py")
        assert rule_ids(findings) == ["KBT005"]
        assert len(findings) == 3

    def test_jnp_dispatch_in_python_loop_triggers(self):
        src = """
        import jax.numpy as jnp

        def f(keys):
            total = 0
            for k in keys:
                total = total + jnp.sum(k)
            return total
        """
        assert rule_ids(findings_for(src, "ops/x.py")) == ["KBT005"]

    def test_vectorized_jnp_is_fine(self):
        src = """
        import jax.numpy as jnp

        def f(x):
            return jnp.sum(x, axis=0)
        """
        assert findings_for(src, "ops/x.py") == []

    def test_annotated_trace_time_unroll_is_fine(self):
        src = """
        import jax.numpy as jnp

        def f(xs):
            acc = xs[0]
            for x in xs[1:]:
                # kbt: allow[KBT005] trace-time unroll over a static tuple
                acc = jnp.maximum(acc, x)
            return acc
        """
        assert findings_for(src, "ops/x.py") == []

    def test_out_of_scope_numpy_unflagged(self):
        src = "import numpy as np\ndef f(x):\n    return np.asarray(x)\n"
        assert findings_for(src, "cache/x.py") == []


# ---------------------------------------------------------------------------
# KBT006 — donated-buffer use after donation
# ---------------------------------------------------------------------------


class TestKBT006:
    BAD = """
    import jax

    scatter = jax.jit(lambda d, r: d.at[r].set(0.0), donate_argnums=(0,))

    def refresh(dev, rows):
        out = scatter(dev, rows)
        total = dev.sum()
        return out, total
    """

    def test_read_after_donation_triggers(self):
        findings = findings_for(self.BAD, "api/x.py")
        assert rule_ids(findings) == ["KBT006"]
        assert "donated" in findings[0].message

    def test_rebind_to_result_is_the_sanctioned_shape(self):
        src = """
        import jax

        scatter = jax.jit(lambda d, r: d.at[r].set(0.0), donate_argnums=(0,))

        def refresh(dev, rows):
            dev = scatter(dev, rows)
            return dev.sum()
        """
        assert findings_for(src, "api/x.py") == []

    def test_alias_of_donated_buffer_is_caught(self):
        src = """
        import jax

        scatter = jax.jit(lambda d, r: d.at[r].set(0.0), donate_argnums=(0,))

        def refresh(dev, rows):
            alias = dev
            out = scatter(dev, rows)
            return out, alias.sum()
        """
        assert rule_ids(findings_for(src, "api/x.py")) == ["KBT006"]

    def test_reassignment_clears_the_taint(self):
        src = """
        import jax

        scatter = jax.jit(lambda d, r: d.at[r].set(0.0), donate_argnums=(0,))

        def refresh(dev, rows, host):
            scatter(dev, rows)
            dev = host
            return dev.sum()
        """
        assert findings_for(src, "api/x.py") == []

    def test_factory_returned_donating_callable_is_tracked(self):
        # the api/resident.py shape: a memoized factory returns the
        # donating jitted scatter; calling `_fn()(dev, ...)` donates arg 0
        src = """
        import jax

        _S = None

        def _fn():
            global _S
            if _S is None:
                _S = jax.jit(lambda d, r: d.at[r].set(0.0),
                             donate_argnums=(0,))
            return _S

        def refresh(dev, rows):
            out = _fn()(dev, rows)
            return out, dev.sum()
        """
        assert rule_ids(findings_for(src, "api/x.py")) == ["KBT006"]

    def test_conditional_donate_tuple_still_tracks(self):
        # backend-conditional donation (the resident scatter's CPU gate)
        # folds may-style: a position that CAN donate is tracked
        src = """
        import jax

        donate = () if backend() == "cpu" else (0,)
        scatter = jax.jit(lambda d, r: d.at[r].set(0.0),
                          donate_argnums=donate)

        def refresh(dev, rows):
            out = scatter(dev, rows)
            return out, dev.sum()
        """
        assert rule_ids(findings_for(src, "api/x.py")) == ["KBT006"]

    def test_annotation_suppresses(self):
        src = """
        import jax

        scatter = jax.jit(lambda d, r: d.at[r].set(0.0), donate_argnums=(0,))

        def refresh(dev, rows):
            out = scatter(dev, rows)
            # kbt: allow[KBT006] cpu-only path, donation is a no-op there
            return out, dev.sum()
        """
        assert findings_for(src, "api/x.py") == []

    # ---- one-level interprocedural donation tracking (ROADMAP standing
    # item): a same-module helper that donates its parameter taints its
    # call sites exactly like a direct donating call ------------------------

    def test_helper_donating_its_param_taints_caller(self):
        src = """
        import jax

        scatter = jax.jit(lambda d, r: d.at[r].set(0.0), donate_argnums=(0,))

        def refresh(buf, rows):
            return scatter(buf, rows)

        def cycle(dev, rows):
            out = refresh(dev, rows)
            return out, dev.sum()
        """
        findings = findings_for(src, "api/x.py")
        assert rule_ids(findings) == ["KBT006"]
        assert any("dev" in f.message for f in findings)

    def test_helper_via_factory_form_taints_caller(self):
        # the `_scatter_fn()()` factory form INSIDE the helper — the
        # one-level scan resolves it through the same symbol table
        src = """
        import jax

        _S = None

        def _scatter_fn():
            global _S
            if _S is None:
                _S = jax.jit(lambda d, r: d.at[r].set(0.0),
                             donate_argnums=(0,))
            return _S

        def refresh(buf, rows):
            return _scatter_fn()(buf, rows)

        def cycle(dev, rows):
            out = refresh(dev, rows)
            return out, dev.sum()
        """
        assert rule_ids(findings_for(src, "api/x.py")) == ["KBT006"]

    def test_caller_rebinding_through_helper_is_clean(self):
        src = """
        import jax

        scatter = jax.jit(lambda d, r: d.at[r].set(0.0), donate_argnums=(0,))

        def refresh(buf, rows):
            return scatter(buf, rows)

        def cycle(dev, rows):
            dev = refresh(dev, rows)
            return dev.sum()
        """
        assert findings_for(src, "api/x.py") == []

    def test_helper_not_donating_its_param_is_inert(self):
        # the helper reads its param but never feeds a donated position —
        # its call sites must NOT taint
        src = """
        import jax

        scatter = jax.jit(lambda d, r: d.at[r].set(0.0), donate_argnums=(0,))

        def peek(buf):
            return buf.sum()

        def cycle(dev, rows):
            total = peek(dev)
            return total, dev.sum()
        """
        assert findings_for(src, "api/x.py") == []


# ---------------------------------------------------------------------------
# KBT007 — jit retrace hazards
# ---------------------------------------------------------------------------


class TestKBT007:
    def test_jit_wrapper_in_function_body_triggers(self):
        src = """
        import jax

        def solve(snap):
            fn = jax.jit(lambda s: s * 2)
            return fn(snap)
        """
        findings = findings_for(src, "ops/x.py")
        assert rule_ids(findings) == ["KBT007"]
        assert "fresh compile cache" in findings[0].message

    def test_memoized_wrapper_is_clean(self):
        # the parallel/mesh.py _jit_cache pattern
        src = """
        import jax

        _cache = {}

        def solve(snap, key):
            fn = _cache.get(key)
            if fn is None:
                fn = jax.jit(lambda s: s * 2)
                _cache[key] = fn
            return fn(snap)
        """
        assert findings_for(src, "parallel/x.py") == []

    def test_global_memo_is_clean(self):
        # the api/resident.py _scatter_fn pattern
        src = """
        import jax

        _S = None

        def _fn():
            global _S
            if _S is None:
                _S = jax.jit(lambda d: d * 2)
            return _S
        """
        assert findings_for(src, "api/x.py") == []

    def test_lru_cached_builder_is_clean(self):
        src = """
        import jax
        from functools import lru_cache

        @lru_cache(maxsize=8)
        def builder(key):
            return jax.jit(lambda s: s * 2)
        """
        assert findings_for(src, "parallel/x.py") == []

    def test_unhashable_static_literal_at_call_site_triggers(self):
        src = """
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("opts",))
        def solve(snap, opts):
            return snap

        def run(snap):
            return solve(snap, opts={"a": 1})
        """
        findings = findings_for(src, "ops/x.py")
        assert rule_ids(findings) == ["KBT007"]
        assert "unhashable" in findings[0].message

    def test_shape_derived_static_arg_triggers(self):
        src = """
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("n",))
        def solve(snap, n):
            return snap

        def run(snap, xs):
            return solve(snap, n=len(xs))
        """
        findings = findings_for(src, "ops/x.py")
        assert rule_ids(findings) == ["KBT007"]
        assert "shape-derived" in findings[0].message

    def test_namedtuple_static_arg_is_clean(self):
        # the AllocateConfig shape: hashable, stable cache key
        src = """
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("config",))
        def solve(snap, config):
            return snap

        def run(snap, config):
            return solve(snap, config=config)
        """
        assert findings_for(src, "ops/x.py") == []

    def test_jitted_closure_over_mutable_module_state_triggers(self):
        src = """
        import jax

        # kbt: allow[KBT003] fixture registry
        _weights = {}

        @jax.jit
        def solve(snap):
            return snap * _weights["w"]
        """
        findings = findings_for(src, "ops/x.py")
        assert rule_ids(findings) == ["KBT007"]
        assert "baked in at trace time" in findings[0].message


# ---------------------------------------------------------------------------
# KBT008 — fail-open seam probes in k8s/
# ---------------------------------------------------------------------------


class TestKBT008:
    def test_defaulted_getattr_probe_triggers(self):
        src = """
        def apply(binder, obj):
            getattr(binder, "add_pv", None)(obj)
        """
        findings = findings_for(src, "k8s/x.py")
        assert rule_ids(findings) == ["KBT008"]
        assert "'add_pv'" in findings[0].message

    def test_lambda_default_probe_triggers(self):
        src = """
        def apply(binder, obj):
            getattr(binder, "add_pv", lambda _o: None)(obj)
        """
        assert rule_ids(findings_for(src, "k8s/x.py")) == ["KBT008"]

    def test_two_arg_getattr_is_fine(self):
        # no default: a missing attribute raises — fail closed
        src = """
        def apply(binder, obj):
            getattr(binder, "add_pv")(obj)
        """
        assert findings_for(src, "k8s/x.py") == []

    def test_dispatch_table_get_probe_triggers(self):
        src = """
        def route(handlers, kind, obj):
            handlers.get(kind)(obj)
        """
        assert rule_ids(findings_for(src, "k8s/x.py")) == ["KBT008"]

    def test_out_of_scope_probe_unflagged(self):
        src = """
        def probe(cache):
            return getattr(cache, "flush_binds", None)
        """
        assert findings_for(src, "framework/x.py") == []

    def test_annotated_capability_probe_is_fine(self):
        src = """
        def reconcile(binder):
            # kbt: allow[KBT008] capability probe: absence means no ledger
            pvs = getattr(binder, "pvs", None)
            return pvs
        """
        assert findings_for(src, "k8s/x.py") == []


# ---------------------------------------------------------------------------
# KBT009 — telemetry clock outside metrics-feeding expressions
# ---------------------------------------------------------------------------


class TestKBT009:
    def test_telemetry_value_in_control_flow_triggers(self):
        src = """
        from kube_batch_tpu.utils import telemetry

        def pace(self):
            t0 = telemetry.perf_counter()
            self.work()
            if telemetry.perf_counter() - t0 > 1.0:
                self.abort()
        """
        findings = findings_for(src, "actions/x.py")
        assert rule_ids(findings) == ["KBT009"]

    def test_metrics_feeding_span_is_the_sanctioned_shape(self):
        src = """
        from kube_batch_tpu.utils import telemetry
        from kube_batch_tpu import metrics

        def timed(self):
            t0 = telemetry.perf_counter()
            self.work()
            metrics.observe_e2e_latency(
                (telemetry.perf_counter() - t0) * 1e3
            )
        """
        assert findings_for(src, "actions/x.py") == []

    def test_unused_binding_is_a_dead_wall_clock_read(self):
        src = """
        from kube_batch_tpu.utils import telemetry

        def f(self):
            t0 = telemetry.perf_counter()
            self.work()
        """
        findings = findings_for(src, "framework/x.py")
        assert rule_ids(findings) == ["KBT009"]
        assert "never read" in findings[0].message

    def test_sink_accumulation_is_clean(self):
        # an accumulating phase-mark shape: the value flows into an
        # ms sink and the next-mark attribute store
        src = """
        from kube_batch_tpu.utils import telemetry

        def mark(self, key):
            now = telemetry.perf_counter()
            self.sink[key] = self.sink.get(key, 0.0) + (now - self.t) * 1e3
            self.t = now
        """
        assert findings_for(src, "actions/x.py") == []

    def test_read_after_branch_join_is_not_dead(self):
        # review-found FP shape: the binding happens in one branch and the
        # read after the join lands on the merge's union cell — the
        # dead-read check must key on the bind SITE, not cell identity
        src = """
        from kube_batch_tpu.utils import telemetry
        from kube_batch_tpu import metrics

        def f(self, cond):
            t0 = 0.0
            if cond:
                t0 = telemetry.perf_counter()
            metrics.observe_e2e_latency(t0)
        """
        assert findings_for(src, "actions/x.py") == []

    def test_loop_carried_read_is_not_dead(self):
        # review-found FP shape: the next iteration reads the previous
        # iteration's binding (two-pass loop walk rebinds the cell)
        src = """
        from kube_batch_tpu.utils import telemetry
        from kube_batch_tpu import metrics

        def f(self, items):
            prev = telemetry.perf_counter()
            for item in items:
                self.work(item)
                metrics.observe_e2e_latency(prev)
                prev = telemetry.perf_counter()
        """
        assert findings_for(src, "actions/x.py") == []

    def test_out_of_scope_unflagged(self):
        src = """
        from kube_batch_tpu.utils import telemetry

        def f():
            t0 = telemetry.perf_counter()
        """
        assert findings_for(src, "testing/x.py") == []


# ---------------------------------------------------------------------------
# KBT010 — host-device sync on resident values in the action layer
# ---------------------------------------------------------------------------


class TestKBT010:
    def test_asarray_on_solve_result_triggers(self):
        src = """
        import numpy as np
        from kube_batch_tpu.ops.assignment import allocate_solve

        def read(snap, config):
            result = allocate_solve(snap, config)
            return np.asarray(result)
        """
        findings = findings_for(src, "actions/x.py")
        # the fixture's bare dispatch also (correctly) lacks a sentinel
        # consumer, so KBT013 fires alongside since the guard-plane PR
        assert rule_ids(findings) == ["KBT010", "KBT013"]

    def test_attribute_of_result_is_still_the_result(self):
        src = """
        import numpy as np
        from kube_batch_tpu.ops.eviction import evict_solve

        def read(snap, config):
            result = evict_solve(snap, config)
            return np.asarray(result.claim_node)
        """
        assert rule_ids(findings_for(src, "actions/x.py")) == [
            "KBT010", "KBT013",  # bare dispatch: no sentinel consumer either
        ]

    def test_device_get_is_always_a_choke_point(self):
        src = """
        import jax

        def read(result):
            return jax.device_get(result.assigned)
        """
        assert rule_ids(findings_for(src, "actions/x.py")) == ["KBT010"]

    def test_asarray_on_host_snapshot_is_fine(self):
        # the flow-awareness KBT005 lacks: host-backed snap reads are free
        src = """
        import numpy as np

        def read(snap):
            return np.asarray(snap.task_job)
        """
        assert findings_for(src, "actions/x.py") == []

    def test_item_on_device_value_triggers(self):
        src = """
        from kube_batch_tpu.ops.assignment import failure_histogram_solve

        def read(snap):
            hist = failure_histogram_solve(snap)
            return hist.item()
        """
        assert rule_ids(findings_for(src, "actions/x.py")) == ["KBT010"]

    def test_taint_survives_branch_merge(self):
        src = """
        import numpy as np
        from kube_batch_tpu.ops.assignment import failure_histogram_solve

        def read(snap, wanted):
            hist = None
            if wanted:
                hist = failure_histogram_solve(snap)
            return np.asarray(hist)
        """
        assert rule_ids(findings_for(src, "actions/x.py")) == ["KBT010"]

    def test_annotation_marks_the_sanctioned_readback(self):
        src = """
        import jax

        def read(result):
            # kbt: allow[KBT010] the cycle's one blocking readback
            return jax.device_get(result.assigned)
        """
        assert findings_for(src, "actions/x.py") == []

    def test_out_of_scope_sync_unflagged(self):
        src = """
        import jax

        def read(result):
            return jax.device_get(result)
        """
        assert findings_for(src, "testing/x.py") == []

    def test_enqueue_gate_solve_is_a_device_source(self):
        # PR 5 dispatch shape: the jitted enqueue admission scan
        src = """
        import numpy as np
        from kube_batch_tpu.ops.admission import enqueue_gate_solve

        def gate(minr, cand, idle, quanta):
            admitted = enqueue_gate_solve(minr, cand, idle, quanta)
            return np.asarray(admitted)
        """
        assert rule_ids(findings_for(src, "actions/x.py")) == ["KBT010"]

    def test_the_call_of_a_table_program_is_a_device_source(self):
        # every dispatch site's shape since the program table
        # (parallel/mesh.py): plan -> program -> call, imported in place
        src = """
        import numpy as np

        def read(snap, mesh):
            from kube_batch_tpu.parallel.mesh import call, program

            hist = call(program("fail_hist", mesh, None, None), mesh, snap)
            return np.asarray(hist)
        """
        assert rule_ids(findings_for(src, "actions/x.py")) == ["KBT010"]

    def test_scatter_factory_result_is_a_device_source(self):
        # PR 5 dispatch shape: the per-mesh resident scatter factory form
        # (`_mesh_shard_scatter_fn(mesh)(dev, rows, vals)`)
        src = """
        import numpy as np

        def refresh(mesh, dev, rows, vals):
            dev = _mesh_shard_scatter_fn(mesh)(dev, rows, vals)
            return np.asarray(dev)
        """
        assert rule_ids(findings_for(src, "api/resident.py")) == ["KBT010"]


# ---------------------------------------------------------------------------
# dataflow: the def-use engine itself
# ---------------------------------------------------------------------------


class TestDataflow:
    @staticmethod
    def _run(src: str):
        """Walk `f` in `src` with a tiny taint visitor: `taint(x)` taints
        x's cell, every load of a tainted name is recorded."""
        import ast as _ast

        from kube_batch_tpu.analysis.dataflow import (
            FlowVisitor,
            walk_function,
        )

        tree = _ast.parse(textwrap.dedent(src))
        func = next(n for n in _ast.walk(tree)
                    if isinstance(n, _ast.FunctionDef) and n.name == "f")
        hits = []

        class V(FlowVisitor):
            def on_call(self, ev, env):
                call = ev.node
                if (isinstance(call.func, _ast.Name)
                        and call.func.id == "taint"):
                    for a in call.args:
                        if isinstance(a, _ast.Name) and a.id in env:
                            env[a.id]["t"] = True

            def on_load(self, ev, env):
                if ev.cell is not None and ev.cell.get("t"):
                    hits.append((ev.name, ev.node.lineno))

        walk_function(func, V())
        return hits

    def test_alias_shares_the_cell(self):
        hits = self._run("""
        def f(a):
            b = a
            taint(a)
            return b
        """)
        assert [h[0] for h in hits] == ["b"]

    def test_reassignment_rebinds_to_a_fresh_cell(self):
        hits = self._run("""
        def f(a, c):
            taint(a)
            a = c
            return a
        """)
        assert hits == []

    def test_branch_taint_survives_the_join(self):
        hits = self._run("""
        def f(a, cond):
            if cond:
                taint(a)
            return a
        """)
        assert [h[0] for h in hits] == ["a"]

    def test_clean_rebind_in_one_branch_does_not_launder(self):
        hits = self._run("""
        def f(a, c, cond):
            taint(a)
            if cond:
                a = c
            return a
        """)
        assert [h[0] for h in hits] == ["a"]

    def test_loop_bottom_taint_reaches_the_top(self):
        hits = self._run("""
        def f(a, xs):
            for x in xs:
                y = a + 1
                taint(a)
            return y
        """)
        assert ("a", 4) in hits  # second pass sees the taint

    def test_tuple_unpack_from_call_taints_every_target(self):
        hits = self._run("""
        def f(a):
            taint(a)
            x, y = a
            return x, y
        """)
        names = {h[0] for h in hits}
        assert {"a", "x", "y"} <= names

    def test_match_arm_bodies_are_walked(self):
        # review-found soundness hole: unhandled statement types were
        # silently skipped, blinding every flow rule inside match blocks
        hits = self._run("""
        def f(a, mode):
            taint(a)
            match mode:
                case "x":
                    return a
                case _:
                    return None
        """)
        assert [h[0] for h in hits] == ["a"]

    def test_match_capture_binds_fresh_and_guard_is_a_test(self):
        hits = self._run("""
        def f(a, mode):
            taint(a)
            match mode:
                case str() as a:
                    return a
        """)
        # the capture rebinds `a` to a fresh cell inside the arm
        assert hits == []


# ---------------------------------------------------------------------------
# engine: suppression contract
# ---------------------------------------------------------------------------


class TestSuppressions:
    def test_allow_without_reason_does_not_suppress(self):
        src = """
        import time

        def f():
            return time.time()  # kbt: allow[KBT001]
        """
        findings = findings_for(src, "actions/x.py")
        # the original finding survives AND the empty allow is itself flagged
        assert rule_ids(findings) == ["KBT000", "KBT001"]

    def test_multiline_annotation_block_covers_next_statement(self):
        src = """
        import time

        def f():
            # kbt: allow[KBT001] long explanation of why this wall-clock
            # read is deliberate, spilling onto a second comment line
            return time.time()
        """
        assert findings_for(src, "actions/x.py") == []

    def test_allow_only_suppresses_its_own_rule(self):
        src = """
        import time

        def f(self):
            with self._lock:
                # kbt: allow[KBT002] reason that names the wrong rule
                time.sleep(1)
        """
        findings = findings_for(src, "actions/x.py")
        assert rule_ids(findings) == ["KBT001"]  # KBT002 suppressed, 001 not

    def test_syntax_error_reports_kbt000(self):
        findings = findings_for("def f(:\n", "actions/x.py")
        assert rule_ids(findings) == ["KBT000"]


# ---------------------------------------------------------------------------
# KBT011 — raw urllib / ad-hoc sleep retry loop outside the transport
# ---------------------------------------------------------------------------


class TestKBT011:
    def test_raw_urlopen_in_k8s_triggers(self):
        src = """
        import urllib.request

        def fetch(url):
            with urllib.request.urlopen(url) as r:
                return r.read()
        """
        assert rule_ids(findings_for(src, "k8s/watch.py")) == ["KBT011"]

    def test_from_import_urlopen_is_caught(self):
        src = """
        from urllib.request import urlopen

        def fetch(url):
            return urlopen(url).read()
        """
        assert rule_ids(findings_for(src, "cmd/server.py")) == ["KBT011"]

    def test_sleep_retry_loop_triggers(self):
        src = """
        import time

        def renew(call):
            for attempt in range(5):
                try:
                    return call()
                except OSError:
                    time.sleep(2 ** attempt)
        """
        assert rule_ids(findings_for(src, "k8s/bind.py")) == ["KBT011"]

    def test_transport_module_is_the_sanctioned_home(self):
        src = """
        import time
        import urllib.request

        def call(url, delays):
            for d in delays:
                try:
                    return urllib.request.urlopen(url)
                except OSError:
                    time.sleep(d)
        """
        assert findings_for(src, "k8s/transport.py") == []

    def test_sleep_outside_a_loop_is_not_a_retry(self):
        src = """
        import time

        def settle():
            time.sleep(0.1)
        """
        assert findings_for(src, "k8s/bind.py") == []

    def test_annotation_suppresses(self):
        src = """
        import time

        def sample(frames):
            while frames:
                frames.pop()
                # kbt: allow[KBT011] sampling cadence, not a retry loop
                time.sleep(0.01)
        """
        assert findings_for(src, "cmd/server.py") == []

    def test_out_of_scope_urlopen_unflagged(self):
        src = """
        import urllib.request

        def fetch(url):
            return urllib.request.urlopen(url).read()
        """
        assert findings_for(src, "testing/e2e.py") == []


# ---------------------------------------------------------------------------
# KBT012 — MOVED to tier D: the writeback-stage handoff contract is a
# KBT302 instance now (analysis/races.py); its fixtures live in
# tests/test_races.py::TestKBT302Legacy and `--select KBT012` aliases
# through (TestCli covers the alias).
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# KBT013 — solve dispatch without a sentinel-verdict consumer
# ---------------------------------------------------------------------------


class TestKBT013:
    def test_dispatch_without_consumer_triggers(self):
        src = """
        def execute(ssn, snap, config):
            result, mode, topk, ginfo = dispatch_allocate_solve(
                snap, config, cols=ssn.columns
            )
            return result
        """
        findings = findings_for(src, "actions/x.py")
        assert rule_ids(findings) == ["KBT013"]
        assert "consume" in findings[0].message

    def test_dispatch_with_consumer_is_clean(self):
        src = """
        def execute(ssn, snap, config, gp):
            result, mode, topk, ginfo = dispatch_allocate_solve(
                snap, config, cols=ssn.columns, guard=gp
            )
            if not gp.consume_verdict("allocate", ginfo["engaged"], 0):
                return None
            return result
        """
        assert findings_for(src, "actions/x.py") == []

    def test_direct_evict_solve_without_consumer_triggers(self):
        src = """
        def solve(ssn, snap, config):
            return evict_solve(snap, config)
        """
        assert rule_ids(findings_for(src, "actions/x.py")) == ["KBT013"]

    def test_call_of_a_table_program_without_consumer_triggers(self):
        src = """
        def solve(snap, mesh, config):
            out = call(program("evict", mesh, None, config, True), mesh,
                       snap, config=config)
            return out[0]
        """
        assert rule_ids(findings_for(src, "actions/x.py")) == ["KBT013"]
        # whatever kind a variable holds may commit
        assert rule_ids(findings_for(
            src.replace('program("evict"', "program(kind"), "actions/x.py",
        )) == ["KBT013"]

    def test_call_of_a_kind_that_commits_nothing_is_clean(self):
        src = """
        def histogram(snap, mesh):
            return call(program("fail_hist", mesh, None, None), mesh, snap)
        """
        assert findings_for(src, "actions/x.py") == []

    def test_solve_claims_without_its_consumer_is_flagged(self):
        """The evict dispatch site itself, its verdict left unconsumed."""
        import pathlib

        import kube_batch_tpu.actions.reclaim as reclaim

        src = pathlib.Path(reclaim.__file__).read_text()
        assert [f for f in check_source(src, "actions/reclaim.py")
                if f.rule == "KBT013"] == []
        assert "consume_sentinel(" in src
        flagged = [f for f in check_source(
            src.replace("consume_sentinel(", "look_at("),
            "actions/reclaim.py") if f.rule == "KBT013"]
        assert flagged and all("program" in f.message for f in flagged)

    def test_dispatch_seam_layer_is_exempt(self):
        # dispatch_*-named helpers RETURN the un-consumed sentinel — the
        # rule holds their call sites to the consumer requirement instead
        src = """
        def dispatch_allocate_solve(snap, config):
            return allocate_sentinel_solve(snap, config)
        """
        assert findings_for(src, "actions/x.py") == []

    def test_out_of_scope_unflagged(self):
        src = """
        def probe(snap, config):
            return evict_solve(snap, config)
        """
        assert findings_for(src, "serve/x.py") == []

    def test_annotation_suppresses(self):
        src = """
        def helper(snap, config):
            # kbt: allow[KBT013] read-only diagnostic solve, never bound
            return evict_solve(snap, config)
        """
        assert findings_for(src, "actions/x.py") == []


# ---------------------------------------------------------------------------
# self-enforcement: the package must be clean (tier-1)
# ---------------------------------------------------------------------------


class TestSelfEnforcement:
    def test_package_has_zero_unsuppressed_findings(self):
        findings = run_paths()  # defaults to the kube_batch_tpu tree
        assert findings == [], "\n" + "\n".join(f.render() for f in findings)

    def test_every_rule_has_title_and_grounding_doc(self):
        for rule in RULES_BY_ID.values():
            assert rule.title
            # each rule documents the incident that motivated it
            assert rule.__doc__ and len(rule.__doc__.strip()) > 40

    def test_all_static_rules_are_registered(self):
        # KBT012 migrated to tier D (races.py KBT302) — id retired here,
        # alive as a --select alias
        assert sorted(RULES_BY_ID) == [
            f"KBT{i:03d}" for i in range(1, 15) if i != 12
        ]

    def test_jaxpr_registry_has_zero_unsuppressed_findings(self):
        # tier B self-enforcement: every registered jitted entry point
        # traces clean (no f64 upcast, no in-graph transfer, no host
        # callback, declared donation intact)
        from kube_batch_tpu.analysis.jaxpr_audit import run_audit

        findings = run_audit()
        assert findings == [], "\n" + "\n".join(f.render() for f in findings)


# ---------------------------------------------------------------------------
# CLI: exit codes + JSONL
# ---------------------------------------------------------------------------


class TestCli:
    def _run(self, *args):
        return subprocess.run(
            [sys.executable, "-m", "kube_batch_tpu.analysis", *args],
            capture_output=True, text=True,
            cwd=str(Path(__file__).resolve().parent.parent),
        )

    def test_clean_tree_exits_zero(self):
        proc = self._run("kube_batch_tpu/analysis")
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_findings_exit_nonzero_and_jsonl_parses(self, tmp_path):
        bad = tmp_path / "ops" / "hot.py"
        bad.parent.mkdir()
        bad.write_text("def f(x):\n    x.block_until_ready()\n")
        proc = self._run("--jsonl", str(bad))
        assert proc.returncode == 1
        rows = [json.loads(l) for l in proc.stdout.splitlines() if l.strip()]
        assert rows and rows[0]["rule"] == "KBT005"
        assert rows[0]["line"] == 2

    def test_select_unknown_rule_is_usage_error(self):
        proc = self._run("--select", "KBT999")
        assert proc.returncode == 2

    def test_nonexistent_path_is_a_finding_not_clean(self):
        # a typo'd CI path must not report clean/exit 0
        proc = self._run("no/such/dir")
        assert proc.returncode == 1
        assert "does not exist" in proc.stdout

    def test_jaxpr_tier_clean_exits_zero(self):
        proc = self._run("--jaxpr-only")
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_jaxpr_select_parity(self):
        # KBT10x ids route to the audit tier; --jsonl shapes match tier A
        proc = self._run("--select", "KBT104", "--jsonl")
        assert proc.returncode == 0, proc.stdout + proc.stderr
        proc = self._run("--select", "KBT999")
        assert proc.returncode == 2

    def test_static_only_select_skips_the_audit_instead_of_gagging_it(
            self, monkeypatch):
        # review finding: `--jaxpr --select KBT001` used to trace every
        # entry point and then discard all audit findings — CI would
        # believe the tier ran while a donation regression passed.  A
        # selection with no audit ids now skips the audit outright
        from kube_batch_tpu.analysis import __main__ as cli
        from kube_batch_tpu.analysis import jaxpr_audit

        def boom(*a, **k):
            raise AssertionError("audit must not run for a static-only select")

        monkeypatch.setattr(jaxpr_audit, "run_audit", boom)
        rc = cli.main(["--jaxpr", "--select", "KBT001",
                       "kube_batch_tpu/analysis"])
        assert rc == 0

    def test_static_only_select_skips_the_hbm_tier_too(self, monkeypatch):
        # same contract for tier C: `--hbm --select KBT001` must not trace
        # the shape ladder only to discard every KBT20x finding
        from kube_batch_tpu.analysis import __main__ as cli
        from kube_batch_tpu.analysis import hbm_audit, jaxpr_audit

        def boom(*a, **k):
            raise AssertionError("a traced tier must not run for a "
                                 "static-only select")

        monkeypatch.setattr(jaxpr_audit, "run_audit", boom)
        monkeypatch.setattr(hbm_audit, "run_hbm_audit", boom)
        rc = cli.main(["--jaxpr", "--hbm", "--select", "KBT001",
                       "kube_batch_tpu/analysis"])
        assert rc == 0

    def test_hbm_select_implies_the_hbm_tier(self, monkeypatch):
        # a KBT20x selection routes to tier C without an explicit --hbm,
        # and skips tiers A and B outright
        from kube_batch_tpu.analysis import __main__ as cli
        from kube_batch_tpu.analysis import hbm_audit, jaxpr_audit

        calls = {}

        def fake_hbm(select=None):
            calls["select"] = select
            return []

        def boom(*a, **k):
            raise AssertionError("tier B must not run for a KBT20x select")

        monkeypatch.setattr(hbm_audit, "run_hbm_audit", fake_hbm)
        monkeypatch.setattr(jaxpr_audit, "run_audit", boom)
        rc = cli.main(["--select", "KBT203"])
        assert rc == 0
        assert calls["select"] == ["KBT203"]

    def test_list_rules_includes_all_tiers(self):
        proc = self._run("--list-rules")
        assert proc.returncode == 0
        assert "KBT010" in proc.stdout and "KBT101" in proc.stdout
        assert "KBT201" in proc.stdout and "KBT204" in proc.stdout


# ---------------------------------------------------------------------------
# KBT014 — span discipline (obs.trace context managers, no clocks in bodies)
# ---------------------------------------------------------------------------


class TestKBT014:
    def test_perf_counter_pair_inside_span_body_flagged(self):
        src = """
        from kube_batch_tpu import metrics
        from kube_batch_tpu.utils import telemetry

        def f(tracer, action):
            with tracer.span("a"):
                t0 = telemetry.perf_counter()
                action()
                metrics.observe_action_latency(
                    "a", (telemetry.perf_counter() - t0) * 1e6)
        """
        findings = findings_for(src, "actions/x.py")
        assert "KBT014" in rule_ids(findings)
        assert sum(1 for f in findings if f.rule == "KBT014") == 2

    def test_raw_time_inside_span_body_flagged(self):
        # serve/ is outside KBT001's scope — the span-body ban still holds
        src = """
        import time

        def f(tracer):
            with tracer.device_span("probe"):
                time.monotonic()
        """
        findings = findings_for(src, "serve/x.py")
        assert rule_ids(findings) == ["KBT014"]

    def test_manual_span_construction_flagged(self):
        src = """
        from kube_batch_tpu.obs.trace import Span

        def f(tracer):
            sp = Span(tracer, "x")
            return sp
        """
        findings = findings_for(src, "cache/x.py")
        assert "KBT014" in rule_ids(findings)

    def test_span_duration_read_after_block_is_the_sanctioned_form(self):
        src = """
        from kube_batch_tpu import metrics

        def f(tracer, action):
            with tracer.span("a") as sp:
                action()
            metrics.observe_action_latency("a", sp.dur_us)
        """
        assert findings_for(src, "scheduler.py") == []

    def test_injected_clock_inside_span_body_is_sanctioned(self):
        src = """
        class S:
            def f(self):
                with self.tracer.span("pace"):
                    t = self.clock.monotonic()
                return t
        """
        assert findings_for(src, "scheduler.py") == []

    def test_out_of_scope_paths_unflagged(self):
        src = """
        from kube_batch_tpu.utils import telemetry

        def f(tracer):
            with tracer.span("a"):
                return telemetry.perf_counter()
        """
        assert findings_for(src, "analysis/x.py") == []

    def test_annotation_suppresses(self):
        src = """
        from kube_batch_tpu.utils import telemetry

        def f(tracer):
            with tracer.span("a"):
                # kbt: allow[KBT014] migration shim measured both ways
                return telemetry.perf_counter()
        """
        assert findings_for(src, "actions/x.py") == []
