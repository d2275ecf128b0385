"""Tier B jaxpr audit: the production registry must trace clean, and each
check must catch its planted bug — a deliberate f64 upcast, an in-graph
transfer, a host callback, and a donation mismatch."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kube_batch_tpu.analysis.jaxpr_audit import (
    AUDIT_RULES,
    REGISTRY,
    EntryPoint,
    audit_entry,
    run_audit,
)


#: the 51 audited entry points on the 8-device test backend, written out:
#: HBM_ALLOWLIST and the other allowlists key on these names, the ledger's
#: readers on the programs behind them.  Marks: ``steady`` (KBT202 applies),
#: ``donate`` (a non-empty donation declaration), ``spmd`` (a pjit oracle
#: that tier C charges per node shard).
PINNED_ENTRIES = [
    ("api.resident.swap", "steady donate"),
    ("api.resident.swap_repl", "steady donate"),
    ("api.resident.swap_sharded", "steady donate"),
    ("ops.admission.enqueue_gate", "steady"),
    ("ops.assignment.allocate_solve", ""),
    ("ops.assignment.allocate_topk_solve", "steady"),
    ("ops.assignment.failure_histogram_bucket_solve", ""),
    ("ops.assignment.failure_histogram_solve", ""),
    ("ops.assignment.warm_allocate_solve", "steady donate"),
    ("ops.eviction.evict_solve[preempt,compact]", "steady"),
    ("ops.eviction.evict_solve[preempt]", "steady"),
    ("ops.eviction.evict_solve[reclaim,compact]", "steady"),
    ("ops.eviction.evict_solve[reclaim]", "steady"),
    ("ops.invariants.allocate_sentinel_solve", ""),
    ("ops.invariants.allocate_topk_sentinel_solve", "steady"),
    ("ops.invariants.enqueue_gate_sentinel", "steady"),
    ("ops.invariants.evict_sentinel_solve[preempt,compact]", "steady"),
    ("ops.invariants.evict_sentinel_solve[preempt]", "steady"),
    ("ops.invariants.evict_sentinel_solve[reclaim,compact]", "steady"),
    ("ops.invariants.evict_sentinel_solve[reclaim]", "steady"),
    ("ops.invariants.warm_allocate_sentinel_solve", "steady donate"),
    ("ops.probe.probe_solve", "steady"),
    ("ops.probe.probe_solve[topk-inert]", "steady"),
    ("parallel.mesh.sentinel_sharded_allocate_solve[pjit]", "spmd"),
    ("parallel.mesh.sentinel_sharded_allocate_solve[shard_map]", ""),
    ("parallel.mesh.sentinel_sharded_allocate_topk_solve[pjit]", "steady spmd"),
    ("parallel.mesh.sentinel_sharded_allocate_topk_solve[shard_map]", "steady"),
    ("parallel.mesh.sentinel_sharded_evict_solve[preempt][pjit]", "steady spmd"),
    ("parallel.mesh.sentinel_sharded_evict_solve[preempt][shard_map]", "steady"),
    ("parallel.mesh.sentinel_sharded_evict_solve[reclaim][pjit]", "steady spmd"),
    ("parallel.mesh.sentinel_sharded_evict_solve[reclaim][shard_map]", "steady"),
    ("parallel.mesh.sentinel_sharded_warm_allocate_solve[pjit]", "steady spmd"),
    ("parallel.mesh.sentinel_sharded_warm_allocate_solve[shard_map]", "steady"),
    ("parallel.mesh.sharded_allocate_solve[pjit]", "spmd"),
    ("parallel.mesh.sharded_allocate_solve[shard_map,2d]", ""),
    ("parallel.mesh.sharded_allocate_solve[shard_map]", ""),
    ("parallel.mesh.sharded_allocate_topk_solve[pjit]", "steady spmd"),
    ("parallel.mesh.sharded_allocate_topk_solve[shard_map]", "steady"),
    ("parallel.mesh.sharded_enqueue_gate", "steady"),
    ("parallel.mesh.sharded_evict_solve[preempt][pjit]", "steady spmd"),
    ("parallel.mesh.sharded_evict_solve[preempt][shard_map]", "steady"),
    ("parallel.mesh.sharded_evict_solve[reclaim][pjit]", "steady spmd"),
    ("parallel.mesh.sharded_evict_solve[reclaim][shard_map]", "steady"),
    ("parallel.mesh.sharded_failure_histogram[pjit]", "spmd"),
    ("parallel.mesh.sharded_failure_histogram[shard_map]", ""),
    ("parallel.mesh.sharded_failure_histogram_bucket[pjit]", "spmd"),
    ("parallel.mesh.sharded_failure_histogram_bucket[shard_map]", ""),
    ("parallel.mesh.sharded_probe_solve[pjit]", "steady spmd"),
    ("parallel.mesh.sharded_probe_solve[shard_map]", "steady"),
    ("parallel.mesh.sharded_warm_allocate_solve[pjit]", "steady spmd"),
    ("parallel.mesh.sharded_warm_allocate_solve[shard_map]", "steady"),
]


def _entry(name, build, **kw):
    return EntryPoint(name=name, build=build, **kw)


def _vec():
    from jax import ShapeDtypeStruct as S

    return S((8,), jnp.float32)


class TestRegistryClean:
    def test_production_registry_has_zero_findings(self):
        findings = run_audit()
        assert findings == [], "\n" + "\n".join(f.render() for f in findings)

    def test_registry_covers_the_hot_path(self):
        names = {e.name for e in REGISTRY}
        assert any("allocate_solve" in n for n in names)
        assert any("allocate_topk_solve" in n for n in names)
        assert any("evict_solve" in n for n in names)
        # both shapes of every evict program: the pending bucket's and the
        # full-axis fallback's (PR 36)
        for entry in ("ops.eviction.evict_solve",
                      "ops.invariants.evict_sentinel_solve"):
            for mode in ("reclaim", "preempt"):
                assert {f"{entry}[{mode}]", f"{entry}[{mode},compact]"} <= names
        assert any("resident" in n for n in names)
        assert any("warm_allocate_solve" in n for n in names)
        assert any("warm_allocate_sentinel_solve" in n for n in names)
        assert any("enqueue_gate" in n for n in names)
        assert any("topk-inert" in n for n in names)

    def test_the_entry_names_and_marks_are_the_pinned_list(self):
        """The registry is derived (parallel/mesh.py's table walked by
        analysis/jaxpr_audit.py): what it derives is held to this list,
        name for name and mark for mark."""
        from kube_batch_tpu.analysis.jaxpr_audit import sharded_registry

        assert len(jax.devices()) == 8
        entries = tuple(REGISTRY) + sharded_registry()

        def marks(e):
            return " ".join(m for m, on in (
                ("steady", e.steady),
                ("donate", e.donate != {"*": ()}),
                ("spmd", e.spmd_shards != 1),
            ) if on)

        assert len(PINNED_ENTRIES) == 51
        assert sorted((e.name, marks(e)) for e in entries) == PINNED_ENTRIES
        assert {e.spmd_shards for e in entries} == {1, 8}

    def test_sharded_variants_traced_on_the_virtual_mesh(self):
        """The conftest's forced 8-device CPU mesh stands in for multi-chip
        hardware: the sharded solve variants and both mesh swap programs
        must be registered and trace clean (KBT101-104 over the sharded path)."""
        from kube_batch_tpu.analysis.jaxpr_audit import sharded_registry

        assert len(jax.devices()) >= 2
        sharded = sharded_registry()
        names = {e.name for e in sharded}
        assert any("sharded_allocate_solve" in n for n in names)
        assert any("sharded_allocate_topk_solve" in n for n in names)
        assert any("sharded_failure_histogram" in n for n in names)
        assert any("sharded_evict_solve" in n for n in names)
        assert any("swap_sharded" in n for n in names)
        assert any("swap_repl" in n for n in names)
        findings = run_audit(registry=sharded)
        assert findings == [], "\n" + "\n".join(f.render() for f in findings)


class TestPlantedBugs:
    def test_planted_f64_upcast_is_detected(self):
        # np.float64 scalar promotes the whole expression under x64 — the
        # exact hazard class a round-head kernel once shipped
        def build():
            fn = jax.jit(lambda x: x * np.float64(2.0))
            return fn, (_vec(),)

        findings = audit_entry(_entry("planted.f64", build))
        assert [f.rule for f in findings] == ["KBT101"]
        assert "float64" in findings[0].message

    def test_planted_astype_f64_is_detected(self):
        def build():
            fn = jax.jit(lambda x: x.astype(jnp.float64).sum())
            return fn, (_vec(),)

        findings = audit_entry(_entry("planted.astype", build))
        assert [f.rule for f in findings] == ["KBT101"]

    def test_planted_concrete_device_put_is_detected(self):
        dev = jax.devices()[0]

        def build():
            fn = jax.jit(lambda x: jax.device_put(x, dev) + 1.0)
            return fn, (_vec(),)

        findings = audit_entry(_entry("planted.transfer", build))
        assert [f.rule for f in findings] == ["KBT102"]

    def test_planted_host_callback_is_detected(self):
        def build():
            def f(x):
                y = jax.pure_callback(
                    lambda v: np.asarray(v),
                    jax.ShapeDtypeStruct((8,), jnp.float32), x,
                )
                return y + 1.0

            return jax.jit(f), (_vec(),)

        findings = audit_entry(_entry("planted.callback", build))
        assert [f.rule for f in findings] == ["KBT103"]

    def test_planted_donation_mismatch_is_detected(self):
        # registry says "donates arg 0 on every backend"; the wrapper
        # doesn't — the silent-regression shape KBT104 exists for
        def build():
            fn = jax.jit(lambda d, r: d.at[r].set(0.0))
            return fn, (_vec(), jax.ShapeDtypeStruct((2,), jnp.int32))

        findings = audit_entry(
            _entry("planted.donation", build, donate={"*": (0,)}))
        assert [f.rule for f in findings] == ["KBT104"]

    def test_declared_donation_passes(self):
        def build():
            fn = jax.jit(lambda d, r: d.at[r].set(0.0), donate_argnums=(0,))
            return fn, (_vec(), jax.ShapeDtypeStruct((2,), jnp.int32))

        findings = audit_entry(
            _entry("planted.donation_ok", build, donate={"*": (0,)}))
        assert findings == []

    def test_broken_entry_reports_instead_of_reading_clean(self):
        def build():
            raise RuntimeError("registry rot")

        findings = audit_entry(_entry("planted.broken", build))
        assert [f.rule for f in findings] == ["KBT000"]
        assert "failed to trace" in findings[0].message


class TestSuppression:
    def _f64_entry(self, allow):
        def build():
            fn = jax.jit(lambda x: x * np.float64(2.0))
            return fn, (_vec(),)

        return _entry("planted.sup", build, allow=allow)

    def test_allow_with_reason_suppresses(self):
        findings = audit_entry(
            self._f64_entry({"KBT101": "fixture: deliberate upcast"}))
        assert findings == []

    def test_allow_without_reason_is_itself_a_finding(self):
        findings = audit_entry(self._f64_entry({"KBT101": "  "}))
        assert [f.rule for f in findings] == ["KBT000"]

    def test_select_filters_audit_rules(self):
        entry = self._f64_entry({})
        findings = run_audit(registry=[entry], select=["KBT102"])
        assert findings == []
        findings = run_audit(registry=[entry], select=["KBT101"])
        assert [f.rule for f in findings] == ["KBT101"]


class TestCatalog:
    def test_audit_rules_documented(self):
        assert set(AUDIT_RULES) == {"KBT101", "KBT102", "KBT103", "KBT104"}
        for title in AUDIT_RULES.values():
            assert title


@pytest.mark.slow
class TestTiming:
    def test_full_audit_is_subsecond_after_warm_import(self):
        import time

        t0 = time.perf_counter()
        run_audit()
        assert time.perf_counter() - t0 < 10.0
