"""Tier C HBM audit: the production registry must be clean-or-allowlisted
at every shape-ladder point, and each rule must catch its planted bug — an
over-budget program, a steady-path full-matrix temporary, a declared-but-
unrealized donation, and a per-round collective whose payload scales with
the node axis."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import ShapeDtypeStruct as S
from jax import shard_map
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from kube_batch_tpu.analysis.jaxpr_audit import (
    REGISTRY,
    EntryPoint,
    ShapePoint,
    sharded_registry,
)
from kube_batch_tpu.analysis.hbm_audit import (
    GIB,
    HBM_ALLOWLIST,
    HBM_RULES,
    _glob_match,
    audit_entry_at,
    budget_bytes,
    headroom_report,
    peak_live_bytes,
    run_hbm_audit,
    shape_points,
)

# a fixture shape point with UNAMBIGUOUS axis extents: task dims resolve to
# {4096, 2048, 1024}, node dims to {512, 256} (T//8 = 512 collides with N
# and is correctly claimed by the node axis) — see hbm_audit._axis_dims
_SP = ShapePoint(
    name="fixture", tasks=4000, nodes=500, T=4096, N=512, J=8, Q=2, R=3,
    W=1, K_aff=1, P=1024, topk=2, warm_w=4, warm_c=4, warm_pi=4,
    probe_b=2, probe_g=4,
)


def _entry(name, build, **kw):
    return EntryPoint(name=name, build=build, **kw)


def _rules(report):
    return [r for r, _ in report.findings]


def _tn_outer_build(sp=None):
    # materializes a [T, N] outer product — the planted full-matrix plane
    fn = jax.jit(lambda a, b: (a[:, None] * b[None, :]).sum())
    return fn, (S((4096,), jnp.float32), S((512,), jnp.float32))


def _mesh4():
    return Mesh(np.array(jax.devices()[:4]), ("nodes",))


class TestShapeLadder:
    def test_three_points_including_the_north_star(self):
        pts = {sp.name: sp for sp in shape_points()}
        assert len(pts) >= 3
        ns = pts["northstar-1m"]
        assert ns.tasks == 1_000_000 and ns.nodes == 100_000
        assert ns.T >= 1_000_000 and ns.N >= 100_000
        # the compacted candidate geometry: P stays well under T
        assert ns.P <= ns.T // 4
        assert "headline-50k" in pts


class TestSelfEnforcement:
    def test_single_device_registry_clean_at_all_points(self):
        findings = run_hbm_audit(registry=tuple(REGISTRY))
        assert findings == [], "\n" + "\n".join(f.render() for f in findings)

    def test_sharded_registry_clean_at_the_north_star(self):
        sharded = sharded_registry()
        assert sharded, "conftest's forced 8-device mesh missing"
        pts = [sp for sp in shape_points() if sp.name == "northstar-1m"]
        findings = run_hbm_audit(registry=sharded, points=pts)
        assert findings == [], "\n" + "\n".join(f.render() for f in findings)

    @pytest.mark.slow
    def test_full_ladder_clean(self):
        findings = run_hbm_audit()
        assert findings == [], "\n" + "\n".join(f.render() for f in findings)

    def test_steady_entries_hold_the_sparse_contract_at_scale(self):
        """The acceptance criterion in words: the steady-path allocate /
        gate / scatter programs carry ZERO unsuppressed [T, N] temporaries
        at 1M×100k — the only KBT202 waivers are the named ROADMAP 1
        corners (evict bids, topk/warm build+fallback planes)."""
        for e_pat, rule, _pt in HBM_ALLOWLIST:
            if rule != "KBT202":
                continue
            assert (
                "evict" in e_pat or "topk" in e_pat or "warm" in e_pat
            ), f"unexpected steady-path KBT202 waiver: {e_pat}"
        for key, reason in HBM_ALLOWLIST.items():
            assert "ROADMAP" in reason, f"waiver without a burn-down " \
                f"cross-reference: {key}"


    def test_the_compact_evict_programs_brought_no_waiver(self):
        """PR 36 registered the evict programs' pending-bucket shape beside
        the full-axis fallback: eight evict waivers before, eight after,
        and the single-device ones say which shape they cover."""
        evict = {k: v for k, v in HBM_ALLOWLIST.items() if "evict" in k[0]}
        assert len(evict) == 8
        for (e_pat, rule, point), reason in evict.items():
            if e_pat.startswith("parallel."):
                continue
            assert "fallback" in reason or "both shapes" in reason, (
                e_pat, rule, point)
            if point == "envelope-150k":
                assert "alone" in reason    # the bucket's shape fits there


class TestEnvelope:
    """Kubernetes' published envelope (150,000 pods on 5,000 nodes:
    ``benchmark/configs/k8s-envelope-150k-5k.json``) on the four chips of
    one v5e-4 host: what one device holds of each program there."""

    FOUR_CHIPS = [
        "parallel.mesh.sharded_allocate_solve[shard_map]",
        "parallel.mesh.sentinel_sharded_allocate_solve[shard_map]",
        "parallel.mesh.sharded_allocate_topk_solve[shard_map]",
        "parallel.mesh.sentinel_sharded_allocate_topk_solve[shard_map]",
        "parallel.mesh.sharded_warm_allocate_solve[shard_map]",
        "parallel.mesh.sentinel_sharded_warm_allocate_solve[shard_map]",
        "parallel.mesh.sharded_evict_solve[reclaim][shard_map]",
        "parallel.mesh.sharded_evict_solve[preempt][shard_map]",
        "parallel.mesh.sharded_failure_histogram[shard_map]",
        # the guard's shadow oracle and shard_map's demotion target
        "parallel.mesh.sharded_allocate_solve[pjit]",
        "parallel.mesh.sharded_evict_solve[reclaim][pjit]",
    ]
    ONE_CHIP_OVER = [
        "ops.assignment.allocate_solve",
        "ops.invariants.allocate_sentinel_solve",
        "ops.assignment.failure_histogram_solve",
        "ops.eviction.evict_solve[reclaim]",
        "ops.eviction.evict_solve[preempt]",
    ]

    @pytest.fixture(scope="class")
    def point(self):
        return next(sp for sp in shape_points() if sp.name == "envelope-150k")

    @pytest.fixture(scope="class")
    def reports(self, point):
        assert len(jax.devices()) >= 4, "conftest's forced mesh missing"
        return {e.name: audit_entry_at(e, point)
                for e in tuple(REGISTRY) + sharded_registry(4)}

    def test_the_point_is_the_documented_limit(self, point):
        assert (point.tasks, point.nodes) == (150_000, 5_000)
        assert (point.T, point.N) == (150_528, 5_120)

    @pytest.mark.parametrize("name", FOUR_CHIPS)
    def test_fits_one_of_four_devices(self, reports, name):
        rep = reports[name]
        assert rep.traced and "KBT201" not in _rules(rep), rep.findings
        assert 0 < rep.peak_bytes <= 8 * GIB   # half a v5e, by this count

    @pytest.mark.parametrize("name", ONE_CHIP_OVER)
    def test_does_not_fit_one_device_and_says_why(self, reports, name):
        rep = reports[name]
        assert "KBT201" in _rules(rep) and rep.peak_bytes > 16 * GIB
        key = next(k for k in HBM_ALLOWLIST
                   if k[1:] == ("KBT201", "envelope-150k")
                   and _glob_match(name, k[0]))
        assert "this deployment lives on 4 chips" in HBM_ALLOWLIST[key]

    def test_the_steady_path_fits_either_way(self, reports):
        for name in ("ops.assignment.allocate_topk_solve",
                     "ops.assignment.warm_allocate_solve",
                     "ops.invariants.warm_allocate_sentinel_solve"):
            assert "KBT201" not in _rules(reports[name]), name

    @pytest.mark.parametrize("mode", ("reclaim", "preempt"))
    @pytest.mark.parametrize("entry", ("ops.eviction.evict_solve",
                                       "ops.invariants.evict_sentinel_solve"))
    def test_on_the_pending_bucket_evict_fits_one_device(self, reports,
                                                         point, entry, mode):
        """The evict solves bid on the pending bucket (PR 36): [P, N] planes
        at P = 32,768 fit one chip where the full-axis fallback's [T, N]
        do not, under the waivers the fallback already had (a [P, N] plane
        is still a task-axis x node-axis temporary: KBT202 stays)."""
        compact = reports[f"{entry}[{mode},compact]"]
        full = reports[f"{entry}[{mode}]"]
        assert point.P == 32_768
        assert compact.traced and _rules(compact) == ["KBT202"]
        assert compact.peak_bytes < 8 * GIB < 16 * GIB < full.peak_bytes
        assert full.peak_bytes > 4 * compact.peak_bytes

    def test_the_oracle_is_charged_by_its_node_shards(self, point):
        """A pjit's intermediates carry no specs: a value with the global
        node axis is charged at bytes / node shards, and left alone where
        the entry declares none."""
        oracle = next(e for e in sharded_registry(4)
                      if e.name == "parallel.mesh.sharded_allocate_solve[pjit]")
        assert oracle.spmd_shards == 4
        import dataclasses

        whole = audit_entry_at(dataclasses.replace(oracle, spmd_shards=1),
                               point)
        sharded = audit_entry_at(oracle, point)
        assert whole.peak_bytes > 16 * GIB and "KBT201" in _rules(whole)
        assert sharded.peak_bytes < whole.peak_bytes / 3.5


class TestPlantedBugs:
    def test_planted_over_budget_program_is_detected(self):
        rep = audit_entry_at(
            _entry("planted.big", _tn_outer_build), _SP,
            budget=1024, label="1 KiB (test)")
        assert _rules(rep) == ["KBT201"]
        assert "exceed" in rep.findings[0][1]
        assert "fixture" in rep.findings[0][1]

    def test_planted_tn_temporary_in_a_steady_program_is_detected(self):
        rep = audit_entry_at(
            _entry("planted.tn", _tn_outer_build, steady=True), _SP)
        assert _rules(rep) == ["KBT202"]
        msg = rep.findings[0][1]
        assert "T=4096" in msg and "N=512" in msg

    def test_the_same_plane_passes_when_not_steady(self):
        # full-matrix oracles are allowed their planes — KBT202 is a
        # steady-path contract, not a blanket ban
        rep = audit_entry_at(_entry("planted.cold", _tn_outer_build), _SP)
        assert rep.traced and _rules(rep) == []

    def test_compacted_geometry_steady_program_passes(self):
        def build(sp=None):
            # [P, topk] candidate table — the shape the contract wants
            fn = jax.jit(lambda t: (t * 2.0).sum(axis=1))
            return fn, (S((1024, 2), jnp.float32),)

        rep = audit_entry_at(
            _entry("planted.sparse", build, steady=True), _SP)
        assert rep.traced and _rules(rep) == []

    def test_planted_unrealized_donation_is_detected(self):
        def build(sp=None):
            fn = jax.jit(lambda d: d.sum(), donate_argnums=(0,))
            return fn, (S((4096, 512), jnp.float32),)

        rep = audit_entry_at(
            _entry("planted.donation", build, donate={"*": (0,)}), _SP)
        assert _rules(rep) == ["KBT203"]
        assert "no shape/dtype-matching output" in rep.findings[0][1]

    def test_realized_donation_passes(self):
        def build(sp=None):
            fn = jax.jit(lambda d, r: d.at[r].set(0.0), donate_argnums=(0,))
            return fn, (S((4096, 512), jnp.float32), S((2,), jnp.int32))

        rep = audit_entry_at(
            _entry("planted.donation_ok", build, donate={"*": (0,)}), _SP)
        assert rep.traced and _rules(rep) == []

    def test_planted_node_scaled_round_collective_is_detected(self):
        def build(sp=None):
            mesh = _mesh4()

            def body(x):  # x: local [N/4]
                def step(c, _):
                    g = jax.lax.all_gather(c, "nodes", tiled=True)  # [N]
                    return c + g.sum(), None

                c, _ = jax.lax.scan(step, x, None, length=3)
                return c

            fn = jax.jit(shard_map(body, mesh=mesh, in_specs=P("nodes"),
                                   out_specs=P("nodes")))
            return fn, (S((512,), jnp.float32),)

        rep = audit_entry_at(_entry("planted.gather", build), _SP)
        assert _rules(rep) == ["KBT204"]
        msg = rep.findings[0][1]
        assert "all_gather" in msg and "N=512" in msg

    def test_per_solve_collective_passes(self):
        # the same gather OUTSIDE the round loop is the allowed one-time
        # node-ledger pattern
        def build(sp=None):
            mesh = _mesh4()

            def body(x):
                g = jax.lax.all_gather(x, "nodes", tiled=True)
                return x + g.sum()

            fn = jax.jit(shard_map(body, mesh=mesh, in_specs=P("nodes"),
                                   out_specs=P("nodes")))
            return fn, (S((512,), jnp.float32),)

        rep = audit_entry_at(_entry("planted.solve_gather", build), _SP)
        assert rep.traced and _rules(rep) == []

    def test_broken_entry_names_the_shape_point_instead_of_crashing(self):
        def build(sp=None):
            raise ValueError("shape-derived python branch blew up")

        rep = audit_entry_at(_entry("planted.broken", build), _SP)
        assert not rep.traced
        assert _rules(rep) == ["KBT000"]
        msg = rep.findings[0][1]
        assert "failed to trace" in msg and "fixture" in msg
        # and the tier driver surfaces it as a finding, not an exception
        findings = run_hbm_audit(
            registry=[_entry("planted.broken", build)], points=[_SP],
            allowlist={})
        assert [f.rule for f in findings] == ["KBT000"]


class TestAllowlist:
    def _tn_entry(self):
        return _entry("planted.tn", _tn_outer_build, steady=True)

    def test_allow_with_reason_suppresses(self):
        allow = {("planted.tn", "KBT202", "fixture"): "fixture: deliberate"}
        findings = run_hbm_audit(
            registry=[self._tn_entry()], points=[_SP], allowlist=allow)
        assert findings == []

    def test_allow_without_reason_is_itself_a_finding(self):
        allow = {("planted.tn", "KBT202", "fixture"): "   "}
        findings = run_hbm_audit(
            registry=[self._tn_entry()], points=[_SP], allowlist=allow)
        assert [f.rule for f in findings] == ["KBT000"]
        assert "no reason" in findings[0].message

    def test_stale_allowlist_entry_is_itself_a_finding(self):
        def build(sp=None):
            fn = jax.jit(lambda x: x + 1.0)
            return fn, (S((256,), jnp.float32),)

        allow = {("planted.clean", "KBT202", "fixture"): "was fixed"}
        findings = run_hbm_audit(
            registry=[_entry("planted.clean", build, steady=True)],
            points=[_SP], allowlist=allow)
        assert [f.rule for f in findings] == ["KBT000"]
        assert "stale" in findings[0].message

    def test_uncovered_allowlist_entry_is_not_judged_stale(self):
        # a single-device run must not flag sharded-namespace waivers
        def build(sp=None):
            fn = jax.jit(lambda x: x + 1.0)
            return fn, (S((256,), jnp.float32),)

        allow = {("parallel.mesh.not_in_this_run", "KBT202", "*"): "r"}
        findings = run_hbm_audit(
            registry=[_entry("planted.clean", build)], points=[_SP],
            allowlist=allow)
        assert findings == []

    def test_wildcard_points_cover_the_whole_ladder(self):
        allow = {("planted.tn", "KBT202", "*"): "fixture: deliberate"}
        findings = run_hbm_audit(
            registry=[self._tn_entry()], points=[_SP], allowlist=allow)
        assert findings == []

    def test_select_filters_hbm_rules_but_keeps_meta(self):
        findings = run_hbm_audit(
            registry=[self._tn_entry()], points=[_SP], allowlist={},
            select=["KBT201"])
        assert findings == []
        findings = run_hbm_audit(
            registry=[self._tn_entry()], points=[_SP], allowlist={},
            select=["KBT202"])
        assert [f.rule for f in findings] == ["KBT202"]

    def test_glob_matches_literal_brackets(self):
        # entry names contain literal [impl] tags — fnmatch would read
        # them as character classes and silently never match
        assert _glob_match("ops.eviction.evict_solve[reclaim]",
                           "ops.eviction.evict_solve[*]")
        assert _glob_match("ops.eviction.evict_solve[preempt]",
                           "ops.eviction.evict_solve[*]")
        # one waiver covers a program's both shapes (PR 36)
        assert _glob_match("ops.eviction.evict_solve[reclaim,compact]",
                           "ops.eviction.evict_solve[*]")
        assert not _glob_match("ops.eviction.evict_solver",
                               "ops.eviction.evict_solve[*]")
        assert _glob_match("anything at all", "*")
        assert not _glob_match("kbt202", "KBT202")


class TestBudget:
    def test_default_budget_is_a_v5e(self, monkeypatch):
        monkeypatch.delenv("KB_HBM_BUDGET", raising=False)
        assert budget_bytes() == (16 * GIB, "v5e")

    def test_profile_override(self, monkeypatch):
        monkeypatch.setenv("KB_HBM_BUDGET", "v6e")
        assert budget_bytes() == (32 * GIB, "v6e")

    def test_gib_override(self, monkeypatch):
        monkeypatch.setenv("KB_HBM_BUDGET", "24")
        b, label = budget_bytes()
        assert b == 24 * GIB and "24" in label

    def test_garbage_override_falls_back_hard(self, monkeypatch):
        # the audit must never silently relax to an infinite budget
        monkeypatch.setenv("KB_HBM_BUDGET", "plenty")
        assert budget_bytes() == (16 * GIB, "v5e")


class TestLiveness:
    def test_donation_credit_lowers_the_peak(self):
        closed = jax.jit(lambda d: d * 2.0 + 1.0).trace(
            S((1024, 1024), jnp.float32)).jaxpr
        undonated = peak_live_bytes(closed)
        donated = peak_live_bytes(closed, donated_flat={0})
        # 4 MiB input frees after its last read instead of surviving
        assert donated == undonated - 4 * 2**20

    def test_cond_charges_the_max_branch_not_the_sum(self):
        def big(v):
            return (v * 2.0).sum()

        closed = jax.jit(
            lambda p, x: jax.lax.cond(p, big, big, x)).trace(
            S((), jnp.bool_), S((1024, 1024), jnp.float32)).jaxpr
        peak = peak_live_bytes(closed)
        # 4 MiB operand + ONE 4 MiB branch temporary (+ scalars)
        assert 8 * 2**20 <= peak < 9 * 2**20

    def test_shard_map_charges_per_device_bytes(self):
        mesh = _mesh4()
        fn = jax.jit(shard_map(lambda x: x * 2.0, mesh=mesh,
                               in_specs=P("nodes"), out_specs=P("nodes")))
        closed = fn.trace(S((512,), jnp.float32)).jaxpr
        peak = peak_live_bytes(closed)
        # one device holds [128] in + [128] body temp + [128] out, far
        # under the 2 × 2 KiB an unsharded walk would charge
        assert 0 < peak <= 2048

    def test_headroom_report_structure(self):
        def build(sp=None):
            return jax.jit(lambda x: x + 1.0), (S((256,), jnp.float32),)

        rep = headroom_report(
            registry=[_entry("planted.report", build)], points=[_SP])
        assert rep["budget_bytes"] > 0
        d = rep["entries"]["planted.report"]["fixture"]
        assert d["traced"] and d["peak_bytes"] > 0
        assert d["headroom_bytes"] == rep["budget_bytes"] - d["peak_bytes"]
        assert d["over_budget"] is False and d["findings"] == []


class TestNestedCollectiveInventory:
    """The jitstats extension behind KBT204's byte formulas: collectives in
    loops nested WITHIN the round loop amplify by their trip counts."""

    def _trace(self, inner):
        mesh = _mesh4()

        def body(x):
            def round_step(c, _):
                if inner == "scan":
                    def merge(m, _):
                        return m + jax.lax.psum(m, "nodes"), None

                    m, _ = jax.lax.scan(merge, c, None, length=5)
                else:
                    m = jax.lax.while_loop(
                        lambda s: s.sum() < 10.0,
                        lambda s: s + jax.lax.psum(s, "nodes"), c)
                return m, None

            c, _ = jax.lax.scan(round_step, x, None, length=2)
            return c

        # check_vma=False: as the production bodies are built (_shard_map)
        fn = jax.jit(shard_map(body, mesh=mesh, in_specs=P("nodes"),
                               out_specs=P("nodes"), check_vma=False))
        return fn.trace(S((512,), jnp.float32)).jaxpr

    def test_inner_scan_trip_count_amplifies_per_round_bytes(self):
        from kube_batch_tpu.utils.jitstats import collective_inventory

        inv = collective_inventory(self._trace("scan"), detail=True)
        # one psum of a local [128] f32 = 512 B per site
        assert inv["ops"]["per_round"]["psum"]["bytes"] == 512
        assert inv["per_round_bytes"] == 512
        assert inv["per_round_bytes_expanded"] == 512 * 5
        assert inv["per_round_has_unbounded_inner_loop"] is False
        (site,) = inv["sites"]
        assert site["depth"] == 2 and site["inner_trips"] == 5
        assert site["unbounded_trips"] is False

    def test_inner_while_marks_the_formula_as_a_floor(self):
        from kube_batch_tpu.utils.jitstats import collective_inventory

        inv = collective_inventory(self._trace("while"), detail=True)
        assert inv["per_round_bytes"] == 512
        # no static trip count: ×1 in the expanded total, flagged
        assert inv["per_round_bytes_expanded"] == 512
        assert inv["per_round_has_unbounded_inner_loop"] is True
        (site,) = inv["sites"]
        assert site["unbounded_trips"] is True


class TestCatalog:
    def test_hbm_rules_documented(self):
        assert set(HBM_RULES) == {"KBT201", "KBT202", "KBT203", "KBT204"}
        for title in HBM_RULES.values():
            assert title
