"""``affinity-10k-5k`` (``schedperf-mixed-10k-5k`` x ``constraints-open``,
1 chip) rehearsed at a tiny size through run.py's own entry:
``rehearsal-constraints-600-96`` keeps the deployment's five templates and
terms on 96 nodes in 3 zones.  The cell is added to the rehearsal's manifest
as ``test_tiers_cell.py`` adds one, from files alone, together with the six
per-layer metrics of the inter-pod terms (``constraints_manifest.py`` says
why ``BENCHMARK.json`` cannot list them yet).  The whole cell runs: the
stream, the bind-order check, both controls, every new metric file read.  A
second green pod on a node under a whole run comes out ``correct: false``, and
so does one bound beside a green pod that a later burst deleted; a program
that cannot give the order of its binds ends the run before anything is
loaded; the window's plan is the same work for every seed."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

import constraints_manifest
import reference_constraints
import run as harness
import server as server_mod
from conftest import BENCH, REPO
from rehearsal_manifest import derive
from streams import constraint_bursts

CELL, STANDS_FOR = "rehearsal-constraints", constraints_manifest.CELL
CONFIG = "rehearsal-constraints-600-96"
ZONE, HOST = "topology.kubernetes.io/zone", "kubernetes.io/hostname"


def load(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def manifest() -> dict:
    full = constraints_manifest.derive()
    out = derive()
    out["configs"].append({
        "name": CONFIG, "file": f"benchmark/configs/{CONFIG}.json"})
    out["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": "rehearsal-constraints",
        "chips": 1})
    for section in ("end_to_end", "per_layer"):
        for tiny, accepted in zip(out[section], full[section]):
            if STANDS_FOR in accepted.get("workloads", [STANDS_FOR]):
                tiny["workloads"].append(CELL)
    out["per_layer"] += constraints_manifest.entries([CELL])
    return out


@pytest.fixture(scope="module")
def manifest_path(tmp_path_factory) -> str:
    path = tmp_path_factory.mktemp("constraints") / "manifest.json"
    path.write_text(json.dumps(manifest()))
    return str(path)


@pytest.fixture(scope="module")
def lines(tmp_path_factory, manifest_path):
    tmp = tmp_path_factory.mktemp("constraints-out")
    out = {}
    for trace in ("0", "1"):
        got = subprocess.run(
            [sys.executable, os.path.join(BENCH, "run.py"),
             "--manifest", manifest_path, "--workload", CELL,
             "--seed", "2147484041", "--seconds", "6", "--trace", trace,
             "--platform", "cpu", "--out", str(tmp / "out")],
            cwd=REPO, capture_output=True, text=True, timeout=900,
            env=dict(os.environ, XLA_FLAGS=(
                "--xla_force_host_platform_device_count=1")))
        assert got.returncode == 0, got.stderr[-2000:]
        out[trace] = (json.loads(got.stdout.strip().splitlines()[-1]),
                      got.stdout)
    return out


def test_the_cell_and_its_configuration_are_in_the_manifest():
    full = load(REPO, "BENCHMARK.json")
    cell = next(w for w in full["workloads"] if w["name"] == STANDS_FOR)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "schedperf-mixed-10k-5k", "constraints-open", 1)
    assert full["workloads"][-1] is cell and len(cell["why"]) <= 200
    entry = next(c for c in full["configs"]
                 if c["name"] == "schedperf-mixed-10k-5k")
    assert full["configs"][-1] is entry and len(entry["source"]) <= 200
    config = load(REPO, entry["file"])
    assert entry["reduced"] == config["reduced"] == []
    assert entry["source"] == config["source"]
    assert "MixedSchedulingBasePod" in entry["source"]
    assert "5000Nodes" in entry["source"]
    assert config["architecture"] is None
    assert config["nodes"] == 5000
    gib = 1 << 30
    assert config["node"] == {"cpu_milli": 4000, "memory_bytes": 32 * gib,
                              "pods": 110}
    assert config["topology"]["zones"] == ["zone1"]
    assert config["population"] == {"kind": "templates",
                                    "pods_per_template": 2000}
    assert config["queues"] == [{"name": "default", "weight": 1}]
    templates = {t["name"]: t for t in config["templates"]}
    assert list(templates) == [
        "default", "pod-affinity", "pod-anti-affinity",
        "preferred-pod-affinity", "preferred-pod-anti-affinity"]
    for t in templates.values():
        assert (t["cpu_milli"], t["memory_bytes"]) == (100, 500 << 20)
    assert templates["default"]["labels"] == {}
    assert templates["pod-affinity"]["pod_affinity"] == [
        {"match_labels": {"color": "blue"}, "topology_key": ZONE}]
    assert templates["pod-anti-affinity"]["pod_anti_affinity"] == [
        {"match_labels": {"color": "green"}, "topology_key": HOST}]
    assert templates["preferred-pod-affinity"]["preferred_pod_affinity"] == [
        [1.0, {"match_labels": {"color": "red"}, "topology_key": HOST}]]
    assert templates["preferred-pod-anti-affinity"][
        "preferred_pod_anti_affinity"] == [
        [1.0, {"match_labels": {"color": "yellow"}, "topology_key": HOST}]]
    assert config["assumed"] and config["from_source"]
    assert len(config["guarantees"]) == 5
    assert all(v is True for v in config["guarantees"].values())
    # the cell's own file keeps the reference's tolerance; only the
    # rehearsal's widens it, and says why
    assert set(config["control"]) == {"terms", "preferred"}
    assert "preferred_tolerance" in load(BENCH, "configs", CONFIG + ".json")[
        "control"]
    (stream,) = load(BENCH, "traffic", "constraints-open.json")["streams"]
    assert (stream["kind"], stream["rate"], stream["per_template"],
            stream["jitter"], stream["warm_sizes"], stream["warm_audits"]
            ) == ("constraint_bursts", 1, 20, 0.2, [1, 2, 1], 1)
    assert "edge_check" not in load(BENCH, "traffic", "constraints-open.json")
    assert "whatif_check" not in load(BENCH, "traffic",
                                      "constraints-open.json")

    # the cell reports what skew-36k-5k reports, end to end and per layer
    def reports(name):
        return {m["name"] for m in full["end_to_end"] + full["per_layer"]
                if name in m.get("workloads", [name])}
    assert reports(STANDS_FOR) == reports("skew-36k-5k")
    assert {"decision_p50_ms", "decision_p90_ms", "setup_s"} <= reports(
        STANDS_FOR)
    # the six files are there, read by the readers the benchmark has
    for name in constraints_manifest.TERMS:
        spec = load(BENCH, "layer_metrics", name + ".json")
        assert spec["reader"] in ("span_totals", "metrics_delta"), name


def test_the_population_is_the_configuration_s_at_full_size():
    config = load(BENCH, "configs", "schedperf-mixed-10k-5k.json")
    ledger = reference_constraints.Ledger(config, 2**31 + 5)
    pgs, pods = ledger.make_population()
    ledger.add(pgs, pods)
    assert pgs == [] and len(pods) == 10000
    assert {name: len(t) for name, t in ledger.live.items()} == dict.fromkeys(
        ledger.templates, 2000)
    # template by template, as the source creates them
    assert [p["annotations"]["bench/template"] for p in pods[::2000]] == list(
        ledger.templates)
    termed = [p for p in pods if "affinity" in p]
    assert len(termed) == 8000
    assert sum("pod_affinity" in p["affinity"]
               or "pod_anti_affinity" in p["affinity"] for p in termed) == 4000
    # 5% of the cluster's CPU requested: nothing is infeasible
    assert sum(c for c, _, _ in ledger.pods.values()) / int(
        ledger.alloc[:, 0].sum()) == 0.05
    nodes = ledger.node_dicts()
    assert len(nodes) == 5000
    assert {n["labels"][ZONE] for n in nodes} == {"zone1"}
    assert len({n["labels"][HOST] for n in nodes}) == 5000
    # the wire form is the program's own (api/serialize.py)
    sys.path.insert(0, REPO)
    from kube_batch_tpu.api.serialize import pod_from_dict

    pod = pod_from_dict(pods[2000])
    assert pod.labels == {"color": "blue"}
    assert pod.affinity.pod_affinity[0].topology_key == ZONE
    # the reference places the population itself with every count zero,
    # and counts what a placement that ignores the terms does
    small = load(BENCH, "configs", CONFIG + ".json")
    tiny = reference_constraints.Ledger(small, 3)
    _, tiny_pods = tiny.make_population()
    tiny.add([], tiny_pods)
    keys = [tiny.key(p) for p in tiny_pods]
    exact = tiny.world.check_binds(tiny.world.place(keys))
    assert exact == dict.fromkeys(exact, 0)
    blind = tiny.world.check_binds(
        tiny.world.place(keys, mode="ignore_terms"))
    assert blind["anti_affinity_violations"] > 0


def test_the_rehearsal_is_correct_and_reads_the_six(lines):
    for trace in ("0", "1"):
        line, stdout = lines[trace]
        assert line["correct"] is True and line["failed"] == 0, stdout[-3000:]
        assert line["attempted"] == 12
        assert line["metrics"] == {}     # no CPU number under a device name
        notes = json.loads(next(
            ln for ln in stdout.splitlines() if ln.startswith("notes: "))[7:])
        assert notes["term_counts"] == dict.fromkeys(notes["term_counts"], 0)
        # both controls wrong
        assert notes["control_terms_violations"] > 0
        assert notes["control_preferred_wrong"] is True
        assert notes["program_from_reference"] <= notes["preferred_tolerance"]
        assert notes["drained"] is True
    got = lines["0"][0]["rehearsal"]
    for name in ("cpu_decision_p50_ms", "cpu_decision_p90_ms", "cpu_setup_s"):
        assert got[name]["value"] > 0, name
    got = lines["1"][0]["rehearsal"]
    assert got["cpu_affinity_mask_ms"]["value"] > 0
    assert got["cpu_affinity_plane_update_ms"]["value"] > 0
    assert got["cpu_affinity_rows_per_cycle"]["value"] > 0
    assert got["cpu_inter_pod_exclusions_per_cycle"]["value"] >= 0
    assert got["cpu_host_fallback_share"]["value"] == 0.0
    assert got["cpu_slow_replay_jobs_per_cycle"]["value"] == 0.0
    for name in ("cpu_host_replay_ms", "cpu_solve_dispatches_per_cycle",
                 "cpu_compiles_in_window", "cpu_device_wait_ms",
                 "cpu_park_floor_ms", "cpu_generator_late_ms"):
        assert name in got, name


def test_a_program_without_the_series_reports_nothing_and_does_not_raise():
    """The parent has neither the series nor the spans: the span readers
    find nothing and leave the metric out; the counter ratios read 0.0 over
    a denominator the parent has and nothing without one."""
    from readers import metrics_delta, span_totals

    cycles = ("volcano_cycle_stage_latency_milliseconds_count",
              'stage="session_open"')
    decided = ("volcano_arrival_to_decision_latency_milliseconds_count", "")
    run = types.SimpleNamespace(
        metrics_pages={"window": ({cycles: 10.0, decided: 100.0},
                                  {cycles: 60.0, decided: 5100.0})},
        span_seconds={"window": 50.0},
        trace_states=({"span_ms": {}, "span_counts": {}},
                      {"span_ms": {"device_wait": 5.0},
                       "span_counts": {"device_wait": 1}}))
    for name in ("affinity_mask_ms", "affinity_plane_update_ms"):
        spec = load(BENCH, "layer_metrics", name + ".json")
        assert span_totals.read(spec, run) is None, name
    for name in ("affinity_rows_per_cycle", "inter_pod_exclusions_per_cycle",
                 "host_fallback_share", "slow_replay_jobs_per_cycle"):
        spec = load(BENCH, "layer_metrics", name + ".json")
        assert metrics_delta.read(spec, run) == 0.0, name
    run.metrics_pages = {"window": ({}, {})}
    for name in ("affinity_rows_per_cycle", "host_fallback_share"):
        spec = load(BENCH, "layer_metrics", name + ".json")
        assert metrics_delta.read(spec, run) is None, name


class SecondGreen(server_mod.Server):
    """The served path with one green pod reported on another's node: a
    scheduler that bound against a required anti-affinity term."""

    def request(self, method, path, body=None, timeout=120.0):
        resp = super().request(method, path, body, timeout)
        if path.startswith("/v1/bindings") and getattr(self, "greens", None):
            rows = [r for r in resp if r["pod"] in self.greens]
            if len(rows) >= 2 and not hasattr(self, "moved"):
                self.moved = (rows[-1]["pod"], rows[0]["node"])
            for r in rows:   # the same lie at every read
                if r["pod"] == getattr(self, "moved", (None,))[0]:
                    r["node"] = self.moved[1]
        return resp

    def raw(self, method, path, data=None, timeout=120.0):
        if (method, path) == ("POST", "/v1/pods"):
            self.greens = getattr(self, "greens", set()) | {
                f"{p['namespace']}/{p['name']}" for p in json.loads(data)
                if p.get("labels", {}).get("color") == "green"}
        return super().raw(method, path, data, timeout)


def test_a_second_green_pod_on_a_node_is_not_correct(tmp_path, manifest_path,
                                                     monkeypatch, capsys):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=1")
    args = harness.argparse.Namespace(
        workload=CELL, seed=7, seconds=3.0, trace=0, platform="cpu")
    out = tmp_path / "out"
    out.mkdir()
    line = harness.run_cell(args, harness.load_json(manifest_path), str(out),
                            server_factory=SecondGreen)
    printed = capsys.readouterr().out
    assert line["correct"] is False
    assert "overfit_binds: 1 <= 0" in printed and "NOT correct" in printed
    for name in ("nodes_over", "double_binds", "unknown_pods", "unbound"):
        assert f"{name}: 0 <= 0" in printed, name


def test_a_bind_beside_a_pod_deleted_since_is_counted():
    """The walk keeps a deleted pod until its delete: a green pod bound
    beside a green pod that a LATER burst deleted is a violation though the
    end state no longer shows it; beside one deleted BEFORE it was posted is
    none; and where the client cannot say which came first (the burst was
    never seen decided before the next DELETE), it is not counted."""
    config = load(BENCH, "configs", CONFIG + ".json")

    def ledger_after_two_bursts(decided_1):
        ledger = reference_constraints.Ledger(config, 3)
        _, pods = ledger.make_population()
        ledger.add([], pods)
        rng = np.random.default_rng(5)
        placed = ledger.world.place([ledger.key(p) for p in pods])
        rows = [{"pod": k, "node": n, "seq": i + 1}
                for i, (k, n) in enumerate(placed)]
        ledger.note_binds(rows)
        greens = {}
        for burst, (sent, decided) in enumerate(
                ((10.0, decided_1), (11.0, 11.4)), start=1):
            old = ledger.oldest_of_each(2)
            new = ledger.make_burst(2, rng)
            ledger.retire([], old)
            ledger.add([], new, burst=True)
            ledger.delete_sent[burst], ledger.decided[burst] = sent, decided
            gone = {ledger.key(p) for p in old}
            rows = [r for r in rows if r["pod"] not in gone]
            live = [(r["pod"], r["node"]) for r in rows]
            for k, n in ledger.world.place(
                    [ledger.key(p) for p in new], bound=live):
                rows.append({"pod": k, "node": n, "seq": len(placed) + len(
                    [r for r in rows if r["seq"] > len(placed)]) + 1})
            greens[burst] = (
                [ledger.key(p) for p in old
                 if p.get("labels", {}).get("color") == "green"],
                [ledger.key(p) for p in new
                 if p.get("labels", {}).get("color") == "green"])
        return ledger, rows, greens

    def verdict(ledger, rows):
        """(violations the walk counts, those a walk of the end state's live
        pods alone would count, the ambiguous binds)."""
        ledger.check_binds(rows)
        live = ledger.world.check_binds(ledger.in_order(rows))
        return (ledger.term_counts["anti_affinity_violations"],
                live["anti_affinity_violations"],
                ledger.term_counts["ambiguous_binds"])

    ledger, rows, greens = ledger_after_two_bursts(10.5)
    assert verdict(ledger, rows) == (0, 0, 0)
    assert ledger.term_counts["unbound"] == 0
    node_of = {k: n for k, (_, n) in ledger.seen_binds.items()}
    newcomer, departed = greens[1][1][0], greens[2][0][0]

    def moved(rows, pod, node):
        return [dict(r, node=node) if r["pod"] == pod else r for r in rows]

    # a green pod of burst 1 on the node of a green pod burst 2 deleted: one
    # more than the end state shows
    ledger, rows, _ = ledger_after_two_bursts(10.5)
    walked, live, unsure = verdict(
        ledger, moved(rows, newcomer, node_of[departed]))
    assert walked == live + 1 and unsure == 0
    # ... of a green pod burst 1 itself deleted: gone before the bind
    ledger, rows, _ = ledger_after_two_bursts(10.5)
    walked, live, _ = verdict(
        ledger, moved(rows, newcomer, node_of[greens[1][0][0]]))
    assert walked == live
    # burst 1 never seen decided before burst 2's DELETE: not provable
    ledger, rows, _ = ledger_after_two_bursts(None)
    walked, live, unsure = verdict(
        ledger, moved(rows, newcomer, node_of[departed]))
    assert walked == live and unsure > 0


class NoBindOrder(server_mod.Server):
    """The parent: ``/v1/bindings`` takes no query."""

    def raw(self, method, path, data=None, timeout=120.0):
        if path == "/v1/bindings?seq=1":
            return 404, b"not found"
        return super().raw(method, path, data, timeout)


def test_a_program_without_the_bind_order_ends_the_run_before_the_load(
        tmp_path, manifest_path, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=1")
    args = harness.argparse.Namespace(
        workload=CELL, seed=7, seconds=3.0, trace=0, platform="cpu")
    out = tmp_path / "out"
    out.mkdir()
    posted = []

    class Watched(NoBindOrder):
        def raw(self, method, path, data=None, timeout=120.0):
            posted.append((method, path))
            return super().raw(method, path, data, timeout)

    with pytest.raises(server_mod.RunFailure, match="bindings.seq=1"):
        harness.run_cell(args, harness.load_json(manifest_path), str(out),
                         server_factory=Watched)
    assert not any(method == "POST" for method, _ in posted)


@pytest.fixture(scope="module")
def plans():
    """The window's plan at the cell's own size, for three seeds."""
    config = load(BENCH, "configs", "schedperf-mixed-10k-5k.json")
    (params,) = load(BENCH, "traffic", "constraints-open.json")["streams"]
    out = []
    for seed in (1, 2147484033, 2**31 + 77):
        ctx = types.SimpleNamespace(
            config=config, ledger=None, failure=RuntimeError,
            server=types.SimpleNamespace(get=lambda path: []))
        out.append((ctx, constraint_bursts.Stream(ctx, params, seed, 50.0)))
    return out


def test_the_window_is_the_same_work_for_every_seed(plans):
    orders, gap_orders = set(), set()
    for ctx, stream in plans:
        assert isinstance(ctx.ledger, reference_constraints.Ledger)
        assert len(stream.window) == stream.n == 50
        for pods, _ in stream.window:
            assert len(pods) == 100
            names = [p["annotations"]["bench/template"] for p in pods]
            assert {n: names.count(n) for n in names} == dict.fromkeys(
                ctx.ledger.templates, 20)
        orders.add(tuple(p["annotations"]["bench/template"]
                         for p in stream.window[0][0]))
        # gaps from the one set of 50 (0.8-1.2 s), in seeded order
        gaps = np.diff(stream.due)
        allowed = 1.0 + 0.2 * np.linspace(-1.0, 1.0, 50)
        assert np.abs(gaps[:, None] - allowed[None, :]).min(axis=1).max() < 1e-9
        gap_orders.add(tuple(np.round(gaps, 9)))
    assert len(orders) == 3 and len(gap_orders) == 3
