"""``BENCHMARK.json`` with the per-layer metrics of the interruption ledger
(PR 37), which it cannot list yet: ``test_span_plane.py`` pins PR 24's
fourteen as the manifest's last entries (PERF.md section 7).  Nine read the
five scheduling cells; the three of the garbage collector have a twin each
(``.read``) for ``whatif-50k-5k``, where a pause stops the batcher's thread
as it stops the loop's and the metric moves ``whatif_p50_ms``.

    python benchmark/tests/interruptions_manifest.py > chiprun_out/interruptions.json
    python benchmark/run.py --manifest chiprun_out/interruptions.json \\
        --workload steady-50k-5k --seed 1 --seconds 50 --trace 1

None of the twelve needs the profiler, and ``run.py`` fetches ``/metrics``
and ``/v1/trace`` before and after every window, but its result line holds
per-layer metrics in a ``--trace 1`` run only.  ``--run`` drives ``run.py``
through this manifest (or the ``--manifest`` given, which then has to list
the twelve) and, in a ``--trace 0`` run, prints what the twelve
read beside the rows of the program's table of cycles and what the client
saw of the same bursts (one line, ``interruptions: {...}``, before the
result line); the whole tree of the slowest deciding cycle is among the
run's notes (``slowest_tree``):

    python benchmark/tests/interruptions_manifest.py --run \\
        --workload steady-50k-5k --seed 1 --seconds 50 --trace 0
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))

LOOP = "loop"
READ = "whatif-50k-5k"
#: name -> (unit, source, moves); all of the layer "loop", all better lower
#: but the share of the slowest decision that has a name
INTERRUPTIONS = {
    "gc_pause_share": ("share", "program_counter", "decision_p90_ms"),
    "gc_full_collections_in_window": (
        "count", "program_counter", "decision_p90_ms"),
    "gc_full_pause_s": ("s", "program_counter", "decision_p90_ms"),
    "loop_stalls_in_window": ("count", "program_counter", "decision_p90_ms"),
    "loop_stall_s_in_window": ("s", "program_counter", "decision_p90_ms"),
    "slow_decisions_in_window": (
        "count", "program_counter", "decision_p90_ms"),
    "loop_between_ms_per_s": ("ms/s", "program_span", "decision_p50_ms"),
    "slowest_decision_ms": ("ms", "program_span", "decision_p90_ms"),
    "slowest_decision_named_share": (
        "share", "program_span", "decision_p90_ms"),
}
HIGHER = {"slowest_decision_named_share"}
#: the garbage collector's three, as the read plane's cell reports them
FOR_THE_READ_PLANE = ("gc_pause_share", "gc_full_collections_in_window",
                      "gc_full_pause_s")


def entries(cells: list, read_cells: list) -> list:
    """The nine as ``per_layer`` entries reported in ``cells``, then the
    three twins reported in ``read_cells``."""
    def entry(name, base, moves, workloads):
        unit, source, _ = INTERRUPTIONS[base]
        return {"name": name, "unit": unit,
                "better": "higher" if base in HIGHER else "lower",
                "source": source, "layer": LOOP, "moves": moves,
                "workloads": list(workloads)}

    return ([entry(name, name, moves, cells)
             for name, (_, _, moves) in INTERRUPTIONS.items()]
            + [entry(name + ".read", name, "whatif_p50_ms", read_cells)
               for name in FOR_THE_READ_PLANE])


def derive() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    deciding = next(m["workloads"] for m in manifest["end_to_end"]
                    if m["name"] == "decision_p90_ms")
    manifest["per_layer"] += entries(deciding, [READ])
    return manifest


def run_through(argv: list) -> int:
    """``run.py`` with this manifest; a ``--trace 0`` run also prints what
    the ledger's readers read (module docstring)."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import run as harness

    if "--manifest" not in argv:    # else: one that lists the twelve
        out = os.path.join(REPO, "chiprun_out")
        os.makedirs(out, exist_ok=True)
        path = os.path.join(out, "interruptions.json")
        with open(path, "w") as f:
            json.dump(derive(), f)
        argv = ["--manifest", path, *argv]
    names = {e["name"] for e in entries([], [])}
    result_line = harness.result_line

    def and_the_ledger(args, manifest, cell, runtime, memory, run):
        line = result_line(args, manifest, cell, runtime, memory, run)
        if not args.trace:
            read = harness.read_metrics(manifest, "per_layer",
                                        "layer_metrics", cell["name"], run)
            print("interruptions: " + json.dumps(dict(
                {k: v["value"] for k, v in read.items() if k in names},
                **window_rows(run))))
        return line

    check_answers = harness.check_answers

    def and_the_slowest_tree(run, mix, seed):
        # asked here, after the window and while the server still runs
        from readers import cycle_table

        rows = cycle_table.rows_added(*run.trace_states)
        if rows:
            slowest = max(rows, key=lambda r: r["worst_ms"])["cycle"]
            run.notes["slowest_tree"] = run.server.get(
                f"/v1/trace/cycles/{slowest}")
        return check_answers(run, mix, seed)

    harness.result_line = and_the_ledger
    harness.check_answers = and_the_slowest_tree
    return harness.main(argv)


def window_rows(run) -> dict:
    """The window as both sides saw it, for matching a burst the client
    found slow to the cycle that decided it.  ``rows``: the deciding rows
    of the program's table that the window added, with the time of their
    worst bind in seconds from the window's opening (the program's
    telemetry clock and the client's are both the machine's monotonic
    clock).  ``interrupted``: the window's rows, deciding or not, that
    carry a full collection, a compile or a stall (a pause in a burst's
    ingest is charged to the cycle its first request woke, which may
    decide nothing).  ``client``: each burst's latency, lateness and POST time as
    the stream sampled them.  ``steps``: when the scraped decisions
    counter rose, and by how much."""
    from readers import cycle_table

    before, after = run.trace_states
    interrupted = {
        r["cycle"]: r for r in after.get("cycles", []) + after.get("kept", [])
        if r.get("cycle") is not None
        and r["cycle"] >= before.get("next_cycle", 0)
        and (r["gc_full"] or r["compile_ms"] or r["stall_ms"])}
    sc, t0 = run.scraper, run.t_window
    steps = [[round(t - t0, 4), c - p] for t, c, p in zip(
        sc.times[1:], sc.counts[1:], sc.counts) if c != p and t >= t0]
    return {
        "rows": [dict(r, at_s=round(r["worst_at"] - t0, 4))
                 for r in cycle_table.rows_added(*run.trace_states)],
        "interrupted": [dict(r, t0_s=round(r["t0"] - t0, 4))
                        for _, r in sorted(interrupted.items())],
        "client": {k: [round(v, 3) for v in run.samples.get(k, [])]
                   for k in ("burst_latency_ms", "generator_late_ms",
                             "ingest_post_ms")},
        "steps": steps, "compiles": after.get("compiles", []),
    }


if __name__ == "__main__":
    if sys.argv[1:2] == ["--run"]:
        sys.exit(run_through(sys.argv[2:]))
    print(json.dumps(derive(), indent=1))
