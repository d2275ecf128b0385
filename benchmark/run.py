"""One cell, one run:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process never imports JAX.  It starts ONE child (``serve.py``: the
program's normal entry point with the shipped conf and every default on),
learns the device from ``GET /version`` (anything but a TPU with the chips
the cell asks for ends the run with no result), loads the cell's cluster
from ``--seed``, warms every shape the window will use, measures for
``--seconds``, checks the answers against the plain reference
(reference.py), prints each number compared beside its limit, and prints
as its last line the one JSON object the benchmark's contract asks for.

Everything that belongs to one cell is data the harness finds by name:
``BENCHMARK.json`` (or ``--manifest``) names the cell's configuration file
and its traffic mix ``traffic/<traffic>.json``; the mix names its streams
``streams/<kind>.py``; each metric has ``end_to_end/<name>.json`` or
``layer_metrics/<name>.json``, which names its reader ``readers/<reader>.py``.
See README.md.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import reference  # noqa: E402
from observe import Scraper, parse_metrics  # noqa: E402
from server import RunFailure, Server  # noqa: E402

#: the profiled part of a --trace 1 window: starts this long after the
#: window opens and lasts this long (a trace is large and tracing slows the
#: host, so it is short and the end-to-end numbers come from --trace 0)
PROFILE_AFTER_S = 2.0
PROFILE_FOR_S = 4.0


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Observed:
    """What one run saw; streams write it, readers read it."""

    def __init__(self, server, ledger, scraper, config, window_s):
        self.server, self.ledger, self.scraper = server, ledger, scraper
        self.config = config
        self.window_s = window_s
        self.t_window = None
        self.samples: dict = {}      # name -> [client-clock samples]
        self.scalars: dict = {}      # setup_s, load_s, cold_drain_s, ...
        self.notes: dict = {}
        self.numbers: dict = {}      # compared with reference.LIMITS
        self.metrics_pages: dict = {}  # span -> (page before, page after)
        self.span_seconds: dict = {}
        self.trace_states = None
        self.cycle_samples: dict = {}  # cycle id -> last_cycle of /v1/trace
        self.profile = None
        self.attempted = 0
        self.failed = 0
        self._posted = 0
        self._lock = threading.Lock()

    def posted(self, n: int = 0) -> int:
        """Count ``n`` more pods as posted; the cumulative count, which the
        decisions counter has to reach."""
        with self._lock:
            self._posted += n
            return self._posted

    @staticmethod
    def failure(message: str) -> RunFailure:
        return RunFailure(message)

    def page(self) -> dict:
        status, text = self.server.raw("GET", "/metrics")
        if status != 200:
            raise RunFailure(f"/metrics answered {status}")
        return parse_metrics(text)


def find_cell(manifest: dict, name: str):
    cell = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if cell is None:
        raise RunFailure(f"no workload {name!r} in the manifest")
    conf = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    return cell, load_json(REPO, conf["file"]), load_json(
        HERE, "traffic", cell["traffic"] + ".json")


def metrics_of(manifest: dict, section: str, cell: str) -> list:
    """The manifest's metrics of ``section`` that this cell reports."""
    return [m for m in manifest[section]
            if "workloads" not in m or cell in m["workloads"]]


def read_metrics(manifest, section, folder, cell, run) -> dict:
    out = {}
    for m in metrics_of(manifest, section, cell):
        spec = load_json(HERE, folder, m["name"] + ".json")
        reader = importlib.import_module("readers." + spec["reader"])
        value = reader.read(spec, run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# --------------------------------------------------------------------------
# the phases of a run
# --------------------------------------------------------------------------


def load_cluster(run: Observed) -> None:
    """Queues, nodes and the configuration's population, then the barrier;
    waits for the cold drain by the decisions counter."""
    server, ledger, config = run.server, run.ledger, run.config
    t0 = time.monotonic()
    server.send("POST", "queues", ledger.queue_dicts())
    server.send("POST", "nodes", ledger.node_dicts(), batch=1000)
    pgs, pods = ledger.make_population()
    server.send("POST", "podgroups", pgs, batch=2500)
    server.send("POST", "pods", pods, batch=5000)
    ledger.add(pgs, pods)
    server.request("POST", "/v1/sync", {})
    t_synced = time.monotonic()
    run.scalars["load_s"] = t_synced - t0
    run.scraper.start()
    if run.scraper.wait_count(run.posted(len(pods)), 900.0) is None:
        raise RunFailure(
            f"cold drain: {run.scraper.counts[-1:]} of {len(pods)} pods "
            f"decided at the deadline")
    run.scalars["cold_drain_s"] = time.monotonic() - t_synced


def check_steady(run: Observed, mix: dict) -> None:
    """Set-up is over only when the program the window will time is the
    one running."""
    want = mix.get("steady_dispatch")
    if not want:
        return
    deadline = time.monotonic() + 30.0
    while True:
        tally = run.server.get("/v1/trace")["solve_dispatches"]
        if any(want in key for key in tally):
            return
        if time.monotonic() > deadline:
            raise RunFailure(
                f"warm-up never engaged a {want!r} program: {tally}")
        time.sleep(0.25)


def profile_window(run: Observed, out_dir: str) -> None:
    """Bracket PROFILE_FOR_S of the window with the child's profiler."""
    time.sleep(max(0.0, run.t_window + PROFILE_AFTER_S - time.monotonic()))
    trace_dir = os.path.join(out_dir, "profile")
    before, t0 = run.page(), time.monotonic()
    run.server.tell(f"trace_start {trace_dir}")
    time.sleep(PROFILE_FOR_S)
    after, t1 = run.page(), time.monotonic()
    run.server.ask(f"trace_stop {os.path.join(out_dir, 'trace_stopped')}",
                   os.path.join(out_dir, "trace_stopped"), timeout=120.0)
    run.metrics_pages["profile"] = (before, after)
    run.span_seconds["profile"] = t1 - t0


def sample_cycles(run: Observed, period_s: float = 0.25) -> None:
    """While the window runs, keep every cycle that /v1/trace shows as its
    last (--trace 1 only: the page is built under the tracer's lock)."""
    t_end = run.t_window + run.window_s
    while time.monotonic() < t_end:
        last = run.server.get("/v1/trace").get("last_cycle")
        if last:
            run.cycle_samples[last["cycle"]] = last
        time.sleep(period_s)


def dump_series(run: Observed, out_dir: str) -> None:
    """Leave what the client saw beside the log: D(t) from the window's
    opening on, and every sample.  Read by nobody but whoever asks why."""
    t0, sc = run.t_window, run.scraper
    first = next((i for i, t in enumerate(sc.times) if t >= t0 - 1.0), 0)
    with open(os.path.join(out_dir, "series.json"), "w") as f:
        json.dump({"decisions": [[round(t - t0, 4), c] for t, c in zip(
            sc.times[first:], sc.counts[first:])],
            "samples": run.samples}, f)


def reduce_profile(out_dir: str):
    """trace_reduce.py in a process of its own that can never take the
    chip (the server is gone by now, and JAX is held to the CPU)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    got = subprocess.run(
        [sys.executable, os.path.join(HERE, "trace_reduce.py"),
         os.path.join(out_dir, "profile")],
        env=env, capture_output=True, timeout=300)
    if got.returncode:
        raise RunFailure(f"trace_reduce.py failed: {got.stderr[-500:]!r}")
    return json.loads(got.stdout.splitlines()[-1])


#: a gang is placed in this many bidding rounds of one cycle at most, each
#: of which fills one more node (ops/assignment.py AllocateConfig.rounds), so
#: "feasible" means "would bind in one cycle": a what-if's answer is unique,
#: whatever order the nodes are tried in, only where no more nodes than this
#: are needed.  The exact comparison stays inside that domain.
ONE_CYCLE_NODES = 6
#: how often a what-if that disagrees with the ledger is asked again, a
#: second apart, before it counts
LEASE_TICKS = 3


def whatif_bodies(run: Observed, spec: dict, seed: int, used) -> list:
    """The seeded probes and sweeps asked after the window.  Every other
    one comes from the configuration's mix, a few members that the
    half-empty cluster holds thousands of times over.  The rest sit at the
    edge of what the cluster holds: a member as large as the k-th
    roomiest node's free CPU, so that only a handful of nodes fit one
    member each, the count asked is the last that fits or the first that
    does not, and one milli-core of error in the capacity plane flips the
    answer."""
    rng = np.random.default_rng([seed, 0x5E])
    mix, ledger = run.config["request_mix"], run.ledger
    queues = [q["name"] for q in run.config["queues"]]
    free_cpu = np.sort((ledger.alloc - used)[:, 0])[::-1]
    mem = float(min(mix["memory_bytes"]))

    def edge():
        for k in range(int(rng.integers(1, ONE_CYCLE_NODES)), 0, -1):
            req = {"cpu": float(max(free_cpu[k - 1], 1)), "memory": mem}
            room = int(reference.slots(
                ledger.alloc, used, reference.request_vec(req)).sum())
            if room <= ONE_CYCLE_NODES:
                break
        return req, room

    out = []
    for i in range(spec["probes"] + spec["sweeps"]):
        queue = queues[int(rng.integers(len(queues)))]
        if i >= spec["probes"]:
            req, _ = edge()
            out.append(("/v1/whatif/sweep", {
                "queue": queue, "requests": req,
                "max_count": spec["max_count"]}))
        elif i % 2:
            req, room = edge()
            count = max(1, min(ONE_CYCLE_NODES, room + int(rng.integers(0, 2))))
            out.append(("/v1/whatif", {
                "queue": queue, "count": count, "requests": req}))
        else:
            out.append(("/v1/whatif", {
                "queue": queue,
                "count": int(rng.integers(1, ONE_CYCLE_NODES + 1)),
                "requests": {"cpu": float(rng.choice(mix["cpu_milli"])),
                             "memory": float(rng.choice(mix["memory_bytes"]))}}))
    return out


def check_whatifs(run: Observed, spec: dict, seed: int, used) -> None:
    """Ask the seeded set on the quiescent cluster as the window asked its
    own, from ``clients`` threads at once, so that the questions share
    dispatches of the read plane, and compare every answer with the
    ledger.  The answers are unique there however they are batched.  A
    lease may trail the last commit by one idle tick, so the questions
    that disagree are asked again a second later, up to LEASE_TICKS
    times; a disagreement that stays counts (a wrong answer stays wrong:
    nothing changes on the cluster meanwhile)."""
    ledger = run.ledger
    bodies = whatif_bodies(run, spec, seed, used)

    def faults_of(path: str, body: dict, resp: dict) -> list:
        if path.endswith("sweep"):
            return reference.check_sweep(resp, body, ledger.alloc, used)
        return reference.check_probe(resp, body, ledger.alloc, used,
                                     ledger.node_index)

    def ask(question):
        path, body = question
        status, raw, _ = run.server.post_until_answered(
            path, json.dumps(body).encode())
        if status != 200:
            raise RunFailure(f"POST {path} answered {status}: {raw[:300]!r}")
        return question, faults_of(path, body, json.loads(raw))

    def batches(page: dict) -> tuple:
        return tuple(page.get(("volcano_whatif_batch_size" + k, ""), 0.0)
                     for k in ("_sum", "_count"))

    before = batches(run.page())
    with ThreadPoolExecutor(int(spec["clients"])) as pool:
        wrong = [(q, f) for q, f in pool.map(ask, bodies) if f]
        after = batches(run.page())
        first_round, ticks = len(wrong), 0
        while wrong and ticks < LEASE_TICKS:
            time.sleep(1.0)
            ticks += 1
            wrong = [(q, f) for q, f in pool.map(ask, [q for q, _ in wrong])
                     if f]
    run.numbers["whatif_wrong"] = len(wrong)
    run.notes["whatif_checked"] = len(bodies)
    run.notes["whatif_wrong_at_first"] = first_round
    run.notes["whatif_lease_ticks"] = ticks
    if after[1] > before[1]:
        run.notes["whatif_check_batch"] = (
            (after[0] - before[0]) / (after[1] - before[1]))
    if wrong:
        run.notes["whatif_wrong_examples"] = [
            {"path": p, "body": b, "faults": f} for (p, b), f in wrong[:3]]
    # the control: the reference in the program's place, over the plane as
    # a lower precision reads it back; has to get some of them wrong
    stored = reference.plane(used, run.config["control"]["whatif"])
    run.notes["control_whatif_wrong"] = sum(
        1 for path, body in bodies if faults_of(path, body, reference.answer(
            body, ledger.alloc, stored, ledger.node_names,
            path.endswith("sweep"))))


def worst(a: dict, b: dict) -> dict:
    return {k: max(a.get(k, 0), b.get(k, 0)) for k in {*a, *b}}


def bound_rows(server) -> list:
    return [b for b in server.get("/v1/bindings")
            if b["status"] in reference.BOUND_STATUSES]


def edge_round(run: Observed, spec: dict, binds: list, numbers: dict, used):
    """After the window's own binds have been counted: ``rounds`` pairs of
    pods that sit on a fit edge (reference.edge_requests), sent one pair
    after another through the same served path, then the counts again.
    Returns (the worse of the counts before and after, used after)."""
    server, ledger, alloc = run.server, run.ledger, run.ledger.alloc
    t0 = time.monotonic()
    # pods that ask for less than the program's comparison quantum make
    # the nodes' room differ by less than it, inside which the program may
    # place a pod either way: they go first, and the edge is clean
    pgs, pods = ledger.under(reference.FIT_QUANTUM_MILLI)
    if pods:
        server.send("DELETE", "pods", pods)
        server.send("DELETE", "podgroups", pgs)
        ledger.retire(pgs, pods)
        gone, deadline = {ledger.key(p) for p in pods}, time.monotonic() + 60.0
        while True:
            binds = bound_rows(server)
            if not any(b["pod"] in gone for b in binds):
                break
            if time.monotonic() > deadline:
                raise RunFailure("deleted pods stayed bound")
            time.sleep(0.2)
        before, used = ledger.check_binds(binds)
        numbers = worst(numbers, before)
    mem = int(min(run.config["request_mix"]["memory_bytes"]))
    requests = reference.edge_requests(alloc, used, mem, int(spec["rounds"]))
    rows = [(ledger.node_index[b["node"]],
             np.array(ledger.pods[b["pod"]][:2] + (1,), np.int64))
            for b in binds
            if b["pod"] in ledger.pods and b["node"] in ledger.node_index]
    # the reference in the program's place, over the exact plane (must
    # bind the 12 and leave the other 12) and over one summed in the
    # control's precision (must get some wrong)
    precision = run.config["control"]["edge"]
    run.notes["edge_reference"] = reference.edge_control(
        alloc, used, requests, "exact")
    run.notes["control_edge"] = reference.edge_control(
        alloc, reference.summed_plane(alloc, rows, precision), requests,
        precision)

    def one(req):
        """A pod of its own, as the configuration's population is made:
        a one-member gang in one of its queues, or a plain pod."""
        if run.config["population"]["kind"] == "gangs":
            return ledger.make_gangs(1, 1, 1, [int(req[0])], [int(req[1])])
        return [], ledger.make_pods(1, int(req[0]), int(req[1]))

    sent = 0
    for exact, over in requests:
        (over_pgs, over_pods), (pgs, pods) = one(over), one(exact)
        if pgs:
            server.send("POST", "podgroups", over_pgs + pgs)
        server.send("POST", "pods", over_pods + pods)
        ledger.add_unfit(over_pods)
        ledger.add(pgs, pods)
        sent += 1
        if run.scraper.wait_count(run.posted(1), 30.0) is None:
            break  # it shows as unbound below
    run.notes["edge_rounds"] = sent
    # the last over pod has to have had its chance: one more cycle
    time.sleep(0.5)
    after, used = ledger.check_binds(bound_rows(server))
    run.notes["edge_s"] = time.monotonic() - t0
    return worst(numbers, after), used


def check_answers(run: Observed, mix: dict, seed: int) -> None:
    """Everything `correct` rests on, after the drain."""
    server, ledger = run.server, run.ledger
    binds = bound_rows(server)
    numbers, used = ledger.check_binds(binds)
    run.notes["pods_live_and_bound"] = len(binds)
    if "edge_check" in mix:
        numbers, used = edge_round(run, mix["edge_check"], binds, numbers,
                                   used)
    run.numbers.update(numbers)
    decided = run.scraper.scrape_once()[1]
    run.numbers["counter_mismatch"] = abs(decided - run.posted())
    run.notes["decisions_counter"] = decided
    run.notes["pods_posted"] = run.posted()
    guard = server.get("/v1/guard")
    unhealthy = sum(1 for p in guard["paths"].values()
                    if p["state"] != "healthy")
    run.numbers["guard_dirty"] = (
        guard["trips_total"] + guard["failed_closed"]
        + guard["audits_mismatched"] + unhealthy
        + (0 if guard["enabled"] else 1))
    run.notes["guard_audits_run"] = guard["audits_run"]
    run.numbers["log_failures"] = server.log_failures()
    run.numbers["http_errors"] = run.scraper.errors
    if "whatif_check" in mix:
        check_whatifs(run, mix["whatif_check"], seed, used)


def run_cell(args, manifest: dict, out_dir: str,
             server_factory=Server) -> dict:
    """Drive one run; returns the result line's object."""
    t_start = time.monotonic()
    cell, config, mix = find_cell(manifest, args.workload)
    ledger = reference.Ledger(config, args.seed)
    server = server_factory(out_dir)
    scraper = Scraper(server, mix["scrape_period_ms"] / 1e3)
    run = Observed(server, ledger, scraper, config, float(args.seconds))
    try:
        runtime = server.wait_up(300.0)
        if runtime["platform"] != args.platform:
            raise RunFailure(
                f"the server runs on {runtime['platform']!r} "
                f"({runtime['device_kind']} x {runtime['device_count']}); "
                f"this run needs {args.platform!r}")
        if runtime["device_count"] < cell["chips"]:
            raise RunFailure(
                f"the cell needs {cell['chips']} chips, the server has "
                f"{runtime['device_count']}")
        peaks = load_json(HERE, "peaks.json")["devices"]
        if args.platform == "tpu" and runtime["device_kind"] not in peaks:
            raise RunFailure(
                f"no peaks for device kind {runtime['device_kind']!r} in "
                f"peaks.json")
        streams = [
            importlib.import_module("streams." + s["kind"]).Stream(
                run, s, args.seed, float(args.seconds))
            for s in mix["streams"]]
        load_cluster(run)
        for s in streams:
            s.warm()
        check_steady(run, mix)
        run.scalars["setup_s"] = time.monotonic() - t_start
        run.scalars["warm_s"] = (run.scalars["setup_s"] - run.scalars["load_s"]
                                 - run.scalars["cold_drain_s"])

        # ---- the measured window ----
        before, trace_before = run.page(), server.get("/v1/trace")
        run.t_window = time.monotonic()
        threads = [threading.Thread(target=_guarded, args=(s.run, run),
                                    name=f"stream-{i}", daemon=True)
                   for i, s in enumerate(streams)]
        if args.trace:
            for name, fn in (("profile", lambda: profile_window(run, out_dir)),
                             ("cycles", lambda: sample_cycles(run))):
                threads.append(threading.Thread(
                    target=_guarded, args=(fn, run), name=name, daemon=True))
        for t in threads:
            t.start()
        time.sleep(max(0.0, run.t_window + run.window_s - time.monotonic()))
        after, trace_after = run.page(), server.get("/v1/trace")
        run.notes["backlog_at_close"] = run.posted() - scraper.counts[-1]
        wakes = {k: after.get(("volcano_cycle_trigger_wakes_total",
                               f'trigger="{k}"'), 0.0)
                 - before.get(("volcano_cycle_trigger_wakes_total",
                               f'trigger="{k}"'), 0.0)
                 for k in ("ingest", "floor")}
        run.notes["wakes_in_window"] = wakes
        run.metrics_pages["window"] = (before, after)
        run.span_seconds["window"] = time.monotonic() - run.t_window
        run.trace_states = (trace_before, trace_after)
        for t in threads:
            t.join(timeout=180.0)
        if "stream_error" in run.notes:
            raise RunFailure(run.notes["stream_error"])
        if any(t.is_alive() for t in threads):
            raise RunFailure("a stream never ended")

        # ---- the drain, then the answers ----
        drained = scraper.wait_count(run.posted(), 60.0) is not None
        run.notes["drained"] = drained
        time.sleep(2 * scraper.period_s)
        for s in streams:
            s.finish()
        dump_series(run, out_dir)
        check_answers(run, mix, args.seed)
        scraper.stop()
        run.notes["compiles_in_window"] = (
            trace_after["retraces_attributed"]
            - trace_before["retraces_attributed"])
        run.notes["solve_dispatches"] = trace_after["solve_dispatches"]
        mem_file = os.path.join(out_dir, "memory_stats.json")
        server.ask(f"mem {mem_file}", mem_file)
        memory = load_json(mem_file)
    finally:
        server.stop()
    if args.trace:
        run.profile = reduce_profile(out_dir)
        with open(os.path.join(out_dir, "cycles.json"), "w") as f:
            json.dump(sorted(run.cycle_samples.values(),
                             key=lambda c: c["cycle"]), f)
    return result_line(args, manifest, cell, runtime, memory, run)


def _guarded(fn, run: Observed) -> None:
    try:
        fn()
    except Exception as e:  # noqa: BLE001 — carried to the main thread
        run.notes.setdefault("stream_error", f"{type(e).__name__}: {e}")


def result_line(args, manifest, cell, runtime, memory, run: Observed) -> dict:
    limits = reference.LIMITS
    print("numbers compared (value <= limit):")
    correct = True
    for name in sorted(run.numbers):
        ok = run.numbers[name] <= limits[name]
        correct = correct and ok
        print(f"  {name}: {run.numbers[name]} <= {limits[name]}"
              f"{'' if ok else '   <-- NOT correct'}")
    print("notes: " + json.dumps(run.notes, default=str))
    print("scalars: " + json.dumps(run.scalars))
    print("samples: " + json.dumps(
        {k: len(v) for k, v in run.samples.items()}))
    if args.trace:
        metrics = read_metrics(manifest, "per_layer", "layer_metrics",
                               cell["name"], run)
    else:
        metrics = read_metrics(manifest, "end_to_end", "end_to_end",
                               cell["name"], run)
    device = {
        "platform": runtime["platform"], "kind": runtime["device_kind"],
        "count": runtime["device_count"],
        "memory_peak_bytes": max(
            [int(m.get("peak_bytes_in_use", 0)) for m in memory] or [0]),
    }
    line = {"correct": bool(correct), "attempted": run.attempted,
            "failed": run.failed, "metrics": metrics, "device": device}
    if args.trace and run.profile is not None:
        if args.platform == "tpu" and not run.profile["busy_s"]:
            raise RunFailure("no operation ran on the device in the trace")
        device["busy_s"] = run.profile["busy_s"]
        device["window_s"] = run.profile["window_s"]
        line["breakdown"] = {
            "device_ops": run.profile["programs"] or run.profile["device_ops"],
            "idle_gaps": run.profile["idle_gaps"]}
    if args.platform != "tpu":
        # a rehearsal: nothing it timed may stand under a device metric's name
        line["rehearsal"] = {"cpu_" + k: v for k, v in metrics.items()}
        line["metrics"] = {}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--platform", default="tpu",
                    help="what the server must report; 'cpu' rehearses at a "
                         "tiny size and prints no device metric")
    ap.add_argument("--manifest", default=os.path.join(REPO, "BENCHMARK.json"))
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    try:
        manifest = load_json(args.manifest)
        if not os.path.isdir(os.path.join(REPO, "kube_batch_tpu")):
            raise RunFailure("the program is not in this checkout")
        out_dir = os.path.abspath(args.out or os.path.join(
            REPO, "chiprun_out", "bench", f"{args.workload}-t{args.trace}"))
        shutil.rmtree(out_dir, ignore_errors=True)
        os.makedirs(out_dir)
        if args.platform == "cpu":
            os.environ["JAX_PLATFORMS"] = "cpu"
        line = run_cell(args, manifest, out_dir)
    except (RunFailure, OSError, KeyError, ValueError) as e:
        print(f"benchmark run FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    if "jax" in sys.modules:
        print("benchmark run FAILED: this process imported jax",
              file=sys.stderr)
        return 1
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
