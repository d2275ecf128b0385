"""HTTP server + process run loop (cmd/kube-batch/app/server.go).

The reference serves Prometheus `/metrics` (+ pprof) on --listen-address
(server.go:96-99) and ingests cluster state through ten API-server informers
(cache.go:256-336). Standalone, the same listener carries both:

- GET  /metrics                — Prometheus text exposition (same metric names)
- GET  /healthz                — liveness
- GET  /version                — JSON: version, jax version, and the device
                                 this process runs on (platform, device_kind,
                                 device_count), native-library state,
                                 compile-cache directory
- POST/DELETE /v1/pods         — informer-shaped ingest (JSON bodies per
- POST/DELETE /v1/nodes          api/serialize.py); POST is add-or-update,
- POST/DELETE /v1/podgroups      matching the informers' upsert handlers
- POST/DELETE /v1/queues         (event_handlers.go).  A LIST body batches:
- POST        /v1/priorityclasses  the whole batch applies under one cache
- POST/DELETE /v1/poddisruptionbudgets  lock acquisition + one dirty-version
- POST/DELETE /v1/persistentvolumes     advance ({"ok":true,"applied":N})
- GET  /v1/queues              — queue list w/ podgroup phase counts (the
                                 Queue CRD status the CLI renders, list.go:51)
- GET  /v1/jobs                — podgroup phases/conditions
- GET  /v1/bindings            — pod→node decisions made so far
                                 (``?seq=1``: with each bind's number, the
                                 order the binds were made in)
- GET  /v1/guard               — result-integrity guard plane state (per-
                                 fast-path breaker, trips, audits, bundles)
- GET  /v1/trace               — cycle tracing plane: last cycle's span
                                 tree, flight-recorder ring stats, and the
                                 ring's solve dispatches tallied by
                                 mode + engaged fast paths
- GET  /v1/trace/cycles/<n>    — the whole span tree of cycle n, while the
                                 ring or the kept list holds it (the rows
                                 of `cycles` / `kept` on /v1/trace)
- GET  /v1/trace/dumps         — flight-recorder dump index; append
                                 /<name>/<trace.json|meta.json> to stream
                                 one dump's files (warm standbys and
                                 followers serve these too)
- GET  /v1/alerts              — guard trip-rate SLO alert state
- POST /v1/whatif              — batched what-if / admission probe against
                                 the resident snapshot (serve/; README
                                 "Query plane" for the schema)
- POST /v1/whatif/sweep        — server-side capacity sweep: binary-search
                                 the largest feasible replica count against
                                 ONE snapshot lease
- GET  /v1/evictions?since=N   — the standalone eviction feed
                                 (cache/evictions.py): what the scheduler
                                 ordered evicted, for the client (the
                                 kubelet) to terminate with DELETE /v1/pods
- GET  /v1/replicate?since=N   — the replication stream (replicate/): the
                                 leader's KBR1 frame for record N+1, a
                                 synthesized full snapshot when N fell off
                                 the ring, or a heartbeat when caught up

`Run` mirrors app.Run (server.go:76-151): build cache + scheduler, start the
HTTP listener, then run the scheduling loop — optionally gated behind leader
election.  ``--follower http://leader:port`` boots the replicated read
plane instead (run_follower): no scheduler, no ingest — a pull loop applies
the leader's cycle deltas to a local device-resident replica and the SAME
serving stack answers /v1/whatif against it."""

from __future__ import annotations

import json
import logging
import os
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from kube_batch_tpu import metrics
from kube_batch_tpu.api import serialize
from kube_batch_tpu.api.pod import PersistentVolume, PodDisruptionBudget
from kube_batch_tpu.api.types import PodGroupPhase, queue_phase_counts
from kube_batch_tpu.cache.cache import SchedulerCache
from kube_batch_tpu.cmd.leader_election import LeaderElector, LostLeadership
from kube_batch_tpu.cmd.options import ServerOption
from kube_batch_tpu.scheduler import Scheduler
from kube_batch_tpu.version import version_string

logger = logging.getLogger("kube_batch_tpu")


def runtime_report() -> dict:
    """What this process runs on, as JAX reports it — logged once at
    start-up and served as ``/version``, so a client (``chip_smoke.py``)
    learns the device from the serving process and not from a guess."""
    import jax

    from kube_batch_tpu.native import fast

    devices = jax.devices()
    return {
        "version": version_string(),
        "jax": jax.__version__,
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "native": fast.resource_lib_state,
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
    }


def _queue_status(cache: SchedulerCache) -> list:
    """Queue list with the CRD's status counts (types.go:211-223)."""
    with cache._lock:
        counts = {
            name: queue_phase_counts()
            for name in cache.queues
        }
        for job in cache.jobs.values():
            c = counts.get(job.queue)
            if c is None or job.pod_group is None:
                continue
            phase = job.pod_group.phase or PodGroupPhase.PENDING
            c[phase.value.lower()] = c.get(phase.value.lower(), 0) + 1
        return [
            {"name": name, "weight": q.weight, **counts[name]}
            for name, q in sorted(cache.queues.items())
        ]


def _job_status(cache: SchedulerCache) -> list:
    with cache._lock:
        rows = []
        for uid, job in sorted(cache.jobs.items()):
            pg = job.pod_group
            rows.append(
                {
                    "uid": uid,
                    "queue": job.queue,
                    "min_member": job.min_available,
                    "phase": (pg.phase.value if pg and pg.phase else "Pending"),
                    "running": pg.running if pg else 0,
                    "conditions": [
                        {"type": c.type, "status": c.status, "reason": c.reason,
                         "message": c.message}
                        for c in (pg.conditions if pg else [])
                    ],
                }
            )
        return rows


def _bindings(cache: SchedulerCache, seq: bool = False) -> list:
    """``seq``: each row also says the how-manieth bind of this process
    the pod's was (0: it arrived bound), so that a client can walk the
    binds in the order they were made."""
    with cache._lock:
        out = []
        for job in cache.jobs.values():
            for task in job.tasks.values():
                if task.node_name is not None:
                    row = {"pod": task.key(), "node": task.node_name,
                           "status": task.status.name}
                    if seq:
                        row["seq"] = cache.bind_seq.get(task.key(), 0)
                    out.append(row)
        return sorted(out, key=lambda r: r["pod"])


def make_handler(cache: SchedulerCache, query_plane=None):
    ingest = {
        # POST is add-or-update: update_pod is delete+add (event_handlers.go:116-130)
        "pods": (serialize.pod_from_dict, cache.update_pod, cache.delete_pod),
        "nodes": (serialize.node_from_dict, cache.add_node,
                  lambda n: cache.delete_node(n.name)),
        "podgroups": (serialize.pod_group_from_dict, cache.add_pod_group,
                      lambda pg: cache.delete_pod_group(pg.key())),
        "queues": (serialize.queue_from_dict, cache.add_queue,
                   lambda q: cache.delete_queue(q.name)),
        "priorityclasses": (serialize.priority_class_from_dict,
                            cache.add_priority_class,
                            lambda pc: cache.delete_priority_class(pc.name)),
        # legacy gang source (event_handlers.go:484-594)
        "poddisruptionbudgets": (
            lambda d: PodDisruptionBudget(**d), cache.add_pdb, cache.delete_pdb),
        # PV ledger ingest (the pv informer analog, cache.go:189-209); no-op
        # deletes/adds when the volume binder is the fake
        "persistentvolumes": (
            lambda d: PersistentVolume(**d),
            lambda pv: getattr(cache.volume_binder, "add_pv", lambda _: None)(pv),
            lambda pv: getattr(cache.volume_binder, "delete_pv", lambda _: None)(pv.name),
        ),
    }

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):  # route to glog-analog logger
            logger.debug("http: " + fmt, *args)

        def _send(self, code: int, body: str, ctype="application/json"):
            self._send_bytes(code, body.encode(), ctype)

        def _send_bytes(self, code: int, data: bytes,
                        ctype="application/octet-stream"):
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def do_GET(self):
            if self.path == "/metrics":
                self._send(200, metrics.render_prometheus(), "text/plain; version=0.0.4")
            elif self.path == "/healthz":
                self._send(200, "ok", "text/plain")
            elif self.path == "/version":
                self._send(200, json.dumps(runtime_report()))
            elif self.path == "/debug/stacks":
                # pprof goroutine-dump analog (main.go:25 net/http/pprof)
                import sys
                import traceback

                frames = sys._current_frames()
                out = []
                for tid, frame in frames.items():
                    out.append(f"--- thread {tid} ---")
                    out.extend(l.rstrip() for l in traceback.format_stack(frame))
                self._send(200, "\n".join(out), "text/plain")
            elif self.path.startswith("/debug/pprof"):
                # CPU-profile analog (?seconds=N): a SAMPLING profiler over
                # every thread via sys._current_frames — cProfile in this
                # handler would profile only the handler's own (sleeping)
                # thread.  Output: sample counts per stack, hottest first,
                # pprof-text-shaped.
                import math
                import sys as _sys
                import time as _time
                from collections import Counter
                from urllib.parse import parse_qs, urlparse

                q = parse_qs(urlparse(self.path).query)
                try:
                    seconds = float(q.get("seconds", ["5"])[0])
                except ValueError:
                    self._send(400, "seconds must be a number", "text/plain")
                    return
                if not math.isfinite(seconds) or seconds <= 0:
                    self._send(400, "seconds must be a positive finite number",
                               "text/plain")
                    return
                seconds = min(seconds, 60.0)
                interval = 0.01
                me = threading.get_ident()
                stacks: Counter = Counter()
                deadline = _time.monotonic() + seconds
                n_samples = 0
                while _time.monotonic() < deadline:
                    for tid, frame in _sys._current_frames().items():
                        if tid == me:
                            continue
                        # raw (code, lineno) tuples per frame: no linecache /
                        # FrameSummary work inside the sampling loop — stacks
                        # are formatted once at output time
                        key = []
                        f = frame
                        while f is not None and len(key) < 12:
                            key.append((f.f_code, f.f_lineno))
                            f = f.f_back
                        stacks[tuple(key)] += 1
                    n_samples += 1
                    # kbt: allow[KBT011] profiler sampling cadence — a
                    # fixed-interval sampler, not a retry/backoff loop
                    _time.sleep(interval)
                out = [
                    f"samples: {n_samples} over {seconds:.1f}s "
                    f"({len(stacks)} distinct stacks)",
                    "NOTE: wall-clock sampler — blocked/sleeping stacks count "
                    "the same as busy ones (a mostly-idle scheduler tops out "
                    "in its sleep/select frames); read busy stacks relative "
                    "to each other for the CPU picture",
                ]
                for key, count in stacks.most_common(40):
                    out.append(f"\n{count} samples ({100.0 * count / max(1, n_samples):.0f}%):")
                    out.extend(
                        f"  {code.co_filename.rsplit('/', 1)[-1]}:{lineno} "
                        f"{code.co_name}"
                        for code, lineno in reversed(key)
                    )
                self._send(200, "\n".join(out), "text/plain")
            elif self.path == "/v1/queues":
                self._send(200, json.dumps(_queue_status(cache)))
            elif self.path == "/v1/jobs":
                self._send(200, json.dumps(_job_status(cache)))
            elif self.path.partition("?")[0] == "/v1/bindings":
                from urllib.parse import parse_qs, urlparse

                q = parse_qs(urlparse(self.path).query)
                self._send(200, json.dumps(_bindings(
                    cache, seq=q.get("seq", ["0"])[0] == "1")))
            elif self.path == "/v1/guard":
                # result-integrity guard plane state: per-fast-path breaker
                # (healthy|demoted|probing), trips, audits, bundle paths —
                # the operator's first stop when a trip alert fires
                from kube_batch_tpu.guard import guard_of

                self._send(200, json.dumps(guard_of(cache).state()))
            elif self.path == "/v1/trace":
                # cycle tracing plane: the last completed cycle's span tree
                # + the flight-recorder ring stats (obs/trace, obs/recorder)
                from kube_batch_tpu.obs.trace import tracer_of

                self._send(200, json.dumps(tracer_of(cache).state()))
            elif self.path.startswith("/v1/trace/cycles/"):
                # one record's whole tree, by the number its row in
                # /v1/trace's `cycles` or `kept` gives
                from kube_batch_tpu.obs.trace import tracer_of

                number = self.path[len("/v1/trace/cycles/"):]
                tree = (tracer_of(cache).cycle_tree(int(number))
                        if number.isdigit() else None)
                if tree is None:
                    self._send(404, json.dumps(
                        {"error": f"no cycle {number!r} in the ring or "
                                  "the kept list"}))
                else:
                    self._send(200, json.dumps(tree))
            elif self.path == "/v1/trace/dumps" or self.path.startswith(
                "/v1/trace/dumps/"
            ):
                self._trace_dumps()
            elif self.path == "/v1/replicate" or self.path.startswith(
                "/v1/replicate?"
            ):
                self._replicate()
            elif self.path == "/v1/evictions" or self.path.startswith(
                "/v1/evictions?"
            ):
                self._evictions()
            elif self.path == "/v1/alerts":
                # guard trip-rate SLO alerts (obs/alerts): firing state,
                # windowed trip counts, thresholds
                from kube_batch_tpu.obs.alerts import alerts_of

                self._send(200, json.dumps(alerts_of(cache).state()))
            else:
                self._send(404, json.dumps({"error": "not found"}))

        def _body(self) -> dict:
            n = int(self.headers.get("Content-Length", 0))
            return json.loads(self.rfile.read(n) or b"{}")

        def _replicate(self):
            """The leader's replication publish endpoint: one KBR1 frame
            per pull, chosen by the follower's applied cursor (heartbeat
            when caught up, a synthesized full snapshot when the cursor
            fell off the ring — the delta-gap escalation)."""
            pub = getattr(cache, "replication", None)
            if pub is None:
                self._send(503, json.dumps(
                    {"error": "replication not enabled"}))
                return
            since = self._since(-1)
            if since is not None:
                self._send_bytes(200, pub.record_for(since))

        def _evictions(self):
            """The standalone deployment's eviction feed: what the
            scheduler ordered evicted since the client's cursor
            (cache/evictions.py).  No cache lock: a kubelet stand-in polls
            this every few milliseconds."""
            log = cache.eviction_log
            if log is None:
                self._send(503, json.dumps({
                    "error": "no eviction feed: with --master an eviction "
                             "is a pod DELETE at the apiserver"}))
                return
            since = self._since(0)
            if since is not None:
                self._send(200, json.dumps(log.since(since)))

        def _since(self, default: int) -> Optional[int]:
            """The ``?since=N`` cursor of a feed; answers 400 and returns
            None where it is no integer."""
            from urllib.parse import parse_qs, urlparse

            q = parse_qs(urlparse(self.path).query)
            try:
                return int(q.get("since", [str(default)])[0])
            except ValueError:
                self._send(400, json.dumps(
                    {"error": "since must be an integer"}))
                return None

        def _trace_dumps(self):
            """Flight-recorder dump streaming: the index lists every dump
            this process published; /<name>/<trace.json|meta.json> streams
            one file.  Only names the recorder itself registered resolve —
            the dump list is the allow-list, so no path escapes it."""
            from kube_batch_tpu.obs.trace import tracer_of

            recorder = tracer_of(cache).recorder
            dumps = recorder.stats()["dumps"] if recorder is not None else []
            by_name = {os.path.basename(p): p for p in dumps}
            rest = self.path[len("/v1/trace/dumps"):].strip("/")
            if not rest:
                self._send(200, json.dumps({
                    "dumps": sorted(by_name),
                    "directory": recorder.directory if recorder else None,
                }))
                return
            parts = rest.split("/")
            root = by_name.get(parts[0])
            if root is None or len(parts) != 2 or parts[1] not in (
                "trace.json", "meta.json"
            ):
                self._send(404, json.dumps({"error": "no such dump file"}))
                return
            try:
                with open(os.path.join(root, parts[1]), "rb") as f:
                    self._send_bytes(200, f.read(), "application/json")
            except OSError as e:
                self._send(404, json.dumps({"error": str(e)}))

        def _ingest(self, delete: bool):
            kind = self.path.rsplit("/", 1)[-1]
            entry = ingest.get(kind)
            if entry is None:
                self._send(404, json.dumps({"error": f"unknown kind {kind}"}))
                return
            parse, add, remove = entry
            apply_fn = remove if delete else add
            try:
                body = self._body()
                if isinstance(body, list):
                    # batched ingest: a list body applies under ONE cache
                    # lock acquisition and ONE dirty-version advance
                    # (cache.ingest_batch) — high-QPS clients stop paying a
                    # lock round-trip (and a lease/delta token move) per
                    # pod.  The whole batch parses BEFORE any element
                    # applies: a malformed element rejects the batch, never
                    # half-applies it.
                    ops = [(apply_fn, parse(d)) for d in body]
                    applied = cache.ingest_batch(ops)
                    if applied < len(ops):
                        # an element that parsed but whose HANDLER raised:
                        # mirror the single-object path's 400, with the
                        # partial count so the client knows what landed
                        self._send(400, json.dumps({
                            "ok": False, "applied": applied,
                            "failed": len(ops) - applied}))
                        return
                    self._send(200, json.dumps(
                        {"ok": True, "applied": applied}))
                    return
                apply_fn(parse(body))
            except (TypeError, ValueError, KeyError) as e:
                self._send(400, json.dumps({"error": str(e)}))
                return
            self._send(200, json.dumps({"ok": True}))

        def do_POST(self):
            if self.path == "/v1/sync":
                # initial-sync barrier: a client that finished its re-list
                # signals the scheduler to start (WaitForCacheSync analog)
                cache.mark_synced()
                self._send(200, "{}")
                return
            if self.path == "/v1/whatif":
                self._whatif(lambda body: query_plane.submit(body))
                return
            if self.path == "/v1/whatif/sweep":
                # server-side capacity sweep: binary-search max replicas
                # against ONE lease (the autoscaler's "how many fit" ask)
                self._whatif(lambda body: query_plane.submit_sweep(body))
                return
            self._ingest(delete=False)

        def _whatif(self, submit):
            """The query plane's serving endpoint: validate, enqueue into
            the micro-batcher, block this handler thread on the per-request
            future (ThreadingHTTPServer gives every request its own thread,
            so concurrent handlers pile into ONE probe dispatch)."""
            from concurrent.futures import TimeoutError as FutureTimeout

            from kube_batch_tpu.serve.batcher import QueueFull
            from kube_batch_tpu.serve.plane import WhatifError

            if query_plane is None:
                self._send(503, json.dumps(
                    {"error": "query plane not enabled"}))
                return
            try:
                body = self._body()
            except (ValueError, json.JSONDecodeError) as e:
                self._send(400, json.dumps({"error": str(e)}))
                return
            try:
                fut = submit(body)
                resp = fut.result(timeout=query_plane.dispatch_timeout + 8)
            except WhatifError as e:
                self._send(e.status, json.dumps({"error": str(e)}))
                return
            except QueueFull as e:
                self._send(503, json.dumps({"error": str(e)}))
                return
            except (FutureTimeout, TimeoutError):
                # abandon the queued probe: a cancelled future is skipped
                # at flush (no device time, no verdict counters for an
                # answer nobody receives); cancel() failing means the
                # flush is resolving it right now — the answer is simply
                # discarded
                fut.cancel()
                self._send(503, json.dumps(
                    {"error": "whatif probe timed out"}))
                return
            self._send(200, json.dumps(resp))

        def do_DELETE(self):
            self._ingest(delete=True)

    return Handler


class AdminServer:
    """The --listen-address listener (server.go:96-99).  With a
    ``query_plane`` the same listener serves ``POST /v1/whatif`` (the
    serve/ read path) beside the admin/ingest API."""

    def __init__(self, cache: SchedulerCache, host: str = "127.0.0.1",
                 port: int = 0, query_plane=None):
        self.query_plane = query_plane
        self.httpd = ThreadingHTTPServer(
            (host, port), make_handler(cache, query_plane=query_plane)
        )
        self.port = self.httpd.server_address[1]
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self.httpd.serve_forever, daemon=True, name="admin-http"
        )
        self._thread.start()

    def stop(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()
        # bounded join: serve_forever returns once shutdown() lands, so the
        # acceptor thread exits promptly — but don't hang stop() on a
        # wedged in-flight handler (the thread is daemon either way)
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None


class TokenBucket:
    """The client-side 50 QPS / 100-burst throttle of the reference
    (options.go:32-33, server.go:69-70). The reference has ONE rest.Config —
    binder, evictor, and status updater all ride the same rate limiter — so
    one bucket instance must be shared across every egress wrapper."""

    def __init__(self, qps: float, burst: int):
        import time as _time

        self._qps = qps
        self._burst = float(burst)
        self._tokens = float(burst)
        self._last = _time.monotonic()
        self._lock = threading.Lock()
        self._time = _time

    def take(self) -> None:
        """Reserve a token under the lock, sleep OUTSIDE it. The balance may
        go negative: each waiter's debt position is its reservation, and its
        wait is the time until its own token mints — so concurrent waiters
        (the 16-worker status pool, the binder, the pv-writes thread) sleep
        in parallel instead of serializing behind whoever holds the lock
        (round-5 ADVICE #3). Aggregate rate is unchanged: tokens still mint at
        qps with a burst cap, and reservations are FIFO by lock order."""
        with self._lock:
            now = self._time.monotonic()
            self._tokens = min(self._burst, self._tokens + (now - self._last) * self._qps)
            self._last = now
            self._tokens -= 1.0
            wait = -self._tokens / self._qps if self._tokens < 0.0 else 0.0
        if wait > 0.0:
            self._time.sleep(wait)


class RateLimitedBackend:
    """Token-bucket throttle applied to the Binder/Evictor seam. Pass a shared
    TokenBucket via `bucket` so multiple seams drain one budget; qps/burst
    kwargs build a private bucket (single-seam deployments and tests)."""

    def __init__(self, backend, qps: float = 0.0, burst: int = 0,
                 bucket: Optional[TokenBucket] = None):
        if bucket is None and qps <= 0.0:
            raise ValueError("RateLimitedBackend needs a shared bucket or qps > 0")
        self._backend = backend
        self._bucket = bucket if bucket is not None else TokenBucket(qps, burst)

    def _take(self) -> None:
        self._bucket.take()

    def bind(self, pod, hostname):
        self._take()
        return self._backend.bind(pod, hostname)

    def evict(self, pod):
        self._take()
        return self._backend.evict(pod)


class RateLimitedStatusUpdater(RateLimitedBackend):
    """The same token bucket on the StatusUpdater seam (the reference's
    status writes ride the identical throttled rest.Config client,
    server.go:69-70).  parallel_safe passes through: the bucket is
    thread-safe, so the close-time jobUpdater pool may call concurrently."""

    @property
    def parallel_safe(self):
        return getattr(self._backend, "parallel_safe", False)

    def degraded(self):
        """Forward the writeback-breaker probe: without this passthrough
        the cache's degraded-cycle shedding would never see the wrapped
        K8sBackend's open breaker."""
        probe = getattr(self._backend, "degraded", None)
        return bool(probe()) if probe is not None else False

    def update_pod_group(self, pg):
        self._take()
        return self._backend.update_pod_group(pg)

    def update_pod_condition(self, pod, cond):
        self._take()
        return self._backend.update_pod_condition(pod, cond)

    def update_queue_status(self, name, counts):
        self._take()
        return self._backend.update_queue_status(name, counts)


def run_warm_standby(elector, sched: Scheduler, cache: SchedulerCache,
                     max_takeovers: Optional[int] = None) -> None:
    """Leadership loop with in-place warm standby (BEYOND the reference's
    crash-on-loss): a lost lease stops the scheduling loop but NOT the
    process — the jit-compiled solve executables and the device-resident
    snapshot stay alive — and the elector re-contends. On every
    (re-)acquire the cache recovers through ``failover_recover``: rebuild
    from the pod store (the watch keeps feeding it while standby), then
    revalidate-or-drop the resident device cache, so a failover normally
    pays NO recompile and NO full re-upload.

    ``max_takeovers`` bounds the loop for tests; production runs forever
    (a supervisor can still kill the process for a hard restart)."""
    takeovers = 0

    def lead():
        # recovery runs AFTER the lease is won (elector.run invokes this
        # only as leader) and before the first cycle of the new reign
        if takeovers > 1:
            cache.failover_recover()
        sched.run_forever()

    while max_takeovers is None or takeovers < max_takeovers:
        takeovers += 1
        try:
            elector.run(lead, on_stopped_leading=sched.stop)
            return  # clean stop (sched.stop() by other means)
        except LostLeadership:
            logger.warning(
                "leadership lost; demoting to warm standby (resident cache "
                "kept) and re-contending")
            elector.reset()


def run_follower(opt: ServerOption) -> None:
    """The replicated read plane's process loop (--follower URL): no
    scheduler, no ingest — a pull thread subscribes to the leader's
    /v1/replicate stream, applies cycle deltas to a local device-resident
    ColumnStore replica, and the admin listener serves the SAME /v1/whatif
    stack (plus sweep/trace/metrics) against it.  Horizontal read scale:
    each follower owns its own devices and probe executables, so serving
    QPS adds up across follower processes while the leader pays one encode
    per cycle regardless of fan-out."""
    from kube_batch_tpu.replicate.follower import (
        FollowerCache,
        ReplicationFollower,
    )
    from kube_batch_tpu.serve.plane import QueryPlane

    cache = FollowerCache()
    query_plane = QueryPlane(cache, prewarm=True)
    follower = ReplicationFollower(opt.follower, cache=cache,
                                   query_plane=query_plane)
    host, port = opt.listen_host_port
    admin = AdminServer(cache, host, port, query_plane=query_plane)
    admin.start()
    logger.info("follower serving on %s:%d, replicating from %s", host,
                admin.port, opt.follower)
    follower.start()
    try:
        follower.join()
    finally:
        follower.stop()
        query_plane.close()
        admin.stop()


def run(opt: ServerOption) -> None:
    """app.Run (server.go:76-151): metrics/admin listener up front, then the
    scheduling loop — behind leader election when enabled. Option validation
    and --version live in cmd/main.py."""
    from kube_batch_tpu.envutil import enable_persistent_compilation_cache

    enable_persistent_compilation_cache()  # restart re-pays no solve compiles
    logger.info("runtime: %s", json.dumps(runtime_report()))
    if opt.follower:
        return run_follower(opt)
    from kube_batch_tpu.cache.evictions import EvictionLog
    from kube_batch_tpu.cache.fake import FakeBinder

    from kube_batch_tpu.cache.volume import StandalonePVBinder

    # with a k8s front end (--master), binds/evictions write back to the
    # apiserver (pods/binding POST, pod DELETE); standalone deployments keep
    # the recording fake binder behind the ingest API and serve what was
    # ordered evicted on GET /v1/evictions for the client to terminate
    k8s_mode = opt.master.startswith("http")
    # one bucket for ALL egress (binds + evictions + status writes): the
    # reference's writes share a single throttled rest.Config (server.go:69-70)
    bucket = TokenBucket(opt.kube_api_qps, opt.kube_api_burst)
    if k8s_mode:
        from kube_batch_tpu.cache.volume import K8sPVLedger
        from kube_batch_tpu.k8s.bind import K8sBackend
        from kube_batch_tpu.k8s.transport import ApiTransport, in_cluster_auth

        auth = in_cluster_auth()
        backend = K8sBackend(opt.master, **auth)
        binder, evictor, eviction_log = backend, backend, None
        status_updater = RateLimitedStatusUpdater(backend, bucket=bucket)
        # pv/pvc/storageclass watches feed this ledger; its claimRef /
        # selected-node PATCHes ride the backend's own transport AND the
        # same shared token bucket as every other egress write
        volume_binder = K8sPVLedger(
            transport=getattr(backend, "transport", None)
            or ApiTransport(opt.master, role="pv", **auth),
            bucket=bucket,
        )
    else:
        eviction_log = EvictionLog()
        binder, evictor = FakeBinder(), eviction_log
        status_updater = None  # cache default: recording fake
        # real PV ledger behind /v1/persistentvolumes
        volume_binder = StandalonePVBinder()
    cache = SchedulerCache(
        scheduler_name=opt.scheduler_name,
        default_queue=opt.default_queue,
        binder=RateLimitedBackend(binder, bucket=bucket),
        evictor=RateLimitedBackend(evictor, bucket=bucket),
        status_updater=status_updater,
        volume_binder=volume_binder,
        resolve_priority=opt.enable_priority_class,
    )
    cache.eviction_log = eviction_log
    on_cycle_end = None
    if opt.state_file:
        from kube_batch_tpu.cache.persistence import load_state, save_state

        if load_state(cache, opt.state_file):
            logger.info("restored cluster state from %s", opt.state_file)
            cache.mark_synced()  # the state file IS the initial listing
        on_cycle_end = lambda: save_state(cache, opt.state_file)  # noqa: E731
    sched = Scheduler(
        cache,
        conf_path=opt.scheduler_conf or None,
        schedule_period=opt.schedule_period,
        on_cycle_end=on_cycle_end,
    )
    # the read-side query plane (serve/): /v1/whatif rides the same
    # listener; KB_WHATIF=0 opts out (e.g. a memory-constrained part where
    # the probe's compiled specializations are unwelcome)
    query_plane = None
    if os.environ.get("KB_WHATIF", "").strip().lower() not in (
        "0", "false", "off", "no"
    ):
        from kube_batch_tpu.serve.plane import QueryPlane

        query_plane = QueryPlane(cache, prewarm=True)
        # the replication publisher (replicate/): each cycle's resident
        # swap goes out as a wire delta on GET /v1/replicate for follower
        # read replicas; KB_REPLICATE=0 opts out.  Publisher encode runs
        # overlapped like the writeback stage (scheduler.drain_pipeline
        # joins it), so the leader's cycle pays ~one host diff.
        if os.environ.get("KB_REPLICATE", "").strip().lower() not in (
            "0", "false", "off", "no"
        ):
            from kube_batch_tpu.obs.trace import tracer_of
            from kube_batch_tpu.replicate.publisher import ReplicationPublisher

            cache.replication = ReplicationPublisher(tracer=tracer_of(cache))
    host, port = opt.listen_host_port
    admin = AdminServer(cache, host, port, query_plane=query_plane)
    admin.start()
    logger.info("admin/metrics listening on %s:%d (whatif %s)", host,
                admin.port, "on" if query_plane is not None else "off")
    # Kubernetes front end (cache.go:256-339 informers): --master pointing
    # at an apiserver URL starts the list+watch adapter.  start() BLOCKS
    # until every resource finished its initial LIST and then marks the
    # cache synced — the reference's unconditional WaitForCacheSync gate
    # before the first cycle (scheduler.go:64); scheduling against a
    # half-seeded cache would overstate node idle capacity.
    watcher = None
    if k8s_mode:
        from kube_batch_tpu.k8s.watch import WatchAdapter

        watcher = WatchAdapter(cache, api_server=opt.master, **auth)
        logger.info("seeding from kubernetes apiserver %s ...", opt.master)
        watcher.start()
        logger.info("kubernetes watch adapter synced against %s", opt.master)
    # WaitForCacheSync (scheduler.go:64 / cache.go:363-384): give clients a
    # bounded window to land their initial listing (or POST /v1/sync) before
    # the first cycle; on timeout schedule whatever arrived. Off by default —
    # only deployments whose clients signal the barrier opt in.
    if opt.cache_sync_timeout > 0:
        cache.wait_for_cache_sync(timeout=opt.cache_sync_timeout)
    try:
        if opt.enable_leader_election:
            if k8s_mode:
                # cross-host HA rides the cluster API: a coordination.k8s.io
                # Lease in --lock-object-namespace (the reference's ConfigMap
                # resourcelock, server.go:106-151) — works across nodes with
                # no shared filesystem
                from kube_batch_tpu.cmd.leader_election import K8sLeaseElector
                from kube_batch_tpu.k8s.transport import ApiTransport

                elector = K8sLeaseElector(
                    ApiTransport(opt.master, role="lease", **auth),
                    namespace=opt.lock_object_namespace,
                )
            else:
                elector = LeaderElector(opt.lock_object_namespace)
            if opt.leader_warm_standby:
                run_warm_standby(elector, sched, cache)
            else:
                # on lease loss the elector stops the loop so run() can
                # raise — the crash-on-loss contract (server.go:145); a
                # supervisor restarts the process as a standby
                elector.run(sched.run_forever, on_stopped_leading=sched.stop)
        else:
            sched.run_forever()
    finally:
        if watcher is not None:
            watcher.stop()
        if query_plane is not None:
            query_plane.close()
        pub = getattr(cache, "replication", None)
        if pub is not None:
            pub.close()
        admin.stop()
