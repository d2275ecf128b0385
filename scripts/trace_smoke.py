"""Cycle-tracing smoke (wired into scripts/check.sh): the span recorder,
the Chrome trace-event export, and the flight recorder, end to end.

Three checks, one JSON summary line:

1. Traced sim run: the smoke preset with tracing on must produce a
   stage-attribution section and a Chrome trace-event export that passes
   structural validation (complete events, balanced nesting, monotonic
   per-thread timestamps).
2. Anomaly capture: the corruption chaos preset's guard trips must each
   arm a flight-recorder dump; every dump's ``trace.json`` must validate
   and its ``meta.json`` must carry the guard_trip trigger.
3. Pipelined overlap: a short REAL pipelined run (wall clock, fake
   backends, slowed binder drain) must render the overlap structure —
   cycle N's writeback span, on its own thread track, overlapping cycle
   N+1's compute spans — and the manual-trigger dump of exactly that ring
   must validate.

Exit 0 = all invariants hold; 1 = any violated.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

# runnable as `python scripts/trace_smoke.py` from the repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from kube_batch_tpu.envutil import apply_cpu_env  # noqa: E402

apply_cpu_env()

_TMP = tempfile.mkdtemp(prefix="kb-trace-smoke-")
os.environ["KB_TRACE_DIR"] = os.path.join(_TMP, "flight")
os.environ["KB_GUARD_DIR"] = os.path.join(_TMP, "guard")

from kube_batch_tpu.obs.trace import (  # noqa: E402
    chrome_trace,
    validate_chrome_trace,
)
from kube_batch_tpu.sim.runner import run_preset  # noqa: E402


def main() -> int:
    errors = []
    summary = {}

    # ---- 1. traced sim smoke + chrome export --------------------------
    chrome_path = os.path.join(_TMP, "smoke-trace.json")
    report = run_preset("smoke", seed=0, chrome_trace_path=chrome_path)
    sa = report.get("stage_attribution") or {}
    if not sa.get("cycles_traced"):
        errors.append("smoke: no traced cycles (is KB_TRACE=0 leaking in?)")
    with open(chrome_path) as f:
        doc = json.load(f)
    errs = validate_chrome_trace(doc)
    if errs:
        errors.append(f"smoke chrome trace invalid: {errs[:3]}")
    names = {e["name"] for e in doc.get("traceEvents", [])
             if e.get("ph") == "X"}
    for want in ("session_open", "status_derive", "action:allocate",
                 "solve_dispatch"):
        if want not in names:
            errors.append(f"smoke trace missing the {want} span")
    summary["sim_smoke"] = {
        "cycles_traced": sa.get("cycles_traced"),
        "spans_total": sa.get("spans_total"),
        "retraces_attributed": sa.get("retraces_attributed"),
    }

    # ---- 2. corruption trips → validating flight dumps ----------------
    report = run_preset("corruption", seed=0)
    guard = report.get("guard") or {}
    if guard.get("chaos_ok") is not True:
        errors.append("corruption: chaos_ok failed")
    dumps = guard.get("flight_dumps") or []
    if not dumps:
        errors.append("corruption: guard trips produced no flight dumps")
    for d in dumps:
        try:
            with open(os.path.join(d, "trace.json")) as f:
                derrs = validate_chrome_trace(json.load(f))
            if derrs:
                errors.append(f"flight dump {d} invalid: {derrs[:3]}")
            with open(os.path.join(d, "meta.json")) as f:
                meta = json.load(f)
            if meta.get("reason") != "guard_trip":
                errors.append(f"flight dump {d}: unexpected reason "
                              f"{meta.get('reason')}")
        except OSError as e:
            errors.append(f"flight dump {d} unreadable: {e}")
    summary["corruption"] = {
        "trips": guard.get("trips_total"),
        "flight_dumps": len(dumps),
        "alert_fired": (guard.get("alerts", {}).get("alerts", {})
                        .get("guard_trips", {}).get("fired_total", 0)),
    }

    # ---- 3. the pipelined overlap, rendered ----------------------------
    overlap = _overlap_check(errors)
    summary["pipelined_overlap"] = overlap

    print(json.dumps({**summary, "errors": errors}, sort_keys=True))
    return 1 if errors else 0


def _overlap_check(errors) -> dict:
    """A short real pipelined run whose writeback is slowed enough that
    cycle N's egress provably overlaps cycle N+1's compute — then assert
    the exported spans actually show it."""
    from kube_batch_tpu import actions as _a  # noqa: F401 — registers
    from kube_batch_tpu import plugins as _p  # noqa: F401 — registers
    from kube_batch_tpu.api.pod import (
        GROUP_NAME_ANNOTATION,
        Node,
        Pod,
        PodGroup,
        Queue,
    )
    from kube_batch_tpu.api.types import PodPhase
    from kube_batch_tpu.cache.cache import SchedulerCache
    from kube_batch_tpu.cache.fake import (
        FakeBinder,
        FakeEvictor,
        FakeStatusUpdater,
    )
    from kube_batch_tpu.framework.conf import load_scheduler_conf
    from kube_batch_tpu.scheduler import Scheduler

    cache = SchedulerCache(binder=FakeBinder(), evictor=FakeEvictor(),
                           status_updater=FakeStatusUpdater())
    cache.add_queue(Queue(name="q0", uid="uq0", weight=1))
    for i in range(4):
        cache.add_node(Node(
            name=f"n{i}",
            allocatable={"cpu": 16000.0, "memory": float(64 * 2 ** 30),
                         "pods": 110.0},
        ))
    sched = Scheduler(cache, conf=load_scheduler_conf(None))

    def add_gang(serial):
        g = f"ov{serial}"
        cache.add_pod_group(PodGroup(
            name=g, namespace="sm", uid=f"pg-{g}", min_member=1,
            queue="q0", creation_index=serial,
        ))
        cache.add_pod(Pod(
            name=f"{g}-0", namespace="sm", uid=f"pod-{g}",
            requests={"cpu": 500.0, "memory": float(2 ** 30)},
            annotations={GROUP_NAME_ANNOTATION: g},
            phase=PodPhase.PENDING, creation_index=serial * 100,
        ))

    add_gang(1)
    sched.run_once_pipelined()  # warm compiles
    orig_flush = cache.flush_binds

    def slow_flush():
        time.sleep(0.08)
        return orig_flush()

    cache.flush_binds = slow_flush
    add_gang(2)
    sched.run_once_pipelined()
    add_gang(3)
    sched.run_once_pipelined()
    sched.drain_pipeline()
    cache.flush_binds = orig_flush
    records = cache.flight_recorder.records()
    found = False
    for i, rec in enumerate(records[:-1]):
        for wb in (s for s in rec.spans if s.name == "writeback"):
            for nxt in (s for s in records[i + 1].spans
                        if s.name in ("session_open", "action:allocate")):
                if wb.t0 < nxt.t1 and nxt.t0 < wb.t1 and wb.tid != nxt.tid:
                    found = True
    if not found:
        errors.append("pipelined overlap not visible in the span records")
    # the manual-trigger dump of this ring must validate too
    cache.flight_recorder.trigger("smoke_manual")
    dumps = cache.flight_recorder.flush()
    doc = chrome_trace(records)
    errs = validate_chrome_trace(doc)
    if errs:
        errors.append(f"overlap trace invalid: {errs[:3]}")
    cache.stop()
    return {"overlap_rendered": found, "manual_dumps": len(dumps)}


if __name__ == "__main__":
    sys.exit(main())
