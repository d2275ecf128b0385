"""enqueue action (actions/enqueue/enqueue.go) — the Inqueue gatekeeper.

Computes cluster idle as Σ allocatable × 1.2 − used (20% overcommit,
enqueue.go:78-81), then admits Pending-phase podgroups in queue/job order:
no MinResources → Inqueue; else requires JobEnqueueable (proportion
capability check) AND MinResources ≤ idle, deducting on admission
(enqueue.go:102-117).

Columnar sessions run this with NO per-job Python loop: candidates come
off the j_sched/j_has_minres job-row columns (synced at session open,
delta across cycles), the static JobEnqueueable verdicts and ordering keys
are vectorized over the column matrices, and the sequential admission
itself (each admission shrinks the idle the next candidate sees) is the
jitted prefix-scan in ops/admission.py with a single readback of the
admitted mask — only PROMOTED jobs touch Python objects.

Ordering exactness: the session's queue_order_fn is a strict total order
(plugin verdicts fall back to the queue name), so the reference's
pop-process-push heap walk provably drains one queue fully before the
next — the gate reproduces it by sorting the involved queues once (they
are few) and concatenating each queue's candidates in tiered job order,
derived columnar for the known JOB_ORDER voters (priority/gang/drf — any
other voter falls back to the object walk below, as do non-columnar
sessions).  MinResources rows are float32 (the device column dtype);
min_resources values beyond f32 precision would shift the fit check by
<1 ulp — inside the sub-quantum tolerance for every real resource unit.
"""

from __future__ import annotations

from functools import cmp_to_key

import numpy as np

from kube_batch_tpu.api.types import PodGroupPhase
from kube_batch_tpu.framework.interface import Action
from kube_batch_tpu.framework.session import JOB_ENQUEUEABLE, JOB_ORDER
from kube_batch_tpu.utils.priority_queue import PriorityQueue

OVERCOMMIT_FACTOR = 1.2

#: JOB_ORDER voters the columnar gate can derive keys for
_COLUMNAR_JOB_ORDER = {"priority", "gang", "drf"}


class EnqueueAction(Action):
    name = "enqueue"

    def __init__(self):
        # which path the most recent execute() took ("columnar" | "walk") —
        # read by the bench and the gate-equivalence tests
        self.last_path = "walk"

    def execute(self, ssn) -> None:
        cols = ssn.columns
        if (
            cols is not None
            and getattr(ssn, "rows_synced", False)
            and ssn.enabled_plugin_names(JOB_ENQUEUEABLE) <= {"proportion"}
            and ssn.enabled_plugin_names(JOB_ORDER) <= _COLUMNAR_JOB_ORDER
        ):
            self.last_path = "columnar"
            if self._execute_columnar(ssn, cols):
                return
        self.last_path = "walk"
        self._execute_walk(ssn, cols)

    # ------------------------------------------------------------------
    def _promote(self, cols, job) -> None:
        """Pending → Inqueue, mirrored into the job-row columns: the device
        snapshot's schedulability row is synced at session open (delta
        across cycles), so a mid-cycle phase flip must write through or
        this cycle's allocate would still skip the job; the phase/touched
        rows keep the delta close-session pass exact."""
        from kube_batch_tpu.api.columns import PHASE_CODE

        job.pod_group.phase = PodGroupPhase.INQUEUE
        if cols is not None and job._cols is cols and job._row >= 0:
            row = job._row
            cols.j_sched[row] = True
            cols.j_phase[row] = PHASE_CODE[PodGroupPhase.INQUEUE]
            cols.j_touched[row] = True

    def _promote_rows(self, ssn, cols, rows) -> None:
        job_by_row = cols.job_by_row
        for r in rows:
            self._promote(cols, job_by_row[r])

    # ------------------------------------------------------------------
    def _execute_columnar(self, ssn, cols) -> bool:
        """The column-gate path; returns False when an exactness guard
        trips (the caller then runs the object walk).

        Promotions are DEFERRED to the end: nothing mutates until the
        admitted set is final, so (a) every fallback return leaves the
        object walk a pristine re-decide, and (b) the sampled shadow audit
        can run the walk ORACLE over the same unmutated state and compare
        decision sets — the guard-plane coverage for this gate that the
        solve paths already have via their shadow oracles."""
        import jax

        spec = ssn.spec
        cand = cols.j_sess & ~cols.j_sched & cols.j_has_pg
        if not cand.any():
            return True
        # the walk skips jobs whose queue left the session's queue dict
        qok = np.zeros(cols.queues.cap, bool)
        for name, qi in cols.queue_rows.items():
            if name in ssn.queues:
                qok[qi] = True
        cand &= qok[cols.j_queue]
        if not cand.any():
            return True
        job_by_row = cols.job_by_row
        # unconditional promotions (enqueue.go:102-105): admission order is
        # unobservable for jobs that consume no budget — decided here,
        # APPLIED at the end with the admitted rows
        uncond_rows = np.flatnonzero(cand & ~cols.j_has_minres).tolist()
        minres_rows = np.flatnonzero(cand & cols.j_has_minres)
        if minres_rows.size == 0:
            self._promote_rows(ssn, cols, uncond_rows)
            return True

        # idle = Σ allocatable × 1.2 − used (enqueue.go:74-81) over the
        # session's nodes — exactly the Ready rows; skew falls back
        if int(cols.n_valid.sum()) != len(ssn.nodes):
            return False
        nv = cols.n_valid
        if nv.any():
            total = spec.from_vec(cols.n_alloc[nv].sum(axis=0))
            used = spec.from_vec(cols.n_used[nv].sum(axis=0))
        else:
            total, used = spec.empty(), spec.empty()
        idle = total.multi(OVERCOMMIT_FACTOR)
        if used.less_equal(idle):
            idle.sub_(used)
        else:
            idle = spec.empty()

        # static JobEnqueueable verdicts (proportion.go:211-233): the
        # capability check against the queue's LIVE allocation — read off
        # the proportion plugin's own queue attrs (exactly what its
        # job_enqueueable closure reads, including any event updates since
        # open), vectorized per queue over the candidate rows
        enq_ok = np.ones(minres_rows.size, bool)
        qrows_of = cols.j_queue[minres_rows]
        if "proportion" in ssn.enabled_plugin_names(JOB_ENQUEUEABLE):
            prop = next(
                (p for p in ssn.plugins
                 if getattr(p, "name", "") == "proportion"), None,
            )
            attrs = getattr(prop, "queue_attrs", {})
            minr64 = cols.j_minres[minres_rows].astype(np.float64)
            for qi in np.unique(qrows_of).tolist():
                qinfo = ssn.queues.get(cols.queue_names[qi])
                attr = attrs.get(cols.queue_names[qi])
                # queue or attr missing → enqueueable (the closure's guard)
                if qinfo is None or attr is None:
                    continue
                capability = qinfo.queue.capability
                if not capability:
                    continue  # no cap → enqueueable
                capv = np.zeros(spec.n)
                for name, v in capability.items():
                    if name in spec:
                        capv[spec.index(name)] = float(v)
                sel = qrows_of == qi
                need = minr64[sel] + attr.allocated.vec
                ok = np.all(
                    (need <= capv) | (need - capv < spec.quanta), axis=1
                )
                idxs = np.flatnonzero(sel)
                enq_ok[idxs[~ok]] = False

        # admission order: queues drained in tiered queue order (strict
        # total order ⇒ exactly the reference heap's behavior), jobs within
        # a queue by the tiered job-order keys, columnar per voter
        qset = sorted({int(qi) for qi in np.unique(qrows_of)})
        queue_objs = [ssn.queues[cols.queue_names[qi]] for qi in qset]
        queue_objs.sort(key=cmp_to_key(
            lambda a, b: -1 if ssn.queue_order_fn(a, b) else 1
        ))
        rank_by_qi = np.zeros(cols.queues.cap, np.int32)
        for pos, q in enumerate(queue_objs):
            rank_by_qi[cols.queue_rows[q.name]] = pos
        keys = []
        from kube_batch_tpu.api.columns import READY_STATUSES

        for name in ssn.ordered_enabled_plugins(JOB_ORDER):
            if name == "priority":
                keys.append(-cols.j_prio[minres_rows])
            elif name == "gang":
                # starved (not ready) gangs first (gang.go:96-121)
                ready = (
                    cols.j_counts[minres_rows][:, READY_STATUSES]
                    .sum(axis=1) >= cols.j_min[minres_rows]
                )
                keys.append(ready.astype(np.int8))
            elif name == "drf":
                # lower dominant share first (drf.go:114-132) — same math
                # as Resource.share over the semantic dims
                m = spec.semantic_mask
                t = ssn.total_allocatable().vec[m]
                alloc = cols.j_alloc[minres_rows][:, m]
                with np.errstate(divide="ignore", invalid="ignore"):
                    ratios = np.where(t > 0, alloc / t, 0.0)
                keys.append(
                    ratios.max(axis=1) if ratios.shape[1]
                    else np.zeros(minres_rows.size)
                )
            else:
                return False  # unsupported voter → object walk
        creation = cols.j_creation[minres_rows]
        sort_keys = []
        if np.unique(creation).size != creation.size:
            # creation-index ties fall back to uid (session job_order_fn's
            # final tie-break) — materialized only when ties exist
            sort_keys.append(np.array(
                [job_by_row[r].uid for r in minres_rows.tolist()]
            ))
        sort_keys.append(creation)
        sort_keys.extend(reversed(keys))
        sort_keys.append(rank_by_qi[qrows_of])
        order = np.lexsort(tuple(sort_keys))
        ordered = minres_rows[order]

        # the jitted prefix-scan (ops/admission.py) at the padded job-axis
        # capacity — shape-stable across the steady-state wobble.  When the
        # cycle's solves shard over the mesh, the scan rides the mesh too
        # (a replicated shard_map body: every device/process computes the
        # same admitted mask — multi-controller placement consistency)
        capJ = cols.jobs.cap
        k = ordered.size
        minr = np.zeros((capJ, spec.n), np.float32)
        minr[:k] = cols.j_minres[ordered]
        candv = np.zeros(capJ, bool)
        candv[:k] = enq_ok[order]
        from kube_batch_tpu.guard import guard_of
        from kube_batch_tpu.obs.trace import tracer_of
        from kube_batch_tpu.parallel.mesh import (
            call,
            default_mesh,
            program,
            shard_map_enabled,
            should_shard,
        )

        gp = guard_of(ssn.cache)
        tracer = tracer_of(ssn.cache)
        idle_v = idle.vec.astype(np.float32)
        quanta_v = spec.quanta.astype(np.float32)
        # the gate rides the mesh where the cycle's solves shard and the
        # shard_map path is on; verdicts are bit-equal either way (both
        # trace ops.admission.gate_scan)
        mesh = (default_mesh()
                if should_shard(cols.nodes.cap) and shard_map_enabled()
                else None)
        # the FUSED gate sentinel (ops/invariants): admitted ⊆ candidates +
        # the all-finite budget sweep run in the same compiled program as
        # the admission scan, verdict riding the one readback — the
        # single-device twin of the solve sentinels.  The replicated
        # shard_map gate has no fused variant
        fused = gp.enabled and mesh is None
        with tracer.device_span("gate_dispatch", cols=cols) as sp_gate:
            out = call(
                program("gate", mesh, None, None, fused), mesh,
                minr, candv, idle_v, quanta_v)
            # kbt: allow[KBT010] the enqueue gate's ONE sanctioned
            # readback: the admitted-rows mask the promotions consume +
            # the fused verdict
            admitted, verdict = jax.device_get(
                out[:2] if fused else (out, np.int32(0)))
            admitted = np.asarray(admitted)[:k]
            bad = int(verdict)
            if gp.enabled and not fused:
                # mesh path: the invariant is host-checkable from the
                # dispatch's own host-built inputs
                bad = int(np.sum(admitted & ~candv[:k]))
                if (not np.isfinite(minr).all()
                        or not np.isfinite(idle_v).all()
                        or not np.isfinite(quanta_v).all()):
                    bad += 1
        sp_gate.set(candidates=int(k))
        # a violation fails CLOSED: no scan-derived promotions from a
        # condemned verdict (the Pending walk re-decides next cycle); the
        # unconditional promotions never consumed the condemned scan
        if gp.enabled and not gp.consume_verdict(
            "enqueue", [], bad, detail=f"enqueue gate verdict={bad}",
        ):
            self._promote_rows(ssn, cols, uncond_rows)
            return True
        admitted_rows = ordered[admitted].tolist()
        # sampled shadow audit (guard tier 2, the object-walk coverage the
        # ROADMAP standing item asks for): every KB_AUDIT_EVERY-th gate
        # dispatch re-derives the admission through the reference walk —
        # the same oracle the gate-equivalence tests pin — over the still
        # UNMUTATED session, and compares decision SETS.  On mismatch the
        # guard trips (unattributable → conservative demotion + resident
        # heal) and the WALK's decisions are applied: the oracle is
        # authoritative, exactly like a demoted solve path running pjit.
        if gp.enabled and gp.audit_due("enqueue"):
            from kube_batch_tpu.guard import make_heal

            walk_jobs = self._walk_decisions(ssn)
            expected = {job.uid for job in walk_jobs}
            actual = {job_by_row[r].uid
                      for r in uncond_rows + admitted_rows}
            matched = expected == actual
            gp.note_audit(
                "enqueue", [], matched,
                detail=(
                    "enqueue gate vs object-walk divergence: "
                    f"gate-only={sorted(actual - expected)[:8]} "
                    f"walk-only={sorted(expected - actual)[:8]}"
                ) if not matched else "",
                heal=make_heal(ssn),
            )
            if not matched:
                for job in walk_jobs:
                    self._promote(cols, job)
                return True
        self._promote_rows(ssn, cols, uncond_rows + admitted_rows)
        return True

    # ------------------------------------------------------------------
    def _execute_walk(self, ssn, cols) -> None:
        """The reference walk (enqueue.go:74-117) — the always-correct
        fallback for non-columnar sessions and exotic plugin sets, and the
        oracle the gate-equivalence tests compare against."""
        for job in self._walk_decisions(ssn):
            self._promote(cols, job)

    def _walk_decisions(self, ssn) -> list:
        """The reference walk's admission DECISIONS, with no mutation:
        the promotion list in walk order.  Shared by the walk execution
        path and the columnar gate's sampled shadow audit (which must run
        the oracle over the still-unmutated session and diff decision
        sets)."""
        decisions = []
        queues = PriorityQueue(less=ssn.queue_order_fn)
        queue_set = set()
        jobs_map = {}
        any_min_res = False
        for job in ssn.jobs.values():
            if job.queue not in ssn.queues:
                continue
            if job.pod_group is None or job.pod_group.phase != PodGroupPhase.PENDING:
                continue
            if job.pod_group.min_resources is None:
                # unconditional promotion (enqueue.go:102-105): admission
                # order is unobservable for jobs that consume no budget, so
                # they skip the priority-queue machinery entirely — at 12.5k
                # Pending podgroups the tiered order comparisons alone were
                # ~0.8s of host time
                decisions.append(job)
                continue
            any_min_res = True
            queue = ssn.queues[job.queue]
            if queue.name not in queue_set:
                queue_set.add(queue.name)
                queues.push(queue)
            jobs_map.setdefault(queue.name, PriorityQueue(less=ssn.job_order_fn)).push(job)

        if not any_min_res:
            return decisions

        # idle = total × 1.2 − used (enqueue.go:74-81)
        total = ssn.spec.empty()
        used = ssn.spec.empty()
        for node in ssn.nodes.values():
            total.add_(node.allocatable)
            used.add_(node.used)
        idle = total.multi(OVERCOMMIT_FACTOR)
        if used.less_equal(idle):
            idle.sub_(used)
        else:
            idle = ssn.spec.empty()

        while queues:
            queue = queues.pop()
            jobs = jobs_map.get(queue.name)
            if not jobs:
                continue
            job = jobs.pop()
            min_req = ssn.spec.empty()
            for name, v in job.pod_group.min_resources.items():
                if name in ssn.spec:
                    min_req.vec[ssn.spec.index(name)] = float(v)
            if ssn.job_enqueueable(job) and min_req.less_equal(idle):
                decisions.append(job)
                idle.sub_(min_req)
            queues.push(queue)
        return decisions
