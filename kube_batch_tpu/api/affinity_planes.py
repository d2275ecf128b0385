"""Match-count planes — inter-pod (anti-)affinity without object scans.

What a pod-affinity term needs to know is a COUNT per (selector signature,
topology domain): how many pods that the term's selector selects sit in the
domain of a node.  kube-scheduler keeps exactly that in its PreFilter state
(``topologyToMatchedTermCount``); here it is a dense plane the ColumnStore
owns and updates at the rows a bind, a delete or a status change touches:

  ``cnt [S, capN]``   pods accounted on node row n (``t_node``) that
                      signature s selects
  ``t_sig [capT, S]`` task row r is selected by signature s
  ``dom(d) [capN]``   topology key d's domain of node row n, as the smallest
                      node row of the domain (hostname: the row itself; a
                      node without the label is a domain of its own, as
                      ``predicates._topology_domain`` has it)

A pending row's required mask and preferred score row are then array
operations over these (segment sum over domain ids, compare, gather), with
the semantics of ``plugins.predicates.pod_affinity_ok`` and
``plugins.nodeorder.preferred_pod_affinity_score`` bit for bit: those two
stay in the tree as the object-scan oracle the planes are tested against
(tests/test_constraints.py), and ``api.snapshot.build_snapshot`` is the one
builder that still calls them.

Selectors are ``PodAffinityTerm.match_labels`` (api/pod.py); a signature is
the sorted tuple of its pairs.  Signatures and topology keys are interned by
the rows that carry a term with them and released with the last such row.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from kube_batch_tpu.api.pod import HOSTNAME_TOPOLOGY
from kube_batch_tpu.utils import telemetry

#: rungs of the sparse row axes ([K] required rows, [Kp] preferred rows) and
#: of the pair axis: the resident scatter's x8 ladder (api/resident.py
#: SCATTER_SLOT_BUCKETS), doubled past its top.  A store that has never held
#: a term ships the one padding row every program was compiled with.
ROW_RUNGS: Tuple[int, ...] = (64, 512, 4096)
PAIR_RUNGS: Tuple[int, ...] = (4, 16, 64)
#: a derived row that moved at more nodes than this since the last snapshot
#: re-ranks its carried rows; at fewer, the nodes join the warm merge's
#: changed set (api/resident.py WarmTableState.plan)
WIDE_CHANGE = 64


def rung(n: int, rungs: Tuple[int, ...]) -> int:
    for r in rungs:
        if n <= r:
            return r
    r = rungs[-1]
    while r < n:
        r *= 2
    return r


class AffinityTerms(NamedTuple):
    """The in-solve half of the inter-pod terms (``DeviceSnapshot.aff_terms``;
    None where no live row carries one): what ``ops.assignment`` needs so
    that pods placed earlier in the same solve count, for the required terms'
    mask and for the preferred terms' score alike.  Row axis [K] is that of
    ``task_aff_idx``; pair axis [Pp] is the (signature, topology key) pairs
    the pending rows' terms use."""

    row: np.ndarray    # [K] i32 global task row (the tie hash's), -1 pad
    anti: np.ndarray   # [K, Pp] bool: row has an anti-affinity term on p
    need: np.ndarray   # [K, Pp] bool: row has an affinity term on p that no
    #                    pod anywhere satisfied at snapshot time (the first-
    #                    pod fast path: the first placement pins the domain)
    selp: np.ndarray   # [K, Pp] bool: pair p's signature selects the row
    dom: np.ndarray    # [Pp, N] i32 domain (smallest node row) per node
    pw: np.ndarray     # [K, Pp] f32: the row's preferred weight on p, signed
    #                    (anti-affinity negative), 0 where it has none
    here: np.ndarray   # [Pp, N] bool: a pod p's signature selects sat in the
    #                    node's domain at snapshot time
    live: np.ndarray   # [N] bool: node rows that hold a node (the min-max
    #                    reduce of a preferred row runs over these)


class _RowTerms(NamedTuple):
    aff: tuple      # ((sig, key), ...) required affinity
    anti: tuple     # ((sig, key), ...) required anti-affinity
    pref: tuple     # ((signed weight, sig, key), ...) preferred pod terms
    node_pref: bool  # carries preferred node-affinity terms


def _signature(term) -> tuple:
    return tuple(sorted(term.match_labels.items()))


def _selects(sig: tuple, labels) -> bool:
    return all(labels.get(k) == v for k, v in sig)


class AffinityPlanes:
    def __init__(self, store):
        self.store = store
        capT, capN = store.tasks.cap, store.nodes.cap
        self.S = PAIR_RUNGS[0]
        self.cnt = np.zeros((self.S, capN), np.int32)
        self.t_sig = np.zeros((capT, self.S), bool)
        self.t_req = np.zeros(capT, bool)    # row carries a required term
        self.t_pref = np.zeros(capT, bool)   # row carries a preferred term
        self.t_ppref = np.zeros(capT, bool)  # ... a preferred POD term
        self._sig_slot: Dict[tuple, int] = {}
        self._sig_of: List[Optional[tuple]] = [None] * self.S
        self._sig_refs = np.zeros(self.S, np.int32)
        self._sig_req_refs = np.zeros(self.S, np.int32)
        self._keys: Dict[str, int] = {HOSTNAME_TOPOLOGY: 0}
        self._key_of: List[str] = [HOSTNAME_TOPOLOGY]
        # derived from the node rows and the keys in use, lazily, and dropped
        # by nodes_changed(): [D, capN] domains, [capN] rows that hold a
        # node, the count of distinct domains
        self._dom: Optional[np.ndarray] = None
        self._live: Optional[np.ndarray] = None
        self._n_domains: Optional[int] = None
        self._rows: Dict[int, _RowTerms] = {}
        # what the derivation last gave each group of rows with the same
        # terms: (mask row, score row); the warm planner's invalidation
        self._last_rows: Dict[tuple, Tuple[np.ndarray, np.ndarray]] = {}
        # what keeping the planes cost since the tally was last taken:
        # seconds inside the update paths below, cells moved
        self._busy_s = 0.0
        self._updates = 0
        self._node_pref_cache: Dict = {}

    # ------------------------------------------------------------------
    # axes
    # ------------------------------------------------------------------
    def grow_tasks(self, cap: int) -> None:
        for name in ("t_sig", "t_req", "t_pref", "t_ppref"):
            old = getattr(self, name)
            new = np.zeros((cap,) + old.shape[1:], old.dtype)
            new[: old.shape[0]] = old
            setattr(self, name, new)

    def grow_nodes(self, cap: int) -> None:
        new = np.zeros((self.S, cap), np.int32)
        new[:, : self.cnt.shape[1]] = self.cnt
        self.cnt = new
        self.nodes_changed()

    def nodes_changed(self) -> None:
        """A node row was bound or freed, or a node's labels moved."""
        self._dom = self._live = self._n_domains = None
        self._node_pref_cache.clear()

    @property
    def live_signatures(self) -> int:
        return len(self._sig_slot)

    def _live_nodes(self) -> np.ndarray:
        """[capN] bool: node rows that hold a node."""
        if self._live is None:
            self._live = np.array(
                [n is not None for n in self.store.node_by_row])
        return self._live

    def live_domains(self) -> int:
        """Distinct domains over the topology keys in use."""
        if self._n_domains is None:
            live = self._live_nodes()
            self._n_domains = int(
                sum(np.unique(d[live]).size for d in self.domains()))
        return self._n_domains

    # ------------------------------------------------------------------
    # interning
    # ------------------------------------------------------------------
    def _grow_sigs(self) -> None:
        S = self.S * 4
        cnt = np.zeros((S, self.cnt.shape[1]), np.int32)
        cnt[: self.S] = self.cnt
        self.cnt = cnt
        sig = np.zeros((self.t_sig.shape[0], S), bool)
        sig[:, : self.S] = self.t_sig
        self.t_sig = sig
        for name in ("_sig_refs", "_sig_req_refs"):
            old = getattr(self, name)
            new = np.zeros(S, np.int32)
            new[: self.S] = old
            setattr(self, name, new)
        self._sig_of.extend([None] * (S - self.S))
        self.S = S

    def _intern(self, term, required: bool) -> Tuple[int, int]:
        sig = _signature(term)
        s = self._sig_slot.get(sig)
        if s is None:
            if len(self._sig_slot) == self.S:
                self._grow_sigs()
            s = self._sig_of.index(None)
            self._sig_slot[sig] = s
            self._sig_of[s] = sig
            # the one scan a signature ever costs: who it selects today
            store = self.store
            for row, t in enumerate(store.task_by_row):
                if t is not None and _selects(sig, t.pod.labels):
                    self.t_sig[row, s] = True
                    n = store.t_node[row]
                    if n >= 0:
                        self.cnt[s, n] += 1
        self._sig_refs[s] += 1
        if required:
            self._sig_req_refs[s] += 1
        d = self._keys.get(term.topology_key)
        if d is None:
            d = self._keys[term.topology_key] = len(self._key_of)
            self._key_of.append(term.topology_key)
            self._dom = self._n_domains = None
        return s, d

    def _release(self, s: int, required: bool) -> None:
        self._sig_refs[s] -= 1
        if required:
            self._sig_req_refs[s] -= 1
        if self._sig_refs[s] == 0:
            del self._sig_slot[self._sig_of[s]]
            self._sig_of[s] = None
            self.cnt[s] = 0
            self.t_sig[:, s] = False

    # ------------------------------------------------------------------
    # rows (called by the ColumnStore's task-axis choke points)
    # ------------------------------------------------------------------
    def take_tally(self) -> Tuple[float, int]:
        """(seconds spent keeping the planes, cells moved) since the last
        call; the ingest drain's ``affinity_plane_update`` span reads it."""
        out = (self._busy_s, self._updates)
        self._busy_s, self._updates = 0.0, 0
        return out

    def bind_row(self, row: int, pod, node_row: int) -> None:
        aff = pod.affinity
        if aff is None and not self._sig_slot:
            return  # a deployment without terms pays this one test
        t0 = telemetry.perf_counter()
        self._bind_row(row, pod, node_row)
        self._busy_s += telemetry.perf_counter() - t0

    def _bind_row(self, row: int, pod, node_row: int) -> None:
        aff = pod.affinity
        if aff is not None and (
            aff.pod_affinity or aff.pod_anti_affinity
            or aff.preferred_pod_affinity or aff.preferred_pod_anti_affinity
            or aff.preferred_node_terms
        ):
            terms = _RowTerms(
                aff=tuple(self._intern(t, True) for t in aff.pod_affinity),
                anti=tuple(self._intern(t, True)
                           for t in aff.pod_anti_affinity),
                pref=tuple(
                    (sign * float(w),) + self._intern(t, False)
                    for sign, group in ((1.0, aff.preferred_pod_affinity),
                                        (-1.0, aff.preferred_pod_anti_affinity))
                    for w, t in group),
                node_pref=bool(aff.preferred_node_terms),
            )
            self._rows[row] = terms
            self.t_req[row] = bool(terms.aff or terms.anti)
            self.t_pref[row] = bool(terms.pref or terms.node_pref)
            self.t_ppref[row] = bool(terms.pref)
        if not self._sig_slot:
            return
        for sig, s in self._sig_slot.items():
            if _selects(sig, pod.labels):
                self.t_sig[row, s] = True
                if node_row >= 0:
                    self.cnt[s, node_row] += 1
                    self._updates += 1

    def free_row(self, row: int, node_row: int) -> None:
        if not self._sig_slot and row not in self._rows:
            return
        t0 = telemetry.perf_counter()
        if node_row >= 0:
            self._move(row, node_row, -1)   # inside this call's own tally
        self.t_sig[row] = False
        terms = self._rows.pop(row, None)
        if terms is not None:
            self.t_req[row] = self.t_pref[row] = self.t_ppref[row] = False
            for s, _ in terms.aff + terms.anti:
                self._release(s, True)
            for _, s, _ in terms.pref:
                self._release(s, False)
        self._busy_s += telemetry.perf_counter() - t0

    def move_row(self, row: int, old: int, new: int) -> None:
        """Task row ``row`` is accounted on node row ``new`` where it was
        on ``old`` (-1: on none)."""
        if old == new or not self._sig_slot:
            return
        t0 = telemetry.perf_counter()
        self._move(row, old, new)
        self._busy_s += telemetry.perf_counter() - t0

    def _move(self, row: int, old: int, new: int) -> None:
        ss = np.flatnonzero(self.t_sig[row])
        if ss.size:
            if old >= 0:
                self.cnt[ss, old] -= 1
            if new >= 0:
                self.cnt[ss, new] += 1
            self._updates += int(ss.size)

    def move_rows(self, rows: np.ndarray, old: np.ndarray,
                  new: np.ndarray) -> None:
        """``move_row`` for whole arrays (the columnar replay's bulk bind,
        a freed node's residents)."""
        if not self._sig_slot or not rows.size:
            return
        t0 = telemetry.perf_counter()
        r, s = np.nonzero(self.t_sig[rows])
        if r.size:
            o, n = old[r], new[r]
            np.subtract.at(self.cnt, (s[o >= 0], o[o >= 0]), 1)
            np.add.at(self.cnt, (s[n >= 0], n[n >= 0]), 1)
            self._updates += int(r.size)
        self._busy_s += telemetry.perf_counter() - t0

    # ------------------------------------------------------------------
    # derivation
    # ------------------------------------------------------------------
    def domains(self) -> np.ndarray:
        """[D, capN] i32: per topology key, each node row's domain as the
        smallest node row in it."""
        if self._dom is not None:
            return self._dom
        store = self.store
        capN = store.nodes.cap
        dom = np.tile(np.arange(capN, dtype=np.int32), (len(self._key_of), 1))
        for d, key in enumerate(self._key_of):
            if d == 0:
                continue
            first: Dict[str, int] = {}
            for row, n in enumerate(store.node_by_row):
                if n is None or n.node is None:
                    continue
                value = n.node.labels.get(key)
                if value is not None:
                    dom[d, row] = first.setdefault(value, row)
        self._dom = dom
        return dom

    def _present(self, s: int, d: int, memo: dict) -> np.ndarray:
        """[capN] i32: pods signature s selects in each node's domain of
        key d."""
        got = memo.get((s, d))
        if got is None:
            if d == 0:
                got = self.cnt[s]
            else:
                dom = self.domains()[d]
                got = np.bincount(dom, weights=self.cnt[s],
                                  minlength=dom.shape[0]).astype(np.int32)[dom]
            memo[(s, d)] = got
        return got

    def _own(self, row: int, s: int, d: int) -> Optional[np.ndarray]:
        """Where a placed row counts itself under (s, d): the mask of its
        own domain, None for a row that is on no node or not selected."""
        n = self.store.t_node[row]
        if n < 0 or not self.t_sig[row, s]:
            return None
        dom = self.domains()[d]
        return dom == dom[n]

    def mask_row(self, row: int, memo: Optional[dict] = None) -> np.ndarray:
        """[capN] bool: ``pod_affinity_ok(task of row, node)`` per node row
        (True at rows that hold no node)."""
        terms = self._rows.get(row)
        capN = self.cnt.shape[1]
        ok = np.ones(capN, bool)
        if terms is None:
            return ok
        memo = {} if memo is None else memo
        for s, d in terms.aff:
            here = self._present(s, d, memo) > 0
            # a term no pod satisfies anywhere does not block (the group's
            # first pod has to land somewhere)
            if self.cnt[s].any():
                ok &= here
        for s, d in terms.anti:
            cnt = self._present(s, d, memo)
            own = self._own(row, s, d)
            ok &= (cnt - own if own is not None else cnt) <= 0
        return ok

    def raw_score_row(self, row: int, memo: Optional[dict] = None
                      ) -> np.ndarray:
        """[capN] f32: ``preferred_pod_affinity_score(task of row, node)``."""
        terms = self._rows.get(row)
        out = np.zeros(self.cnt.shape[1], np.float32)
        if terms is None:
            return out
        memo = {} if memo is None else memo
        for w, s, d in terms.pref:
            out += np.float32(w) * (self._present(s, d, memo) > 0)
        return out

    def node_score_row(self, row: int) -> np.ndarray:
        """[capN] f32: ``preferred_node_affinity_score`` per node row."""
        from kube_batch_tpu.plugins.nodeorder import (
            preferred_node_affinity_score,
        )

        store = self.store
        task = store.task_by_row[row]
        key = repr(task.pod.affinity.preferred_node_terms)
        got = self._node_pref_cache.get(key)
        if got is None:
            got = np.zeros(self.cnt.shape[1], np.float32)
            for n in store.node_by_row:
                if n is not None:
                    got[n._row] = preferred_node_affinity_score(task, n)
            self._node_pref_cache[key] = got
        return got

    def scaled_score_row(self, row: int, memo: Optional[dict] = None
                         ) -> np.ndarray:
        """The raw row min-max reduced to 0..10 over the node rows in use
        (nodeorder.minmax_scale_rows: InterPodAffinityPriority's reduce)."""
        from kube_batch_tpu.plugins.nodeorder import minmax_scale_rows

        raw = self.raw_score_row(row, memo)
        live = self._live_nodes()
        out = np.zeros_like(raw)
        if live.any():
            out[live] = minmax_scale_rows(raw[live][None, :])[0]
        return out

    # ------------------------------------------------------------------
    def snapshot_rows(self, pending: np.ndarray):
        """The sparse rows of one device snapshot, for the pending rows
        alone: ``(aff_idx, aff_mask, pref_idx, pref_node, pref_pod, terms,
        stats)``.  Rows with the same terms share one derivation."""
        t0 = telemetry.perf_counter()
        store = self.store
        capN = self.cnt.shape[1]
        stats = {"required": 0, "preferred": 0, "changed_nodes": None,
                 "rerank_rows": (), "derive_s": 0.0}
        if not self._rows and not self._sig_slot:
            return (np.full(1, -1, np.int32), np.ones((1, capN), bool),
                    np.full(1, -1, np.int32), np.zeros((1, capN), np.float32),
                    np.zeros((1, capN), np.float32), None, stats)
        memo: dict = {}
        changed = np.zeros(capN, bool)
        rerank: List[int] = []
        seen: Dict[tuple, tuple] = {}

        def derive(row: int):
            """(mask, node score, pod score) of the row's group."""
            terms = self._rows.get(row)
            if terms is None:
                return None
            key = (terms, repr(store.task_by_row[row].pod.affinity
                               .preferred_node_terms)
                   if terms.node_pref else None)
            got = seen.get(key)
            if got is None:
                mask = self.mask_row(row, memo)
                node = (self.node_score_row(row) if terms.node_pref
                        else np.zeros(capN, np.float32))
                pod = (self.scaled_score_row(row, memo) if terms.pref
                       else np.zeros(capN, np.float32))
                got = seen[key] = (mask, node, pod, key, [])
            got[4].append(row)
            return got

        # the in-solve rows: those that carry an inter-pod term, required or
        # preferred, and those a live term's signature selects (their
        # placement moves the term's count inside the solve).  Of them the
        # required ones (a required term, or selected by one) are counted
        # apart; a row without a required term keeps an all-true mask
        req_sigs = np.flatnonzero(self._sig_req_refs > 0)
        r_mask = pending & self.t_req
        if req_sigs.size:
            r_mask |= pending & self.t_sig[:, req_sigs].any(axis=1)
        stats["required"] = int(r_mask.sum())
        x_rows = np.flatnonzero(
            r_mask | (pending & (self.t_ppref | self.t_sig.any(axis=1))))
        K = rung(x_rows.size, ROW_RUNGS) if self._sig_slot else 1
        aff_idx = np.full(K, -1, np.int32)
        aff_mask = np.ones((K, capN), bool)
        aff_idx[: x_rows.size] = x_rows
        for k, row in enumerate(x_rows.tolist()):
            got = derive(row)
            if got is not None:
                aff_mask[k] = got[0]

        p_rows = np.flatnonzero(pending & self.t_pref)
        stats["preferred"] = int(p_rows.size)
        Kp = rung(p_rows.size, ROW_RUNGS) if self.t_pref.any() else 1
        pref_idx = np.full(Kp, -1, np.int32)
        pref_node = np.zeros((Kp, capN), np.float32)
        pref_pod = np.zeros((Kp, capN), np.float32)
        pref_idx[: p_rows.size] = p_rows
        for k, row in enumerate(p_rows.tolist()):
            _, pref_node[k], pref_pod[k], _, _ = derive(row)

        # what moved since the last snapshot, per group: few nodes join the
        # warm merge's changed set, many re-rank the group's rows
        last, self._last_rows = self._last_rows, {}
        for mask, node, pod, key, rows in seen.values():
            score = node + pod
            self._last_rows[key] = (mask, score)
            was = last.get(key)
            if was is None or was[0].shape != mask.shape:
                continue  # every row of the group is new to the table
            moved = (was[0] != mask) | (was[1] != score)
            if int(moved.sum()) > WIDE_CHANGE:
                rerank.extend(rows)
            else:
                changed |= moved
        stats["changed_nodes"] = changed
        stats["rerank_rows"] = rerank

        terms = (self._solve_terms(x_rows, aff_idx, K, memo)
                 if self._sig_slot else None)
        stats["derive_s"] = telemetry.perf_counter() - t0
        return aff_idx, aff_mask, pref_idx, pref_node, pref_pod, terms, stats

    def _solve_terms(self, x_rows: np.ndarray, aff_idx: np.ndarray,
                     K: int, memo: dict) -> AffinityTerms:
        pairs: Dict[Tuple[int, int], int] = {}
        per_row = []
        for row in x_rows.tolist():
            terms = self._rows.get(row)
            if terms is None:
                per_row.append(((), (), ()))
                continue
            for sd in terms.aff + terms.anti + tuple(
                    (s, d) for _, s, d in terms.pref):
                pairs.setdefault(sd, len(pairs))
            per_row.append((terms.aff, terms.anti, terms.pref))
        Pp = rung(len(pairs), PAIR_RUNGS)
        capN = self.cnt.shape[1]
        anti = np.zeros((K, Pp), bool)
        need = np.zeros((K, Pp), bool)
        selp = np.zeros((K, Pp), bool)
        pw = np.zeros((K, Pp), np.float32)
        here = np.zeros((Pp, capN), bool)
        dom = np.tile(np.arange(capN, dtype=np.int32), (Pp, 1))
        domains = self.domains()
        unmet = {s: not self.cnt[s].any() for s, _ in pairs}
        for (s, d), p in pairs.items():
            dom[p] = domains[d]
            selp[: x_rows.size, p] = self.t_sig[x_rows, s]
            here[p] = self._present(s, d, memo) > 0
        for k, (aff, anti_terms, pref) in enumerate(per_row):
            for sd in anti_terms:
                anti[k, pairs[sd]] = True
            for sd in aff:
                need[k, pairs[sd]] = unmet[sd[0]]
            for w, s, d in pref:
                pw[k, pairs[(s, d)]] += np.float32(w)
        return AffinityTerms(row=aff_idx.copy(), anti=anti, need=need,
                             selp=selp, dom=dom, pw=pw, here=here,
                             live=self._live_nodes().copy())

    # ------------------------------------------------------------------
    def rebuilt_counts(self) -> np.ndarray:
        """The plane as a scan of every row gives it (tests and
        ``ColumnStore.check_consistency``: the incremental update against a
        rebuild)."""
        store = self.store
        cnt = np.zeros_like(self.cnt)
        for sig, s in self._sig_slot.items():
            for row, t in enumerate(store.task_by_row):
                if (t is not None and store.t_node[row] >= 0
                        and _selects(sig, t.pod.labels)):
                    cnt[s, store.t_node[row]] += 1
        return cnt
