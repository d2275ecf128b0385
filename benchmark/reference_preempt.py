"""The plain reference of priority preemption inside one queue
(``configs/schedperf-preempt-20k-5k.json``: upstream scheduler_perf's
PreemptionBasic): ``reference_tiers.Ledger``'s contract (what was sent by
class, the eviction feed as it came, what the stand-in deleted) with the one
thing that differs kept here: a pod is protected by **its job's priority
inside the claimant's queue**, not by the gang veto.  Every job of this
deployment has one member, so no gang protects anybody.

Nothing here imports the program; numpy int64 on milli-cores, bytes and pod
slots, so no sum rounds.  ``check_binds`` reports under ``reference.LIMITS``'
names, as ``reference_tiers.Ledger.check_binds`` does:

- a pod of the highest class (``high``) in the feed -> ``gangs_split``: once
  the stand-in deletes it its one-member job is bound below ``minMember``;
- a victim never sent, already deleted or never reported Running (as in
  ``reference_tiers``), of another queue than its claimant, or of a job whose
  priority is not lower than its claimant's -> ``unknown_pods``;
- a claim (the entries one action wrote for one claimant on one node, in a
  row) whose victims plus the node's idle do not cover the claimant in CPU,
  memory and a pod slot, by the feed's own order (each claim frees its
  victims and promises the claimant its room), or a node over allocatable at
  the end -> ``nodes_over``;
- a claimant given a second claim while a victim of an earlier one was
  still to be deleted -> ``double_binds``;
- a ``high`` pod never bound after the drain -> ``unbound``;
- an *over* pod of the edge round bound, or named as a claimant ->
  ``overfit_binds``.

``victims_per_claim`` is a note, not a limit: the reference scheduler
evicts lowest task order first until the victims ALONE cover the request
(preempt.go:219-237 after :262-277 validateVictims; a node's idle is never
counted), which is four 900m pods for a 3,000m claimant; upstream
Kubernetes, which counts the node's 400m idle, would take three.

``place`` is the reference scheduler's preempt, one preemptor after another
(preempt.go:180-260), with the modes the controls need; ``edge_control``
puts the edge round's pods against a victim plane summed in a lower
precision.
"""

from __future__ import annotations

import numpy as np

import reference
import reference_tiers
from reference import OVER_MILLI, plane
from reference_tiers import QUANTUM


class Ledger(reference_tiers.Ledger):
    """What was sent, by priority class; what was ordered evicted, and
    whether the claimant outranked it; what was deleted."""

    def __init__(self, config: dict, seed: int):
        classes = config["priority_classes"]
        super().__init__(dict(config, tiers=classes), seed)
        self.prio = {c["name"]: int(c["priority"]) for c in classes}
        top = max(self.prio.values())
        #: nobody outranks these, so nothing may ever evict them
        self.protected = {n for n, p in self.prio.items() if p == top}
        self.outranked = 0      # victims their claimant did not outrank
        self.used0 = None       # [N, 3] at the Running report (note_bound)

    # -- what the harness sends --------------------------------------------

    def make_population(self):
        pop = self.config["population"]
        return self.make_tier(pop["class"], int(pop["pods"]))

    def note_bound(self, binds: list) -> None:
        """The binds after the cold drain: what every node holds before
        the first claim, for ``uncovered_claims``."""
        with self.lock:
            self.used0 = reference.Ledger.check_binds(self, binds)[1]

    def add_bound(self, pgs: list, pods: list) -> None:
        """These were sent already bound and Running (the edge round's
        fillers): live, Running, and on their nodes from the start as far
        as the claims' accounting goes (no claim came to their nodes
        before them)."""
        self.add(pgs, pods)
        with self.lock:
            for pod in pods:
                key = self.key(pod)
                self.running.add(key)
                cpu, mem, _ = self.pods[key]
                self.used0[self.node_index[pod["node_name"]]] += (cpu, mem, 1)

    # -- the eviction feed and the stand-in --------------------------------

    def may_evict(self, claimant: str, victim: str) -> bool:
        """Same queue, and the victim's job of lower priority than the
        claimant's (every job here is its one pod's class)."""
        return (self.queue.get(victim) == self.queue.get(claimant)
                and self.prio.get(self.tier.get(victim), 0)
                < self.prio.get(self.tier.get(claimant), 0))

    def note_evictions(self, entries: list) -> list:
        with self.lock:
            self.outranked += sum(
                1 for e in entries
                if e["pod"] in self.tier
                and not self.may_evict(e["claimant"], e["pod"]))
        return super().note_evictions(entries)

    def groups_of(self, doomed: list) -> list:
        """The one-member PodGroups of the victims ``note_evictions`` just
        returned: gone with their pods, for the stand-in's second DELETE."""
        out = []
        with self.lock:
            for pod in doomed:
                gang = pod["annotations"][reference.GROUP_NAME_ANNOTATION]
                if gang in self.gangs and not self.gangs[gang][0]:
                    out.append(self.gangs.pop(gang)[1])
        return out

    def claims(self) -> list:
        """[(claimant, node name, [victim keys])] in the feed's order."""
        out, last = [], None
        for e in self.feed:
            claim = (e["claimant"], e["action"], e["node"])
            if claim != last:
                out.append((e["claimant"], e["node"], []))
                last = claim
            out[-1][2].append(e["pod"])
        return out

    def victims_per_claim(self) -> float:
        claims = self.claims()
        return (sum(len(v) for _, _, v in claims) / len(claims)
                if claims else 0.0)

    def uncovered_claims(self) -> int:
        """Claims whose victims plus the node's idle do not hold their
        claimant, the feed replayed in its own order over ``used0``: a
        claim frees its victims (each once, where the ledger knew them)
        and, covered or not, promises its claimant the room."""
        if self.used0 is None:
            return super().uncovered_claims()
        asked = {k: v[:2] for k, v in self.pods.items()}
        asked.update(self.unfit)
        used, freed, short = self.used0.copy(), set(), 0
        for claimant, node_name, victims in self.claims():
            node = self.node_index.get(node_name, -1)
            for key in set(victims) - freed:
                cpu, mem, at = self.deleted.get(key, (0, 0, -1))
                if at >= 0:
                    used[at] -= (cpu, mem, 1)
            freed.update(victims)
            need = asked.get(claimant)
            if need is None or node < 0:
                continue
            room = self.alloc[node] - used[node]
            short += bool(need[0] > room[0] + QUANTUM or need[1] > room[1]
                          or room[2] < 1)
            used[node] += (need[0], need[1], 1)
        return short

    # -- what came back ------------------------------------------------------

    def check_binds(self, binds: list):
        numbers, used = super().check_binds(binds)
        with self.lock:
            numbers["unknown_pods"] += self.outranked
        return numbers, used

    def victims_on(self, binds: list, running_only: bool = True) -> dict:
        """node index -> [(priority, -order, key, cpu, mem, queue)] of the
        live bound pods, in the order victims are taken: lowest priority
        first, then newest first.  Whom a row may be a victim of is the
        claimant's to say (``may_take``).  ``running_only`` False is the
        control's world, where every bound pod runs."""
        out: dict = {}
        with self.lock:
            for b in binds:
                key = b["pod"]
                if key in self.pods and (key in self.running
                                         or not running_only):
                    cpu, mem, _ = self.pods[key]
                    out.setdefault(self.node_index[b["node"]], []).append(
                        (self.prio[self.tier[key]], -self.order[key], key,
                         cpu, mem, self.queue[key]))
        for rows in out.values():
            rows.sort()
        return out


def may_take(row: tuple, queue: str, prio: int, mode: str = "exact") -> bool:
    """Is ``row`` (of ``victims_on``) a victim for a claimant of ``queue``
    and job priority ``prio``?  ``ignore_priority`` is the control: any
    running pod of the queue."""
    return row[5] == queue and (mode == "ignore_priority" or row[0] < prio)


# --------------------------------------------------------------------------
# the edge round: a pod whose only way in is the eviction of every victim of
# one node, summed exactly, and a pod that asks 12 m more than any node has
# --------------------------------------------------------------------------


def evictable_cpu(n_nodes: int, victims: dict, queue: str, prio: int,
                  precision: str = "exact") -> np.ndarray:
    """[N] the CPU that preempt may take on each node for a claimant of
    ``queue`` and ``prio``: its victims' requests, summed in ``precision``
    in the order they are taken (all nodes at once, one victim deep at a
    time)."""
    depth = max(map(len, victims.values()), default=0)
    cpu = np.zeros((depth, n_nodes), np.int64)
    for node, rows in victims.items():
        for i, row in enumerate(rows):
            if may_take(row, queue, prio):
                cpu[i, node] = row[3]
    total = np.zeros(n_nodes, np.int64)
    for layer in cpu:
        total = plane(total + layer, precision)
    return total


def edge_pair(idle_cpu, victims: dict, queue: str, prio: int):
    """(exact CPU, over CPU, node) for one round, or None where no one
    node offers most.  The exact pod asks all the CPU preempt may take for
    it on the node that offers most: the victims alone have to cover a
    claimant (preempt.go:262-277), so it binds there after every victim of
    that node is evicted, and one milli-core too few in the victim plane
    leaves it pending.  The over pod asks ``OVER_MILLI`` more than any node
    would have free with every victim gone (idle + evictable): no eviction
    covers it and no node ever holds it."""
    cap = evictable_cpu(len(idle_cpu), victims, queue, prio)
    node = int(np.argmax(cap))
    if int((cap == cap[node]).sum()) != 1:
        return None
    return (int(cap[node]), int((idle_cpu + cap).max()) + OVER_MILLI, node)


def edge_control(idle_cpu, victims: dict, rounds: list, queue: str,
                 prio: int, precision: str) -> dict:
    """The reference in the program's place over a victim plane summed in
    ``precision``.  Each of ``rounds`` is (node, filler row, exact CPU, over
    CPU): the filler joins its node's victims (and leaves its idle) as the
    round begins; the over pod binds where idle plus the plane holds it,
    the exact pod where the plane says the victims cover it, and the
    victims of a placement leave the plane.  Counts the exact pods left
    pending and the over pods bound; both 0 over the exact plane."""
    idle_cpu = np.array(idle_cpu, np.int64)
    victims = {n: list(rows) for n, rows in victims.items()}
    out = {"unbound": 0, "overfit_binds": 0}
    for at, filler, exact, over in rounds:
        victims[at] = sorted(victims.get(at, []) + [filler])
        idle_cpu[at] -= filler[3]
        cap = evictable_cpu(len(idle_cpu), victims, queue, prio, precision)
        out["overfit_binds"] += bool(
            int((idle_cpu + cap).max()) + QUANTUM >= over)
        covers = cap + QUANTUM >= exact
        if not covers.any():
            out["unbound"] += 1
            continue
        node = int(np.argmax(np.where(covers, cap, -1)))
        taken = [r for r in victims[node] if may_take(r, queue, prio)]
        victims[node] = [r for r in victims[node] if r not in taken]
        idle_cpu[node] += sum(r[3] for r in taken) - exact
    return out


# --------------------------------------------------------------------------
# the reference as a scheduler, for the controls
# --------------------------------------------------------------------------


def place(alloc, used, victims: dict, claimants: list, mode: str = "exact"):
    """Preempt as the reference scheduler does it, one preemptor after
    another (preempt.go:180-260): each claimant ``(cpu, mem, queue,
    priority)`` in turn scans the nodes in order; on the first whose victims
    (the running pods of lower-priority jobs of its queue) together cover
    it in CPU, memory and a pod slot it evicts them lowest task order
    first until the request is covered, and is pipelined there.  Returns
    (used after, evictions): ``evictions`` rows of (victim key, victim
    priority, claimant index, claimant priority, node).

    ``exact`` charges a cycle's own evictions and respects priority.
    ``ignore_priority``: any running pod of the queue is a victim
    (``outranked`` has to count > 0).  ``stale``: a cycle's own evictions
    are not charged, so the victims of an earlier claimant are offered
    again and the node they left is handed out twice (a node has to come
    out over)."""
    used = used.copy()
    victims = {n: list(rows) for n, rows in victims.items()}
    gone: set = set()
    evictions = []
    for at, (cpu, mem, queue, prio) in enumerate(claimants):
        need = np.array([cpu, mem, 1], np.int64)
        for node in sorted(victims):
            rows = [r for r in victims[node] if may_take(r, queue, prio, mode)]
            offer = np.array([sum(r[3] for r in rows),
                              sum(r[4] for r in rows), len(rows)], np.int64)
            if (offer < need).any():
                continue
            got = np.zeros(3, np.int64)
            for row in rows:
                if (got >= need).all():
                    break
                got += (row[3], row[4], 1)
                if row[2] not in gone:
                    gone.add(row[2])
                    used[node] -= (row[3], row[4], 1)
                    evictions.append((row[2], row[0], at, prio, node))
                if mode != "stale":
                    victims[node].remove(row)
            used[node] += need
            break
    return used, evictions


def outranked(evictions: list) -> int:
    """Evictions of ``place`` whose victim the claimant did not outrank:
    the ledger's own rule (``Ledger.may_evict``) over the reference's
    output."""
    return sum(1 for _, v_prio, _, c_prio, _ in evictions if v_prio >= c_prio)
