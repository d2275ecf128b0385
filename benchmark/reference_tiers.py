"""The plain reference of a deployment with priority tiers under overcommit
(``configs/borg-tiers-50k-5k.json``): ``reference.Ledger``'s contract plus
which pods may be evicted, what the scheduler ordered evicted (from ``GET
/v1/evictions``) and what the kubelet stand-in deleted for it.

Nothing here imports the program; numpy int64 on milli-cores, bytes and pod
slots, so no sum rounds.  What ``run.py`` reads of a ledger stays as it is
(``alloc`` ``[N, 3]``, ``pods[key]`` = ``(cpu, mem, gang)``).
``check_binds`` returns ``reference.LIMITS``' seven names and counts what
evictions may never do under the names there are (PERF.md section 2):

- a protected pod (a tier whose gangs have ``min_member`` > 1: production,
  mid) in the feed -> ``gangs_split``: once the stand-in deletes it its gang
  is bound below ``minMember``;
- an eviction of a pod never sent, already deleted, or never reported
  Running -> ``unknown_pods``;
- a claimant bound where the victims did not cover it -> ``nodes_over``:
  the node's live pods exceed its allocatable (the victims are gone by
  then, so the end state shows it); and a *claim* of the feed (the entries
  one action wrote for one claimant on one node, in a row) whose victims
  together ask less than the claimant in CPU or memory -> ``nodes_over``
  too: an eviction without a covered placement, whether or not the
  claimant then binds there;
- a claimant given a second claim while a victim of an earlier one was
  still to be deleted (its DELETE not yet acknowledged to the stand-in)
  -> ``double_binds``: an eviction in flight ordered again, the cascade
  the releasing gate rules out;
- a production pod never bound after the drain -> ``unbound``;
- an *over* pod of the edge round bound, or named as the claimant of any
  eviction -> ``overfit_binds``: it asks more than any node offers, so it may
  neither bind nor cost anybody a pod.

``place`` is the reference as a sequential reclaim in the reference
scheduler's own order (per claimant: nodes in order, cross-queue victims in
reverse task order until covered, reclaim.go:107-199), for the controls:
with ``stale`` it does not charge a cycle's own evictions and has to leave a
node over; ``edge_control`` puts the edge round's pods against a victim
plane summed in a lower precision.
"""

from __future__ import annotations

import threading

import numpy as np

import reference
from reference import OVER_MILLI, plane

#: the program, like the scheduler it follows, tolerates this much CPU in a
#: compare (reference.FIT_QUANTUM_MILLI): victims "cover" a request that
#: exceeds their sum by no more than it
QUANTUM = reference.FIT_QUANTUM_MILLI


class Ledger(reference.Ledger):
    """What was sent, by tier; what was ordered evicted; what was deleted."""

    def __init__(self, config: dict, seed: int):
        super().__init__(config, seed)
        self.tiers = {t["name"]: t for t in config["tiers"]}
        #: tiers whose pods may never be evicted (the gang plugin's veto)
        self.protected = {t["name"] for t in config["tiers"]
                          if int(t["min_member"]) > 1}
        self.tier: dict = {}        # "ns/name" -> tier name, every pod sent
        self.queue: dict = {}       # "ns/name" -> queue name
        self.order: dict = {}       # "ns/name" -> creation index
        self.pod_dicts: dict = {}   # "ns/name" -> the pod as sent
        self.running: set = set()   # keys the stand-in reported Running
        self.feed: list = []        # every feed entry, as it came
        self.deleted: dict = {}     # key -> (cpu, mem, node index) of victims
        self.bad_evictions = 0      # unknown, deleted or not Running
        self.protected_evicted = 0
        self.in_flight: dict = {}   # claimant -> victims not yet released
        self.repeat_in_flight = 0   # claims that met one of an earlier claim
        self._claim = None          # (claimant, action, node) being read
        self.lock = threading.Lock()

    # -- what the harness sends --------------------------------------------

    def priority_class_dicts(self) -> list:
        return [{"name": t["name"], "value": int(t["priority"])}
                for t in self.config["tiers"]]

    def _tier_pod(self, tier: str, cpu: int, mem: int, gang: str) -> dict:
        pod = self._pod(cpu, mem, gang)
        pod["priority_class"] = tier
        return pod

    def make_tier(self, tier: str, n_groups: int, sizes=None, queues=None,
                  cpus=None, mem=None):
        """(podgroup dicts, pod dicts) for ``n_groups`` fresh PodGroups of
        ``tier``: ``sizes[g]`` members each (the tier's ``size`` by
        default), ``min_member`` the tier's, requests drawn uniformly from
        the tier's shapes (or ``cpus[g]`` milli-cores and ``mem`` bytes: the
        edge round's pods and the warm-up's ticks), in ``queues[g]`` (round-robin from
        the first queue by default).  Not live until ``add``."""
        t = self.tiers[tier]
        names = [q["name"] for q in self.config["queues"]]
        if sizes is None:
            sizes = [int(t["size"])] * n_groups
        if queues is None:
            queues = [names[g % len(names)] for g in range(n_groups)]
        total = int(np.sum(sizes))
        if cpus is None:
            cpu_all = self.rng.choice(np.asarray(t["cpu_milli"], np.int64),
                                      total)
            mem_all = self.rng.choice(np.asarray(t["memory_bytes"], np.int64),
                                      total)
        else:
            cpu_all = np.repeat(np.asarray(cpus, np.int64), sizes)
            mem_all = np.full(total, int(mem), np.int64)
        pgs, pods, at = [], [], 0
        for size, queue in zip(sizes, queues):
            j = self._next_gang
            self._next_gang += 1
            gang = f"pg{j}"
            pgs.append({"name": gang, "namespace": self.namespace,
                        "uid": f"pg-{self.namespace}-{gang}",
                        "min_member": min(int(t["min_member"]), int(size)),
                        "queue": queue, "priority_class": tier,
                        "running": 0, "succeeded": 0, "failed": 0,
                        "creation_index": j, "shadow": False})
            for m in range(at, at + int(size)):
                pod = self._tier_pod(tier, int(cpu_all[m]), int(mem_all[m]),
                                     gang)
                self.queue[self.key(pod)] = queue
                pods.append(pod)
            at += int(size)
        return pgs, pods

    def make_population(self):
        """The configuration's ``population``: production and mid gangs up
        to their shares of the cluster's CPU, then PodGroups of the two low
        tiers (``size_min``-``size_max`` tasks, drawn per seed), beb and
        free in turn, until the pods are exactly ``population.pods``."""
        want = int(self.config["population"]["pods"])
        total_cpu = int(self.alloc[:, 0].sum())
        pgs, pods = [], []
        for t in self.config["tiers"]:
            if "cpu_share" not in t:
                continue
            per_gang = int(t["size"]) * int(t["cpu_milli"][0])
            g, p = self.make_tier(
                t["name"], int(round(t["cpu_share"] * total_cpu / per_gang)))
            pgs += g
            pods += p
        low = [t for t in self.config["tiers"] if "cpu_share" not in t]
        left, turn = want - len(pods), 0
        while left > 0:
            t = low[turn % len(low)]
            size = min(left, int(self.rng.integers(
                int(t["size_min"]), int(t["size_max"]) + 1)))
            g, p = self.make_tier(t["name"], 1, sizes=[size], queues=[
                self.config["queues"][turn % len(self.config["queues"])][
                    "name"]])
            pgs += g
            pods += p
            left -= size
            turn += 1
        return pgs, pods

    def add(self, pgs: list, pods: list) -> None:
        with self.lock:
            super().add(pgs, pods)
            for pod in pods:
                key = self.key(pod)
                self.tier[key] = pod.get("priority_class", "")
                self.order[key] = int(pod["creation_index"])
                self.pod_dicts[key] = pod

    def add_unfit(self, pods: list) -> None:
        with self.lock:
            super().add_unfit(pods)
            for pod in pods:
                self.tier[self.key(pod)] = pod.get("priority_class", "")

    # -- the eviction feed and the stand-in --------------------------------

    def note_evictions(self, entries: list) -> list:
        """Take the feed's new ``entries``; returns the pod dicts the
        stand-in has to DELETE for them (each live victim once)."""
        out = []
        with self.lock:
            for e in entries:
                self.feed.append(e)
                key = e["pod"]
                claim = (e["claimant"], e["action"], e["node"])
                if e["claimant"]:   # phase 2 of preempt names none
                    waiting = self.in_flight.setdefault(e["claimant"], set())
                    if claim != self._claim and waiting:
                        self.repeat_in_flight += 1
                    waiting.add(key)
                self._claim = claim
                if self.tier.get(key) in self.protected:
                    self.protected_evicted += 1
                if key not in self.pods or key not in self.running:
                    # never sent, deleted before (a second order for a pod
                    # that is gone), or never reported Running
                    self.bad_evictions += 1
                    continue
                cpu, mem, gang = self.pods.pop(key)
                self.loose.pop(key, None)
                self.deleted[key] = (cpu, mem,
                                     self.node_index.get(e["node"], -1))
                if gang is not None:
                    members = self.gangs[gang][0]
                    members[:] = [p for p in members if self.key(p) != key]
                out.append(self.pod_dicts[key])
        return out

    def note_released(self, pods: list) -> None:
        """The stand-in's DELETE of ``pods`` was acknowledged: they are no
        longer evictions in flight."""
        gone = {self.key(p) for p in pods}
        with self.lock:
            for claimant in [c for c, w in self.in_flight.items()
                             if w & gone]:
                self.in_flight[claimant] -= gone

    def uncovered_claims(self) -> int:
        """Claims of the feed whose victims ask less than their claimant in
        CPU or memory (``QUANTUM`` tolerated in CPU, as the program does):
        victims the ledger never knew add nothing."""
        asked = {k: v[:2] for k, v in self.pods.items()}
        asked.update(self.unfit)
        got: dict = {}      # claim -> [cpu, mem], in the feed's order
        last = None
        for n, e in enumerate(self.feed):
            claim = (e["claimant"], e["action"], e["node"])
            if claim != last:
                at, last = n, claim
            total = got.setdefault((at, claim), [0, 0])
            cpu, mem, _ = self.deleted.get(e["pod"], (0, 0, -1))
            total[0] += cpu
            total[1] += mem
        short = 0
        for (_, (claimant, _, _)), (cpu, mem) in got.items():
            need = asked.get(claimant)  # none for a claimant since deleted
            short += need is not None and (
                cpu + QUANTUM < need[0] or mem < need[1])
        return short

    # -- what came back ------------------------------------------------------

    def check_binds(self, binds: list):
        with self.lock:
            numbers, used = super().check_binds(binds)
            numbers["gangs_split"] += self.protected_evicted
            numbers["unknown_pods"] += self.bad_evictions
            numbers["overfit_binds"] += sum(
                1 for e in self.feed if e["claimant"] in self.unfit)
            numbers["double_binds"] += self.repeat_in_flight
            numbers["nodes_over"] += self.uncovered_claims()
        return numbers, used

    def victims_on(self, binds: list) -> dict:
        """node index -> [(order, key, cpu, mem, queue)] of the live pods
        that may be evicted (Running, of an unprotected tier), newest
        first: the order victims are taken in."""
        out: dict = {}
        with self.lock:
            for b in binds:
                key = b["pod"]
                if (key in self.pods and key in self.running
                        and self.tier[key] not in self.protected):
                    cpu, mem, _ = self.pods[key]
                    out.setdefault(self.node_index[b["node"]], []).append(
                        (-self.order[key], key, cpu, mem, self.queue[key]))
        for rows in out.values():
            rows.sort()
        return out


# --------------------------------------------------------------------------
# the edge round: pods whose only way in is an eviction that covers them
# exactly, and pods that ask 12 m more than any eviction could free
# --------------------------------------------------------------------------


def evictable_cpu(n_nodes: int, victims: dict, queue: str,
                  precision: str = "exact") -> np.ndarray:
    """[N] the CPU that reclaim may take on each node for a claimant of
    ``queue``: its cross-queue victims' requests, summed in ``precision``
    in the order they are taken (all nodes at once, one victim deep at a
    time; a same-queue victim adds nothing where it stands)."""
    depth = max(map(len, victims.values()), default=0)
    cpu = np.zeros((depth, n_nodes), np.int64)
    for node, rows in victims.items():
        for i, (_, _, c, _, q) in enumerate(rows):
            if q != queue:
                cpu[i, node] = c
    total = np.zeros(n_nodes, np.int64)
    for layer in cpu:
        total = plane(total + layer, precision)
    return total


def most_offered(idle_cpu, victims: dict, queues: list,
                 precision: str = "exact") -> int:
    """The most CPU any node would have free with every victim that reclaim
    may take for one queue gone (idle + evictable), over ``queues``."""
    return max(int((idle_cpu + evictable_cpu(
        len(idle_cpu), victims, q, precision)).max()) for q in queues)


def edge_pair(idle_cpu, victims: dict, queue: str, queues: list):
    """(exact CPU, over CPU, node) for one round.  The exact pod asks all
    the CPU reclaim may take for ``queue`` on the node that offers most: it
    has to bind there, after every cross-queue victim of that node is
    evicted (the victims alone have to cover a claimant; one milli-core
    too few in the victim plane leaves it pending).  The over pod asks
    ``OVER_MILLI`` more than any node would have free with the victims of
    any one claimant gone (idle + evictable, the most over ``queues``): no
    eviction covers it, and no node holds it even while a later round's
    claimant of another queue (whose victims include this queue's pods)
    waits for the room its victims left, so it may never bind and may cost
    nobody a pod."""
    cap = evictable_cpu(len(idle_cpu), victims, queue)
    node = int(np.argmax(cap))
    return (int(cap[node]),
            most_offered(idle_cpu, victims, queues) + OVER_MILLI, node)


def edge_control(idle_cpu, victims: dict, rounds: list, queues: list,
                 precision: str) -> dict:
    """The reference in the program's place over a victim plane summed in
    ``precision``: of each round's two pods (``rounds``: (queue, exact CPU,
    over CPU)) the over pod binds where idle plus the plane holds it, the
    exact pod where the plane says the victims cover it, and the victims
    of a placement leave the plane.  Counts the exact pods left pending
    and the over pods bound; both 0 over the exact plane."""
    victims = {n: list(rows) for n, rows in victims.items()}
    out = {"unbound": 0, "overfit_binds": 0}
    for queue, exact, over in rounds:
        out["overfit_binds"] += (
            most_offered(idle_cpu, victims, queues, precision) + QUANTUM
            >= over)
        cap = evictable_cpu(len(idle_cpu), victims, queue, precision)
        covers = cap + QUANTUM >= exact
        if covers.any():
            node = int(np.argmax(np.where(covers, cap, -1)))
            victims[node] = [r for r in victims.get(node, ())
                             if r[4] == queue]
        else:
            out["unbound"] += 1
    return out


# --------------------------------------------------------------------------
# the reference as a scheduler, for the control
# --------------------------------------------------------------------------


def place(alloc, used, victims: dict, claimants: list,
          mode: str = "exact"):
    """A sequential reclaim in the reference's order: each claimant
    ``(cpu, mem, queue)`` in turn scans the nodes in order; on the first
    whose cross-queue victims cover it in CPU, memory and a pod slot it
    evicts them newest first until covered, and is placed there.  Returns
    (used after, evicted keys).  ``exact`` charges a cycle's own evictions
    (a victim is taken once); ``stale`` does not (a broken guarantee: the
    victims of an earlier claimant are offered again, and the node they
    left is handed out twice)."""
    used = used.copy()
    victims = {n: list(rows) for n, rows in victims.items()}
    gone: set = set()
    for cpu, mem, queue in claimants:
        need = np.array([cpu, mem, 1], np.int64)
        for node in sorted(victims):
            rows = [r for r in victims[node] if r[4] != queue]
            offer = np.array([sum(r[2] for r in rows),
                              sum(r[3] for r in rows), len(rows)], np.int64)
            if (offer < need).any():
                continue
            got = np.zeros(3, np.int64)
            for row in rows:
                if (got >= need).all():
                    break
                got += (row[2], row[3], 1)
                if row[1] not in gone:
                    gone.add(row[1])
                    used[node] -= (row[2], row[3], 1)
                if mode == "exact":
                    victims[node].remove(row)
            used[node] += need
            break
    return used, gone
