"""The plain reference: what a correct answer is, from what was sent and what
came back.

Nothing here imports the program.  Everything is numpy int64 on
milli-cores, bytes and pod slots, so no sum rounds.  A placement has many
right answers, so the reference is a checker, not a second scheduler: it
knows every pod, gang and node the harness sent (the ``Ledger``), takes the
binds the server reports, and counts what may never happen.  Every count
is compared with a limit of 0 (``LIMITS``).

What-if answers on a quiescent cluster are unique, so they are compared
exactly: a gang of ``count`` identical members fits iff
sum over nodes of floor(free / request) >= count.

``place_first_fit`` and ``plane`` exist for the control (control.py, the
tests): the same reference put in the program's place with its capacity
plane kept in a lower precision, which has to come out NOT correct.
"""

from __future__ import annotations

import numpy as np

GROUP_NAME_ANNOTATION = "scheduling.k8s.io/group-name"
#: a task counts as decided once the bind is out (api/types.TaskStatus names)
BOUND_STATUSES = ("BINDING", "BOUND", "RUNNING")

#: every number the comparison reads, with its limit.  All are counts of
#: things that may not happen at all, so every limit is 0 (PERF.md §2).
LIMITS = {
    "unknown_pods": 0,        # binds that name a pod never sent, or deleted
    "unknown_nodes": 0,       # binds that name a node never sent
    "double_binds": 0,        # pods bound twice
    "nodes_over": 0,          # nodes over allocatable in any resource
    "gangs_split": 0,         # gangs bound below minMember
    "unbound": 0,             # pods posted and not bound after the drain
    "overfit_binds": 0,       # pods bound that no node has room for (edge round)
    "counter_mismatch": 0,    # |decisions counter - binds the harness found|
    "guard_dirty": 0,         # trips + failed-closed + mismatched audits + unhealthy paths
    "log_failures": 0,        # failure markers in the server's log
    "whatif_wrong": 0,        # what-if answers that differ from the ledger
    "whatif_in_window_bad": 0,  # verdicts without a version, feasible giants, ...
    "http_errors": 0,         # requests the server refused or dropped
}


class Ledger:
    """What was sent: the nodes, and the live pods with their requests and
    gangs.  Requests are integers: milli-cores, bytes, one pod slot."""

    def __init__(self, config: dict, seed: int):
        self.config = config
        self.namespace = config.get("namespace", "bench")
        self.rng = np.random.default_rng(seed)
        node = config["node"]
        n = int(config["nodes"])
        self.node_names = [f"n{i}" for i in range(n)]
        self.node_index = {name: i for i, name in enumerate(self.node_names)}
        self.alloc = np.tile(np.array(
            [int(node["cpu_milli"]), int(node["memory_bytes"]),
             int(node["pods"])], np.int64), (n, 1))
        self.pods: dict = {}      # "ns/name" -> (cpu, mem, gang or None)
        self.gangs: dict = {}     # gang -> ([pod dict], podgroup dict, minMember)
        self.loose: dict = {}     # "ns/name" -> pod dict, of the pods with no gang
        self.unfit: dict = {}     # pods sent that no node may take: key -> (cpu, mem)
        self._next_gang = 0
        self._next_pod = 0

    # -- what the harness sends --------------------------------------------

    def queue_dicts(self) -> list:
        return [{"name": q["name"], "uid": f"queue-{q['name']}",
                 "weight": int(q["weight"])} for q in self.config["queues"]]

    def node_dicts(self) -> list:
        out = []
        for i, name in enumerate(self.node_names):
            res = {"cpu": float(self.alloc[i, 0]),
                   "memory": float(self.alloc[i, 1]),
                   "pods": float(self.alloc[i, 2])}
            out.append({"name": name, "allocatable": res, "capacity": res,
                        "ready": True, "unschedulable": False})
        return out

    def _pod(self, cpu: int, mem: int, gang) -> dict:
        i = self._next_pod
        self._next_pod += 1
        name = f"t{i}"
        pod = {"name": name, "namespace": self.namespace,
               "uid": f"pod-{self.namespace}-{name}",
               "requests": {"cpu": float(cpu), "memory": float(mem)},
               "phase": "Pending", "deleting": False, "priority": 0,
               "scheduler_name": "volcano", "creation_index": i}
        if not mem:
            del pod["requests"]["memory"]
        if gang is not None:
            pod["annotations"] = {GROUP_NAME_ANNOTATION: gang}
        return pod

    @staticmethod
    def key(pod: dict) -> str:
        return f"{pod['namespace']}/{pod['name']}"

    def make_gangs(self, n_gangs: int, size: int, min_member: int,
                   cpu_choices, mem_choices):
        """(podgroup dicts, pod dicts) for ``n_gangs`` fresh gangs whose
        members' requests are drawn uniformly from the choices; queues go
        round-robin over the configuration's.  Not live until ``add``."""
        cpus = self.rng.choice(np.asarray(cpu_choices, np.int64),
                               n_gangs * size)
        mems = self.rng.choice(np.asarray(mem_choices, np.int64),
                               n_gangs * size)
        queues = self.config["queues"]
        pgs, pods = [], []
        for g in range(n_gangs):
            j = self._next_gang
            self._next_gang += 1
            gang = f"pg{j}"
            pgs.append({"name": gang, "namespace": self.namespace,
                        "uid": f"pg-{self.namespace}-{gang}",
                        "min_member": int(min_member),
                        "queue": queues[j % len(queues)]["name"],
                        "running": 0, "succeeded": 0, "failed": 0,
                        "creation_index": j, "shadow": False})
            pods.extend(self._pod(int(cpus[g * size + m]),
                                  int(mems[g * size + m]), gang)
                        for m in range(size))
        return pgs, pods

    def make_pods(self, n: int, cpu: int, mem: int) -> list:
        """``n`` plain pods with no PodGroup of their own (the cache gives
        each a shadow PodGroup with minMember 1).  Not live until ``add``."""
        return [self._pod(cpu, mem, None) for _ in range(n)]

    def make_population(self):
        """(podgroup dicts, pod dicts) of the configuration's
        ``population``: gangs from the request mix, or plain pods."""
        pop, config = self.config["population"], self.config
        if pop["kind"] == "gangs":
            gang, mix = config["gang"], config["request_mix"]
            return self.make_gangs(
                pop["pods"] // gang["size"], gang["size"], gang["min_member"],
                mix["cpu_milli"], mix["memory_bytes"])
        return [], self.make_pods(pop["pods"], pop["cpu_milli"],
                                  pop["memory_bytes"])

    def add(self, pgs: list, pods: list) -> None:
        """These were sent: they are live from now on."""
        for pg in pgs:
            self.gangs[pg["name"]] = ([], pg, pg["min_member"])
        for pod in pods:
            gang = pod.get("annotations", {}).get(GROUP_NAME_ANNOTATION)
            req = pod["requests"]
            self.pods[self.key(pod)] = (
                int(req["cpu"]), int(req.get("memory", 0)), gang)
            if gang is not None:
                self.gangs[gang][0].append(pod)
            else:
                self.loose[self.key(pod)] = pod

    def add_unfit(self, pods: list) -> None:
        """These were sent and ask for more than any node has left: they
        have to stay pending, and a bind of one is counted."""
        for pod in pods:
            req = pod["requests"]
            self.unfit[self.key(pod)] = (int(req["cpu"]),
                                         int(req.get("memory", 0)))

    def retire(self, pgs: list, pods: list) -> None:
        """These were deleted: they are gone from now on."""
        for pod in pods:
            del self.pods[self.key(pod)]
            self.loose.pop(self.key(pod), None)
        for pg in pgs:
            del self.gangs[pg["name"]]

    def under(self, cpu_milli: int):
        """(podgroup dicts, pod dicts) of every live pod that asks for less
        CPU than ``cpu_milli``, with the gangs made of such pods only."""
        pgs = [pg for members, pg, _ in self.gangs.values() if members and all(
            self.pods[self.key(p)][0] < cpu_milli for p in members)]
        names = {pg["name"] for pg in pgs}
        pods = [p for g in names for p in self.gangs[g][0]]
        pods += [p for k, p in self.loose.items()
                 if self.pods[k][0] < cpu_milli]
        return pgs, pods

    def oldest_gangs(self, n_gangs: int, skip: int = 0):
        """(podgroup dicts, pod dicts) of the oldest live gangs after the
        first ``skip``, for a DELETE body."""
        names = list(self.gangs)[skip:skip + n_gangs]
        pgs = [self.gangs[g][1] for g in names]
        pods = [p for g in names for p in self.gangs[g][0]]
        return pgs, pods

    # -- what came back ------------------------------------------------------

    def check_binds(self, binds: list):
        """Count everything that may not happen in ``binds`` (rows of
        ``{"pod", "node"}``).  Returns (numbers, used[N, 3] int64)."""
        numbers = dict.fromkeys(
            ("unknown_pods", "unknown_nodes", "double_binds", "nodes_over",
             "gangs_split", "unbound", "overfit_binds"), 0)
        seen: set = set()
        idx, req = [], []
        per_gang: dict = {}
        for b in binds:
            key = b["pod"]
            if key in seen:
                numbers["double_binds"] += 1
                continue
            seen.add(key)
            pod = self.pods.get(key)
            if pod is None and key in self.unfit:
                numbers["overfit_binds"] += 1
                pod = self.unfit[key] + (None,)
            if pod is None:
                numbers["unknown_pods"] += 1
                continue
            node = self.node_index.get(b["node"])
            if node is None:
                numbers["unknown_nodes"] += 1
                continue
            idx.append(node)
            req.append((pod[0], pod[1], 1))
            if pod[2] is not None:
                per_gang[pod[2]] = per_gang.get(pod[2], 0) + 1
        used = np.zeros_like(self.alloc)
        if idx:
            np.add.at(used, np.asarray(idx, np.int64),
                      np.asarray(req, np.int64))
        numbers["nodes_over"] = int((used > self.alloc).any(axis=1).sum())
        numbers["gangs_split"] = sum(
            1 for gang, c in per_gang.items()
            if 0 < c < self.gangs[gang][2])
        numbers["unbound"] = len(self.pods) - len(seen & set(self.pods))
        return numbers, used


# --------------------------------------------------------------------------
# what-ifs on a quiescent cluster
# --------------------------------------------------------------------------


def request_vec(requests: dict) -> np.ndarray:
    """[3] int64 (cpu milli, memory bytes, 1 pod slot) of a what-if body's
    ``requests``."""
    return np.array([int(requests.get("cpu", 0)),
                     int(requests.get("memory", 0)), 1], np.int64)


def plane(used: np.ndarray, precision: str) -> np.ndarray:
    """The per-node usage plane as a program that keeps it in ``precision``
    would read it back.  ``exact`` is the reference; ``bfloat16`` (8
    significant bits, round to nearest even) is the control's; ``stale``
    is the control where nothing can round: the plane is never written."""
    if precision == "exact":
        return used
    if precision == "stale":
        return np.zeros_like(used)
    if precision != "bfloat16":
        raise ValueError(f"no such precision: {precision}")
    bits = used.astype(np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32).astype(np.int64)


def slots(alloc: np.ndarray, used: np.ndarray, req: np.ndarray) -> np.ndarray:
    """[N] how many members asking ``req`` each node still holds."""
    free = np.clip(alloc - used, 0, None)
    need = req > 0
    return (free[:, need] // req[need]).min(axis=1)


def check_probe(resp: dict, body: dict, alloc, used, node_index) -> list:
    """Faults of one /v1/whatif verdict against the ledger ([] = right)."""
    req, count = request_vec(body["requests"]), int(body["count"])
    room = slots(alloc, used, req)
    faults = []
    if "snapshot_version" not in resp:
        faults.append("no snapshot_version")
    want = bool(room.sum() >= count)
    if bool(resp.get("feasible")) != want:
        faults.append(f"feasible={resp.get('feasible')} but the ledger has "
                      f"room for {int(room.sum())} of {count}")
    if resp.get("feasible"):
        placed = [n for n in resp.get("nodes", []) if n]
        if len(placed) != count:
            faults.append(f"{len(placed)} of {count} members placed")
        per_node: dict = {}
        for n in placed:
            per_node[n] = per_node.get(n, 0) + 1
        for n, k in per_node.items():
            i = node_index.get(n)
            if i is None:
                faults.append(f"unknown node {n!r}")
            elif room[i] < k:
                faults.append(f"{k} members on {n}, which holds {int(room[i])}")
    return faults


def check_sweep(resp: dict, body: dict, alloc, used) -> list:
    """Faults of one /v1/whatif/sweep answer against the ledger."""
    req, max_count = request_vec(body["requests"]), int(body["max_count"])
    want = int(min(slots(alloc, used, req).sum(), max_count))
    faults = []
    if "snapshot_version" not in resp:
        faults.append("no snapshot_version")
    if resp.get("max_fit") != want:
        faults.append(f"max_fit={resp.get('max_fit')} but the ledger holds "
                      f"{want} (of {max_count})")
    return faults


def answer(body: dict, alloc, used, node_names, sweep: bool) -> dict:
    """The reference's own answer to a what-if body — what the control
    puts in the program's place (over a ``plane`` of lower precision)."""
    req = request_vec(body["requests"])
    room = slots(alloc, used, req)
    if sweep:
        fit = int(min(room.sum(), int(body["max_count"])))
        return {"snapshot_version": 0, "max_fit": fit, "feasible": fit >= 1}
    count = int(body["count"])
    feasible = bool(room.sum() >= count)
    nodes = []
    if feasible:
        for i in np.flatnonzero(room):
            nodes.extend([node_names[i]] * int(min(room[i], count - len(nodes))))
            if len(nodes) >= count:
                break
    return {"snapshot_version": 0, "feasible": feasible, "nodes": nodes}


# --------------------------------------------------------------------------
# the edge round: binds that sit on a fit edge, so that the ledger's counts
# see a capacity plane kept in a lower precision than the program states
# --------------------------------------------------------------------------


#: the program, like the scheduler it follows, lets a request exceed a
#: node's room by up to this much CPU and still fit (resource_info.go:66-72,
#: ops/feasibility.py); an over pod asks for just more than that
FIT_QUANTUM_MILLI = 10
OVER_MILLI = 12


def edge_requests(alloc, used, mem: int, rounds: int) -> list:
    """[(exact request [3], over request [3])] for ``rounds`` rounds, sent
    one round after another on a quiescent cluster.  Round j's exact pod
    asks for all the CPU the j-th roomiest node has left (among the nodes
    with ``mem`` bytes and a pod slot free): when it is sent the j - 1
    roomier nodes are full, each by its own round's pod, so it fits that
    node (or one tied with it) and no other, and a plane that reads one
    milli-core too much in use there leaves it pending.  The over pod asks
    OVER_MILLI more than the same node has, which no node has and none
    ever will, so it has to stay pending, and a plane that reads too little
    in use binds it."""
    free = alloc - used
    able = (free[:, 1] >= mem) & (free[:, 2] >= 1) & (free[:, 0] > 0)
    cpus = np.sort(free[able, 0])[::-1][:rounds]
    return [(np.array([c, mem, 1], np.int64),
             np.array([c + OVER_MILLI, mem, 1], np.int64)) for c in cpus]


def summed_plane(alloc, rows: list, precision: str) -> np.ndarray:
    """The usage plane a program that keeps it in ``precision`` would hold
    after these binds (``rows`` of (node index, request [3])): every bind
    adds its request to what the plane last read back."""
    stored = np.zeros_like(alloc)
    for node, req in rows:
        stored[node] = plane(stored[node] + req, precision)
    return stored


def edge_control(alloc, stored, requests: list, precision: str) -> dict:
    """The reference in the program's place over a plane of lower
    precision: each round's two pods first-fit against ``stored``.  Counts
    the exact pods it leaves pending and the over pods it binds; both are
    0 over the exact plane."""
    stored = stored.copy()
    out = {"unbound": 0, "overfit_binds": 0}
    for exact, over in requests:
        for req, unfit in ((over, True), (exact, False)):
            fits = ((alloc - stored) >= req).all(axis=1)
            if fits.any():
                node = int(np.argmax(fits))
                stored[node] = plane(stored[node] + req, precision)
                out["overfit_binds"] += unfit
            else:
                out["unbound"] += not unfit
    return out


# --------------------------------------------------------------------------
# the reference as a scheduler, for the control
# --------------------------------------------------------------------------


def place_first_fit(ledger: Ledger, precision: str = "exact") -> list:
    """Bind every gang of the ledger first-fit, whole gangs only, reading
    room from a usage plane summed in ``precision`` (each bind adds its
    request to what the plane last read back).  With ``exact`` the result
    passes ``check_binds``; the control runs it with a fault."""
    alloc = ledger.alloc
    used = np.zeros_like(alloc)          # the truth the plane is written from
    stored = np.zeros_like(alloc)        # what the plane reads back
    binds = []
    first = 0                            # nodes before it are full for any pod
    gangs = [[ledger.key(p) for p in members]
             for members, _, _ in ledger.gangs.values()]
    loose = [[k] for k, p in ledger.pods.items() if p[2] is None]
    for members in gangs + loose:
        trial_used, trial_stored, picked = used, stored, []
        for key in members:
            cpu, mem, _ = ledger.pods[key]
            req = np.array([cpu, mem, 1], np.int64)
            fits = ((alloc[first:] - trial_stored[first:]) >= req).all(axis=1)
            if not fits.any():
                picked = None
                break
            node = first + int(np.argmax(fits))
            if trial_used is used:
                trial_used, trial_stored = used.copy(), stored.copy()
            trial_used[node] += req
            trial_stored[node] = plane(trial_stored[node] + req, precision)
            picked.append((key, node))
        if picked is None:
            continue
        used, stored = trial_used, trial_stored
        binds.extend({"pod": k, "node": ledger.node_names[n]}
                     for k, n in picked)
        while first < len(alloc) and (alloc[first] - stored[first]).min() <= 0:
            first += 1
    return binds
