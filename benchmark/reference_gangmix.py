"""The plain reference of a deployment whose gangs differ: a ledger with
``reference.Ledger``'s contract that also knows a GPU column, each gang's
own size and each gang's own queue (``configs/philly-36k-5k.json``).

Nothing here imports the program; numpy int64 on milli-cores, bytes, pod
slots and milli-GPUs, so no sum rounds.  What ``run.py`` reads of a ledger
stays as it is: ``alloc`` is ``[N, 3]`` (cpu, memory, pod slots) and
``pods[key]`` is ``(cpu, mem, gang)``; the GPUs stand beside them
(``gpu_alloc`` ``[N]``, ``gpus[key]``).  ``check_binds`` returns
``reference.LIMITS``' seven names and counts a node over its GPUs in
``nodes_over``.  ``make_gangs`` keeps its signature for the edge round's
one-pod gangs, which ask no GPU.

The configuration's ``gang`` group gives ``sizes`` and their ``shares``
(``min_member`` is the gang's size: a job runs whole or not at all), its
``queue_skew`` the Zipf exponent over a seeded permutation of its queues.
How many gangs of each size, and of each queue, ``n`` gangs hold is the
largest-remainder rounding of ``n`` times the shares (``counts``): a seed
changes which gang has which size and queue, never how many there are.
"""

from __future__ import annotations

import numpy as np

import reference
from reference import plane

GPU = "nvidia.com/gpu"


def counts(n: int, shares) -> np.ndarray:
    """``n`` split over ``shares`` by largest remainder: whole numbers that
    add up to ``n``, each within one of ``n`` times its share."""
    shares = np.asarray(shares, np.float64)
    exact = n * shares / shares.sum()
    out = np.floor(exact + 1e-9).astype(np.int64)
    order = np.argsort(-(exact - out), kind="stable")
    out[order[: n - int(out.sum())]] += 1
    return out


def zipf_shares(n: int, s: float) -> np.ndarray:
    return 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s


class Ledger(reference.Ledger):
    """What was sent: nodes with GPUs, and the live pods with their
    requests, their gangs' sizes and queues."""

    def __init__(self, config: dict, seed: int):
        super().__init__(config, seed)
        n = len(self.node_names)
        self.gpu_alloc = np.full(n, int(config["node"]["gpu_milli"]), np.int64)
        self.gpus: dict = {}      # "ns/name" -> milli-GPUs
        self.gpu_used = np.zeros(n, np.int64)   # of the last check_binds
        names = [q["name"] for q in config["queues"]]
        #: the queues from the hottest down: one permutation a run, so the
        #: population and the window heat the same queue
        self.queue_order = [names[i] for i in self.rng.permutation(len(names))]
        self._deleted_more = 0    # pods deleted beyond what was posted

    # -- what the harness sends --------------------------------------------

    def node_dicts(self) -> list:
        out = super().node_dicts()
        for i, node in enumerate(out):
            res = dict(node["allocatable"])
            res[GPU] = float(self.gpu_alloc[i])
            node["allocatable"] = node["capacity"] = res
        return out

    def _pod(self, cpu: int, mem: int, gang, gpu: int = 0) -> dict:
        pod = super()._pod(cpu, mem, gang)
        if gpu:
            pod["requests"][GPU] = float(gpu)
        return pod

    def gang_sizes(self, n_gangs: int) -> np.ndarray:
        """The sizes ``n_gangs`` gangs have, largest last: the multiset,
        in no seeded order yet."""
        gang = self.config["gang"]
        return np.repeat(np.asarray(gang["sizes"], np.int64),
                         counts(n_gangs, gang["shares"]))

    def gang_queues(self, n_gangs: int) -> list:
        """The queues ``n_gangs`` gangs have: the Zipf quantiles over
        ``queue_order``, hottest first."""
        per = counts(n_gangs, zipf_shares(len(self.queue_order),
                                          float(self.config["queue_skew"])))
        return [q for q, k in zip(self.queue_order, per) for _ in range(k)]

    def make_mix(self, sizes, queues):
        """(podgroup dicts, pod dicts) for one fresh gang per entry of
        ``sizes``, in ``queues[i]``: every member asks one GPU, and CPU and
        memory drawn uniformly from the request mix.  Not live until
        ``add``."""
        mix = self.config["request_mix"]
        total = int(np.sum(sizes))
        cpus = self.rng.choice(np.asarray(mix["cpu_milli"], np.int64), total)
        mems = self.rng.choice(np.asarray(mix["memory_bytes"], np.int64), total)
        gpus = self.rng.choice(np.asarray(mix["gpu_milli"], np.int64), total)
        pgs, pods, at = [], [], 0
        for size, queue in zip(sizes, queues):
            j = self._next_gang
            self._next_gang += 1
            gang = f"pg{j}"
            pgs.append({"name": gang, "namespace": self.namespace,
                        "uid": f"pg-{self.namespace}-{gang}",
                        "min_member": int(size), "queue": queue,
                        "running": 0, "succeeded": 0, "failed": 0,
                        "creation_index": j, "shadow": False})
            pods.extend(self._pod(int(cpus[m]), int(mems[m]), gang,
                                  int(gpus[m]))
                        for m in range(at, at + int(size)))
            at += int(size)
        return pgs, pods

    def make_population(self):
        """The configuration's ``population``: as many gangs as its pods
        make at the mean gang size, their sizes and queues the quantile
        multisets in seeded order, then one-pod gangs added or dropped
        until the pods are exactly ``population.pods``."""
        want = int(self.config["population"]["pods"])
        gang = self.config["gang"]
        shares = np.asarray(gang["shares"], np.float64)
        mean = float((np.asarray(gang["sizes"]) * shares).sum() / shares.sum())
        sizes = self.gang_sizes(int(round(want / mean)))
        spare = want - int(sizes.sum())
        if spare >= 0:
            sizes = np.concatenate((sizes, np.ones(spare, np.int64)))
        else:
            ones = np.flatnonzero(sizes == 1)[:-spare]
            if len(ones) < -spare:
                raise ValueError("the population cannot be trimmed to size")
            sizes = np.delete(sizes, ones)
        queues = self.gang_queues(len(sizes))
        return self.make_mix(self.rng.permutation(sizes),
                             [queues[i] for i in self.rng.permutation(len(queues))])

    def add(self, pgs: list, pods: list) -> None:
        super().add(pgs, pods)
        for pod in pods:
            self.gpus[self.key(pod)] = int(pod["requests"].get(GPU, 0))

    def retire(self, pgs: list, pods: list) -> None:
        super().retire(pgs, pods)
        for pod in pods:
            del self.gpus[self.key(pod)]

    def oldest_covering(self, n_pods: int):
        """(podgroup dicts, pod dicts) of the oldest live gangs that free
        at least what ``n_pods`` new pods ask for, for a DELETE body.  What
        earlier calls freed beyond their need is counted first, so the
        cluster's occupancy does not drift down burst after burst."""
        need = n_pods - self._deleted_more
        names = []
        for name, (members, _, _) in self.gangs.items():
            if need <= 0:
                break
            names.append(name)
            need -= len(members)
        self._deleted_more = -need
        pgs = [self.gangs[g][1] for g in names]
        pods = [p for g in names for p in self.gangs[g][0]]
        return pgs, pods

    # -- what came back ------------------------------------------------------

    def check_binds(self, binds: list):
        """``reference.Ledger.check_binds`` with the GPU column beside it: a
        node over its GPUs is a node over.  Returns (numbers, used[N, 3])."""
        numbers, used = super().check_binds(binds)
        gpu_used = np.zeros(len(self.node_names), np.int64)
        seen: set = set()
        for b in binds:                 # a pod's first bind, as above
            node = self.node_index.get(b["node"])
            if b["pod"] not in seen and node is not None:
                gpu_used[node] += self.gpus.get(b["pod"], 0)
            seen.add(b["pod"])
        self.gpu_used = gpu_used
        numbers["nodes_over"] = int(((used > self.alloc).any(axis=1)
                                     | (gpu_used > self.gpu_alloc)).sum())
        return numbers, used


# --------------------------------------------------------------------------
# the reference as a scheduler, for the control and the tests
# --------------------------------------------------------------------------


def place_first_fit(ledger: Ledger, precision: str = "exact") -> list:
    """``reference.place_first_fit`` over four columns: every gang of the
    ledger first-fit, whole gangs only, reading room from a usage plane
    summed in ``precision``.  With ``exact`` the result passes
    ``check_binds``; ``stale`` (the plane is never written) puts every pod
    on the first node, over its GPUs."""
    alloc = np.column_stack((ledger.alloc, ledger.gpu_alloc))
    used = np.zeros_like(alloc)
    stored = np.zeros_like(alloc)
    binds = []
    first = 0                            # nodes before it are full for any pod
    for members, _, _ in ledger.gangs.values():
        trial_used, trial_stored, picked = used.copy(), stored.copy(), []
        for pod in members:
            key = ledger.key(pod)
            cpu, mem, _ = ledger.pods[key]
            req = np.array([cpu, mem, 1, ledger.gpus[key]], np.int64)
            fits = ((alloc[first:] - trial_stored[first:]) >= req).all(axis=1)
            if not fits.any():
                picked = None
                break
            node = first + int(np.argmax(fits))
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
