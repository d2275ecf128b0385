"""The plain reference of ``schedperf-mixed-10k-5k``: pods that carry labels
and inter-pod (anti-)affinity terms, on nodes that carry a zone.

Nothing here imports the program; numpy int64 and dicts, as ``reference.py``.
``World`` holds what is true of the cluster whoever placed the pods: each
node's allocatable and labels, each pod's requests, labels and terms.

``World.check_binds`` walks binds AND DELETES in the order they happened and
counts, at each bind and against the pods bound before it and not deleted
since: a node taken over its allocatable; a required anti-affinity term with
a selected pod in its topology domain; a required affinity term unmet in the
domain while some pod anywhere meets it (no pod anywhere: the group's first
pod may land anywhere, as kube-batch's predicate has it); a pod bound twice;
a bind of a pod or on a node never sent.  ``World.place`` is the reference's
sequential per-task loop (allocate.go:151-184: predicate every node, score
every node that passes, take the best) for the comparison at small size and
for the two controls.

Where the order comes from (``Ledger.events``).  The order of the binds among
themselves is the program's own word: ``GET /v1/bindings?seq=1`` reports the
how-manieth bind of the process each was (``SchedulerCache.bind_seq``), and
nothing outside the program can see it.  ``/v1/bindings`` lists live pods
only, so the stream reads it before the warm-up and before the window as
well: every pod a burst deletes is the oldest of its template and was bound
by then, and the ledger keeps its node and number after the delete.  Where a
delete falls among the binds is the CLIENT's: the deletes of burst j were
acknowledged before burst j's pods were posted, so they precede every bind
of a pod of burst j or later; they follow the binds of burst k < j if the
decisions counter covered burst k before burst j's DELETE was sent.  A bind
for which neither holds (the program was a burst behind) is walked twice,
with the delete before it for the counts a missing peer lowers
(``nodes_over``, ``anti_affinity_violations``) and after it for the one a
missing peer raises (``affinity_violations``): ``ambiguous_binds`` says how
many there were.

Departures from upstream Kubernetes, the program's own (README, "Supported
constraints"): a term's namespaces are not modelled (the source's templates
name every namespace in use, so nothing changes here), and an EXISTING pod's
anti-affinity against the newcomer is not checked: only the incoming pod's
terms are (every green pod carries the same term, so it is symmetric here).

``Ledger`` is ``reference.Ledger``'s contract over the configuration's five
templates, under the thirteen names of ``reference.LIMITS``: a bind against a
required term is an ``overfit_binds`` (the pod was bound where it does not
fit); so is, once, a window whose preferred-term shares left their tolerance
(``preferred_shares``).
"""

from __future__ import annotations

import numpy as np

import reference

HOSTNAME = "kubernetes.io/hostname"
#: how far the program's preferred-term shares may lie from the sequential
#: reference's: ``beside``, the share of a window's red pods bound in a
#: domain that holds another red pod, and ``apart``, the share of its yellow
#: pods bound on a node without another yellow pod, both at the end state
#: (ISSUE 41's control).  The reference places one pod at a time and so sees
#: every pod before; the program's solve walks the termed pods of a cycle in
#: rank order against the placements of the same solve
#: (ops/assignment.make_term_round), so what is left to differ is: ties
#: between equal nodes, which the program breaks by hash and the reference by
#: index; the 0..10 min-max scale of the preference row meeting a node's
#: least-requested edge (the program scores a cycle's pods on the usage the
#: cycle found, the reference on the usage after each pod); a neighbour
#: deleted since the bind.  Dropping the preferred rows moves the red share
#: from ~1 to ~0.2 (the emptiest node seldom holds a red pod) and the yellow
#: one from ~1 to ~0.8, so 0.1 lies between the two.
SHARE_TOLERANCE = 0.1

COUNTS = ("nodes_over", "anti_affinity_violations", "affinity_violations",
          "double_binds", "unknown_pods", "unknown_nodes", "unbound")


def _sig(match_labels: dict) -> tuple:
    return tuple(sorted(match_labels.items()))


def _selects(sig: tuple, labels: dict) -> bool:
    return all(labels.get(k) == v for k, v in sig)


class World:
    def __init__(self, node_names, node_labels, alloc):
        self.node_names = list(node_names)
        self.node_index = {n: i for i, n in enumerate(self.node_names)}
        self.node_labels = list(node_labels)      # [N] dict
        self.alloc = np.asarray(alloc, np.int64)  # [N, 3] cpu, mem, pods
        self.requests: dict = {}   # key -> (cpu, mem)
        self.labels: dict = {}     # key -> {label: value}
        # key -> ([(sig, topology key)], [(sig, key)], [(signed w, sig, key)])
        self.terms: dict = {}
        self._domains: dict = {}   # topology key -> [N] int domain id

    @classmethod
    def from_pods(cls, nodes: dict, pods) -> "World":
        """From the program's own objects (the CPU tests): ``nodes`` name ->
        Node, ``pods`` Pod objects.  Reads attributes; imports nothing."""
        names = sorted(nodes)
        world = cls(names, [dict(nodes[n].labels) for n in names], [
            [int(nodes[n].allocatable["cpu"]),
             int(nodes[n].allocatable["memory"]),
             int(nodes[n].allocatable.get("pods", 110))] for n in names])
        for pod in pods:
            aff = pod.affinity
            world.add_pod(
                f"{pod.namespace}/{pod.name}", int(pod.requests["cpu"]),
                int(pod.requests.get("memory", 0)), pod.labels,
                [(t.match_labels, t.topology_key)
                 for t in (aff.pod_affinity if aff else ())],
                [(t.match_labels, t.topology_key)
                 for t in (aff.pod_anti_affinity if aff else ())],
                [(w, t.match_labels, t.topology_key)
                 for w, t in (aff.preferred_pod_affinity if aff else ())],
                [(w, t.match_labels, t.topology_key)
                 for w, t in (aff.preferred_pod_anti_affinity if aff else ())])
        return world

    def add_pod(self, key, cpu, mem, labels, aff=(), anti=(), pref=(),
                pref_anti=()) -> None:
        self.requests[key] = (int(cpu), int(mem))
        self.labels[key] = dict(labels or {})
        self.terms[key] = (
            [(_sig(m), k) for m, k in aff], [(_sig(m), k) for m, k in anti],
            [(float(w), _sig(m), k) for w, m, k in pref]
            + [(-float(w), _sig(m), k) for w, m, k in pref_anti])

    def domain(self, key: str) -> np.ndarray:
        """[N] domain id per node under topology key ``key``: hostname is
        the node itself; a node without the label is a domain of its own."""
        got = self._domains.get(key)
        if got is None:
            n = len(self.node_names)
            got = np.arange(n, dtype=np.int64)
            if key != HOSTNAME:
                ids: dict = {}
                for i, labels in enumerate(self.node_labels):
                    if key in labels:
                        got[i] = n + ids.setdefault(labels[key], len(ids))
            self._domains[key] = got
        return got

    def _pairs(self, keys) -> set:
        return {(sig, k) for key in keys if key in self.terms
                for group in self.terms[key][:2] for sig, k in group} | {
            (sig, k) for key in keys if key in self.terms
            for _, sig, k in self.terms[key][2]}

    # ------------------------------------------------------------------
    def check_binds(self, order) -> dict:
        """``order``: [(pod key, node name)] as bound; a node of None says
        the pod was deleted there.  See the module docstring for what is
        counted."""
        counts = dict.fromkeys(COUNTS, 0)
        n = len(self.node_names)
        used = np.zeros_like(self.alloc)
        pairs = sorted(self._pairs([k for k, _ in order]))
        here = {p: np.zeros(2 * n, np.int64) for p in pairs}  # by domain id
        total: dict = {}
        seen: set = set()
        at: dict = {}        # pod -> node index, while it counts
        gone: set = set()    # deleted before its bind came up in the walk

        def count(key, i, sign):
            cpu, mem = self.requests[key]
            used[i] += (sign * cpu, sign * mem, sign)
            counted = set()
            for sig, k in pairs:
                if _selects(sig, self.labels[key]):
                    here[(sig, k)][self.domain(k)[i]] += sign
                    if sig not in counted:
                        counted.add(sig)
                        total[sig] = total.get(sig, 0) + sign

        for key, node_name in order:
            if node_name is None:
                if key in at:
                    count(key, at.pop(key), -1)
                else:
                    gone.add(key)
                continue
            if key in seen:
                counts["double_binds"] += 1
                continue
            seen.add(key)
            if key not in self.requests:
                counts["unknown_pods"] += 1
                continue
            i = self.node_index.get(node_name)
            if i is None:
                counts["unknown_nodes"] += 1
                continue
            if key in gone:
                continue
            cpu, mem = self.requests[key]
            if (used[i] + (cpu, mem, 1) > self.alloc[i]).any():
                counts["nodes_over"] += 1
            aff, anti, _ = self.terms[key]
            for sig, k in anti:
                if here[(sig, k)][self.domain(k)[i]]:
                    counts["anti_affinity_violations"] += 1
            for sig, k in aff:
                if not here[(sig, k)][self.domain(k)[i]] and total.get(sig):
                    counts["affinity_violations"] += 1
            at[key] = i
            count(key, i, 1)
        counts["unbound"] = len(set(self.requests) - seen)
        self.used = used
        return counts

    # ------------------------------------------------------------------
    def place(self, keys, bound=(), mode: str = "exact") -> list:
        """The sequential loop: ``keys`` in order, on top of ``bound``
        [(key, node)].  ``mode``: ``exact``; ``ignore_terms`` (the first
        control: a scheduler that packs first-fit and does not know the
        terms; one that spreads would keep a hostname anti-affinity by
        accident while the group is smaller than the cluster);
        ``no_preference`` (preferred weights zeroed: the second).  Returns
        [(key, node name)]; a pod no node takes is left out."""
        n = len(self.node_names)
        alloc = self.alloc.astype(np.float64)
        used = np.zeros((n, 3), np.float64)
        pairs = sorted(self._pairs(list(keys) + [k for k, _ in bound]))
        count = {p: np.zeros(n, np.int64) for p in pairs}   # per node
        total = {sig: 0 for sig, _ in pairs}

        def put(key, i):
            cpu, mem = self.requests[key]
            used[i] += (cpu, mem, 1)
            counted = set()
            for sig, k in pairs:
                if _selects(sig, self.labels[key]):
                    count[(sig, k)][i] += 1
                    if sig not in counted:
                        counted.add(sig)
                        total[sig] += 1

        def present(sig, k):
            dom = self.domain(k)
            per = np.bincount(dom, weights=count[(sig, k)], minlength=2 * n)
            return per[dom] > 0

        for key, node in bound:
            put(key, self.node_index[node])
        out = []
        for key in keys:
            cpu, mem = self.requests[key]
            req = np.array([cpu, mem, 1], np.float64)
            ok = ((used + req) <= alloc).all(axis=1)
            aff, anti, pref = self.terms[key]
            if mode != "ignore_terms":
                for sig, k in anti:
                    ok &= ~present(sig, k)
                for sig, k in aff:
                    if total[sig]:
                        ok &= present(sig, k)
            if not ok.any():
                continue
            # nodeorder.go's priorities on cpu and memory, 0..10 each
            frac = (used[:, :2] + req[:2]) / alloc[:, :2]
            score = (10.0 * (1.0 - frac).clip(0, 1).mean(axis=1)
                     + 10.0 - np.abs(frac[:, 0] - frac[:, 1]) * 10.0)
            if pref and mode != "no_preference":
                raw = np.zeros(n)
                for w, sig, k in pref:
                    raw += w * present(sig, k)
                span = raw.max() - raw.min()
                if span > 0:
                    score = score + 10.0 * (raw - raw.min()) / span
            if mode == "ignore_terms":
                score = -np.arange(n, dtype=np.float64)   # first fit
            i = int(np.argmax(np.where(ok, score, -np.inf)))
            put(key, i)
            out.append((key, self.node_names[i]))
        return out

    def preferred_shares(self, order, late) -> dict:
        """Over the pods ``late`` among ``order``'s binds, at the end state:
        ``beside``, the share of those with a preferred AFFINITY term that
        sit in a domain holding another selected pod; ``apart``, the share
        of those with a preferred ANTI-affinity term that sit in a domain
        holding no other selected pod."""
        node_of = dict(order)
        n = len(self.node_names)
        pairs = sorted({(sig, k) for key in late if key in self.terms
                        for _, sig, k in self.terms[key][2]})
        here = {p: np.zeros(2 * n, np.int64) for p in pairs}
        for key, node in node_of.items():
            if key in self.labels and node in self.node_index:
                for sig, k in pairs:
                    if _selects(sig, self.labels[key]):
                        here[(sig, k)][
                            self.domain(k)[self.node_index[node]]] += 1
        tally = {"beside": [0, 0], "apart": [0, 0]}
        for key in late:
            if key not in node_of or key not in self.terms:
                continue
            i = self.node_index[node_of[key]]
            for w, sig, k in self.terms[key][2]:
                others = here[(sig, k)][self.domain(k)[i]] - int(
                    _selects(sig, self.labels[key]))
                name = "beside" if w > 0 else "apart"
                tally[name][1] += 1
                tally[name][0] += int((others > 0) == (w > 0))
        return {name: (hit / n_ if n_ else 1.0)
                for name, (hit, n_) in tally.items()}


class Ledger(reference.Ledger):
    """What was sent of ``schedperf-mixed-10k-5k``: ``reference.Ledger``
    with each pod's template, and the nodes' zones."""

    def __init__(self, config: dict, seed: int):
        super().__init__(config, seed)
        self.templates = {t["name"]: t for t in config["templates"]}
        topo = config["topology"]
        self.zone_key, zones = topo["zone_key"], topo["zones"]
        self.node_label_dicts = [
            {self.zone_key: zones[i % len(zones)], HOSTNAME: name}
            for i, name in enumerate(self.node_names)]
        self.world = World(self.node_names, self.node_label_dicts, self.alloc)
        self.live: dict = {name: {} for name in self.templates}  # FIFO
        self.template_of: dict = {}
        self._bursts = 0
        self.born: dict = {}           # pod -> the burst that posted it (0:
        #                                the load)
        self.died: dict = {}           # pod -> the burst that deleted it
        self.seen_binds: dict = {}     # pod -> (its bind's number, node)
        # by burst, from the stream: when its DELETE was sent, and when the
        # decisions counter covered its pods (None: never)
        self.delete_sent: dict = {}
        self.decided: dict = {0: float("-inf")}
        self.rebinds: set = set()      # pods seen bound a second time
        self.ambiguous_binds = 0
        self.preferred_fault = 0       # set by controls()
        self.term_counts: dict = {}

    def node_dicts(self) -> list:
        out = super().node_dicts()
        for node, labels in zip(out, self.node_label_dicts):
            node["labels"] = labels
        return out

    def make_template_pods(self, name: str, n: int) -> list:
        t = self.templates[name]
        pods = []
        for _ in range(n):
            pod = self._pod(int(t["cpu_milli"]), int(t["memory_bytes"]), None)
            pod["annotations"] = {"bench/template": name}
            if t.get("labels"):
                pod["labels"] = dict(t["labels"])
            affinity = {k: t[k] for k in (
                "pod_affinity", "pod_anti_affinity", "preferred_pod_affinity",
                "preferred_pod_anti_affinity") if t.get(k)}
            if affinity:
                pod["affinity"] = affinity
            pods.append(pod)
        return pods

    def make_population(self):
        """Template by template, as the source's ``createPods`` ops do."""
        per = int(self.config["population"]["pods_per_template"])
        return [], [p for name in self.templates
                    for p in self.make_template_pods(name, per)]

    def make_burst(self, per_template: int, rng) -> list:
        """``per_template`` fresh pods of each template, in seeded order."""
        pods = [p for name in self.templates
                for p in self.make_template_pods(name, per_template)]
        return [pods[i] for i in rng.permutation(len(pods))]

    def oldest_of_each(self, per_template: int) -> list:
        return [p for table in self.live.values()
                for p in list(table.values())[:per_template]]

    def add(self, pgs: list, pods: list, burst: bool = False) -> None:
        super().add(pgs, pods)
        self._bursts += int(burst)
        for pod in pods:
            key, name = self.key(pod), pod["annotations"]["bench/template"]
            t = self.templates[name]
            self.template_of[key] = name
            self.live[name][key] = pod
            self.born[key] = self._bursts if burst else 0
            self.world.add_pod(
                key, t["cpu_milli"], t["memory_bytes"], t.get("labels"),
                [(x["match_labels"], x["topology_key"])
                 for x in t.get("pod_affinity", ())],
                [(x["match_labels"], x["topology_key"])
                 for x in t.get("pod_anti_affinity", ())],
                [(w, x["match_labels"], x["topology_key"])
                 for w, x in t.get("preferred_pod_affinity", ())],
                [(w, x["match_labels"], x["topology_key"])
                 for w, x in t.get("preferred_pod_anti_affinity", ())])

    def retire(self, pgs: list, pods: list) -> None:
        """Deleted ahead of the next burst's posts.  The world keeps the pod:
        the walk needs what it was while it lived."""
        super().retire(pgs, pods)
        for pod in pods:
            key = self.key(pod)
            self.live[self.template_of[key]].pop(key)
            self.died[key] = self._bursts + 1

    def note_binds(self, rows: list) -> None:
        """Rows of ``/v1/bindings?seq=1``: the node and the number of every
        bind seen, kept past the pod's delete.  A pod seen on two nodes, or
        under two numbers, is a second bind."""
        for b in rows:
            if "seq" in b:
                was = self.seen_binds.setdefault(b["pod"], (b["seq"], b["node"]))
                if was != (b["seq"], b["node"]):
                    self.rebinds.add(b["pod"])

    def events(self, late_deletes: bool) -> list:
        """Every bind seen, in the program's order, with the deletes among
        them (module docstring): [(pod, node | None)].  ``late_deletes``:
        where the client cannot say, the delete follows the bind."""
        rows = sorted((seq, pod, node)
                      for pod, (seq, node) in self.seen_binds.items())
        last = max(self.delete_sent, default=0)
        deletes: dict = {}
        for pod, j in self.died.items():
            deletes.setdefault(j, []).append(pod)
        sent = [self.delete_sent.get(j, float("inf"))
                for j in range(1, last + 1)]

        def bounds(k):
            """Bursts whose deletes are surely (lo), and may be (hi), done
            when a pod that burst ``k`` posted is bound."""
            t_dec = self.decided.get(k)
            hi = last if t_dec is None else sum(t <= t_dec for t in sent)
            return k, max(k, hi)

        by_burst = {k: bounds(k) for k in set(self.born.values()) | {0}}
        done = [by_burst[self.born.get(pod, 0)] for _, pod, _ in rows]
        # time runs along the program's order: what is surely done stays done
        at = np.maximum.accumulate([lo for lo, _ in done] or [0])
        if not late_deletes:
            at = np.maximum(at, np.minimum.accumulate(
                [hi for _, hi in done][::-1] or [0])[::-1])
        out, d = [], 0
        for (_, pod, node), upto in zip(rows, at):
            while d < min(int(upto), last):
                d += 1
                out.extend((q, None) for q in deletes.get(d, ()))
            out.append((pod, node))
        for j in sorted(deletes):
            if j > d:
                out.extend((q, None) for q in deletes[j])
        self.ambiguous_binds = sum(lo != hi for lo, hi in done)
        return out

    def in_order(self, binds: list) -> list:
        """[(pod, node)] of ``/v1/bindings`` rows in the order the binds
        were made: by a row's own ``seq`` (``?seq=1``), else by the one the
        stream read; a pod bound since then comes last."""
        late = len(self.seen_binds) + len(binds)
        rows = sorted(binds, key=lambda b: (
            b.get("seq", self.seen_binds.get(b["pod"], (late,))[0]), b["pod"]))
        return [(b["pod"], b["node"]) for b in rows]

    def check_binds(self, binds: list):
        """``binds``: the live pods' rows at the end.  They join what the
        stream's earlier reads saw; a row without a number (run.py's own
        read) takes the one the stream read, else comes last."""
        late = len(self.seen_binds) + len(binds)
        for i, (pod, node) in enumerate(self.in_order(binds)):
            if self.seen_binds.setdefault(pod, (late + i, node))[1] != node:
                self.rebinds.add(pod)
        early = self.events(late_deletes=False)
        counts = self.world.check_binds(early)
        used = self.world.used
        late_order = self.events(late_deletes=True)
        if late_order != early:
            counts["affinity_violations"] = self.world.check_binds(
                late_order)["affinity_violations"]
        counts["double_binds"] += len(self.rebinds)
        # ``unbound``: a live pod that no read saw bound, and a deleted one
        # likewise (the traffic deletes the oldest, bound long before)
        self.term_counts = dict(counts, ambiguous_binds=self.ambiguous_binds)
        numbers = dict.fromkeys(
            ("unknown_pods", "unknown_nodes", "double_binds", "nodes_over",
             "gangs_split", "unbound", "overfit_binds"), 0)
        for name in ("unknown_pods", "unknown_nodes", "double_binds",
                     "nodes_over", "unbound"):
            numbers[name] = counts[name]
        numbers["overfit_binds"] = (
            counts["anti_affinity_violations"] + counts["affinity_violations"]
            + self.preferred_fault)
        return numbers, used

    def controls(self, binds: list) -> dict:
        """Both negative controls over a run's end state, and the verdict on
        the program's preferred-term shares (``preferred_fault``).

        1. ``place(ignore_terms)`` over the live pods in bind order: the
           same checker has to count violations.
        2. The shares of the pods the bursts posted (red beside a red pod,
           yellow on a node without another yellow pod) as the program bound
           them, against the sequential reference placing the same pods on
           the same base (everything bound that no burst posted), with the
           preference weights on (the program has to agree within
           ``SHARE_TOLERANCE``) and zeroed (which has to disagree)."""
        order = [(k, n) for k, n in self.in_order(binds) if k in self.pods]
        keys = [k for k, _ in order]
        blind = self.world.check_binds(self.world.place(keys, mode="ignore_terms"))
        base = [(k, n) for k, n in order if not self.born.get(k)]
        late = [k for k, _ in order if self.born.get(k)]
        shares = {"program": self.world.preferred_shares(order, late)}
        for name, mode in (("reference", "exact"), ("control", "no_preference")):
            placed = self.world.place(late, bound=base, mode=mode)
            shares[name] = self.world.preferred_shares(base + placed, late)

        def far(a, b):
            return max(abs(shares[a][k] - shares[b][k])
                       for k in ("beside", "apart"))

        # a configuration may widen it and say why (the rehearsal's 96 nodes
        # and 2 pods of a template a burst: one pod is 2-4% of a share)
        tolerance = float(self.config["control"].get(
            "preferred_tolerance", SHARE_TOLERANCE))
        self.preferred_fault = int(far("program", "reference") > tolerance)
        return {
            "control_terms_violations": (
                blind["anti_affinity_violations"]
                + blind["affinity_violations"]),
            "preferred_shares": shares,
            "preferred_tolerance": tolerance,
            "program_from_reference": far("program", "reference"),
            "control_from_program": far("control", "program"),
            "control_preferred_wrong": far("control", "program") > tolerance,
        }
