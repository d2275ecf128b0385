"""Shared test fixtures — the rebuild's pkg/scheduler/util/test_utils.go:
builders that feed synthetic objects through the real cache handlers, plus
fake-backend assembly."""

from __future__ import annotations

import threading
from typing import Dict, Optional

from kube_batch_tpu.api.pod import GROUP_NAME_ANNOTATION, Node, Pod, PodGroup, Queue
from kube_batch_tpu.api.resources import DEFAULT_SPEC
from kube_batch_tpu.api.types import PodPhase
from kube_batch_tpu.cache.cache import SchedulerCache
from kube_batch_tpu.cache.fake import FakeBinder, FakeEvictor

GiB = 2**30
_counter = [0]


def build_resource_list(cpu_milli: float, memory: float, gpu: float = 0.0) -> Dict[str, float]:
    """BuildResourceList[WithGPU] (test_utils.go:34-52)."""
    r = {"cpu": cpu_milli, "memory": memory}
    if gpu:
        r["nvidia.com/gpu"] = gpu
    return r


def build_node(name: str, cpu: float = 8000, mem: float = 16 * GiB, pods: int = 110,
               labels=None, taints=None, **kw) -> Node:
    alloc = {"cpu": cpu, "memory": mem, "pods": pods}
    return Node(name=name, allocatable=alloc, labels=labels or {}, taints=taints or [], **kw)


def build_pod(
    namespace: str,
    name: str,
    node_name: Optional[str],
    phase: PodPhase,
    requests: Dict[str, float],
    group_name: Optional[str] = None,
    priority: int = 0,
    **kw,
) -> Pod:
    """BuildPod (test_utils.go:60-92): sets the group-name annotation."""
    _counter[0] += 1
    annotations = {}
    if group_name:
        annotations[GROUP_NAME_ANNOTATION] = group_name
    return Pod(
        name=name,
        namespace=namespace,
        requests=requests,
        node_name=node_name,
        phase=phase,
        annotations=annotations,
        priority=priority,
        creation_index=_counter[0],
        **kw,
    )


def build_cache(
    nodes=(),
    pods=(),
    pod_groups=(),
    queues=(),
) -> SchedulerCache:
    """The canonical fake-backend cache assembly (allocate_test.go:150-163):
    real SchedulerCache + Fake seams, objects fed through real handlers."""
    cache = SchedulerCache(
        spec=DEFAULT_SPEC,
        binder=FakeBinder(),
        evictor=FakeEvictor(),
    )
    for q in queues:
        cache.add_queue(q if isinstance(q, Queue) else Queue(name=q))
    for pg in pod_groups:
        cache.add_pod_group(pg)
    for n in nodes:
        cache.add_node(n)
    for p in pods:
        cache.add_pod(p)
    return cache


class PacedCondition:
    """A :class:`CycleTrigger`'s condition variable on the trigger's own
    injected (virtual) clock, for one thread: a wait never blocks, it moves
    the clock on — to the wait's end, or only as far as the next scripted
    signal that falls inside it, which it then raises.  ``install`` puts it
    in the trigger's place; ``script`` is ``[(virtual time, notify()'s
    keywords)]``, or in the keywords' place a callable that raises the
    signal itself (an ingest through a cache whose signal is the trigger's
    ``notify``, a ``Scheduler.stop``)."""

    def __init__(self, trigger, script=()):
        self.trigger, self.clock = trigger, trigger.clock
        self.script = sorted(script, key=lambda s: s[0])
        # re-entrant: a scripted notify() runs inside the caller's guard
        self._guard = threading.RLock()

    @classmethod
    def install(cls, trigger, script=()) -> "PacedCondition":
        trigger._cond = cond = cls(trigger, script)
        return cond

    def __enter__(self):
        self._guard.acquire()
        return self

    def __exit__(self, *exc):
        self._guard.release()

    def notify_all(self) -> None:
        pass

    def wait(self, timeout: float) -> None:
        end = self.clock.monotonic() + timeout
        if self.script and self.script[0][0] <= end:
            at, kwargs = self.script.pop(0)
            self.clock.advance_to(at)
            if callable(kwargs):
                kwargs()
            else:
                self.trigger.notify(**kwargs)
        else:
            self.clock.advance_to(end)
