"""A what-if that the read plane refuses with 503 is asked again and its
latency runs from the first send; only one that no patience gets answered,
or that meets another error, has failed."""

import types

import pytest

import server as server_mod
from streams import whatif_clients


class Scripted(server_mod.Server):
    """``post_until_answered`` over a scripted ``raw``; no child."""

    def __init__(self, statuses):
        self.statuses, self.sent = list(statuses), 0

    def raw(self, method, path, data=None, timeout=120.0):
        self.sent += 1
        status = self.statuses.pop(0) if self.statuses else 200
        return status, (b'{"snapshot_version": 7, "feasible": true}'
                        if status == 200 else b'{"error": "no lease"}')


@pytest.mark.parametrize("statuses,patience,want", [
    ([503, 503, 200], 30.0, (200, 2)),   # refused twice, then answered
    ([200], 30.0, (200, 0)),
    ([503, 503, 503], 0.0, (503, 0)),    # no patience: the refusal stands
    ([500, 200], 30.0, (500, 0)),        # any other error is not asked again
])
def test_a_refusal_is_asked_again(statuses, patience, want):
    srv = Scripted(statuses)
    status, _raw, refusals = srv.post_until_answered("/v1/whatif", b"{}",
                                                     patience)
    assert (status, refusals) == want
    assert srv.sent == refusals + 1


@pytest.mark.parametrize("statuses,failed,refusals", [
    ([503, 200], 0, 1),
    ([500], 1, 0),
])
def test_the_stream_counts_refusals_and_failures(statuses, failed, refusals):
    import time
    srv = Scripted(statuses)
    ctx = types.SimpleNamespace(
        server=srv, samples={}, numbers={}, notes={}, metrics_pages={},
        attempted=0, failed=0,
        t_window=time.monotonic(),
        scraper=types.SimpleNamespace(version_at=lambda t: 7),
        config={"queues": [{"name": "q"}],
                "request_mix": {"cpu_milli": [250], "memory_bytes": [1 << 30]},
                "node": {"cpu_milli": 32000}})
    stream = whatif_clients.Stream(ctx, {
        "clients": 1, "probe_share": 1.0, "counts": [1], "max_count": 8,
        "block": 1000, "warm_requests": 0}, seed=3, seconds=0.05)
    stream.run()
    stream.finish()
    assert ctx.failed == failed
    assert sum(ctx.samples["whatif_refusals"]) == refusals
    assert ctx.attempted == len(ctx.samples["whatif_refusals"]) >= 1
    assert len(ctx.samples["whatif_ms"]) == ctx.attempted - failed
