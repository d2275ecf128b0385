"""The whole harness at a tiny size on the CPU, all three kinds of stream,
through run.py's own entry; and the same run with the timed path broken
underneath, which has to come out ``correct: false``.

The run skips only the look for a chip (``--platform cpu``): the server is
the program's normal entry point as the benchmark starts it."""

import json
import os
import subprocess
import sys

import pytest

import run as harness
import server as server_mod
from conftest import BENCH, REPO

def run_cli(tmp_path, manifest, workload, *extra):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    got = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--manifest", manifest,
         "--workload", workload, "--seed", "2147484001", "--seconds", "5",
         "--out", str(tmp_path / "out"), *extra],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    return got


@pytest.mark.parametrize("workload,trace,reports", [
    ("rehearsal-steady", "0", ["cpu_decision_p50_ms", "cpu_decision_p90_ms",
                               "cpu_setup_s"]),
    ("rehearsal-kubemark", "1", ["cpu_cycles_rate", "cpu_session_open_ms",
                                 "cpu_cold_drain_s", "cpu_ingest_post_ms"]),
    ("rehearsal-whatif", "0", ["cpu_whatif_p50_ms", "cpu_whatif_rate",
                               "cpu_setup_s"]),
])
def test_rehearsal_runs_correct(tmp_path, rehearsal_path, workload, trace,
                                reports):
    got = run_cli(tmp_path, rehearsal_path, workload, "--trace", trace, "--platform", "cpu")
    assert got.returncode == 0, got.stderr[-2000:]
    line = json.loads(got.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    # a CPU run carries no number under a device metric's name
    assert line["metrics"] == {}
    for name in reports:
        assert line["rehearsal"][name]["value"] > 0, name
    if trace == "1":
        assert {"busy_s", "window_s"} <= set(line["device"])


def test_no_tpu_no_result(tmp_path, rehearsal_path):
    """Without ``--platform cpu`` a run whose server is not on a TPU ends
    non-zero with nothing on stdout."""
    env_cpu = dict(os.environ, JAX_PLATFORMS="cpu")
    got = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--manifest", rehearsal_path,
         "--workload", "rehearsal-kubemark", "--seed", "1", "--seconds", "2",
         "--trace", "0", "--out", str(tmp_path / "out")],
        cwd=REPO, env=env_cpu, capture_output=True, text=True, timeout=300)
    assert got.returncode != 0
    assert got.stdout.strip() == ""
    assert "needs 'tpu'" in got.stderr


class TamperingServer(server_mod.Server):
    """The served path with an answer altered where it is produced."""

    fault = None

    def request(self, method, path, body=None, timeout=120.0):
        resp = super().request(method, path, body, timeout)
        if self.fault == "move_bind" and path == "/v1/bindings":
            # every pod of the fullest node's neighbour lands on one node
            target = resp[0]["node"]
            for row in resp[: len(resp) // 2]:
                row["node"] = target
        if self.fault == "drop_bind" and path == "/v1/bindings":
            resp = resp[1:]
        if self.fault == "bind_unfit" and path == "/v1/bindings":
            # a program whose capacity plane reads too little in use binds
            # the pods that ask for more than any node has left
            resp = resp + [{"pod": pod, "node": resp[0]["node"],
                            "status": "BOUND"} for pod in self.over_pods]
        return resp

    def post_until_answered(self, path, data, *args, **kwargs):
        status, text, refusals = super().post_until_answered(
            path, data, *args, **kwargs)
        if (self.fault == "max_fit" and path == "/v1/whatif/sweep"
                and status == 200):
            resp = json.loads(text)
            resp["max_fit"] = resp["max_fit"] + 1
            text = json.dumps(resp).encode()
        return status, text, refusals

    def raw(self, method, path, data=None, timeout=120.0):
        status, text = super().raw(method, path, data, timeout)
        if self.fault == "bind_unfit" and (method, path) == ("POST", "/v1/pods"):
            pods = json.loads(data)
            if len(pods) == 2:  # an edge round's pair: the over pod first
                self.over_pods.append(
                    f"{pods[0]['namespace']}/{pods[0]['name']}")
        if self.fault == "counter" and path == "/metrics":
            # a decisions counter that has moved: one decision short
            key = b"volcano_arrival_to_decision_latency_milliseconds_count{} "
            i = text.find(key)
            if i >= 0:
                j = text.find(b"\n", i)
                n = int(text[i + len(key):j])
                if n >= self.short_from:
                    text = text[:i + len(key)] + str(n + 1).encode() + text[j:]
        return status, text


@pytest.mark.parametrize("workload,fault,number", [
    ("rehearsal-kubemark", "move_bind", "nodes_over"),
    ("rehearsal-kubemark", "drop_bind", "unbound"),
    ("rehearsal-kubemark", "counter", "counter_mismatch"),
    ("rehearsal-kubemark", "bind_unfit", "overfit_binds"),
    ("rehearsal-whatif", "max_fit", "whatif_wrong"),
])
def test_broken_path_is_not_correct(tmp_path, rehearsal_path, monkeypatch,
                                    capsys, workload, fault, number):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=1")
    TamperingServer.fault = fault
    TamperingServer.short_from = 0
    TamperingServer.over_pods = []
    args = harness.argparse.Namespace(
        workload=workload, seed=5, seconds=3.0, trace=0, platform="cpu")
    out = tmp_path / "out"
    out.mkdir()
    line = harness.run_cell(args, harness.load_json(rehearsal_path), str(out),
                            server_factory=TamperingServer)
    printed = capsys.readouterr().out
    assert line["correct"] is False
    assert f"{number}: " in printed and "NOT correct" in printed
