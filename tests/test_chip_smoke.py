"""chip_smoke.py rehearsed on the CPU: the same command the chip check
runs, at a tiny size, plus the ways it must refuse to pass — no chip and no
stated platform, and a server whose every cycle raises while /healthz still
answers ok."""

from __future__ import annotations

import json
import os
import subprocess
import sys

from kube_batch_tpu.envutil import cpu_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")
# the smallest size at which every check holds: compaction needs a task
# capacity of 1,024 rows or more, and one virtual device keeps the expected
# solve mode "single" (the conftest mesh of 8 would expect "sharded", which
# a node axis this narrow never is)
SIZE = ["--nodes", "96", "--pods", "1200"]


def _smoke(tmp_path, *args, env=None):
    return subprocess.run(
        [sys.executable, SMOKE, "--out", str(tmp_path / "out"),
         "--deadline", "600", *args],
        env=env if env is not None else cpu_env(n_devices=1),
        capture_output=True, text=True, timeout=700,
    )


def test_rehearsal_passes_on_cpu_and_parent_stays_off_jax(tmp_path):
    r = _smoke(tmp_path, "--platform", "cpu", *SIZE,
               "--rounds", "3", "--min-audits", "0")
    assert r.returncode == 0, r.stderr[-4000:]
    summary, verdict = map(json.loads, r.stdout.strip().splitlines())
    # the last line is the verdict with exactly these keys, nothing beside
    assert verdict == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    assert list(summary) == ["smoke"]
    smoke = summary["smoke"]
    assert smoke["pods_bound"] == 1200 and smoke["rounds"] == 3
    assert smoke["solve_dispatches"]["single"] >= 1
    assert smoke["solve_dispatches"]["single+topk+warm"] >= 3
    assert smoke["guard"]["trips_total"] == 0
    assert smoke["whatif"]["sweep"]["max_fit"] == 64
    # the summary is also left beside the child's log
    with open(tmp_path / "out" / "result.json") as f:
        assert json.load(f) == verdict | summary
    # main() refuses to pass if this process ever imported jax; the module
    # itself must not pull it in either
    probe = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, %r); import chip_smoke; "
         "assert 'jax' not in sys.modules" % REPO],
        env=cpu_env(), capture_output=True, text=True, timeout=120,
    )
    assert probe.returncode == 0, probe.stderr[-2000:]


def test_no_chip_and_no_stated_platform_fails_without_a_result(tmp_path):
    # JAX_PLATFORMS=cpu hides any accelerator; no --platform is given
    r = _smoke(tmp_path, *SIZE, "--rounds", "1", "--min-audits", "0")
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "expected 'tpu'" in r.stderr
    assert not (tmp_path / "out" / "result.json").exists()


def test_a_server_whose_cycles_raise_fails_the_smoke(tmp_path):
    """The loop catches a raising cycle, logs it and carries on, and
    /healthz keeps answering ok — the smoke must not.  Planted here with a
    conf whose plugin tier names a plugin that does not exist: every
    open_session raises inside the cycle."""
    conf = tmp_path / "broken.yaml"
    conf.write_text(
        'actions: "enqueue, reclaim, allocate, backfill, preempt"\n'
        "tiers:\n- plugins:\n  - name: gang\n  - name: nosuchplugin\n"
    )
    r = _smoke(tmp_path, "--platform", "cpu", *SIZE, "--rounds", "1",
               "--min-audits", "0", "--scheduler-conf", str(conf))
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "scheduling cycle failed" in r.stderr
