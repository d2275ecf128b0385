"""The one child and the HTTP client (after chip_smoke.py's ``Server``).

The parent never imports JAX: a parent that has touched JAX holds the chip
and the child that needs it then fails or hangs.
"""

from __future__ import annotations

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

#: lines in the child's log that mean a failure was caught and carried on
#: from (scheduler.py, serve/plane.py, actions/allocate.py, guard/plane.py)
LOG_FAILURE_MARKERS = (
    b"scheduling cycle failed",
    b"pre-warm failed",
    b"lease publication failed",
    b"probe dispatch failed",
    b"Traceback (most recent call last)",
)


#: how long a client keeps asking a read plane that answers 503 before the
#: request counts as failed
REFUSAL_PATIENCE_S = 30.0


class RunFailure(Exception):
    """The run cannot give a result; the message says why."""


class Server:
    """``benchmark/serve.py``: the program's entry point with the shipped
    five-action conf and every default on (pipelined loop, guard, query
    plane, replication publisher).  Only the egress throttle is raised, as
    a deployment would: the reference's 50 QPS default would spend 1,000 s
    writing 50,000 binds."""

    def __init__(self, out_dir: str, sync_timeout: float = 1100.0):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self.out_dir = out_dir
        self.log_path = os.path.join(out_dir, "server.log")
        # the child's environment is this process's (no JAX_PLATFORMS, no
        # KB_* switch added), plus the two directories that keep a trip's
        # bundle with the run; JAX_COMPILATION_CACHE_DIR passes through and
        # otherwise the program keeps its cache at <checkout>/.jax_cache
        env = dict(os.environ)
        env["KB_GUARD_DIR"] = os.path.join(out_dir, "guard")
        env["KB_TRACE_DIR"] = os.path.join(out_dir, "flight")
        env.pop("BENCH_RUN", None)
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "serve.py"),
             "--listen-address", f"127.0.0.1:{self.port}",
             "--scheduler-conf",
             os.path.join(REPO, "config", "kube-batch-tpu-conf.yaml"),
             "--cache-sync-timeout", str(sync_timeout),
             "--kube-api-qps", "1000000", "--kube-api-burst", "1000000"],
            cwd=REPO, env=env, stdin=subprocess.PIPE, stdout=self._log,
            stderr=subprocess.STDOUT)

    # -- HTTP ----------------------------------------------------------------

    def raw(self, method: str, path: str, data: bytes = None,
            timeout: float = 120.0):
        """(status, body bytes).  One connection per request: the server
        speaks HTTP/1.0."""
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=timeout)
        try:
            headers = {"Content-Type": "application/json"} if data else {}
            conn.request(method, path, body=data, headers=headers)
            r = conn.getresponse()
            return r.status, r.read()
        finally:
            conn.close()

    def post_until_answered(self, path: str, data: bytes,
                            patience_s: float = REFUSAL_PATIENCE_S,
                            timeout: float = 60.0):
        """POST, and again while the answer is 503, the read plane's "not
        now, ask again" (no lease published, a probe still compiling), for
        at most ``patience_s`` from the first send, as a client that needs
        the answer does.  (last status, body, how many 503s came first)."""
        give_up, refusals = time.monotonic() + patience_s, 0
        while True:
            status, raw = self.raw("POST", path, data, timeout)
            if status != 503 or time.monotonic() >= give_up:
                return status, raw, refusals
            refusals += 1
            time.sleep(0.01)

    def request(self, method: str, path: str, body=None, timeout=120.0):
        data = None if body is None else json.dumps(body).encode()
        try:
            status, raw = self.raw(method, path, data, timeout)
        except OSError as e:  # refused, reset, timed out
            self.check()
            raise RunFailure(f"{method} {path} failed: {e}")
        if status != 200:
            raise RunFailure(f"{method} {path} answered {status}: {raw[:300]!r}")
        return json.loads(raw) if raw else None

    def get(self, path: str, timeout=120.0):
        return self.request("GET", path, timeout=timeout)

    def send(self, method: str, kind: str, items: list, batch: int = 5000):
        """Batched list-body ingest; every element must apply."""
        for i in range(0, len(items), batch):
            self.send_raw(method, kind, json.dumps(items[i:i + batch]).encode(),
                          len(items[i:i + batch]))

    def send_raw(self, method: str, kind: str, data: bytes, n: int) -> None:
        """One list body that was rendered ahead of time."""
        status, raw = self.raw(method, f"/v1/{kind}", data)
        resp = json.loads(raw) if status == 200 and raw else {}
        if not resp.get("ok") or resp.get("applied") != n:
            raise RunFailure(
                f"{method} /v1/{kind} answered {status} {raw[:200]!r} for "
                f"{n} items")

    # -- the child -------------------------------------------------------------

    def log_failures(self) -> int:
        with open(self.log_path, "rb") as f:
            log = f.read()
        return sum(log.count(m) for m in LOG_FAILURE_MARKERS)

    def check(self) -> None:
        if self.proc.poll() is not None:
            raise RunFailure(
                f"the server exited with code {self.proc.returncode}; "
                f"see {self.log_path}")

    def wait_up(self, timeout: float) -> dict:
        deadline = time.monotonic() + timeout
        while True:
            self.check()
            try:
                status, raw = self.raw("GET", "/version", timeout=5.0)
                if status == 200:
                    return json.loads(raw)
            except OSError:
                pass  # not listening yet
            if time.monotonic() > deadline:
                raise RunFailure("the server never answered /version")
            time.sleep(0.1)

    def ask(self, command: str, done_file: str, timeout: float = 60.0):
        """Send one line to the child's side thread and wait for the file
        that says it is done."""
        if os.path.exists(done_file):
            os.remove(done_file)
        self.tell(command)
        deadline = time.monotonic() + timeout
        while not os.path.exists(done_file):
            self.check()
            if time.monotonic() > deadline:
                raise RunFailure(f"the child never answered {command!r}")
            time.sleep(0.02)

    def tell(self, command: str) -> None:
        self.proc.stdin.write((command + "\n").encode())
        self.proc.stdin.flush()

    def stop(self) -> None:
        """Terminate and reap the child.  SIGTERM dies through libtpu's
        handler in ~3 s (PERF.md §7); SIGKILL if it will not go."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=30)
        try:
            self.proc.stdin.close()
        except OSError:
            pass
        self._log.close()
