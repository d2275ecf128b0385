"""The benchmark's child: the program's normal entry point, in this process,
with a side thread that answers the parent's few questions on stdin.

``kube_batch_tpu.cmd.main.main()`` runs on the main thread exactly as
``python -m kube_batch_tpu.cmd.main`` runs it; no file of the program is
edited and none of its options is set here.  The side thread exists for
what only the process that holds the chip can do:

- ``mem <file>``: write each local device's ``memory_stats()`` as JSON;
- ``trace_start <dir>`` / ``trace_stop``: bracket a ``jax.profiler`` trace.

It blocks on ``stdin.readline()`` and costs nothing between commands.
"""

from __future__ import annotations

import json
import os
import sys
import threading

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _answer(line: str) -> None:
    import jax

    cmd, _, arg = line.strip().partition(" ")
    if cmd == "mem":
        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        tmp = arg + ".tmp"
        with open(tmp, "w") as f:
            json.dump(stats, f)
        os.replace(tmp, arg)
    elif cmd == "trace_start":
        # no Python tracer: it hooks every call of a host path that is
        # bound by the interpreter, and would time itself
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(arg, profiler_options=options)
    elif cmd == "trace_stop":
        jax.profiler.stop_trace()
        with open(arg, "w") as f:
            f.write("stopped\n")


def _listen() -> None:
    for line in sys.stdin:
        try:
            _answer(line)
        except Exception as e:  # noqa: BLE001 — reported; the server runs on
            print(f"benchmark/serve.py: {line.strip()!r} failed: {e!r}",
                  file=sys.stderr, flush=True)


def main() -> int:
    sys.path.insert(0, REPO)
    from kube_batch_tpu.cmd import main as entry

    threading.Thread(target=_listen, name="bench-side", daemon=True).start()
    return entry.main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
