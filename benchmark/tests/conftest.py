"""The benchmark's own tests: ``python -m pytest benchmark/tests -q`` from
the repo root.  They are outside ``tests/``, so tier-1 neither counts nor
waits for them.  No test initialises a JAX backend in the test process:
the rehearsal tests start the harness, which starts the server as a child,
and test_trace_reduce.py only reads traces with ``jax.profiler.ProfileData``."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)


import json  # noqa: E402

import pytest  # noqa: E402

from rehearsal_manifest import derive  # noqa: E402


@pytest.fixture(scope="session")
def rehearsal_path(tmp_path_factory) -> str:
    """The rehearsal's manifest as a file, for ``run.py --manifest``."""
    path = tmp_path_factory.mktemp("rehearsal") / "manifest.json"
    path.write_text(json.dumps(derive()))
    return str(path)
