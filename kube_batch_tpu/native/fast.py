"""ctypes loader (+ lazy auto-build) for the native resource ops.

Exposes `resource_lib` — a ctypes CDLL with typed signatures, or None when
the library can't be built/loaded — and `resource_lib_state`, which says in
words which of the two this process got (the server reports it at
start-up). api/resources.py consults the library per call; all semantics
have a numpy twin so behavior is identical either way (the test suite runs
both paths — tests/test_native.py)."""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
from typing import Optional, Tuple

logger = logging.getLogger("kube_batch_tpu")

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libresource_ops.so")
_SRC = os.path.join(_DIR, "resource_ops.c")
# digest of the source the library beside it was built from — a library is
# loaded only when this matches resource_ops.c as it is now (file times say
# nothing after a copy or a checkout)
_BUILT_FROM = _SO + ".built-from"
# digest of a source that failed to build, so a broken toolchain is not
# re-run on every import
_FAIL_STAMP = os.path.join(_DIR, ".build-failed")

# raw addresses (int) are passed for speed — a cached arr.ctypes.data beats
# building a POINTER object per call by ~2 us
_D = ctypes.c_void_p


def _read(path: str) -> str:
    try:
        with open(path) as f:
            return f.read()
    except OSError:
        return ""


def _build(digest: str) -> Optional[str]:
    """Build via the Makefile (single source of truth for the recipe; its
    tmp-then-mv keeps concurrent builders atomic).  Returns None on
    success, else the reason in words."""
    if _read(_FAIL_STAMP) == digest:
        return "this source already failed to build here"
    try:
        # -B: make's own staleness test is the file times this loader
        # refuses to trust
        subprocess.run(
            ["make", "-B", "-C", _DIR, "libresource_ops.so"],
            check=True,
            capture_output=True,
            timeout=60,
        )
    except (OSError, subprocess.SubprocessError) as e:
        logger.warning("native resource_ops build failed (%s); using numpy", e)
        try:
            with open(_FAIL_STAMP, "w") as f:
                f.write(digest)
        except OSError:
            pass
        return f"build failed: {type(e).__name__}"
    with open(_BUILT_FROM, "w") as f:
        f.write(digest)
    try:
        os.unlink(_FAIL_STAMP)
    except OSError:
        pass
    return None


def _load() -> Tuple[Optional[ctypes.CDLL], str]:
    if os.environ.get("KB_NO_NATIVE"):  # escape hatch / fallback testing
        return None, "numpy (KB_NO_NATIVE set)"
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()
    state = "loaded (built earlier from this resource_ops.c)"
    if not os.path.exists(_SO) or _read(_BUILT_FROM) != digest:
        why = _build(digest)
        if why is not None:
            return None, f"numpy ({why})"
        state = "built from resource_ops.c by this process"
    try:
        lib = ctypes.CDLL(_SO)
    except OSError as e:
        logger.warning("native resource_ops load failed (%s); using numpy", e)
        return None, f"numpy (load failed: {e})"
    n = ctypes.c_ssize_t  # ptrdiff_t
    lib.kb_add_.argtypes = [_D, _D, n]
    lib.kb_add_.restype = None
    lib.kb_sub_clamped_.argtypes = [_D, _D, n]
    lib.kb_sub_clamped_.restype = None
    lib.kb_less_equal.argtypes = [_D, _D, _D, n]
    lib.kb_less_equal.restype = ctypes.c_int
    lib.kb_less_equal_strict.argtypes = [_D, _D, n]
    lib.kb_less_equal_strict.restype = ctypes.c_int
    lib.kb_set_max_.argtypes = [_D, _D, n]
    lib.kb_set_max_.restype = None
    lib.kb_share.argtypes = [_D, _D, _D, n]
    lib.kb_share.restype = ctypes.c_double
    return lib, state


resource_lib, resource_lib_state = _load()
