"""Native (C) fast paths for the host runtime.

The device compute path is JAX/XLA; the host runtime around it keeps its hot
inner loops native, like the reference keeps its whole runtime in compiled Go.
Currently: resource-vector arithmetic (fast.py), used by api.resources when
the shared library is present (auto-built on first import when a C compiler
is available; numpy fallback, logged at WARNING, otherwise)."""

from kube_batch_tpu.native.fast import resource_lib

__all__ = ["resource_lib"]
