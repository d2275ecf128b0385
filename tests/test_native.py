"""Native fast-path parity: every Resource op runs on both the C library and
the numpy fallback with identical results (native/resource_ops.c's contract)."""

from __future__ import annotations

import copy
import pickle

import numpy as np
import pytest

from kube_batch_tpu.api import resources as res_mod
from kube_batch_tpu.api.resources import DEFAULT_SPEC, ResourceSpec


@pytest.fixture(params=["native", "numpy"])
def lib_mode(request, monkeypatch):
    if request.param == "numpy":
        monkeypatch.setattr(res_mod, "_LIB", None)
    elif res_mod._LIB is None:
        pytest.skip("native library unavailable")
    return request.param


def _pair():
    a = DEFAULT_SPEC.build(32000, 1 << 34, 110, {"nvidia.com/gpu": 8000})
    b = DEFAULT_SPEC.build(1000, 1 << 30, 1, {"nvidia.com/gpu": 2000})
    return a, b


class TestParity:
    def test_add_sub_roundtrip(self, lib_mode):
        a, b = _pair()
        before = a.vec.copy()
        a.add_(b)
        assert a.milli_cpu == 33000
        a.sub_(b)
        np.testing.assert_allclose(a.vec, before)

    def test_sub_clamps_and_asserts(self, lib_mode):
        a, b = _pair()
        with pytest.raises(AssertionError):
            b.sub(a)  # underflow
        # clamp path with asserts off
        import os
        os.environ["PANIC_ON_ERROR"] = "false"
        try:
            c = b.sub(a)
            assert (c.vec >= 0).all()
        finally:
            del os.environ["PANIC_ON_ERROR"]

    def test_less_equal_tolerance(self, lib_mode):
        # excess below the quantum passes (resource_info.go:269-284)
        a = DEFAULT_SPEC.build(1005, 1 << 30, 1)
        b = DEFAULT_SPEC.build(1000, 1 << 30, 1)
        assert a.less_equal(b)       # 5m < 10m quantum
        assert not a.less_equal_strict(b)
        a2 = DEFAULT_SPEC.build(1020, 1 << 30, 1)
        assert not a2.less_equal(b)

    def test_set_max_and_share(self, lib_mode):
        a, b = _pair()
        b.set_max_(a)
        np.testing.assert_allclose(b.vec, a.vec)
        total = DEFAULT_SPEC.build(64000, 1 << 35, 220, {"nvidia.com/gpu": 16000})
        assert a.share(total) == pytest.approx(0.5)
        # pods dim excluded from share (semantic mask)
        tiny = DEFAULT_SPEC.build(0, 0, 220)
        assert tiny.share(total) == 0.0


class TestPointerLifetime:
    def test_vec_rebinding_refreshes_addr(self):
        a, b = _pair()
        a.vec = a.vec + b.vec  # the pre-native idiom must stay safe
        cpu = a.milli_cpu
        a.add_(b)
        assert a.milli_cpu == cpu + b.milli_cpu

    def test_deepcopy_and_pickle_get_fresh_buffers(self):
        a, _ = _pair()
        for other in (copy.deepcopy(a), pickle.loads(pickle.dumps(a))):
            other.add_(DEFAULT_SPEC.build(1000))
            assert other.milli_cpu == a.milli_cpu + 1000
            assert a.milli_cpu == 32000  # original untouched

    def test_spec_pickle_round_trip(self):
        spec = ResourceSpec(scalar_names=("x.com/npu",))
        back = pickle.loads(pickle.dumps(spec))
        assert back == spec
        r = back.build(100, scalars={"x.com/npu": 500})
        assert r.less_equal(back.build(200, scalars={"x.com/npu": 500}))
