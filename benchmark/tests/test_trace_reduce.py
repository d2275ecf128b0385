"""trace_reduce.py against a trace recorded on the chip (the first traced
run of ``kubemark-3k-100``, PR 23, TPU v5 lite; gzipped to keep the
checkout small) and against a hand-written one whose answer is known."""

import gzip
import os

import pytest

import trace_reduce
from conftest import HERE

RECORDED = os.path.join(HERE, "data", "kubemark-3k-100.pr23.xplane.pb.gz")

SYNTHETIC = """
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 300000 }
    events { metadata_id: 1 offset_ps: 700000 duration_ps: 200000 } }
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 200000 }
    events { metadata_id: 3 offset_ps: 100000 duration_ps: 200000 }
    events { metadata_id: 2 offset_ps: 700000 duration_ps: 200000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_solve(42)" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.1 = f32[8] fusion(...)" } }
  event_metadata { key: 3 value { id: 3 name: "%sort.2 = s32[8] sort(...)" } }
}
planes { name: "/device:CUSTOM:Megascale Trace" }
planes {
  name: "/host:CPU"
  lines { name: "python3" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 320000 duration_ps: 360000 } }
  event_metadata { key: 1 value { id: 1 name: "cv.wait" } }
  event_metadata { key: 2 value { id: 2 name: "session_open" } }
}
"""


def profile_data(raw: bytes):
    from jax.profiler import ProfileData

    return ProfileData.from_serialized_xspace(raw)


def test_synthetic_trace_has_the_known_answer():
    from jax.profiler import ProfileData

    raw = ProfileData.text_proto_to_serialized_xspace(SYNTHETIC)
    got = trace_reduce.reduce_data(profile_data(raw))
    # ops cover [0, 300) and [700, 900) ns of a [0, 1000) ns window: the
    # overlap of the first two counts once, the empty plane is no chip
    assert got["device_planes"] == ["/device:TPU:0"]
    assert got["busy_s"] == pytest.approx(500e-9)
    assert got["window_s"] == pytest.approx(1000e-9)
    assert got["idle_share"] == pytest.approx(50.0)
    assert got["programs"] == [["jit_solve", pytest.approx(500e-9)]]
    assert got["device_ops"][0] == ["%fusion.1", pytest.approx(400e-9)]
    # the longest gap, [300, 700), is named by the host event that is not
    # a wait and by the program that ended it
    assert got["idle_gaps"][0] == ["session_open -> jit_solve",
                                   pytest.approx(400e-9)]


def test_recorded_chip_trace_reproduces():
    with gzip.open(RECORDED) as f:
        got = trace_reduce.reduce_data(profile_data(f.read()))
    assert got["device_planes"] == ["/device:TPU:0"]
    assert got["busy_s"] == pytest.approx(0.002949274, rel=1e-6)
    assert got["window_s"] == pytest.approx(3.910873868, rel=1e-6)
    assert got["idle_share"] == pytest.approx(99.92458785, rel=1e-6)
    assert got["programs"] == [["jit_scatter", pytest.approx(0.002953341,
                                                            rel=1e-6)]]
    assert got["device_ops"][0][0] == "%fusion"
    assert len(got["idle_gaps"]) <= 10
    assert all(name.endswith("jit_scatter") or name.endswith("end of trace")
               for name, _ in got["idle_gaps"])


def test_a_trace_without_a_device_reports_no_busy_time(tmp_path):
    from jax.profiler import ProfileData

    raw = ProfileData.text_proto_to_serialized_xspace(
        SYNTHETIC[SYNTHETIC.index('planes { name: "/device:CUSTOM'):])
    got = trace_reduce.reduce_data(profile_data(raw))
    assert got["busy_s"] == 0.0 and got["device_planes"] == []
