"""The trace reduction on a synthetic trace with known answers."""

import pytest

import trace_reduce

# device: ops at [0, 5), [6, 7) (a strip kernel), [7, 8) overlapping
# [7.5, 9) -- all in us; host: the traced window [0, 10) and a host span
# covering [5, 6) and [9, 10)
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 7000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 7500000 duration_ps: 1500000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 0 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.12" } }
  event_metadata { key: 2 value { id: 2 name: "%_strip_class_call.3 = (f32[8,10]) custom-call(u8[4,128] %list_data.1)" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.7" } }
  event_metadata { key: 4 value { id: 4 name: "jit_search" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 4500000 duration_ps: 5500000 }
    events { metadata_id: 3 offset_ps: 5200000 duration_ps: 900000 }
    events { metadata_id: 4 offset_ps: 5000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench::traced" } }
  event_metadata { key: 2 value { id: 2 name: "bench::wait" } }
  event_metadata { key: 3 value { id: 3 name: "serving::dispatch" } }
  event_metadata { key: 4 value { id: 4 name: "$python frame" } }
}
"""


@pytest.fixture(scope="module")
def reduction():
    from jax.profiler import ProfileData

    return trace_reduce.Reduction(ProfileData.from_text_proto(XSPACE))


def test_window_and_busy(reduction):
    assert reduction.window_s == pytest.approx(10e-6)
    # union of [0,5) [6,7) [7,8) [7.5,9) = 5 + 3 us; the modules line and
    # the host plane are not device ops
    assert reduction.busy_s == pytest.approx(8e-6)


def test_kernel_time(reduction):
    assert reduction.op_time_s([r"_strip_class_call"]) == pytest.approx(2.5e-6)
    assert reduction.op_time_s([r"no_such_kernel"]) == 0.0


def test_op_names_are_short():
    assert trace_reduce.op_name(
        "%fusion.12 = bf16[8,128] fusion(f32[8,128] %x.3), kind=kLoop") \
        == "fusion"
    assert trace_reduce.op_name("copy-start.4") == "copy-start"


def test_top_ops_merge_numbered_names(reduction):
    top = dict(reduction.top_ops())
    assert top["fusion"] == pytest.approx(6e-6)
    assert top["_strip_class_call"] == pytest.approx(2.5e-6)


def test_idle_gaps_labelled_by_innermost_host_span(reduction):
    gaps = reduction.idle_gaps()
    assert [g[0] for g in gaps] == ["serving::dispatch", "bench::wait"]
    assert gaps[0][1] == pytest.approx(1e-6)
    assert gaps[1][1] == pytest.approx(1e-6)
