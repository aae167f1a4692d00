"""Trace reduction against traces whose answers are known."""
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"

SYNTHETIC = '''
planes {
  id: 1
  name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 stats { metadata_id: 9 int64_value: 7 } }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 2000000 stats { metadata_id: 9 int64_value: 8 } }
  }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 1000000 duration_ps: 3000000 }
    events { metadata_id: 5 offset_ps: 6000000 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "jit_decode(123)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_dense_topk(456)" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.1 = f32[8] fusion(f32[8] %a)" } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.2 = f32[8] fusion(f32[8] %b)" } }
  event_metadata { key: 5 value { id: 5 name: "%dense_topk.1 = (f32[4,20]) custom-call(f32[4,768] %q), custom_call_target=\\"tpu_custom_call\\"" } }
  stat_metadata { key: 9 value { id: 9 name: "run_id" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 5 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 500000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 1500000 }
    events { metadata_id: 3 offset_ps: 9500000 duration_ps: 1000000 }
  }
  lines { id: 6 name: "tfrt" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 800000 duration_ps: 100000 stats { metadata_id: 9 int64_value: 7 } }
    events { metadata_id: 4 offset_ps: 7000000 duration_ps: 100000 stats { metadata_id: 9 int64_value: 8 } }
  }
  event_metadata { key: 1 value { id: 1 name: "$profiler.py:101 start_trace" } }
  event_metadata { key: 2 value { id: 2 name: "retriever.kb_call" } }
  event_metadata { key: 3 value { id: 3 name: "$profiler.py:213 stop_trace" } }
  event_metadata { key: 4 value { id: 4 name: "DoEnqueueProgram" } }
  stat_metadata { key: 9 value { id: 9 name: "run_id" } }
}
'''


def synthetic():
    from jax.profiler import ProfileData
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(SYNTHETIC))


def test_synthetic_trace_reduces_to_known_numbers():
    # device runs ahead of host: run 8 is enqueued at 7.0 us but shows at
    # 7.001 us, run 7 at 0.8 us and shows at 1.0 -> no shift needed;
    # window: 0.5 us (end of start_trace) .. 9.5 us (stop_trace)
    s = trace.reduce(synthetic())
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(9.0e-6)
    assert s.programs["decode"] == [pytest.approx(4e-6), 1]
    assert s.programs["dense_topk"] == [pytest.approx(2e-6), 1]
    assert s.busy_s == pytest.approx(6e-6)
    assert s.ops["dense_topk:dense_topk.1"] == pytest.approx(2e-6)
    assert s.ops["decode:fusion.2"] == pytest.approx(3e-6)
    # idle: 0.5..1.0 (before any span: host other), 5.0..7.0 (inside the
    # kb_call span 5.0..6.5 for 1.5 us, the most), 9.0..9.5 (other)
    assert s.gaps["retriever.kb_call"] == pytest.approx(2e-6)
    assert s.gaps["host: other"] == pytest.approx(1e-6)
    bd = s.breakdown()
    assert bd["device_ops"][0][0] == "decode:fusion.2"
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_recorded_v5e_trace():
    """Three decode and three KB calls traced on one TPU v5 lite: the KB
    call's program holds the pad of the KB to the kernel's block and the
    Pallas kernel itself."""
    from jax.profiler import ProfileData
    s = trace.reduce(ProfileData.from_file(str(DATA / "probe_v5e.xplane.pb")))
    assert s.n_devices == 1
    assert s.program_n("dense_topk") == 3 and s.program_n("decode") == 3
    per_call = s.program_s("dense_topk") / 3
    assert 0.020 < per_call < 0.026
    assert s.ops["dense_topk:pad.0"] + s.ops["dense_topk:dense_topk.1"] \
        == pytest.approx(s.program_s("dense_topk"), rel=1e-3)
    assert 0 < s.busy_s < s.window_s


def test_program_and_op_names():
    assert trace.program_name("jit_decode(17004621832882675677)") == "decode"
    assert trace.program_name("jit__lambda_") == "_lambda_"
    assert trace.op_name("%pad.0 = f32[1313792,768] pad(f32[1313458,768] %kb.1)") == "pad.0"
