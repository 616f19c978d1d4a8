"""The trace reduction, on a hand-written trace and on a recorded one."""

from pathlib import Path

import jax
import pytest

from bench import trace_reduce as tr

DATA = Path(__file__).resolve().parent.parent / "testdata"

# One chip; times in ps.  Ops: fusion.1 [0, 100 ns), an all-gather-start
# [100, 130), collective-permute-done [120, 160) overlapping it, a gap,
# while.1 [300, 400) around its body's fusion.2 [300, 400).  Host: the module's run 5 completes at host 1400
# (device end 400 -> clock offset 1000); bench.data covers host
# [1100, 1400), so the gap [160, 300) whose middle is device 230 = host
# 1230 is labelled bench.data.
SPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 100000 duration_ps: 30000 }
    events { metadata_id: 3 offset_ps: 120000 duration_ps: 40000 }
    events { metadata_id: 7 offset_ps: 300000 duration_ps: 100000 }
    events { metadata_id: 4 offset_ps: 300000 duration_ps: 100000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 5 offset_ps: 0 duration_ps: 400000
             stats { metadata_id: 6 int64_value: 5 } } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2 name: "%all-gather-start.3 = (f32[4]{0}, f32[8]{0}) all-gather-start(f32[4]{0} %x), dimensions={0}" } }
  event_metadata { key: 3 value { id: 3 name: "%collective-permute-done.2 = f32[8]{0} collective-permute-done(f32[8]{0} %y)" } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %q), kind=kOutput" } }
  event_metadata { key: 5 value { id: 5 name: "jit_step(1)" } }
  event_metadata { key: 7 value { id: 7 name: "%while.1 = (s32[]) while((s32[]) %t), condition=%c, body=%b" } }
  stat_metadata { key: 6 value { id: 6 name: "run_id" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1100000 duration_ps: 300000 }
    events { metadata_id: 2 offset_ps: 1400000 duration_ps: 10000
             stats { metadata_id: 3 int64_value: 5 } } }
  event_metadata { key: 1 value { id: 1 name: "bench.data" } }
  event_metadata { key: 2 value { id: 2 name: "CompleteCallbacks" } }
  stat_metadata { key: 3 value { id: 3 name: "run_id" } }
}
"""


def test_opcode_and_instruction():
    t = ("%copy-start = (bf16[8]{0:T(8)S(1)}, u32[]{:S(2)}) "
         "copy-start(bf16[8]{0} %a.1), cross_program_prefetch_index=0")
    assert tr.opcode(t) == "copy-start"
    assert tr.instruction(t) == "copy-start"
    assert tr.opcode("%fusion.7 = f32[2]{0} fusion(f32[2]{0} %p)") == "fusion"
    assert tr.instruction("%fusion.7 = f32[2]{0} fusion(f32[2]{0} %p)") \
        == "fusion.7"


def test_union_and_subtract():
    assert tr.union([(5, 8), (0, 2), (1, 3), (8, 9)]) == [(0, 3), (5, 9)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]


def test_hand_written_trace():
    pd = jax.profiler.ProfileData.from_text_proto(SPACE)
    r = tr.reduce(*tr.load(pd), steps=2, window_s=1e-6)
    ns = 1e-9
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx(260 * ns)        # [0,160) + [300,400)
    assert r["collective_s"] == pytest.approx(70 * ns)   # 30 + 40
    assert r["exposed_collective_s"] == pytest.approx(60 * ns)  # [100,160)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(100 * ns)]
    assert dict(r["device_ops"])["all-gather-start.3"] == \
        pytest.approx(30 * ns)
    assert "while.1" not in dict(r["device_ops"])      # a container
    assert r["idle_gaps"] == [["bench.data", pytest.approx(140 * ns)]]


def test_recorded_v5e_trace():
    """Recorded on one TPU v5e by bench/tools/record_trace.py: three runs
    of copy-start (13 ns), copy-done (3 ns) and a fusion (15,903, 15,736
    and 15,751 ns), read from the trace by hand."""
    pd = jax.profiler.ProfileData.from_file(str(DATA / "v5e_probe.xplane.pb"))
    r = tr.reduce(*tr.load(pd), steps=3, window_s=0.0025,
                  labels=("probe_",))
    ns = 1e-9
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx(47438 * ns)
    assert r["collective_s"] == 0
    ops = dict(r["device_ops"])
    assert ops["fusion"] == pytest.approx(47390 * ns)
    assert ops["copy-start"] == pytest.approx(39 * ns)
    assert ops["copy-done"] == pytest.approx(9 * ns)
    # gaps 1+2+818134 | 1+2+666747 | 2 ns, every one inside a probe_step
    # once the device clock is moved by the median completion offset
    assert r["idle_gaps"] == [["probe_step", pytest.approx(1484889 * ns)]]
