"""The reduction from a profiler trace to busy time, op time and idle gaps,
on hand-made events and on a small trace recorded on the CPU."""
import re
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP.parent))

from chip import tracing as T  # noqa: E402

CPU_TRACE = CHIP / "tests" / "cpu_trace.xplane.pb"


def hand_made():
    # two devices over a 10 s window; the host builds a batch, steps, saves
    tr = T.Trace()
    tr.devices["/device:TPU:0"] = [("fusion.1", 1.0, 3.0),
                                   ("all-reduce.7", 3.0, 4.0),
                                   ("fusion.2", 6.0, 7.0)]
    tr.devices["/device:TPU:1"] = [("fusion.1", 1.0, 4.0),
                                   ("fusion.3", 6.0, 8.0)]
    tr.host = [("bench.window", 0.0, 10.0), ("bench.train_span", 0.0, 5.0),
               ("bench.input", 0.0, 1.0), ("bench.train_span", 5.0, 10.0),
               ("bench.input", 5.0, 6.0), ("bench.save", 8.0, 10.0)]
    return tr


def test_op_names_and_self_time():
    assert T.op_name("fusion.12") == "fusion"
    assert T.op_name("%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} "
                     "%p), replica_groups={}") == "all-reduce"
    assert T.op_name("%copy-start = (bf16[4]) copy-start(%x.1)") \
        == "copy-start"
    evs = [("%while.1 = (s32[]) while()", 0.0, 10.0),
           ("%fusion.2 = f32[] fusion()", 1.0, 4.0),
           ("%fusion.3 = f32[] fusion()", 5.0, 6.0),
           ("%copy.4 = f32[] copy()", 11.0, 12.0)]
    own = {n.split(" ")[0]: t for n, _, _, t in T.self_times(evs)}
    assert own == {"%while.1": 6.0, "%fusion.2": 3.0, "%fusion.3": 1.0,
                   "%copy.4": 1.0}
    tr = T.Trace(devices={"d": evs})
    assert dict(T.top_ops(tr, (0.0, 12.0))) == {"while": 6.0, "fusion": 4.0,
                                                "copy": 1.0}


def test_interval_arithmetic():
    assert T.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert T.intersect([(0, 2), (3, 5)], [(1, 4)]) == [(1, 2), (3, 4)]
    assert T.subtract([(0, 10)], [(1, 2), (5, 6)]) == [(0, 1), (2, 5),
                                                       (6, 10)]
    assert T.clip([(0, 3), (8, 12)], (1, 10)) == [(1, 3), (8, 10)]


def test_busy_steps_collectives_and_idle_attribution():
    tr = hand_made()
    win = tr.span("bench.window")
    # device 0 busy 1-4 and 6-7 (4 s), device 1 busy 1-4 and 6-8 (5 s)
    assert T.busy(tr, [win]) == pytest.approx(4.5)
    steps = T.step_intervals(tr)
    assert steps == [(1.0, 5.0), (6.0, 8.0)]
    assert T.busy(tr, steps) == pytest.approx(4.5)
    # the all-reduce lies on device 0 only: 1 s, mean over two devices
    assert T.op_seconds(tr, steps, T.is_collective) == pytest.approx(0.5)
    idle = dict(T.idle_by_host(tr, win))
    assert sum(idle.values()) == pytest.approx(10 - 4.5)
    # device 0 idles 0-1 (input), 4-6 (step 4-5, input 5-6), 7-10 (step
    # 7-8, save 8-10); device 1 idles 0-1, 4-6 and 8-10
    assert idle["bench.input"] == pytest.approx(2.0)
    assert idle["bench.save"] == pytest.approx(2.0)
    assert idle["bench.train_span"] == pytest.approx(1.5)
    ops = dict(T.top_ops(tr, win))
    assert ops["fusion"] == pytest.approx((2 + 1 + 3 + 2) / 2)
    assert ops["all-reduce"] == pytest.approx(0.5)


def test_recorded_cpu_trace():
    # on the CPU the ops run on the PjRt client's thread of the host plane
    tr = T.load(str(CPU_TRACE), plane=re.compile(r"^/host:CPU$"),
                op_line="tf_XLAPjRtCpuClient")
    win = tr.span("bench.window")
    assert win is not None and len(tr.spans("bench.train_span")) == 3
    assert len(tr.spans("bench.save")) == 1
    names = {T.op_name(n) for n, _, _ in tr.devices["/host:CPU"]}
    assert "dot_general" in names
    busy = T.busy(tr, [win])
    assert 0 < busy < win[1] - win[0]
    idle = dict(T.idle_by_host(tr, win))
    assert sum(idle.values()) == pytest.approx(win[1] - win[0] - busy)
    # the sleeps inside the batch and save spans are idle device time
    assert idle["bench.input"] > 0.003 and idle["bench.save"] > 0.003
    assert 0 < T.busy(tr, T.step_intervals(tr)) <= busy
