"""The reduction from a profiler trace to busy time, op time and idle gaps,
on hand-made events and on a small trace recorded on the CPU."""
import re
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP.parent))

from chip import harness  # noqa: E402
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


def test_collectives_hidden_and_exposed():
    # one device, two steps, its ops one after another inside a loop's
    # event (which holds ops, so does not count as running). Step 1: an
    # async all-gather, start 1.0 to done 3.0, with a fusion between, then
    # a TPU async-collective fusion pair, 3.0 to 3.5, with a fusion
    # between: only their start and done ops (0.4 s) are exposed. Step 2:
    # a reduce-scatter, start 5.8 to done 7.0, with a fusion 5.9-6.0, a
    # synchronous all-reduce 6.0-6.5 and an idle gap 6.5-6.6 inside it,
    # then a synchronous collective-permute 7.0-7.2: 1.3 s exposed, of
    # which the ops themselves run 1.2 s.
    tr = T.Trace()
    tr.devices["/device:TPU:0"] = [
        ("%while.1 = (s32[]) while(s32[] %p)", 0.2, 7.5),
        ("%fusion.1 = f32[8] fusion(f32[8] %a)", 0.5, 1.0),
        ("%all-gather-start.3 = (f32[4], f32[8]) all-gather-start(f32[4] "
         "%x)", 1.0, 1.1),
        ("%fusion.3 = f32[8] fusion(f32[8] %b)", 1.1, 2.9),
        ("%all-gather-done.3 = f32[8] all-gather-done((f32[4], f32[8]) "
         "%all-gather-start.3)", 2.9, 3.0),
        ("%async-collective-start.2 = (f32[4], f32[8]) fusion(f32[4] %w), "
         "kind=kCustom", 3.0, 3.1),
        ("%fusion.5 = f32[8] fusion(f32[8] %d)", 3.1, 3.4),
        ("%async-collective-done.2 = f32[8] fusion(f32[4] %get-tuple-"
         "element.1, f32[8] %get-tuple-element.2), kind=kCustom", 3.4, 3.5),
        ("%fusion.2 = f32[8] fusion(f32[8] %all-gather-done.3)", 5.5, 5.8),
        ("%reduce-scatter-start.1 = (f32[8], f32[2]) "
         "reduce-scatter-start(f32[8] %y)", 5.8, 5.9),
        ("%fusion.4 = f32[8] fusion(f32[8] %c)", 5.9, 6.0),
        ("%all-reduce.7 = f32[8] all-reduce(f32[8] %z)", 6.0, 6.5),
        ("%reduce-scatter-done.1 = f32[2] reduce-scatter-done((f32[8], "
         "f32[2]) %reduce-scatter-start.1)", 6.6, 7.0),
        ("%collective-permute.2 = f32[2] collective-permute(f32[2] %r)",
         7.0, 7.2)]
    tr.host = [("bench.window", 0.0, 10.0), ("bench.train_span", 0.0, 4.0),
               ("bench.train_span", 5.0, 8.0)]
    assert T.collective_intervals(tr.devices["/device:TPU:0"]) == \
        pytest.approx([(1.0, 3.0), (3.0, 3.5), (6.0, 6.5), (5.8, 7.0),
                       (7.0, 7.2)])
    steps = T.step_intervals(tr)
    assert T.collective_op_seconds(tr, steps) == pytest.approx(1.6)
    assert T.collective_exposed_seconds(tr, steps) == pytest.approx(1.7)
    run = {"trace": tr, "steps": [{}, {}]}
    want = {"collective_ms": 800, "collective_ms.all_reduce": 250,
            "collective_ms.async": 100, "collective_ms.permute": 100,
            "collective_exposed_ms": 850}
    assert {k: harness.load_reader(k)(run) for k in want} == \
        pytest.approx(want)
    # a second device with no collective halves the means
    tr.devices["/device:TPU:1"] = [("%fusion.1 = f32[8] fusion()", 0.5, 3.5)]
    assert {k: harness.load_reader(k)(run) for k in want} == \
        pytest.approx({k: v / 2 for k, v in want.items()})
    # no collective anywhere, or none of a kind: nothing to read
    one = T.Trace(devices={"d": [("fusion.1", 0.5, 3.5)]}, host=tr.host)
    for k in want:
        assert harness.load_reader(k)({"trace": one, "steps": [{}]}) is None
    del tr.devices["/device:TPU:0"][-1]
    assert harness.load_reader("collective_ms.permute")(run) is None
