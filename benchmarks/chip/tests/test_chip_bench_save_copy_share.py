"""``save_copy_share``: the share of the bytes the window's saves pulled
from the device that the host cache then copied again. On hand-made
counts, where the program counts no copies (it reads nothing), and in a
tiny traced run: 100% on the CPU backend, whose leaves the cache copies,
and 0% where the leaves are reported off the CPU, so the cache adopts the
device-to-host buffers."""
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
HERE = CHIP / "tests"
sys.path.insert(0, str(CHIP.parents[1] / "src"))
sys.path.insert(0, str(CHIP.parent))
sys.path.insert(0, str(HERE))

from chip import harness  # noqa: E402
from chip.program import Program, Span  # noqa: E402


def read(run):
    return harness.load_reader("save_copy_share")(run)


def counted(*counts):
    """A run whose trace holds the counts (counter, n), one save each."""
    spans = [Span("transom.count", float(t), float(t), 1,
                  {"counter": name, "n": n})
             for t, (name, n) in enumerate(counts)]
    return {"program_trace": Program(window=(0.0, 20.0), spans=[
        Span("transom.save.d2h", 0.0, 1.0, 1)], counts=spans)}


@pytest.mark.parametrize("counts,share", [
    ([("tce.save.d2h_bytes", 2e9), ("tce.save.copied_bytes", 0),
      ("tce.save.adopted_bytes", 2e9)], 0.0),
    ([("tce.save.d2h_bytes", 2e9), ("tce.save.copied_bytes", 2e9),
      ("tce.save.d2h_bytes", 2e9), ("tce.save.copied_bytes", 0)], 50.0),
    ([("tce.save.d2h_bytes", 2e9), ("tce.save.copied_bytes", 2e9)], 100.0),
    # a program that does not count its copies, as before adoption
    ([("tce.save.d2h_bytes", 2e9)], None),
    ([("tce.save.copied_bytes", 0)], None),            # nothing from the device
])
def test_share_of_the_counts(counts, share):
    got = read(counted(*counts))
    assert got == (None if share is None else pytest.approx(share))


def test_nothing_without_the_programs_events():
    assert read({"program_trace": None}) is None


@pytest.mark.parametrize("off_cpu,share", [(False, 100.0), (True, 0.0)])
def test_a_tiny_traced_run(monkeypatch, off_cpu, share):
    import chip_bench_tiny as tiny
    from repro.core.tce import engine
    if off_cpu:
        monkeypatch.setattr(engine, "_platform", lambda arr: "tpu")
    out = tiny.run_tiny("tiny.train_ckpt", trace=True, save_every=2)
    assert out["correct"], out["checks"]
    assert out["metrics"]["save_copy_share"]["value"] == pytest.approx(share)
