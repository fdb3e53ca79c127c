"""The benchmark's harness at a toy size on the CPU: a BENCHMARK.json with
the test configurations' cells added, and a run that skips the look for a
chip."""
import copy
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
sys.path.insert(0, str(CHIP.parents[1] / "src"))
sys.path.insert(0, str(CHIP.parent))

from chip import harness  # noqa: E402

TINY = {"olmo1b-6l": "tiny"}


def toy(cell: str) -> str:
    config, mix = cell.split(".", 1)
    return f"{TINY[config]}.{mix}"


def tiny_bench() -> dict:
    """The real BENCHMARK.json plus a toy copy of each cell."""
    bench = harness.load_benchmark()
    out = copy.deepcopy(bench)
    for name in TINY.values():
        out["configs"].append({"name": name,
                               "file": str(HERE / f"{name}.json")})
    for w in bench["workloads"]:
        out["workloads"].append(dict(w, name=toy(w["name"]),
                                     config=TINY[w["config"]]))
    for m in out["end_to_end"] + out["per_layer"]:
        if "workloads" in m:
            m["workloads"] = m["workloads"] + [toy(w) for w in m["workloads"]]
    return out


def run_tiny(cell: str, seed: int = 2**31 + 77, seconds: float = 0.5,
             trace: bool = False, **traffic) -> dict:
    c = harness.resolve(cell, tiny_bench())
    c.traffic.update(traffic)
    return harness.run(c, seed, seconds, trace, time.perf_counter(),
                       require_tpu=False)
