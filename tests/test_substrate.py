"""The Substrate API: protocol conformance, the shared recovery driver on
both substrates, torn-save safety, and the loss-curve-continuity capstone.

Tier-1 tests exercise the simulated substrate (seconds); the real-process
tests (subprocess ranks, SIGKILL faults) are marked ``slow`` and run in CI's
full pass.
"""
import json

import pytest

from repro.report import REQUIRED_KEYS, strip_volatile, validate
from repro.substrate import (FaultNotice, StepSlice, Substrate,
                             build_substrate)
from repro.substrate.driver import (DriveConfig, KillSpec, StallSpec,
                                    run_protected)

SIM_KW = dict(n_nodes=4, n_spares=4)
KILLS = (KillSpec(13, 1), KillSpec(27, 2))
CFG = dict(total_steps=40, ckpt_every=10, seed=0)


def drive_sim(kills=(), scenario="t", stalls=(), **over):
    sub = build_substrate("sim", **SIM_KW)
    try:
        return run_protected(
            sub, DriveConfig(scenario=scenario, **dict(CFG, **over)),
            kills, stalls)
    finally:
        sub.close()


# --------------------------------------------------------------------------- #
# protocol surface
# --------------------------------------------------------------------------- #
def test_sim_substrate_satisfies_protocol():
    sub = build_substrate("sim", **SIM_KW)
    try:
        assert isinstance(sub, Substrate)
    finally:
        sub.close()


def test_process_substrate_class_has_protocol_surface():
    # structural check without spawning processes
    from repro.substrate.process import ProcessSubstrate
    for name in ("start_ranks", "health", "kill", "save_via_tce",
                 "restore_via_tce", "step_metrics", "close"):
        assert callable(getattr(ProcessSubstrate, name)), name


def test_process_ranks_always_run_on_cpu(monkeypatch):
    # an accelerator platform inherited from the parent must not reach the
    # rank workers: N ranks cannot share one chip
    from repro.substrate.process import _worker_env
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    assert _worker_env()["JAX_PLATFORMS"] == "cpu"


def test_build_substrate_rejects_unknown_mode():
    with pytest.raises(ValueError, match="unknown substrate mode"):
        build_substrate("quantum")


def test_driver_has_no_isinstance_dispatch():
    # the design guarantee: everything proven on the simulated substrate
    # holds for real processes because the driver cannot tell them apart
    import inspect

    import repro.substrate.driver as driver
    src = inspect.getsource(driver)
    assert "isinstance(" not in src


def test_kill_spec_parsing():
    assert KillSpec.parse("13:1") == KillSpec(13, 1, "node_hw")
    assert KillSpec.parse("9:0:network") == KillSpec(9, 0, "network")
    assert KillSpec.parse_list("") == ()
    assert KillSpec.parse_list("9:1, 17:0:gpu_xid") == (
        KillSpec(9, 1), KillSpec(17, 0, "gpu_xid"))
    with pytest.raises(ValueError):
        KillSpec.parse("13")
    with pytest.raises(ValueError):
        KillSpec.parse("a:b")


def test_stall_spec_parsing():
    assert StallSpec.parse("9:1") == StallSpec(9, 1, 1.5)
    assert StallSpec.parse("9:1:2.5") == StallSpec(9, 1, 2.5)
    assert StallSpec.parse_list("") == ()
    assert StallSpec.parse_list("9:1, 17:0:0.5") == (
        StallSpec(9, 1), StallSpec(17, 0, 0.5))
    with pytest.raises(ValueError):
        StallSpec.parse("9")
    with pytest.raises(ValueError):
        StallSpec.parse("9:1:2.5:x")


def test_sim_stall_surfaces_in_rank_walls_and_attribution():
    # a scripted stall on the simulated substrate must not fault the slice,
    # but the stalled rank's modelled wall time — and the streaming TEE's
    # slow-rank attribution — must name it
    rep = drive_sim(stalls=(StallSpec(13, 2, 30.0),), scenario="stall_sim")
    assert rep["completed"]
    assert rep["restarts"] == {"inplace": 0, "resched": 0}
    assert rep["stalls"] == [{"step": 13, "rank": 2, "seconds": 30.0}]
    att = rep["measured"]["stall_attribution"]
    assert len(att) == 1
    assert att[0]["slowest_rank"] == 2
    assert att[0]["slowdown"] > 1.3
    assert att[0]["anomalous"]
    assert 2 in att[0]["attributed_ranks"]
    assert 0.0 < att[0]["confidence"] <= 1.0


# --------------------------------------------------------------------------- #
# the shared driver on the simulated substrate (tier-1)
# --------------------------------------------------------------------------- #
def test_sim_kill_and_recover_completes():
    rep = drive_sim(KILLS)
    assert rep["completed"]
    assert rep["steps_done"] == 40
    assert rep["restarts"] == {"inplace": 0, "resched": 2}
    assert len(rep["evicted_nodes"]) == 2
    assert rep["decisions"]["by_decision"] == {"claim_spare": 2}
    assert rep["lost_steps"] > 0
    # the FSM walked the full recovery cycle twice
    states = [s for _, s, _ in rep["state_history"]]
    assert states.count("checking") == 2
    assert states.count("rescheduling") == 2
    assert states[-1] == "done"


def test_sim_loss_curve_continuity():
    # rewind-and-replay must regrow the curve exactly: the merged curve of
    # a twice-killed run equals the uninterrupted run's, step for step
    faulty = drive_sim(KILLS, scenario="a")
    clean = drive_sim((), scenario="a")
    assert [e[0] for e in faulty["losses"]] == list(range(1, 41))
    assert faulty["losses"] == clean["losses"]
    assert faulty["final_loss"] == clean["final_loss"]
    # but the fault run paid for it in modelled downtime
    assert faulty["modeled"]["downtime_s"] > 0
    assert clean["modeled"]["downtime_s"] == 0


def test_sim_driver_report_schema_and_determinism():
    a, b = drive_sim(KILLS, scenario="det"), drive_sim(KILLS, scenario="det")
    assert validate(a) == []
    for key in REQUIRED_KEYS:
        assert key in a, key
    assert a["engine"] == "substrate"
    # identical runs produce identical reports (modulo measured wall time)
    sa = json.dumps(strip_volatile(a), sort_keys=True, default=str)
    sb = json.dumps(strip_volatile(b), sort_keys=True, default=str)
    assert sa == sb
    assert a["timeline_digest"] == b["timeline_digest"]


def test_sim_gives_up_when_spares_exhausted():
    sub = build_substrate("sim", n_nodes=4, n_spares=0)
    try:
        rep = run_protected(
            sub, DriveConfig(total_steps=40, ckpt_every=10, scenario="g"),
            (KillSpec(13, 1),))
    finally:
        sub.close()
    assert not rep["completed"]
    assert rep["decisions"]["by_decision"].get("give_up", 0) >= 1
    assert [s for _, s, _ in rep["state_history"]][-1] == "failed"


def test_sim_restart_budget_enforced():
    kills = tuple(KillSpec(5 + 2 * i, i % 2) for i in range(4))
    rep = drive_sim(kills, max_restarts=2, scenario="budget")
    assert not rep["completed"]
    total = rep["restarts"]["inplace"] + rep["restarts"]["resched"]
    assert total == 2


def test_sim_kill_fires_once_across_replay():
    # a kill scripted at step 13 must not re-fire when replay passes 13
    rep = drive_sim((KillSpec(13, 1),), scenario="once")
    assert rep["completed"]
    assert rep["restarts"]["resched"] == 1
    assert len(rep["kills"]) == 1


def test_step_slice_ok_property():
    assert StepSlice(5).ok
    assert not StepSlice(5, fault=FaultNotice(5, (1,))).ok


# --------------------------------------------------------------------------- #
# real processes (slow: subprocess ranks, SIGKILL faults)
# --------------------------------------------------------------------------- #
PROC_KW = dict(n_ranks=2, n_spares=2, seed=0, total_steps=24,
               batch=2, seq=16, lr=3e-4)
PROC_CFG = dict(total_steps=24, ckpt_every=6, seed=0)
PROC_KILLS = (KillSpec(9, 1), KillSpec(17, 0))


def drive_proc(kills=(), scenario="p", **kw):
    sub = build_substrate("process", **dict(PROC_KW, **kw))
    try:
        return run_protected(
            sub, DriveConfig(scenario=scenario, **PROC_CFG), kills)
    finally:
        sub.close()


@pytest.mark.slow
def test_process_trains_through_two_sigkills_with_loss_continuity():
    # the capstone: a tiny-but-real model trains to completion through two
    # injected rank kills and the loss curve is bit-identical to an
    # uninterrupted run's (deterministic CPU replay from real checkpoints)
    faulty = drive_proc(PROC_KILLS, scenario="cap")
    clean = drive_proc((), scenario="cap")
    assert faulty["completed"] and clean["completed"]
    assert faulty["restarts"]["resched"] == 2
    assert [e[0] for e in faulty["losses"]] == list(range(1, 25))
    assert faulty["losses"] == clean["losses"]
    assert faulty["final_loss"] == clean["final_loss"]
    # pinned: llama3-8b reduced, 1 layer, batch=2 seq=16, seed 0, 24 steps
    assert faulty["final_loss"] == pytest.approx(clean["final_loss"],
                                                 abs=0.0)
    assert faulty["final_loss"] == pytest.approx(5.8429465, abs=1e-3)


@pytest.mark.slow
def test_same_fault_sequence_same_decisions_on_both_substrates():
    # the api_redesign invariant: the recovery driver cannot tell the
    # substrates apart, so the same fault schedule yields the same planner
    # decision kinds whether the ranks are modelled or real processes
    sim = drive_sim(PROC_KILLS, scenario="eq",
                    total_steps=24, ckpt_every=6)
    proc = drive_proc(PROC_KILLS, scenario="eq")
    sim_kinds = [e["decision"] for e in sim["decisions"]["log"]]
    proc_kinds = [e["decision"] for e in proc["decisions"]["log"]]
    assert sim_kinds == proc_kinds == ["claim_spare", "claim_spare"]
    assert sim["restarts"] == proc["restarts"]
    assert ([s for _, s, _ in sim["state_history"]]
            == [s for _, s, _ in proc["state_history"]])


@pytest.mark.slow
def test_process_stall_injection_attributes_slow_rank():
    # a rank SIGSTOPped mid-step must not fault the run, but its measured
    # wall time has to dominate and the streaming TEE has to name it.
    # 4 ranks, not 2: slow-rank attribution is consensus-based and needs a
    # majority of healthy ranks to define "normal"
    sub = build_substrate("process", **dict(PROC_KW, n_ranks=4, n_spares=0))
    try:
        rep = run_protected(
            sub, DriveConfig(scenario="stall_proc", **PROC_CFG),
            stalls=(StallSpec(9, 1, 2.0),))
    finally:
        sub.close()
    assert rep["completed"]
    assert rep["restarts"] == {"inplace": 0, "resched": 0}
    assert rep["stalls"] == [{"step": 9, "rank": 1, "seconds": 2.0}]
    att = rep["measured"]["stall_attribution"]
    assert len(att) == 1
    assert att[0]["stalled_ranks"] == [1]
    # the SIGSTOPped rank's real wall time dominates the gang's
    assert att[0]["slowest_rank"] == 1
    assert att[0]["slowdown"] > 1.3
    assert att[0]["anomalous"]
    assert 1 in att[0]["attributed_ranks"]
    assert 0.0 < att[0]["confidence"] <= 1.0


@pytest.mark.slow
def test_process_killed_mid_save_never_torn():
    from repro.substrate.process import ProcessSubstrate
    sub = ProcessSubstrate(**PROC_KW)
    try:
        sub.start_ranks()
        assert sub.step_metrics(6).ok
        assert sub.save_via_tce(6)
        assert sub.store.latest_step() == 6
        # rank 0 SIGKILLs itself after its shard write but before the
        # controller can see all acks: the manifest must never commit
        sub.schedule_save_death(0, 12, "after_write")
        assert sub.step_metrics(12).ok
        assert not sub.save_via_tce(12)
        assert sub.store.latest_step() == 6      # torn step invisible
        # recovery: respawn the dead rank, restore, replay
        sl = sub.step_metrics(12)
        assert not sl.ok and sl.fault.dead_ranks == (0,)
        sub.start_ranks()
        assert sub.restore_via_tce() == 6
        assert sub.step_metrics(12).ok
        # bit-exact restore: replicated ranks agree leaf for leaf
        digs = sub.digests()
        assert len(digs) == 2 and digs[0] == digs[1]
        # and the retried save of the same step commits cleanly
        assert sub.save_via_tce(12)
        assert sub.store.latest_step() == 12
    finally:
        sub.close()
