"""Rank worker: one real JAX training process under ProcessSubstrate.

    python -m repro.substrate.worker --spec '<json>'

Spawned by :class:`repro.substrate.process.ProcessSubstrate`, one process
per rank, ``JAX_PLATFORMS=cpu``. Speaks a JSON-lines command protocol on
stdin/stdout (stdout is re-pointed at startup so stray library prints land
on stderr, never inside the protocol stream):

    {"cmd": "step", "upto": N}          -> {"ok":1,"step":N,"losses":[[s,l],..],
                                            "wall_s": W}
    {"cmd": "save", "step": S}          -> {"ok":1,"stored":B,"full":K,"refs":R}
    {"cmd": "restore", "step": S|null}  -> {"ok":1,"step":S}
    {"cmd": "digest"}                   -> {"ok":1,"step":s,"leaves":{path:crc}}
    {"cmd": "ping"}                     -> {"ok":1}
    {"cmd": "exit"}                     -> {"ok":1} then exits

Training is **replicated deterministic data-parallel**: every rank computes
the identical full-batch update from the same seed (CPU JAX is
deterministic), so ranks hold bit-identical state without collectives and
any survivor's metrics stand for the job's. Each rank persists only its
``shard_state(flat, n_ranks)[rank]`` slice through the real TCE
``DiskStore`` datapath (streaming-crc digests, changed-leaves-only delta
refs, optional codecs); the *controller* commits the manifest only after
every rank acked its shard write, so a rank SIGKILLed mid-save can never
produce a torn (partially visible) checkpoint.

``save`` accepts ``die_at`` ("before_write" / "after_write") so tests can
inject a kill at the worst moments of the save path.

On every restore the delta-tracking map is cleared: after a rewind the same
step number may be written again, and a delta ref into the aborted write
would be self-referential.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import signal
import sys
import time


def _hijack_stdout():
    """Reserve real stdout for the protocol; stray prints go to stderr."""
    proto = os.fdopen(os.dup(1), "w", buffering=1)
    os.dup2(2, 1)
    sys.stdout = sys.stderr
    return proto


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--spec", required=True, help="JSON worker spec")
    args = ap.parse_args()
    spec = json.loads(args.spec)

    proto = _hijack_stdout()
    os.environ["JAX_PLATFORMS"] = "cpu"   # one chip cannot host N ranks

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core.tce import DiskStore
    from repro.core.tce.engine import flatten_pytree, unflatten_like
    from repro.core.tce.fastcopy import crc32_stream
    from repro.core.tce.sharding import shard_state
    from repro.data import SyntheticLMData
    from repro.launch.train import make_batch
    from repro.train import (AdamConfig, TrainConfig, init_train_state,
                             make_train_step)

    rank = int(spec["rank"])
    n_ranks = int(spec["n_ranks"])
    seed = int(spec.get("seed", 0))
    total_steps = int(spec.get("total_steps", 100))
    batch, seq = int(spec.get("batch", 4)), int(spec.get("seq", 32))
    codec = spec.get("codec", "raw")
    delta = bool(spec.get("delta", True))
    # glob patterns, same defaults as TCEConfig.lossless_paths (plus the
    # rng key, which must survive any lossy codec bit-exactly)
    lossless = tuple(spec.get("lossless_paths",
                              ("*opt*", "*adam*", "*mu*", "*nu*", "*step*",
                               "*scale*", "*rng*")))

    cfg = get_config(spec.get("arch", "llama3-8b")).reduced()
    if spec.get("layers"):
        cfg = dataclasses.replace(cfg, n_layers=int(spec["layers"]))
    opt_cfg = AdamConfig(lr=float(spec.get("lr", 3e-4)),
                         warmup_steps=max(total_steps // 10, 1),
                         decay_steps=total_steps)
    store = DiskStore(spec["ckpt_dir"])
    data = SyntheticLMData(cfg.vocab_size, seq, batch, seed)
    step_fn = jax.jit(make_train_step(cfg, opt_cfg, TrainConfig()),
                      donate_argnums=(0,))

    def fresh_state():
        return init_train_state(cfg, opt_cfg, jax.random.key(seed))

    state = fresh_state()
    step = 0
    # delta bookkeeping: leaf path -> (content crc, step whose rank dir
    # holds the actual bytes). Cleared on every restore (see module doc).
    digest_home: dict = {}

    def flat_np():
        return {k: np.asarray(v) for k, v in flatten_pytree(state).items()}

    def handle_step(cmd: dict) -> dict:
        nonlocal state, step
        upto = int(cmd["upto"])
        losses = []
        # wall time runs from the controller's dispatch timestamp (same
        # host, shared wall clock): time this rank spends SIGSTOPped by the
        # controller's stall injection — even frozen before it read the
        # command — counts, so a stalled rank reads as genuinely slow
        t_sent = cmd.get("t_sent")
        wall0 = time.perf_counter()
        while step < upto:
            state, metrics = step_fn(state, make_batch(cfg, data, step))
            step += 1
            losses.append([step, float(metrics["loss"])])
        wall = (time.time() - t_sent if t_sent is not None
                else time.perf_counter() - wall0)
        return {"ok": 1, "step": step, "losses": losses,
                "wall_s": round(wall, 6)}

    def handle_save(cmd: dict) -> dict:
        nonlocal digest_home
        s = int(cmd["step"])
        die_at = cmd.get("die_at")
        if die_at == "before_write":
            os.kill(os.getpid(), signal.SIGKILL)
        shards = shard_state(flat_np(), n_ranks)[rank]
        digests = {p: crc32_stream(d) for p, (_sp, d) in shards.items()}
        refs = {}
        if delta:
            for p, dig in digests.items():
                home = digest_home.get(p)
                if home is not None and home[0] == dig:
                    refs[p] = (home[1], dig)
        stored = store.write_rank(s, rank, shards, refs=refs,
                                  digests=digests, codec=codec,
                                  lossless_paths=lossless)
        for p, dig in digests.items():
            if p not in refs:
                digest_home[p] = (dig, s)
        if die_at == "after_write":
            os.kill(os.getpid(), signal.SIGKILL)
        return {"ok": 1, "stored": int(stored),
                "full": len(shards) - len(refs), "refs": len(refs)}

    def handle_restore(cmd: dict) -> dict:
        nonlocal state, step, digest_home
        digest_home = {}
        ck = cmd.get("step")
        if ck is None:
            state = fresh_state()
            step = 0
            return {"ok": 1, "step": 0}
        ck = int(ck)
        from repro.core.tce.sharding import unshard_state
        flat = unshard_state(store.read_all(ck))
        state = unflatten_like(state, flat)
        step = ck
        return {"ok": 1, "step": ck}

    def handle_digest(_cmd: dict) -> dict:
        return {"ok": 1, "step": step,
                "leaves": {p: crc32_stream(a) for p, a in flat_np().items()}}

    handlers = {"step": handle_step, "save": handle_save,
                "restore": handle_restore, "digest": handle_digest,
                "ping": lambda c: {"ok": 1}}

    proto.write(json.dumps({"ready": 1, "rank": rank,
                            "pid": os.getpid()}) + "\n")
    proto.flush()
    for line in sys.stdin:
        line = line.strip()
        if not line:
            continue
        cmd = json.loads(line)
        if cmd.get("cmd") == "exit":
            proto.write(json.dumps({"ok": 1}) + "\n")
            proto.flush()
            break
        try:
            resp = handlers[cmd["cmd"]](cmd)
        except Exception as e:  # report, don't die: the controller decides
            resp = {"ok": 0, "error": f"{type(e).__name__}: {e}"}
        proto.write(json.dumps(resp) + "\n")
        proto.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
