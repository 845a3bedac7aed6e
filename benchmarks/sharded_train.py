"""Sharded-train A/B: replicated vs ZeRO-sharded weight update, same round,
plus the kill-and-resume arm (elastic gang recovery vs uninterrupted).

Two arms train the SAME MLP for the same optimizer steps over the same
seeded :class:`~synapseml_tpu.data.DataLoader` stream, each in a FRESH
subprocess forced onto a multi-device CPU mesh (4 virtual devices — the
deploy-coldstart fresh-arm discipline, so neither arm inherits the other's
compile cache and the parent backend's device count doesn't matter):

  (a) replicated — the status-quo trainer: optimizer state replicated on
      every data-parallel replica;
  (b) zero       — ``TrainerConfig(partition_rules=..., zero_shard=True)``:
      the optimizer state partitions over the ``('data','fsdp')`` replica
      group inside the one jitted step (arXiv:2004.13336).

Reports per arm: per-replica and total optimizer-state bytes (measured
from the live shardings), warm per-step wall time, final loss; plus the
cross-arm bars — per-replica opt-state bytes <= replicated/dp + epsilon,
step-time ratio >= 0.9x, final-loss delta 0.0 and final-params max abs
diff at f32.

The ELASTIC section (same round, CPU A/B): an uninterrupted 2-worker gang
run vs a 2-worker gang SIGKILLed at one member mid-run and resumed on the
survivor (N=2→M=1 elastic resume from the last committed coordinated
checkpoint). Reports **recovery seconds** (survivor relaunch → first
post-resume optimizer step, restore + re-rendezvous + compile included)
and **goodput** (useful steps / total wall-clock including the lost work
and the second launch) as a ratio against the uninterrupted arm. A CPU
A/B (the arms name ``JAX_PLATFORMS=cpu``); on chips: not measured. Prints
one JSON line.
"""
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import textwrap
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).parent))
sys.path.insert(0, str(Path(__file__).parent.parent))

DEVICES = 4
D_IN = 64
HIDDEN = 512
BATCH = 256
STEPS = 40
WARM_SKIP = 4  # steps excluded from the warm per-step wall (compiles)
EPS_BYTES = 8192  # unshardable leaves: count scalar + small bias moments

GANG_STEPS = 40
GANG_STEP_MS = 60.0       # per-step floor so the kill lands mid-run
GANG_CHECKPOINT_EVERY = 5
GANG_KILL_AFTER_STEP = 15  # SIGKILL once this step's commit lands


def _arm_main(arm: str, out_path: str) -> None:
    """Runs inside the fresh subprocess: train one arm, dump the record +
    final params."""
    import jax

    jax.config.update("jax_platforms", "cpu")

    import flax.linen as nn

    from synapseml_tpu.data import DataLoader
    from synapseml_tpu.data.source import MemorySource
    from synapseml_tpu.models.trainer import Trainer, TrainerConfig
    from synapseml_tpu.parallel import partition as pp
    from synapseml_tpu.parallel.mesh import MeshConfig, create_mesh

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            h = nn.relu(nn.Dense(HIDDEN)(x))
            h = nn.relu(nn.Dense(HIDDEN)(h))
            return nn.Dense(2)(h)

    rs = np.random.default_rng(0)
    X = rs.normal(size=(4096, D_IN)).astype(np.float32)
    data = {"x": X, "labels": (X[:, 0] > 0).astype(np.int32)}

    mesh = create_mesh(MeshConfig(data=-1))
    dp = mesh.data_parallel_size()
    cfg = TrainerConfig(total_steps=STEPS, learning_rate=1e-2)
    if arm == "zero":
        cfg.partition_rules = pp.PartitionRules(
            zero_axes=("data", "fsdp"), mesh=mesh.config)
        cfg.zero_shard = True
    trainer = Trainer(MLP(), mesh, cfg)
    loader = DataLoader(MemorySource(data), BATCH, seed=13, multiple_of=dp)
    it = iter(loader)
    first = next(it)
    state = trainer.init_state(first, jax.random.PRNGKey(3))

    losses: list = []
    step_walls: list = []
    t_prev = [time.perf_counter()]

    def cb(i, metrics):
        losses.append(float(metrics["loss"]))
        now = time.perf_counter()
        step_walls.append(now - t_prev[0])
        t_prev[0] = now

    def chain():
        yield first
        yield from it

    t0 = time.perf_counter()
    state = trainer.fit(state, chain(), max_steps=STEPS, callback=cb)
    wall = time.perf_counter() - t0
    loader.close()

    host_params = jax.tree.map(lambda x: np.asarray(x, np.float32),
                               state.params)
    np.savez(out_path + ".params.npz",
             **{str(i): leaf for i, leaf in
                enumerate(jax.tree.leaves(host_params))})
    record = {
        "arm": arm, "dp": dp, "steps": int(state.step),
        "final_loss": losses[-1],
        "wall_s": round(wall, 3),
        "warm_step_ms": round(
            1e3 * float(np.mean(step_walls[WARM_SKIP:])), 3),
        "opt_bytes_total": pp.total_bytes(state.opt_state),
        "opt_bytes_per_replica": pp.per_device_bytes(state.opt_state),
        "param_bytes_total": pp.total_bytes(state.params),
    }
    with open(out_path, "w") as f:
        json.dump(record, f)


def _run_arm(arm: str, tmp: str) -> dict:
    out_path = os.path.join(tmp, f"{arm}.json")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={DEVICES}"
                        ).strip()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--arm", arm, out_path],
        env=env, capture_output=True, text=True, timeout=240)
    if proc.returncode != 0:
        raise RuntimeError(f"{arm} arm failed:\n{proc.stdout}\n{proc.stderr}")
    with open(out_path) as f:
        record = json.load(f)
    params = np.load(out_path + ".params.npz")
    record["_params"] = [params[k] for k in sorted(params, key=int)]
    return record


GANG_WORKER = textwrap.dedent("""
    import json, sys, time

    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np
    import flax.linen as nn

    from synapseml_tpu.parallel.gang import run_gang_member
    from synapseml_tpu.models.trainer import Trainer, TrainerConfig
    from synapseml_tpu.parallel.mesh import MeshConfig, create_mesh
    from synapseml_tpu.data.source import MemorySource

    addr, part = sys.argv[1], int(sys.argv[2])
    ckdir, logp = sys.argv[3], sys.argv[4]
    total_steps, step_ms = int(sys.argv[5]), float(sys.argv[6])
    checkpoint_every = int(sys.argv[7])

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(2)(nn.relu(nn.Dense(64)(x)))

    rs = np.random.default_rng(7)
    X = rs.normal(size=(2048, 8)).astype(np.float32)
    src = MemorySource({"x": X, "labels": (X[:, 0] > 0).astype(np.int32)},
                       shard_rows=64)
    log = open(logp, "a")

    def trainer_fn(info):
        mesh = create_mesh(MeshConfig(data=1))
        return Trainer(MLP(), mesh, TrainerConfig(
            total_steps=total_steps, learning_rate=1e-2))

    def cb(i, metrics):
        log.write(json.dumps({"t": time.time(),
                              "loss": float(metrics["loss"])}) + "\\n")
        log.flush()
        if step_ms:
            time.sleep(step_ms / 1000.0)

    code = run_gang_member(addr, part, trainer_fn=trainer_fn, source=src,
                           checkpoint_dir=ckdir, total_steps=total_steps,
                           batch_size=32, seed=3,
                           checkpoint_every=checkpoint_every, grace_s=60.0,
                           epochs=None, shuffle_rows="none", callback=cb)
    log.close()
    sys.exit(code)
""")


def _launch_gang(tmp, tag, world, ckdir, steps, step_ms):
    from synapseml_tpu.parallel.gang import launch_gang_processes

    script = os.path.join(tmp, "gang_worker.py")
    if not os.path.exists(script):
        with open(script, "w") as f:
            f.write(GANG_WORKER)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    repo_root = str(Path(__file__).resolve().parent.parent)
    env["PYTHONPATH"] = repo_root + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    logs = [os.path.join(tmp, f"gang_{tag}_{p}.jsonl") for p in range(world)]
    procs, coord, _ = launch_gang_processes(
        script, world, checkpoint_dir=ckdir,
        worker_args_fn=lambda p, addr: [
            addr, str(p), ckdir, logs[p], str(steps), str(step_ms),
            str(GANG_CHECKPOINT_EVERY)],
        env=env, coordinator_kw=dict(beat_timeout_s=90.0, grace_s=60.0,
                                     poll_s=0.05))
    return procs, coord, logs


def _finish_gang(procs, coord, timeout_s=200, wait_commit_step=None):
    from synapseml_tpu.parallel.gang import finish_gang_processes

    _, codes = finish_gang_processes(procs, coord, timeout_s=timeout_s,
                                     wait_commit_step=wait_commit_step)
    return codes


def _first_step_time(log_path):
    with open(log_path) as f:
        for line in f:
            return json.loads(line)["t"]
    return None


def _gang_elastic_section(tmp):
    """Same-round A/B: uninterrupted 2-worker gang vs killed-and-resumed.
    Useful steps = GANG_STEPS (the steps in the surviving lineage)."""
    from synapseml_tpu.parallel import checkpoint as cp
    from synapseml_tpu.parallel.gang import EXIT_RESIZE

    # arm U: uninterrupted
    ck_u = os.path.join(tmp, "ck_unint")
    os.makedirs(ck_u)
    t0 = time.perf_counter()
    procs, coord, _ = _launch_gang(tmp, "unint", 2, ck_u, GANG_STEPS,
                                   GANG_STEP_MS)
    codes_u = _finish_gang(procs, coord, wait_commit_step=GANG_STEPS)
    wall_u = time.perf_counter() - t0
    if codes_u != [0, 0]:
        raise RuntimeError(f"uninterrupted gang arm failed: {codes_u}")

    # arm E phase 1: 2 workers, SIGKILL rank 1 after the commit lands
    ck_e = os.path.join(tmp, "ck_elastic")
    os.makedirs(ck_e)
    t1 = time.perf_counter()
    procs, coord, _ = _launch_gang(tmp, "e1", 2, ck_e, GANG_STEPS,
                                   GANG_STEP_MS)
    committed = coord.wait_commit(step=GANG_KILL_AFTER_STEP, timeout_s=150)
    if committed is None:  # kill only AFTER a restorable point exists —
        # otherwise phase 2 fresh-starts from scratch and every elastic
        # bar (final_step, recovery, goodput) passes without a single
        # checkpoint ever restoring, masking commit-path regressions
        raise RuntimeError(
            f"no commit landed at step {GANG_KILL_AFTER_STEP} before kill")
    t_kill = time.perf_counter()
    procs[1].send_signal(signal.SIGKILL)
    codes_1 = _finish_gang(procs, coord)
    phase1_wall = time.perf_counter() - t1
    if codes_1[0] != EXIT_RESIZE or codes_1[1] != -signal.SIGKILL:
        raise RuntimeError(f"kill phase exits unexpected: {codes_1}")
    resume_step = cp.latest_verified_step(ck_e)
    if resume_step is None or resume_step < GANG_KILL_AFTER_STEP:
        raise RuntimeError(
            f"survivor has no restorable checkpoint >= "
            f"{GANG_KILL_AFTER_STEP} (latest verified: {resume_step}) — "
            "phase 2 would not be an elastic resume")

    # arm E phase 2: N=2 -> M=1 elastic resume on the survivor
    t2 = time.perf_counter()
    t2_epoch = time.time()
    procs, coord, logs = _launch_gang(tmp, "e2", 1, ck_e, GANG_STEPS,
                                      GANG_STEP_MS)
    codes_2 = _finish_gang(procs, coord, wait_commit_step=GANG_STEPS)
    phase2_wall = time.perf_counter() - t2
    if codes_2 != [0]:
        raise RuntimeError(f"resume phase failed: {codes_2}")
    first_step_t = _first_step_time(logs[0])
    recovery_s = (first_step_t - t2_epoch) if first_step_t else None

    goodput_unint = GANG_STEPS / wall_u
    goodput_elastic = GANG_STEPS / (phase1_wall + phase2_wall)
    final_step = cp.latest_verified_step(ck_e)
    # orig_world stays frozen at the FIRST launch's world across resumes —
    # a fresh start on the survivor would stamp 1, proving phase 2
    # restarted instead of resuming
    orig_world = cp.checkpoint_meta(ck_e).get("orig_world")
    bars = {
        "resumed_to_completion": final_step == GANG_STEPS
        and orig_world == 2,
        "recovery_under_60s": recovery_s is not None and recovery_s < 60.0,
        "goodput_ratio_ge_0p25": goodput_elastic / goodput_unint >= 0.25,
    }
    return {
        "committed_before_kill": committed,
        "resume_step": resume_step,
        "final_step": final_step,
        "orig_world": orig_world,
        "detect_plus_drain_s": round(phase1_wall
                                     - (t_kill - t1), 3),
        "recovery_s": round(recovery_s, 3) if recovery_s else None,
        "uninterrupted_wall_s": round(wall_u, 3),
        "elastic_wall_s": round(phase1_wall + phase2_wall, 3),
        "goodput_steps_per_s": {
            "uninterrupted": round(goodput_unint, 3),
            "elastic": round(goodput_elastic, 3)},
        "goodput_ratio": round(goodput_elastic / goodput_unint, 3),
        "bars": bars,
    }


def run(jax, platform, n_chips):
    tmp = tempfile.mkdtemp(prefix="synapseml_shardedtrain_")
    try:
        replicated = _run_arm("replicated", tmp)
        zero = _run_arm("zero", tmp)
        elastic = _gang_elastic_section(tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    param_diff = max(
        float(np.max(np.abs(a - b))) if a.size else 0.0
        for a, b in zip(replicated.pop("_params"), zero.pop("_params")))
    dp = zero["dp"]
    opt_ratio = (zero["opt_bytes_per_replica"]
                 / max(replicated["opt_bytes_per_replica"], 1))
    step_ratio = (replicated["warm_step_ms"]
                  / max(zero["warm_step_ms"], 1e-9))
    loss_delta = abs(replicated["final_loss"] - zero["final_loss"])
    bars = {
        "opt_bytes_bound": zero["opt_bytes_per_replica"]
        <= replicated["opt_bytes_per_replica"] / dp + EPS_BYTES,
        "step_time_ratio_ge_0p9": step_ratio >= 0.9,
        "loss_delta_zero": loss_delta <= 1e-5,
        "param_parity_f32": param_diff <= 5e-6,
    }
    bars.update({f"elastic_{k}": v for k, v in elastic["bars"].items()})
    return {
        "metric": "sharded-train ZeRO per-replica opt-state bytes ratio"
                  + ("" if platform == "tpu" else " (CPU A/B)"),
        "value": round(opt_ratio, 4), "unit": "x", "lower_is_better": True,
        "benchmark": "sharded_train", "platform": platform,
        "mode": "cpu_ab" if platform != "tpu" else "tpu_ab",
        "devices_per_arm": DEVICES, "dp": dp, "steps": STEPS,
        "replicated": replicated, "zero": zero,
        "opt_bytes_per_replica_ratio": round(opt_ratio, 4),
        "step_time_ratio": round(step_ratio, 3),
        "final_loss_delta": loss_delta,
        "param_max_abs_diff": param_diff,
        "elastic": elastic,
        "bars": bars, "all_bars_pass": all(bars.values()),
    }


def main():
    if len(sys.argv) >= 4 and sys.argv[1] == "--arm":
        _arm_main(sys.argv[2], sys.argv[3])
        return
    from benchmarks._common import init_jax

    jax, platform, n_chips = init_jax()
    print(json.dumps(run(jax, platform, n_chips)))


if __name__ == "__main__":
    main()
