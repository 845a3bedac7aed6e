"""Continual-training flywheel (ISSUE 14): request logging + scrubbing,
crash-safe supervised training, checkpoint verification, and the
fault-contained serve→log→retrain→canary loop. The chaos suite drives a
fault at every seam and asserts ``prod`` stays untouched."""

import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from synapseml_tpu.core.faults import FaultSpec, inject_faults
from synapseml_tpu.core.logging import scrub
from synapseml_tpu.core.params import ComplexParam
from synapseml_tpu.core.pipeline import Transformer
from synapseml_tpu.registry import Deployment, ModelRegistry

pytestmark = pytest.mark.continual

D_IN, N_CLASSES = 4, 3
_W_TRUE = np.random.default_rng(3).normal(size=(D_IN,))


# ---------------------------------------------------------------------------
# shared model bits (module-level so worker subprocesses can unpickle/load)
# ---------------------------------------------------------------------------

def _mlp():
    import flax.linen as nn

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(N_CLASSES)(nn.relu(nn.Dense(8)(x)))

    return MLP()


def _forward(params, X):
    """Numpy mirror of the flax MLP (Dense_1 = input layer, Dense_0 = the
    first-constructed output layer)."""
    h = np.maximum(X @ np.asarray(params["Dense_1"]["kernel"])
                   + np.asarray(params["Dense_1"]["bias"]), 0)
    return (h @ np.asarray(params["Dense_0"]["kernel"])
            + np.asarray(params["Dense_0"]["bias"]))


class MLPScorer(Transformer):
    """Servable classifier over a published params pytree — replies
    ``{"pred": <argmax>}`` per request body ``{"x": [...]}``."""

    params = ComplexParam("params", "weights pytree", default=None)

    def _transform(self, df):
        W = self.get("params")

        def per_part(p):
            out = dict(p)
            preds = [{"pred": int(np.argmax(_forward(
                W, np.asarray(b["x"], dtype=np.float32)[None, :])))}
                for b in p["body"]]
            out["reply"] = np.asarray(preds, dtype=object)
            return out

        return df.map_partitions(per_part)


def _trainer(steps, lr=0.05, action="raise"):
    from synapseml_tpu.models.trainer import Trainer, TrainerConfig
    from synapseml_tpu.parallel.mesh import MeshConfig, create_mesh

    return Trainer(_mlp(), create_mesh(MeshConfig()),
                   TrainerConfig(total_steps=steps, learning_rate=lr,
                                 nonfinite_action=action))


def make_rows(n, seed, poison=False):
    r = np.random.default_rng(seed)
    X = r.normal(size=(n, D_IN)).astype(np.float32)
    y = np.digitize(X @ _W_TRUE,
                    np.quantile(X @ _W_TRUE, [1 / 3, 2 / 3])).astype(np.int32)
    if poison:
        y = r.integers(0, N_CLASSES, size=n).astype(np.int32)
    return X, y


def _v1_stage(seed=1):
    """A deliberately under-trained v1 (2 steps, default lr)."""
    import jax

    from synapseml_tpu.data.source import MemorySource
    from synapseml_tpu.models.trainer import fit_source

    X0, y0 = make_rows(64, 0)
    s = fit_source(_trainer(2, lr=1e-4), MemorySource(
        {"x": X0, "labels": y0}, shard_rows=32),
        batch_size=16, total_steps=2, seed=seed)
    return MLPScorer().set(params=jax.tree.map(np.asarray, s.params))


def write_part(logdir, idx, Xp, yp, garbage=0, drop_y=0):
    """Hand-craft one committed log part (the layout RequestLogger emits)."""
    name = f"part-{idx:05d}.jsonl"
    with open(os.path.join(logdir, name), "w") as f:
        for i in range(len(Xp)):
            body = {"x": [float(v) for v in Xp[i]]}
            if i >= drop_y:
                body["y"] = int(yp[i])
            f.write(json.dumps({"ts": 0, "method": "POST", "path": "/",
                                "status": 200, "latency_ms": 1.0,
                                "body": body, "reply": {}}) + "\n")
        for _ in range(garbage):
            f.write("{torn json!!\n")
    with open(os.path.join(logdir, name + ".DONE"), "w") as f:
        json.dump({"rows": len(Xp)}, f)
    return name


def row_fn(record):
    b = record["body"]
    return {"x": np.asarray(b["x"], dtype=np.float32),
            "labels": np.int32(b["y"])}


def make_train_fn(total_steps=30, batch_size=16):
    def train_fn(ctx, attempt):
        import jax

        from synapseml_tpu.data.source import MemorySource
        from synapseml_tpu.models.trainer import fit_source
        from synapseml_tpu.parallel.checkpoint import AsyncCheckpointer

        src = MemorySource(ctx.train_cols, shard_rows=32)
        t = _trainer(total_steps)
        init = ctx.prod.stage.get("params") if ctx.prod is not None else None
        with AsyncCheckpointer(ctx.checkpoint_dir, keep=10) as ck:
            state = fit_source(
                t, src, batch_size=batch_size, total_steps=total_steps,
                seed=ctx.spec.seed, init_params=init, scan_chunk=1,
                checkpointer=ck, checkpoint_every=5,
                resume_from=ctx.checkpoint_dir, skip_fn=attempt.skip_fn,
                callback=lambda i, m: attempt.heartbeat(i))
        return MLPScorer().set(params=jax.tree.map(np.asarray, state.params))

    return train_fn


def eval_fn(stage, holdout):
    """Mean NLL of the scorer on the held-out slice (lower = better)."""
    logits = _forward(stage.get("params"), holdout["x"].astype(np.float32))
    z = logits - logits.max(-1, keepdims=True)
    logp = z - np.log(np.exp(z).sum(-1, keepdims=True))
    return float(-logp[np.arange(len(logits)),
                       holdout["labels"].astype(int)].mean())


def _params_equal(a, b):
    import jax

    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# scrubber satellite
# ---------------------------------------------------------------------------

def test_scrub_free_text_patterns():
    counts = {}
    out = scrub('user a.user+tag@example.co.uk paid with '
                '4111 1111 1111 1111, token eyJhbGciOiJIUzI1NiJ9.eyJzdWIi'
                'OiIxIn0.sig-part and Authorization: Bearer abc.def.ghi; '
                'also apiKey=supersecret and "password": "hunter2"', counts)
    assert "@" not in out.replace("####@####", "")
    assert "4111" not in out
    assert "eyJ" not in out
    assert "supersecret" not in out and "hunter2" not in out
    assert counts["email"] == 1 and counts["digits"] == 1
    assert counts["jwt"] == 1 and counts["bearer"] == 1
    assert counts["keyvalue"] == 1 and counts["json"] == 1


def test_scrub_preserves_nonsecret_text():
    counts = {}
    text = ('{"durationMs": 1234.567, "uid": "abc123", "n": 42, '
            '"note": "step 1000000 of 2000000"}')
    assert scrub(text, counts) == text
    assert counts == {}


# ---------------------------------------------------------------------------
# request logger
# ---------------------------------------------------------------------------

def test_request_logger_atomic_parts_and_source(tmp_path):
    from synapseml_tpu.continual import RequestLogger, logged_request_source

    with RequestLogger(str(tmp_path), shard_rows=4, seed=1) as lg:
        for i in range(10):
            lg.log(method="POST", path="/", status=200, latency_ms=1.0,
                   body=json.dumps({"x": [i], "email": "u@x.io"}).encode(),
                   reply={"pred": i % 3})
        lg.flush()
        parts = lg.committed_parts()
        assert len(parts) == 3  # 4 + 4 + 2 (flush commits the tail)
        # DONE markers carry rows + the scrub tally
        done = json.load(open(parts[0] + ".DONE"))
        assert done["rows"] == 4 and done["scrubbed"].get("email", 0) > 0
        # no in-flight litter visible to a part glob
        assert not [n for n in os.listdir(tmp_path)
                    if n.endswith(".jsonl") and not os.path.exists(
                        os.path.join(tmp_path, n + ".DONE"))]
        src = logged_request_source(str(tmp_path))
        rows = sum(len(next(iter(c.values())))
                   for _, c in src.iter_shards())
        assert rows == 10
        body = src.read_shard(0)["body"][0]
        assert body["email"] == "####@####"  # scrubbed at write time
        assert lg.stats()["logged"] == 10


def test_request_logger_sampling_deterministic(tmp_path):
    from synapseml_tpu.continual import RequestLogger

    def run(sub):
        with RequestLogger(str(tmp_path / sub), sample_rate=0.5,
                           seed=42, shard_rows=1000) as lg:
            for i in range(200):
                lg.log(method="POST", path="/", body=b"{}", reply={},
                       status=200, latency_ms=0.1)
            lg.flush()
            return lg.stats()["logged"]

    a, b = run("a"), run("b")
    assert a == b  # one seeded RNG ⇒ identical kept-set size
    assert 50 < a < 150  # actually sampling, not pass/drop-everything


def test_request_logger_sheds_when_queue_full(tmp_path):
    from synapseml_tpu.continual import RequestLogger

    lg = RequestLogger(str(tmp_path), shard_rows=1000, max_queue=2)
    gate = threading.Event()
    orig = lg._write_record

    def slow(item):
        gate.wait(10)
        orig(item)

    lg._write_record = slow
    for i in range(20):
        lg.log(method="POST", path="/", body=b"{}", reply={}, status=200,
               latency_ms=0.1)
    assert lg.dropped > 0  # shed (never blocked the serving thread)
    gate.set()
    lg.close()
    assert lg.stats()["logged"] + lg.dropped == 20


@pytest.mark.chaos
def test_request_logger_commit_fault_sheds_shard(tmp_path):
    """An injected fault at the commit seam sheds that shard's rows and
    the logger keeps committing — degraded, never a torn committed part."""
    from synapseml_tpu.continual import RequestLogger

    with RequestLogger(str(tmp_path), shard_rows=4) as lg:
        with inject_faults([FaultSpec("crash", match="log_commit",
                                      times=1, planes=("continual",))]):
            for i in range(8):
                lg.log(method="POST", path="/", body=b"{}", reply={},
                       status=200, latency_ms=0.1)
            lg.flush()
        assert lg.dropped == 4 and lg.logged == 4
        parts = lg.committed_parts()
        assert len(parts) == 1
        # every committed part parses end to end (never torn)
        for p in parts:
            for line in open(p):
                json.loads(line)


# ---------------------------------------------------------------------------
# checkpoint verification satellite
# ---------------------------------------------------------------------------

def _small_fit(ckdir, steps=8, every=2):
    from synapseml_tpu.data.source import MemorySource
    from synapseml_tpu.models.trainer import fit_source
    from synapseml_tpu.parallel.checkpoint import AsyncCheckpointer

    X, y = make_rows(64, 2)
    with AsyncCheckpointer(str(ckdir), keep=10) as ck:
        return fit_source(_trainer(steps), MemorySource(
            {"x": X, "labels": y}, shard_rows=32),
            batch_size=16, total_steps=steps, seed=3, scan_chunk=1,
            checkpointer=ck, checkpoint_every=every)


def test_checkpoint_sidecar_verification_demotes(tmp_path):
    from synapseml_tpu.parallel.checkpoint import (
        CheckpointCorrupt, latest_step, latest_verified_step,
        restore_checkpoint, verify_checkpoint)

    _small_fit(tmp_path)
    newest = latest_step(str(tmp_path))
    assert verify_checkpoint(str(tmp_path), newest)
    # corrupt the newest payload in place (torn write / bit rot)
    npz = os.path.join(str(tmp_path), f"step_{newest:010d}", "state.npz")
    with open(npz, "r+b") as f:
        f.seek(80)
        f.write(b"\xff\xff\xff\xff")
    assert not verify_checkpoint(str(tmp_path), newest)
    demoted = latest_verified_step(str(tmp_path))
    assert demoted is not None and demoted < newest
    # default restore demotes; explicitly asking for the corrupt step raises
    tree = restore_checkpoint(str(tmp_path))
    assert int(np.asarray(tree["step"])) == demoted
    with pytest.raises(CheckpointCorrupt):
        restore_checkpoint(str(tmp_path), step=newest)
    # the tree-structure JSON is a payload too: tearing it demotes again
    with open(os.path.join(str(tmp_path), f"step_{demoted:010d}",
                           "state.tree.json"), "a") as f:
        f.write("garbage")
    assert not verify_checkpoint(str(tmp_path), demoted)
    assert latest_verified_step(str(tmp_path)) < demoted


# ---------------------------------------------------------------------------
# trainer satellites: non-finite guard + skip windows
# ---------------------------------------------------------------------------

def test_nonfinite_loss_counts_and_raises():
    from synapseml_tpu.core import observability as obs
    from synapseml_tpu.data.source import MemorySource
    from synapseml_tpu.models.trainer import NonFiniteLossError, fit_source

    X, y = make_rows(64, 4)
    X_bad = X.copy()
    X_bad[32:48] = np.nan  # third 16-row shard poisons step 2 (unshuffled)
    src = MemorySource({"x": X_bad, "labels": y}, shard_rows=16)

    t = _trainer(4, action="count")
    before = obs.get_registry().counter(
        "synapseml_train_nonfinite_total", "", ("engine",))
    n0 = before.labels(engine="trainer").value
    # chunked path: losses are already materialized per chunk, so "count"
    # mode observes them for free (the per-step path samples log windows)
    fit_source(t, src, batch_size=16, total_steps=4, seed=0, scan_chunk=4,
               shuffle_rows="none")
    assert before.labels(engine="trainer").value > n0  # counted, not raised
    assert t.last_finite_step >= 2

    t2 = _trainer(4, action="raise")
    with pytest.raises(NonFiniteLossError) as ei:
        fit_source(t2, MemorySource({"x": X_bad, "labels": y},
                                    shard_rows=16),
                   batch_size=16, total_steps=4, seed=0, scan_chunk=1,
                   shuffle_rows="none")
    # the shard order is a seeded permutation: the poisoned step is
    # deterministic per seed but not positionally pinned here
    assert 1 <= ei.value.step <= 4
    assert ei.value.last_finite_step == ei.value.step - 1


def test_fit_source_skip_fn_consumes_without_training():
    import jax

    from synapseml_tpu.data.source import MemorySource
    from synapseml_tpu.models.trainer import fit_source

    X, y = make_rows(64, 5)

    def run(skip):
        t = _trainer(4)
        return fit_source(t, MemorySource({"x": X, "labels": y},
                                          shard_rows=16),
                          batch_size=16, total_steps=4, seed=6,
                          scan_chunk=1, skip_fn=skip)

    full = run(None)
    skipped = run(lambda i: True)  # consume everything, train nothing
    assert int(skipped.step) == int(full.step) == 4
    assert not _params_equal(full.params, skipped.params)
    # skipping batch 0 only: steps still advance to the total
    partial = run(lambda i: i == 0)
    assert int(partial.step) == 4
    assert not _params_equal(partial.params, full.params)
    leaves = [np.ptp(np.asarray(x)) for x in jax.tree.leaves(skipped.params)]
    assert any(v > 0 for v in leaves)  # params are the real init, not zeros


# ---------------------------------------------------------------------------
# supervisor
# ---------------------------------------------------------------------------

def _supervised_fit(att, ckdir, steps=12):
    from synapseml_tpu.data.source import MemorySource
    from synapseml_tpu.models.trainer import fit_source
    from synapseml_tpu.parallel.checkpoint import AsyncCheckpointer

    X, y = make_rows(96, 9)
    with AsyncCheckpointer(str(ckdir), keep=10) as ck:
        return fit_source(_trainer(steps), MemorySource(
            {"x": X, "labels": y}, shard_rows=32),
            batch_size=16, total_steps=steps, seed=9, scan_chunk=1,
            checkpointer=ck, checkpoint_every=3, resume_from=str(ckdir),
            skip_fn=att.skip_fn, callback=lambda i, m: att.heartbeat(i))


@pytest.mark.chaos
def test_supervisor_crash_restart_bit_parity(tmp_path):
    """Injected trainer crash at step 5 → bounded restart resumes from the
    latest verified checkpoint; final params bit-identical to an
    uninterrupted run (the checkpointable-iterator guarantee)."""
    from synapseml_tpu.continual import TrainSupervisor
    from synapseml_tpu.core.resilience import resilience_measures

    ref = _supervised_fit(_NoopAttempt(), tmp_path / "ref")

    sup = TrainSupervisor(str(tmp_path / "sup"), max_restarts=2)
    r0 = resilience_measures("training").to_dict().get("retry_count", 0)
    with inject_faults([FaultSpec("crash", match="step:5", times=1,
                                  planes=("training",))]) as plan:
        state = sup.run(lambda att: _supervised_fit(att, tmp_path / "sup"))
    assert sup.restarts == 1
    assert len(plan.injected) == 1
    assert resilience_measures("training").to_dict().get(
        "retry_count", 0) == r0 + 1
    assert int(state.step) == 12
    assert _params_equal(ref.params, state.params)


class _NoopAttempt:
    skip_fn = None
    resume = False

    def heartbeat(self, step):
        pass


@pytest.mark.chaos
def test_supervisor_nan_rewind_skips_poisoned_window(tmp_path):
    """A NaN batch raises; the supervisor rewinds to the verified
    checkpoint and the retry SKIPS the poisoned window — training
    completes with finite params and the rewind counters move."""
    from synapseml_tpu.continual import TrainSupervisor
    from synapseml_tpu.data.source import MemorySource
    from synapseml_tpu.models.trainer import fit_source
    from synapseml_tpu.parallel.checkpoint import AsyncCheckpointer

    X, y = make_rows(128, 10)
    X[96:112] = np.nan  # shard 6 of 8 → poisons exactly one batch

    def attempt(att):
        with AsyncCheckpointer(str(tmp_path), keep=10) as ck:
            return fit_source(
                _trainer(8), MemorySource({"x": X, "labels": y},
                                          shard_rows=16),
                batch_size=16, total_steps=8, seed=0, scan_chunk=1,
                shuffle_rows="none", checkpointer=ck, checkpoint_every=2,
                resume_from=str(tmp_path), skip_fn=att.skip_fn,
                callback=lambda i, m: att.heartbeat(i))

    sup = TrainSupervisor(str(tmp_path), max_restarts=1, max_rewinds=2)
    state = sup.run(attempt)
    assert sup.rewinds == 1 and sup.restarts == 0
    assert int(state.step) == 8
    assert all(np.isfinite(np.asarray(x)).all()
               for x in __import__("jax").tree.leaves(state.params))
    lo, hi = sup.skip_windows[0]
    assert 0 <= lo < hi <= 8  # window covers the seed-placed poisoned step


_CHILD_SCRIPT = r"""
import os, signal, sys, time
ckdir, mode, marker = sys.argv[1], sys.argv[2], sys.argv[3]
import numpy as np
import flax.linen as nn
from synapseml_tpu.models.trainer import Trainer, TrainerConfig, fit_source
from synapseml_tpu.parallel.mesh import MeshConfig, create_mesh
from synapseml_tpu.parallel.checkpoint import (AsyncCheckpointer,
                                               latest_verified_step)
from synapseml_tpu.data.source import MemorySource

class MLP(nn.Module):
    @nn.compact
    def __call__(self, x):
        return nn.Dense(3)(nn.relu(nn.Dense(8)(x)))

r = np.random.default_rng(9)
X = r.normal(size=(96, 4)).astype(np.float32)
y = (np.arange(96) % 3).astype(np.int32)
t = Trainer(MLP(), create_mesh(MeshConfig()),
            TrainerConfig(total_steps=16, learning_rate=0.05))
base = latest_verified_step(ckdir) or 0

def cb(i, m):
    if mode != "clean" and not os.path.exists(marker) and base + i == 6:
        with open(marker, "w") as f:
            f.write("hit")
        if mode == "kill":
            os.kill(os.getpid(), signal.SIGKILL)
        time.sleep(3600)  # mode == "hang": wedge without dying

with AsyncCheckpointer(ckdir, keep=10) as ck:
    fit_source(t, MemorySource({"x": X, "labels": y}, shard_rows=32),
               batch_size=16, total_steps=16, seed=9, scan_chunk=1,
               checkpointer=ck, checkpoint_every=3, resume_from=ckdir,
               callback=cb)
"""


@pytest.mark.chaos(timeout_s=300)
def test_supervisor_subprocess_sigkill_and_hang_watchdog(tmp_path):
    """The real thing: a subprocess trainer SIGKILLed mid-fit resumes to a
    final state byte-identical to an uninterrupted run; a WEDGED trainer
    (no checkpoint progress) is hang-detected, killed and restarted."""
    from synapseml_tpu.continual import TrainSupervisor
    from synapseml_tpu.parallel.checkpoint import restore_checkpoint

    script = tmp_path / "child.py"
    script.write_text(_CHILD_SCRIPT)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH",
                                                             ""))

    def run(mode, hang_timeout=60.0):
        ckdir = tmp_path / mode
        sup = TrainSupervisor(str(ckdir), max_restarts=2,
                              hang_timeout_s=hang_timeout, poll_s=0.2)
        attempts = sup.run_subprocess(
            [sys.executable, str(script), str(ckdir), mode,
             str(tmp_path / f"{mode}.marker")], env=env, timeout_s=240)
        return sup, attempts, restore_checkpoint(str(ckdir), step=16)

    t0 = time.monotonic()
    _, attempts, clean = run("clean")
    clean_s = time.monotonic() - t0
    assert attempts == 1

    sup_k, attempts_k, killed = run("kill")
    assert attempts_k == 2 and sup_k.restarts == 1
    assert _params_equal(clean["params"], killed["params"])

    # the restarted child must import jax, compile and reach its first
    # checkpoint inside the hang window, or the healthy restart is killed
    # too: the window follows what the clean child just took on this
    # machine under this load, not a constant that assumes an idle one
    sup_h, attempts_h, hung = run("hang",
                                  hang_timeout=max(5.0, 2.0 * clean_s))
    assert attempts_h == 2 and sup_h.restarts == 1
    assert _params_equal(clean["params"], hung["params"])


# ---------------------------------------------------------------------------
# the loop (no fleet): gate + containment
# ---------------------------------------------------------------------------

def _loop_fixture(tmp_path, **spec_kw):
    from synapseml_tpu.continual import ContinualLoop, ContinualSpec

    reg = ModelRegistry(str(tmp_path / "reg"))
    reg.publish("m", _v1_stage(), version="v1")
    reg.pin("m", "prod", "v1")
    logdir = tmp_path / "log"
    os.makedirs(logdir, exist_ok=True)
    kw = {"min_new_rows": 50, "gate_min_margin": 0.05, "seed": 5}
    kw.update(spec_kw)
    spec = ContinualSpec(model="m", **kw)
    loop = ContinualLoop(spec, reg, str(logdir), make_train_fn(), eval_fn,
                         row_fn=row_fn, state_dir=str(tmp_path / "state"))
    return reg, logdir, loop


def _write_clean_parts(logdir, start=0, n_parts=8, rows=30, seed=7):
    X, y = make_rows(n_parts * rows, seed)
    for k in range(n_parts):
        write_part(str(logdir), start + k, X[k * rows:(k + 1) * rows],
                   y[k * rows:(k + 1) * rows])


def test_loop_promotes_then_fails_gate_on_poison(tmp_path):
    """Iteration 1 (clean data): candidate beats prod → promoted.
    Iteration 2 (poisoned train parts, clean holdout): gate fails, prod
    untouched, malformed + label-less rows quarantined."""
    reg, logdir, loop = _loop_fixture(tmp_path)
    _write_clean_parts(logdir)
    rec = loop.run_once()
    assert rec["outcome"] == "promoted", rec
    v2 = rec["version"]
    assert reg.alias_target("m", "prod") == v2
    assert rec["gate"]["margin"] > 0.05
    assert loop.state["champion_ckpt"]

    # craft iteration 2 so the poisoned parts land in the TRAIN split and
    # the clean ones in the HOLDOUT split (the split is a seeded hash)
    names = [f"part-{i:05d}.jsonl" for i in range(90, 102)]
    holdout = [n for n in names if loop._holdout_part(n)]
    train = [n for n in names if not loop._holdout_part(n)]
    assert holdout and train
    Xp, yp = make_rows(300, 11, poison=True)
    Xc, yc = make_rows(120, 12)
    for j, n in enumerate(train):
        write_part(str(logdir), int(n[5:10]), Xp[j * 30:(j + 1) * 30],
                   yp[j * 30:(j + 1) * 30], garbage=2, drop_y=2)
    for j, n in enumerate(holdout):
        write_part(str(logdir), int(n[5:10]), Xc[j * 16:(j + 1) * 16],
                   yc[j * 16:(j + 1) * 16])

    rec2 = loop.run_once()
    assert rec2["outcome"] == "gate_failed", rec2
    assert rec2["quarantined"] >= 2 * len(train)  # garbage + label-less rows
    assert reg.alias_target("m", "prod") == v2  # prod untouched
    assert reg.list_versions("m") == ["v1", v2]  # nothing published


def test_loop_skips_when_not_due_and_drift_triggers(tmp_path):
    from synapseml_tpu.core import observability as obs

    reg, logdir, loop = _loop_fixture(tmp_path, min_new_rows=10_000,
                                      drift_gauge="synapseml_test_drift",
                                      drift_threshold=0.5)
    _write_clean_parts(logdir, n_parts=2)
    ok, reason = loop.should_run()
    assert not ok
    rec = loop.run_once()
    assert rec["outcome"] == "skipped:not_due"
    assert loop._new_parts()  # nothing consumed
    obs.get_registry().gauge("synapseml_test_drift", "t").set(0.9)
    ok, reason = loop.should_run()
    assert ok and "drift" in reason


@pytest.mark.chaos
def test_loop_seam_faults_contained(tmp_path):
    """A seeded fault at EVERY seam aborts exactly one iteration with
    ``prod`` untouched; the next iteration (fault exhausted) promotes."""
    reg, logdir, loop = _loop_fixture(tmp_path)
    _write_clean_parts(logdir)
    for seam in ("watch", "snapshot", "train", "eval", "publish",
                 "promote"):
        with inject_faults([FaultSpec("crash", match=f"m:{seam}", times=1,
                                      planes=("continual",))]) as plan:
            rec = loop.run_once()
        assert rec["outcome"] == f"error:{seam}", (seam, rec)
        assert len(plan.injected) == 1
        # the containment contract: prod NEVER moves on a failed iteration
        assert reg.alias_target("m", "prod") == "v1", seam
        if seam != "promote":
            # ...and nothing is published before the promote seam
            assert reg.list_versions("m") == ["v1"], seam
        if seam in ("eval", "publish", "promote"):
            # those iterations consumed the data before failing — refeed
            _write_clean_parts(logdir,
                               start=200 + 10 * len(loop.history))
    # raise_errors: same containment + recorded outcome, then re-raised
    from synapseml_tpu.continual import LoopAborted

    with inject_faults([FaultSpec("crash", match="m:watch", times=1,
                                  planes=("continual",))]):
        with pytest.raises(LoopAborted):
            loop.run_once(raise_errors=True)
    assert loop.history[-1]["outcome"] == "error:watch"
    assert reg.alias_target("m", "prod") == "v1"

    rec = loop.run_once()  # no plan active: the loop recovered
    assert rec["outcome"] == "promoted"
    assert reg.alias_target("m", "prod") == rec["version"]


# ---------------------------------------------------------------------------
# E2E flywheel acceptance: two live-fleet iterations + SIGKILL-equivalent
# mid-train crash + canary p95 rollback
# ---------------------------------------------------------------------------

def _post(address, body: dict, path="/"):
    req = urllib.request.Request(
        address + path, data=json.dumps(body).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=15) as r:
        return r.read()


def _send_labeled_traffic(address, n, seed):
    X, y = make_rows(n, seed)
    for i in range(n):
        _post(address, {"x": [float(v) for v in X[i]], "y": int(y[i])})


@pytest.mark.chaos(timeout_s=420)
def test_e2e_flywheel_two_iterations_live_fleet(tmp_path):
    """The ISSUE-14 acceptance: a live 2-worker fleet serves v1; logged
    traffic retrains it. Iteration 1 survives a mid-train trainer crash
    (supervisor restart) and promotes a genuinely better v2 through the
    canary — its params BYTE-IDENTICAL to an uninterrupted reference
    iteration. Iteration 2 is fed fault-injected (poisoned) data, fails
    the gate, and prod + its serving outputs are byte-identical to before.
    Iteration 3 passes the gate but regresses canary p95 — auto-rollback
    leaves prod untouched."""
    import dataclasses

    from synapseml_tpu.continual import (ContinualLoop, ContinualSpec,
                                         RequestLogger)
    from synapseml_tpu.io.distributed_serving import \
        serve_pipeline_distributed

    v1 = _v1_stage()
    reg = ModelRegistry(str(tmp_path / "reg"))
    reg.publish("m", v1, version="v1")
    reg.pin("m", "prod", "v1")
    logdir = str(tmp_path / "log")

    handle = serve_pipeline_distributed(v1, num_workers=2,
                                        batch_interval_ms=0, version="v1")
    lg = None
    try:
        lg = RequestLogger(logdir, shard_rows=30, seed=0)
        handle.front.set_request_logger(lg)
        _send_labeled_traffic(handle.address, 240, seed=7)
        lg.flush()
        assert lg.stats()["logged"] == 240

        dep = Deployment(handle, reg, "m", warmup=[{"x": [0.0] * D_IN}])
        spec = ContinualSpec(model="m", min_new_rows=50,
                             gate_min_margin=0.05, seed=5,
                             canary_weight=0.5, canary_min_requests=8,
                             canary_timeout_s=90.0, canary={})
        loop = ContinualLoop(spec, reg, logdir, make_train_fn(), eval_fn,
                             row_fn=row_fn, deployment=dep,
                             state_dir=str(tmp_path / "state"))

        # uninterrupted REFERENCE iteration: same spec/seed/log snapshot,
        # separate registry + state, no fleet — the parity baseline
        ref_reg = ModelRegistry(str(tmp_path / "ref_reg"))
        ref_reg.publish("m", v1, version="v1")
        ref_reg.pin("m", "prod", "v1")
        ref_loop = ContinualLoop(
            dataclasses.replace(spec), ref_reg, logdir, make_train_fn(),
            eval_fn, row_fn=row_fn, state_dir=str(tmp_path / "ref_state"))
        ref_rec = ref_loop.run_once()
        assert ref_rec["outcome"] == "promoted", ref_rec
        ref_params = ref_reg.resolve("m", "prod").stage.get("params")

        # ---- iteration 1: crash mid-train, restart, canary, promote ----
        with inject_faults([FaultSpec("crash", match="step:11", times=1,
                                      planes=("training",))]):
            rec1 = loop.run_once()
        assert rec1["outcome"] == "promoted", rec1
        assert rec1["supervisor"]["restarts"] == 1
        v2 = rec1["version"]
        assert reg.alias_target("m", "prod") == v2
        # killed-and-resumed candidate == uninterrupted reference, bytes
        prod_params = reg.resolve("m", "prod").stage.get("params")
        assert _params_equal(ref_params, prod_params)
        # the whole fleet now serves v2
        for w in handle.registry.workers():
            assert w.get("version") == v2

        probe = {"x": [0.1, -0.2, 0.3, 0.4]}
        r0 = _post(handle.address, probe)

        # ---- iteration 2: poisoned data fails the gate ----
        names = [f"part-{i:05d}.jsonl" for i in range(900, 912)]
        holdout = [n for n in names if loop._holdout_part(n)]
        train = [n for n in names if not loop._holdout_part(n)]
        assert holdout and train
        Xp, yp = make_rows(360, 11, poison=True)
        Xc, yc = make_rows(120, 12)
        for j, n in enumerate(train):
            write_part(logdir, int(n[5:10]), Xp[j * 30:(j + 1) * 30],
                       yp[j * 30:(j + 1) * 30], garbage=2, drop_y=1)
        for j, n in enumerate(holdout):
            write_part(logdir, int(n[5:10]), Xc[j * 16:(j + 1) * 16],
                       yc[j * 16:(j + 1) * 16])
        rec2 = loop.run_once()
        assert rec2["outcome"] == "gate_failed", rec2
        assert rec2["quarantined"] > 0
        assert reg.alias_target("m", "prod") == v2  # prod untouched...
        assert _post(handle.address, probe) == r0   # ...and so is serving
        assert _params_equal(
            prod_params, reg.resolve("m", "prod").stage.get("params"))

        # ---- iteration 3: gate passes, canary p95 regresses, rollback ---
        _send_labeled_traffic(handle.address, 120, seed=21)
        lg.flush()
        spec3 = dataclasses.replace(
            spec, gate_min_margin=-1e9, canary_min_requests=3,
            canary={"p95_regression_factor": 1e-6,
                    "min_latency_samples": 1,
                    "error_rate_threshold": 1.0, "window": 1000,
                    "min_samples": 1000})
        loop3 = ContinualLoop(spec3, reg, logdir, make_train_fn(), eval_fn,
                              row_fn=row_fn, deployment=dep,
                              state_dir=str(tmp_path / "state"))
        rec3 = loop3.run_once()
        assert rec3["outcome"] == "canary_rolled_back", rec3
        assert reg.alias_target("m", "prod") == v2
        assert _post(handle.address, probe) == r0
        # loop health series moved
        from synapseml_tpu.core import observability as obs

        snap = obs.get_registry().snapshot()
        assert any(k.startswith("synapseml_continual_iterations_total")
                   for k in snap)
    finally:
        if lg is not None:
            lg.close()
        handle.stop()
