"""Elastic gang layer: preemption-tolerant multi-host training runs.

The multi-controller SPMD shape (every host one failure domain) plus the
scale-reliability arithmetic of arXiv:1810.11112 (mean time between host
failures drops below run length) make *survival* the gating property of a
long run. This module keeps the :class:`~synapseml_tpu.parallel.backend.
DriverRendezvous` TCP channel ALIVE after bootstrap and turns it into the
gang's membership plane:

* **failure detection** — every worker sends one heartbeat per optimizer
  step (fed from the ``supervisor.heartbeat(step)`` seam via
  ``Trainer.fit(gang=...)``); the driver tracks per-rank last-beat times
  against a missed-beat deadline and treats a dropped connection (SIGKILL,
  OOM, host loss) as immediate death. Per-host step latencies export as
  ``synapseml_train_gang_*`` gauges, so stragglers are visible before they
  become failures.
* **verdicts** — the driver broadcasts one of two verdicts:
  ``abort_and_checkpoint`` (a member received a preemption notice: all
  hosts run the coordinated-checkpoint dance inside the grace window, then
  exit :data:`EXIT_PREEMPTED`) or ``resize`` (a member is already dead —
  no complete checkpoint is possible, survivors exit :data:`EXIT_RESIZE`
  and the launcher resumes M survivors from the last *committed* step).
* **coordinated checkpoints** — periodic saves go through
  ``parallel.checkpoint.save_checkpoint_shard`` (each host writes only its
  locally-addressable slices + its per-host ``data_iter`` cursors); the
  driver's commit scanner writes the two-phase ``DONE`` marker only when
  every rank's ACK landed, then runs keep-last-K verified retention GC.
* **N→M elastic resume** — :func:`elastic_restore` reassembles the
  N-shard checkpoint on ANY number of survivors and rebuilds the
  :class:`~synapseml_tpu.data.state.ElasticPlan` from the per-rank
  cursors; ``models.trainer.fit_gang_source`` re-derives placement from
  the PR-10 rule tables and continues the batch stream with zero replayed
  and zero skipped rows.

The emergency-checkpoint dance (preemption notice, SIGTERM):

    worker i --preempt--> driver
    driver   --verdict: abort_and_checkpoint--> all workers
    worker j --ready(step_j)--> driver           (stops at its boundary)
    driver   --sync(S = max step_j)--> all       (lockstep SPMD: all equal)
    worker j  trains to S, writes its shard, --ack(S)--> driver
    driver    commit_checkpoint(S) --committed(S)--> all
    worker j  exits EXIT_PREEMPTED

Every phase is deadline-bounded (``core.resilience.Deadline``); a dance
that cannot complete inside the grace window degrades to ``resize`` —
survivors resume from the previous committed step (bounded lost work,
never a torn artifact: an uncommitted step dir is invisible to restore).
"""

from __future__ import annotations

import json
import os
import signal
import socket
import threading
import time

from ..core import observability as obs
from ..core.faults import active_fault_plan
from ..core.resilience import Deadline, resilience_measures
from .checkpoint import (checkpoint_meta, checkpoint_world, commit_checkpoint,
                         gc_checkpoints, latest_verified_step,
                         restore_checkpoint, restore_host_states)

__all__ = ["GangCoordinator", "GangWorker", "GangAborted", "Preempted",
           "ElasticResume", "elastic_restore", "run_gang_member",
           "launch_gang_processes", "finish_gang_processes",
           "EXIT_PREEMPTED", "EXIT_RESIZE"]

# distinct exit codes so a supervisor/launcher can tell "resume me" apart
# from a crash: EX_TEMPFAIL for a preemption-notice exit (a coordinated
# emergency checkpoint WAS committed), +1 for a resize exit (a member died;
# resume from the last periodic commit)
EXIT_PREEMPTED = 75
EXIT_RESIZE = 76


class GangAborted(RuntimeError):
    """The driver broadcast a ``resize`` verdict (a gang member died) —
    exit now and let the launcher resume the survivors from the last
    committed checkpoint."""


class Preempted(RuntimeError):
    """This worker completed the emergency-checkpoint dance: ``step`` is
    the committed step. Exit with :data:`EXIT_PREEMPTED`."""

    def __init__(self, step: int):
        super().__init__(f"gang preempted: emergency checkpoint committed "
                         f"at step {step}")
        self.step = int(step)


_GANG_METRICS = obs.HandleCache(lambda reg: {
    "members": reg.gauge(
        "synapseml_train_gang_members",
        "gang members currently alive (driver view)"),
    "last_step": reg.gauge(
        "synapseml_train_gang_last_step",
        "newest heartbeat step per rank", ("rank",)),
    "step_latency": reg.gauge(
        "synapseml_train_gang_step_latency_ms",
        "wall time between a rank's consecutive heartbeats — the "
        "straggler gauge", ("rank",)),
    "beats": reg.counter(
        "synapseml_train_gang_beats_total",
        "heartbeats received per rank", ("rank",)),
    "beats_missed": reg.counter(
        "synapseml_train_gang_beats_missed_total",
        "missed-beat detections per rank (deadline exceeded)", ("rank",)),
    "verdicts": reg.counter(
        "synapseml_train_gang_verdicts_total",
        "driver verdict broadcasts", ("verdict",)),
    "commits": reg.counter(
        "synapseml_train_gang_commits_total",
        "coordinated checkpoints committed (two-phase DONE written)",
        ("kind",)),
})


def _send_line(sock: socket.socket, payload: dict) -> None:
    sock.sendall((json.dumps(payload) + "\n").encode())


class _Member:
    """Driver-side per-rank record."""

    def __init__(self, rank: int, conn: socket.socket):
        self.rank = rank
        self.conn = conn
        self.last_seen = time.monotonic()
        self.last_step = -1
        self.alive = True
        self.done_code: str | None = None  # orderly exit ("bye") reason
        self.ready_step: int | None = None
        self.ack_step: int | None = None
        self.lock = threading.Lock()  # serialize sends to this conn


class GangCoordinator:
    """Driver side of the gang channel.

    Built on the sockets :class:`~synapseml_tpu.parallel.backend.
    DriverRendezvous` keeps open after bootstrap (``keep_alive=True``) —
    the same deterministic rank order. ``beat_timeout_s`` is the
    missed-beat deadline (cover your slowest compile), ``grace_s`` bounds
    the whole emergency-checkpoint dance (the preemption grace window).
    ``checkpoint_dir`` enables the commit scanner: periodic per-rank shard
    writes become restorable the moment the full ACK set lands, and
    ``keep`` verified steps are retained.
    """

    def __init__(self, conns: dict[int, socket.socket], *,
                 checkpoint_dir: str | None = None,
                 beat_timeout_s: float = 30.0, grace_s: float = 20.0,
                 keep: int = 3, poll_s: float = 0.1,
                 run_id: str | None = None):
        self.world = len(conns)
        # this launch's incarnation id (DriverRendezvous.run_id): commits
        # only accept ACKs stamped with it — stale acks from a killed
        # previous run over the same dir can never complete a set
        self.run_id = run_id
        self.members = {rank: _Member(rank, conn)
                        for rank, conn in sorted(conns.items())}
        self.checkpoint_dir = checkpoint_dir
        self.beat_timeout_s = float(beat_timeout_s)
        self.grace_s = float(grace_s)
        self.keep = int(keep)
        self.poll_s = float(poll_s)
        self.failure: tuple[int, str] | None = None
        self.committed_steps: list[int] = []
        self.preempt_commit_step: int | None = None
        self._events: list[dict] = []
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._dance = threading.Event()   # one dance at a time
        self._verified_cache: dict = {}  # step -> verification outcome
        self._threads: list[threading.Thread] = []

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "GangCoordinator":
        for m in self.members.values():
            t = threading.Thread(target=self._reader, args=(m,), daemon=True)
            t.start()
            self._threads.append(t)
        for fn in (self._monitor, self._commit_scan):
            t = threading.Thread(target=fn, daemon=True)
            t.start()
            self._threads.append(t)
        _GANG_METRICS.get()["members"].set(self.alive_count())
        return self

    def close(self) -> None:
        self._stop.set()
        for m in self.members.values():
            try:
                m.conn.close()
            except OSError:
                pass

    # -- queries ------------------------------------------------------------
    def alive_count(self) -> int:
        return sum(1 for m in self.members.values() if m.alive)

    def alive_ranks(self) -> list[int]:
        return [r for r, m in self.members.items() if m.alive]

    def status(self) -> dict:
        return {r: {"alive": m.alive, "last_step": m.last_step,
                    "done": m.done_code}
                for r, m in self.members.items()}

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def wait_failure(self, timeout_s: float) -> tuple[int, str] | None:
        deadline = Deadline(timeout_s)
        while not deadline.expired():
            if self.failure is not None:
                return self.failure
            time.sleep(self.poll_s)
        return self.failure

    def wait_all_exited(self, timeout_s: float) -> bool:
        """True once every member is done (orderly bye) or dead."""
        deadline = Deadline(timeout_s)
        while not deadline.expired():
            if all(not m.alive or m.done_code is not None
                   for m in self.members.values()):
                return True
            time.sleep(self.poll_s)
        return False

    def wait_commit(self, step: int | None = None,
                    timeout_s: float = 30.0) -> int | None:
        """Block until a coordinated checkpoint commits (any, or ``step``)."""
        deadline = Deadline(timeout_s)
        while not deadline.expired():
            with self._lock:
                hits = [s for s in self.committed_steps
                        if step is None or s == step]
            if hits:
                return hits[-1]
            time.sleep(self.poll_s)
        return None

    # -- protocol: reader / monitor / commit scanner ------------------------
    def _record(self, **event) -> None:
        with self._lock:
            self._events.append(event)

    def _reader(self, m: _Member) -> None:
        f = m.conn.makefile("r")
        try:
            for line in f:
                if self._stop.is_set():
                    return
                try:
                    msg = json.loads(line)
                except json.JSONDecodeError:
                    continue
                self._on_message(m, msg)
                if msg.get("t") == "bye":
                    return
        except (OSError, ValueError):
            pass
        finally:
            if not self._stop.is_set() and m.alive and m.done_code is None:
                # connection died without an orderly bye: the process is
                # gone (SIGKILL / host loss) — immediate failure, no need
                # to wait out the beat deadline
                self._mark_dead(m, "connection lost")

    def _on_message(self, m: _Member, msg: dict) -> None:
        t = msg.get("t")
        now = time.monotonic()
        if t == "beat":
            gm = _GANG_METRICS.get()
            dt_ms = (now - m.last_seen) * 1e3
            m.last_step = int(msg.get("step", m.last_step))
            m.last_seen = now
            gm["beats"].inc(rank=str(m.rank))
            gm["last_step"].set(m.last_step, rank=str(m.rank))
            gm["step_latency"].set(dt_ms, rank=str(m.rank))
        elif t == "preempt":
            m.last_seen = now
            self._record(event="preempt_notice", rank=m.rank)
            self.request_checkpoint(f"preemption notice from rank {m.rank}")
        elif t == "ready":
            m.last_seen = now
            m.ready_step = int(msg["step"])
        elif t == "ack":
            m.last_seen = now
            m.ack_step = int(msg["step"])
        elif t == "bye":
            m.done_code = str(msg.get("code", "done"))
            m.alive = False
            self._record(event="bye", rank=m.rank, code=m.done_code)
            _GANG_METRICS.get()["members"].set(self.alive_count())

    def _monitor(self) -> None:
        while not self._stop.is_set():
            time.sleep(min(self.poll_s, self.beat_timeout_s / 4))
            now = time.monotonic()
            for m in self.members.values():
                if not m.alive or m.done_code is not None:
                    continue
                if now - m.last_seen > self.beat_timeout_s:
                    _GANG_METRICS.get()["beats_missed"].inc(rank=str(m.rank))
                    resilience_measures("parallel").count("beats_missed")
                    self._mark_dead(
                        m, f"missed beats for {self.beat_timeout_s:.1f}s "
                           f"(last step {m.last_step})")

    def _mark_dead(self, m: _Member, reason: str) -> None:
        first = False
        with self._lock:
            if not m.alive:
                return
            m.alive = False
            if self.failure is None:
                self.failure = (m.rank, reason)
                first = True
            self._events.append({"event": "member_dead", "rank": m.rank,
                                 "reason": reason})
        _GANG_METRICS.get()["members"].set(self.alive_count())
        if first:
            # a dead member cannot contribute a shard — no complete
            # coordinated checkpoint is possible; survivors must exit and
            # resume from the last committed step on the new world
            self._broadcast_verdict("resize", reason=reason)

    def _broadcast_verdict(self, verdict: str, **extra) -> None:
        _GANG_METRICS.get()["verdicts"].inc(verdict=verdict)
        resilience_measures("parallel").count("gang_abort")
        self._record(event="verdict", verdict=verdict, **extra)
        self._broadcast({"t": "verdict", "verdict": verdict, **extra})

    def _broadcast(self, payload: dict) -> None:
        for m in self.members.values():
            if not m.alive:
                continue
            try:
                with m.lock:
                    _send_line(m.conn, payload)
            except OSError:
                pass  # the reader thread will notice the dead conn

    # -- the emergency-checkpoint dance -------------------------------------
    def request_checkpoint(self, reason: str = "driver request") -> None:
        """Kick off the coordinated emergency checkpoint (idempotent; runs
        on its own thread — the caller may be a reader). Outcome lands in
        ``preempt_commit_step`` / the event log."""
        if self._dance.is_set():
            return
        self._dance.set()
        t = threading.Thread(target=self._run_dance, args=(reason,),
                             daemon=True)
        t.start()
        self._threads.append(t)

    def _run_dance(self, reason: str) -> None:
        deadline = Deadline(self.grace_s)
        self._broadcast_verdict("abort_and_checkpoint", reason=reason)
        live = [m for m in self.members.values() if m.alive]
        while not deadline.expired():
            if self.failure is not None:
                return  # a member died mid-dance: resize already sent
            if all(m.ready_step is not None for m in live
                   if m.alive and m.done_code is None):
                break
            time.sleep(self.poll_s)
        readys = [m.ready_step for m in live if m.ready_step is not None]
        if not readys or deadline.expired():
            self._record(event="dance_failed", phase="ready",
                         reason="grace window expired")
            self._broadcast_verdict("resize",
                                    reason="emergency checkpoint "
                                           "could not synchronize")
            return
        sync_step = max(readys)
        self._record(event="sync", step=sync_step)
        self._broadcast({"t": "sync", "step": sync_step})
        while not deadline.expired():
            if self.failure is not None:
                return
            if all(m.ack_step == sync_step for m in live
                   if m.alive and m.done_code is None):
                break
            time.sleep(self.poll_s)
        target = None
        if self.checkpoint_dir is not None and not deadline.expired():
            target = commit_checkpoint(self.checkpoint_dir, sync_step,
                                       self.world, run_id=self.run_id)
        if target is None:
            self._record(event="dance_failed", phase="commit",
                         reason="ACK set incomplete inside grace window")
            self._broadcast_verdict("resize",
                                    reason="emergency checkpoint "
                                           "did not commit")
            return
        with self._lock:
            self.committed_steps.append(sync_step)
        self.preempt_commit_step = sync_step
        _GANG_METRICS.get()["commits"].inc(kind="emergency")
        self._record(event="committed", step=sync_step, kind="emergency")
        self._broadcast({"t": "committed", "step": sync_step})

    def _commit_scan(self) -> None:
        """Periodic-checkpoint committer: a step dir whose full ACK set has
        landed gets its DONE marker (+ retention GC). Workers never commit
        — a lone surviving worker must not be able to publish a world-N
        checkpoint that N-1 ranks never finished."""
        if self.checkpoint_dir is None:
            return
        # dir mtime_ns at the last FAILED commit attempt: any progress
        # (a new ACK or payload landing) bumps the step dir's mtime, so an
        # unchanged dir needs no re-parse — without this, a run whose ACKs
        # never satisfy the fence (or a slow straggler's half-written step)
        # costs a full ACK-set parse per dir every poll tick, forever
        attempted: dict[str, int] = {}
        while not self._stop.is_set():
            time.sleep(self.poll_s)
            try:
                seen = set()
                for d in sorted(os.listdir(self.checkpoint_dir)):
                    if not d.startswith("step_"):
                        continue
                    try:
                        step = int(d.split("_", 1)[1])
                    except ValueError:
                        continue
                    seen.add(d)
                    target = os.path.join(self.checkpoint_dir, d)
                    if os.path.exists(os.path.join(target, "DONE")):
                        attempted.pop(d, None)
                        continue
                    try:
                        mtime = os.stat(target).st_mtime_ns
                    except OSError:
                        continue
                    if attempted.get(d) == mtime:
                        continue  # nothing landed since the last attempt
                    if commit_checkpoint(self.checkpoint_dir, step,
                                         self.world,
                                         run_id=self.run_id) is not None:
                        attempted.pop(d, None)
                        with self._lock:
                            self.committed_steps.append(step)
                        _GANG_METRICS.get()["commits"].inc(kind="periodic")
                        self._record(event="committed", step=step,
                                     kind="periodic")
                        gc_checkpoints(self.checkpoint_dir, self.keep,
                                       verified_cache=self._verified_cache)
                    else:
                        attempted[d] = mtime
                for gone in set(attempted) - seen:  # GC'd / pruned dirs
                    attempted.pop(gone, None)
            except OSError:
                continue


class GangWorker:
    """Worker side of the gang channel (one per training process).

    ``heartbeat(step)`` is wired into the per-step fit loop
    (``Trainer.fit(gang=...)``; the ``supervisor.heartbeat(step)`` seam
    feeds the same call in supervised runs). ``check(step)`` surfaces the
    driver's verdicts; the fit loop turns them into :class:`GangAborted`
    (resize) or the emergency-checkpoint dance + :class:`Preempted`.
    ``install_preemption_hook()`` converts SIGTERM (the cloud preemption
    notice) into the ``preempt`` message at the next step boundary.
    """

    def __init__(self, sock: socket.socket, rank: int, world: int,
                 grace_s: float = 20.0, run_id: str | None = None):
        self.sock = sock
        self.rank = int(rank)
        self.world = int(world)
        self.grace_s = float(grace_s)
        # the rendezvous reply's run_id; fit_gang_source stamps every
        # shard ACK with it so the driver's commit fence recognizes THIS
        # incarnation's writes
        self.run_id = run_id
        self.driver_lost = False
        self._verdict: str | None = None
        self._sync_step: int | None = None
        self._committed_step: int | None = None
        self._preempt_flag = False
        self._preempt_sent = False
        self._ready_sent = False
        self._send_lock = threading.Lock()
        self._thread: threading.Thread | None = None

    def start(self) -> "GangWorker":
        self._thread = threading.Thread(target=self._reader, daemon=True)
        self._thread.start()
        return self

    def _reader(self) -> None:
        try:
            for line in self.sock.makefile("r"):
                try:
                    msg = json.loads(line)
                except json.JSONDecodeError:
                    continue
                t = msg.get("t")
                if t == "verdict":
                    # resize overrides an in-flight dance (a member died)
                    v = msg.get("verdict")
                    if self._verdict != "resize":
                        self._verdict = v
                elif t == "sync":
                    self._sync_step = int(msg["step"])
                elif t == "committed":
                    self._committed_step = int(msg["step"])
        except (OSError, ValueError):
            pass
        finally:
            self.driver_lost = True

    def _send(self, payload: dict) -> None:
        if self.driver_lost:
            return
        try:
            with self._send_lock:
                _send_line(self.sock, payload)
        except OSError:
            self.driver_lost = True  # keep training; the driver is gone

    # -- the per-step seam --------------------------------------------------
    def heartbeat(self, step: int) -> None:
        """One beat per optimizer step. Consults the ``gang`` fault plane
        first: a ``drop`` spec suppresses the send (missed-beat chaos), a
        ``crash`` spec kills this worker at an exact step."""
        plan = active_fault_plan()
        if plan is not None and plan.on_gang(
                f"beat:rank={self.rank}:step={int(step)}"):
            return
        self._send({"t": "beat", "rank": self.rank, "step": int(step)})

    def check(self, step: int):
        """Poll the driver's verdict at a step boundary. Returns None
        (keep training), ``"resize"`` (exit now, resume from the last
        commit), or ``("sync", S)`` — train to step S, checkpoint, then
        :meth:`ack_and_wait_commit`."""
        if self._preempt_flag and not self._preempt_sent:
            self._preempt_sent = True
            self._send({"t": "preempt", "rank": self.rank})
        v = self._verdict
        if v == "resize":
            return "resize"
        if v == "abort_and_checkpoint":
            if not self._ready_sent:
                self._ready_sent = True
                self._send({"t": "ready", "rank": self.rank,
                            "step": int(step)})
            deadline = Deadline(self.grace_s)
            while self._sync_step is None:
                if self._verdict == "resize" or self.driver_lost \
                        or deadline.expired():
                    return "resize"
                time.sleep(0.02)
            return ("sync", self._sync_step)
        return None

    def ack_and_wait_commit(self, step: int,
                            timeout_s: float | None = None) -> bool:
        """Phase-2 handshake after the local shard write: ack, then wait
        for the driver's ``committed`` broadcast. False = the commit never
        landed (treat as resize: the last PERIODIC commit is the resume
        point)."""
        self._send({"t": "ack", "rank": self.rank, "step": int(step)})
        deadline = Deadline(timeout_s if timeout_s is not None
                            else self.grace_s)
        while self._committed_step != int(step):
            if self._verdict == "resize" or self.driver_lost \
                    or deadline.expired():
                return False
            time.sleep(0.02)
        return True

    def preempt(self) -> None:
        """Mark this worker preempted (the SIGTERM hook body): the next
        ``check()`` forwards the notice to the driver."""
        self._preempt_flag = True

    def install_preemption_hook(self, signum: int = signal.SIGTERM) -> None:
        """SIGTERM = the cloud's preemption notice. The handler only sets
        a flag — all real work (socket send, checkpoint) happens at the
        next step boundary, inside the grace window."""
        signal.signal(signum, lambda *_: self.preempt())

    def close(self, code: str = "done") -> None:
        """Orderly exit: tell the driver (so EOF is not read as a death),
        then close."""
        self._send({"t": "bye", "rank": self.rank, "code": code})
        try:
            self.sock.close()
        except OSError:
            pass


class ElasticResume:
    """What :func:`elastic_restore` hands the worker: the reassembled
    global train-state tree, the committed ``step``, the rebuilt
    :class:`~synapseml_tpu.data.state.ElasticPlan` (None for single-host
    checkpoints) and the rank-0 ``meta`` dict."""

    def __init__(self, step: int, tree, plan, meta: dict):
        self.step = int(step)
        self.tree = tree
        self.plan = plan
        self.meta = dict(meta)


def elastic_restore(checkpoint_dir: str) -> ElasticResume | None:
    """Restore the latest VERIFIED committed checkpoint for an elastic
    resume on any surviving world size. Returns None when the directory
    holds no committed checkpoint (fresh start).

    The global tree reassembles from the N per-rank shards host-side
    (``restore_checkpoint``); params and optimizer state then re-derive
    their :class:`PartitionSpec` placement from the trainer's rule table
    exactly as any restore does (``Trainer.resume_state`` →
    ``checkpoint_sharding_fn``) — the M-survivor mesh reshards without a
    host ever holding a device-resident full copy. The per-rank
    ``data_iter`` cursors become the :class:`ElasticPlan` that maps the N
    virtual streams onto the survivors."""
    from ..data.state import ElasticPlan

    step = latest_verified_step(checkpoint_dir)
    if step is None:
        return None
    # the scan above already hashed every payload; committed checkpoints
    # are immutable, so the restore reads skip re-verification — recovery
    # time is on the bench's recovery_s critical path
    tree = restore_checkpoint(checkpoint_dir, step, verify=False)
    world = checkpoint_world(checkpoint_dir, step)
    meta = checkpoint_meta(checkpoint_dir, step)
    plan = None
    if world is not None:
        host_states = restore_host_states(checkpoint_dir, step,
                                          verify=False)
        orig = int(meta.get("orig_world", world))
        plan = ElasticPlan.from_host_states(orig, host_states)
    resilience_measures("parallel").count("gang_resume")
    return ElasticResume(step=step, tree=tree, plan=plan, meta=meta)


def run_gang_member(driver_address: str, partition_id: int, *,
                    trainer_fn, source, checkpoint_dir: str,
                    total_steps: int, batch_size: int, seed: int,
                    checkpoint_every: int = 10, grace_s: float = 60.0,
                    executor_id: str | None = None, on_exit=None,
                    **fit_kwargs) -> int:
    """One process's whole gang-member lifecycle, protocol included:
    rendezvous (keep-alive) → :class:`GangWorker` stamped with the
    rendezvous ``run_id`` → SIGTERM preemption hook →
    :func:`~synapseml_tpu.models.trainer.fit_gang_source` → orderly
    ``bye`` + exit-code mapping. Returns the code a launcher should
    ``sys.exit()`` with: 0 (done), :data:`EXIT_PREEMPTED` (emergency
    checkpoint committed — relaunch to resume) or :data:`EXIT_RESIZE`
    (a member died — relaunch on the survivors).

    ``trainer_fn(info)`` builds this rank's Trainer from the rendezvous
    reply (``info["rank"]``/``info["world"]``) — mesh construction is the
    caller's (each host builds over ITS OWN devices). ``on_exit(kind,
    payload)`` observes the outcome: ``("done", TrainState)``,
    ``("preempted", Preempted)`` or ``("resize", GangAborted)``. Extra
    keyword args pass through to ``fit_gang_source`` (epochs,
    shuffle_rows, callback, ...). This is the ONE copy of the worker
    protocol — the chaos tests and the kill-and-resume bench both launch
    through it."""
    from ..models.trainer import fit_gang_source
    from .backend import worker_rendezvous

    info, sock = worker_rendezvous(
        driver_address, executor_id or f"exec-{partition_id}",
        int(partition_id), keep_alive=True)
    gw = GangWorker(sock, info["rank"], info["world"], grace_s=grace_s,
                    run_id=info.get("run_id")).start()
    gw.install_preemption_hook()
    trainer = trainer_fn(info)
    try:
        state = fit_gang_source(
            trainer, source, batch_size=batch_size,
            total_steps=total_steps, seed=seed, gang=gw,
            checkpoint_dir=checkpoint_dir, rank=info["rank"],
            world=info["world"], checkpoint_every=checkpoint_every,
            **fit_kwargs)
    except Preempted as e:
        if on_exit is not None:
            on_exit("preempted", e)
        gw.close("preempted")
        return EXIT_PREEMPTED
    except GangAborted as e:
        if on_exit is not None:
            on_exit("resize", e)
        gw.close("resize")
        return EXIT_RESIZE
    if on_exit is not None:
        on_exit("done", state)
    gw.close("done")
    return 0


def launch_gang_processes(script_path: str, world: int, *,
                          checkpoint_dir: str, worker_args_fn,
                          env: dict | None = None,
                          coordinator_kw: dict | None = None,
                          rendezvous_timeout_s: float = 120.0):
    """Launcher side of :func:`run_gang_member`: spawn one OS process per
    rank running ``script_path`` (a worker script built on
    ``run_gang_member``), bootstrap the keep-alive rendezvous, and start
    the :class:`GangCoordinator` over the live sockets. A failed launch
    (worker import error, rendezvous timeout) kills every spawned process
    before re-raising — it must never orphan live training subprocesses.

    ``worker_args_fn(rank, addr)`` returns the argv AFTER the interpreter
    and script (the worker's own parameters). Returns ``(procs, coord,
    driver)``; pair with :func:`finish_gang_processes`. The chaos tests
    and the kill-and-resume bench both launch through here — this is the
    ONE copy of the bootstrap/teardown ordering."""
    import subprocess
    import sys

    from ..core.platform import check_chip_launch
    from .backend import DriverRendezvous

    check_chip_launch(int(world), os.environ if env is None else env)
    driver = DriverRendezvous(world_size=int(world), keep_alive=True)
    driver.start()
    addr = f"127.0.0.1:{driver.port}"
    procs = [subprocess.Popen(
        [sys.executable, script_path, *worker_args_fn(p, addr)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env)
        for p in range(int(world))]
    # drain each worker's pipe from launch: a worker writing more than the
    # OS pipe buffer (XLA warnings, a traceback) would otherwise block in
    # write() mid-step, stop heartbeating, and get a healthy gang resized
    for p in procs:
        buf: list[str] = []
        t = threading.Thread(target=lambda f=p.stdout, b=buf:
                             b.extend(iter(f.readline, "")), daemon=True)
        t.start()
        p._gang_drain = (t, buf)
    try:
        driver.join(timeout_s=rendezvous_timeout_s)
        coord = driver.gang(checkpoint_dir=checkpoint_dir,
                            **(coordinator_kw or {}))
    except BaseException:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        raise
    return procs, coord, driver


def finish_gang_processes(procs, coord, *, timeout_s: float = 120.0,
                          wait_commit_step: int | None = None):
    """Teardown side of :func:`launch_gang_processes`: drain every
    worker's output, optionally wait for the commit scanner's poll tick
    on ``wait_commit_step`` (the last ACKs land right before the workers
    exit), then unconditionally kill stragglers and close the
    coordinator. Returns ``(outputs, exit_codes)``."""
    outs, codes = [], []
    try:
        for p in procs:
            p.wait(timeout=timeout_s)
            drain, buf = getattr(p, "_gang_drain", (None, None))
            if drain is not None:
                drain.join(timeout=10.0)
                outs.append("".join(buf))
            else:  # launched outside launch_gang_processes
                out, _ = p.communicate(timeout=timeout_s)
                outs.append(out)
            codes.append(p.returncode)
        if wait_commit_step is not None:
            coord.wait_commit(step=wait_commit_step, timeout_s=15)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        coord.close()
    return outs, codes
