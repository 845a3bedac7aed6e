"""Sharded checkpoint/resume keyed by mesh (orbax-backed, npz fallback).

Reference checkpointing is model-level: LightGBM ``modelString`` carry-over
(``LightGBMBase.scala:48-60``), VW ``initialModel`` bytes, pytorch-lightning
ModelCheckpoint (SURVEY.md §5). TPU equivalent: orbax sharded checkpoints that
restore onto a different mesh topology (host-side numpy round-trip when orbax
is unavailable or the target is single-process).
"""

from __future__ import annotations

import concurrent.futures
import contextlib
import hashlib
import json
import logging
import os
import shutil
import threading
from typing import Any

import jax
import numpy as np

from ..core import serialization

__all__ = ["save_checkpoint", "restore_checkpoint", "latest_step",
           "latest_verified_step", "verify_checkpoint",
           "CheckpointCorrupt", "checkpoint_sharding", "AsyncCheckpointer",
           # coordinated multi-host checkpoints (two-phase commit)
           "save_checkpoint_shard", "commit_checkpoint", "checkpoint_world",
           "restore_host_states", "checkpoint_meta", "gc_checkpoints"]

_logger = logging.getLogger("synapseml_tpu.parallel.checkpoint")

# serializes the commit write side (sweep + DONE install): the emergency
# dance and the periodic commit scanner are different threads of one
# coordinator and can try to commit the SAME complete step concurrently
_commit_lock = threading.Lock()

# per-checkpoint-dir verification memo for the save_checkpoint(keep=) path
# (AsyncCheckpointer and GangCoordinator thread their own instance caches)
_gc_memo: dict[str, dict] = {}


class CheckpointCorrupt(RuntimeError):
    """A checkpoint payload failed its sha256 sidecar verification — the
    file is torn or bit-rotted, not merely incomplete."""


def _step_dir(path: str, step: int) -> str:
    return os.path.join(path, f"step_{step:010d}")


def _to_host(keypath, x):
    """Host-side numpy for one leaf. A leaf spanning other processes
    cannot be fetched by the SINGLE-host npz writer (no host holds the
    full value) — point the caller at the coordinated per-host shard
    writer instead of surfacing jax's generic non-addressable fetch
    failure mid-write."""
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        from .partition import tree_path_name

        raise ValueError(
            f"checkpoint leaf {tree_path_name(keypath)!r} is sharded "
            "across processes — the single-host npz writer cannot gather "
            "it. Use the coordinated multi-host path: every process calls "
            "save_checkpoint_shard(...) (each writes only its locally-"
            "addressable shard slices) and the driver commits via "
            "commit_checkpoint(...); restore_checkpoint reassembles the "
            "shards on ANY number of surviving hosts")
    return np.asarray(x)


def save_checkpoint(path: str, tree: Any, step: int = 0, use_orbax: bool | None = None,
                    sharding: dict | None = None, keep: int | None = None) -> str:
    """Save a pytree (params/opt state). Device arrays are fetched host-side
    first so the artifact is topology-independent. ``sharding`` (the
    partition-plane manifest section: rule table + mesh config) is written
    as ``sharding.json`` beside the state, so a restore on ANY topology
    knows the placement the run declared (``checkpoint_sharding`` reads
    it back; ``parallel.partition.checkpoint_sharding_fn`` turns it into
    per-leaf shard-slice restores). ``keep`` runs :func:`gc_checkpoints`
    after the write — retain only the last ``keep`` verified steps."""
    target = _step_dir(path, step)
    os.makedirs(target, exist_ok=True)
    host_tree = jax.tree_util.tree_map_with_path(_to_host, tree)
    if use_orbax is None:
        use_orbax = False  # npz path is deterministic + dependency-light; orbax opt-in
    if use_orbax:
        import orbax.checkpoint as ocp

        ckptr = ocp.PyTreeCheckpointer()
        ckptr.save(os.path.join(target, "orbax"), host_tree, force=True)
    else:
        payloads = serialization.save_pytree(host_tree,
                                             os.path.join(target, "state"))
    if sharding:
        with open(os.path.join(target, "sharding.json"), "w") as f:
            json.dump(sharding, f, indent=2, sort_keys=True)
    if not use_orbax:
        # sha256 sidecar per payload (state.npz, the part files a large
        # state spills into, AND the tree/sharding JSON — a torn tree.json
        # would otherwise pass verification then die as an opaque
        # JSONDecodeError), written BEFORE the DONE marker: restore
        # verifies against them and demotes a torn step to the previous
        # completed one
        for payload in payloads + [os.path.join(target, "sharding.json")]:
            _write_digest_sidecar(payload)
    with open(os.path.join(target, "DONE"), "w") as f:
        f.write(str(step))
    if keep is not None:
        # persistent per-path memo: committed checkpoints are immutable,
        # so without it every save would re-hash the full payload of all
        # retained steps ON THE TRAINING THREAD; the just-written step is
        # seeded (its sidecars were computed from the on-disk bytes)
        cache = _gc_memo.setdefault(os.path.abspath(path), {})
        if not use_orbax:
            cache[int(step)] = True
        gc_checkpoints(path, keep, verified_cache=cache)
    return target


def _sidecar_path(payload_path: str) -> str:
    return payload_path + ".sha256"


def _sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _write_digest_sidecar(payload_path: str) -> None:
    if not os.path.isfile(payload_path):
        return
    with open(_sidecar_path(payload_path), "w") as f:
        f.write(_sha256_file(payload_path))


# ---------------------------------------------------------------------------
# coordinated multi-host sharded checkpoints (two-phase commit)
# ---------------------------------------------------------------------------
#
# Layout of one committed N-host step dir:
#
#   step_0000000012/
#     state.shard00000-of-00004.npz    # rank 0: every fully-addressable
#     state.shard00000-of-00004.json   #   (replicated) leaf + its chunks
#     state.shard00001-of-00004.npz    # ranks > 0: only locally-addressable
#     ...                              #   chunks + their per-host payload
#     *.sha256                         # integrity sidecars per payload
#     state.tree.json                  # global tree structure (rank 0)
#     sharding.json                    # optional partition-plane section
#     ACK.00001-of-00004               # phase 1: rank i's payload is durable
#     DONE                             # phase 2: the driver's COMMIT marker
#
# Phase 1: each process writes its shard npz + manifest + sidecars, fsyncs,
# then drops its ACK. Phase 2: the driver (gang coordinator) sees the full
# ACK set and writes DONE (JSON: step + world). A write torn ANYWHERE —
# missing shard, missing ACK, no DONE, bit-rot — is never restorable:
# completeness requires DONE + every shard, and the sha256 sidecars make a
# torn payload surface as :class:`CheckpointCorrupt` instead of garbage.

def _shard_stem(rank: int, world: int) -> str:
    return f"state.shard{rank:05d}-of-{world:05d}"


def _ack_name(rank: int, world: int) -> str:
    return f"ACK.{rank:05d}-of-{world:05d}"


def _fsync_file(path: str) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _flatten_leaves(tree) -> dict:
    """Slash-joined path -> RAW leaf: the ONE shared serialization codec
    with an identity leaf_fn (no np.asarray — leaves may be cross-process
    jax arrays), so shard assembly rebuilds through the same structure
    JSON as the single-file format and the schemes cannot drift."""
    return serialization.flatten_pytree(tree, leaf_fn=lambda x: x)


def _local_chunks(leaf):
    """The locally-addressable pieces of a cross-process array as
    ``[(start_indices, stop_indices, np.ndarray)]`` (deduped — replicated-
    over-local-devices shards appear once)."""
    chunks, seen = [], set()
    for s in leaf.addressable_shards:
        idx = tuple(s.index)
        shape = leaf.shape
        key = tuple((sl.start or 0, sl.stop if sl.stop is not None else dim)
                    for sl, dim in zip(idx, shape))
        if key in seen:
            continue
        seen.add(key)
        chunks.append(([k[0] for k in key], [k[1] for k in key],
                       np.asarray(s.data)))
    return chunks


def save_checkpoint_shard(path: str, tree: Any, step: int, *,
                          process_index: int, process_count: int,
                          host_tree: Any | None = None,
                          sharding: dict | None = None,
                          meta: dict | None = None,
                          chunk_fn=None, run_id: str | None = None) -> str:
    """Phase 1 of a coordinated multi-host checkpoint: write THIS process's
    shard of ``tree`` (call on every process, same ``step``).

    Per leaf: a cross-process ``jax.Array`` contributes only this host's
    locally-addressable shard slices (index ranges recorded in the shard
    manifest); a fully-addressable leaf is written whole by rank 0 only.
    ``host_tree`` is per-host payload (e.g. the loader's ``data_iter``
    cursor) — every rank stores its own copy, and
    :func:`restore_host_states` returns all of them (the N→M elastic
    resume input). ``meta`` (rank 0) records run-level facts like the
    gang's original world size. ``chunk_fn(path_name, leaf) ->
    [(start, stop, array)] | None`` overrides chunk extraction (tests,
    host-side ZeRO states). ``run_id`` stamps the ACK with this launch's
    incarnation — the driver's :func:`commit_checkpoint` fences on it, so
    a STALE ack left by a killed previous run can never combine with the
    new run's acks into a commit over a payload still being overwritten.

    Ends by dropping this rank's ACK marker. NO ``DONE`` is written here —
    the checkpoint only becomes restorable when the driver, having seen
    every ACK, runs :func:`commit_checkpoint` (phase 2)."""
    if not 0 <= int(process_index) < int(process_count):
        raise ValueError(f"process_index {process_index} outside world "
                         f"{process_count}")
    rank, world = int(process_index), int(process_count)
    target = _step_dir(path, step)
    os.makedirs(target, exist_ok=True)
    stem = _shard_stem(rank, world)
    flat = _flatten_leaves(tree)
    payload: dict[str, np.ndarray] = {}
    manifest: dict = {"rank": rank, "world": world, "step": int(step),
                      "globals": [], "chunks": {}, "host": None}
    for name, leaf in flat.items():
        chunks = chunk_fn(name, leaf) if chunk_fn is not None else None
        if chunks is None and isinstance(leaf, jax.Array) \
                and not leaf.is_fully_addressable:
            chunks = _local_chunks(leaf)
        if chunks is not None:
            parts = []
            for k, (start, stop, arr) in enumerate(chunks):
                key = f"c:{name}#{k}"
                payload[key] = np.asarray(arr)
                parts.append({"key": key,
                              "start": [int(x) for x in start],
                              "stop": [int(x) for x in stop]})
            shape = getattr(leaf, "shape", None)
            if shape is None:
                shape = np.shape(leaf)
            manifest["chunks"][name] = {
                "shape": [int(s) for s in shape],
                "dtype": str(np.dtype(getattr(leaf, "dtype", np.float32))),
                "parts": parts}
        elif rank == 0:
            payload[f"g:{name}"] = np.asarray(leaf)
            manifest["globals"].append(name)
    if host_tree is not None:
        for name, leaf in serialization.flatten_pytree(host_tree).items():
            payload[f"h:{name}"] = leaf
        manifest["host"] = serialization.tree_structure(host_tree)
    if rank == 0 and meta:
        manifest["meta"] = dict(meta)
    written = [stem + ".npz", stem + ".json"]
    np.savez(os.path.join(target, stem + ".npz"), **payload)
    with open(os.path.join(target, stem + ".json"), "w") as f:
        json.dump(manifest, f, sort_keys=True)
    if rank == 0:
        with open(os.path.join(target, "state.tree.json"), "w") as f:
            json.dump(serialization.tree_structure(tree), f)
        written.append("state.tree.json")
        if sharding:
            with open(os.path.join(target, "sharding.json"), "w") as f:
                json.dump(sharding, f, indent=2, sort_keys=True)
            written.append("sharding.json")
    for name in written:
        _fsync_file(os.path.join(target, name))
        _write_digest_sidecar(os.path.join(target, name))
    ack = os.path.join(target, _ack_name(rank, world))
    payload = {"step": int(step), "rank": rank, "files": written}
    if run_id is not None:
        payload["run"] = str(run_id)
    # temp + rename, never in place: the driver's commit scanner may read
    # the ACK at any instant (an empty/partial ACK would fail the parse),
    # and the rename bumps the step dir's mtime — the scanner's
    # nothing-changed gate relies on it, including when a relaunch
    # overwrites a torn dir's files under their existing names
    tmp = ack + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f)
    _fsync_file(tmp)
    os.replace(tmp, ack)
    return target


def commit_checkpoint(path: str, step: int, process_count: int,
                      run_id: str | None = None) -> str | None:
    """Phase 2 (driver side): verify the full ACK set for ``step`` — every
    rank's marker present, stamped with THIS run's ``run_id`` (when given),
    and every file each ACK lists on disk — then write the ``DONE`` COMMIT
    marker. Returns the step dir, or None when the set is still incomplete
    (commit later, or never: an uncommitted dir is invisible to
    ``latest_step``/restore). The run-id fence matters on resume: a killed
    run's leftover ACK in a torn dir must not combine with the new run's
    ACKs while the new incarnation is still overwriting the payload."""
    world = int(process_count)
    target = _step_dir(path, step)
    if not os.path.isdir(target):
        return None
    fenced = 0
    acked: set[str] = set()
    for rank in range(world):
        ack = os.path.join(target, _ack_name(rank, world))
        if not os.path.isfile(ack):
            return None
        try:
            with open(ack) as f:
                data = json.load(f)
            listed = data.get("files", [])
        except (OSError, json.JSONDecodeError):
            return None
        if run_id is not None and data.get("run") != str(run_id):
            fenced += 1  # stale ack from a previous incarnation
            continue
        if any(not os.path.isfile(os.path.join(target, name))
               for name in listed):
            return None
        acked.update(listed)
    if fenced:
        # The ACK set is otherwise complete — only the run-id fence blocks
        # the commit. A torn relaunch hits this transiently (the new
        # incarnation overwrites the acks), but a worker launched WITHOUT
        # the rendezvous run_id hits it forever: every checkpoint silently
        # stays uncommitted. Surface it once per (dir, step).
        _warn_run_fenced(path, step, fenced, world)
        return None
    # Serialize the write side: the emergency dance and the periodic
    # commit scanner run on different coordinator threads and can reach a
    # complete ACK set for the SAME step simultaneously — without the
    # lock, both would race on the sweep and the DONE install (a torn
    # half-written DONE, or one thread's tmp vanishing under the other).
    done = os.path.join(target, "DONE")
    with _commit_lock:
        if os.path.exists(done):  # already committed (idempotent success)
            return target
        # Drop anything a PREVIOUS incarnation left in this reused step
        # dir (an N-world shard + sidecar a killed run wrote before an
        # N→M resume re-reached the same step): the driver is the only
        # writer left (every rank's ACK is in), and verify_checkpoint
        # hashes EVERY sidecar'd payload in the dir — one stale torn file
        # would brick the recommitted step as CheckpointCorrupt forever.
        keep = set(acked)
        keep.update(name + ".sha256" for name in acked)
        keep.update(_ack_name(r, world) for r in range(world))
        keep.add("DONE")
        try:
            for name in os.listdir(target):
                if name not in keep:
                    with contextlib.suppress(OSError):
                        os.remove(os.path.join(target, name))
        except OSError:
            pass
        tmp = f"{done}.tmp.{os.getpid()}.{threading.get_ident()}"
        with open(tmp, "w") as f:
            json.dump({"step": int(step), "world": world}, f)
        _fsync_file(tmp)
        os.replace(tmp, done)  # a torn DONE must never look committed
    return target


def _done_world(target: str) -> int | None:
    """World size recorded in a step dir's DONE marker (None: legacy
    single-host marker, or no marker)."""
    try:
        with open(os.path.join(target, "DONE")) as f:
            raw = f.read()
    except OSError:
        return None
    try:
        data = json.loads(raw)
    except json.JSONDecodeError:
        return None  # legacy plain-int marker
    return int(data["world"]) if isinstance(data, dict) and "world" in data \
        else None


def checkpoint_world(path: str, step: int) -> int | None:
    """How many processes wrote a committed step (None = single-host)."""
    return _done_world(_step_dir(path, step))


def _assemble_sharded(target: str, world: int) -> Any:
    """Reassemble the global tree from N shard files, host-side — the
    reader may be ANY number of processes (each reads all shards off the
    shared checkpoint dir; with a ``sharding_fn`` each then device_puts
    only its own slices). Chunk coverage is validated element-exactly:
    a manifest whose parts don't tile the recorded shape means a rank's
    write was torn or lost -> :class:`CheckpointCorrupt`."""
    with open(os.path.join(target, "state.tree.json")) as f:
        structure = json.load(f)
    flat: dict[str, np.ndarray] = {}
    # per leaf: element-wise coverage mask. A REPLICATED leaf yields the
    # identical full-range chunk from every rank (harmless re-writes); a
    # count-based check would let OVERLAPPING partial chunks compensate
    # for an uncovered hole (4+4 elements over an 8-element leaf can leave
    # [6:8] as uninitialized np.empty garbage) — the mask cannot be fooled
    covered: dict[str, np.ndarray] = {}
    for rank in range(world):
        stem = _shard_stem(rank, world)
        with open(os.path.join(target, stem + ".json")) as f:
            manifest = json.load(f)
        with np.load(os.path.join(target, stem + ".npz"),
                     allow_pickle=False) as npz:
            for name in manifest.get("globals", ()):
                flat[name] = npz[f"g:{name}"]
            for name, info in manifest.get("chunks", {}).items():
                shape = tuple(int(s) for s in info["shape"])
                if name not in flat:
                    flat[name] = np.empty(shape, dtype=np.dtype(info["dtype"]))
                    covered[name] = np.zeros(shape, dtype=bool)
                for part in info["parts"]:
                    idx = tuple(slice(a, b) for a, b in
                                zip(part["start"], part["stop"]))
                    flat[name][idx] = npz[part["key"]]
                    covered[name][idx] = True
    for name, mask in covered.items():
        if not mask.all():
            got, want = int(np.count_nonzero(mask)), int(mask.size)
            raise CheckpointCorrupt(
                f"sharded checkpoint leaf {name!r} assembled {got} of "
                f"{want} elements from {world} shard(s) — a rank's chunk "
                "set is missing or does not tile the leaf")
    return serialization.rebuild_pytree(structure, flat)


def restore_host_states(path: str, step: int | None = None,
                        verify: bool = True) -> dict[int, Any]:
    """Every rank's per-host payload (``host_tree`` at save time) from a
    committed multi-host checkpoint: ``{rank: tree}``. For a single-host
    checkpoint returns ``{}`` — the per-host state rides inside the main
    tree there. This is the elastic-resume input: N ``data_iter`` cursors
    that :class:`~synapseml_tpu.data.state.ElasticPlan` redistributes
    over M survivors."""
    if step is None:
        # latest_verified_step already hashed the chosen step's payloads —
        # re-verifying below would be a second full sha256 pass over every
        # shard on the recovery-time path
        step = latest_verified_step(path) if verify else latest_step(path)
        if step is None:
            raise FileNotFoundError(f"no completed checkpoint under {path}")
        verify = False
    target = _step_dir(path, step)
    world = _done_world(target)
    if world is None:
        return {}
    if verify and not verify_checkpoint(path, step):
        raise CheckpointCorrupt(
            f"checkpoint step {step} under {path} fails verification")
    out: dict[int, Any] = {}
    for rank in range(world):
        stem = _shard_stem(rank, world)
        with open(os.path.join(target, stem + ".json")) as f:
            manifest = json.load(f)
        if manifest.get("host") is None:
            continue
        with np.load(os.path.join(target, stem + ".npz"),
                     allow_pickle=False) as npz:
            flat = {k[2:]: npz[k] for k in npz.files if k.startswith("h:")}
        out[rank] = serialization.rebuild_pytree(manifest["host"], flat)
    return out


def checkpoint_meta(path: str, step: int | None = None) -> dict:
    """Rank 0's ``meta`` dict from a committed multi-host checkpoint
    (e.g. ``{"orig_world": N, "seed": s}``); ``{}`` for single-host."""
    if step is None:
        step = latest_verified_step(path)
        if step is None:
            return {}
    target = _step_dir(path, step)
    world = _done_world(target)
    if world is None:
        return {}
    with open(os.path.join(target, _shard_stem(0, world) + ".json")) as f:
        return json.load(f).get("meta") or {}


def verify_checkpoint(path: str, step: int) -> bool:
    """True iff every payload with a sha256 sidecar matches it. Payloads
    WITHOUT a sidecar (pre-sidecar checkpoints, orbax dirs) verify
    vacuously — verification tightens the contract, it must not brick
    every existing checkpoint on disk."""
    target = _step_dir(path, step)
    for name in os.listdir(target) if os.path.isdir(target) else ():
        if not name.endswith(".sha256"):
            continue
        payload = os.path.join(target, name[:-len(".sha256")])
        if not os.path.isfile(payload):
            return False
        with open(os.path.join(target, name)) as f:
            expected = f.read().strip()
        if _sha256_file(payload) != expected:
            return False
    return True


def latest_verified_step(path: str) -> int | None:
    """The newest completed step whose payloads pass sidecar verification —
    what a crash-safe resume (``continual.TrainSupervisor``) restores from.
    A failing step demotes to the previous completed one with ONE
    structured warning per corrupt step."""
    for step in reversed(_completed_steps(path)):
        if verify_checkpoint(path, step):
            return step
        _warn_corrupt(path, step)
    return None


_warned_run_fenced: set = set()


def _warn_run_fenced(path: str, step: int, fenced: int, world: int) -> None:
    """ONE structured warning per (path, step) whose complete ACK set is
    blocked from committing ONLY by the run-id fence — the scanner polls
    every tick and a persistent mismatch (a worker built without the
    rendezvous ``run_id``) would otherwise be an invisible no-commit."""
    key = (os.path.abspath(path), int(step))
    if key in _warned_run_fenced:
        return
    _warned_run_fenced.add(key)
    _logger.warning(json.dumps({
        "event": "checkpoint_commit_run_fenced",
        "path": path, "step": int(step),
        "fenced_acks": int(fenced), "world": int(world),
        "hint": "ACK run ids do not match this incarnation; pass the "
                "rendezvous reply's run_id to GangWorker/"
                "save_checkpoint_shard (transient during a torn relaunch)"}))


_warned_corrupt: set = set()


def _warn_corrupt(path: str, step: int) -> None:
    """ONE structured warning per corrupt (path, step) per process — the
    supervisor and loop re-scan frequently and must not spam the log."""
    key = (os.path.abspath(path), int(step))
    if key in _warned_corrupt:
        return
    _warned_corrupt.add(key)
    _logger.warning(json.dumps({
        "event": "checkpoint_verification_failed",
        "path": path, "step": int(step),
        "action": "demoted to previous completed step"}))


def checkpoint_sharding(path: str, step: int | None = None) -> dict | None:
    """The ``sharding`` section saved with a checkpoint (None when the run
    declared no rule table, or for pre-sharding-plane checkpoints). With
    ``step=None`` this reads the latest VERIFIED step — the same default
    every resume path uses, so a torn newest checkpoint cannot pair the
    previous step's params with the torn step's rule table."""
    if step is None:
        step = latest_verified_step(path)
        if step is None:
            return None
    target = os.path.join(_step_dir(path, step), "sharding.json")
    if not os.path.isfile(target):
        return None
    import json

    with open(target) as f:
        return json.load(f)


def _is_complete(target: str) -> bool:
    """A step dir counts only when the DONE marker AND the state payload
    both exist — a crash between payload write and marker (or a marker left
    beside a vanished payload) must never be restorable as 'latest'. A
    multi-host dir (DONE records a world size) additionally requires EVERY
    rank's ACK + shard payload: a commit marker beside a vanished shard is
    a torn write, not a checkpoint."""
    if not os.path.exists(os.path.join(target, "DONE")):
        return False
    world = _done_world(target)
    if world is not None:
        return all(
            os.path.isfile(os.path.join(target, _ack_name(r, world)))
            and os.path.isfile(os.path.join(
                target, _shard_stem(r, world) + ".npz"))
            for r in range(world))
    return (os.path.exists(os.path.join(target, "state.npz"))
            or os.path.isdir(os.path.join(target, "orbax")))


def _completed_steps(path: str) -> list[int]:
    """Steps with a fully written checkpoint. Partially-written dirs (no
    DONE / no payload — a crash mid-save) and malformed names are ignored,
    so ``latest_step``/``restore_checkpoint``/GC can never pick one up."""
    if not os.path.isdir(path):
        return []
    steps = []
    for d in os.listdir(path):
        if not d.startswith("step_"):
            continue
        try:
            step = int(d.split("_", 1)[1])
        except ValueError:
            continue  # foreign dir that merely looks like a step
        if _is_complete(os.path.join(path, d)):
            steps.append(step)
    return sorted(steps)


def latest_step(path: str) -> int | None:
    steps = _completed_steps(path)
    return steps[-1] if steps else None


def restore_checkpoint(path: str, step: int | None = None, sharding_fn=None,
                       verify: bool = True) -> Any:
    """Restore a checkpoint, optionally placing leaves as they load.

    ``verify`` (default on) checks every payload against its sha256
    sidecar first: with ``step=None`` a corrupt newest checkpoint demotes
    to the previous completed step (one structured warning — the "latest
    verified checkpoint" contract the training supervisor resumes on); an
    EXPLICITLY requested corrupt step raises :class:`CheckpointCorrupt`
    instead of returning garbage params.

    ``sharding_fn`` re-places leaves on the current mesh and accepts
    either signature:

    * ``fn(leaf) -> Sharding`` (legacy), or
    * ``fn(path_name, leaf) -> Sharding | None`` (path-aware — what
      ``parallel.partition.checkpoint_sharding_fn`` builds from a rule
      table; ``path_name`` is the slash-joined tree path, and returning
      None keeps that leaf host-side numpy, e.g. the loader's
      ``data_iter`` state).

    With a sharded target each ``device_put`` transfers only that
    device's shard slices — no host materializes a device-resident full
    copy of any leaf."""
    verified_already = False
    if step is None:
        if verify:
            step = latest_verified_step(path)
            verified_already = True  # don't re-hash the same payloads
        else:
            step = latest_step(path)
        if step is None:
            raise FileNotFoundError(f"no completed checkpoint under {path}")
    target = _step_dir(path, step)
    if not _is_complete(target):
        if os.path.isdir(target) and (
                _done_world(target) is not None
                or any(n.startswith(("state.shard", "ACK."))
                       for n in os.listdir(target))):
            # a partially-written MULTI-HOST dir: some phase-1 shards (or
            # even a commit marker beside a vanished shard) exist — that
            # is a torn coordinated write, distinct from "no such step"
            # (a legacy DONE with a vanished single-host payload stays a
            # FileNotFoundError, as before)
            raise CheckpointCorrupt(
                f"checkpoint step {step} under {path} is a torn multi-host "
                f"write (phase-1 shards without a complete commit) — "
                f"latest completed: {latest_step(path)}")
        raise FileNotFoundError(
            f"checkpoint step {step} under {path} is incomplete (crash "
            f"during save?) — latest completed: {latest_step(path)}")
    if verify and not verified_already and not verify_checkpoint(path, step):
        raise CheckpointCorrupt(
            f"checkpoint step {step} under {path} fails its sha256 sidecar "
            f"verification (torn or bit-rotted payload) — latest verified: "
            f"{latest_verified_step(path)}")
    orbax_dir = os.path.join(target, "orbax")
    world = _done_world(target)
    if world is not None:
        tree = _assemble_sharded(target, world)
    elif os.path.isdir(orbax_dir):
        import orbax.checkpoint as ocp

        tree = ocp.PyTreeCheckpointer().restore(orbax_dir)
    else:
        tree = serialization.load_pytree(os.path.join(target, "state"))
    if sharding_fn is not None:
        import inspect

        try:
            # path-aware iff the callable REQUIRES two positional args —
            # a legacy one-leaf callback with extra defaulted params
            # (lambda leaf, mesh=m: ...) must keep its old contract
            sig = inspect.signature(sharding_fn)
            required = [p for p in sig.parameters.values()
                        if p.kind in (p.POSITIONAL_ONLY,
                                      p.POSITIONAL_OR_KEYWORD)
                        and p.default is p.empty]
            path_aware = len(required) >= 2
        except (TypeError, ValueError):
            path_aware = False
        if path_aware:
            from .partition import place_leaf, tree_path_name

            def place(keypath, x):
                sh = sharding_fn(tree_path_name(keypath), x)
                return x if sh is None else place_leaf(x, sh)

            tree = jax.tree_util.tree_map_with_path(place, tree)
        else:
            tree = jax.tree.map(
                lambda x: jax.device_put(x, sharding_fn(x)), tree)
    return tree


def gc_checkpoints(path: str, keep: int,
                   verified_cache: dict | None = None) -> list[int]:
    """Retention GC: keep the last ``keep`` VERIFIED step dirs; prune every
    completed step older than the oldest kept one. The newest verified step
    is never pruned, and nothing newer than it is touched (an unverified-
    but-newer completed dir may be a checkpoint another process is still
    committing — the restore path already demotes past it). Corrupt
    (completed-but-unverified) steps OLDER than the newest verified one are
    pruned too: they can never be restored, only re-warn on every scan.

    ``verified_cache`` (a mutable dict ``{step: bool}``) memoizes
    verification outcomes — committed checkpoints are immutable, so a
    week-long run doesn't re-hash its whole history every save, and a
    bit-rotted newest dir (kept by the newer-than-verified guard, FAILING
    verification) isn't re-hashed on every call either.
    Returns the pruned steps."""
    if keep < 1:
        raise ValueError(f"keep must be >= 1, got {keep}")
    done = _completed_steps(path)
    cache = verified_cache if verified_cache is not None else {}

    def _check(step: int) -> bool:
        if step not in cache:
            cache[step] = verify_checkpoint(path, step)
        return cache[step]

    pruned = []
    # torn dirs first: an INCOMPLETE step older than the newest COMPLETED
    # one can never commit (per-worker saves are ordered, so every rank
    # has already moved past it — a full ACK set can no longer form).
    # Phase-1 shards a killed run left behind, a vanished payload: without
    # this, a preemption-heavy week accumulates torn dirs unboundedly and
    # the gang's commit scanner re-stats them forever. This is the ONE
    # torn-dir retention policy — AsyncCheckpointer._gc rides it too.
    if done:
        for d in os.listdir(path) if os.path.isdir(path) else ():
            if not d.startswith("step_"):
                continue
            try:
                step = int(d.split("_", 1)[1])
            except ValueError:
                continue
            target = os.path.join(path, d)
            if step < done[-1] and not _is_complete(target):
                shutil.rmtree(target, ignore_errors=True)
                pruned.append(step)
    verified = [s for s in done if _check(s)]
    if not verified:
        return sorted(set(pruned))
    kept = set(verified[-keep:])
    newest_verified = verified[-1]
    for step in done:
        if step >= newest_verified or step in kept:
            continue
        shutil.rmtree(_step_dir(path, step), ignore_errors=True)
        cache.pop(step, None)
        pruned.append(step)
    return sorted(set(pruned))


class AsyncCheckpointer:
    """Checkpoint writes that overlap with training.

    ``save`` is non-blocking on the device→host transfer: device leaves get
    an async on-device copy (``jnp.copy`` — an enqueued dispatch, so the
    caller may donate or mutate its own state the moment ``save`` returns)
    with ``copy_to_host_async`` started immediately; the blocking
    ``np.asarray`` fetch AND serialization + fsync run on a single
    background thread. This is the TPU-idiomatic replacement for the
    reference's synchronous pytorch-lightning ModelCheckpoint. Backpressure
    mirrors orbax's AsyncCheckpointer: at most ONE write is in flight — a
    ``save`` while the previous write is still running blocks until it
    completes (surfacing its error), so snapshots can never queue
    unboundedly and OOM the host on 7B-class states. One worker thread
    keeps saves ordered; ``keep`` retains only the most recent completed
    checkpoints (top-k retention, like the reference's ``save_top_k``).

    Call ``wait()`` (or use as a context manager) before reading checkpoints
    or exiting — the last write's errors surface there.
    """

    def __init__(self, path: str, keep: int = 3, use_orbax: bool = False,
                 sharding: dict | None = None, process_index: int = 0,
                 process_count: int = 1, host_state_key: str = "data_iter",
                 meta: dict | None = None, coordinated: bool | None = None,
                 run_id: str | None = None):
        if keep < 1:
            raise ValueError(f"keep must be >= 1, got {keep}")
        self.path = path
        self.keep = keep
        self.use_orbax = use_orbax
        # the partition-plane manifest section written beside every step
        # (fit_source fills this in from the trainer's rule table)
        self.sharding = sharding
        # coordinated mode (process_count > 1, or coordinated=True for a
        # one-survivor elastic gang): each save writes THIS process's
        # shard via save_checkpoint_shard — the ``host_state_key`` subtree
        # (the loader cursor a _LoaderCheckpointer injects) moves into the
        # per-host payload, and the gang DRIVER commits/GCs once every
        # rank's ACK lands. Single-host mode is unchanged.
        self.process_index = int(process_index)
        self.process_count = int(process_count)
        self.coordinated = (self.process_count > 1 if coordinated is None
                            else bool(coordinated))
        self.host_state_key = host_state_key
        self.meta = meta
        self.run_id = run_id  # launch incarnation; fences stale ACKs
        self._verified_cache: dict = {}  # step -> verification outcome
        self._exec = concurrent.futures.ThreadPoolExecutor(max_workers=1)
        self._inflight: concurrent.futures.Future | None = None

    def save(self, tree: Any, step: int):
        """Snapshot (async dispatches only), write in the background;
        returns the Future. Blocks first iff the previous write is still
        running (single-pending backpressure)."""
        self.wait()  # at most one write in flight; surfaces prior errors

        import jax.numpy as jnp

        def snap(x):
            if isinstance(x, jax.Array):
                c = jnp.copy(x)  # async device-side copy; donation-safe
                try:
                    c.copy_to_host_async()  # start DMA; worker blocks on it
                except Exception:
                    pass  # some backends/shardings lack the fast path
                return c
            # np.array (not asarray) forces a copy for host-numpy leaves, so
            # callers may mutate their buffers the moment save() returns
            return np.array(x)

        snapshot = jax.tree.map(snap, tree)
        self._inflight = self._exec.submit(self._write, snapshot, step)
        return self._inflight

    def _write(self, snapshot: Any, step: int) -> str:
        if self.coordinated:
            # coordinated shard write: the per-host cursor leaves the
            # global tree (every rank keeps its own), non-addressable
            # leaves contribute only local slices, the DRIVER commits
            host_tree = None
            if isinstance(snapshot, dict) and self.host_state_key in snapshot:
                snapshot = dict(snapshot)
                host_tree = {self.host_state_key:
                             snapshot.pop(self.host_state_key)}
            return save_checkpoint_shard(
                self.path, snapshot, step,
                process_index=self.process_index,
                process_count=self.process_count,
                host_tree=host_tree, sharding=self.sharding, meta=self.meta,
                run_id=self.run_id)
        # the blocking device→host fetch happens HERE, off the train loop
        host_tree = jax.tree_util.tree_map_with_path(_to_host, snapshot)
        target = save_checkpoint(self.path, host_tree, step,
                                 use_orbax=self.use_orbax,
                                 sharding=self.sharding)
        # the digest sidecars were just computed FROM the on-disk bytes —
        # seeding the memo spares _gc a second full-payload hash per save
        # (on the single writer thread, where a long hash pass would stall
        # the next save()'s backpressure wait)
        self._verified_cache[int(step)] = True
        self._gc()
        return target

    def _gc(self) -> None:
        # keep-last-K VERIFIED retention: a week-long run must not fill
        # the disk, and the kept set must always include a restorable
        # (hash-clean) checkpoint — pruning by completion alone could
        # retain K torn dirs and nothing restorable
        # (gc_checkpoints also prunes crash-leftover partial dirs older
        # than the newest completed step — one torn-dir policy, one place)
        gc_checkpoints(self.path, self.keep,
                       verified_cache=self._verified_cache)

    def wait(self) -> None:
        """Block until the in-flight write (if any) finishes; re-raises its
        error. With single-pending backpressure there is at most one."""
        fut, self._inflight = self._inflight, None
        if fut is not None:
            fut.result()

    def close(self) -> None:
        self.wait()
        self._exec.shutdown(wait=True)

    def __enter__(self) -> "AsyncCheckpointer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
