"""AOT-compiled executable ladders: publish-time compilation, load-time reuse.

The TVM lesson (PAPERS.md, arXiv:1802.04799) applied to the deploy plane:
compile-time work belongs *offline*. Every ``/admin/load`` hot-swap used to
pay jit traces at warmup — bounded by the PR-4 bucket ladder, but still the
dominant cost of a fleet rollout, and heavy models had to cap default warmup
at small rungs to stay inside the deploy-plane load timeout. This module
moves that cost to ``registry.publish``:

* **Capture** (:class:`AOTCapture`) — during a publish-time warmup drive of
  the saved stage, every :class:`~synapseml_tpu.core.batching.CompiledCache`
  miss records its built jit and first-call arguments. ``export()`` then
  AOT-lowers each one (``jit(...).lower(...).compile()``) and serializes the
  compiled executable.
* **Mechanism feature-detection** (:func:`aot_mechanism`) — prefers the raw
  XLA executable round-trip (``client.serialize_executable`` /
  ``deserialize_executable``: a true zero-compile load), falls back to
  ``jax.export`` StableHLO blobs (skips Python tracing; XLA still compiles
  at load), and degrades to ``None`` (plain JIT warmup) when neither exists.
* **Keying** — every entry is addressed by ``(fn_id, bucket shape, dtype)``
  digest plus the *runtime fingerprint* ``(platform, jax, jaxlib, XLA-flags
  sha)``. A stale key can never load into the wrong runtime: any mismatch
  is a structured warning + JIT fallback, never a wrong executable.
* **Instance binding** — cache keys discriminate stage instances by
  process-local tokens (``core.batching.instance_token``), which cannot
  travel across processes. Entries instead record the *first-seen ordinal*
  of their instance during the publish warmup drive; at load the provider
  re-binds ordinals in first-seen order while the worker replays the SAME
  manifest-recorded warmup (rows + buckets), single-threaded. Two stages of
  one pipeline always fire in pipeline order under identical batch
  preparation, so ordinal ``k`` at load is the stage that was ordinal ``k``
  at publish. Binding is restricted to the warmup thread and frozen after
  it — a concurrent serve loop on the old pipeline can never pollute the
  ordering.
* **Load tier** (:class:`AOTExecutableSet`) — installed as a second tier on
  the ``CompiledCache``: a miss consults the artifact's executable blobs
  (sha256-verified on read) before tracing, so ``/admin/load`` maps in
  precompiled executables and the first post-swap request runs with zero
  compile stalls. Corrupt or missing blobs fall back to tracing per entry.
"""

from __future__ import annotations

import functools
import hashlib
import json
import logging
import os
import threading
import time

import numpy as np

from ..core import observability as obs
from .store import IntegrityError, _canonical_json

__all__ = [
    "AOTError", "AOTCapture", "AOTExecutableSet",
    "aot_mechanism", "runtime_fingerprint", "fingerprint_mismatch",
    "aot_key_digest", "capture_stage_ladder", "walk_stages",
    "emit_load_metrics",
]

logger = logging.getLogger("synapseml_tpu.registry.aot")

# deploy-plane warmup observability (satellite: the same fields the
# /admin/load reply breaks down, as synapseml_deploy_* series)
_AOT_METRICS = obs.HandleCache(lambda reg: {
    "io_ms": reg.histogram(
        "synapseml_deploy_warmup_io_ms",
        "per-swap wall time spent materializing + deserializing AOT "
        "executable blobs (plus registry resolve I/O)").labels(),
    "compile_ms": reg.histogram(
        "synapseml_deploy_warmup_compile_ms",
        "per-swap wall time spent tracing/compiling during warmup (zero "
        "when the full ladder rode the AOT path)").labels(),
    "aot_hits": reg.counter(
        "synapseml_deploy_aot_hits_total",
        "warmup cache misses served from AOT executable blobs").labels(),
    "aot_misses": reg.counter(
        "synapseml_deploy_aot_misses_total",
        "warmup cache misses with no matching AOT blob (traced "
        "instead)").labels(),
    "loaded": reg.counter(
        "synapseml_deploy_executables_loaded_total",
        "distinct precompiled executables deserialized at load").labels(),
    "traced": reg.counter(
        "synapseml_deploy_executables_traced_total",
        "executables traced+compiled during /admin/load warmup").labels(),
    "fallbacks": reg.counter(
        "synapseml_deploy_aot_fallbacks_total",
        "swaps that fell back to JIT warmup despite the artifact shipping "
        "AOT blobs", ("reason",)),
})


class AOTError(RuntimeError):
    """An AOT executable blob cannot serve the requested call."""


# ---------------------------------------------------------------------------
# mechanism feature-detection
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def aot_mechanism() -> str | None:
    """Best executable-serialization mechanism this jax/jaxlib supports:
    ``"xla"`` (raw ``serialize_executable`` round-trip — zero-compile
    loads), ``"export"`` (``jax.export`` StableHLO — skips tracing, XLA
    still compiles at load), or ``None`` (no AOT; plain JIT warmup). Probed
    once per process with a trivial program."""
    def _build_probe():
        import jax

        return jax.jit(lambda x: x + 1)

    try:
        import jax
        import jax.numpy as jnp

        comp = _build_probe().lower(
            jnp.zeros((2,), jnp.float32)).compile()
        rexec = comp.runtime_executable()
        blob = rexec.client.serialize_executable(rexec)
        # jaxlib 0.9.0 requires the devices the executable runs on
        de = rexec.client.deserialize_executable(
            bytes(blob), jax.local_devices()[:1])
        out = de.execute([jax.device_put(np.ones(2, np.float32))])
        if float(np.asarray(out[0])[0]) == 2.0:
            return "xla"
    except Exception as e:  # noqa: BLE001 - any probe failure just demotes
        # logged once (lru_cache): the next reader must see WHY the
        # mechanism is "export" and loads compile again
        logger.warning("aot_mechanism: serialize_executable probe failed, "
                     "demoting to jax.export: %s: %s", type(e).__name__, e)
    try:
        from jax import export as jexport

        del jexport
        return "export"
    except Exception as e:  # noqa: BLE001
        logger.warning("aot_mechanism: jax.export unavailable, no AOT: "
                     "%s: %s", type(e).__name__, e)
        return None


def runtime_fingerprint() -> dict:
    """The key components that make an executable blob loadable: platform,
    jax/jaxlib versions, and an XLA-flags fingerprint (device-count and
    optimization flags change compiled code and device topology)."""
    import jax
    import jaxlib

    return {
        "platform": jax.default_backend(),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "xla_flags_sha256": hashlib.sha256(
            os.environ.get("XLA_FLAGS", "").encode()).hexdigest(),
    }


def fingerprint_mismatch(recorded: dict, current: dict | None = None
                         ) -> str | None:
    """None when ``recorded`` matches the current runtime; otherwise a
    human-readable reason (the structured-warning payload — a stale key
    must never load into the wrong runtime)."""
    current = current or runtime_fingerprint()
    for field in ("platform", "jax", "jaxlib", "xla_flags_sha256"):
        want, got = recorded.get(field), current.get(field)
        if want != got:
            return (f"aot {field} mismatch: artifact compiled for "
                    f"{want!r}, runtime is {got!r}")
    return None


# ---------------------------------------------------------------------------
# keying + pytree template codec (JSON-safe — no pickle in artifacts)
# ---------------------------------------------------------------------------

def _jsonable(obj):
    """Canonical JSON-safe form of a cache-key component: tuples/lists
    collapse to lists (both sides of the digest pass through this), scalars
    stay, everything else stringifies."""
    if isinstance(obj, (tuple, list)):
        return [_jsonable(x) for x in obj]
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    return str(obj)


def aot_key_digest(fn_id: str, shape, dtype) -> str:
    """Stable cross-process digest of the (fn_id, bucket shape, dtype)
    portion of a CompiledCache key (the instance token is process-local and
    handled by ordinal binding instead)."""
    return hashlib.sha256(_canonical_json(
        [fn_id, _jsonable(shape), _jsonable(dtype)])).hexdigest()


def _encode_template(obj, counter: list) -> dict:
    """JSON template of a pytree built from dict/list/tuple/None/leaves,
    with leaf indices assigned in ``jax.tree_util.tree_flatten`` order
    (dicts traverse in sorted-key order). Raises TypeError on custom pytree
    nodes — those entries fall back to JIT."""
    if isinstance(obj, dict):
        keys = sorted(obj)
        return {"t": "d", "k": keys,
                "v": [_encode_template(obj[k], counter) for k in keys]}
    if isinstance(obj, (list, tuple)):
        return {"t": "l" if isinstance(obj, list) else "t",
                "v": [_encode_template(x, counter) for x in obj]}
    if obj is None:
        return {"t": "n"}
    idx = counter[0]
    counter[0] += 1
    return {"t": "x", "i": idx}


def _decode_template(template: dict, leaves):
    kind = template["t"]
    if kind == "d":
        return {k: _decode_template(v, leaves)
                for k, v in zip(template["k"], template["v"])}
    if kind in ("l", "t"):
        seq = [_decode_template(v, leaves) for v in template["v"]]
        return seq if kind == "l" else tuple(seq)
    if kind == "n":
        return None
    return leaves[template["i"]]


# ---------------------------------------------------------------------------
# publish-side capture
# ---------------------------------------------------------------------------

def _build_jittable(fn):
    """The one jit acquisition on the capture path: stage builders usually
    return a ``jax.jit`` wrapper directly (has ``.lower``); builders that
    return a closure *around* a jit (e.g. params partially applied) get
    re-wrapped so the closure's constants bake into the lowered module."""
    import jax

    return fn if hasattr(fn, "lower") else jax.jit(fn)


class AOTCapture:
    """Publish-time recorder installed on the CompiledCache via
    ``set_capture``: every miss built on the capturing thread is wrapped so
    its first call's concrete arguments are recorded next to the built jit;
    :meth:`export` then AOT-compiles and serializes each one."""

    def __init__(self):
        self._thread = threading.get_ident()
        self._ordinals: dict = {}
        self._records: list[dict] = []
        self._lock = threading.Lock()

    @property
    def tokens(self) -> list:
        """Every instance token seen (publish evicts their temporary
        executables from the process cache afterwards)."""
        return [t for t in self._ordinals if t is not None]

    def wrap(self, key: tuple, built):
        """Called by ``CompiledCache.get`` on a miss. Off-thread misses
        (a concurrent serve loop) pass through untouched — ordinal order
        must reflect only the warmup drive."""
        if threading.get_ident() != self._thread:
            return built
        fn_id, instance, shape, dtype = key
        with self._lock:
            ordinal = self._ordinals.setdefault(instance,
                                                len(self._ordinals))
            rec = {"fn_id": fn_id, "ordinal": ordinal, "shape": shape,
                   "dtype": dtype, "built": built, "call": None}
            self._records.append(rec)

        def wrapper(*args, **kwargs):
            if rec["call"] is None:
                rec["call"] = (args, kwargs)
            return built(*args, **kwargs)

        return wrapper

    def export(self, mechanism: str, put_blob) -> tuple[list[dict], list[dict]]:
        """AOT-compile + serialize every recorded entry. ``put_blob(bytes)
        -> sha256`` stores each executable (content-addressed next to the
        weights). Returns ``(entries, skipped)`` — a skip (donated buffers,
        custom pytree outputs, lowering failure) just means that shape JIT
        warms at load."""
        entries, skipped = [], []
        for rec in self._records:
            if rec["call"] is None:
                skipped.append({"fn_id": rec["fn_id"],
                                "shape": _jsonable(rec["shape"]),
                                "reason": "never invoked during capture"})
                continue
            try:
                entry, blob = _serialize_entry(rec, mechanism)
            except Exception as e:  # noqa: BLE001 - per-entry fallback
                skipped.append({"fn_id": rec["fn_id"],
                                "shape": _jsonable(rec["shape"]),
                                "reason": f"{type(e).__name__}: {e}"})
                continue
            entry["sha256"] = put_blob(blob)
            entry["bytes"] = len(blob)
            entries.append(entry)
        return entries, skipped


def _serialize_entry(rec: dict, mechanism: str) -> tuple[dict, bytes]:
    import jax
    from jax import tree_util as jtu

    args, kwargs = rec["call"]
    target = _build_jittable(rec["built"])
    lowered = target.lower(*args, **kwargs)
    if getattr(lowered, "donate_argnums", ()):
        raise AOTError("donated arguments cannot be AOT-served (the "
                       "executable would consume the caller's buffers)")
    entry = {
        "key": aot_key_digest(rec["fn_id"], rec["shape"], rec["dtype"]),
        "fn_id": rec["fn_id"],
        "ordinal": rec["ordinal"],
        "shape": _jsonable(rec["shape"]),
        "dtype": _jsonable(rec["dtype"]),
        "mechanism": mechanism,
    }
    if mechanism == "export":
        from jax import export as jexport

        exported = jexport.export(target)(*args, **kwargs)
        return entry, bytes(exported.serialize())
    compiled = lowered.compile()
    out = compiled(*args, **kwargs)
    counter = [0]
    template = _encode_template(out, counter)
    n_leaves = len(jtu.tree_leaves(out))
    if counter[0] != n_leaves:
        raise AOTError(f"output pytree has custom nodes ({n_leaves} leaves "
                       f"vs {counter[0]} template slots)")
    in_leaves = jtu.tree_leaves(lowered.in_avals)
    flat_args = jtu.tree_leaves((args, kwargs))
    if len(in_leaves) != len(flat_args):
        raise AOTError("input pytree has custom nodes or hoisted constants")
    entry["in_specs"] = [{"shape": [int(d) for d in a.shape],
                          "dtype": str(a.dtype)} for a in in_leaves]
    entry["out_template"] = template
    rexec = compiled.runtime_executable()
    return entry, bytes(rexec.client.serialize_executable(rexec))


def walk_stages(stage):
    """Deterministic pipeline-tree walk (root first, then nested ``stages``
    in order) — shared by the autotuner and anything needing one canonical
    stage order."""
    seen: set[int] = set()
    out = []

    def walk(obj):
        if obj is None or id(obj) in seen:
            return
        seen.add(id(obj))
        out.append(obj)
        getter = getattr(obj, "get", None)
        if callable(getter):
            try:
                children = getter("stages")
            except Exception:  # noqa: BLE001 - not every stage has 'stages'
                return
            if isinstance(children, (list, tuple)):
                for child in children:
                    walk(child)

    walk(stage)
    return out


def capture_stage_ladder(stage, rows, buckets, loop_cfg: dict,
                         put_blob) -> dict:
    """Drive ``stage`` through the serve-loop warmup at every ladder rung
    with capture on, then export+store the executables. Returns the
    manifest ``aot`` section. Graceful degradation: no mechanism -> a
    section with only a ``skipped`` note (loads fall back to JIT)."""
    mechanism = aot_mechanism()
    if mechanism is None:
        return {"entries": [], "skipped":
                [{"reason": "no executable-serialization mechanism in this "
                            "jax/jaxlib"}]}
    from ..core import batching as cb
    from ..io.serving import run_warmup

    cache = cb.get_compiled_cache()
    capture = AOTCapture()
    cache.set_capture(capture)
    try:
        run_warmup(stage, rows, list(buckets), loop_cfg)
    finally:
        cache.set_capture(None)
    entries, skipped = capture.export(mechanism, put_blob)
    # the captured executables were compiled against a throwaway reload of
    # the artifact — evict them so publish doesn't pin one dead copy of the
    # weights per publish
    for token in capture.tokens:
        cache.evict_instance(token)
    return {
        "mechanism": mechanism,
        "runtime": runtime_fingerprint(),
        "entries": entries,
        "skipped": skipped,
        "warmup": {"rows": list(rows),
                   "buckets": sorted(int(b) for b in buckets)},
        "total_bytes": sum(e["bytes"] for e in entries),
    }


# ---------------------------------------------------------------------------
# load-side second tier
# ---------------------------------------------------------------------------

def _build_xla_callable(blob: bytes, entry: dict):
    """Deserialize a raw XLA executable and wrap it behind the builder
    call convention: flatten live args, verify against the recorded input
    specs, execute, rebuild the recorded output pytree. No tracing, no
    compilation — the zero-cold-start path."""
    import jax
    from jax import tree_util as jtu

    device = jax.local_devices()[0]
    rexec = device.client.deserialize_executable(bytes(blob), [device])
    in_specs = [(tuple(s["shape"]), np.dtype(s["dtype"]))
                for s in entry["in_specs"]]
    template = entry["out_template"]

    def call(*args, **kwargs):
        flat = jtu.tree_leaves((args, kwargs))
        if len(flat) != len(in_specs):
            raise AOTError(
                f"aot executable {entry['fn_id']} expects "
                f"{len(in_specs)} arrays, got {len(flat)}")
        bufs = []
        for x, (shape, want) in zip(flat, in_specs):
            if isinstance(x, jax.Array) and tuple(x.shape) == shape \
                    and x.dtype == want:
                bufs.append(x)
                continue
            a = np.asarray(x)
            if tuple(a.shape) != shape:
                raise AOTError(
                    f"aot executable {entry['fn_id']} expects shape "
                    f"{shape}, got {tuple(a.shape)}")
            if a.dtype != want:
                a = a.astype(want)
            bufs.append(jax.device_put(a))
        return _decode_template(template, rexec.execute(bufs))

    return call


def _build_export_callable(blob: bytes, entry: dict):
    """``jax.export`` fallback: deserialization skips Python tracing of the
    original stage function; XLA still compiles once on first call (inside
    the one jit this builder owns)."""
    import jax
    from jax import export as jexport

    exported = jexport.deserialize(bytearray(blob))
    return jax.jit(exported.call)


class AOTExecutableSet:
    """The CompiledCache's persistent second tier for one loaded artifact.

    ``lookup`` runs on cache misses: entries match by (fn_id, shape, dtype)
    digest + the instance's first-seen ordinal (bound on the warmup thread,
    frozen afterwards). Blob reads are sha256-verified; a corrupt or
    missing blob demotes that entry to JIT with one structured warning —
    the swap itself always proceeds."""

    def __init__(self, aot_section: dict, blob_dir: str):
        self.mechanism = aot_section.get("mechanism")
        self.blob_dir = blob_dir
        self._by_key: dict[tuple, dict] = {}
        for e in aot_section.get("entries", ()):
            self._by_key[(e["key"], int(e["ordinal"]))] = e
        self._ordinals: dict = {}
        self._materialized: dict[tuple, object] = {}
        self._warned: set = set()
        self._bind_thread: int | None = None
        self._lock = threading.Lock()
        # load-report surface (the /admin/load warmup breakdown)
        self.hits = 0          # lookups served from a blob
        self.misses = 0        # lookups with no matching entry
        self.errors = 0        # blobs rejected (integrity/deserialize)
        self.loaded = 0        # distinct executables deserialized
        self.io_ms = 0.0       # wall spent reading + deserializing blobs

    def __len__(self) -> int:
        return len(self._by_key)

    def begin_binding(self) -> None:
        """Open the ordinal-binding window to the CURRENT thread (the
        warmup drive). Lookups from other threads see no entries until
        :meth:`freeze` — a concurrent serve loop on the old pipeline must
        not perturb first-seen ordering."""
        with self._lock:
            self._bind_thread = threading.get_ident()

    def freeze(self) -> None:
        """Close the binding window: known instances keep resolving from
        any thread; unknown instances fall back to tracing."""
        with self._lock:
            self._bind_thread = None

    def lookup(self, fn_id: str, instance, shape, dtype):
        with self._lock:
            if self._bind_thread is not None:
                if threading.get_ident() != self._bind_thread:
                    return None
                ordinal = self._ordinals.setdefault(instance,
                                                    len(self._ordinals))
            else:
                ordinal = self._ordinals.get(instance)
                if ordinal is None:
                    return None
        key = (aot_key_digest(fn_id, shape, dtype), ordinal)
        entry = self._by_key.get(key)
        if entry is None:
            with self._lock:
                self.misses += 1
            return None
        try:
            fn = self._load(key, entry)
        except Exception as e:  # noqa: BLE001 - a bad blob demotes to JIT
            with self._lock:
                self.errors += 1
                first = key not in self._warned
                self._warned.add(key)
            if first:
                logger.warning(json.dumps({
                    "event": "aot_blob_rejected", "fn_id": fn_id,
                    "sha256": entry.get("sha256"),
                    "error": f"{type(e).__name__}: {e}",
                    "action": "falling back to JIT trace for this entry"}))
            return None
        with self._lock:
            self.hits += 1
        return fn

    def _load(self, key: tuple, entry: dict):
        with self._lock:
            fn = self._materialized.get(key)
        if fn is not None:
            return fn
        t0 = time.perf_counter()
        path = os.path.join(self.blob_dir, entry["sha256"])
        with open(path, "rb") as f:
            blob = f.read()
        got = hashlib.sha256(blob).hexdigest()
        if got != entry["sha256"]:
            raise IntegrityError(
                f"aot blob {entry['sha256']} corrupt on read: bytes hash "
                f"to {got}")
        mechanism = entry.get("mechanism", self.mechanism)
        if mechanism == "xla":
            fn = _build_xla_callable(blob, entry)
        elif mechanism == "export":
            fn = _build_export_callable(blob, entry)
        else:
            raise AOTError(f"unknown aot mechanism {mechanism!r}")
        with self._lock:
            self._materialized[key] = fn
            self.loaded += 1
            self.io_ms += (time.perf_counter() - t0) * 1e3
        return fn

    def report(self) -> dict:
        with self._lock:
            return {"aot_hits": self.hits, "aot_misses": self.misses,
                    "aot_errors": self.errors,
                    "executables_loaded": self.loaded,
                    "io_ms": round(self.io_ms, 2),
                    "entries": len(self._by_key)}


def load_blocker(aot_section: dict) -> str | None:
    """Why this runtime cannot ride the artifact's AOT blobs (None = it
    can): fingerprint mismatch, mechanism unavailable here, or an artifact
    whose capture produced no entries."""
    if not aot_section.get("entries"):
        return "artifact has no aot entries"
    mechanism = aot_section.get("mechanism")
    available = aot_mechanism()
    if mechanism == "xla" and available != "xla":
        return (f"artifact uses the {mechanism!r} mechanism but this "
                f"runtime supports {available!r}")
    if mechanism == "export" and available is None:
        return "this runtime has no executable-serialization support"
    return fingerprint_mismatch(aot_section.get("runtime", {}))


def log_fallback(reason: str, model: str | None = None,
                 version: str | None = None) -> None:
    """ONE structured warning per fallback decision (the satellite fix: a
    platform/version mismatch must demote to JIT warmup loudly, never fail
    the swap)."""
    coarse = ("mismatch" if "mismatch" in reason
              else "disabled" if "disabled" in reason
              else "unsupported")
    _AOT_METRICS.get()["fallbacks"].inc(reason=coarse)
    logger.warning(json.dumps({
        "event": "aot_fallback", "model": model, "version": version,
        "reason": reason, "action": "JIT warmup (swap proceeds)"}))


def emit_load_metrics(breakdown: dict) -> None:
    """Mirror an /admin/load warmup breakdown into the synapseml_deploy_*
    series (PR-2 metrics registry)."""
    m = _AOT_METRICS.get()
    m["io_ms"].observe(float(breakdown.get("io_ms", 0.0)))
    m["compile_ms"].observe(float(breakdown.get("compile_ms", 0.0)))
    for field, handle in (("aot_hits", "aot_hits"),
                          ("aot_misses", "aot_misses"),
                          ("executables_loaded", "loaded"),
                          ("executables_traced", "traced")):
        n = int(breakdown.get(field, 0))
        if n:
            m[handle].inc(n)
