"""Zero-cold-start deploys (ISSUE 9): AOT-compiled executable ladders in
the registry, the CompiledCache second tier, autotuned backend pinning,
store garbage collection, and the runtime-mismatch / corrupt-blob fallback
paths."""

import json
import os
import subprocess
import sys
import textwrap
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from _aot_pipeline import (TunableAffine, build_pipeline, make_mlp_onnx,
                           sample_rows)
from synapseml_tpu.core import batching as cb
from synapseml_tpu.core.pipeline import PipelineModel, Transformer
from synapseml_tpu.registry import ArtifactStore, ModelRegistry
from synapseml_tpu.registry import aot as raot

pytestmark = pytest.mark.aot

BUCKETS = [8, 16, 32]


@pytest.fixture()
def fresh_cache():
    cache = cb.reset_compiled_cache()
    yield cache
    cb.reset_compiled_cache()


class Placeholder(Transformer):
    """Initial pipeline a worker boots with before its first hot swap."""

    def _transform(self, df):
        def per_part(p):
            out = dict(p)
            out["reply"] = np.asarray([{"placeholder": True}] * len(p["id"]),
                                      dtype=object)
            return out

        return df.map_partitions(per_part)


def _post(base, path, payload, timeout=60):
    req = urllib.request.Request(base + path,
                                 data=json.dumps(payload).encode(),
                                 method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def _publish(tmp_path, version="v1", aot=True, autotune=None, **pipe_kw):
    reg = ModelRegistry(str(tmp_path / "store"))
    pub = reg.publish(
        "mlp", build_pipeline(**pipe_kw), version=version,
        aot={"rows": sample_rows(), "buckets": BUCKETS} if aot else None,
        autotune=autotune)
    return reg, pub


# ---------------------------------------------------------------------------
# mechanics: mechanism probe, fingerprints, keys, template codec
# ---------------------------------------------------------------------------

def test_mechanism_detected():
    # this environment ships a jaxlib with executable serialization; the
    # probe must find SOME mechanism (graceful None is for foreign jaxes)
    assert raot.aot_mechanism() in ("xla", "export")


def test_fingerprint_match_and_mismatch_reasons():
    fp = raot.runtime_fingerprint()
    assert raot.fingerprint_mismatch(fp) is None
    for field in ("platform", "jax", "jaxlib", "xla_flags_sha256"):
        doctored = dict(fp, **{field: "something-else"})
        reason = raot.fingerprint_mismatch(doctored)
        assert reason is not None and field in reason


def test_key_digest_stable_across_tuple_list_spelling():
    a = raot.aot_key_digest("fn", (8, ("x", 1)), "float32")
    b = raot.aot_key_digest("fn", [8, ["x", 1]], "float32")
    assert a == b
    assert a != raot.aot_key_digest("fn", (16, ("x", 1)), "float32")
    assert a != raot.aot_key_digest("other", (8, ("x", 1)), "float32")


def test_template_codec_roundtrip_matches_tree_flatten_order():
    import jax.tree_util as jtu

    obj = {"b": (np.ones(2), [np.zeros(3), None]), "a": np.full(1, 7.0)}
    counter = [0]
    template = raot._encode_template(obj, counter)
    leaves = jtu.tree_leaves(obj)
    assert counter[0] == len(leaves)
    rebuilt = raot._decode_template(template, leaves)
    assert isinstance(rebuilt["b"], tuple) and rebuilt["b"][1][1] is None
    flat2 = jtu.tree_leaves(rebuilt)
    assert all(np.array_equal(x, y) for x, y in zip(leaves, flat2))


# ---------------------------------------------------------------------------
# ordinal binding: two same-class instances must never swap executables
# ---------------------------------------------------------------------------

def test_ordinal_binding_two_instances_keep_their_weights(tmp_path,
                                                          fresh_cache):
    import jax

    def make_builder(scale):
        def build():
            return jax.jit(lambda x: x * scale)

        return build

    class Obj:
        pass

    a, b = Obj(), Obj()
    capture = raot.AOTCapture()
    cache = fresh_cache
    cache.set_capture(capture)
    try:
        # same fn_id, same shape, same dtype — only the instance differs
        fa = cache.get("f", (4,), make_builder(2.0),
                       instance=cb.instance_token(a))
        fb = cache.get("f", (4,), make_builder(10.0),
                       instance=cb.instance_token(b))
        x = np.ones(4, np.float32)
        assert float(np.asarray(fa(x))[0]) == 2.0
        assert float(np.asarray(fb(x))[0]) == 10.0
    finally:
        cache.set_capture(None)
    import hashlib

    blobs = {}

    def put_blob(data):
        digest = hashlib.sha256(data).hexdigest()
        blobs[digest] = data
        return digest

    entries, skipped = capture.export(raot.aot_mechanism(), put_blob)
    assert not skipped and len(entries) == 2
    blob_dir = tmp_path / "aot"
    blob_dir.mkdir()
    for digest, data in blobs.items():
        (blob_dir / digest).write_bytes(data)
    for entry in entries:
        entry.setdefault("mechanism", raot.aot_mechanism())
    provider = raot.AOTExecutableSet(
        {"mechanism": raot.aot_mechanism(), "entries": entries},
        str(blob_dir))
    provider.begin_binding()
    # fresh process simulation: new instances, first-seen order preserved
    a2, b2 = Obj(), Obj()
    fa2 = provider.lookup("f", cb.instance_token(a2), (4,), None)
    fb2 = provider.lookup("f", cb.instance_token(b2), (4,), None)
    provider.freeze()
    x = np.ones(4, np.float32)
    assert float(np.asarray(fa2(x))[0]) == 2.0
    assert float(np.asarray(fb2(x))[0]) == 10.0
    # frozen: an unknown instance falls back to tracing, never aliases
    c = Obj()
    assert provider.lookup("f", cb.instance_token(c), (4,), None) is None
    # off-thread lookups during a binding window are ignored
    provider2 = raot.AOTExecutableSet(
        {"mechanism": raot.aot_mechanism(), "entries": entries},
        str(blob_dir))
    provider2.begin_binding()
    seen = {}

    def other_thread():
        seen["fn"] = provider2.lookup("f", cb.instance_token(Obj()),
                                      (4,), None)

    t = threading.Thread(target=other_thread)
    t.start()
    t.join()
    assert seen["fn"] is None


# ---------------------------------------------------------------------------
# publish: manifest entries, blobs, store gc
# ---------------------------------------------------------------------------

def test_publish_records_aot_entries_and_blobs(tmp_path, fresh_cache):
    reg, pub = _publish(tmp_path)
    aot = pub.manifest["aot"]
    assert aot["mechanism"] == raot.aot_mechanism()
    assert len(aot["entries"]) == len(BUCKETS)
    assert aot["warmup"]["buckets"] == BUCKETS
    assert raot.fingerprint_mismatch(aot["runtime"]) is None
    store = ArtifactStore(str(tmp_path / "store"))
    for entry in aot["entries"]:
        assert store.has_blob(entry["sha256"])
        assert entry["bytes"] > 0 and entry["fn_id"] == "onnx_model"
    # the signed manifest survives verification with the aot section
    assert store.read_manifest("mlp", "v1")["aot"]["entries"]
    # publish evicted its temporary capture executables from the cache
    assert len(fresh_cache) == 0


def test_store_gc_prunes_orphans_keeps_referenced(tmp_path, fresh_cache):
    reg, pub = _publish(tmp_path)
    store = ArtifactStore(str(tmp_path / "store"))
    orphan = store.put_blob_bytes(b"orphaned by a failed publish")
    referenced = {e["sha256"] for e in pub.manifest["files"]}
    referenced |= {e["sha256"] for e in pub.manifest["aot"]["entries"]}
    # dry run: reports, deletes nothing
    report = store.gc(dry_run=True, min_age_s=0.0)
    assert report["pruned"] == [orphan] and report["dry_run"]
    assert store.has_blob(orphan)
    # young-blob grace window protects in-flight publishes
    report = store.gc(min_age_s=3600.0)
    assert report["pruned"] == [] and report["kept_young"] == 1
    # real gc: orphan gone, every referenced blob survives
    report = store.gc(min_age_s=0.0)
    assert report["pruned"] == [orphan]
    assert not store.has_blob(orphan)
    assert all(store.has_blob(d) for d in referenced)
    # the version still resolves and serves after gc
    resolved = ModelRegistry(str(tmp_path / "store")).resolve("mlp", "v1")
    assert resolved.version == "v1"


# ---------------------------------------------------------------------------
# /admin/load: the zero-cold-start acceptance surface
# ---------------------------------------------------------------------------

def _why(wu, mechanism=None):
    """What a failed zero-trace assertion must say: which mechanism the
    probe found on this machine (``"export"`` compiles at load and keeps the
    rung cap) and the whole warm-up summary."""
    return f"aot_mechanism()={mechanism or raot.aot_mechanism()!r}, warmup={wu}"


def _serve_placeholder():
    from synapseml_tpu.io.serving import serve_pipeline

    return serve_pipeline(Placeholder(), batch_interval_ms=5, version="v0")


def test_admin_load_aot_serves_first_request_with_zero_traces(tmp_path,
                                                              fresh_cache):
    from synapseml_tpu.core import observability as obs

    reg, pub = _publish(tmp_path)
    srv = _serve_placeholder()
    try:
        cache = cb.get_compiled_cache()
        misses0 = cache.miss_count("onnx_model")
        status, reply = _post(srv.address, "/admin/load",
                              {"registry": str(tmp_path / "store"),
                               "model": "mlp", "ref": "v1"})
        assert status == 200 and reply["ok"]
        wu = reply["warmup"]
        assert wu["mode"] == "aot" and wu["fallback_reason"] is None, _why(wu)
        assert wu["aot_hits"] == len(BUCKETS), _why(wu)
        assert wu["executables_loaded"] == len(BUCKETS)
        assert wu["executables_traced"] == 0, _why(wu)
        assert wu["compile_ms"] == 0.0 and wu["io_ms"] > 0
        # first post-swap request over HTTP, then direct transforms at
        # every ladder rung (7->8, 12->16, 30->32): ZERO new traces —
        # every executable came from the artifact's blobs
        status, out = _post(srv.address, "/", sample_rows(1, seed=101)[0])
        assert status == 200 and "pred" in out
        from synapseml_tpu.core.dataframe import DataFrame

        loaded = srv.pipeline_holder.pipeline
        onnx = loaded.get("stages")[1]
        rs = np.random.default_rng(5)
        for n in (7, 12, 30):
            out_df = onnx.transform(DataFrame.from_dict(
                {"features": rs.normal(size=(n, 4)).astype(np.float32)}))
            assert len(out_df.collect_column("pred")) == n
        assert cache.miss_count("onnx_model") - misses0 == 0
        assert cache.stats()["aot_hits"] == len(BUCKETS)
        # satellite: the same fields surface as synapseml_deploy_* series
        text = obs.prometheus_exposition()[0].decode()
        assert "synapseml_deploy_aot_hits_total" in text
        assert "synapseml_deploy_warmup_io_ms" in text
        assert "synapseml_deploy_executables_loaded_total" in text
        # a FRESH pipeline's instances never alias the frozen provider:
        # direct transform of a new stage traces (miss), correct output
        onnx2 = make_mlp_onnx(seed=3)
        from synapseml_tpu.core.dataframe import DataFrame

        feats = np.ones((4, 4), np.float32)
        out2 = onnx2.transform(DataFrame.from_dict({"features": feats}))
        assert cache.miss_count("onnx_model") - misses0 == 1
        assert len(out2.collect_column("pred")) == 4
    finally:
        srv.stop()


def test_aot_and_jit_arms_give_identical_predictions(tmp_path, fresh_cache):
    reg, pub = _publish(tmp_path)
    bodies = sample_rows(6, seed=42)
    replies = {}
    for arm in ("aot", "jit"):
        srv = _serve_placeholder()
        try:
            status, reply = _post(srv.address, "/admin/load",
                                  {"registry": str(tmp_path / "store"),
                                   "model": "mlp", "ref": "v1",
                                   "aot": arm == "aot"})
            assert status == 200
            assert reply["warmup"]["mode"] == arm
            if arm == "jit":
                assert reply["warmup"]["fallback_reason"] == \
                    "aot disabled by request"
            replies[arm] = [_post(srv.address, "/", b)[1] for b in bodies]
        finally:
            srv.stop()
        cb.reset_compiled_cache()
    # byte-identical across arms: the deserialized executable computes the
    # exact program the JIT arm compiles
    assert json.dumps(replies["aot"], sort_keys=True) == \
        json.dumps(replies["jit"], sort_keys=True)


def test_warmup_cap_lifted_when_aot_present(tmp_path, fresh_cache):
    reg = ModelRegistry(str(tmp_path / "store"))
    big = [8, 16, 32, 64, 128, 256]
    reg.publish("mlp", build_pipeline(mini_batch_size=256), version="v1",
                aot={"rows": sample_rows(), "buckets": big})
    srv = _serve_placeholder()
    try:
        status, reply = _post(srv.address, "/admin/load",
                              {"registry": str(tmp_path / "store"),
                               "model": "mlp", "ref": "v1"})
        assert status == 200
        wu = reply["warmup"]
        # default JIT warmup stops at rungs <= 64; with AOT blobs the full
        # published ladder (incl. 128/256) maps in with zero compiles
        assert wu["aot_hits"] == len(big) and wu["executables_traced"] == 0, \
            _why(wu)
        misses0 = cb.get_compiled_cache().miss_count("onnx_model")
        status, out = _post(srv.address, "/",
                            sample_rows(1, seed=9)[0])
        assert status == 200 and "pred" in out
        assert cb.get_compiled_cache().miss_count("onnx_model") == misses0
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# fallback paths: runtime mismatch, corrupt blob — swap NEVER fails
# ---------------------------------------------------------------------------

def _doctor_manifest(tmp_path, **runtime_overrides):
    store = ArtifactStore(str(tmp_path / "store"))
    manifest = store.read_manifest("mlp", "v1")
    manifest.pop("signature", None)
    manifest["aot"]["runtime"].update(runtime_overrides)
    store.write_manifest("mlp", "v1", manifest)


@pytest.mark.parametrize("overrides,needle", [
    ({"platform": "tpu"}, "platform"),
    ({"jaxlib": "9.9.9"}, "jaxlib"),
])
def test_runtime_mismatch_falls_back_to_jit_and_swaps(tmp_path, fresh_cache,
                                                      overrides, needle,
                                                      caplog):
    import logging

    reg, pub = _publish(tmp_path)
    _doctor_manifest(tmp_path, **overrides)
    srv = _serve_placeholder()
    try:
        with caplog.at_level(logging.WARNING,
                             logger="synapseml_tpu.registry.aot"):
            status, reply = _post(srv.address, "/admin/load",
                                  {"registry": str(tmp_path / "store"),
                                   "model": "mlp", "ref": "v1"})
        # the swap SUCCEEDS on the JIT path with one structured warning
        assert status == 200 and reply["ok"]
        wu = reply["warmup"]
        assert wu["mode"] == "jit"
        assert needle in wu["fallback_reason"]
        assert wu["aot_hits"] == 0 and wu["executables_traced"] > 0
        warnings = [r for r in caplog.records
                    if "aot_fallback" in r.getMessage()]
        assert len(warnings) == 1
        payload = json.loads(warnings[0].getMessage())
        assert needle in payload["reason"]
        # and it still serves correctly
        status, out = _post(srv.address, "/", sample_rows(1)[0])
        assert status == 200 and "pred" in out
    finally:
        srv.stop()


def test_corrupted_blob_rejected_falls_back_swap_succeeds(tmp_path,
                                                          fresh_cache):
    reg, pub = _publish(tmp_path)
    # materialize the version cache, then corrupt every aot blob IN PLACE
    resolved = reg.resolve("mlp", "v1")
    aot_dir = os.path.join(os.path.dirname(resolved.path), "aot")
    blobs = os.listdir(aot_dir)
    assert len(blobs) == len(BUCKETS)
    for name in blobs:
        with open(os.path.join(aot_dir, name), "r+b") as f:
            f.seek(0)
            f.write(b"\x00corrupted\x00")
    srv = _serve_placeholder()
    try:
        status, reply = _post(srv.address, "/admin/load",
                              {"registry": str(tmp_path / "store"),
                               "model": "mlp", "ref": "v1"})
        # integrity check rejects each blob; warmup traces instead; the
        # swap still succeeds and serves correct predictions
        assert status == 200 and reply["ok"]
        wu = reply["warmup"]
        assert wu["mode"] == "aot"
        assert wu["aot_errors"] == len(BUCKETS)
        assert wu["aot_hits"] == 0
        assert wu["executables_traced"] >= len(BUCKETS)
        status, out = _post(srv.address, "/", sample_rows(1)[0])
        assert status == 200 and "pred" in out
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# autotune: search records winners, load pins them
# ---------------------------------------------------------------------------

def test_autotune_records_winner_and_load_pins_it(tmp_path, fresh_cache):
    reg = ModelRegistry(str(tmp_path / "store"))
    pipe = PipelineModel(stages=[TunableAffine(impl="slow"),
                                 build_pipeline().get("stages")[0],
                                 make_mlp_onnx(), ])
    pub = reg.publish(
        "tuned", pipe, version="v1",
        aot={"rows": sample_rows(), "buckets": [8]},
        autotune={"trials": 2, "winners": {"histogram_impl": "onehot"}})
    tune = pub.manifest["autotune"]
    assert tune["winners"]["impl"] == "fast"
    # the search's warm cache entries must not hide rungs from capture
    assert len(pub.manifest["aot"]["entries"]) == 1
    # bench-fed override recorded verbatim next to the searched winner
    assert tune["winners"]["histogram_impl"] == "onehot"
    assert tune["timings_ms"]["impl"]["slow"]["8"] > \
        tune["timings_ms"]["impl"]["fast"]["8"]
    # load pins the winner onto the freshly loaded stage (saved artifact
    # still says 'slow')
    srv = _serve_placeholder()
    try:
        status, reply = _post(srv.address, "/admin/load",
                              {"registry": str(tmp_path / "store"),
                               "model": "tuned", "ref": "v1"})
        assert status == 200
        applied = reply["warmup"].get("autotune") or []
        assert {"stage": "TunableAffine", "param": "impl",
                "from": "slow", "to": "fast"} in applied
        loaded = srv.pipeline_holder.pipeline
        assert loaded.get("stages")[0].get("impl") == "fast"
        # opting out keeps the saved defaults — and since the shipped AOT
        # executables were compiled WITH the winners baked in, the load
        # must also demote to JIT (serving tuned kernels under untuned
        # configs would make the opt-out a lie)
        status, reply = _post(srv.address, "/admin/load",
                              {"registry": str(tmp_path / "store"),
                               "model": "tuned", "ref": "v1",
                               "autotune": False})
        assert status == 200
        assert srv.pipeline_holder.pipeline.get("stages")[0].get("impl") \
            == "slow"
        wu = reply["warmup"]
        assert wu["mode"] == "jit"
        assert "autotune disabled" in wu["fallback_reason"]
    finally:
        srv.stop()


def test_autotune_all_candidates_failing_restores_original(fresh_cache):
    from synapseml_tpu.core.params import Param
    from synapseml_tpu.registry.autotune import autotune_stage

    class Exploding(Transformer):
        impl = Param("impl", "always broken", default="a",
                     validator=lambda v: v in ("a", "b"))
        _AUTOTUNE_PARAMS = {"impl": ("a", "b")}

        def _transform(self, df):
            raise RuntimeError("kaboom")

    stage = Exploding(impl="a")
    section = autotune_stage(stage, sample_rows(), [8],
                             {"parse_json": True, "input_col": "body"})
    # no winner recorded, and the stage is NOT left on the last failing
    # candidate for the AOT capture that follows
    assert section is None
    assert stage.get("impl") == "a"


def test_export_mechanism_serves_but_keeps_rung_cap(tmp_path, fresh_cache,
                                                    monkeypatch):
    # force the portable jax.export fallback end-to-end: blobs skip
    # tracing but still XLA-compile at load, so the full-ladder rung-cap
    # lift must NOT apply
    monkeypatch.setattr(raot, "aot_mechanism", lambda: "export")
    reg = ModelRegistry(str(tmp_path / "store"))
    big = [8, 16, 32, 64, 128, 256]
    pub = reg.publish("mlp", build_pipeline(mini_batch_size=256),
                      version="v1",
                      aot={"rows": sample_rows(), "buckets": big})
    assert pub.manifest["aot"]["mechanism"] == "export"
    assert len(pub.manifest["aot"]["entries"]) == len(big)
    srv = _serve_placeholder()
    try:
        status, reply = _post(srv.address, "/admin/load",
                              {"registry": str(tmp_path / "store"),
                               "model": "mlp", "ref": "v1"})
        assert status == 200
        wu = reply["warmup"]
        assert wu["mode"] == "aot"
        # default cap (rungs <= 64) applied: 128/256 NOT warmed at load
        assert wu["aot_hits"] == len([b for b in big if b <= 64])
        assert wu["executables_traced"] == 0
        # and the deserialized module still serves correctly
        status, out = _post(srv.address, "/", sample_rows(1)[0])
        assert status == 200 and "pred" in out
    finally:
        srv.stop()


def test_missing_aot_blob_self_heals_on_next_resolve(tmp_path, fresh_cache):
    reg, pub = _publish(tmp_path)
    resolved = reg.resolve("mlp", "v1")
    aot_dir = os.path.join(os.path.dirname(resolved.path), "aot")
    victim = os.path.join(aot_dir, os.listdir(aot_dir)[0])
    os.unlink(victim)
    # the .complete marker is already written; a transient fetch failure
    # must not become a permanent JIT fallback — resolve re-fetches
    reg.resolve("mlp", "v1")
    assert os.path.isfile(victim)


def test_autotune_skips_foreign_platform(tmp_path, fresh_cache):
    from synapseml_tpu.registry.autotune import apply_autotune

    stage = TunableAffine(impl="slow")
    applied = apply_autotune(stage, {"platform": "tpu",
                                     "winners": {"impl": "fast"}})
    assert applied == [] and stage.get("impl") == "slow"


# ---------------------------------------------------------------------------
# cross-process: publish in one process, zero-trace serve in a fresh one
# ---------------------------------------------------------------------------

_SERVE_DRIVER = textwrap.dedent("""
    import json, os, sys, urllib.request
    sys.path.insert(0, {repo!r}); sys.path.insert(0, {tests!r})
    import numpy as np
    from _aot_pipeline import sample_rows
    from synapseml_tpu.core import batching as cb
    from synapseml_tpu.core.pipeline import Transformer
    from synapseml_tpu.io.serving import serve_pipeline
    from synapseml_tpu.registry.aot import aot_mechanism

    class Placeholder(Transformer):
        def _transform(self, df):
            def pp(p):
                out = dict(p)
                out["reply"] = np.asarray([{{}}] * len(p["id"]), dtype=object)
                return out
            return df.map_partitions(pp)

    srv = serve_pipeline(Placeholder(), batch_interval_ms=5, version="v0")

    def post(path, payload):
        req = urllib.request.Request(srv.address + path,
                                     data=json.dumps(payload).encode(),
                                     method="POST")
        with urllib.request.urlopen(req, timeout=120) as r:
            return json.loads(r.read())

    cache = cb.get_compiled_cache()
    misses0 = cache.miss_count("onnx_model")
    reply = post("/admin/load", {{"registry": {store!r}, "model": "mlp",
                                  "ref": "v1"}})
    preds = [post("/", b) for b in sample_rows(6, seed=42)]
    print(json.dumps({{
        "warmup": reply["warmup"],
        "mechanism": aot_mechanism(),
        "miss_delta": cache.miss_count("onnx_model") - misses0,
        "aot_hits": cache.stats()["aot_hits"],
        "preds": preds,
    }}))
    srv.stop()
""")

_PUBLISH_DRIVER = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, {repo!r}); sys.path.insert(0, {tests!r})
    from _aot_pipeline import build_pipeline, sample_rows
    from synapseml_tpu.core.dataframe import DataFrame
    from synapseml_tpu.registry import ModelRegistry
    import numpy as np

    reg = ModelRegistry({store!r})
    pipe = build_pipeline()
    reg.publish("mlp", pipe, version="v1",
                aot={{"rows": sample_rows(), "buckets": [8, 16, 32]}})
    # reference predictions straight through the published pipeline
    feats = np.stack([np.asarray(b["features"], np.float32)
                      for b in sample_rows(6, seed=42)])
    df = DataFrame.from_dict({{
        "id": np.asarray([str(i) for i in range(6)], dtype=object),
        "method": np.asarray(["POST"] * 6, dtype=object),
        "path": np.asarray(["/"] * 6, dtype=object),
        "body": np.asarray(list(sample_rows(6, seed=42)), dtype=object)}})
    out = pipe.transform(df)
    print(json.dumps({{"preds": list(out.collect_column("reply"))}},
                     default=str))
""")


def _run_driver(script: str) -> dict:
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=240, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, f"driver failed:\n{proc.stderr[-4000:]}"
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_cross_process_publish_then_zero_trace_serve(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tests = os.path.join(repo, "tests")
    store = str(tmp_path / "store")
    pub_out = _run_driver(_PUBLISH_DRIVER.format(repo=repo, tests=tests,
                                                 store=store))
    serve_out = _run_driver(_SERVE_DRIVER.format(repo=repo, tests=tests,
                                                 store=store))
    wu = serve_out["warmup"]
    # the acceptance criterion: a FRESH process serves the ladder with
    # zero traces — every executable came from the artifact's blobs
    why = _why(wu, serve_out["mechanism"])
    assert wu["mode"] == "aot", why
    assert wu["executables_traced"] == 0 and wu["compile_ms"] == 0.0, why
    assert serve_out["miss_delta"] == 0
    assert serve_out["aot_hits"] == 3
    # and the served predictions equal the publisher's direct transform
    served = [p["pred"] for p in serve_out["preds"]]
    direct = [p["pred"] for p in pub_out["preds"]]
    assert served == direct
