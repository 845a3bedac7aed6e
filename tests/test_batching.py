"""Continuous batching + shared shape-bucketed compile cache (PR 4).

Covers: ShapeBucketer ladder/slicing, CompiledCache hit/miss/evict/LRU and
metrics, numerical identity of bucketed/padded vs unpadded execution for
EVERY adopted stage (onnx, hf embedder, hf causal LM, deep text, deep
vision, gbdt predict, knn), the ladder-bounded compile-count guarantee
under mixed-size streams (direct and through a served pipeline), the
adaptive serve-loop scheduler, and the /admin/load-path warmup precompile.
"""

import contextlib
import json
import threading
import time

import numpy as np
import pytest

from synapseml_tpu.core import DataFrame, batching as cb
from synapseml_tpu.core.pipeline import Transformer


@pytest.fixture()
def fresh_cache():
    cache = cb.reset_compiled_cache()
    yield cache
    cb.reset_compiled_cache()


@contextlib.contextmanager
def exact_bucketer(upto: int = 64):
    """A ladder with every integer rung — bucket_for(n) == n, i.e. the
    UNPADDED reference execution."""
    prev = cb.set_default_bucketer(
        cb.ShapeBucketer(ladder=list(range(1, upto + 1))))
    try:
        yield
    finally:
        cb.set_default_bucketer(prev)


# ---------------------------------------------------------------------------
# ShapeBucketer
# ---------------------------------------------------------------------------

def test_bucketer_ladder_and_bucket_for():
    b = cb.ShapeBucketer(min_bucket=8, max_bucket=64)
    assert b.ladder == (8, 16, 32, 64)
    assert b.bucket_for(1) == 8
    assert b.bucket_for(8) == 8
    assert b.bucket_for(9) == 16
    assert b.bucket_for(64) == 64
    # beyond the ladder: exact shape, never pad toward the next pow-2
    assert b.bucket_for(1000) == 1000
    assert b.cap_for(64) == 64
    assert b.cap_for(48) == 32
    assert b.cap_for(5) == 5  # below the ladder: the cap stays a hard bound
    assert b.cap_for(200) == 200  # above it too: never silently shrunk
    assert [s for s in b.slices(500, 200)] == [
        (0, 200, 200), (200, 400, 200), (400, 500, 100)]  # tail stays exact
    assert b.buckets_upto(64) == [8, 16, 32, 64]
    assert b.buckets_upto(48) == [8, 16, 32]


def test_bucketer_seq_ladder():
    """The sequence/page dimension buckets like the batch dimension: the
    token-serving plane keys prefill executables off seq_bucket_for and
    decode executables off bucket_for, so both stay ladder-bounded."""
    b = cb.ShapeBucketer(min_bucket=8, max_bucket=64,
                         min_seq_bucket=16, max_seq_bucket=128)
    assert b.seq_ladder == (16, 32, 64, 128)
    assert b.seq_bucket_for(1) == 16
    assert b.seq_bucket_for(17) == 32
    assert b.seq_bucket_for(128) == 128
    # multiple_of: prompt buckets tile whole KV pages
    assert b.seq_bucket_for(17, multiple_of=24) == 48
    # cap clamps at a model horizon instead of padding past it
    assert b.seq_bucket_for(100, cap=120) == 120
    assert b.seq_bucket_for(100, cap=128) == 128
    with pytest.raises(ValueError):
        b.seq_bucket_for(130, cap=128)
    # warmup/compile-bound set: every rung plus the cap bucket
    assert b.seq_buckets_upto(128) == [16, 32, 64, 128]
    assert b.seq_buckets_upto(100) == [16, 32, 64, 100]
    # explicit seq ladder + validation
    assert cb.ShapeBucketer(seq_ladder=[8, 80]).seq_ladder == (8, 80)
    with pytest.raises(ValueError):
        cb.ShapeBucketer(seq_ladder=[0, 8])


def test_bucketer_slices_cover_and_bound():
    b = cb.ShapeBucketer(min_bucket=8, max_bucket=64)
    for n in (1, 7, 8, 9, 33, 64, 65, 200):
        got = list(b.slices(n, 64))
        assert got[0][0] == 0 and got[-1][1] == n
        for (s0, e0, _), (s1, _, _) in zip(got, got[1:]):
            assert e0 == s1  # contiguous, no overlap
        for s, e, bucket in got:
            assert e - s <= bucket <= 64
            assert bucket in (8, 16, 32, 64)
    assert list(b.slices(0, 64)) == []


def test_bucketer_multiple_of():
    b = cb.ShapeBucketer(min_bucket=8, max_bucket=64)
    for _s, _e, bucket in b.slices(13, 64, multiple_of=4):
        assert bucket % 4 == 0
    assert b.bucket_for(3, multiple_of=6) % 6 == 0


def test_bucketer_explicit_ladder_and_validation():
    assert cb.ShapeBucketer(ladder=[4, 2, 2]).ladder == (2, 4)
    with pytest.raises(ValueError):
        cb.ShapeBucketer(ladder=[0, 2])
    with pytest.raises(ValueError):
        cb.ShapeBucketer(min_bucket=16, max_bucket=8)


def test_pad_rows_modes():
    a = np.arange(6, dtype=np.float32).reshape(3, 2)
    zero = cb.pad_rows(a, 5)
    assert zero.shape == (5, 2) and np.all(zero[3:] == 0)
    edge = cb.pad_rows(a, 5, mode="edge")
    assert np.all(edge[3:] == a[-1])
    one = cb.pad_rows(a, 5, mode="constant", constant=1)
    assert np.all(one[3:] == 1)
    assert cb.pad_rows(a, 3) is a  # no copy when already at the bucket
    assert cb.unpad_rows(zero, 3).shape == (3, 2)


# ---------------------------------------------------------------------------
# CompiledCache
# ---------------------------------------------------------------------------

def test_compiled_cache_hit_miss_evict(fresh_cache):
    cache = cb.CompiledCache(capacity=2)
    calls = []

    def build_for(tag):
        def build():
            calls.append(tag)
            return lambda x: (tag, x)
        return build

    f8 = cache.get("fn", (8,), build_for("b8"))
    assert f8(1) == ("b8", 1)
    assert cache.get("fn", (8,), build_for("never")) is f8
    stats = cache.stats()
    assert {k: stats[k] for k in ("hits", "misses", "evictions", "size")} \
        == {"hits": 1, "misses": 1, "evictions": 0, "size": 1}
    cache.get("fn", (16,), build_for("b16"))
    cache.get("fn", (8,), build_for("never"))   # refresh 8's recency
    cache.get("fn", (32,), build_for("b32"))    # evicts 16 (LRU)
    assert calls == ["b8", "b16", "b32"]
    stats = cache.stats()
    assert stats["evictions"] == 1 and stats["size"] == 2
    cache.get("fn", (16,), build_for("b16-again"))  # rebuilt after eviction
    assert calls[-1] == "b16-again"


def test_compiled_cache_distinguishes_instance_and_dtype(fresh_cache):
    cache = cb.CompiledCache()
    a = cache.get("fn", (8,), lambda: (lambda: "a"), instance=1)
    b = cache.get("fn", (8,), lambda: (lambda: "b"), instance=2)
    c = cache.get("fn", (8,), lambda: (lambda: "c"), instance=1,
                  dtype="float64")
    assert a() == "a" and b() == "b" and c() == "c"


def test_compiled_cache_metrics_and_trace_span(fresh_cache):
    from synapseml_tpu.core import observability as obs

    cache = cb.get_compiled_cache()
    before = cache.miss_count("metrics_probe")
    fn = cache.get("metrics_probe", (4,), lambda: (lambda x: x + 1))
    assert fn(1) == 2  # first call runs under the compile span
    assert cache.miss_count("metrics_probe") == before + 1
    spans = [s for s in obs.get_tracer().spans_as_dicts()
             if s["name"] == "compile"
             and s["attributes"].get("fn") == "metrics_probe"]
    assert spans, "miss's first call must emit a compile span"
    snap = obs.get_registry().snapshot()
    trace_hist = snap.get('synapseml_compile_trace_ms{fn="metrics_probe"}')
    assert trace_hist and trace_hist["count"] >= 1


def test_compiled_cache_thread_safety(fresh_cache):
    cache = cb.CompiledCache(capacity=8)
    results = []

    def worker(i):
        fn = cache.get("t", (i % 4,), lambda i=i: (lambda: i % 4))
        results.append((i % 4, fn()))

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(32)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # every caller got SOME callable for its bucket (first build wins; a
    # racing duplicate build computes the same thing)
    assert len(results) == 32
    assert len(cache) == 4


def test_instance_token_stable_and_invalidated(fresh_cache):
    class Obj:
        pass

    o = Obj()
    t1 = cb.instance_token(o)
    assert cb.instance_token(o) == t1
    cb.invalidate_token(o)
    assert cb.instance_token(o) != t1
    assert cb.instance_token(Obj()) != cb.instance_token(Obj())


def test_invalidate_token_evicts_cached_executables(fresh_cache):
    """A dead config's entries must leave the cache — their build closures
    pin the captured weights otherwise."""
    class Obj:
        pass

    cache = cb.get_compiled_cache()
    o = Obj()
    tok = cb.instance_token(o)
    cache.get("fn", (8,), lambda: (lambda: 1), instance=tok)
    cache.get("fn", (16,), lambda: (lambda: 2), instance=tok)
    other = cache.get("fn", (8,), lambda: (lambda: 3), instance="other")
    assert len(cache) == 3
    cb.invalidate_token(o)
    assert len(cache) == 1  # only the unrelated instance survives
    assert cache.get("fn", (8,), lambda: (lambda: 4),
                     instance="other") is other


def test_release_executables_walks_nested_pipelines(fresh_cache):
    from synapseml_tpu.core.pipeline import PipelineModel

    cache = cb.get_compiled_cache()
    inner = _make_onnx_mlp()
    pm = PipelineModel(stages=[inner])
    cache.get("onnx_model", (8,), lambda: (lambda: 1),
              instance=cb.instance_token(inner))
    assert len(cache) == 1
    cb.release_executables(pm)
    assert len(cache) == 0


def test_instance_token_survives_pickle_without_aliasing():
    """A stage pickled into a worker keeps its token (identical copies may
    share executables), while a stage minted in the receiving process draws
    a disjoint uuid — two DIFFERENT stages can never alias one entry."""
    import pickle

    model = _make_onnx_mlp()
    parent_token = cb.instance_token(model)
    copy = pickle.loads(pickle.dumps(parent_token))  # what travels
    fresh = _make_onnx_mlp()  # "worker-local" stage minting its own token
    assert copy == parent_token
    assert cb.instance_token(fresh) != parent_token


# ---------------------------------------------------------------------------
# property: bucketed/padded == unpadded, for every adopted stage
# ---------------------------------------------------------------------------

SIZES = (1, 3, 9)


def _make_onnx_mlp(din=4, dout=3, seed=0):
    from synapseml_tpu.onnx import ONNXModel
    from synapseml_tpu.onnx import proto as P
    from synapseml_tpu.onnx.proto import (AttributeProto, GraphProto,
                                          ModelProto, NodeProto,
                                          ValueInfoProto, numpy_to_tensor)

    rs = np.random.default_rng(seed)
    dh = 8
    W1 = rs.normal(size=(din, dh)).astype(np.float32)
    b1 = rs.normal(size=(dh,)).astype(np.float32)
    W2 = rs.normal(size=(dh, dout)).astype(np.float32)
    b2 = rs.normal(size=(dout,)).astype(np.float32)

    def node(op, inputs, outputs, **attrs):
        return NodeProto(input=list(inputs), output=list(outputs), op_type=op,
                         attribute=[AttributeProto.make(k, v)
                                    for k, v in attrs.items()])

    g = GraphProto(
        name="mlp",
        node=[node("Gemm", ["x", "W1", "b1"], ["h_pre"]),
              node("Relu", ["h_pre"], ["h"]),
              node("Gemm", ["h", "W2", "b2"], ["logits"]),
              node("Softmax", ["logits"], ["probs"], axis=-1)],
        initializer=[numpy_to_tensor(W1, "W1"), numpy_to_tensor(b1, "b1"),
                     numpy_to_tensor(W2, "W2"), numpy_to_tensor(b2, "b2")],
        input=[ValueInfoProto(name="x", elem_type=P.FLOAT, dims=["N", din])],
        output=[ValueInfoProto(name="probs", elem_type=P.FLOAT,
                               dims=["N", dout])],
    )
    return ONNXModel(ModelProto(graph=g).encode(),
                     feed_dict={"x": "features"},
                     fetch_dict={"probs": "probs"},
                     argmax_dict={"probs": "pred"}, mini_batch_size=64)


def _padded_vs_exact(transform, compare):
    """Run ``transform(n)`` under the pow-2 ladder and under the every-rung
    (unpadded) ladder; ``compare`` asserts equality per size."""
    for n in SIZES:
        padded = transform(n)
        with exact_bucketer():
            exact = transform(n)
        compare(padded, exact, n)


def test_onnx_bucketed_matches_unpadded(fresh_cache):
    model = _make_onnx_mlp()

    def transform(n):
        rs = np.random.default_rng(n)  # same inputs for padded and exact
        df = DataFrame.from_dict(
            {"features": rs.normal(size=(n, 4)).astype(np.float32)})
        out = model.transform(df)
        return (np.stack(list(out.collect_column("probs"))),
                np.asarray(out.collect_column("pred")))

    def compare(padded, exact, n):
        np.testing.assert_allclose(padded[0], exact[0], rtol=1e-6, atol=1e-7)
        np.testing.assert_array_equal(padded[1], exact[1])

    _padded_vs_exact(transform, compare)


def test_hf_embedder_bucketed_matches_unpadded(fresh_cache):
    from synapseml_tpu.hf import HuggingFaceSentenceEmbedder

    st = HuggingFaceSentenceEmbedder(model_name="bert-tiny", batch_size=8,
                                     max_token_len=16)

    def transform(n):
        df = DataFrame.from_dict({"text": np.asarray(
            [f"sentence number {i} with a few words" for i in range(n)],
            dtype=object)})
        return np.asarray(
            list(st.transform(df).collect_column("embeddings")))

    def compare(padded, exact, n):
        np.testing.assert_allclose(padded, exact, rtol=1e-5, atol=1e-6)

    _padded_vs_exact(transform, compare)


def test_hf_embedder_bucketed_matches_unpadded_normalized(fresh_cache):
    """The explicit L2-normalize param (cosine indexes) must not break
    pad-row invariance: padded == unpadded with normalize on, and the
    outputs actually ARE unit-norm."""
    from synapseml_tpu.hf import HuggingFaceSentenceEmbedder

    st = HuggingFaceSentenceEmbedder(model_name="bert-tiny", batch_size=8,
                                     max_token_len=16, normalize=True)

    def transform(n):
        df = DataFrame.from_dict({"text": np.asarray(
            [f"sentence number {i} with a few words" for i in range(n)],
            dtype=object)})
        return np.asarray(
            list(st.transform(df).collect_column("embeddings")))

    def compare(padded, exact, n):
        np.testing.assert_allclose(padded, exact, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.linalg.norm(padded, axis=-1), 1.0,
                                   atol=1e-5)

    _padded_vs_exact(transform, compare)


def test_hf_causal_lm_bucketed_matches_unpadded(fresh_cache):
    from synapseml_tpu.hf import HuggingFaceCausalLM

    st = HuggingFaceCausalLM(model_name="llama-tiny", max_new_tokens=4,
                             batch_size=4, prompt_bucket=8)

    def transform(n):
        df = DataFrame.from_dict({"prompt": np.asarray(
            [f"prompt {i}" for i in range(n)], dtype=object)})
        return list(st.transform(df).collect_column("completions"))

    def compare(padded, exact, n):
        for a, b in zip(padded, exact):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    _padded_vs_exact(transform, compare)


@pytest.fixture(scope="module")
def text_model():
    from synapseml_tpu.models import DeepTextClassifier

    rs = np.random.default_rng(0)
    texts = [f"{'good' if i % 2 else 'bad'} sample {i}" for i in range(16)]
    df = DataFrame.from_dict({
        "text": np.asarray(texts, dtype=object),
        "label": (np.arange(16) % 2).astype(np.int32)})
    return DeepTextClassifier(checkpoint="bert-tiny", num_classes=2,
                              batch_size=8, max_token_len=8, max_steps=2,
                              learning_rate=1e-3).fit(df)


def test_deep_text_bucketed_matches_unpadded(text_model, fresh_cache):
    def transform(n):
        df = DataFrame.from_dict({"text": np.asarray(
            [f"the {i} quick brown fox" for i in range(n)], dtype=object)})
        out = text_model.transform(df)
        return (np.asarray(list(out.collect_column("scores"))),
                np.asarray(out.collect_column("prediction")))

    def compare(padded, exact, n):
        np.testing.assert_allclose(padded[0], exact[0], rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(padded[1], exact[1])

    _padded_vs_exact(transform, compare)


class _CountingTokenizer:
    """Wraps a model's tokenizer as a caller timing it would: each call's
    texts and padded length, in order."""

    def __init__(self, inner, events=None):
        self.inner, self.calls, self.events = inner, [], events

    def __call__(self, texts, **kw):
        enc = self.inner(texts, **kw)
        self.calls.append((list(texts), enc["input_ids"].shape[1]))
        if self.events is not None:
            self.events.append(("tok", len(self.calls) - 1))
        return enc


def _streamed_texts():
    """19 rows in batches of 8: batches whose longest rows pad to 8, 16 and
    24 tokens, the last a partial slice of 3 rows."""
    words = lambda i, n: " ".join(f"w{i}x{j}" for j in range(n))
    return ([words(i, 3 + i % 4) for i in range(8)]
            + [words(i, 10 + i % 3) for i in range(8, 16)]
            + [words(i, 18 + i % 2) for i in range(16, 19)])


def _scores(out):
    return (np.asarray(list(out.collect_column("scores"))),
            np.asarray(out.collect_column("prediction")))


def test_deep_text_streamed_batches_match_one_slice_partitions(text_model,
                                                              fresh_cache):
    """A partition of several batches pads each batch to its own longest row
    and scores it as that batch alone, as its own partition, would score."""
    model = text_model.copy({"max_token_len": 32, "batch_size": 8})
    model._get_apply()
    model._tok = tok = _CountingTokenizer(model._tok)
    texts = _streamed_texts()
    got = _scores(model.transform(
        DataFrame.from_dict({"text": np.asarray(texts, dtype=object)})))
    bounds = [(0, 8), (8, 16), (16, 19)]
    want = [(texts[s:e], L) for (s, e), L in zip(bounds, (8, 16, 24))]
    assert tok.calls == want
    for s, e in bounds:
        alone = _scores(model.transform(DataFrame.from_dict(
            {"text": np.asarray(texts[s:e], dtype=object)})))
        np.testing.assert_array_equal(got[0][s:e], alone[0])
        np.testing.assert_array_equal(got[1][s:e], alone[1])
    assert tok.calls[3:] == want     # each alone: one call at the same length


class _StubResult:
    """A device result that records when the host turns it into numpy."""

    def __init__(self, k, rows, events):
        self.k, self.rows, self.events = k, rows, events

    def __array__(self, dtype=None, copy=None):
        self.events.append(("fetch", self.k))
        return np.full((self.rows, 2), 0.5, np.float32)


def _stub_model(text_model, events, shapes):
    model = text_model.copy({"max_token_len": 32, "batch_size": 8})
    model._get_apply()
    model._tok = _CountingTokenizer(model._tok, events)

    def run_for(bucket, seq_len):
        def run(ids, mask):
            assert ids.shape == mask.shape == (bucket, seq_len)
            k = len(shapes)
            shapes.append((bucket, seq_len))
            events.append(("dispatch", k))
            return _StubResult(k, bucket, events)
        return run

    model._apply_fn = run_for
    return model


def _score_batches():
    from synapseml_tpu.models.text import _SCORE_METRICS

    c = _SCORE_METRICS.get()["batches"]
    return {p: c.labels(prep=p).value for p in ("overlapped", "exposed")}


def test_deep_text_stream_order_and_counter(text_model, fresh_cache):
    """Batch k+1 is tokenized and dispatched before batch k is fetched; the
    counter reads one exposed batch a partition and the rest overlapped."""
    events, shapes = [], []
    model = _stub_model(text_model, events, shapes)
    texts = _streamed_texts()
    before = _score_batches()
    model.transform(DataFrame.from_dict(
        {"text": np.asarray(texts + texts[:8], dtype=object)}, num_partitions=2))
    after = _score_batches()
    # partitions of 14 and 13 rows: two batches each
    want = []
    for first in (0, 2):
        want += [("tok", first), ("dispatch", first),
                 ("tok", first + 1), ("dispatch", first + 1),
                 ("fetch", first), ("fetch", first + 1)]
    assert events == want
    assert after["exposed"] - before["exposed"] == 2
    assert after["overlapped"] - before["overlapped"] == 2

    events.clear(), shapes.clear(), model._tok.calls.clear()
    before = _score_batches()
    model.transform(DataFrame.from_dict({"text": np.asarray(texts, dtype=object)}))
    after = _score_batches()
    assert events == [("tok", 0), ("dispatch", 0), ("tok", 1), ("dispatch", 1),
                      ("fetch", 0), ("tok", 2), ("dispatch", 2), ("fetch", 1),
                      ("fetch", 2)]
    assert shapes == [(8, 8), (8, 16), (8, 24)]
    assert after["exposed"] - before["exposed"] == 1
    assert after["overlapped"] - before["overlapped"] == 2


def test_deep_text_one_slice_partition_tokenizes_once(text_model, fresh_cache):
    """A partition that fits one slice: one tokenizer call over all its rows
    at the partition's padded length, one executable."""
    events, shapes = [], []
    model = _stub_model(text_model, events, shapes)
    texts = _streamed_texts()[5:11]
    before = _score_batches()
    model.transform(DataFrame.from_dict({"text": np.asarray(texts, dtype=object)}))
    after = _score_batches()
    length = model._tok.inner(texts, max_len=32)["input_ids"].shape[1]
    assert model._tok.calls == [(texts, length)] and length == 16
    assert shapes == [(8, length)]
    assert events == [("tok", 0), ("dispatch", 0), ("fetch", 0)]
    assert after["exposed"] - before["exposed"] == 1
    assert after["overlapped"] - before["overlapped"] == 0


def test_deep_text_stream_compile_bound(text_model, fresh_cache):
    """A streamed partition builds one executable per distinct (bucket,
    length) of its batches, and a second transform builds none."""
    model = text_model.copy({"max_token_len": 32, "batch_size": 8})
    texts = _streamed_texts()
    texts = texts + texts[:8] + texts[8:11]  # a last slice of 6 rows pads to 16 again
    df = DataFrame.from_dict({"text": np.asarray(texts, dtype=object)})
    model._get_apply()
    slices = list(cb.default_bucketer().slices(len(texts), 8))
    pairs = {(bucket, model._tok(texts[s:e], max_len=32)["input_ids"].shape[1])
             for s, e, bucket in slices}
    assert len(slices) == 4 and pairs == {(8, 8), (8, 16), (8, 24)}
    misses0 = fresh_cache.miss_count("deep_text_model")
    first = _scores(model.transform(df))
    assert fresh_cache.miss_count("deep_text_model") - misses0 == len(pairs)
    second = _scores(model.transform(df))
    assert fresh_cache.miss_count("deep_text_model") - misses0 == len(pairs)
    np.testing.assert_array_equal(first[0], second[0])


def test_deep_vision_bucketed_matches_unpadded(fresh_cache):
    from synapseml_tpu.models import DeepVisionClassifier

    rs = np.random.default_rng(0)
    imgs = rs.normal(size=(12, 16, 16, 3)).astype(np.float32)
    df = DataFrame.from_rows(
        [{"image": imgs[i], "label": int(i % 2)} for i in range(12)])
    model = DeepVisionClassifier(backbone="resnet_tiny", num_classes=2,
                                 batch_size=8, max_steps=2).fit(df)

    def transform(n):
        qdf = DataFrame.from_rows([{"image": imgs[i % 12]} for i in range(n)])
        out = model.transform(qdf)
        return np.asarray(list(out.collect_column("scores")))

    def compare(padded, exact, n):
        np.testing.assert_allclose(padded, exact, rtol=1e-5, atol=1e-6)

    _padded_vs_exact(transform, compare)


def test_gbdt_bucketed_matches_unpadded(fresh_cache):
    from synapseml_tpu.gbdt import LightGBMClassifier

    rs = np.random.default_rng(3)
    X = rs.normal(size=(120, 6)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.int32)
    model = LightGBMClassifier(num_iterations=5, num_leaves=7,
                               max_bin=15).fit(
        DataFrame.from_dict({"features": X, "label": y}))

    def transform(n):
        rq = np.random.default_rng(n)  # same inputs for padded and exact
        df = DataFrame.from_dict(
            {"features": rq.normal(size=(n, 6)).astype(np.float32)})
        out = model.transform(df)
        return (np.asarray(list(out.collect_column("probability"))),
                np.asarray(out.collect_column("prediction")))

    def compare(padded, exact, n):
        np.testing.assert_allclose(padded[0], exact[0], rtol=1e-6)
        np.testing.assert_array_equal(padded[1], exact[1])

    _padded_vs_exact(transform, compare)


def test_gbdt_beyond_ladder_stays_out_of_shared_cache(fresh_cache):
    """Offline scans past the ladder keep their exact shape AND stay in the
    booster's per-instance cache — arbitrary large batch sizes must not
    churn the shared LRU and evict warmed serving executables."""
    from synapseml_tpu.gbdt import LightGBMRegressor

    rs = np.random.default_rng(0)
    X = rs.normal(size=(80, 4)).astype(np.float32)
    model = LightGBMRegressor(num_iterations=3, num_leaves=7, max_bin=15).fit(
        DataFrame.from_dict({"features": X, "label": X[:, 0]}))
    booster = model.get_booster()
    cache = cb.get_compiled_cache()
    big = cb.default_bucketer().max_bucket + 1
    before = cache.stats()["size"]
    for n in (big, big + 33):
        out = booster.raw_score(rs.normal(size=(n, 4)).astype(np.float32))
        assert out.shape[0] == n
    assert cache.stats()["size"] == before  # shared LRU untouched
    # serving-sized batches still go through the shared bucketed cache
    booster.raw_score(rs.normal(size=(5, 4)).astype(np.float32))
    assert cache.stats()["size"] == before + 1


def test_knn_bucketed_matches_unpadded(fresh_cache):
    from synapseml_tpu.nn import KNN

    rs = np.random.default_rng(5)
    X = rs.normal(size=(20, 4)).astype(np.float32)
    df = DataFrame.from_rows(
        [{"features": X[i], "values": f"v{i}"} for i in range(20)])
    model = KNN(k=3, query_batch=8).fit(df)

    def transform(n):
        rq = np.random.default_rng(n)  # same inputs for padded and exact
        qdf = DataFrame.from_rows(
            [{"features": rq.normal(size=4).astype(np.float32)}
             for _ in range(n)])
        return list(model.transform(qdf).collect_column("output"))

    def compare(padded, exact, n):
        assert len(padded) == len(exact) == n
        for a, b in zip(padded, exact):
            assert [m["index"] for m in a] == [m["index"] for m in b]
            np.testing.assert_allclose([m["distance"] for m in a],
                                       [m["distance"] for m in b], rtol=1e-5)

    _padded_vs_exact(transform, compare)


# ---------------------------------------------------------------------------
# compile-count bound: a mixed-size stream compiles <= ladder-many programs
# ---------------------------------------------------------------------------

def test_three_size_stream_compiles_ladder_bound(fresh_cache):
    """The satellite unit test: 3 distinct request sizes -> at most
    ladder-size executables, asserted via the cache miss counter."""
    model = _make_onnx_mlp()
    cache = cb.get_compiled_cache()
    rs = np.random.default_rng(0)
    for n in (1, 5, 17):
        model.transform(DataFrame.from_dict(
            {"features": rs.normal(size=(n, 4)).astype(np.float32)}))
    ladder_bound = len(cb.default_bucketer().buckets_upto(64))
    assert cache.stats()["misses"] <= ladder_bound
    # the same sizes again are pure hits
    misses_before = cache.stats()["misses"]
    for n in (1, 5, 17):
        model.transform(DataFrame.from_dict(
            {"features": rs.normal(size=(n, 4)).astype(np.float32)}))
    assert cache.stats()["misses"] == misses_before


class _RowsScorerT(Transformer):
    """Serving wrapper: each request body is {"rows": [[...], ...]} and all
    bodies in a drained batch flatten into ONE stage transform — so the
    served stage sees the mixed drained-batch sizes directly."""

    def __init__(self, stage, reply_of, **kw):
        super().__init__(**kw)
        self._stage = stage
        self._reply_of = reply_of

    def _transform(self, df):
        def per_part(p):
            counts = [len(b["rows"]) for b in p["body"]]
            flat = [np.asarray(r, np.float32) for b in p["body"]
                    for r in b["rows"]]
            out = dict(p)
            if not flat:
                out["reply"] = np.empty(0, dtype=object)
                return out
            replies = self._reply_of(self._stage, flat)
            grouped, i = [], 0
            for c in counts:
                grouped.append({"n": c, "first": replies[i] if c else None})
                i += c
            out["reply"] = np.asarray(grouped, dtype=object)
            return out

        return df.map_partitions(per_part)


def _post(address, payload):
    import urllib.request

    req = urllib.request.Request(address, data=json.dumps(payload).encode(),
                                 method="POST")
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read())


def test_served_mixed_stream_compile_bound_onnx_and_text(text_model,
                                                         fresh_cache):
    """Acceptance: a mixed-batch-size request stream (sizes across 1..64)
    through a served ONNXModel and a served deep-text stage triggers at most
    len(bucket_ladder) compiles each, via the cache miss counter."""
    from synapseml_tpu.io.serving import serve_pipeline

    cache = cb.get_compiled_cache()
    rs = np.random.default_rng(0)
    sizes = [1, 2, 3, 5, 8, 13, 21, 33, 48, 64]

    # registry counters are cumulative across the test session: assert on
    # the DELTA this stream causes
    onnx_misses0 = cache.miss_count("onnx_model")
    text_misses0 = cache.miss_count("deep_text_model")
    onnx = _make_onnx_mlp()
    srv = serve_pipeline(
        _RowsScorerT(onnx, lambda st, flat: [
            int(v) for v in st.transform(DataFrame.from_dict(
                {"features": np.stack(flat)})).collect_column("pred")]),
        batch_interval_ms=5)
    try:
        for n in sizes:
            reply = _post(srv.address,
                          {"rows": rs.normal(size=(n, 4)).tolist()})
            assert reply["n"] == n
    finally:
        srv.stop()
    onnx_misses = cache.miss_count("onnx_model") - onnx_misses0
    assert 0 < onnx_misses <= len(cb.default_bucketer().buckets_upto(64))

    def text_replies(st, flat):
        texts = ["short sample text"] * len(flat)
        out = st.transform(DataFrame.from_dict(
            {"text": np.asarray(texts, dtype=object)}))
        return [int(v) for v in out.collect_column("prediction")]

    srv = serve_pipeline(_RowsScorerT(text_model, text_replies),
                         batch_interval_ms=5)
    try:
        for n in sizes[:6]:  # bert is slower; sizes still span 3 rungs
            reply = _post(srv.address,
                          {"rows": rs.normal(size=(n, 1)).tolist()})
            assert reply["n"] == n
    finally:
        srv.stop()
    text_misses = cache.miss_count("deep_text_model") - text_misses0
    assert 0 < text_misses <= len(
        cb.default_bucketer().buckets_upto(text_model.get("batch_size")))


# ---------------------------------------------------------------------------
# adaptive serve-loop scheduler + warmup precompile + serving satellites
# ---------------------------------------------------------------------------

def _enqueue(server, n, age_s=0.0):
    from synapseml_tpu.io.serving import _Exchange

    for i in range(n):
        ex = _Exchange(f"r{i}-{time.monotonic_ns()}", "POST", "/", {}, b"{}")
        ex.enqueued_at -= age_s
        server._queue.put_nowait(ex)


@pytest.fixture()
def bare_server():
    from synapseml_tpu.io.serving import ServingServer

    srv = ServingServer()
    yield srv
    srv.stop()


def test_adaptive_flushes_full_bucket_immediately(bare_server):
    _enqueue(bare_server, 8)
    t0 = time.perf_counter()
    batch = bare_server.read_batch_adaptive(
        latency_budget_s=5.0, ladder=(8, 16))
    assert batch.count() == 8
    assert time.perf_counter() - t0 < 1.0  # did NOT wait out the budget


def test_adaptive_drains_backlog_past_the_first_rung(bare_server):
    # a deep queue must NOT flush at the smallest rung — the backlog drains
    # toward max_rows before any rung/budget decision
    _enqueue(bare_server, 20)
    t0 = time.perf_counter()
    batch = bare_server.read_batch_adaptive(
        latency_budget_s=0.05, ladder=(8, 16))
    assert batch.count() == 20
    assert time.perf_counter() - t0 < 2.0


def test_adaptive_waits_latency_budget_then_flushes_partial(bare_server):
    _enqueue(bare_server, 3)
    t0 = time.perf_counter()
    batch = bare_server.read_batch_adaptive(
        latency_budget_s=0.08, ladder=(8, 16))
    elapsed = time.perf_counter() - t0
    assert batch.count() == 3
    assert 0.03 < elapsed < 2.0  # waited toward the budget, then flushed


def test_adaptive_single_request_flushes_immediately(bare_server):
    _enqueue(bare_server, 1)
    t0 = time.perf_counter()
    batch = bare_server.read_batch_adaptive(
        latency_budget_s=5.0, ladder=(8, 16))
    assert batch.count() == 1
    assert time.perf_counter() - t0 < 1.0


def test_expired_requests_dropped_not_served(bare_server):
    from synapseml_tpu.core import observability as obs

    _enqueue(bare_server, 2, age_s=bare_server.reply_timeout_s + 1)
    _enqueue(bare_server, 1)
    batch = bare_server.read_batch(timeout_s=0.01)
    assert batch.count() == 1  # the two expired ones never reach the stage
    snap = obs.get_registry().snapshot()
    assert snap.get("synapseml_serving_expired_requests_total", 0) >= 2


def test_empty_batch_schema_cached(bare_server):
    a = bare_server.read_batch(timeout_s=0.001)
    b = bare_server.read_batch(timeout_s=0.001)
    assert a.is_empty()
    assert a is b  # one immutable schema'd empty batch, reused per poll
    assert sorted(a.columns) == ["body", "id", "method", "path"]


def test_warmup_precompiles_ladder_buckets(fresh_cache):
    """/admin/load's warmup path: with a configured bucket ladder, warmup
    compiles EVERY rung's executable before the swap — follow-up requests
    at any rung size add zero misses (zero-compile-stall)."""
    from synapseml_tpu.io.serving import serve_pipeline

    cache = cb.get_compiled_cache()
    onnx = _make_onnx_mlp()
    stage = _RowsScorerT(onnx, lambda st, flat: [
        int(v) for v in st.transform(DataFrame.from_dict(
            {"features": np.stack(flat)})).collect_column("pred")])
    srv = serve_pipeline(stage, batch_interval_ms=5, bucket_ladder=(8, 16))
    try:
        warmed = srv._warmup(stage, rows=[{"rows": [[0.1] * 4]}])
        assert warmed == 1 + 8 + 16  # given size plus each ladder rung
        misses_after_warmup = cache.stats()["misses"]
        assert misses_after_warmup >= 2  # one executable per stage rung
        rs = np.random.default_rng(0)
        for n in (1, 4, 8, 11, 16):
            reply = _post(srv.address,
                          {"rows": rs.normal(size=(n, 4)).tolist()})
            assert reply["n"] == n
        assert cache.stats()["misses"] == misses_after_warmup
    finally:
        srv.stop()


def test_default_warmup_buckets_capped_and_coalesce_validated():
    from synapseml_tpu.io.serving import serve_pipeline

    srv = serve_pipeline(_make_onnx_mlp(), batch_interval_ms=5)
    try:
        # default: flush at the full process ladder, warm only the
        # latency-sensitive small rungs (deploy-plane load timeout safety)
        assert srv._bucket_ladder == tuple(
            b for b in cb.default_bucketer().ladder if b <= 1024)
        assert srv._warmup_buckets == tuple(
            b for b in srv._bucket_ladder if b <= 64)
    finally:
        srv.stop()
    from synapseml_tpu.io.distributed_serving import serve_pipeline_distributed

    with pytest.raises(ValueError, match="micro-batch"):
        serve_pipeline_distributed(_make_onnx_mlp(), num_workers=1,
                                   batch_interval_ms=0,
                                   coalesce_window_ms=5.0)


def test_reply_batch_routes_under_single_lock(bare_server):
    from synapseml_tpu.io.serving import _Exchange

    exchanges = [_Exchange(f"id{i}", "POST", "/", {}, b"") for i in range(4)]
    with bare_server._lock:
        for ex in exchanges:
            bare_server._pending[ex.request_id] = ex
    df = DataFrame.from_dict({
        "id": np.asarray([f"id{i}" for i in range(4)] + ["ghost"],
                         dtype=object),
        "reply": np.asarray([{"i": i} for i in range(5)], dtype=object)})
    n = bare_server.reply_batch(df)
    assert n == 4  # ghost id skipped, everyone else woken
    assert all(ex.reply_event.is_set() for ex in exchanges)
    assert json.loads(exchanges[2].reply_body) == {"i": 2}


def test_request_coalescer_groups_same_path():
    from synapseml_tpu.io.distributed_serving import _RequestCoalescer

    co = _RequestCoalescer(window_s=0.2, max_group=4)
    groups = []

    def join():
        groups.append(co.join("/score"))

    threads = [threading.Thread(target=join) for _ in range(4)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # all four landed in one group, released EARLY on reaching max_group
    assert len({id(g) for g in groups}) == 1
    assert groups[0].count == 4
    assert time.perf_counter() - t0 < 0.19
    # a later joiner starts a fresh group (the old one is closed)
    g2 = co.join("/score")
    assert g2 is not groups[0] and g2.count == 1
