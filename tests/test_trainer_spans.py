"""The fit loop times itself: `Trainer.fit`'s spans and counters on the
profiler's clock, the scope names inside the step, and the gauges that read
them (a two-layer encoder on the CPU; times here prove order and clock, never
speed)."""

import glob
import os
import threading

import jax
import numpy as np
import pytest

from synapseml_tpu.core import instrumentation
from synapseml_tpu.core import observability as obs
from synapseml_tpu.models.flax_nets.bert import BertClassifier, bert_tiny
from synapseml_tpu.models.trainer import Trainer, TrainerConfig

CHUNK, DISPATCHES, BATCH = 2, 3, 8
COMPILES = 'synapseml_train_step_compiles_total{program="%s"}'
DISPATCHED = 'synapseml_train_dispatches_total{program="%s"}'
LOOP_MS = 'synapseml_train_loop_ms{phase="%s"}'


def _batch(seed=0, B=BATCH, T=16, vocab=1024):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, vocab, (B, T)).astype(np.int32),
            "attention_mask": np.ones((B, T), np.int32),
            "labels": rng.integers(0, 2, (B,)).astype(np.int32)}


def _trainer(mesh, **kw):
    return Trainer(BertClassifier(bert_tiny(), num_classes=2), mesh,
                   TrainerConfig(total_steps=100), **kw)


def _children(spans, root):
    out = {}
    for s in sorted(spans, key=lambda s: s.start_ns):
        if s.parent_id == root.span_id:
            out.setdefault(s.name, []).append(s)
    return out


def _roots(spans):
    return [s for s in spans if s.name == "train.fit"]


@pytest.fixture(scope="module")
def chunked(mesh_dp8):
    """One chunked fit of 3 dispatches, then a second fit on its state."""
    obs.reset_tracer()
    obs.reset_registry()
    tr = _trainer(mesh_dp8)
    n = CHUNK * DISPATCHES
    state = tr.init_state(_batch(), jax.random.PRNGKey(0))
    state = tr.fit(state, iter([_batch(i) for i in range(n)]), max_steps=n,
                   scan_chunk=CHUNK, log_every=CHUNK)
    first = {"spans": obs.get_tracer().finished_spans(),
             "snapshot": obs.get_registry().snapshot(),
             "metrics": list(tr.metrics)}
    state = tr.fit(state, iter([_batch(i) for i in range(n)]), max_steps=n,
                   scan_chunk=CHUNK)
    spans = obs.get_tracer().finished_spans()
    second_root = _roots(spans)[-1]
    return {**first, "root": _roots(first["spans"])[0],
            "second": _children(spans, second_root), "second_root": second_root,
            "snapshot_after": obs.get_registry().snapshot(),
            "main_tid": threading.get_ident(),
            # both signatures of its scanned step are compiled: later tests
            # that need no fresh compile train on it from a fresh state
            "trainer": tr}


# ---- (a) the spans of a chunked fit ---------------------------------------

def test_one_root_a_fit(chunked):
    roots = _roots(chunked["spans"])
    assert len(roots) == 1 and roots[0].parent_id is None
    assert roots[0].attributes == {"scan_chunk": CHUNK, "first_step": 0,
                                   "steps_done": CHUNK * DISPATCHES}


# a chunked dispatch has two `train.place` spans: the producer's, which moves the
# chunk's bytes before the loop asks for it, and the dispatch's own, which then
# finds nothing left to move
PLACES = 2 * DISPATCHES


def _places(kids, main_tid):
    """(the producer's `train.place` spans, the dispatches' own), by start."""
    ahead = [s for s in kids["train.place"] if s.tid != main_tid]
    own = [s for s in kids["train.place"] if s.tid == main_tid]
    return ahead, own


@pytest.mark.parametrize("name,count", [
    ("train.dispatch", DISPATCHES), ("train.place", PLACES),
    ("train.fetch", DISPATCHES), ("train.chunk_wait", DISPATCHES + 1),
    ("train.chunk_build", DISPATCHES)])
def test_children_of_the_root(chunked, name, count):
    kids = _children(chunked["spans"], chunked["root"])[name]
    assert len(kids) == count
    assert all(s.trace_id == chunked["root"].trace_id and s.duration_ms >= 0
               and s.end_ns >= s.start_ns for s in kids)
    # a child lies inside its root on the epoch-ns clock
    assert all(chunked["root"].start_ns <= s.start_ns
               and s.end_ns <= chunked["root"].end_ns + 1_000_000 for s in kids)


def test_dispatch_spans_count_steps(chunked):
    kids = _children(chunked["spans"], chunked["root"])["train.dispatch"]
    assert [s.attributes["steps"] for s in kids] == [CHUNK] * DISPATCHES
    assert [s.attributes["first_step"] for s in kids] == [0, CHUNK, 2 * CHUNK]
    assert all(s.attributes["program"] == "scan" for s in kids)


def test_place_counts_the_chunk_bytes(chunked):
    per_chunk = CHUNK * sum(v.nbytes for v in _batch().values())
    kids = _children(chunked["spans"], chunked["root"])
    ahead, own = _places(kids, chunked["main_tid"])
    # a chunk's bytes are counted once: where they left the host
    assert [s.attributes["bytes"] for s in ahead] == [per_chunk] * DISPATCHES
    assert [s.attributes["bytes"] for s in own] == [0] * DISPATCHES
    assert sum(s.attributes["bytes"] for s in kids["train.place"]) \
        == per_chunk * DISPATCHES
    assert [s.attributes["bytes"] for s in kids["train.chunk_build"]] \
        == [per_chunk] * DISPATCHES


def test_every_chunked_dispatch_found_its_chunk_on_the_device(chunked):
    kids = _children(chunked["spans"], chunked["root"])
    ahead, own = _places(kids, chunked["main_tid"])
    assert [s.attributes["ahead"] for s in own] == [True] * DISPATCHES
    # `ahead` is the dispatch's own finding: one a dispatch, so a share of
    # dispatches can be counted from the spans that carry it
    assert all("ahead" not in s.attributes for s in ahead)


@pytest.mark.parametrize("on_device", [False, True], ids=["host_arrays", "device_arrays"])
def test_train_steps_scan_says_whether_its_input_was_ahead(chunked, on_device):
    obs.reset_tracer()
    tr = chunked["trainer"]
    state = tr.init_state(_batch(), jax.random.PRNGKey(0))
    stacked = {k: np.stack([v] * CHUNK) for k, v in _batch().items()}
    nbytes = sum(v.nbytes for v in stacked.values())
    if on_device:
        stacked = tr.mesh.shard_stacked_batch(stacked)
    tr.train_steps_scan(state, stacked)
    (place,) = [s for s in obs.get_tracer().finished_spans() if s.name == "train.place"]
    assert place.attributes == {"bytes": 0 if on_device else nbytes, "ahead": on_device}


def test_chunk_build_comes_from_the_producer_thread(chunked):
    builds = _children(chunked["spans"], chunked["root"])["train.chunk_build"]
    assert all(s.tid != chunked["main_tid"] for s in builds)
    assert chunked["root"].tid == chunked["main_tid"]
    for s in builds:
        a = s.attributes
        assert a["steps"] == CHUNK
        assert min(a["next_ms"], a["stack_ms"], a["put_wait_ms"]) >= 0
        assert a["next_ms"] + a["stack_ms"] + a["put_wait_ms"] <= s.duration_ms + 1.0


def test_the_producer_places_inside_stack_ms(chunked):
    """`stack_ms` runs from the last `next()` to the `put`: the producer's
    `train.place` lies inside it, so `chunk_build_share` stays its busy share."""
    kids = _children(chunked["spans"], chunked["root"])
    ahead, _ = _places(kids, chunked["main_tid"])
    for build, place in zip(kids["train.chunk_build"], ahead):
        assert place.tid == build.tid
        assert build.start_ns <= place.start_ns and place.end_ns <= build.end_ns + 1000
        assert place.duration_ms <= build.attributes["stack_ms"] + 1e-3


def test_the_loop_order_within_a_cycle(chunked):
    kids = _children(chunked["spans"], chunked["root"])
    ahead, own = _places(kids, chunked["main_tid"])
    for wait, placed, place, dispatch, fetch in zip(
            kids["train.chunk_wait"], ahead, own, kids["train.dispatch"],
            kids["train.fetch"]):
        # the chunk is on its way to the device before the loop has it ...
        assert placed.end_ns <= wait.end_ns + 1000
        # ... and the loop goes on as before: its own placement finds nothing
        # to move, then the program, then its losses
        assert wait.end_ns <= place.start_ns + 1000
        assert place.end_ns <= dispatch.start_ns + 1000
        assert dispatch.end_ns <= fetch.start_ns + 1000
    # every chunk after the first was placed while an earlier one trained:
    # before the fetch that precedes its own dispatch had returned
    for placed, fetch_before in zip(ahead[1:], kids["train.fetch"]):
        assert placed.start_ns <= fetch_before.end_ns


# ---- (b) which dispatch compiled -------------------------------------------

def test_compiled_is_the_first_dispatch(chunked):
    kids = _children(chunked["spans"], chunked["root"])["train.dispatch"]
    assert kids[0].attributes["compiled"] is True
    # jax 0.9.0 meets a second signature at the second dispatch: the state is
    # then the step's own output (PERF.md, section 7), and never again
    assert [s.attributes["compiled"] for s in kids[1:]] == [True, False]


def test_compile_counter_equals_compiled_spans(chunked):
    kids = _children(chunked["spans"], chunked["root"])["train.dispatch"]
    n = sum(s.attributes["compiled"] for s in kids)
    assert chunked["snapshot"][COMPILES % "scan"] == n == 2
    assert chunked["snapshot"][DISPATCHED % "scan"] == DISPATCHES


def test_second_fit_on_the_returned_state_compiles_nothing(chunked):
    kids = chunked["second"]["train.dispatch"]
    assert len(kids) == DISPATCHES
    assert [s.attributes["compiled"] for s in kids] == [False] * DISPATCHES
    assert [s.attributes["first_step"] for s in kids] \
        == [CHUNK * DISPATCHES + i * CHUNK for i in range(DISPATCHES)]
    assert chunked["second_root"].trace_id != chunked["root"].trace_id
    assert chunked["snapshot_after"][COMPILES % "scan"] == 2
    assert chunked["snapshot_after"][DISPATCHED % "scan"] == 2 * DISPATCHES


@pytest.mark.parametrize("phase,count", [
    ("chunk_wait", DISPATCHES + 1), ("place", PLACES), ("dispatch", DISPATCHES),
    ("fetch", DISPATCHES), ("chunk_build", DISPATCHES)])
def test_loop_histogram_has_one_observation_a_span(chunked, phase, count):
    hist = chunked["snapshot"][LOOP_MS % phase]
    assert hist["count"] == count
    spans = _children(chunked["spans"], chunked["root"])["train." + phase]
    assert hist["sum"] == pytest.approx(sum(s.duration_ms for s in spans), abs=2e-3)


# ---- the trainer's own gauges (C13) ----------------------------------------

def test_step_duration_is_one_observation_a_cycle_after_the_first(chunked):
    hist = chunked["snapshot"]['synapseml_train_step_duration_ms{engine="trainer"}']
    assert hist["count"] == DISPATCHES - 1
    fetch = _children(chunked["spans"], chunked["root"])["train.fetch"]
    cycles = [(b.end_ns - a.end_ns) / 1e6 / CHUNK for a, b in zip(fetch, fetch[1:])]
    assert hist["sum"] == pytest.approx(sum(cycles), abs=2e-3)


def test_throughput_leaves_the_first_dispatch_out(chunked):
    entries = chunked["metrics"]
    assert [e["step"] for e in entries] == [CHUNK, 2 * CHUNK, 3 * CHUNK]
    # nothing to divide by until a second cycle has ended
    assert "samples_per_sec" not in entries[0]
    fetch = _children(chunked["spans"], chunked["root"])["train.fetch"]
    want = 2 * CHUNK * BATCH * 1e9 / (fetch[-1].end_ns - fetch[0].end_ns)
    assert entries[-1]["samples_per_sec"] == pytest.approx(want, rel=1e-9)
    assert chunked["snapshot"]['synapseml_train_samples_per_sec{engine="trainer"}'] \
        == pytest.approx(want, rel=1e-9)
    assert not {"mfu", "model_tflops_per_sec"} & set(entries[-1])
    assert not any(k.startswith("synapseml_train_mfu") for k in chunked["snapshot"])


# ---- (c) the per-step path --------------------------------------------------

@pytest.fixture(scope="module")
def per_step(mesh_dp8, tmp_path_factory):
    from synapseml_tpu.parallel import AsyncCheckpointer

    obs.reset_tracer()
    obs.reset_registry()
    tr = _trainer(mesh_dp8)
    state = tr.init_state(_batch(), jax.random.PRNGKey(0))
    with AsyncCheckpointer(str(tmp_path_factory.mktemp("ck")), keep=10) as ck:
        tr.fit(state, iter([_batch(i) for i in range(4)]), max_steps=4,
               scan_chunk=1, log_every=2, checkpointer=ck, checkpoint_every=2)
    spans = obs.get_tracer().finished_spans()
    return {"root": _roots(spans)[0], "kids": _children(spans, _roots(spans)[0]),
            "snapshot": obs.get_registry().snapshot()}


def test_per_step_path_records_program_step(per_step):
    kids = per_step["kids"]["train.dispatch"]
    assert [s.attributes["program"] for s in kids] == ["step"] * 4
    assert [s.attributes["first_step"] for s in kids] == [0, 1, 2, 3]
    assert [s.attributes["steps"] for s in kids] == [1] * 4
    assert per_step["root"].attributes == {"scan_chunk": 1, "first_step": 0,
                                           "steps_done": 4}
    assert per_step["snapshot"][DISPATCHED % "step"] == 4
    assert per_step["snapshot"][COMPILES % "step"] \
        == sum(s.attributes["compiled"] for s in kids) >= 1
    assert "train.chunk_wait" not in per_step["kids"]


def test_per_step_path_fetches_at_log_every(per_step):
    assert len(per_step["kids"]["train.place"]) == 4
    assert len(per_step["kids"]["train.fetch"]) == 2      # log_every=2 of 4 steps


def test_checkpoint_span_only_when_it_saves(per_step):
    saves = per_step["kids"]["train.checkpoint"]
    assert [s.attributes["step"] for s in saves] == [2, 4]   # 4 is also the final one
    assert per_step["snapshot"][LOOP_MS % "checkpoint"]["count"] == 2


def test_dispatch_outside_fit_has_no_step_number(mesh_dp8):
    obs.reset_tracer()
    tr = _trainer(mesh_dp8)
    state = tr.init_state(_batch(), jax.random.PRNGKey(0))
    tr.train_step(state, _batch())
    (d,) = [s for s in obs.get_tracer().finished_spans() if s.name == "train.dispatch"]
    assert d.attributes["first_step"] is None and d.parent_id is None


def test_an_odd_tail_runs_per_step_under_the_same_root(chunked):
    obs.reset_tracer()
    tr = chunked["trainer"]
    state = tr.init_state(_batch(), jax.random.PRNGKey(0))
    state = tr.fit(state, iter([_batch(i) for i in range(3)]), max_steps=3, scan_chunk=2)
    spans = obs.get_tracer().finished_spans()
    kids = _children(spans, _roots(spans)[0])
    assert [(s.attributes["program"], s.attributes["first_step"])
            for s in kids["train.dispatch"]] == [("scan", 0), ("step", 2)]
    assert [s.attributes["steps"] for s in kids["train.chunk_build"]] == [2, 1]
    assert int(state.step) == 3


def test_a_failing_loader_ends_the_root_with_the_error(mesh_dp8):
    obs.reset_tracer()
    tr = _trainer(mesh_dp8)
    state = tr.init_state(_batch(), jax.random.PRNGKey(0))

    def batches():
        yield _batch(0)
        raise ValueError("shard unreadable")

    with pytest.raises(ValueError, match="shard unreadable"):
        tr.fit(state, batches(), max_steps=8, scan_chunk=2)
    root = _roots(obs.get_tracer().finished_spans())[0]
    assert root.status == "error" and "shard unreadable" in root.attributes["error"]
    assert tr._fit_step is None


# ---- (d) the clock ------------------------------------------------------------

def test_spans_lie_on_the_profilers_clock(chunked, tmp_path):
    """With the profiler's host tracer on, each `train.dispatch` span's
    `start_ns - profile_start_time` is the time of its own `TraceAnnotation`
    in the trace."""
    from perfbench.lib import program_spans, xplane

    tr = chunked["trainer"]                                 # compiled before the trace
    state = tr.init_state(_batch(), jax.random.PRNGKey(0))
    n = CHUNK * DISPATCHES
    obs.reset_tracer()
    with instrumentation.profile_trace(str(tmp_path), host_tracer_level=1):
        tr.fit(state, iter([_batch(i) for i in range(n)]), max_steps=n, scan_chunk=CHUNK)
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                     "*.xplane.pb"))
    origin = program_spans.profile_start_ns(path)
    assert origin is not None and abs(origin - obs.get_tracer().finished_spans()[0].start_ns) < 60e9
    annotated = sorted(
        (start, end) for plane in xplane.read(path, lambda name: name.startswith("/host:"))
        for line in plane["lines"] for name, start, end, _ in line["events"]
        if name == "train.dispatch")
    spans = sorted((s.start_ns - origin, s.end_ns - origin)
                   for s in obs.get_tracer().finished_spans() if s.name == "train.dispatch")
    assert len(annotated) == len(spans) == DISPATCHES
    for (a0, a1), (s0, s1) in zip(annotated, spans):
        assert abs(a0 - s0) < 1e6 and abs(a1 - s1) < 1e6     # 1 ms, in ns


def test_profile_trace_passes_its_level_on(monkeypatch, tmp_path):
    seen = []

    class FakeTrace:
        def __init__(self, log_dir, **kw):
            seen.append(kw)

        def __enter__(self):
            return None

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "trace", FakeTrace)
    with instrumentation.profile_trace(str(tmp_path)):
        pass
    with instrumentation.profile_trace(str(tmp_path), host_tracer_level=2):
        pass
    levels = [(kw["profiler_options"].host_tracer_level,
               kw["profiler_options"].python_tracer_level) for kw in seen]
    assert levels == [(0, 0), (2, 0)]


# ---- (e) scope names inside the step ---------------------------------------

def _user_loss(trainer):
    def loss_fn(variables, batch):
        logits = trainer.module.apply(variables, batch["input_ids"],
                                      batch["attention_mask"])
        return (logits ** 2).mean()

    return loss_fn


@pytest.mark.parametrize("user_loss", [False, True], ids=["default_loss", "user_loss_fn"])
def test_scanned_step_carries_the_scope_names(mesh_dp8, user_loss):
    tr = _trainer(mesh_dp8)
    if user_loss:
        tr._loss_fn = _user_loss(tr)
    state = tr.init_state(_batch(), jax.random.PRNGKey(0))
    stacked = {k: np.stack([v, v]) for k, v in _batch().items()}
    tr.train_steps_scan(state, stacked)                     # builds _scan_step
    state = tr.init_state(_batch(), jax.random.PRNGKey(0))  # the first was donated
    sd = state.as_dict() | {"batch_stats": None}
    with tr.mesh.scope():
        text = tr._scan_step.lower(sd, tr.mesh.shard_stacked_batch(stacked)) \
            .as_text(debug_info=True)
    # op names in the lowered text are relative to jit(multi)/while/body
    for scope in ('"jvp(forward)/', '"transpose(jvp(forward))/', '"optimizer/',
                  '"step_metrics/'):
        assert scope in text, scope


# ---- Span -------------------------------------------------------------------

def test_span_is_on_the_epoch_ns_clock():
    import time

    before = time.time_ns()
    tracer = obs.Tracer()
    with tracer.span("x") as s:
        assert s.end_ns is None
    after = time.time_ns()
    assert before <= s.start_ns <= s.end_ns <= after
    assert s.start_wall == s.start_ns / 1e9
    d = s.to_dict()
    assert d["start_ns"] == s.start_ns and d["start_wall"] == s.start_wall
    (event,) = [e for e in obs.chrome_trace_events([d])["traceEvents"] if e["ph"] == "X"]
    assert event["ts"] == pytest.approx(s.start_ns / 1e3, abs=1.0)   # microseconds


def test_discarded_span_leaves_nothing_behind():
    tracer = obs.Tracer()
    with tracer.span("outer") as outer:
        dropped = tracer.start_span("inner")
        tracer.discard_span(dropped)
        assert tracer.current_span() is outer
    assert [s.name for s in tracer.finished_spans()] == ["outer"]
