"""The five readers of the fit loop's own spans and counters, on a hand-built
span list: the shares by hand arithmetic, and `None` wherever the spans are not
the measured window's."""

import gzip
import os
from types import SimpleNamespace

import pytest

from perfbench.layer_metrics import (chunk_build_share, chunk_wait_share,
                                     dispatch_gap_share, place_share,
                                     scan_step_compiles)
from perfbench.lib import program_spans
from perfbench.lib.manifest import BENCH_DIR

T0 = 1_790_000_000_000_000_000      # epoch ns
MS = 1_000_000


def span(name, start_ms, dur_ms, parent="root", span_id=None, trace="t1", **attributes):
    return SimpleNamespace(
        name=name, trace_id=trace, span_id=span_id or f"{name}@{start_ms}",
        parent_id=parent, start_ns=T0 + int(start_ms * MS), duration_ms=float(dur_ms),
        attributes=attributes)


def a_window():
    """A fit of 10 s and 3 dispatches. Cycle n: wait, place, dispatch, fetch.

        wait      0..10      3000..3004    6000..6002    9000..9001 (END)
        place    10..110     3004..3304    6002..6302
        dispatch 110..120    3304..3314    6302..6312
        fetch    120..3000   3314..6000    6312..9000
    """
    spans = [span("train.fit", -5000, 900, parent=None, span_id="old", trace="t0"),
             span("train.dispatch", -4900, 10, parent="old", trace="t0"),
             span("train.fit", 0, 10_000, parent=None, span_id="root")]
    for start, wait in ((0, 10), (3000, 4), (6000, 2)):
        spans += [span("train.chunk_wait", start, wait),
                  span("train.place", start + wait, 100 if start == 0 else 300, bytes=1),
                  span("train.dispatch", start + wait + (100 if start == 0 else 300), 10)]
    spans += [span("train.chunk_wait", 9000, 1),
              span("train.fetch", 120, 2880), span("train.fetch", 3314, 2686),
              span("train.fetch", 6312, 2688),
              span("data.prefetch", 50, 5, parent=None, trace="t9")]
    for start in (0, 2500, 5000):
        spans.append(span("train.chunk_build", start, 2400, next_ms=700.0, stack_ms=300.0,
                          put_wait_ms=1400.0, bytes=1, steps=8))
    return spans


FACTS = {"window_s": 10.0, "dispatches": 3, "peaks": {"bf16_flops_per_s": 197e12}}


@pytest.fixture()
def spans(monkeypatch):
    held = a_window()
    monkeypatch.setattr(program_spans, "finished_spans", lambda: list(held))
    return held


@pytest.mark.parametrize("reader,want", [
    (chunk_wait_share, 100 * (10 + 4 + 2 + 1) / 10_000),
    (place_share, 100 * (100 + 300 + 300) / 10_000),
    (chunk_build_share, 100 * 3 * (700 + 300) / 10_000),
    # fetch 1 ends at 3000, dispatch 2 at 3314; fetch 2 at 6000, dispatch 3 at 6312
    (dispatch_gap_share, 100 * (314 + 312) / 10_000),
], ids=lambda x: getattr(x, "__name__", "").rsplit(".", 1)[-1] or None)
def test_shares_by_hand(spans, reader, want):
    assert reader.read(FACTS) == pytest.approx(want, rel=1e-9)


def test_the_window_is_the_last_root_and_its_own_children(spans):
    children = program_spans.window(FACTS)
    assert children["train.fit"][0].span_id == "root"
    assert [len(children[n]) for n in ("train.dispatch", "train.place", "train.fetch",
                                       "train.chunk_wait", "train.chunk_build")] \
        == [3, 3, 3, 4, 3]
    assert "data.prefetch" not in children
    starts = [s.start_ns for s in children["train.fetch"]]
    assert starts == sorted(starts)


SHARES = [chunk_wait_share, chunk_build_share, place_share, dispatch_gap_share]


@pytest.mark.parametrize("reader", SHARES, ids=lambda m: m.__name__.rsplit(".", 1)[-1])
@pytest.mark.parametrize("fault", ["root_too_long", "root_too_short", "dispatch_missing",
                                   "no_root", "rehearsal"])
def test_none_where_the_spans_are_not_the_windows(spans, reader, fault):
    facts = dict(FACTS)
    if fault == "root_too_long":
        facts["window_s"] = 10.0 / 1.021
    elif fault == "root_too_short":
        facts["window_s"] = 10.0 / 0.979
    elif fault == "dispatch_missing":          # the ring dropped one
        spans.remove(next(s for s in spans if s.name == "train.dispatch"
                          and s.parent_id == "root"))
    elif fault == "no_root":                   # a parent commit records none
        spans[:] = [s for s in spans if s.name == "data.prefetch"]
    elif fault == "rehearsal":
        facts["peaks"] = None
    assert reader.read(facts) is None


def test_within_two_percent_still_reads(spans):
    assert place_share.read({**FACTS, "window_s": 10.0 / 1.019}) is not None
    assert place_share.read({**FACTS, "window_s": 10.0 / 0.981}) is not None


def test_gap_needs_a_fetch_for_every_dispatch(spans):
    spans.remove(next(s for s in spans if s.name == "train.fetch"))
    assert dispatch_gap_share.read(FACTS) is None
    assert place_share.read(FACTS) is not None


def test_compile_counter_is_read_from_the_registry():
    from synapseml_tpu.core import observability as obs

    reg = obs.reset_registry()
    try:
        assert scan_step_compiles.read(FACTS) is None      # no such series: left out
        family = reg.counter("synapseml_train_step_compiles_total", "", ("program",))
        family.inc(program="scan")
        family.inc(program="scan")
        family.inc(program="step")
        value = scan_step_compiles.read(FACTS)
        assert value == 2 and isinstance(value, int)
        assert scan_step_compiles.read({**FACTS, "peaks": None}) is None
    finally:
        obs.reset_registry()


def test_readers_take_the_programs_own_spans():
    """A real tracer's spans have what the readers use."""
    from synapseml_tpu.core import observability as obs

    tracer = obs.reset_tracer()
    try:
        with tracer.span("train.fit") as root:
            with tracer.span("train.dispatch"):
                pass
        facts = {"window_s": root.duration_ms / 1e3, "dispatches": 1, "peaks": {}}
        children = program_spans.window(facts)
        assert [s.name for s in children["train.dispatch"]] == ["train.dispatch"]
        assert program_spans.end_ns(root) == pytest.approx(root.end_ns, abs=1)
        assert place_share.read(facts) == 0.0
    finally:
        obs.reset_tracer()


def test_profile_start_of_the_recorded_trace(tmp_path):
    path = tmp_path / "tiny.xplane.pb"
    with gzip.open(os.path.join(BENCH_DIR, "testdata", "tiny_train.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    assert program_spans.profile_start_ns(str(path)) == 1790706377994038586
