"""The five readers of what the program records when its scanned step gets an
executable, on a span ring and a registry built by hand: the value, `None`
without the series (a parent commit) and `None` in a rehearsal."""

from types import SimpleNamespace

import pytest

from perfbench.layer_metrics import (scan_build_s, scan_rebuild_s, step_code_mb,
                                     step_resident_gb, step_temp_gb)
from perfbench.lib import program_spans
from perfbench.lib.manifest import load_manifest

FACTS = {"peaks": {"bf16_flops_per_s": 197e12}}
READERS = [scan_build_s, scan_rebuild_s, step_temp_gb, step_resident_gb, step_code_mb]
BYTES = {"args": 6_900_000_000, "outputs": 6_830_000_000, "aliased": 6_820_000_000,
         "temp": 9_380_000_000, "code": 41_500_000}


def compiled(program, signature, trace_ms, lower_ms, backend_ms, **more):
    return SimpleNamespace(name="train.compile", attributes={
        "program": program, "signature": signature, "trace_ms": trace_ms,
        "lower_ms": lower_ms, "backend_ms": backend_ms, "cache": "hit", **more})


def two_signatures():
    """An encoder job's ring: the scanned step built twice, the per-step
    program once, and a span that is none of the readers' business."""
    return [SimpleNamespace(name="train.dispatch", attributes={"program": "scan"}),
            compiled("scan", 1, 9000.0, 1500.0, 7000.0),
            compiled("scan", 2, 8000.0, 1250.0, 750.0, temp_bytes=BYTES["temp"]),
            compiled("step", 1, 100.0, 20.0, 30.0)]


@pytest.fixture()
def ring(monkeypatch):
    held = two_signatures()
    monkeypatch.setattr(program_spans, "finished_spans", lambda: list(held))
    return held


@pytest.fixture()
def registry():
    from synapseml_tpu.core import observability as obs

    reg = obs.reset_registry()
    try:
        yield reg
    finally:
        obs.reset_registry()


def gauge(reg, program="scan", **kinds):
    family = reg.gauge("synapseml_train_program_bytes", "", ("program", "kind"))
    for kind, value in kinds.items():
        family.set(value, program=program, kind=kind)


def test_build_seconds_by_hand(ring):
    assert scan_build_s.read(FACTS) == pytest.approx(17.5 + 10.0)
    assert scan_rebuild_s.read(FACTS) == pytest.approx(10.0)


def test_one_signature_rebuilds_for_zero_seconds(ring):
    ring[:] = [s for s in ring if s.attributes.get("signature") != 2]
    assert scan_build_s.read(FACTS) == pytest.approx(17.5)
    value = scan_rebuild_s.read(FACTS)
    assert value == 0.0 and value is not None


def test_bytes_by_hand(registry):
    gauge(registry, **BYTES)
    gauge(registry, program="step", **{k: 1 for k in BYTES})
    assert step_temp_gb.read(FACTS) == pytest.approx(9.38)
    assert step_resident_gb.read(FACTS) == pytest.approx(6.9 + 6.83 - 6.82)
    assert step_code_mb.read(FACTS) == pytest.approx(41.5)


def test_resident_needs_all_three_counts(registry):
    gauge(registry, args=BYTES["args"], outputs=BYTES["outputs"])
    assert step_resident_gb.read(FACTS) is None


@pytest.mark.parametrize("reader", READERS, ids=lambda m: m.__name__.rsplit(".", 1)[-1])
def test_none_without_the_series(monkeypatch, registry, reader):
    """A parent commit: spans of other names, a registry without the gauge."""
    monkeypatch.setattr(program_spans, "finished_spans", lambda: two_signatures()[:1])
    registry.counter("synapseml_train_step_compiles_total", "", ("program",)) \
        .inc(program="scan")
    assert reader.read(FACTS) is None


@pytest.mark.parametrize("reader", READERS, ids=lambda m: m.__name__.rsplit(".", 1)[-1])
def test_none_in_a_rehearsal(ring, registry, reader):
    gauge(registry, **BYTES)
    assert reader.read({"peaks": None}) is None


def test_the_readers_take_the_programs_own_record():
    """What `Trainer._built` leaves behind has what the readers use."""
    from synapseml_tpu.core import observability as obs
    from synapseml_tpu.models import trainer as trainer_mod

    tracer, reg = obs.reset_tracer(), obs.reset_registry()
    try:
        with tracer.span("train.dispatch") as under:
            build = trainer_mod._Build(under)
            build.close()
        build.seconds.update(trace=2.0, lower=0.5, backend=1.5)
        build.taken = {"arg_bytes": 30, "out_bytes": 25, "alias_bytes": 20,
                       "temp_bytes": 7, "code_bytes": 3}
        holder = SimpleNamespace(_signatures={"scan": 0})
        trainer_mod.Trainer._compile_span(holder, "scan", build)
        trainer_mod.Trainer._compile_span(holder, "scan", build)
        assert scan_build_s.read(FACTS) == pytest.approx(8.0)
        assert scan_rebuild_s.read(FACTS) == pytest.approx(4.0)
        assert step_temp_gb.read(FACTS) == pytest.approx(7e-9)
        assert step_resident_gb.read(FACTS) == pytest.approx(35e-9)
        assert step_code_mb.read(FACTS) == pytest.approx(3e-6)
    finally:
        obs.reset_tracer()
        obs.reset_registry()


def test_the_manifest_names_the_five_without_a_list():
    by_name = {m["name"]: m for m in load_manifest()["per_layer"]}
    for reader in READERS:
        entry = by_name[reader.__name__.rsplit(".", 1)[-1]]
        assert "workloads" not in entry       # every cell runs the scanned step
        assert entry["source"] in ("program_span", "program_counter")
