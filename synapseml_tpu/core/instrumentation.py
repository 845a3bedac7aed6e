"""Per-phase instrumentation measures + profiler trace helper.

Reference: ``lightgbm/.../LightGBMPerformance.scala`` —
``TaskInstrumentationMeasures`` mark columnStatistics/rowStatistics/sampling/
network-init/dataset-prep/training windows and travel back with results; VW
returns ``TrainingStats`` per partition (``VowpalWabbitBaseLearner.scala:71-96``).
Here one collector serves every engine: estimators thread an
``InstrumentationMeasures`` through fit and attach ``.to_dict()`` to the model
(``train_measures`` param), and ``profile_trace`` wraps ``jax.profiler.trace``
for on-demand XLA-level traces.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Iterator

__all__ = ["InstrumentationMeasures", "profile_trace", "chip_peak_tflops"]

# bf16 peak TFLOP/s of one chip, keyed by the exact ``device_kind`` string
# jax reports (lower-cased). Source: Google Cloud TPU documentation, the
# per-generation system-architecture pages ("TPU v5e": 197 TFLOP/s bf16).
_CHIP_PEAK_TFLOPS = {
    "tpu v2": 45.0,
    "tpu v3": 123.0,
    "tpu v4": 275.0,
    "tpu v5 lite": 197.0,   # v5e
    "tpu v5e": 197.0,
    "tpu v5": 459.0,        # v5p
    "tpu v5p": 459.0,
    "tpu v6 lite": 918.0,   # v6e (Trillium)
    "tpu v6e": 918.0,
}


def chip_peak_tflops(device_kind: str) -> float:
    """bf16 peak of the named TPU chip (the MFU denominator). A device that
    is not in the table is an error, not a default — callers on non-TPU
    platforms do not ask."""
    try:
        return _CHIP_PEAK_TFLOPS[(device_kind or "").strip().lower()]
    except KeyError:
        raise ValueError(
            f"no bf16 peak recorded for device_kind {device_kind!r}; add it "
            f"to core.instrumentation._CHIP_PEAK_TFLOPS with its source "
            f"(known: {sorted(_CHIP_PEAK_TFLOPS)})") from None


class InstrumentationMeasures:
    """Named wall-clock phase windows + point marks + counters.

    ``measure(name)`` windows accumulate across repeated entries (loop
    phases); ``count(name)`` tallies events; everything exports as one flat
    dict of ``*_ms`` / ``*_count`` / mark timestamps.
    """

    def __init__(self):
        self._t0 = time.perf_counter()
        self._phases: dict[str, float] = {}
        self._counts: dict[str, int] = {}
        self._marks: dict[str, float] = {}
        # every mutation is bumped from serving/executor threads (the
        # resilience planes share one collector per plane): ONE lock guards
        # phases, marks AND counts — measure()/mark() racing count() was a
        # real lost-update hole when threads shared a plane collector
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def measure(self, name: str) -> Iterator[None]:
        start = time.perf_counter()
        try:
            yield
        finally:
            elapsed_ms = (time.perf_counter() - start) * 1e3
            with self._lock:
                self._phases[name] = self._phases.get(name, 0.0) + elapsed_ms

    def mark(self, name: str) -> None:
        at_ms = (time.perf_counter() - self._t0) * 1e3
        with self._lock:
            self._marks[name] = at_ms

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counts[name] = self._counts.get(name, 0) + n

    def phase_ms(self, name: str) -> float:
        with self._lock:
            return self._phases.get(name, 0.0)

    def to_dict(self) -> dict:
        with self._lock:  # snapshot under the lock: a half-applied measure()
            phases = dict(self._phases)  # must never tear the export
            counts = dict(self._counts)
            marks = dict(self._marks)
        out = {f"{k}_ms": round(v, 3) for k, v in phases.items()}
        out.update({f"{k}_count": v for k, v in counts.items()})
        out.update({f"{k}_at_ms": round(v, 3) for k, v in marks.items()})
        out["total_ms"] = round((time.perf_counter() - self._t0) * 1e3, 3)
        return out


@contextlib.contextmanager
def profile_trace(log_dir: str, host_tracer_level: int = 0) -> Iterator[None]:
    """``jax.profiler.trace`` context: captures an XLA/TPU trace viewable in
    TensorBoard/Perfetto. The SURVEY §5 tracing-subsystem analog — wrap any
    fit/transform/bench region.

    ``host_tracer_level`` is the profiler's own (0 off, 1 ``TraceAnnotation``
    spans, 2 and 3 more of the runtime's); the Python tracer stays off. The
    default records the device's planes only: at level 1 or 2 the TPU
    runtime adds a host span for every tile it transposes on the way to the
    device, 3.2 million in one ViT-B/16 dispatch of 617 MB, which stalls that
    dispatch by seconds and fills the tracer (PERF.md, section 6). The fit
    loop's own spans (``train.*``) need no host tracer: they are kept by
    ``core.observability`` on the trace's clock (``Span``), and
    ``export_chrome_trace`` writes them to a timeline of their own."""
    import jax.profiler

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = int(host_tracer_level)
    opts.python_tracer_level = 0
    with jax.profiler.trace(log_dir, create_perfetto_trace=False,
                            profiler_options=opts):
        yield
