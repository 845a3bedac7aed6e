"""Traces a few seconds in the middle of a measured window from a side thread
(the window's own thread is inside `Trainer.fit`), then reduces the trace."""

from __future__ import annotations

import glob
import os
import shutil
import threading
import time

from . import trace_reduce


class WindowTracer:
    def __init__(self, out_dir: str, seconds: float):
        self.out_dir = out_dir
        self.delay_s = 0.3 * seconds
        self.length_s = min(5.0, max(0.25 * seconds, 0.5))
        self.error = None
        self._thread = threading.Thread(target=self._run, daemon=True)

    def start(self):
        shutil.rmtree(self.out_dir, ignore_errors=True)
        self._thread.start()
        return self

    def _run(self):
        import jax

        try:
            time.sleep(self.delay_s)
            opts = jax.profiler.ProfileOptions()
            # the device's planes only. With the host's spans on (any level)
            # the runtime records one span for every tile it transposes on the
            # way to the device: 3.2 million in one dispatch of the ViT cell,
            # which stalled that dispatch by 1-2 s, filled the tracer before the
            # next `dispatch` span and made the file 129 MB (PERF.md, section 6)
            opts.python_tracer_level = 0
            opts.host_tracer_level = 0
            jax.profiler.start_trace(self.out_dir, profiler_options=opts)
            try:
                time.sleep(self.length_s)
            finally:
                jax.profiler.stop_trace()
        except Exception as e:   # reported by finish(); the window goes on
            self.error = e

    def finish(self) -> dict | None:
        """Wait for the trace, reduce it, remove it."""
        self._thread.join(timeout=240)
        if self._thread.is_alive():
            raise RuntimeError("the profiler did not stop within 240 s")
        if self.error is not None:
            raise RuntimeError(f"tracing failed: {self.error!r}")
        paths = glob.glob(os.path.join(self.out_dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        try:
            if not paths:
                raise RuntimeError(f"the profiler wrote no trace under {self.out_dir}")
            return trace_reduce.reduce_file(paths[0])
        finally:
            shutil.rmtree(self.out_dir, ignore_errors=True)
