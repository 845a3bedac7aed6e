"""The window driver of a language-model training cell: `train_window.py`'s
set-up, window and comparison (its `FedIterator`, `DispatchProbe`,
`build_trainer`, `make_loader` and memory peak are imported, not copied) with
the loss weights of the configuration file given to the `Trainer`,
packed token rows (`lib/datagen_lm.py`), the decoder's reference
(`reference/sparse_moe_lm.py`, `lib/check_lm.py`) and, in a traced run, the
device time by named scope (`lib/scope_times.py`) beside the reduction.

In a rehearsal the sizes of `rehearsal/<config>.json` are merged over the
cell's (the encoders' `rehearsal/overrides.json`, which `Cell` has merged,
leaves this family's own sizes as published).
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import os
import time

from perfbench.drivers import train_window as tw
from perfbench.drivers.train_window import (DispatchProbe, FedIterator, _find_adam,
                                            _peak_bytes, make_loader)
from perfbench.lib import check_lm, datagen_lm
from perfbench.lib.manifest import BENCH_DIR, ROOT, _merge, load_json
from perfbench.lib.norms import moment_and_change
from perfbench.lib.tracer import WindowTracer

SCOPES = ("attn.indexer", "attn.select", "attn.sparse", "moe.route", "moe.experts")


class ScopeTracer(WindowTracer):
    """`WindowTracer` that traces long enough to hold two starts of the
    scanned program (a dispatch of this family takes seconds, and the
    reduction counts steps in whole dispatch cycles), and whose reduction also
    holds the trace's device time by scope, read before the trace is removed:
    `trace["scopes"]`."""

    def __init__(self, out_dir: str, seconds: float, dispatch_s: float):
        super().__init__(out_dir, seconds)
        self.length_s = max(self.length_s, 2.2 * dispatch_s)

    def finish(self) -> dict | None:
        from perfbench.lib import scope_times

        self._thread.join(timeout=240)      # `WindowTracer.finish` reports a failure
        paths = glob.glob(os.path.join(self.out_dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        scopes = scope_times.read(paths[0], SCOPES) if paths else None
        trace = super().finish()
        if trace is not None:
            trace["scopes"] = scopes
            if scopes:      # where the time goes, by name path: in the line's breakdown
                trace["breakdown"]["ops_by_path"] = scopes["ops"]
        return trace


def merge_rehearsal(cell) -> None:
    """Merging twice changes nothing."""
    path = os.path.join(BENCH_DIR, "rehearsal", f"{cell.config_name}.json")
    if cell.rehearse and os.path.exists(path):
        over = load_json(path)
        cell.config = _merge(cell.config, over["config"])
        cell.traffic = _merge(cell.traffic, over["traffic"])
        cell.limits = _merge(cell.limits, over["limits"])


def build_trainer(cell, adapter):
    """`train_window.build_trainer`'s trainer, with the `TrainerConfig` fields
    that the configuration file states (`adapter.trainer_options`: the loss
    terms' weights, which the reference reads from the same keys)."""
    from synapseml_tpu.models.trainer import Trainer

    base = tw.build_trainer(cell, adapter)
    return Trainer(base.module, base.mesh,
                   dataclasses.replace(base.cfg, **adapter.trainer_options(cell.config)))


def start_state(cell, adapter, trainer, seed: int):
    """The seed's weights as the `Trainer`'s state, with its counters (`step`,
    the optimizer's `count`) placed on the mesh. `resume_state` makes them
    outside a mesh, so their type carries none while the step's own outputs
    carry it: the jitted step meets a second signature at its second dispatch
    and is traced and compiled twice, as in every job. This family's step is
    0.39 GB of code, and a layout with two signatures never loaded from the
    chip machine's compile cache (PERF.md, section 6, PR 29). The placement
    belongs in `Trainer.resume_state`, for every job: a `perf_opt` PR of its
    own (PERF.md, section 7)."""
    import jax

    state = trainer.resume_state(device_weights(cell, adapter, trainer, seed))
    rep = trainer.mesh.replicated()
    place = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: jax.device_put(x, rep) if getattr(x, "ndim", None) == 0 else x, tree)
    return dataclasses.replace(state, opt_state=place(state.opt_state), step=place(state.step))


def device_weights(cell, adapter, trainer, seed: int):
    """The seed's weights on the device in one jitted call, in the program's tree."""
    import jax

    from perfbench.reference import sparse_moe_lm as ref

    sizes = ref.sizes(cell.config)

    @functools.partial(jax.jit, out_shardings=trainer.mesh.replicated())
    def make(key_seed):
        return adapter.to_program(ref.init_params(sizes, key_seed), cell.config)

    return make(ref.fold_seed(seed))


def first_dispatch_numbers(cell, adapter, probe, state, seed: int) -> dict:
    """What `correct` compares on the program's side: every step's loss and
    gradient norm as the scanned program returned them, the leaves' norms of
    the first moment and of the parameters' change (in the reference's leaf
    names), and the steps the state counts."""
    import jax
    import numpy as np
    import optax

    from perfbench.reference import sparse_moe_lm as ref

    first = [jax.device_get(m) for m in probe.first_metrics]
    probe.keep_metrics = False
    sizes = ref.sizes(cell.config)
    mu = _find_adam(state.opt_state, optax.ScaleByAdamState).mu

    @jax.jit
    def norms(params, mu, key_seed):
        return moment_and_change(adapter.from_program(params, cell.config),
                                 adapter.from_program(mu, cell.config),
                                 ref.init_params(sizes, key_seed))

    out = norms(state.params, mu, ref.fold_seed(seed))
    return {"loss": [float(x) for m in first for x in np.asarray(m["loss"])],
            "grad_norm": [float(x) for m in first for x in np.asarray(m["grad_norm"])],
            "moment_norm": {k: float(x) for k, x in out["moment"].items()},
            "change_norm": {k: float(x) for k, x in out["change"].items()},
            "steps": int(state.step)}


def run(cell, args, ctx) -> dict:
    """Set-up, window and comparison of one run, as `train_window.run`."""
    import jax

    merge_rehearsal(cell)
    traffic = cell.traffic
    chunk, batch_rows = int(traffic["scan_chunk"]), int(traffic["batch"])
    check_steps = int(traffic["check_steps"])
    if check_steps % chunk:
        raise ValueError("check_steps must be whole dispatches")
    adapter = cell.module("programs", cell.config["program"])

    # ---- set-up --------------------------------------------------------
    data = datagen_lm.make_rows(cell.config, traffic, args.seed)
    trainer = build_trainer(cell, adapter)
    probe = DispatchProbe(trainer)
    state = start_state(cell, adapter, trainer, args.seed)
    loader = make_loader(trainer, data, traffic, args.seed)
    fed = FedIterator(iter(loader), chunk, keep=check_steps)
    try:
        state = trainer.fit(state, fed.phase(batches=check_steps),
                            max_steps=check_steps, scan_chunk=chunk)
        program = first_dispatch_numbers(cell, adapter, probe, state, args.seed)
        settle = int(traffic["settle_dispatches"]) * chunk
        t_settle = time.perf_counter()
        if settle:
            state = trainer.fit(state, fed.phase(batches=settle),
                                max_steps=settle, scan_chunk=chunk)
        first_scan_calls = probe.scan_calls
        jax.block_until_ready(state.params)
        # a dispatch's seconds, for the tracer's length (0 without a settling one)
        dispatch_s = (time.perf_counter() - t_settle) * chunk / max(settle, chunk)
        setup_split = ctx.meter.snapshot()

        # ---- the window --------------------------------------------------
        tracer = None
        if args.trace:
            # a directory of the process's own: two runs side by side (the
            # tests' rehearsals) would remove each other's trace
            tracer = ScopeTracer(os.path.join(ROOT, ".perfbench_out", f"trace_{os.getpid()}"),
                                 float(args.seconds), dispatch_s).start()
        t_start = time.perf_counter()
        ctx.setup_s = time.time() - ctx.t_process
        state = trainer.fit(
            state, fed.phase(deadline=t_start + float(args.seconds)),
            max_steps=10 ** 9, scan_chunk=chunk)
        jax.block_until_ready((state.params, state.opt_state, state.step))
        t_end = time.perf_counter()
        trace = tracer.finish() if tracer is not None else None
        if trace is not None:
            trace["steps"] = trace["cycles"] * chunk if trace["cycles"] else None
    finally:
        loader.close()

    window_s = t_end - t_start
    steps_fed, loader_wait_s = fed.fed, fed.wait_s
    steps_done = int(state.step) - program["steps"] - settle
    in_window = ctx.meter.since(setup_split)
    window_scans = probe.scan_calls - first_scan_calls
    failed = steps_fed - steps_done
    notes = []
    if in_window["programs"] or in_window["cache_misses"]:
        notes.append(f"{in_window['programs']} programs compiled inside the window")
        failed = steps_fed
    if probe.step_calls or window_scans * chunk != steps_fed:
        notes.append(f"window left the scanned path: {probe.step_calls} per-step "
                     f"calls, {window_scans} dispatches for {steps_fed} batches")
        failed = steps_fed
    memory_peak = max(_peak_bytes(d) for d in trainer.mesh.mesh.devices.flat)

    # ---- free the program's state, then the reference --------------------
    kept = fed.kept
    del state, trainer, probe, loader, fed
    reference_t0 = time.perf_counter()
    verdict = check_lm.compare_first_steps(cell, data, kept, program, args.seed)
    reference_s = time.perf_counter() - reference_t0

    return {
        "samples": steps_done * batch_rows, "steps": steps_done,
        "window_s": window_s, "attempted": steps_fed, "failed": max(failed, 0),
        "dispatches": window_scans, "loader_wait_s": loader_wait_s,
        "notes": notes, "memory_peak_bytes": memory_peak,
        "setup_split": setup_split, "trace": trace, "verdict": verdict,
        "reference_s": reference_s}
