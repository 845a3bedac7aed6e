"""The window driver of a latent-attention MoE decoder's training cell:
`hybrid_lm_train_window.py`'s set-up, window and comparison, with everything
of the family found by the names in the configuration file: the reference and
the adapter by `program` (`reference/latent_moe_lm.py`), the comparison by
`check` (`lib/check_latent_lm.py`), the scopes a traced run reads by
`trace_scopes` and the kernel whose launches it reads by `trace_kernel`. A
further decoder family needs no driver of its own. Imported, not copied: `lm_train_window.py`'s `build_trainer`, `merge_rehearsal` and
`ScopeTracer`, `train_window.py`'s `FedIterator`, `DispatchProbe`,
`make_loader` and memory peak, the hybrid driver's `host_constants`. The
routers' selection bias goes to the `Trainer`'s state as the model's
constants; a traced run also reads the launches and device time of the flash
kernel's forward (`lib/kernel_times.py`).
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import os
import time

from perfbench.drivers.hybrid_lm_train_window import host_constants
from perfbench.drivers.lm_train_window import ScopeTracer, build_trainer, merge_rehearsal
from perfbench.drivers.train_window import (DispatchProbe, FedIterator, _find_adam,
                                            _peak_bytes, make_loader)
from perfbench.lib import datagen_lm
from perfbench.lib.manifest import ROOT
from perfbench.lib.norms import moment_and_change
from perfbench.lib.tracer import WindowTracer


class LatentTracer(ScopeTracer):
    """`ScopeTracer` (its length follows the dispatch's) with the scopes and
    the kernel that the configuration names (`trace_scopes`; `trace_kernel`:
    {"scope", "marks"}): `trace["scopes"]`, `trace["flash_kernel"]`."""

    def __init__(self, config: dict, out_dir: str, seconds: float, dispatch_s: float):
        super().__init__(out_dir, seconds, dispatch_s)
        self.scopes = tuple(config["trace_scopes"])
        self.kernel = config["trace_kernel"]

    def finish(self) -> dict | None:
        from perfbench.lib import kernel_times, scope_times

        self._thread.join(timeout=240)      # `WindowTracer.finish` reports a failure
        paths = glob.glob(os.path.join(self.out_dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        scopes = scope_times.read(paths[0], self.scopes, top=16) if paths else None
        kernel = (kernel_times.read(paths[0], self.kernel["scope"], tuple(self.kernel["marks"]))
                  if paths else None)
        trace = WindowTracer.finish(self)
        if trace is not None:
            trace["scopes"], trace["flash_kernel"] = scopes, kernel
            if scopes:      # where the time goes, by scope and by name path
                trace["breakdown"]["ops_by_path"] = scopes["ops"]
                trace["breakdown"]["scope_s"] = scopes["scopes"]
                trace["breakdown"]["scope_total_s"] = scopes["total_s"]
            if kernel:
                trace["breakdown"]["flash_kernel"] = kernel
        return trace


def reference(cell):
    """The cell's plain reference, by the configuration's `program` name."""
    return cell.module("reference", cell.config["program"])


def start_state(cell, adapter, trainer, seed: int):
    """The seed's weights and selection bias as the `Trainer`'s state, with its
    counters placed on the mesh (`lm_train_window.start_state` says why)."""
    import jax

    ref = reference(cell)
    sizes = ref.sizes(cell.config)

    @functools.partial(jax.jit, out_shardings=trainer.mesh.replicated())
    def make(key_seed):
        return (adapter.to_program(ref.init_params(sizes, key_seed), cell.config),
                adapter.constants_to_program(ref.select_bias(sizes, key_seed), cell.config))

    params, constants = make(ref.fold_seed(seed))
    state = trainer.resume_state(params, constants=constants)
    rep = trainer.mesh.replicated()
    place = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: jax.device_put(x, rep) if getattr(x, "ndim", None) == 0 else x, tree)
    return dataclasses.replace(state, opt_state=place(state.opt_state), step=place(state.step))


def first_dispatch_numbers(cell, adapter, probe, state, seed: int, constants: list) -> dict:
    """What `correct` compares on the program's side: every step's loss and
    gradient norm as the scanned program returned them, the leaves' norms of
    the first moment and of the parameters' change (in the reference's leaf
    names), the steps the state counts, and how many entries of the state's
    constants differ, bit for bit, from `constants` (`host_constants` of the
    state the steps started from)."""
    import jax
    import numpy as np
    import optax

    first = [jax.device_get(m) for m in probe.first_metrics]
    probe.keep_metrics = False
    ref = reference(cell)
    sizes = ref.sizes(cell.config)
    mu = _find_adam(state.opt_state, optax.ScaleByAdamState).mu

    @jax.jit
    def norms(params, mu, key_seed):
        return moment_and_change(adapter.from_program(params, cell.config),
                                 adapter.from_program(mu, cell.config),
                                 ref.init_params(sizes, key_seed))

    out = norms(state.params, mu, ref.fold_seed(seed))
    now = host_constants(state)
    changed = abs(len(now) - len(constants)) + sum(
        int(np.sum(a.view(np.uint32) != b.view(np.uint32))) for a, b in zip(now, constants))
    return {"loss": [float(x) for m in first for x in np.asarray(m["loss"])],
            "grad_norm": [float(x) for m in first for x in np.asarray(m["grad_norm"])],
            "moment_norm": {k: float(x) for k, x in out["moment"].items()},
            "change_norm": {k: float(x) for k, x in out["change"].items()},
            "constants_changed": changed,
            "steps": int(state.step)}


def run(cell, args, ctx) -> dict:
    """Set-up, window and comparison of one run, as `hybrid_lm_train_window.run`."""
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
    constants = host_constants(state)
    loader = make_loader(trainer, data, traffic, args.seed)
    fed = FedIterator(iter(loader), chunk, keep=check_steps)
    try:
        state = trainer.fit(state, fed.phase(batches=check_steps),
                            max_steps=check_steps, scan_chunk=chunk)
        program = first_dispatch_numbers(cell, adapter, probe, state, args.seed, constants)
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
            tracer = LatentTracer(cell.config,
                                  os.path.join(ROOT, ".perfbench_out", f"trace_{os.getpid()}"),
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
            # (token, choice) pairs the held experts got, a step, over the whole job:
            # the operation count assumes the uniform share (`flops/`: `held_pairs`)
            from synapseml_tpu.core import observability as obs

            pairs = obs.get_registry().snapshot().get("synapseml_moe_held_pairs_total")
            if pairs is not None:
                trace["breakdown"]["moe_held_pairs_a_step"] = pairs / int(state.step)
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
    verdict = cell.module("lib", cell.config["check"]).compare_first_steps(
        cell, data, kept, program, args.seed)
    reference_s = time.perf_counter() - reference_t0

    return {
        "samples": steps_done * batch_rows, "steps": steps_done,
        "window_s": window_s, "attempted": steps_fed, "failed": max(failed, 0),
        "dispatches": window_scans, "loader_wait_s": loader_wait_s,
        "notes": notes, "memory_peak_bytes": memory_peak,
        "setup_split": setup_split, "trace": trace, "verdict": verdict,
        "reference_s": reference_s}
