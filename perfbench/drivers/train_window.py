"""The window driver of a fine-tune cell.

It drives `models.trainer.fit_source`'s own machinery (a `data.DataLoader`
over a `MemorySource`, then `Trainer.fit` on the chunked-scan path, called with
the arguments `fit_source` gives it) on ONE `Trainer`, whose scanned step is
compiled in set-up by the job's first dispatch and then handed to the window.

Set-up: rows and weights from the seed, the trainer, the first dispatch
(`check_steps` optimizer steps through the loader and `Trainer.fit`: it loads
or compiles the program, and its losses, gradient norms and state are what
`correct` compares). Window: `Trainer.fit` again on the same loader, fed until
the first dispatch boundary after `seconds`. After the window: the peak memory
is read, the program's state is freed, and the plain reference follows the
first dispatch's steps on the batches the loader fed.

What the harness adds to the program is on the outside of its calls: it wraps
the batch iterator it hands to `Trainer.fit` (wait clock, the deadline) and
the trainer's `train_steps_scan` entry (dispatch count, the first dispatch's
metrics).
"""

from __future__ import annotations

import functools
import time

import numpy as np

from perfbench.lib import check as check_lib
from perfbench.lib import datagen
from perfbench.lib.norms import moment_and_change


class FedIterator:
    """The batch iterator handed to `Trainer.fit`. Times every `next()`,
    keeps the first batches it fed (by reference, for the comparison), and ends
    a phase on a whole number of dispatches."""

    def __init__(self, inner, scan_chunk: int, keep: int):
        self._inner = inner
        self._chunk = scan_chunk
        self._keep = keep
        self.kept: list[dict] = []
        self.fed = 0            # batches handed out in the current phase
        self.wait_s = 0.0       # time inside next() in the current phase
        self._limit = 0
        self._deadline = None

    def phase(self, *, batches: int | None = None, deadline: float | None = None):
        """Begin a phase that ends after `batches`, or at the first dispatch
        boundary after `deadline` (a `time.perf_counter()` value)."""
        self.fed, self.wait_s = 0, 0.0
        self._limit, self._deadline = batches, deadline
        return self

    def __iter__(self):
        return self

    def __next__(self) -> dict:
        if self._limit is not None and self.fed >= self._limit:
            raise StopIteration
        if self._deadline is not None and self.fed % self._chunk == 0 \
                and time.perf_counter() >= self._deadline:
            raise StopIteration
        t0 = time.perf_counter()
        batch = next(self._inner)
        self.wait_s += time.perf_counter() - t0
        self.fed += 1
        if len(self.kept) < self._keep:
            self.kept.append(batch)
        return batch


class DispatchProbe:
    """Wraps `trainer.train_steps_scan` from the outside: a count of its
    calls, and the metrics of the calls of the first phase. `train_step` (the
    per-step program, which the window must never reach) is counted too."""

    def __init__(self, trainer):
        self.scan_calls = 0
        self.step_calls = 0
        self.first_metrics: list = []
        self.keep_metrics = True
        scan, step = trainer.train_steps_scan, trainer.train_step

        @functools.wraps(scan)
        def scan_probe(state, stacked):
            out = scan(state, stacked)
            self.scan_calls += 1
            if self.keep_metrics:
                self.first_metrics.append(out[1])
            return out

        @functools.wraps(step)
        def step_probe(state, batch):
            self.step_calls += 1
            return step(state, batch)

        trainer.train_steps_scan = scan_probe
        trainer.train_step = step_probe


def build_trainer(cell, adapter):
    from synapseml_tpu.models.trainer import Trainer, TrainerConfig
    from synapseml_tpu.parallel.mesh import MeshConfig, create_mesh

    opt = cell.traffic["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError(f"the trainer's optimizer is AdamW, not {opt['name']!r}")
    mesh = create_mesh(MeshConfig(**cell.traffic["mesh"]), allow_fewer=False)
    tcfg = TrainerConfig(
        learning_rate=opt["learning_rate"], weight_decay=opt["weight_decay"],
        grad_clip=opt["grad_clip"], b1=opt["b1"], b2=opt["b2"],
        lr_schedule=opt["lr_schedule"])
    return Trainer(adapter.build(cell.config), mesh, tcfg)


def make_loader(trainer, data: dict, traffic: dict, seed: int):
    """`fit_source`'s own `DataLoader` call, for a fresh stream."""
    from synapseml_tpu.data import DataLoader
    from synapseml_tpu.data.source import MemorySource

    ld, chunk = traffic["loader"], int(traffic["scan_chunk"])
    return DataLoader(
        MemorySource(data), int(traffic["batch"]), seed=int(seed), epochs=None,
        drop_remainder=ld["drop_remainder"], shuffle_rows=ld["shuffle_rows"],
        shuffle_window=ld["shuffle_window"],
        multiple_of=trainer.mesh.data_parallel_size(), prefetch=ld["prefetch"],
        place_fn=None, columns=None,
        state_history=max(64, 3 * chunk + ld["prefetch"] + 8),
        host_index=0, host_count=1, state=None)


def _device_weights(cell, adapter, trainer, seed: int):
    """The seed's weights on the device in one jitted call, in the program's
    tree, replicated over the cell's mesh."""
    import jax

    from perfbench.reference import encoder as ref

    sizes = ref.sizes(cell.config)

    @functools.partial(jax.jit, out_shardings=trainer.mesh.replicated())
    def make(key_seed):
        return adapter.to_program(ref.init_params(sizes, key_seed), cell.config)

    return make(ref.fold_seed(seed))


def _program_norms(cell, adapter, state, seed: int) -> dict:
    """Leaf norms of the program's first moment and of its parameters' change
    from the seed's weights, in the reference's leaf names."""
    import jax
    import optax

    from perfbench.reference import encoder as ref

    sizes = ref.sizes(cell.config)
    mu = _find_adam(state.opt_state, optax.ScaleByAdamState).mu

    @jax.jit
    def norms(params, mu, key_seed):
        p0 = ref.init_params(sizes, key_seed)
        return moment_and_change(adapter.from_program(params, cell.config),
                                 adapter.from_program(mu, cell.config), p0)

    out = norms(state.params, mu, ref.fold_seed(seed))
    return {k: {n: float(x) for n, x in v.items()} for k, v in out.items()}


def first_dispatch_numbers(cell, adapter, probe, state, seed: int) -> dict:
    """What `correct` compares on the program's side, once the first
    `check_steps` have run: every step's loss and gradient norm as the scanned
    program returned them, the leaves' norms, and the steps the state counts."""
    import jax

    first = [jax.device_get(m) for m in probe.first_metrics]
    probe.keep_metrics = False
    norms = _program_norms(cell, adapter, state, seed)
    return {"loss": [float(x) for m in first for x in np.asarray(m["loss"])],
            "grad_norm": [float(x) for m in first for x in np.asarray(m["grad_norm"])],
            "moment_norm": norms["moment"], "change_norm": norms["change"],
            "steps": int(state.step)}


def _find_adam(opt_state, kind):
    if isinstance(opt_state, kind):
        return opt_state
    if isinstance(opt_state, (tuple, list)):
        for child in opt_state:
            found = _find_adam(child, kind)
            if found is not None:
                return found
    return None


def _peak_bytes(device) -> int:
    """Peak of the device's memory: the arrays' high-water mark plus what the
    loaded programs reserve for their temporaries. The TPU runtime counts the
    two apart (`peak_bytes_in_use`, `peak_bytes_reserved`), and the scanned
    step's activations are in the second (PERF.md, section 3)."""
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0))


def run(cell, args, ctx) -> dict:
    """Set-up, window and comparison of one run. `ctx` carries the process's
    clock (`t_process`), the compile meter and the tracer; returns the facts
    that `perfbench/run.py` turns into the result line."""
    import jax

    traffic = cell.traffic
    chunk, batch_rows = int(traffic["scan_chunk"]), int(traffic["batch"])
    check_steps = int(traffic["check_steps"])
    if check_steps % chunk:
        raise ValueError("check_steps must be whole dispatches")
    adapter = cell.module("programs", cell.config["program"])

    # ---- set-up --------------------------------------------------------
    data = datagen.make_rows(cell.config, traffic, args.seed)
    trainer = build_trainer(cell, adapter)
    probe = DispatchProbe(trainer)
    state = trainer.resume_state(_device_weights(cell, adapter, trainer, args.seed))
    loader = make_loader(trainer, data, traffic, args.seed)
    fed = FedIterator(iter(loader), chunk, keep=check_steps)
    try:
        # the job's first dispatch(es): through the loader and Trainer.fit, as
        # the window; loads or compiles the scanned step
        state = trainer.fit(state, fed.phase(batches=check_steps),
                            max_steps=check_steps, scan_chunk=chunk)
        program = first_dispatch_numbers(cell, adapter, probe, state, args.seed)
        # the scanned step compiles a second time for a state that is its own
        # output (PERF.md, Open questions): one more dispatch settles it
        settle = int(traffic["settle_dispatches"]) * chunk
        if settle:
            state = trainer.fit(state, fed.phase(batches=settle),
                                max_steps=settle, scan_chunk=chunk)
        first_scan_calls = probe.scan_calls
        jax.block_until_ready(state.params)
        setup_split = ctx.meter.snapshot()

        # ---- the window --------------------------------------------------
        tracer = ctx.start_tracer()           # a side thread, --trace 1 only
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
    verdict = check_lib.compare_first_steps(cell, data, kept, program, args.seed)
    reference_s = time.perf_counter() - reference_t0

    return {
        "samples": steps_done * batch_rows, "steps": steps_done,
        "window_s": window_s, "attempted": steps_fed, "failed": max(failed, 0),
        "dispatches": window_scans, "loader_wait_s": loader_wait_s,
        "notes": notes, "memory_peak_bytes": memory_peak,
        "setup_split": setup_split, "trace": trace, "verdict": verdict,
        "reference_s": reference_s}
