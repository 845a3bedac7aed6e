"""GSPMD trainer: the TPU-native replacement for horovod.spark's TorchEstimator.

Reference call stack being replaced (SURVEY.md §3.2): horovod SparkBackend
spawns per-task python workers running pytorch-lightning with ring-allreduce on
gradients. Here: ONE jitted train step over the named mesh — the batch is
sharded on ('data','fsdp'), params on fsdp/tensor axes per logical rules, and
XLA inserts the gradient reductions (ICI psum) that horovod/NCCL did by hand.

Also covers the reference's fine-tuning semantics:
  * layer freezing (``LitDeepTextModel._fine_tune_layers:120``) via an optax
    masked transform over param-path predicates,
  * gradient accumulation (horovod ``backward_passes_per_step``) via
    optax.MultiSteps,
  * checkpoint/resume via parallel.checkpoint.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Any, Callable, Iterator

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..core import observability as obs
from ..parallel.mesh import MeshContext, logical_axis_rules

__all__ = ["TrainerConfig", "Trainer", "cross_entropy_loss", "TrainState",
           "NonFiniteLossError",
           "fit_source", "fit_arrays", "fit_gang_source",
           # horizontally fused training arrays (HFTA): N hyperparameter
           # trials inside ONE jitted step — implementation lives in
           # .fused_trainer (kept importable from here; the module split
           # lets the no-inline-jit static check cover the fused step)
           "FusedTrainer", "fused_fit_source", "fused_fit_arrays"]


def __getattr__(name):  # PEP 562: lazy, avoids a circular import at load
    if name in ("FusedTrainer", "fused_fit_source", "fused_fit_arrays",
                "FUSED_OPT_HPARAMS", "FUSED_LOSS_HPARAMS"):
        from . import fused_trainer

        return getattr(fused_trainer, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jax.Array
    batch_stats: Any | None = None
    # model variables that are neither trained nor optimizer state (collection
    # 'constants': a router's selection bias): given to every step, returned
    # by it as they went in, saved and restored with the rest
    constants: Any | None = None

    def as_dict(self) -> dict:
        d = {"params": self.params, "opt_state": self.opt_state, "step": self.step}
        if self.batch_stats is not None:
            d["batch_stats"] = self.batch_stats
        if self.constants is not None:
            d["constants"] = self.constants
        return d

    def _step_input(self) -> dict:
        """What the jitted step takes and returns: every field, None where the
        state has none (the scanned step's carry keeps one structure)."""
        return self.as_dict() | {"batch_stats": self.batch_stats, "constants": self.constants}


@dataclasses.dataclass
class TrainerConfig:
    learning_rate: float = 1e-4
    weight_decay: float = 0.01
    warmup_steps: int = 0
    total_steps: int = 1000
    grad_clip: float = 1.0
    grad_accum: int = 1
    freeze_predicate: Callable[[tuple[str, ...]], bool] | None = None  # True -> frozen
    lr_schedule: str = "constant"  # constant | cosine | linear
    b1: float = 0.9
    b2: float = 0.999
    # weight on the switch-MoE load-balance aux loss (sown by MoEBlock as
    # intermediates/moe_aux_loss); only consulted when the module's config
    # has moe_experts > 0
    moe_aux_weight: float = 0.01
    # weight on the learned-sparse-attention indexer's own loss (sown by
    # Attention as intermediates/sparse_attn_indexer_kl, summed over the layers); it
    # reaches the indexer's weights alone
    indexer_loss_weight: float = 1.0
    # weight beta of the exit distribution's entropy in a looped model's
    # objective (`exit_weighted_loss`); only consulted when the module's
    # config has loop_steps > 1
    exit_entropy_weight: float = 0.1
    # declarative sharding (parallel.partition.PartitionRules): regex
    # param-path rules place params AND optimizer state on the mesh —
    # plain pytrees need no nn.Partitioned metadata. zero_shard=True adds
    # ZeRO weight-update sharding: optimizer state partitions over the
    # table's zero_axes replica group inside the one jitted step
    # (arXiv:2004.13336), cutting per-replica opt-state memory to ~1/dp.
    partition_rules: Any | None = None
    zero_shard: bool = False
    # non-finite loss guard: every loss value materialized host-side by the
    # fit loops is checked; non-finite steps count into
    # synapseml_train_nonfinite_total and the last finite step lands on the
    # synapseml_train_last_finite_step gauge (the supervisor's rewind
    # trigger is a metric read, not a log grep). "count" only observes;
    # "raise" aborts the fit with NonFiniteLossError naming the poisoned
    # step — what continual.TrainSupervisor rewinds on.
    nonfinite_action: str = "count"  # count | raise


# phases of the fit loop: a ``train.<phase>`` span each, and one series each of
# synapseml_train_loop_ms
_LOOP_PHASES = ("chunk_wait", "place", "dispatch", "fetch", "checkpoint",
                "chunk_build")

_TRAIN_METRICS = obs.HandleCache(lambda reg: {
    "nonfinite": reg.counter(
        "synapseml_train_nonfinite_total",
        "optimizer steps whose loss was NaN/Inf", ("engine",)),
    "last_finite": reg.gauge(
        "synapseml_train_last_finite_step",
        "newest optimizer step with a finite loss"),
    "dispatches": reg.counter(
        "synapseml_train_dispatches_total",
        "calls of the jitted train step (scan: K optimizer steps a call)",
        ("program",)),
    "compiles": reg.counter(
        "synapseml_train_step_compiles_total",
        "executables the jitted step got (a new signature: traced, lowered, "
        "then compiled or loaded): one a train.compile span", ("program",)),
    "compile_seconds": reg.counter(
        "synapseml_train_compile_seconds_total",
        "jax's own seconds of building the jitted step's executables, by "
        "phase (trace | lower | backend: compile or cache load)",
        ("program", "phase")),
    "program_bytes": reg.gauge(
        "synapseml_train_program_bytes",
        "XLA's memory analysis of the jitted step's newest executable, bytes "
        "a device by kind (args | outputs | aliased | temp | code)",
        ("program", "kind")),
    "loop_ms": {phase: reg.histogram(
        "synapseml_train_loop_ms",
        "host time of one boundary of the fit loop (the train.<phase> "
        "span's duration)", ("phase",)).labels(phase=phase)
        for phase in _LOOP_PHASES},
    "step_ms": reg.histogram(
        "synapseml_train_step_duration_ms",
        "training step (boosting iteration / optimizer step) wall "
        "time", ("engine",)).labels(engine="trainer"),
    "samples_per_sec": reg.gauge(
        "synapseml_train_samples_per_sec", "fit-loop throughput",
        ("engine",)).labels(engine="trainer"),
    # what a step's metrics carry beside loss and grad_norm, for modules that
    # have the mechanism (``_MODEL_STATS``); fetched with the loss
    "moe_held_pairs": reg.counter(
        "synapseml_moe_held_pairs_total",
        "(token, choice) pairs routed to the experts held here, over the "
        "layers and steps trained"),
    "moe_expert_load_max_ratio": reg.gauge(
        "synapseml_moe_expert_load_max_ratio",
        "most-loaded held expert's pairs over the held experts' mean, worst "
        "layer of the newest step"),
    "sparse_attn_selected_share": reg.gauge(
        "synapseml_sparse_attn_selected_share",
        "keys the indexer selected over the causal candidates, newest step"),
    "sparse_attn_indexer_kl": reg.gauge(
        "synapseml_sparse_attn_indexer_kl",
        "the indexer's loss summed over the layers, newest step"),
    "moe_bias_steered_share": reg.gauge(
        "synapseml_moe_bias_steered_share",
        "(token, choice) pairs whose expert the router's scores alone would "
        "not have chosen (the selection bias steered them), mean over the "
        "layers of the newest step"),
    "loop_exit_mean_step": reg.gauge(
        "synapseml_loop_exit_mean_step",
        "a looped model's expected exit step sum_t t p_t under its exit "
        "gates, mean over the labelled tokens of the newest step"),
})

# a step statistic -> how the layers' sown values become its one number
# (`loop_exit_mean_step` is no layer's: `exit_weighted_loss` returns it)
_MODEL_STATS = {"moe_held_pairs": jnp.sum, "moe_expert_load_max_ratio": jnp.max,
                "sparse_attn_selected_share": jnp.mean,
                "sparse_attn_indexer_kl": jnp.sum,
                "moe_bias_steered_share": jnp.mean,
                "loop_exit_mean_step": jnp.mean}


class _LoopSpan:
    """One boundary of the fit loop, timed once and kept twice: as a
    ``core.observability`` span (always recorded; ``obs.Span`` says where its
    times lie in a profiler trace) and as a ``jax.profiler.TraceAnnotation``
    of the same name, which an operator's own profile shows when its host
    tracer is on and which costs a flag check when it is not. The span's
    clocks are read right beside the annotation's, with the tracer's
    bookkeeping (new ids, the lock that the chunk producer's spans share)
    outside both, so the two agree to microseconds. The duration of a
    ``train.<phase>`` span also feeds ``synapseml_train_loop_ms{phase}``.
    ``keep = False`` inside the block leaves no span behind."""

    __slots__ = ("span", "keep", "_start", "_ann", "_tracer")

    def __init__(self, name: str, attributes: dict | None = None,
                 parent: "obs.SpanContext | None" = None):
        self._start = (name, attributes, parent)
        self.keep = True

    def __enter__(self) -> "_LoopSpan":
        self._tracer = obs.get_tracer()
        self.span = self._tracer.start_span(*self._start)
        self._ann = jax.profiler.TraceAnnotation(self._start[0])
        self._ann.__enter__()
        self.span.restart()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.span.end(exc)
        self._ann.__exit__(exc_type, exc, tb)
        if self.keep:
            self._tracer.end_span(self.span, error=exc)
        else:
            self._tracer.discard_span(self.span)
        phase = self.span.name.removeprefix("train.")
        if phase in _LOOP_PHASES and self.keep and exc is None:
            _TRAIN_METRICS.get()["loop_ms"][phase].observe(self.span.duration_ms)


def _tree_nbytes(tree) -> int:
    return sum(int(getattr(x, "nbytes", 0)) for x in jax.tree.leaves(tree))


def _host_leaves(tree) -> list:
    """The leaves a placement still has to move: those not yet ``jax.Array``s."""
    return [x for x in jax.tree.leaves(tree) if not isinstance(x, jax.Array)]


def _place_attrs(batch) -> dict:
    """``train.place``'s attributes where a dispatch places its own input:
    the bytes of the leaves that still are host arrays, and ``ahead``, true
    where none is (the fit's chunk producer, or a loader's ``place_fn``, put
    the input on the device before the loop asked for it)."""
    host = _host_leaves(batch)
    return {"bytes": _tree_nbytes(host), "ahead": not host}


def _leading_dim(stacked) -> int:
    """K of a pytree whose leaves lead with K."""
    return int(np.shape(jax.tree.leaves(stacked)[0])[0])


def _stack_steps(batches: list) -> dict:
    """K same-shape batches -> one pytree whose leaves lead with K."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *batches)


# jax's monitoring events of a build, by the phase they time, and of the
# persistent cache, by what it did
_BUILD_PHASES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
                 "/jax/core/compile/backend_compile_duration": "backend"}
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}
# train.compile's byte attributes: (the gauge's kind, the field of XLA's
# memory analysis)
_PROGRAM_BYTES = {"arg_bytes": ("args", "argument_size_in_bytes"),
                  "out_bytes": ("outputs", "output_size_in_bytes"),
                  "alias_bytes": ("aliased", "alias_size_in_bytes"),
                  "temp_bytes": ("temp", "temp_size_in_bytes"),
                  "code_bytes": ("code", "generated_code_size_in_bytes")}

_building = threading.local()  # .record: the thread's open _Build, or None


class _Build:
    """jax's own account of what one call built: the seconds of its trace,
    lowering and backend (compile or cache load) events, the executables it
    got (a backend event each) and whether the persistent cache served them.
    Open from its making to ``close()``, on the thread that made it: an event
    adds to the open record of the thread it fires on, so what another
    thread compiles meanwhile (the chunk producer's placing) is not counted."""

    __slots__ = ("under", "start_ns", "host_ms", "seconds", "executables",
                 "cache", "taken", "_t0")

    def __init__(self, under: "obs.Span"):
        self.under = under            # the train.dispatch span it belongs to
        self.seconds = {"trace": 0.0, "lower": 0.0, "backend": 0.0}
        self.executables = 0
        self.cache = "off"
        self.taken: dict | None = None   # of(): XLA's byte counts, and take_ms
        self.start_ns = time.time_ns()
        self._t0 = time.perf_counter()
        _building.record = self

    def close(self) -> None:
        _building.record = None
        self.host_ms = (time.perf_counter() - self._t0) * 1e3

    def of(self, executable) -> None:
        """Keep XLA's memory analysis of ``executable``: bytes a device."""
        stats = executable.memory_analysis()
        if stats is not None:
            self.taken = {name: int(getattr(stats, field))
                          for name, (_, field) in _PROGRAM_BYTES.items()}

    def attributes(self) -> dict:
        return {**{f"{phase}_ms": s * 1e3 for phase, s in self.seconds.items()},
                "cache": self.cache, **(self.taken or {})}


def _on_build_seconds(event: str, seconds: float, **_) -> None:
    rec = getattr(_building, "record", None)
    phase = _BUILD_PHASES.get(event)
    if rec is not None and phase is not None:
        rec.seconds[phase] += seconds
        rec.executables += phase == "backend"


def _on_cache_event(event: str, **_) -> None:
    rec = getattr(_building, "record", None)
    served = _CACHE_EVENTS.get(event)
    if rec is not None and served is not None and rec.cache != "miss":
        rec.cache = served        # one miss among a build's programs: a miss


_listening = threading.Lock()
_listens = False


def _listen_for_builds() -> None:
    """Register the two listeners with ``jax.monitoring``, once a process.
    jax calls them where something is traced, lowered, compiled or loaded,
    and never between."""
    global _listens
    with _listening:
        if not _listens:
            jax.monitoring.register_event_duration_secs_listener(_on_build_seconds)
            jax.monitoring.register_event_listener(_on_cache_event)
            _listens = True


class NonFiniteLossError(RuntimeError):
    """The fit loop saw a non-finite loss at ``step`` (the optimizer step
    the poisoned batch trained). ``last_finite_step`` is the newest step
    whose loss was still finite — rewind past the window between them."""

    def __init__(self, step: int, last_finite_step: int):
        super().__init__(
            f"non-finite loss at step {step} (last finite step: "
            f"{last_finite_step}) — rewind to a checkpoint at or before "
            f"{last_finite_step} and skip the offending batch window")
        self.step = int(step)
        self.last_finite_step = int(last_finite_step)


def _graft_params(boxed, values):
    """Replace the values inside a (possibly nn.Partitioned-boxed) init tree
    with pretrained host arrays, keeping the partitioning metadata. Every
    module param must exist in ``values`` with a matching shape."""
    from flax.core import meta

    flat_vals = {"/".join(str(getattr(k, "key", k)) for k in path): v
                 for path, v in jax.tree_util.tree_flatten_with_path(values)[0]}
    used = set()

    def pick(path, x):
        key = "/".join(str(getattr(k, "key", k)) for k in path)
        if key not in flat_vals:
            raise KeyError(f"pretrained params missing {key!r}; has "
                           f"{sorted(flat_vals)[:8]}...")
        used.add(key)
        v = np.asarray(flat_vals[key])
        target = x.value if isinstance(x, meta.Partitioned) else x
        if tuple(v.shape) != tuple(np.shape(target)):
            raise ValueError(f"shape mismatch for {key!r}: checkpoint "
                             f"{v.shape} vs module {np.shape(target)}")
        v = v.astype(np.asarray(target).dtype)
        return x.replace_boxed(v) if isinstance(x, meta.Partitioned) else v

    out = jax.tree_util.tree_map_with_path(
        pick, boxed, is_leaf=lambda x: isinstance(x, meta.Partitioned))
    unused = set(flat_vals) - used
    if unused:
        raise ValueError(f"checkpoint keys not consumed by the module: "
                         f"{sorted(unused)[:8]}... — key map out of sync")
    return out


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       mask: jax.Array | None = None) -> jax.Array:
    """Mean cross-entropy. ``mask`` weighs the labels; one of fewer axes (the
    loader's ``[B]`` row mask on ``[B, T]`` labels) covers every token of its
    row. Labels of more than one axis (a label a token) may be negative: such
    a position is left out (the next-token convention: ``labels[:, t]`` is
    ``input_ids[:, t + 1]``, the last position -100)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    if labels.ndim > 1:
        keep = (labels >= 0).astype(jnp.float32)
        if mask is not None:
            keep = keep * mask.reshape(mask.shape + (1,) * (labels.ndim - mask.ndim))
        labels, mask = jnp.maximum(labels, 0), keep
    nll = -jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def exit_weighted_loss(nll: jax.Array, gate_logits: jax.Array, labels: jax.Array,
                       mask: jax.Array | None = None,
                       entropy_weight: float = 0.1) -> tuple:
    """A looped model's objective: per token, the cross-entropy of every loop
    step ``nll`` [R, B, T] weighed by the exit distribution p of the gate
    logits [R, B, T] (`ops.looped_head.exit_distribution`), less
    ``entropy_weight`` times p's entropy: sum_t p_t nll_t - beta H(p); the
    mean over the tokens whose label is not negative (and whose row ``mask``,
    the loader's [B], keeps), as `cross_entropy_loss` takes it. Float32.
    Returns it with the step statistics: ``loop_exit_mean_step``, the mean
    over the same tokens of the expected exit step sum_t t p_t."""
    from ..ops.looped_head import exit_distribution

    with jax.named_scope("loop.head"):
        p, log_p = exit_distribution(gate_logits)
        per_token = jnp.sum(p * nll, axis=0) + entropy_weight * jnp.sum(p * log_p, axis=0)
        keep = (labels >= 0).astype(jnp.float32)
        if mask is not None:
            keep = keep * mask.reshape(mask.shape + (1,) * (labels.ndim - mask.ndim))
        kept = jnp.maximum(jnp.sum(keep), 1.0)
        step = jnp.einsum("r,r...->...", jnp.arange(1, p.shape[0] + 1, dtype=jnp.float32), p)
        return jnp.sum(per_token * keep) / kept, \
            {"loop_exit_mean_step": jnp.sum(step * keep) / kept}


def _make_schedule(cfg: TrainerConfig):
    if cfg.lr_schedule == "cosine":
        return optax.warmup_cosine_decay_schedule(
            0.0, cfg.learning_rate, max(cfg.warmup_steps, 1), max(cfg.total_steps, 2))
    if cfg.lr_schedule == "linear":
        return optax.join_schedules(
            [optax.linear_schedule(0.0, cfg.learning_rate, max(cfg.warmup_steps, 1)),
             optax.linear_schedule(cfg.learning_rate, 0.0,
                                   max(cfg.total_steps - cfg.warmup_steps, 1))],
            [cfg.warmup_steps])
    return cfg.learning_rate


def _align_restored(fresh, got, path: str):
    """Yield restored leaves in ``fresh``'s flatten order (jax sorts dict
    keys; sequences are positional), matching dict children BY KEY so a
    serialized container whose iteration order differs from the live
    state's flatten order cannot silently swap same-shaped leaves.
    Validates container kinds and leaf shapes, with the failing path in
    every error."""
    if isinstance(fresh, dict):
        if not isinstance(got, dict):
            raise ValueError(f"{path}: expected a dict, restored "
                             f"{type(got).__name__}")
        if set(got) != set(fresh):
            missing = sorted(set(fresh) - set(got))
            extra = sorted(set(got) - set(fresh))
            raise ValueError(f"{path}: restored dict keys differ "
                             f"(missing {missing}, extra {extra})")
        for k in sorted(fresh):  # jax.tree flatten order for dicts
            yield from _align_restored(fresh[k], got[k], f"{path}[{k!r}]")
    elif isinstance(fresh, (list, tuple)):  # incl. optax NamedTuple states
        if not isinstance(got, (list, tuple)):
            raise ValueError(f"{path}: expected a sequence, restored "
                             f"{type(got).__name__}")
        if len(got) != len(fresh):
            raise ValueError(
                f"{path}: restored sequence has {len(got)} children but "
                f"this optimizer expects {len(fresh)} — optimizer config "
                "changed since the checkpoint was written")
        names = getattr(type(fresh), "_fields", None)
        for i, (f, g) in enumerate(zip(fresh, got)):
            label = names[i] if names else i
            yield from _align_restored(f, g, f"{path}.{label}")
    elif fresh is None:
        if got is not None:
            raise ValueError(f"{path}: expected an empty node, restored "
                             f"{type(got).__name__}")
    else:  # leaf: ShapeDtypeStruct from eval_shape
        if tuple(np.shape(got)) != tuple(fresh.shape):
            raise ValueError(
                f"{path}: restored leaf shape {np.shape(got)} != expected "
                f"{tuple(fresh.shape)} — params/optimizer mismatch with "
                "the checkpoint")
        yield got


def _make_optimizer(cfg: TrainerConfig, params) -> optax.GradientTransformation:
    tx = optax.chain(
        optax.clip_by_global_norm(cfg.grad_clip),
        optax.adamw(_make_schedule(cfg), b1=cfg.b1, b2=cfg.b2,
                    weight_decay=cfg.weight_decay),
    )
    if cfg.freeze_predicate is not None:
        def label_tree(p):
            return jax.tree_util.tree_map_with_path(
                lambda path, _: "frozen" if cfg.freeze_predicate(
                    tuple(getattr(k, "key", str(k)) for k in path)) else "train", p)

        tx = optax.multi_transform({"train": tx, "frozen": optax.set_to_zero()},
                                   label_tree(params))
    if cfg.grad_accum > 1:
        tx = optax.MultiSteps(tx, every_k_schedule=cfg.grad_accum)
    return tx


class Trainer:
    """Owns: param init on-mesh, the jitted train step, and the epoch loop."""

    def __init__(self, module: nn.Module, mesh_ctx: MeshContext, cfg: TrainerConfig,
                 loss_fn: Callable[[Any, dict], jax.Array] | None = None,
                 has_batch_stats: bool = False, rules=None):
        self.module = module
        self.mesh = mesh_ctx
        self.cfg = cfg
        self.has_batch_stats = has_batch_stats
        self.rules = rules or logical_axis_rules()
        self._loss_fn = loss_fn
        self._train_step = None
        self._scan_step = None
        # K device-resident batches -> the [K, B, ...] chunk (_fit_chunked)
        self._stack_chunk = None
        # inside fit: the loop's own count of the step the next dispatch
        # trains from (train.dispatch's first_step); None outside
        self._fit_step: int | None = None
        # executables each jitted step got so far (train.compile's signature)
        self._signatures = {"scan": 0, "step": 0}
        _listen_for_builds()
        self._metrics: list[dict] = []
        # newest optimizer step whose loss was finite (post-step numbering,
        # comparable to checkpoint step numbers); -1 until the first loss
        # lands. Mirrored on the synapseml_train_last_finite_step gauge so
        # the rewind trigger is a metric read.
        self.last_finite_step: int = -1

    # ---- sharding helpers ----
    def _unbox_with_sharding(self, tree):
        """nn.Partitioned leaves -> device arrays placed by logical rules."""
        from ..parallel.mesh import shard_params

        return shard_params(tree, self.mesh, self.rules)

    def _rule_place_params(self, params):
        """Declarative placement: the cfg's regex rule table
        (``parallel.partition.PartitionRules``) maps param paths to mesh
        specs — plain pytrees (convert_hf checkpoints, module inits whose
        metadata the logical rules replicated) get real placement. Also
        records the sharding pytree the jitted step constrains against."""
        from ..parallel import partition as pp

        rules = self.cfg.partition_rules
        if rules is None:
            self._param_shardings = None
            return params
        specs = pp.match_partition_rules(rules, params)
        self._param_shardings = pp.tree_shardings(self.mesh, specs, params)
        return pp.place_tree(params, self._param_shardings)

    def _rule_place_opt_state(self, params, opt_state):
        """Optimizer-state placement from the SAME rule table (optax state
        paths embed the param names), plus the ZeRO weight-update sharding
        over the replica axes when ``cfg.zero_shard`` — per-replica
        optimizer memory drops to ~1/dp while the step stays ONE jitted
        program (the constraint in ``_step_fn`` keeps every update
        sharded)."""
        from ..parallel import partition as pp

        rules = self.cfg.partition_rules
        if rules is None:
            self._opt_shardings = None
            return opt_state
        skel = jax.eval_shape(lambda: opt_state)
        specs = pp.opt_state_specs(rules, skel, self.mesh,
                                   zero=self.cfg.zero_shard)
        self._opt_shardings = pp.tree_shardings(self.mesh, specs, skel)
        placed = pp.place_tree(opt_state, self._opt_shardings)
        pp.emit_shard_metrics(params, placed, self.mesh)
        return placed

    def _place_counters(self, opt_state, step: int) -> tuple[Any, jax.Array]:
        """``opt_state`` with its 0-d leaves (Adam's ``count``, a schedule's
        or ``MultiSteps``' counters), and ``step``, replicated on the mesh, as
        the jitted step returns them; a leaf the rule table already placed
        stays as it is. A counter made off the mesh has another type than
        the step's output, so the step would meet a second signature at its
        second dispatch and be traced and compiled again."""
        from ..parallel.partition import place_leaf

        rep = self.mesh.replicated()

        def place(x):
            if np.ndim(x) or getattr(getattr(x, "sharding", None), "mesh", None) == rep.mesh:
                return x
            return place_leaf(x, rep)

        return jax.tree.map(place, opt_state), place_leaf(np.int32(step), rep)

    def checkpoint_sharding_fn(self):
        """Path-aware ``sharding_fn`` for ``restore_checkpoint``: leaves
        restore DIRECTLY onto their rule-table placement (each device
        receives only its shard slices — no device-resident full copy).
        None when the trainer has no rule table (host-numpy restore)."""
        from ..parallel import partition as pp

        if self.cfg.partition_rules is None:
            return None
        return pp.checkpoint_sharding_fn(self.cfg.partition_rules,
                                         self.mesh,
                                         zero=self.cfg.zero_shard)

    def sharding_manifest(self) -> dict | None:
        """The serializable ``sharding`` section (rule table + mesh) that
        checkpoints and registry manifests carry for round-trips."""
        import dataclasses as dc

        from ..parallel import partition as pp

        rules = self.cfg.partition_rules
        if rules is None:
            return None
        if rules.mesh is None:
            rules = dc.replace(rules, mesh=self.mesh.config)
        return pp.sharding_manifest_section(rules)

    def ensure_optimizer(self, params) -> None:
        """(Re)build the optax transform for externally restored params —
        the checkpoint-resume path that skips init_state."""
        self._tx = _make_optimizer(self.cfg, params)

    def resume_state(self, params, opt_state=None, step: int = 0,
                     batch_stats=None, constants=None) -> TrainState:
        """Build a TrainState from restored host/device pytrees (see
        parallel.checkpoint.restore_checkpoint) without re-initializing.

        A serialized ``opt_state`` comes back as plain tuples/dicts (the
        npz round-trip keeps order but not optax's NamedTuple node types);
        its leaves are matched STRUCTURALLY against a freshly initialized
        optimizer skeleton — dict children by key (order-insensitive, so a
        dict whose serialized order differs from jax's sorted flatten order
        cannot silently swap same-shaped leaves like Adam's mu/nu),
        sequence children by position — then poured into the skeleton so
        optax transforms see their own state classes again.

        With ``cfg.partition_rules`` set, the restored leaves are placed
        by the rule table (params sharded, optimizer state ZeRO-sharded
        when enabled) — a replicated checkpoint restores ONTO the sharded
        mesh with each device receiving only its shard slices, instead of
        the old host-first full-leaf device_put."""
        self.ensure_optimizer(params)
        params = self._rule_place_params(params)
        if opt_state is None:
            opt_state = self._tx.init(params)
        else:
            # eval_shape: the reference structure/shapes with ZERO allocation
            # (a real init would materialize ~2x-param Adam moments just to
            # throw them away — an OOM risk on 7B-class resumes)
            fresh = jax.eval_shape(self._tx.init, params)
            _, treedef = jax.tree.flatten(fresh)
            opt_state = jax.tree.unflatten(
                treedef, list(_align_restored(fresh, opt_state, "opt_state")))
        opt_state, step = self._place_counters(
            self._rule_place_opt_state(params, opt_state), step)
        return TrainState(params=params, opt_state=opt_state, step=step,
                          batch_stats=batch_stats, constants=constants)

    def init_state(self, example_batch: dict, rng: jax.Array | None = None,
                   init_params=None, init_batch_stats=None) -> TrainState:
        """Fresh state; ``init_params`` (host pytree, e.g. from
        models.convert_hf) grafts pretrained values into the module's
        Partitioned boxes so they inherit the logical shardings — the
        transfer-learning entry the reference gets from HF/torchvision
        ``from_pretrained``."""
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        inputs = self._model_inputs(example_batch)
        with self.mesh.scope():
            variables = self.module.init(rng, **inputs)
        boxed = variables["params"]
        if init_params is not None:
            boxed = _graft_params(boxed, init_params)
        params = self._unbox_with_sharding(boxed)
        params = self._rule_place_params(params)
        batch_stats = None
        if self.has_batch_stats and "batch_stats" in variables:
            batch_stats = self._unbox_with_sharding(
                _graft_params(variables["batch_stats"], init_batch_stats)
                if init_batch_stats is not None else variables["batch_stats"])
        constants = None
        if "constants" in variables:
            constants = self._unbox_with_sharding(variables["constants"])
        tx = _make_optimizer(self.cfg, params)
        self._tx = tx
        opt_state, step = self._place_counters(
            self._rule_place_opt_state(params, tx.init(params)), 0)
        return TrainState(params=params, opt_state=opt_state, step=step,
                          batch_stats=batch_stats, constants=constants)

    def _model_inputs(self, batch: dict) -> dict:
        drop = {"labels", "label", "mask", "_valid"}
        return {k: v for k, v in batch.items() if k not in drop}

    @property
    def _looped(self) -> bool:
        """The module runs a looped stack: it takes the labels and gives each
        step's cross-entropy and exit gate (`exit_weighted_loss`)."""
        return getattr(getattr(self.module, "cfg", None), "loop_steps", 1) > 1

    @property
    def _sows(self) -> bool:
        """The module sows loss terms and step statistics (``_MODEL_STATS``)."""
        cfg = getattr(self.module, "cfg", None)
        return getattr(cfg, "moe_experts", 0) > 0 or getattr(cfg, "attn_topk", 0) > 0

    def _fold_sown(self, loss, inter) -> tuple:
        """``loss`` with the sown terms added, and the step statistics."""
        sown: dict = {}
        for path, v in jax.tree_util.tree_flatten_with_path(inter)[0]:
            for k in path:
                name = getattr(k, "key", None)
                if name == "moe_aux_loss" or name in _MODEL_STATS:
                    sown.setdefault(name, []).append(jnp.mean(jnp.asarray(v)))
        aux_terms = sown.pop("moe_aux_loss", None)
        if aux_terms:
            loss = loss + self.cfg.moe_aux_weight * (
                sum(aux_terms) / len(aux_terms))
        stats = {name: _MODEL_STATS[name](jnp.stack(terms)).astype(jnp.float32)
                 for name, terms in sown.items()}
        if "sparse_attn_indexer_kl" in stats:
            loss = loss + self.cfg.indexer_loss_weight * stats["sparse_attn_indexer_kl"]
        return loss, stats

    def default_loss(self, variables, batch, train: bool):
        kwargs = dict(self._model_inputs(batch))
        mutable = []
        if self.has_batch_stats:
            kwargs["train"] = train
            mutable = ["batch_stats"] if train else []
        if train and self._sows:
            # collect the sown terms: the switch load-balance loss — without
            # it the router trains with zero balancing pressure and can
            # collapse every token onto one expert —, the sparse-attention
            # indexer's loss, and the step statistics
            mutable = list(mutable) + ["intermediates"]
        labels = batch.get("labels", batch.get("label"))
        if self._looped:
            kwargs["labels"] = labels
        if mutable:
            logits, new_vars = self.module.apply(variables, mutable=mutable, **kwargs)
        else:
            logits, new_vars = self.module.apply(variables, **kwargs), {}
        if self._looped:    # (each step's cross-entropy, the exit gates' logits)
            loss, stats = exit_weighted_loss(*logits, labels, batch.get("_valid"),
                                             self.cfg.exit_entropy_weight)
            new_vars = dict(new_vars, step_stats=stats)
        else:
            loss = cross_entropy_loss(logits, labels, batch.get("_valid"))
        inter = new_vars.get("intermediates") if isinstance(new_vars, dict) else None
        if inter:
            loss, stats = self._fold_sown(loss, inter)
            new_vars = {k: v for k, v in new_vars.items()
                        if k != "intermediates"}
            if stats:
                new_vars["step_stats"] = stats
        return loss, (logits, new_vars)

    # ---- the jitted step ----
    def _step_fn(self):
        if not hasattr(self, "_tx"):
            raise RuntimeError("optimizer not built: call init_state() for a fresh "
                               "run or resume_state() after restore_checkpoint()")
        tx = self._tx
        # rule-table shardings captured INTO the jitted step: the constraint
        # keeps every new param/opt-state value on its declared placement —
        # this is where the ZeRO weight update happens (XLA partitions the
        # moment updates across the replica group instead of replicating)
        param_sh = getattr(self, "_param_shardings", None)
        opt_sh = getattr(self, "_opt_shardings", None)

        # the scopes name the step's device ops whatever the module is called
        # and whether or not a user loss_fn is set: ".../jvp(forward)/...",
        # ".../transpose(jvp(forward))/..." (the backward pass),
        # ".../optimizer/...", ".../step_metrics/...". Metadata only.
        def step_fn(state: dict, batch: dict) -> tuple[dict, dict]:
            if param_sh is not None:
                # read on the declared placement too: the scanned step's carry
                # keeps it, where XLA would lay the parameters out as the
                # ZeRO update computes them and the state would come back
                # with another type than it went in
                state = dict(state, params=jax.lax.with_sharding_constraint(
                    state["params"], param_sh))

            def loss_of(params):
                variables = {"params": params}
                if state.get("batch_stats") is not None:
                    variables["batch_stats"] = state["batch_stats"]
                if state.get("constants") is not None:
                    variables["constants"] = state["constants"]
                with jax.named_scope("forward"):
                    if self._loss_fn is not None:
                        loss = self._loss_fn(variables, batch)
                        return loss, (None, {})
                    return self.default_loss(variables, batch, train=True)

            (loss, (_, new_vars)), grads = jax.value_and_grad(loss_of, has_aux=True)(
                state["params"])
            with jax.named_scope("optimizer"):
                updates, new_opt = tx.update(grads, state["opt_state"], state["params"])
                new_params = optax.apply_updates(state["params"], updates)
                if param_sh is not None:
                    new_params = jax.lax.with_sharding_constraint(
                        new_params, param_sh)
                if opt_sh is not None:
                    new_opt = jax.lax.with_sharding_constraint(new_opt, opt_sh)
            new_state = {"params": new_params, "opt_state": new_opt,
                         "step": state["step"] + 1}
            if state.get("batch_stats") is not None:
                new_state["batch_stats"] = new_vars.get("batch_stats", state["batch_stats"])
            else:
                new_state["batch_stats"] = None
            if "constants" in state:      # as given: the carry keeps its structure
                new_state["constants"] = state["constants"]
            with jax.named_scope("step_metrics"):
                grad_norm = optax.global_norm(grads).astype(jnp.float32)
            # beside them, for a module that has the mechanism: _MODEL_STATS
            metrics = {"loss": loss.astype(jnp.float32), "grad_norm": grad_norm,
                       **new_vars.get("step_stats", {})}
            return new_state, metrics

        return step_fn

    @contextlib.contextmanager
    def _dispatching(self, program: str, steps: int) -> Iterator[_Build]:
        """The ``train.dispatch`` span around a call of a jitted step. The
        call returns when the program is enqueued, and holds the trace, the
        lowering and the compile or cache load when the signature is new:
        ``compiled`` says whether jax reported an executable built under it,
        and the ``_Build`` this yields holds what it cost (``_built`` turns
        it into ``train.compile`` spans). ``first_step`` is the fit loop's
        own count of the step this dispatch trains from: None outside
        ``fit``, since reading ``state.step`` would wait for the device.

        The caller makes the call in its own frame, inside this ``with``: a
        helper frame above it, or a dozen more locals in ``fit``, moved the
        step's trace onto a slow alignment of CPython's 16 KiB frame-stack
        chunks and cost a quarter of the trace and lowering time on the
        chip's host (PERF.md section 6, PR 27;
        ``perfbench/tools/chunk_shim.c`` counts it on any machine)."""
        first = self._fit_step
        with _LoopSpan("train.dispatch", {"program": program, "steps": steps,
                                          "first_step": first}) as ls:
            build = _Build(ls.span)
            try:
                yield build
            finally:
                build.close()
            ls.span.set_attribute("compiled", build.executables > 0)
        if first is not None:
            self._fit_step = first + steps
        _TRAIN_METRICS.get()["dispatches"].inc(program=program)

    @contextlib.contextmanager
    def _built(self, program: str, built: _Build) -> Iterator[None]:
        """After a dispatch under which a jitted step got an executable: one
        ``train.compile`` span an executable, under that dispatch's span,
        with jax's seconds by phase, what the persistent cache did, and XLA's
        memory analysis of the executable the NEXT dispatch runs (bytes a
        device, and ``take_ms``, the host time of reading them). The caller
        takes that one inside this ``with`` and hands it to ``built.of``:
        ``fn.trace(sd, placed).lower().compile()`` on the dispatch's own
        outputs and batch, which is the next dispatch's signature.

        Where the outputs' type is the inputs' (a state from ``init_state``
        or ``resume_state``, which place the counters as the step returns
        them, or one that came out of the step), that finds what the call
        just built and costs a lookup: the step has one signature. Where it is
        new (a ``TrainState`` built by hand, its counters off the mesh), it
        builds the second executable here, and the next dispatch, which would
        have built it, finds it: the work moves by one dispatch and none is
        added. The executable that ran once and was replaced keeps its times
        and has no bytes.

        The caller makes the take in its own frame, as the dispatch, binds no
        name for it and spells it ``trace().lower()``: through ``fn.lower``,
        or with two more locals in ``train_steps_scan``, the second
        signature's trace fell on a slow alignment of the frame stack
        (``_dispatching``; the counts are in PERF.md section 6, PR 37)."""
        take = _Build(built.under)
        try:
            with jax.profiler.TraceAnnotation("train.compile"), self.mesh.scope():
                yield
        finally:
            take.close()
            if built.taken is not None:   # beside the bytes: what reading them took
                built.taken["take_ms"] = take.host_ms
            if take.executables:      # a second executable: the bytes are its
                take.taken, built.taken = built.taken, None
                self._compile_span(program, built)
                self._compile_span(program, take)
            else:                     # it found the executable the call built
                self._compile_span(program, built)

    def _compile_span(self, program: str, build: _Build) -> None:
        self._signatures[program] += 1
        obs.get_tracer().record_span(
            "train.compile", build.start_ns, build.host_ms,
            {"program": program, "signature": self._signatures[program],
             **build.attributes()}, parent=build.under.context)
        m = _TRAIN_METRICS.get()
        m["compiles"].inc(program=program)
        for phase, seconds in build.seconds.items():
            m["compile_seconds"].inc(seconds, program=program, phase=phase)
        if build.taken is not None:
            for name, (kind, _) in _PROGRAM_BYTES.items():
                m["program_bytes"].set(build.taken[name], program=program, kind=kind)

    def train_step(self, state: TrainState, batch: dict) -> tuple[TrainState, dict]:
        if self._train_step is None:
            self._train_step = jax.jit(self._step_fn(), donate_argnums=(0,))
        with _LoopSpan("train.place", _place_attrs(batch)):
            placed = self.mesh.shard_batch(batch)
        with self._dispatching("step", 1) as built, self.mesh.scope():
            sd, metrics = self._train_step(state._step_input(), placed)
        if built.executables:
            with self._built("step", built):
                built.of(self._train_step.trace(sd, placed).lower().compile())
        return TrainState(params=sd["params"], opt_state=sd["opt_state"], step=sd["step"],
                          batch_stats=sd["batch_stats"], constants=sd["constants"]), metrics

    # ---- scanned multi-step: K optimizer steps in ONE dispatch ----
    # Host dispatch overhead disappears: the train loop itself lives
    # on-device as a lax.scan, the TPU-idiomatic replacement for horovod's
    # per-step host-driven loop. A state from init_state / resume_state has
    # the types the step returns, so the step has one signature (_built).
    def train_steps_scan(self, state: TrainState, stacked_batches: dict
                         ) -> tuple[TrainState, dict]:
        """stacked_batches: pytree whose leaves have leading dim K (num steps)."""
        if self._scan_step is None:
            step_fn = self._step_fn()

            def multi(sd: dict, batches: dict):
                return jax.lax.scan(step_fn, sd, batches)

            self._scan_step = jax.jit(multi, donate_argnums=(0,))
        with _LoopSpan("train.place", _place_attrs(stacked_batches)):
            placed = self.mesh.shard_stacked_batch(stacked_batches)
        with self._dispatching("scan", _leading_dim(placed)) as built, self.mesh.scope():
            sd, metrics = self._scan_step(state._step_input(), placed)
        if built.executables:
            with self._built("scan", built):
                built.of(self._scan_step.trace(sd, placed).lower().compile())
        return (TrainState(params=sd["params"], opt_state=sd["opt_state"], step=sd["step"],
                           batch_stats=sd["batch_stats"], constants=sd["constants"]), metrics)

    # ---- non-finite loss guard ----
    def _observe_losses(self, losses, last_step: int) -> None:
        """Check host-side per-step losses ending at post-step number
        ``last_step``: advance ``last_finite_step``, count non-finite steps
        into ``synapseml_train_nonfinite_total``, and (under
        ``cfg.nonfinite_action='raise'``) abort with
        :class:`NonFiniteLossError` naming the first poisoned step."""
        arr = np.asarray(losses, dtype=np.float64).reshape(-1)
        if arr.size == 0:
            return
        finite = np.isfinite(arr)
        m = _TRAIN_METRICS.get()
        if bool(finite.all()):
            self.last_finite_step = max(self.last_finite_step, int(last_step))
        else:
            first_bad = int(np.argmax(~finite))
            bad_step = last_step - arr.size + 1 + first_bad
            if first_bad > 0:
                self.last_finite_step = max(self.last_finite_step,
                                            int(bad_step - 1))
            m["nonfinite"].inc(int((~finite).sum()), engine="trainer")
            if self.cfg.nonfinite_action == "raise":
                m["last_finite"].set(self.last_finite_step)
                raise NonFiniteLossError(bad_step, self.last_finite_step)
        m["last_finite"].set(self.last_finite_step)

    # ---- loop ----
    def fit(self, state: TrainState, batch_iter: Iterator[dict], max_steps: int,
            log_every: int = 50, callback: Callable[[int, dict], None] | None = None,
            scan_chunk: int = 8, checkpointer=None,
            checkpoint_every: int = 0,
            skip_fn: Callable[[int], bool] | None = None,
            gang=None) -> TrainState:
        """Streaming fit over ANY batch iterator.

        Default path: ``scan_chunk`` same-shape batches train in ONE
        ``lax.scan`` dispatch — the DataFrame/streaming plane gets the same
        dispatch amortization as array training. A background thread pulls
        the batches of the next chunks and places each on the mesh
        (``mesh.shard_batch``), so a chunk is device-resident before the loop
        asks for it; the loop stacks it there with one small jitted program
        and hands ``train_steps_scan`` the ``[K, B, ...]`` device arrays. No
        host array of the chunk's size is made. At most two chunks wait on
        the device beside the one that runs; the producer is never more than
        three chunks of batches ahead of the trained step. Odd-shaped or
        leftover batches run per-step automatically, as host batches, so
        iterators with varying batch shapes stay correct (each shape still
        compiles once). A per-step ``callback`` (or ``scan_chunk<=1``) forces
        the per-step loop.

        ``checkpointer`` (a ``parallel.AsyncCheckpointer``) +
        ``checkpoint_every``: full train state (params/opt_state/step/
        batch_stats) is snapshotted every N steps and written in the
        checkpointer's background thread — training never stalls on disk.
        The final state is always saved; resume via
        ``restore_checkpoint`` + ``Trainer.resume_state``.

        ``skip_fn(batch_index)`` (batch_index = the global pre-step
        counter, i.e. the ``state.step`` value the batch would train from)
        marks batches to CONSUME BUT NOT TRAIN: the batch is pulled from
        the iterator (keeping the deterministic stream position and the
        checkpointable step↔batch alignment) and ``state.step`` advances
        with params untouched. This is the supervisor's NaN-rewind
        mechanism — skip past a poisoned batch window instead of training
        on it again. Forces the per-step path.

        ``gang`` (a :class:`~synapseml_tpu.parallel.gang.GangWorker`)
        makes this fit a gang member: one heartbeat per optimizer step, a
        verdict poll at every step boundary — a ``resize`` verdict raises
        :class:`~synapseml_tpu.parallel.gang.GangAborted` (a member died;
        exit and resume from the last committed checkpoint), an
        ``abort_and_checkpoint`` verdict runs the emergency-checkpoint
        dance (train to the gang's sync step, force a checkpoint, ack,
        wait for the driver's commit) and raises :class:`~synapseml_tpu.
        parallel.gang.Preempted`. Forces the per-step path.

        The loop times itself: every call records one ``train.fit`` root
        span and a span at each boundary under it (``train.chunk_wait``,
        ``train.place``, ``train.dispatch``, ``train.fetch``,
        ``train.checkpoint``; from the chunk producer's thread
        ``train.chunk_build`` and the ``train.place`` of each chunk), with
        ``synapseml_train_loop_ms{phase}`` and the dispatch and compile
        counters beside them (docs/OBSERVABILITY.md).
        """
        it = iter(batch_iter)
        if checkpointer is not None and 0 < checkpoint_every < scan_chunk:
            # checkpoints can only happen between dispatches; honor the
            # requested durability by shrinking the fused chunk
            scan_chunk = checkpoint_every
        ckpt_due = self._ckpt_writer(checkpointer, checkpoint_every)
        per_step = (callback is not None or skip_fn is not None or scan_chunk <= 1
                    or max_steps <= 1 or gang is not None)
        base = int(state.step)
        # the root of the loop's spans: train.chunk_wait, train.place,
        # train.dispatch, train.fetch and train.checkpoint on this thread,
        # train.chunk_build and a chunk's train.place on the chunk
        # producer's, all of one trace id
        with _LoopSpan("train.fit", {"scan_chunk": 1 if per_step else scan_chunk,
                                     "first_step": base}) as root:
            self._fit_step = base
            try:
                if not per_step:
                    state, steps_done = self._fit_chunked(
                        state, it, max_steps, scan_chunk, log_every, ckpt_due,
                        root.span.context)
                else:
                    state, steps_done = self._fit_per_step(
                        state, it, max_steps, log_every, callback, ckpt_due,
                        checkpointer, skip_fn, gang)
            finally:
                self._fit_step = None
            root.span.set_attribute("steps_done", steps_done)
        return state

    def _fit_per_step(self, state: TrainState, it: Iterator[dict],
                      max_steps: int, log_every: int, callback, ckpt_due,
                      checkpointer, skip_fn, gang) -> tuple[TrainState, int]:
        base = self._fit_step
        meter = _ThroughputMeter()
        # per-step host materialization of the loss blocks async
        # dispatch — only the "raise" guard (the supervised continual
        # path, which needs prompt NaN detection for its rewind) pays
        # it; "count" mode samples the losses already pulled at the
        # log windows, keeping the default path's overlap intact
        eager_guard = self.cfg.nonfinite_action == "raise"
        if gang is not None:
            gang.heartbeat(base)  # alive before the first (slow) compile
        sync_at: int | None = None
        i = -1
        for i in range(max_steps):
            try:
                batch = next(it)  # never pull past max_steps batches
            except StopIteration:
                i -= 1
                break
            if skip_fn is not None and skip_fn(base + i):
                # consumed, not trained: the stream stays aligned with
                # the step counter, the params stay at the checkpoint
                state = dataclasses.replace(state,
                                            step=state.step + 1)
                self._fit_step += 1
                self._count_skipped()
                ckpt_due(state, i + 1)
            else:
                state, metrics = self.train_step(state, batch)
                # this loop fetches no loss a step: its cycle ends where
                # the dispatch returns
                meter.cycle(time.time_ns(), batch, steps=1)
                if eager_guard:
                    self._observe_losses(
                        [self._fetch_loss(metrics)], last_step=base + i + 1)
                if callback is not None:
                    callback(i, metrics)
                if (i + 1) % log_every == 0:
                    lf = self._fetch_loss(metrics)
                    if not eager_guard:
                        self._observe_losses([lf],
                                             last_step=base + i + 1)
                    self._metrics.append(meter.entry(lf))
                ckpt_due(state, i + 1)
            if gang is not None:
                step_now = base + i + 1
                gang.heartbeat(step_now)
                if sync_at is None:
                    v = gang.check(step_now)
                    if v == "resize":
                        from ..parallel.gang import GangAborted

                        raise GangAborted(
                            "gang verdict: resize — a member failed; "
                            "exit and resume from the last committed "
                            "checkpoint")
                    if isinstance(v, tuple):  # ("sync", S)
                        sync_at = int(v[1])
                if sync_at is not None and step_now >= sync_at:
                    # emergency coordinated checkpoint at the gang's
                    # sync step: force the write, flush it, phase-2 ack
                    from ..parallel.gang import GangAborted, Preempted

                    ckpt_due(state, i + 1, final=True)
                    if checkpointer is not None:
                        checkpointer.wait()
                    if checkpointer is not None \
                            and gang.ack_and_wait_commit(step_now):
                        raise Preempted(step_now)
                    raise GangAborted(
                        "emergency checkpoint did not commit inside "
                        "the grace window — resume from the last "
                        "committed step")
        ckpt_due(state, i + 1, final=True)
        return state, i + 1

    @staticmethod
    def _fetch_loss(metrics: dict) -> float:
        """A single step's loss on the host: blocks until the device has it."""
        with _LoopSpan("train.fetch") as fetch:
            Trainer._fetch_model_stats(metrics, fetch.span)
            return float(np.asarray(metrics["loss"]))

    @staticmethod
    def _fetch_model_stats(metrics: dict, span) -> None:
        """Inside ``train.fetch``: the statistics a step's metrics carry for a
        module with routed experts or learned sparse attention, onto the span
        (the dispatch's last step; pairs summed over its steps) and into
        their series. Nothing for any other module."""
        if len(metrics) <= 2:
            return
        m = _TRAIN_METRICS.get()
        for name in _MODEL_STATS:
            if name not in metrics:
                continue
            steps = np.asarray(metrics[name], np.float64).reshape(-1)
            if name == "moe_held_pairs":        # the one counter among them
                value = float(steps.sum())
                m[name].inc(value)
            else:
                value = float(steps[-1])
                m[name].set(value)
            span.set_attribute(name, value)

    @staticmethod
    def _count_skipped() -> None:
        from ..core import observability as obs

        obs.get_registry().counter(
            "synapseml_train_skipped_steps_total",
            "batches consumed but not trained (NaN-rewind skip windows)",
            ("engine",)).inc(engine="trainer")

    def _ckpt_writer(self, checkpointer, every: int):
        """Periodic full-state async snapshots (no-op without a checkpointer)."""
        last = [0]

        def due(state: TrainState, steps_done: int, final: bool = False):
            if checkpointer is None or steps_done <= 0:
                return
            if final or (every > 0 and steps_done - last[0] >= every):
                if final and last[0] == steps_done:
                    return  # already saved at exactly this step
                step = int(state.step)
                with _LoopSpan("train.checkpoint", {"step": step}):
                    checkpointer.save(state.as_dict(), step=step)
                last[0] = steps_done

        return due

    def _fit_chunked(self, state: TrainState, it: Iterator[dict],
                     max_steps: int, scan_chunk: int, log_every: int,
                     ckpt_due, root: "obs.SpanContext") -> tuple[TrainState, int]:
        import queue
        import threading

        END = object()
        # one chunk queued and one in the producer's hand: at most two chunks
        # wait on the device beside the one that runs, whatever a chunk's size
        q: "queue.Queue" = queue.Queue(maxsize=1)
        stop = threading.Event()  # consumer died: unblock the producer
        # set while the loop waits for the device (and before its first
        # program): the producer starts a chunk only then. Between two
        # programs the loop alone runs Python, so the producer's gather and
        # placing cannot take the interpreter lock from it while the chip idles
        device_busy = threading.Event()
        device_busy.set()

        def shape_key(b: dict):
            # dtype via attribute lookup: np.asarray on a jax.Array would
            # force a device-to-host copy per batch just to read the dtype
            return tuple(sorted(
                (k, np.shape(v), str(getattr(v, "dtype", None)
                                     or np.asarray(v).dtype))
                for k, v in b.items()))

        def put(item) -> bool:
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.5)
                    return True
                except queue.Full:
                    continue
            return False

        taken = 0

        def gather(group: list) -> tuple[dict | None, bool, float]:
            """Fill ``group`` with consecutive same-shape batches up to
            ``scan_chunk``. Returns the batch of another shape that ended it
            early (else None), whether the stream may hold more, and the
            seconds spent inside ``next(it)``."""
            nonlocal taken
            key = shape_key(group[0]) if group else None
            next_s = 0.0
            while len(group) < scan_chunk and taken < max_steps:
                t0 = time.perf_counter()
                try:
                    b = next(it)
                except StopIteration:
                    return None, False, next_s + time.perf_counter() - t0
                next_s += time.perf_counter() - t0
                taken += 1
                if key is None:
                    key = shape_key(b)
                elif shape_key(b) != key:
                    return b, True, next_s
                group.append(b)
            return None, taken < max_steps, next_s

        def producer():
            try:
                carry, more = None, True
                while more or carry is not None:
                    while not device_busy.wait(timeout=0.5):
                        if stop.is_set():
                            return
                    # one span a chunk put on the queue, under the fit's root
                    # (the tracer's stack is per thread)
                    with _LoopSpan("train.chunk_build", parent=root) as ls:
                        group = [] if carry is None else [carry]
                        carry, more, next_s = gather(group)
                        if not group:  # the stream ended on a chunk boundary
                            ls.keep = False
                            break
                        t0 = time.perf_counter()
                        steps = len(group)
                        if steps == scan_chunk:
                            # on its way to the device from here: the copy
                            # runs on the runtime's threads while the chip
                            # computes the chunk before. Only transfers leave
                            # this thread; every program is the main thread's
                            with _LoopSpan("train.place",
                                           {"bytes": _tree_nbytes(_host_leaves(group))},
                                           parent=root):
                                item = ("chunk", [self.mesh.shard_batch(b)
                                                  for b in group])
                        else:  # short/odd tail: per-step, no extra scan compile
                            item = ("steps", group)
                        del group  # not held through the wait on a full queue
                        t1 = time.perf_counter()
                        ls.span.attributes.update(
                            next_ms=next_s * 1e3, stack_ms=(t1 - t0) * 1e3,
                            bytes=_tree_nbytes(item[1]), steps=steps)
                        sent = put(item)
                        del item
                        ls.span.set_attribute(
                            "put_wait_ms", (time.perf_counter() - t1) * 1e3)
                    if not sent:
                        return
                put(END)
            except BaseException as e:  # surface producer errors
                put(e)

        if self._stack_chunk is None:
            # one local stack a device, no collective: the pieces and the
            # chunk are sharded over the same axes of the batch dim
            self._stack_chunk = jax.jit(
                _stack_steps, out_shardings=self.mesh.stacked_batch_sharding())
        threading.Thread(target=producer, daemon=True).start()
        meter = _ThroughputMeter()
        steps_done = logged_at = 0
        base = self._fit_step
        try:
            while True:
                with _LoopSpan("train.chunk_wait"):
                    item = q.get()
                    # the get lets the producer go on: hold it before it has
                    # the interpreter lock again (if it wins that race, it
                    # builds beside this dispatch, which costs time, no more)
                    device_busy.clear()
                if item is END:
                    break
                if isinstance(item, BaseException):
                    raise item
                kind, payload = item
                del item  # the pieces of a chunk live no longer than its stack
                if kind == "chunk":
                    payload = self._stack_chunk(payload)
                    state, metrics = self.train_steps_scan(state, payload)
                    device_busy.set()
                    with _LoopSpan("train.fetch") as fetch:
                        losses = np.asarray(metrics["loss"])
                        self._fetch_model_stats(metrics, fetch.span)
                    meter.cycle(fetch.span.end_ns, payload, steps=scan_chunk)
                    steps_done += scan_chunk
                    loss = float(losses[-1])
                else:
                    device_busy.set()  # a tail: nothing left to keep clear of
                    losses = []
                    for b in payload:
                        state, metrics = self.train_step(state, b)
                        with _LoopSpan("train.fetch") as fetch:
                            losses.append(float(np.asarray(metrics["loss"])))
                            self._fetch_model_stats(metrics, fetch.span)
                        meter.cycle(fetch.span.end_ns, b, steps=1)
                    steps_done += len(payload)
                    loss = losses[-1]
                self._observe_losses(losses, last_step=base + steps_done)
                if steps_done - logged_at >= log_every or steps_done >= max_steps:
                    self._metrics.append(meter.entry(loss))
                    logged_at = steps_done
                ckpt_due(state, steps_done)
            ckpt_due(state, steps_done, final=True)
        finally:
            stop.set()
        return state, steps_done

    @property
    def metrics(self) -> list[dict]:
        return self._metrics


class _ThroughputMeter:
    """samples/sec and step time of one fit, counted in dispatch cycles. A
    cycle ends where the loop has a dispatch's losses back (``train.fetch``'s
    end) or, in the per-step loop, where the dispatch call returns. The clock
    starts at the END of the first cycle, so the first dispatch's trace and
    compile are in no rate; every later cycle's time over its steps is one
    observation of ``synapseml_train_step_duration_ms``. Utilisation is not
    this meter's to give: it needs the model's FLOPs from its shapes
    (``perfbench/flops/`` and the benchmark's ``step_mfu``)."""

    def __init__(self):
        self.steps = 0
        self.n_samples = 0          # of the cycles after the first
        self._first_ns: int | None = None
        self._last_ns = 0

    def cycle(self, end_ns: int, batch: dict, steps: int) -> None:
        """``batch`` leaves are (B, ...) when steps==1, (K, B, ...) stacked
        when steps==K; ``end_ns`` is on ``obs.Span``'s clock."""
        self.steps += steps
        if self._first_ns is None:
            self._first_ns = self._last_ns = end_ns
            return
        first = np.shape(next(iter(batch.values())))
        self.n_samples += int(np.prod(first[: (2 if steps > 1 else 1)]))
        if end_ns > self._last_ns:
            _TRAIN_METRICS.get()["step_ms"].observe(
                (end_ns - self._last_ns) / 1e6 / steps)
            self._last_ns = end_ns

    def entry(self, loss: float) -> dict:
        """One line of ``Trainer.metrics``; ``samples_per_sec`` once a cycle
        after the first has ended."""
        out = {"step": self.steps, "loss": loss}
        if self._first_ns is not None and self._last_ns > self._first_ns:
            out["samples_per_sec"] = (self.n_samples * 1e9
                                      / (self._last_ns - self._first_ns))
            _TRAIN_METRICS.get()["samples_per_sec"].set(out["samples_per_sec"])
        return out


def plan_fit(n: int, batch_size: int, epochs: int, max_steps: int) -> tuple[int, int]:
    """(effective batch size, total optimizer steps) for an n-row fit.
    Raises on empty input — shared by the DeepText/DeepVision estimators."""
    if n == 0:
        raise ValueError("cannot fit on an empty DataFrame (0 rows)")
    bs = min(batch_size, n)
    steps_per_epoch = max(n // bs, 1)
    total = max_steps if max_steps > 0 else steps_per_epoch * epochs
    return bs, total


def _fit_with_optional_checkpointing(stage, fit_fn):
    """Run a fit under an AsyncCheckpointer when checkpoint_dir is set
    (reference pytorch-lightning ModelCheckpoint role); fit_fn(ck, every)."""
    ckpt_dir = stage.get("checkpoint_dir")
    if not ckpt_dir:
        return fit_fn(None, 0)
    from ..parallel.checkpoint import AsyncCheckpointer

    with AsyncCheckpointer(ckpt_dir, keep=stage.get("checkpoint_keep")) as ck:
        return fit_fn(ck, stage.get("checkpoint_every"))


class _LoaderCheckpointer:
    """Checkpointer shim that rides the loader's iterator state along with
    every train-state snapshot: the saved tree gains a ``data_iter`` subtree
    (see :mod:`synapseml_tpu.data.state`), so a restore resumes the batch
    stream mid-epoch bit-identically — no replayed, no skipped rows. One
    batch == one ``state.step`` increment, so the step number indexes the
    loader's per-batch snapshots directly."""

    def __init__(self, inner, loader):
        self._inner = inner
        self._loader = loader

    def save(self, tree, step: int):
        snap = self._loader.state_for_batch(int(step))
        if snap is None:
            # never save a checkpoint that LOOKS resumable but would restart
            # the stream from epoch 0 — fit_source sizes the loader's
            # snapshot history off scan_chunk/prefetch so this cannot
            # happen unless that sizing drifts
            raise RuntimeError(
                f"loader state for batch {step} is no longer in the "
                "snapshot history — checkpoint would lose its data_iter "
                "subtree (resume guarantee broken); widen state_history")
        tree = dict(tree)
        tree["data_iter"] = snap.to_tree()
        return self._inner.save(tree, step=step)

    def wait(self):
        return self._inner.wait()

    def close(self):
        return self._inner.close()


def fit_source(trainer: "Trainer", source, *, batch_size: int, total_steps: int,
               seed: int, init_params=None, init_batch_stats=None,
               scan_chunk: int = 8, checkpointer=None, checkpoint_every: int = 0,
               state: "TrainState | None" = None, data_state: dict | None = None,
               epochs: int | None = None, drop_remainder: bool = True,
               shuffle_rows: str = "full", shuffle_window: int = 4096,
               prefetch: int = 2, device_prefetch: bool = False,
               columns: list | None = None, host_index: int = 0,
               host_count: int = 1,
               resume_from: str | None = None,
               skip_fn: Callable[[int], bool] | None = None,
               callback: Callable[[int, dict], None] | None = None
               ) -> "TrainState":
    """Streaming fit over a :class:`synapseml_tpu.data.ShardedSource`.

    The data plane supplies seeded shard + row shuffles, bucket-ladder batch
    shapes, and a bounded-queue background prefetcher; this function adds
    mesh alignment (batches pad to a multiple of the data-parallel size),
    state init from the first batch, and resumable checkpointing — when a
    ``checkpointer`` is given, every snapshot carries the loader's iterator
    state so ``restore_checkpoint`` + ``resume_state`` + ``fit_source(...,
    state=..., data_state=tree["data_iter"])`` continues the exact batch
    stream an uninterrupted run would have produced.

    ``total_steps`` is the TOTAL optimizer-step target: resuming from step N
    runs ``total_steps - N`` further steps. ``device_prefetch`` places the
    next batch on the mesh inside the prefetch thread (double-buffered
    ``jax.device_put``) — only engaged on the per-step path
    (``scan_chunk<=1``); the chunked scan path needs no flag: its chunk
    producer places every batch of a chunk ahead of the dispatch and the
    chunk is stacked on the device (``Trainer.fit``).

    ``host_index``/``host_count`` default to 0/1 — ONE logical stream,
    identical on every process, because ``mesh.shard_batch`` expects each
    process to supply the same global batch (GSPMD splits it). Per-host
    disjoint shard feeding is the ``data.DataLoader``-level feature for
    custom multi-host input pipelines.

    ``resume_from`` (a checkpoint directory) restores the latest completed
    checkpoint THROUGH the trainer's rule-table ``sharding_fn`` — each
    restored leaf device_puts directly onto its declared placement, so a
    replicated checkpoint resumes onto a sharded/ZeRO mesh without any
    host-first full-leaf materialization — and threads the saved
    ``data_iter`` state back into the loader. A directory with no
    completed checkpoint starts fresh."""
    from ..data import DataLoader, IteratorState

    if state is None and resume_from is not None:
        from ..parallel.checkpoint import latest_verified_step
        from ..parallel.checkpoint import restore_checkpoint

        # VERIFIED latest: a torn/corrupted newest checkpoint demotes to
        # the previous completed step instead of resuming garbage params
        last = latest_verified_step(resume_from)
        if last is not None:
            tree = restore_checkpoint(
                resume_from, last,
                sharding_fn=trainer.checkpoint_sharding_fn())
            state = trainer.resume_state(
                tree["params"], tree.get("opt_state"),
                step=int(np.asarray(tree["step"])),
                batch_stats=tree.get("batch_stats"),
                constants=tree.get("constants"))
            if data_state is None:
                data_state = tree.get("data_iter")
    if checkpointer is not None \
            and getattr(checkpointer, "sharding", None) is None \
            and hasattr(checkpointer, "sharding"):
        # checkpoints carry the rule table + mesh so a restore tool (or a
        # resume on a different topology) knows the intended placement
        checkpointer.sharding = trainer.sharding_manifest()

    dp = trainer.mesh.data_parallel_size()
    done = int(state.step) if state is not None else 0
    remaining = total_steps - done
    if state is not None and remaining <= 0:
        return state
    if state is not None and done > 0 and data_state is None:
        raise ValueError(
            f"resuming from step {done} without data_state= — the loader "
            "would silently restart the stream from epoch 0. Pass "
            "data_state=tree['data_iter'] from the restored checkpoint for "
            "a bit-identical continuation, or data_state='fresh' to "
            "deliberately restart the stream")
    if isinstance(data_state, str):
        if data_state != "fresh":
            raise ValueError(f"data_state must be a restored data_iter "
                             f"tree or 'fresh', got {data_state!r}")
        # fresh stream, but keep the batch counter aligned with state.step
        # so checkpoint snapshots stay addressable by step number
        data_state = IteratorState(seed=int(seed),
                                   batches_emitted=done).to_tree()
    place = trainer.mesh.shard_batch if (device_prefetch and scan_chunk <= 1) \
        else None
    loader = DataLoader(
        source, batch_size, seed=seed, epochs=epochs,
        drop_remainder=drop_remainder, shuffle_rows=shuffle_rows,
        shuffle_window=shuffle_window, multiple_of=dp, prefetch=prefetch,
        place_fn=place, columns=columns,
        # the chunked fit's producer consumes up to ~3 chunks ahead of the
        # checkpointed step; the snapshot ring must outlive that lag or
        # saves lose their data_iter subtree
        state_history=max(64, 3 * max(scan_chunk, 1) + prefetch + 8),
        host_index=host_index, host_count=host_count,
        state=IteratorState.from_tree(data_state) if data_state is not None
        else None)
    it = iter(loader)
    try:
        if state is None:
            first = next(it)
            state = trainer.init_state(first, jax.random.PRNGKey(seed),
                                       init_params=init_params,
                                       init_batch_stats=init_batch_stats)

            def chain():
                yield first
                yield from it

            batch_iter: Iterator[dict] = chain()
        else:
            batch_iter = it
        ck = _LoaderCheckpointer(checkpointer, loader) \
            if checkpointer is not None else None
        return trainer.fit(state, batch_iter, max_steps=remaining,
                           scan_chunk=scan_chunk, checkpointer=ck,
                           checkpoint_every=checkpoint_every,
                           skip_fn=skip_fn, callback=callback)
    finally:
        loader.close()


class _ElasticLoaderCheckpointer:
    """The gang-mode counterpart of :class:`_LoaderCheckpointer`: every
    snapshot carries THIS host's per-stream cursors (an
    ``ElasticStreamSet.state_for_batch`` dict keyed by virtual-stream id);
    the multi-host :class:`~synapseml_tpu.parallel.AsyncCheckpointer`
    moves that subtree into the per-host shard payload, so the union of
    all ranks' shards always covers every stream of the
    :class:`~synapseml_tpu.data.ElasticPlan`."""

    def __init__(self, inner, stream, base_step: int):
        self._inner = inner
        self._stream = stream
        self._base = int(base_step)

    def save(self, tree, step: int):
        snap = self._stream.state_for_batch(int(step) - self._base)
        if snap is None:
            raise RuntimeError(
                f"elastic stream state for batch {int(step) - self._base} "
                f"(checkpoint step {step}) is no longer in the snapshot "
                "history — widen state_history")
        tree = dict(tree)
        tree["data_iter"] = snap
        return self._inner.save(tree, step=step)

    def wait(self):
        return self._inner.wait()

    def close(self):
        return self._inner.close()


def fit_gang_source(trainer: "Trainer", source, *, batch_size: int,
                    total_steps: int, seed: int, gang, checkpoint_dir: str,
                    rank: int, world: int, checkpoint_every: int = 10,
                    epochs: int | None = None,
                    drop_remainder: bool = True, shuffle_rows: str = "full",
                    shuffle_window: int = 4096, columns: list | None = None,
                    init_params=None, log_every: int = 50,
                    callback: Callable[[int, dict], None] | None = None
                    ) -> "TrainState":
    """One gang member's preemption-tolerant streaming fit.

    The elastic counterpart of :func:`fit_source`: the run is
    ``orig_world`` frozen virtual streams (an
    :class:`~synapseml_tpu.data.ElasticPlan`); this host serves the
    streams the plan assigns to ``rank`` of ``world`` and trains with the
    gang seams live — per-step heartbeats, verdict polling, coordinated
    per-host shard checkpoints every ``checkpoint_every`` steps. The
    DRIVER commits once every rank's ACK lands and owns the keep-last-K
    verified retention (``GangCoordinator(keep=...)``) — workers never
    commit or prune, so a lone survivor can't publish or destroy a
    world-N checkpoint on its own. A commit needs EVERY rank's ACK, so a
    finite-``epochs`` run whose streams exhaust a rank before
    ``total_steps`` stops committing at that rank's last ACK (a
    structured warning fires; size ``total_steps`` to the dataset or use
    the default ``epochs=None`` infinite cycling).

    On entry the checkpoint dir decides everything: a committed checkpoint
    ⇒ **N→M elastic resume** — the global tree reassembles from the N
    shards, params/optimizer state re-place via the trainer's rule table,
    and every virtual stream continues from its committed cursor (zero
    replayed, zero skipped rows — ``world`` may differ from the world that
    wrote the checkpoint); an empty dir ⇒ fresh start with
    ``orig_world = world``.

    Raises :class:`~synapseml_tpu.parallel.gang.Preempted` (exit
    ``EXIT_PREEMPTED``: an emergency checkpoint committed) or
    :class:`~synapseml_tpu.parallel.gang.GangAborted` (exit
    ``EXIT_RESIZE``: resume from the last commit)."""
    from ..data import ElasticPlan, ElasticStreamSet
    from ..parallel.checkpoint import AsyncCheckpointer
    from ..parallel.gang import elastic_restore

    resume = elastic_restore(checkpoint_dir)
    if resume is not None:
        if resume.plan is None:
            raise ValueError(
                f"checkpoint dir {checkpoint_dir} holds a single-host "
                "checkpoint — fit_gang_source resumes only coordinated "
                "(per-host shard) checkpoints; use fit_source(resume_from=)")
        plan = resume.plan
        done = resume.step
        tree = resume.tree
        state = trainer.resume_state(
            tree["params"], tree.get("opt_state"),
            step=int(np.asarray(tree["step"])),
            batch_stats=tree.get("batch_stats"),
            constants=tree.get("constants"))
    else:
        plan = ElasticPlan.fresh(world, seed)
        done, state = 0, None
    if world > plan.orig_world:
        raise ValueError(
            f"world={world} exceeds the run's frozen stream count "
            f"(orig_world={plan.orig_world}): extra hosts would have no "
            "virtual stream to serve and no shard to ACK, wedging every "
            "commit — relaunch the gang with world <= orig_world (clamp "
            "in the launcher)")
    remaining = total_steps - done
    if state is not None and remaining <= 0:
        return state
    dp = trainer.mesh.data_parallel_size()
    stream = ElasticStreamSet(
        source, batch_size, plan, rank, world, epochs=epochs,
        drop_remainder=drop_remainder, shuffle_rows=shuffle_rows,
        shuffle_window=shuffle_window, multiple_of=dp, columns=columns,
        state_history=max(64, checkpoint_every + 8))
    ck = AsyncCheckpointer(
        checkpoint_dir, process_index=rank, process_count=world,
        coordinated=True, sharding=trainer.sharding_manifest(),
        meta={"orig_world": plan.orig_world, "seed": int(seed)},
        run_id=getattr(gang, "run_id", None))
    shim = _ElasticLoaderCheckpointer(ck, stream, base_step=done)
    it = iter(stream)
    try:
        if state is None:
            first = next(it)
            state = trainer.init_state(first, jax.random.PRNGKey(seed),
                                       init_params=init_params)

            def chain(head, rest):
                yield head
                yield from rest

            batch_iter: Iterator[dict] = chain(first, it)
        else:
            batch_iter = it
        out = trainer.fit(state, batch_iter, max_steps=remaining,
                          scan_chunk=1, log_every=log_every,
                          checkpointer=shim,
                          checkpoint_every=checkpoint_every, gang=gang,
                          callback=callback)
    except BaseException:
        # a Preempted/GangAborted (or crash) exit wins over any pending
        # background-write error — but still release the writer thread
        stream.close()
        try:
            ck.close()
        except Exception:  # noqa: BLE001
            pass
        raise
    # clean completion: close() surfaces a failed final shard write — the
    # caller must NOT believe the last checkpoint landed when it didn't
    stream.close()
    ck.close()
    if int(out.step) < total_steps:
        # finite-epochs stream dried before total_steps: THIS rank sends
        # no further ACKs, so no commit past its last one can ever form —
        # the other ranks' later steps are unrestorable. Loud, not silent.
        import json as _json
        import logging as _logging

        _logging.getLogger("synapseml_tpu.models.trainer").warning(
            _json.dumps({
                "event": "gang_stream_exhausted_early",
                "rank": int(rank), "step": int(out.step),
                "total_steps": int(total_steps),
                "hint": "commits beyond this rank's last ACK cannot "
                        "complete; size total_steps to the dataset or use "
                        "epochs=None"}))
    return out


def fit_arrays(trainer: "Trainer", data: dict, *, batch_size: int, total_steps: int,
               seed: int, init_params=None, init_batch_stats=None,
               scan_chunk: int = 8, checkpointer=None,
               checkpoint_every: int = 0, shard_rows: int | None = None) -> "TrainState":
    """Shared estimator fit loop over host arrays — a thin wrapper that puts
    the arrays behind a :class:`synapseml_tpu.data.MemorySource` and
    delegates to :func:`fit_source`, so in-memory and out-of-core training
    share ONE batch-assembly/shuffle/prefetch plane. ``shard_rows`` controls
    the virtual shard layout (None = one shard): matching an on-disk layout
    row-for-row makes this stream bit-identical to ``fit_source`` over the
    same rows under the same seed."""
    from ..data.source import MemorySource

    n = next(iter(data.values())).shape[0]
    return fit_source(trainer, MemorySource(data, shard_rows=shard_rows),
                      batch_size=batch_size, total_steps=total_steps,
                      seed=seed, init_params=init_params,
                      init_batch_stats=init_batch_stats, scan_chunk=scan_chunk,
                      checkpointer=checkpointer,
                      checkpoint_every=checkpoint_every,
                      drop_remainder=n >= batch_size)
