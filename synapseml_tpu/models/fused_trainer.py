"""Horizontally fused training arrays: ONE jitted step trains N trials.

The HFTA result (PAPERS.md, arXiv:2102.02344): hyperparameter trials of the
same architecture differ only in scalar knobs, so fusing N model replicas
along a leading "trial" axis recovers close to an order of magnitude of
accelerator utilization versus running the trials back-to-back (or on a
thread pool, where the device serializes N separate dispatch streams and
each distinct config pays its own XLA compile).

Design:

* **Stacked state.** Params / optimizer state / step counters carry a
  leading trial axis sized to a *rung* of the trial-count ladder
  (:func:`core.batching.default_trial_bucketer`), so sweeps of any size
  compile at most ladder-many step executables — the TVM lesson
  (arXiv:1802.04799): pay compilation once, amortize over many executions.
* **Hyperparameters as data.** Per-trial learning rate / weight decay /
  Adam betas / grad-clip ride inside the optimizer state via
  ``optax.inject_hyperparams`` (loss-side knobs like label smoothing ride
  in a ``hparams`` subtree), so N configs share ONE executable acquired
  through the process-wide :class:`core.batching.CompiledCache` — never N.
  The injected math is the SAME ``clip_by_global_norm -> adamw`` chain the
  serial :class:`Trainer` builds, so fused and serial runs agree to f32
  rounding (the parity suite in ``tests/test_fused_automl.py``).
* **One shared batch.** Every step consumes one batch from the PR-5
  :class:`data.DataLoader` (loaded and device-put once) broadcast across
  trials via ``vmap(in_axes=None)`` — no per-trial input pipelines.
* **Early stop without recompiles.** A per-trial ``active`` mask zeroes
  dead trials' updates inside the same executable; :meth:`compact` at rung
  boundaries gathers survivors into a smaller stacked state (a new rung =
  at most one more ladder compile).

Scope: constant learning rate (per-trial schedules would need
count-dependent hyperparams), no gradient accumulation / layer freezing /
batch_stats — sweeps needing those fall back to the serial path.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Iterable, Iterator

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..core import batching as cb
from ..core.hpo_metrics import HPO_ARRAY_METRICS as _HPO_METRICS
from ..parallel.mesh import MeshContext

__all__ = ["FusedTrainer", "FUSED_OPT_HPARAMS", "FUSED_LOSS_HPARAMS",
           "fused_fit_source", "fused_fit_arrays"]

# scalar knobs that become traced optimizer-state leaves (one executable
# serves any values) vs loss-side knobs threaded into the vmapped loss
FUSED_OPT_HPARAMS = ("learning_rate", "weight_decay", "b1", "b2", "grad_clip")
FUSED_LOSS_HPARAMS = ("label_smoothing",)


def _fused_tx(learning_rate, weight_decay, b1, b2, grad_clip):
    """EXACTLY the serial Trainer's constant-lr optimizer chain
    (``_make_optimizer`` with no freeze/accum) — the parity guarantee
    rests on the two paths sharing this formula."""
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(learning_rate, b1=b1, b2=b2, weight_decay=weight_decay))


def _batch_shape_key(batch: dict) -> tuple:
    return tuple(sorted(
        (k, tuple(np.shape(v)), str(getattr(v, "dtype", None)
                                    or np.asarray(v).dtype))
        for k, v in batch.items()))


class FusedTrainer:
    """Trains ``len(trials)`` hyperparameter variants of one module inside
    a single jitted step.

    ``trials``: one dict per trial; keys may override
    :data:`FUSED_OPT_HPARAMS`, :data:`FUSED_LOSS_HPARAMS` and ``seed``
    (the per-trial init/dropout PRNG seed). Unset keys inherit from
    ``cfg`` (a :class:`TrainerConfig`); ``label_smoothing`` defaults 0 —
    at 0 the fused loss is bit-for-bit the serial ``cross_entropy_loss``.

    State is a plain dict pytree (like the serial step's): ``params`` /
    ``opt_state`` / ``step`` / ``active`` / ``hparams``, every leaf with a
    leading trial-rung axis.
    """

    def __init__(self, module, mesh_ctx: MeshContext, cfg, trials: list[dict],
                 loss_fn: Callable[[Any, dict], jax.Array] | None = None,
                 trial_bucketer: cb.ShapeBucketer | None = None):
        if not trials:
            raise ValueError("FusedTrainer needs at least one trial")
        if cfg.grad_accum > 1 or cfg.freeze_predicate is not None:
            raise ValueError(
                "fused training arrays do not support grad_accum/freezing — "
                "run those configs on the serial Trainer path")
        if cfg.lr_schedule != "constant":
            raise ValueError(
                "fused training arrays support constant learning rates only "
                f"(got lr_schedule={cfg.lr_schedule!r}); schedules need "
                "count-dependent hyperparams — use the serial path")
        base = {"learning_rate": cfg.learning_rate,
                "weight_decay": cfg.weight_decay, "b1": cfg.b1, "b2": cfg.b2,
                "grad_clip": cfg.grad_clip, "label_smoothing": 0.0,
                # None = inherit init_state's default_seed (the sweep seed),
                # matching fit_source's PRNGKey(seed) init on the serial arm
                "seed": None}
        allowed = set(base)
        merged = []
        for i, t in enumerate(trials):
            unknown = set(t) - allowed
            if unknown:
                raise ValueError(
                    f"trial {i} has non-fusable keys {sorted(unknown)}; "
                    f"fusable scalar hyperparameters: {sorted(allowed)}")
            if loss_fn is not None:
                overridden = set(t) & set(FUSED_LOSS_HPARAMS)
                if overridden:
                    # a custom loss_fn(variables, batch) has no hyperparameter
                    # argument — the override would be silently discarded and
                    # identical trials reported as distinct configs
                    raise ValueError(
                        f"trial {i} sets {sorted(overridden)} but a custom "
                        "loss_fn is in use, which cannot receive loss-side "
                        "hyperparameters; drop the override or fold it into "
                        "loss_fn")
            merged.append({**base, **t})
        self.module = module
        self.mesh = mesh_ctx
        self.cfg = cfg
        self.trials = merged
        self.n_trials = len(merged)
        self._loss_fn = loss_fn
        self._bucketer = trial_bucketer or cb.default_trial_bucketer()
        self._tx = optax.inject_hyperparams(_fused_tx)(
            learning_rate=cfg.learning_rate, weight_decay=cfg.weight_decay,
            b1=cfg.b1, b2=cfg.b2, grad_clip=cfg.grad_clip)
        # slot -> original trial index (compact() drops dead slots)
        self.slot_ids: list[int] = []
        self._active_host = np.zeros(0, np.float32)
        self._metrics: list[dict] = []

    # ---- bookkeeping ----
    @property
    def rung(self) -> int:
        return len(self._active_host)

    @property
    def n_live(self) -> int:
        return int(self._active_host.sum())

    def live_trials(self) -> list[int]:
        return [tid for s, tid in enumerate(self.slot_ids)
                if self._active_host[s] > 0]

    def _model_inputs(self, batch: dict) -> dict:
        drop = {"labels", "label", "mask", "_valid"}
        return {k: v for k, v in batch.items() if k not in drop}

    def _hparam_column(self, key: str, slot_trials: list[int]) -> jnp.ndarray:
        return jnp.asarray([self.trials[t][key] for t in slot_trials],
                           jnp.float32)

    # ---- loss (serial cross_entropy_loss + optional label smoothing) ----
    def _trial_loss(self, params, batch: dict, label_smoothing) -> jax.Array:
        if self._loss_fn is not None:
            return self._loss_fn({"params": params}, batch)
        logits = self.module.apply({"params": params},
                                   **self._model_inputs(batch))
        labels = batch.get("labels", batch.get("label"))
        mask = batch.get("_valid")
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(
            logp, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
        # at label_smoothing == 0 this is EXACTLY cross_entropy_loss
        per = (1.0 - label_smoothing) * nll \
            + label_smoothing * (-jnp.mean(logp, axis=-1))
        if mask is not None:
            return jnp.sum(per * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        return jnp.mean(per)

    # ---- state init ----
    def init_state(self, example_batch: dict, default_seed: int = 0) -> dict:
        """Stacked state for every trial, padded up to the trial-count rung
        (pad slots replicate trial 0 with ``active=0`` — they never train).
        Trials without an explicit ``seed`` init from ``default_seed`` — the
        sweep seed, so a serial ``fit_source`` run under the same seed inits
        identically."""
        rung = self._bucketer.bucket_for(self.n_trials)
        slot_trials = list(range(self.n_trials)) \
            + [0] * (rung - self.n_trials)
        inputs = self._model_inputs(example_batch)
        cache = cb.get_compiled_cache()
        token = cb.instance_token(self)
        module = self.module

        def build():
            from flax.core import meta

            def init_one(key):
                return meta.unbox(module.init(key, **inputs)["params"])

            return jax.jit(jax.vmap(init_one))

        init_fn = cache.get("fused_init",
                            (rung,) + _batch_shape_key(example_batch),
                            build, instance=token)
        keys = jnp.stack([
            jax.random.PRNGKey(int(default_seed
                                   if self.trials[t]["seed"] is None
                                   else self.trials[t]["seed"]))
            for t in slot_trials])
        with self.mesh.scope():
            params = init_fn(keys)
        tx = self._tx

        def _build_opt():
            return jax.jit(jax.vmap(tx.init))

        with self.mesh.scope():
            opt_state = cache.get("fused_opt_init", (rung,), _build_opt,
                                  instance=token)(params)
        hp = dict(opt_state.hyperparams)
        for key in FUSED_OPT_HPARAMS:
            hp[key] = self._hparam_column(key, slot_trials)
        opt_state = opt_state._replace(hyperparams=hp)
        self.slot_ids = slot_trials[: self.n_trials]
        self._active_host = np.asarray(
            [1.0] * self.n_trials + [0.0] * (rung - self.n_trials),
            np.float32)
        _HPO_METRICS.get()["active"].set(self.n_live, engine="fused_trainer")
        return {
            "params": params, "opt_state": opt_state,
            "step": jnp.zeros((rung,), jnp.int32),
            "active": jnp.asarray(self._active_host),
            "hparams": {
                "label_smoothing": self._hparam_column("label_smoothing",
                                                       slot_trials)},
        }

    # ---- the one fused step ----
    def _build_step(self):
        tx = self._tx
        trial_loss = self._trial_loss

        def build():
            def one_trial(tstate, batch, ls):
                loss, grads = jax.value_and_grad(
                    lambda p: trial_loss(p, batch, ls))(tstate["params"])
                updates, new_opt = tx.update(grads, tstate["opt_state"],
                                             tstate["params"])
                new_params = optax.apply_updates(tstate["params"], updates)
                return (new_params, new_opt, loss.astype(jnp.float32),
                        optax.global_norm(grads).astype(jnp.float32))

            def step(state, batch):
                new_params, new_opt, loss, gnorm = jax.vmap(
                    one_trial,
                    in_axes=({"params": 0, "opt_state": 0}, None, 0))(
                        {"params": state["params"],
                         "opt_state": state["opt_state"]},
                        batch, state["hparams"]["label_smoothing"])
                live = state["active"] > 0.0

                def keep(new, old):
                    m = live.reshape(live.shape + (1,) * (jnp.ndim(new) - 1))
                    return jnp.where(m, new, old)

                metrics = {"loss": jnp.where(live, loss, jnp.nan),
                           "grad_norm": jnp.where(live, gnorm, 0.0)}
                return {"params": jax.tree.map(keep, new_params,
                                               state["params"]),
                        "opt_state": jax.tree.map(keep, new_opt,
                                                  state["opt_state"]),
                        "step": state["step"] + live.astype(jnp.int32),
                        "active": state["active"],
                        "hparams": state["hparams"]}, metrics

            return jax.jit(step, donate_argnums=(0,))

        return build

    def train_step(self, state: dict, batch: dict) -> tuple[dict, dict]:
        """One fused optimizer step for every live trial. The executable is
        acquired through the shared CompiledCache keyed on (trial rung,
        batch shape) — any number of configs rides ladder-many compiles."""
        fn = cb.get_compiled_cache().get(
            "fused_train_step", (self.rung,) + _batch_shape_key(batch),
            self._build_step(), instance=cb.instance_token(self))
        placed = self.mesh.shard_batch(batch)
        with self.mesh.scope():
            return fn(state, placed)

    # ---- early-stop masking + rung compaction ----
    def deactivate(self, state: dict, trial_ids: Iterable[int]) -> dict:
        """Freeze the given trials (by ORIGINAL trial index): their updates
        are masked to zero inside the SAME executable — no recompile."""
        doomed = set(trial_ids)
        for slot, tid in enumerate(self.slot_ids):
            if tid in doomed:
                self._active_host[slot] = 0.0
        _HPO_METRICS.get()["active"].set(self.n_live, engine="fused_trainer")
        return dict(state, active=jnp.asarray(self._active_host))

    def compact(self, state: dict) -> dict:
        """Gather surviving trials into the smallest trial-count rung that
        holds them (rung boundaries only — same rung is a no-op, so sweeps
        compile at most ladder-many step executables total). Dead trials'
        states are dropped; :meth:`unstack` them first if needed."""
        keep = [s for s in range(len(self.slot_ids))
                if self._active_host[s] > 0]
        if not keep:
            raise RuntimeError("compact() with zero live trials — "
                               "the sweep is already finished")
        new_rung = self._bucketer.bucket_for(len(keep))
        if new_rung == self.rung:
            return state
        idx = keep + [keep[0]] * (new_rung - len(keep))

        def build():
            def gather(st, ix):
                return jax.tree.map(lambda x: jnp.take(x, ix, axis=0), st)

            return jax.jit(gather)

        fn = cb.get_compiled_cache().get(
            "fused_compact", (self.rung, new_rung), build,
            instance=cb.instance_token(self))
        core = {k: state[k] for k in ("params", "opt_state", "step",
                                      "hparams")}
        with self.mesh.scope():
            core = fn(core, jnp.asarray(idx, jnp.int32))
        self.slot_ids = [self.slot_ids[s] for s in keep]
        self._active_host = np.asarray(
            [1.0] * len(keep) + [0.0] * (new_rung - len(keep)), np.float32)
        _HPO_METRICS.get()["compactions"].inc(engine="fused_trainer")
        return dict(core, active=jnp.asarray(self._active_host))

    # ---- results ----
    def unstack(self, state: dict) -> dict[int, Any]:
        """Per-trial :class:`TrainState` views (host-fetched once), keyed by
        ORIGINAL trial index. Early-stopped trials still occupying a slot
        return their frozen state; trials dropped by :meth:`compact` are
        absent."""
        from .trainer import TrainState

        host = jax.device_get({"params": state["params"],
                               "opt_state": state["opt_state"],
                               "step": state["step"]})
        out = {}
        for slot, tid in enumerate(self.slot_ids):
            pick = lambda x, s=slot: x[s]  # noqa: E731
            out[tid] = TrainState(
                params=jax.tree.map(pick, host["params"]),
                opt_state=jax.tree.map(pick, host["opt_state"]),
                step=host["step"][slot])
        return out

    # ---- loop ----
    def fit(self, state: dict, batch_iter: Iterator[dict], max_steps: int,
            *, early_stop: Callable[[int, dict], Iterable[int]] | None = None,
            check_every: int = 25, compact_on_stop: bool = True) -> dict:
        """Drive the fused array over a shared batch stream.

        ``early_stop(step, {trial_id: loss})`` runs every ``check_every``
        steps over the live trials' current losses and returns trial ids to
        stop; stopped trials are masked out immediately and survivors are
        gathered to a smaller rung when they fit one
        (``compact_on_stop``)."""
        m = _HPO_METRICS.get()
        it = iter(batch_iter)
        done = object()
        t_start = time.perf_counter()
        trial_steps = 0
        for i in range(max_steps):
            batch = next(it, done)
            if batch is done:
                break
            t0 = time.perf_counter()
            state, metrics = self.train_step(state, batch)
            m["step_ms"].observe((time.perf_counter() - t0) * 1e3,
                                 engine="fused_trainer")
            m["steps"].inc(engine="fused_trainer")
            trial_steps += self.n_live
            if early_stop is not None and (i + 1) % check_every == 0:
                losses = np.asarray(metrics["loss"])
                live_losses = {tid: float(losses[s])
                               for s, tid in enumerate(self.slot_ids)
                               if self._active_host[s] > 0}
                doomed = list(early_stop(i + 1, live_losses))
                if doomed:
                    state = self.deactivate(state, doomed)
                    if self.n_live == 0:
                        break
                    if compact_on_stop:
                        state = self.compact(state)
        wall = max(time.perf_counter() - t_start, 1e-9)
        m["trials_per_sec"].set(trial_steps / wall, engine="fused_trainer")
        self._metrics.append({"trial_steps": trial_steps, "wall_s": wall,
                              "live": self.n_live})
        return state

    @property
    def metrics(self) -> list[dict]:
        return self._metrics


def fused_fit_source(trainer: FusedTrainer, source, *, batch_size: int,
                     total_steps: int, seed: int, epochs: int | None = None,
                     drop_remainder: bool = True, shuffle_rows: str = "full",
                     shuffle_window: int = 4096, prefetch: int = 2,
                     columns: list | None = None,
                     early_stop=None, check_every: int = 25) -> dict:
    """Fused-array fit over a :class:`data.ShardedSource`: ONE deterministic
    :class:`data.DataLoader` stream (seeded shuffles, bucket-ladder padding,
    background prefetch, device-put once per batch) shared by every trial —
    the same loader configuration ``fit_source`` uses, so a serial run under
    the same seed consumes the identical batch sequence (the parity-suite
    contract)."""
    from ..data import DataLoader

    loader = DataLoader(
        source, batch_size, seed=seed, epochs=epochs,
        drop_remainder=drop_remainder, shuffle_rows=shuffle_rows,
        shuffle_window=shuffle_window,
        multiple_of=trainer.mesh.data_parallel_size(), prefetch=prefetch,
        columns=columns)
    it = iter(loader)
    try:
        first = next(it)
        state = trainer.init_state(first, default_seed=seed)

        def chain():
            yield first
            yield from it

        return trainer.fit(state, chain(), max_steps=total_steps,
                           early_stop=early_stop, check_every=check_every)
    finally:
        loader.close()


def fused_fit_arrays(trainer: FusedTrainer, data: dict, *, batch_size: int,
                     total_steps: int, seed: int, **kwargs) -> dict:
    """In-memory twin of :func:`fused_fit_source` (mirrors
    ``trainer.fit_arrays``: same MemorySource + drop_remainder policy, so
    fused and serial arms see bit-identical batch streams)."""
    from ..data.source import MemorySource

    n = next(iter(data.values())).shape[0]
    kwargs.setdefault("drop_remainder", n >= batch_size)
    return fused_fit_source(trainer, MemorySource(data),
                            batch_size=batch_size, total_steps=total_steps,
                            seed=seed, **kwargs)
