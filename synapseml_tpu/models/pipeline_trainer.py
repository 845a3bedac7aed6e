"""Pipeline-stage trainer: GPipe schedule inside the one jitted train step.

``parallel/pipeline.py`` has carried the full schedule family (GPipe,
scattered-io, interleaved) since the mesh-axis work landed, but nothing
TRAINED through it — every trainer assumed the whole model applies on every
device. This module closes that gap (ROADMAP open item 1c, MPMD pipeline
parallelism per arXiv:2412.14374's framing): a model bigger than one host
declares its stage split — the partition rule table's ``stage_regex``
names the cut points — and trains with each stage's weights AND optimizer
state living only on that stage's ``pipe``-axis coordinate.

Layout contract (the GPipe chainability rule): the model factors into

* ``embed_fn(shared_params, microbatch) -> x``   (runs replicated),
* ``stage_fn(stage_params, x) -> x``             (the repeated block —
  every stage structurally identical; rides the pipeline ring),
* ``head_loss_fn(shared_params, x_out, microbatch) -> scalar loss``
  (replicated; owns labels/masking).

Params assemble as ``{"shared": <embed+head tree>, "stages": <leading-
stage-axis stack>}`` — either pre-split, or a flat tree cut by
``cfg.partition_rules.stage_regex`` via
:func:`~synapseml_tpu.parallel.partition.split_stage_params`. Everything
else — the optax formula, fit/fit_source/fit_arrays loops, checkpoint
resume, ZeRO optimizer-state sharding — is inherited from
:class:`~synapseml_tpu.models.trainer.Trainer` unchanged, so pipeline
training composes with the rest of the sharding plane for free.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..parallel.mesh import MeshContext
from .trainer import Trainer, TrainerConfig, TrainState, _make_optimizer

__all__ = ["PipelineTrainer"]


class PipelineTrainer(Trainer):
    """Trains a stage-split model over the mesh's ``pipe`` axis.

    Drop-in for :func:`~synapseml_tpu.models.trainer.fit_source` /
    ``fit_arrays`` — pass the assembled (or flat + ``stage_regex``) param
    tree as ``init_params``. On a mesh whose ``pipe`` axis is absent or
    size 1 the schedule falls back to the sequential stage chain
    (``pipeline_sharded``'s fallback), which is also the parity reference
    the tests hold the 2-stage mesh to.
    """

    def __init__(self, mesh_ctx: MeshContext, cfg: TrainerConfig, *,
                 stage_fn: Callable[[Any, Any], Any],
                 head_loss_fn: Callable[[Any, Any, dict], jax.Array],
                 embed_fn: Callable[[Any, dict], Any] | None = None,
                 n_micro: int = 4, axis_name: str = "pipe",
                 remat: bool = False, io: str = "replicated"):
        super().__init__(None, mesh_ctx, cfg)
        self.stage_fn = stage_fn
        self.embed_fn = embed_fn
        self.head_loss_fn = head_loss_fn
        self.n_micro = int(n_micro)
        self.axis_name = axis_name
        self.remat = remat
        self.io = io
        if self.n_micro < 1:
            raise ValueError(f"n_micro must be >= 1, got {n_micro}")
        # Trainer._step_fn routes through self._loss_fn when set — the
        # whole fit/scan/checkpoint machinery is reused untouched
        self._loss_fn = self._pipeline_loss

    # ---- param assembly ---------------------------------------------------
    def _assemble(self, init_params) -> dict:
        from ..parallel import partition as pp
        from ..parallel.pipeline import stack_stage_params

        if isinstance(init_params, dict) and "stages" in init_params:
            stages = init_params["stages"]
            if isinstance(stages, (list, tuple)):
                stages = stack_stage_params(list(stages))
            return {"shared": init_params.get("shared") or {},
                    "stages": stages}
        rules = self.cfg.partition_rules
        if rules is None or rules.stage_regex is None:
            raise ValueError(
                "PipelineTrainer needs either init_params={'shared': ..., "
                "'stages': [per-stage trees] | stacked} or a flat tree "
                "plus cfg.partition_rules.stage_regex naming the cut "
                "points")
        shared, stacked = pp.stack_stages(init_params, rules.stage_regex)
        return {"shared": shared, "stages": stacked}

    def _n_stages(self, params: dict) -> int:
        return int(jax.tree.leaves(params["stages"])[0].shape[0])

    # ---- placement (overrides the flat-tree rule placement) ---------------
    def _rule_place_params(self, params):
        from ..parallel import partition as pp

        specs = pp.pipeline_param_specs(self.cfg.partition_rules, params,
                                        axis_name=self.axis_name)
        self._param_shardings = pp.tree_shardings(self.mesh, specs, params)
        return pp.place_tree(params, self._param_shardings)

    def _rule_place_opt_state(self, params, opt_state):
        from ..parallel import partition as pp

        skel = jax.eval_shape(lambda: opt_state)
        specs = pp.pipeline_opt_specs(self.cfg.partition_rules, skel,
                                      self.mesh, zero=self.cfg.zero_shard,
                                      axis_name=self.axis_name)
        self._opt_shardings = pp.tree_shardings(self.mesh, specs, skel)
        placed = pp.place_tree(opt_state, self._opt_shardings)
        pp.emit_shard_metrics(params, placed, self.mesh,
                              engine="pipeline_trainer")
        return placed

    def checkpoint_sharding_fn(self):
        from ..parallel import partition as pp

        rules = self.cfg.partition_rules or pp.PartitionRules()
        return pp.checkpoint_sharding_fn(rules, self.mesh,
                                         zero=self.cfg.zero_shard,
                                         pipeline_axis=self.axis_name)

    # ---- state init -------------------------------------------------------
    def init_state(self, example_batch: dict, rng: jax.Array | None = None,
                   init_params=None, init_batch_stats=None) -> TrainState:
        if init_params is None:
            raise ValueError(
                "PipelineTrainer has no module to init from — pass the "
                "stage-split (or flat + stage_regex) param tree as "
                "init_params")
        params = self._assemble(init_params)
        n_stages = self._n_stages(params)
        pipe = self.mesh.axis_sizes.get(self.axis_name, 1)
        if pipe > 1 and n_stages != pipe:
            raise ValueError(
                f"{n_stages} stages cannot split over a {self.axis_name!r} "
                f"axis of size {pipe} (one stage per coordinate)")
        params = self._rule_place_params(params)
        self._tx = _make_optimizer(self.cfg, params)
        opt_state, step = self._place_counters(
            self._rule_place_opt_state(params, self._tx.init(params)), 0)
        return TrainState(params=params, opt_state=opt_state, step=step,
                          batch_stats=None)

    # ---- the pipelined loss (consumed by Trainer._step_fn) ----------------
    def _pipeline_loss(self, variables, batch: dict) -> jax.Array:
        from ..parallel.pipeline import pipeline_sharded

        params = variables["params"]
        batch = {k: v for k, v in batch.items()}
        n_rows = int(jax.tree.leaves(batch)[0].shape[0])
        if n_rows % self.n_micro:
            raise ValueError(
                f"batch of {n_rows} rows does not split into "
                f"{self.n_micro} microbatches — pick batch_size a "
                "multiple of n_micro")
        mb = n_rows // self.n_micro
        micro = jax.tree.map(
            lambda x: x.reshape((self.n_micro, mb) + x.shape[1:]), batch)
        shared = params.get("shared", {})
        if self.embed_fn is not None:
            x0 = jax.vmap(lambda b: self.embed_fn(shared, b))(micro)
        else:
            x0 = micro
        outs = pipeline_sharded(self.mesh, self.stage_fn, params["stages"],
                                x0, axis_name=self.axis_name,
                                remat=self.remat, io=self.io)
        losses = jax.vmap(lambda o, b: self.head_loss_fn(shared, o, b))(
            outs, micro)
        return jnp.mean(losses)
