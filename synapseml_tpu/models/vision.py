"""DeepVisionClassifier / DeepVisionModel — vision transfer learning on the mesh.

Reference: ``dl/DeepVisionClassifier.py:31-268`` (horovod TorchEstimator with
torchvision backbones) + ``dl/DeepVisionModel.py`` predict wrapper. Rebuilt:
Flax ViT/ResNet backbones trained by the GSPMD Trainer; images arrive as an
image column ([H,W,C] arrays) produced by image.ImageTransformer.
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np

from ..core import DataFrame, Estimator, Model
from ..core import batching as cb
from ..core.params import ComplexParam, Param, TypeConverters
from ..parallel.mesh import MeshConfig, create_mesh
from .flax_nets.resnet import resnet18, resnet50, resnet_tiny
from .flax_nets.vit import ViTClassifier, vit_b16, vit_tiny
from .trainer import (Trainer, TrainerConfig,
                      _fit_with_optional_checkpointing, fit_arrays, plan_fit)

__all__ = ["DeepVisionClassifier", "DeepVisionModel"]


def _build_module(backbone: str, num_classes: int, arch_spec=None):
    """(module, has_batch_stats). ``backbone`` is a preset name or a local HF
    checkpoint dir (handled by the caller via ``arch_spec`` from
    convert_hf.pretrained_vision)."""
    if arch_spec is not None:
        kind, info = arch_spec
        if kind == "vit":
            return ViTClassifier(info["cfg"], num_classes=num_classes,
                                 patch=info["patch"]), False
        from .flax_nets.resnet import ResNet

        return ResNet(num_classes=num_classes, **info), True
    if backbone == "vit_b16":
        return ViTClassifier(vit_b16(), num_classes=num_classes, patch=16), False
    if backbone == "vit_tiny":
        return ViTClassifier(vit_tiny(), num_classes=num_classes, patch=8), False
    if backbone == "resnet50":
        return resnet50(num_classes=num_classes), True
    if backbone == "resnet18":
        return resnet18(num_classes=num_classes), True
    if backbone == "resnet_tiny":
        return resnet_tiny(num_classes=num_classes), True
    raise ValueError(f"unknown backbone {backbone!r}; "
                     "have vit_b16|vit_tiny|resnet50|resnet18|resnet_tiny "
                     "or a local HF checkpoint directory")


class _VisionParams:
    image_col = Param("image_col", "input image column ([H,W,C] float arrays)",
                      default="image")
    label_col = Param("label_col", "label column", default="label")
    prediction_col = Param("prediction_col", "argmax output column", default="prediction")
    scores_col = Param("scores_col", "softmax scores column", default="scores")
    backbone = Param("backbone", "vit_b16|vit_tiny|resnet50|resnet18|resnet_tiny",
                     default="resnet_tiny")
    num_classes = Param("num_classes", "number of classes", default=2,
                        converter=TypeConverters.to_int)
    batch_size = Param("batch_size", "global batch size", default=32,
                       converter=TypeConverters.to_int)


class DeepVisionClassifier(Estimator, _VisionParams):
    feature_name = "deep_learning"

    learning_rate = Param("learning_rate", "peak lr", default=1e-3,
                          converter=TypeConverters.to_float)
    num_train_epochs = Param("num_train_epochs", "epochs", default=2,
                             converter=TypeConverters.to_int)
    max_steps = Param("max_steps", "hard step cap (-1 = epochs)", default=-1,
                      converter=TypeConverters.to_int)
    seed = Param("seed", "init seed", default=0, converter=TypeConverters.to_int)
    checkpoint_dir = Param("checkpoint_dir", "when set, write async training "
                           "checkpoints here (reference pytorch-lightning "
                           "ModelCheckpoint role); resume via "
                           "parallel.restore_checkpoint + Trainer.resume_state",
                           default=None)
    checkpoint_every = Param("checkpoint_every", "checkpoint every N optimizer "
                             "steps — the fused scan chunk shrinks to N "
                             "when smaller (0 = only the final state)", default=0,
                             converter=TypeConverters.to_int)
    checkpoint_keep = Param("checkpoint_keep", "retain the most recent K "
                            "checkpoints", default=3,
                            converter=TypeConverters.to_int)
    mesh_config = ComplexParam("mesh_config", "MeshConfig override", default=None)

    def _fit(self, df: DataFrame) -> "DeepVisionModel":
        from .convert_hf import is_checkpoint_dir

        arch_spec = None
        init_params = init_stats = None
        if is_checkpoint_dir(self.get("backbone")):
            # local HF/torchvision-format checkpoint (the reference's
            # torchvision-backbone transfer path, dl/DeepVisionClassifier.py)
            from .convert_hf import pretrained_vision

            kind, info, variables = pretrained_vision(
                self.get("backbone"), num_classes=self.get("num_classes"),
                seed=self.get("seed"))
            arch_spec = (kind, info)
            init_params = variables["params"]
            init_stats = variables.get("batch_stats")
        module, has_bn = _build_module(self.get("backbone"), self.get("num_classes"),
                                       arch_spec)
        mesh = create_mesh(self.get("mesh_config") or MeshConfig())

        labels = df.collect_column(self.get("label_col")).astype(np.int32)
        bs, total = plan_fit(len(labels), self.get("batch_size"),
                             self.get("num_train_epochs"), self.get("max_steps"))
        images = np.stack(list(df.collect_column(self.get("image_col")))).astype(np.float32)

        trainer = Trainer(module, mesh,
                          TrainerConfig(learning_rate=self.get("learning_rate"),
                                        total_steps=total, lr_schedule="cosine",
                                        warmup_steps=max(total // 10, 1)),
                          has_batch_stats=has_bn)
        state = _fit_with_optional_checkpointing(
            self, lambda ck, every: fit_arrays(
                trainer, {"x": images, "labels": labels},
                batch_size=bs, total_steps=total, seed=self.get("seed"),
                init_params=init_params, init_batch_stats=init_stats,
                checkpointer=ck, checkpoint_every=every))

        return DeepVisionModel(
            model_params=jax.tree.map(np.asarray, state.params),
            batch_stats=(jax.tree.map(np.asarray, state.batch_stats)
                         if state.batch_stats is not None else None),
            arch_spec=arch_spec,
            backbone=self.get("backbone"), num_classes=self.get("num_classes"),
            image_col=self.get("image_col"), prediction_col=self.get("prediction_col"),
            scores_col=self.get("scores_col"), batch_size=self.get("batch_size"),
            train_metrics=trainer.metrics,
        )


class DeepVisionModel(Model, _VisionParams):
    feature_name = "deep_learning"

    model_params = ComplexParam("model_params", "trained parameter pytree")
    batch_stats = ComplexParam("batch_stats", "BN running stats", default=None)
    arch_spec = ComplexParam("arch_spec", "(kind, info) for pretrained-dir fits",
                             default=None)
    mesh_config = ComplexParam("mesh_config", "MeshConfig for sharded inference",
                               default=None)
    train_metrics = ComplexParam("train_metrics", "loss/throughput trace", default=None)

    def __init__(self, **kw):
        super().__init__(**kw)
        self._apply_fn = None

    def _post_load(self):
        self._apply_fn = None
        cb.invalidate_token(self)

    _APPLY_KEYS = frozenset({"model_params", "batch_stats", "arch_spec",
                             "backbone", "num_classes", "mesh_config"})

    def set(self, **kw):
        out = super().set(**kw)
        if self._APPLY_KEYS & kw.keys():
            self._apply_fn = None  # cached closure captured the old values
            cb.invalidate_token(self)
        return out

    def _get_apply(self):
        """Returns ``run_for(bucket, img_shape)`` — per-bucket executables
        via the process-wide CompiledCache."""
        if self._apply_fn is None:
            module, has_bn = _build_module(self.get("backbone"), self.get("num_classes"),
                                           self.get("arch_spec"))
            variables = {"params": self.get("model_params")}
            if self.get("batch_stats") is not None:
                variables["batch_stats"] = self.get("batch_stats")
            mesh = None
            if self.get("mesh_config") is not None:
                # batch-sharded inference; explainer perturbation batches ride
                # this path too (SURVEY §7 step 8)
                mesh = create_mesh(self.get("mesh_config"))
                variables = jax.tree.map(
                    lambda v: jax.device_put(np.asarray(v), mesh.replicated()),
                    variables)

            def apply_fn(variables, x):
                logits = module.apply(variables, x)
                return jax.nn.softmax(logits, axis=-1)

            def run_for(bucket: int, img_shape: tuple):
                def build():
                    jitted = jax.jit(apply_fn)
                    if mesh is not None:
                        def run(x, _j=jitted, _m=mesh):
                            with _m.scope():
                                return _j(variables, _m.shard_batch(x))
                        return run
                    return lambda x: jitted(variables, x)

                return cb.get_compiled_cache().get(
                    "deep_vision_model", (bucket,) + tuple(img_shape), build,
                    instance=cb.instance_token(self), dtype="float32")

            self._module_has_bn = has_bn
            self._mesh = mesh
            self._apply_fn = run_for
        return self._apply_fn

    def _transform(self, df: DataFrame) -> DataFrame:
        self.require_columns(df, self.get("image_col"))
        run_for = self._get_apply()
        bs = self.get("batch_size")
        dp = self._mesh.data_parallel_size() if self._mesh is not None else 1
        bucketer = cb.default_bucketer()

        def per_part(part):
            imgs = part[self.get("image_col")]
            if len(imgs) == 0:
                # keep the output schema rectangular across partitions
                out = dict(part)
                out[self.get("scores_col")] = np.zeros((0, self.get("num_classes")), np.float32)
                out[self.get("prediction_col")] = np.zeros(0, np.int32)
                return out
            x = np.stack(list(imgs)).astype(np.float32)
            chunks = []
            for s, e, bucket in bucketer.slices(len(x), bs, multiple_of=dp):
                p = run_for(bucket, x.shape[1:])(cb.pad_rows(x[s:e], bucket))
                chunks.append(cb.unpad_rows(p, e - s))
            probs = np.concatenate(chunks, axis=0)
            out = dict(part)
            out[self.get("scores_col")] = probs
            out[self.get("prediction_col")] = np.argmax(probs, axis=-1).astype(np.int32)
            return out

        return df.map_partitions(per_part)
