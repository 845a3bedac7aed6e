"""DeepTextClassifier / DeepTextModel — text fine-tuning on the mesh.

Reference: ``dl/DeepTextClassifier.py:27-288`` (horovod TorchEstimator subclass,
HF checkpoint + tokenizer transformation_fn, layer-freezing fine-tune in
``dl/LitDeepTextModel.py:120``) and the ``DeepTextModel`` per-row predict
(``dl/DeepTextModel.py:84-118``). Rebuilt: Flax BERT + GSPMD Trainer; the
param surface keeps the reference's names (text_col/label_col/checkpoint/
batch_size/learning_rate/max_token_len/num_train_epochs/unfreeze_layers).
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np

from ..core import DataFrame, Estimator, Model
from ..core import batching as cb
from ..core import observability as obs
from ..core.params import ComplexParam, Param, TypeConverters
from ..parallel.mesh import MeshConfig, MeshContext, create_mesh
from .flax_nets.bert import BertClassifier, bert_base, bert_tiny
from .tokenizer import resolve_tokenizer
from .trainer import (Trainer, TrainerConfig, TrainState,
                      _fit_with_optional_checkpointing, fit_arrays, plan_fit)

__all__ = ["DeepTextClassifier", "DeepTextModel"]

_ARCHS = {"bert-base": bert_base, "bert-tiny": bert_tiny}

_SCORE_METRICS = obs.HandleCache(lambda reg: {
    "batches": reg.counter(
        "synapseml_text_score_batches_total",
        "DeepTextModel.transform batches by how their host preparation ran: "
        "overlapped (an earlier batch of the partition was on the device) "
        "or exposed (nothing in flight: a partition's first batch)",
        ("prep",)),
})


def _resolve_arch(name: str):
    """Known preset or fail fast — a typo must not silently train a
    randomly-initialized bert-base."""
    try:
        return _ARCHS[name]
    except KeyError:
        raise ValueError(f"unknown checkpoint {name!r}; available presets: "
                         f"{sorted(_ARCHS)} (or pass a local HF checkpoint "
                         f"directory for pretrained weights)") from None




class _TextParams:
    text_col = Param("text_col", "input text column", default="text")
    label_col = Param("label_col", "label column", default="label")
    prediction_col = Param("prediction_col", "argmax output column", default="prediction")
    scores_col = Param("scores_col", "softmax scores output column", default="scores")
    checkpoint = Param("checkpoint", "architecture preset or HF checkpoint name",
                       default="bert-tiny")
    num_classes = Param("num_classes", "number of classes", default=2,
                        converter=TypeConverters.to_int)
    max_token_len = Param("max_token_len", "max sequence length (reference default 128)",
                          default=128, converter=TypeConverters.to_int)
    batch_size = Param("batch_size", "global batch size", default=32,
                       converter=TypeConverters.to_int)


class DeepTextClassifier(Estimator, _TextParams):
    feature_name = "deep_learning"

    learning_rate = Param("learning_rate", "peak learning rate", default=5e-5,
                          converter=TypeConverters.to_float)
    num_train_epochs = Param("num_train_epochs", "training epochs", default=3,
                             converter=TypeConverters.to_int)
    max_steps = Param("max_steps", "hard cap on optimizer steps (-1 = epochs decide)",
                      default=-1, converter=TypeConverters.to_int)
    unfreeze_layers = Param("unfreeze_layers",
                            "train only the last N encoder layers (+head); -1 = all "
                            "(reference LitDeepTextModel._fine_tune_layers)",
                            default=-1, converter=TypeConverters.to_int)
    grad_accum = Param("grad_accum", "gradient accumulation steps "
                       "(horovod backward_passes_per_step analog)", default=1,
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
    attn_impl = Param("attn_impl", "attention backend: auto | einsum | flash "
                      "| ring | ulysses (None = architecture default; auto "
                      "picks einsum or flash from the call; ring/ulysses "
                      "need a mesh with a seq axis > 1; ulysses also needs "
                      "n_heads divisible by the seq-axis size)", default=None,
                      validator=lambda v: v in (None, "auto", "einsum", "flash",
                                                "ring", "ulysses"))
    tokenizer = ComplexParam("tokenizer", "tokenizer object/config/name", default=None)
    mesh_config = ComplexParam("mesh_config", "MeshConfig override", default=None)
    weight_decay = Param("weight_decay", "adamw weight decay", default=0.01,
                         converter=TypeConverters.to_float)

    def _make_config(self, vocab_size: int):
        return _resolve_arch(self.get("checkpoint"))(vocab_size=vocab_size)

    def _freeze_predicate(self, n_layers_total: int):
        n = self.get("unfreeze_layers")
        if n is None or n < 0:
            return None
        trainable_layers = {f"layer_{i}" for i in
                            range(max(n_layers_total - n, 0), n_layers_total)}

        def frozen(path: tuple[str, ...]) -> bool:
            if path and path[0] in ("classifier", "pooler"):
                return False
            return not any(p in trainable_layers for p in path)

        return frozen

    def _fit(self, df: DataFrame) -> "DeepTextModel":
        from .convert_hf import is_checkpoint_dir, tokenizer_for_checkpoint

        ck = self.get("checkpoint")
        init_params = None
        if is_checkpoint_dir(ck):
            # local HF checkpoint directory: pretrained weights + its tokenizer
            # (the reference's AutoModelForSequenceClassification.from_pretrained
            # transfer-learning path, dl/DeepTextClassifier.py:27-288)
            from .convert_hf import pretrained_text_classifier

            cfg, init_params = pretrained_text_classifier(
                ck, num_classes=self.get("num_classes"), seed=self.get("seed"))
            tok = tokenizer_for_checkpoint(self.get("tokenizer"), ck, cfg.vocab_size)
        else:
            tok = resolve_tokenizer(self.get("tokenizer"))
            cfg = self._make_config(tok.vocab_size)
        if self.get("attn_impl"):
            import dataclasses

            cfg = dataclasses.replace(cfg, attn_impl=self.get("attn_impl"))
        # an explicit mesh_config that does not fit the devices is an error,
        # never a silent degrade to data-parallel
        mesh = create_mesh(self.get("mesh_config") or MeshConfig(),
                           allow_fewer=False)
        module = BertClassifier(cfg, num_classes=self.get("num_classes"))

        texts = df.collect_column(self.get("text_col"))
        labels = df.collect_column(self.get("label_col")).astype(np.int32)
        encoded = tok(list(texts), max_len=self.get("max_token_len"))
        data = {**encoded, "labels": labels}

        bs, total = plan_fit(len(labels), self.get("batch_size"),
                             self.get("num_train_epochs"), self.get("max_steps"))
        tcfg = TrainerConfig(
            learning_rate=self.get("learning_rate"),
            weight_decay=self.get("weight_decay"),
            total_steps=total, grad_accum=self.get("grad_accum"),
            warmup_steps=max(total // 10, 1), lr_schedule="linear",
            freeze_predicate=self._freeze_predicate(cfg.n_layers),
        )
        trainer = Trainer(module, mesh, tcfg)
        state = _fit_with_optional_checkpointing(
            self, lambda ck, every: fit_arrays(
                trainer, data, batch_size=bs, total_steps=total,
                seed=self.get("seed"), init_params=init_params,
                checkpointer=ck, checkpoint_every=every))

        host_params = jax.tree.map(np.asarray, state.params)
        # always persist the arch: a preset's meaning may evolve (e.g. the
        # pre->post-norm change) and a saved model must keep evaluating with
        # the architecture it was trained as
        return DeepTextModel(
            model_params=host_params,
            arch_config=cfg,
            tokenizer_config=tok.to_config(),
            checkpoint=self.get("checkpoint"),
            num_classes=self.get("num_classes"),
            text_col=self.get("text_col"),
            prediction_col=self.get("prediction_col"),
            scores_col=self.get("scores_col"),
            max_token_len=self.get("max_token_len"),
            batch_size=self.get("batch_size"),
            train_metrics=trainer.metrics,
            # sequence-parallel attention only exists on a mesh with a seq
            # axis: the model scores on the mesh it was trained on
            mesh_config=(self.get("mesh_config")
                         if cfg.attn_impl in ("ring", "ulysses") else None),
        )


class DeepTextModel(Model, _TextParams):
    feature_name = "deep_learning"

    model_params = ComplexParam("model_params", "trained Flax parameter pytree")
    mesh_config = ComplexParam(
        "mesh_config", "MeshConfig for sharded inference (params + batches "
        "distribute over the mesh; explainer perturbation batches ride the "
        "same path)", default=None)
    arch_config = ComplexParam("arch_config", "TransformerConfig (pretrained-dir "
                               "fits; None = resolve checkpoint preset)", default=None)
    tokenizer_config = ComplexParam("tokenizer_config", "tokenizer config dict")
    train_metrics = ComplexParam("train_metrics", "loss/throughput trace", default=None)
    attn_impl = Param("attn_impl", "serve-time attention backend override: "
                      "auto | einsum | flash (None = the trained arch's "
                      "choice); pure kernel selection — the param tree is "
                      "unchanged", default=None,
                      validator=lambda v: v in (None, "auto", "einsum", "flash"))

    # publish-time backend search (registry/autotune.py): the single-chip
    # attention impls the attn_backends decision bench compares — the
    # fastest per platform is pinned into the artifact manifest at publish
    # and re-applied at /admin/load. Declared on the MODEL (the class
    # artifacts actually serve), not the estimator: ring/ulysses need a
    # mesh topology and stay out of the serve-path search. A manifest's pin
    # owns the choice where one was published; elsewhere the arch's default
    # 'auto' picks from the call (transformer.resolve_attn_impl).
    _AUTOTUNE_PARAMS = {"attn_impl": ("einsum", "flash")}

    def __init__(self, **kw):
        super().__init__(**kw)
        self._apply_fn = None

    def _post_load(self):
        self._apply_fn = None
        cb.invalidate_token(self)

    _APPLY_KEYS = frozenset({"model_params", "arch_config", "tokenizer_config",
                             "checkpoint", "num_classes", "mesh_config",
                             "attn_impl"})

    def set(self, **kw):
        out = super().set(**kw)
        if self._APPLY_KEYS & kw.keys():
            self._apply_fn = None  # cached closure captured the old values
            cb.invalidate_token(self)
        return out

    def _get_apply(self):
        """Returns ``run_for(bucket, seq_len)`` — a per-bucket executable
        factory backed by the process-wide CompiledCache, so a variable
        scoring stream compiles at most ladder-many programs."""
        if self._apply_fn is None:
            import jax.numpy as jnp

            tok = resolve_tokenizer(self.get("tokenizer_config"))
            cfg = self.get("arch_config")
            if cfg is None:
                from .convert_hf import legacy_prenorm_fixup

                cfg = _resolve_arch(self.get("checkpoint"))(vocab_size=tok.vocab_size)
                cfg = legacy_prenorm_fixup(cfg, self.get("model_params"))
            if self.get("attn_impl"):
                import dataclasses

                # serve-time kernel override (the autotune pin): same math,
                # same param tree, different attention impl
                cfg = dataclasses.replace(cfg,
                                          attn_impl=self.get("attn_impl"))
            module = BertClassifier(cfg, num_classes=self.get("num_classes"))

            params = self.get("model_params")
            mesh = None
            if self.get("mesh_config") is not None:
                from ..parallel.mesh import shard_inference_params

                mesh = create_mesh(self.get("mesh_config"), allow_fewer=False)
                params = shard_inference_params(
                    module, {"input_ids": jnp.zeros((1, 8), jnp.int32),
                             "attention_mask": jnp.ones((1, 8), jnp.int32)},
                    params, mesh)
            else:
                # one device copy: the saved params are host numpy, and a
                # host tree passed to jit is re-uploaded on every batch
                params = jax.device_put(params)

            def apply_fn(params, input_ids, attention_mask):
                logits = module.apply({"params": params}, input_ids, attention_mask)
                return jax.nn.softmax(logits, axis=-1)

            def run_for(bucket: int, seq_len: int):
                def build():
                    jitted = jax.jit(apply_fn)
                    if mesh is not None:
                        def run(ids, m, _j=jitted, _m=mesh):
                            with _m.scope():
                                return _j(params, _m.shard_batch(ids),
                                          _m.shard_batch(m))
                        return run
                    return lambda ids, m: jitted(params, ids, m)

                return cb.get_compiled_cache().get(
                    "deep_text_model", (bucket, seq_len), build,
                    instance=cb.instance_token(self), dtype="int32")

            self._tok = tok
            self._mesh = mesh
            self._apply_fn = run_for
        return self._apply_fn

    def _transform(self, df: DataFrame) -> DataFrame:
        self.require_columns(df, self.get("text_col"))
        run_for = self._get_apply()
        bs = self.get("batch_size")
        dp = self._mesh.data_parallel_size() if self._mesh is not None else 1
        bucketer = cb.default_bucketer()

        def dispatch(texts, bucket):
            # each batch pads to its own longest row (the tokenizer's rule),
            # so batch k+1 is tokenized without waiting for the partition
            enc = self._tok(texts, max_len=self.get("max_token_len"))
            ids = np.asarray(enc["input_ids"])
            mask = np.asarray(enc["attention_mask"])
            return run_for(bucket, ids.shape[1])(
                cb.pad_rows(ids, bucket), cb.pad_rows(mask, bucket))

        def per_part(part):
            texts = list(part[self.get("text_col")])
            if not texts:
                # keep the output schema rectangular across partitions
                out = dict(part)
                out[self.get("scores_col")] = np.zeros((0, self.get("num_classes")), np.float32)
                out[self.get("prediction_col")] = np.zeros(0, np.int32)
                return out
            batches = _SCORE_METRICS.get()["batches"]
            probs_chunks, in_flight = [], None
            for s, e, bucket in bucketer.slices(len(texts), bs, multiple_of=dp):
                # one batch of lookahead: batch k+1 is prepared and queued
                # behind batch k before batch k is fetched
                p = dispatch(texts[s:e], bucket)
                batches.inc(prep="exposed" if in_flight is None else "overlapped")
                if in_flight is not None:
                    probs_chunks.append(cb.unpad_rows(*in_flight))
                in_flight = (p, e - s)
            probs_chunks.append(cb.unpad_rows(*in_flight))
            probs = np.concatenate(probs_chunks, axis=0)
            out = dict(part)
            out[self.get("scores_col")] = probs
            out[self.get("prediction_col")] = np.argmax(probs, axis=-1).astype(np.int32)
            return out

        return df.map_partitions(per_part)
