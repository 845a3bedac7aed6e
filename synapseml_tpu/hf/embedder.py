"""HuggingFaceSentenceEmbedder (reference ``hf/HuggingFaceSentenceEmbedder.py:26-228``,
sentence-transformers + optional TensorRT): text -> pooled encoder embedding.

Here: a Flax BERT-style encoder jitted once per batch shape; masked mean
pooling (the sentence-transformers default) or CLS pooling; L2 normalization
optional. Padded fixed-size batches keep one compiled program.
"""

from __future__ import annotations

import numpy as np

from ..core import batching as cb
from ..core.dataframe import DataFrame
from ..core.params import ComplexParam, Param, TypeConverters
from ..core.pipeline import Transformer
from ..models.flax_nets.bert import BertEmbeddings, bert_base, bert_tiny
from ..models.flax_nets.transformer import Encoder

__all__ = ["HuggingFaceSentenceEmbedder"]

_ARCHS = {"bert-base": bert_base, "bert-tiny": bert_tiny}


class _BertEncoder:
    """Embeddings + encoder stack (no classification head)."""

    def __init__(self, cfg):
        import flax.linen as nn

        class Net(nn.Module):
            @nn.compact
            def __call__(self, input_ids, attention_mask):
                x = BertEmbeddings(cfg, name="embeddings")(input_ids)
                mask = attention_mask[:, None, None, :].astype(bool)
                return Encoder(cfg, name="encoder")(x, mask)

        self.net = Net()
        self.cfg = cfg


class HuggingFaceSentenceEmbedder(Transformer):
    feature_name = "hf"

    model_name = Param("model_name", "encoder preset or local HF checkpoint dir",
                       default="bert-tiny")
    model_params = ComplexParam("model_params", "flax param pytree (None = random)",
                                default=None)
    tokenizer = ComplexParam("tokenizer", "tokenizer spec/object", default=None)
    input_col = Param("input_col", "text column", default="text")
    output_col = Param("output_col", "embedding column", default="embeddings")
    pooling = Param("pooling", "mean | cls", default="mean",
                    validator=lambda v: v in ("mean", "cls"))
    normalize = Param("normalize", "L2-normalize embeddings (opt in for "
                      "cosine indexes; raw pooled vectors by default so "
                      "callers stop re-normalizing per batch)", default=False,
                      converter=TypeConverters.to_bool)
    max_token_len = Param("max_token_len", "truncation length", default=128,
                          converter=TypeConverters.to_int)
    batch_size = Param("batch_size", "rows per padded batch", default=32,
                       converter=TypeConverters.to_int)
    mesh_config = ComplexParam("mesh_config", "MeshConfig for sharded "
                               "embedding (params + batches over the mesh)",
                               default=None)

    _CACHE_KEYS = frozenset({"model_name", "model_params", "tokenizer",
                             "mesh_config", "pooling", "normalize"})

    def set(self, **kw):
        out = super().set(**kw)
        if self._CACHE_KEYS & kw.keys():
            self.__dict__.pop("_cache_model", None)
            cb.invalidate_token(self)  # cached executables captured old state
        return out

    def _setup(self):
        if self.__dict__.get("_cache_model") is None:
            import jax
            import jax.numpy as jnp

            # pretrained-dir or preset (the reference's sentence-transformers
            # load path, hf/HuggingFaceSentenceEmbedder.py:26-228)
            import functools

            from ..models.convert_hf import (
                legacy_prenorm_fixup,
                pretrained_encoder,
                resolve_model_source,
            )

            cfg, loaded, tok = resolve_model_source(
                self.get("model_name"), _ARCHS, self.get("tokenizer"),
                functools.partial(pretrained_encoder, dtype=jnp.float32),
                preset_kwargs={"dtype": jnp.float32})
            params = self.get("model_params")
            if params is None:
                params = loaded
            elif loaded is None:
                cfg = legacy_prenorm_fixup(cfg, params)
            enc = _BertEncoder(cfg)
            if params is None:
                params = enc.net.init(jax.random.PRNGKey(0),
                                      jnp.zeros((1, 8), jnp.int32),
                                      jnp.ones((1, 8), jnp.int32))["params"]
            mesh = None
            if self.get("mesh_config") is not None:
                from ..parallel.mesh import create_mesh, shard_inference_params

                mesh = create_mesh(self.get("mesh_config"))
                if self.get("batch_size") % mesh.data_parallel_size():
                    raise ValueError(
                        f"batch_size ({self.get('batch_size')}) must be a "
                        f"multiple of the mesh data-parallel size "
                        f"({mesh.data_parallel_size()})")
                params = shard_inference_params(
                    enc.net, {"input_ids": jnp.zeros((1, 8), jnp.int32),
                              "attention_mask": jnp.ones((1, 8), jnp.int32)},
                    params, mesh)

            def embed_fn(ids, mask):
                h = enc.net.apply({"params": params}, ids, mask)  # [B,T,H]
                if self.get("pooling") == "cls":
                    pooled = h[:, 0]
                else:
                    m = mask[:, :, None].astype(h.dtype)
                    pooled = jnp.sum(h * m, axis=1) / jnp.maximum(
                        jnp.sum(m, axis=1), 1e-9)
                if self.get("normalize"):
                    pooled = pooled / jnp.maximum(
                        jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9)
                return pooled

            self.__dict__["_cache_model"] = (embed_fn, tok, mesh)
        return self.__dict__["_cache_model"]

    def _embed_for(self, bucket: int, seq_len: int):
        """Per-(batch bucket, seq len) executable via the CompiledCache —
        a mixed request stream compiles at most ladder-many programs per
        sequence shape instead of one per distinct batch size."""
        embed_fn, _tok, mesh = self._setup()

        def build():
            import jax

            jitted = jax.jit(embed_fn)
            if mesh is not None:
                def sharded(ids, m, _j=jitted, _m=mesh):
                    with _m.scope():
                        return _j(_m.shard_batch(ids), _m.shard_batch(m))
                return sharded
            return jitted

        return cb.get_compiled_cache().get(
            "hf_embedder", (bucket, seq_len), build,
            instance=cb.instance_token(self), dtype="int32")

    def _transform(self, df: DataFrame) -> DataFrame:
        self.require_columns(df, self.get("input_col"))
        _embed_fn, tok, mesh = self._setup()
        B = self.get("batch_size")
        dp = mesh.data_parallel_size() if mesh is not None else 1
        bucketer = cb.default_bucketer()

        def per_part(p):
            texts = [str(t) for t in p[self.get("input_col")]]
            n = len(texts)
            if n == 0:
                q = dict(p)
                q[self.get("output_col")] = np.empty((0, 0), np.float32)
                return q
            enc = tok(texts, max_len=self.get("max_token_len"))
            ids = np.asarray(enc["input_ids"], np.int32)
            mask = np.asarray(enc["attention_mask"], np.int32)
            chunks = []
            for s, e, bucket in bucketer.slices(n, B, multiple_of=dp):
                ib = cb.pad_rows(ids[s:e], bucket)
                # padded rows keep mask=1 so pooled denominators stay
                # nonzero; their embeddings are sliced off below
                mb = cb.pad_rows(mask[s:e], bucket, mode="constant",
                                 constant=1)
                embed = self._embed_for(bucket, ids.shape[1])
                chunks.append(cb.unpad_rows(embed(ib, mb), e - s))
            q = dict(p)
            q[self.get("output_col")] = np.concatenate(chunks, axis=0)
            return q

        return df.map_partitions(per_part)
