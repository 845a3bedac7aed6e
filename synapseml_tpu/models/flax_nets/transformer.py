"""Shared transformer building blocks, TPU-first.

Design (not a torch port — reference models arrive via torchvision/HF in
``dl/LitDeepVisionModel.py`` / ``dl/LitDeepTextModel.py``; here they are Flax
modules built for GSPMD):
  * every weight carries logical axis names (``nn.with_logical_partitioning``)
    mapped to mesh axes by ``parallel.mesh.logical_axis_rules`` — tensor
    parallelism is a rule change, not a code change;
  * compute dtype bf16 by default (MXU native), params fp32;
  * attention is einsum-based with optional GQA + rotary embeddings and a
    decode-time KV cache; the sequence axis is ready for ring attention
    (``ops.ring_attention``) when seq-parallel is on;
  * a stack's layers may differ (``layer_types``, ``moe_dense_layers``): a
    gated short convolution (``ShortConv``, ``ops.short_conv``) or attention
    as the token mixer, a dense MLP or routed experts as the feed-forward;
  * attention's keys and values may come from one low-rank latent a position
    with a rotary key that all heads share (``LatentAttention``,
    ``kv_latent_rank > 0``), and the routed experts may stand beside a shared
    expert that every token passes through (``moe_shared_mlp_dim > 0``);
  * optional ``nn.remat`` on blocks trades FLOPs for HBM: the backward pass
    runs each block forward again and keeps nothing of it, except what an
    attention op's own backward pass reads beside its inputs, by the names
    the op exports (``REMAT_SAVED_NAMES``). The indexed attention
    (``attn_topk > 0``, ``ops.sparse_attention``): its output, its
    selection's thresholds and two row statistics, 136 MB a layer where
    running its tile loops again cost a fifth of the step; that backward pass
    computes the indexer's head products, the index scores and the attention
    scores again, and no statistic or search. The flash op
    (``attn_impl == "flash"``, ``ops.attention``): the kernel's output and
    log-sum-exp, 68 to 272 MB a layer where the second launch of the kernel
    cost 6 to 9% of the step's device time. ``ring`` and ``ulysses`` keep
    nothing: under ``remat`` their forward still runs twice a step.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["TransformerConfig", "Attention", "LatentAttention", "MlpBlock", "ShortConv",
           "Block", "Encoder",
           "RMSNorm", "apply_rope", "make_causal_mask"]

Dtype = Any


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    hidden: int = 768
    n_layers: int = 12
    n_heads: int = 12
    n_kv_heads: int | None = None  # None -> MHA; < n_heads -> GQA
    head_dim: int | None = None  # None -> hidden // n_heads
    mlp_dim: int = 3072
    max_len: int = 512
    dropout: float = 0.0
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32
    causal: bool = False
    use_rope: bool = False
    rope_theta: float = 10000.0
    norm: str = "layernorm"  # or "rmsnorm"
    # 'pre' (norm before attn/mlp + final encoder norm — ViT/Llama) or 'post'
    # (norm after each residual add, no final norm — original BERT). Post-norm
    # is required for faithful ingestion of HF BERT checkpoints.
    norm_position: str = "pre"
    # learned absolute position embeddings added by the LM wrapper (GPT-2
    # family); RoPE models leave this False
    learned_pos: bool = False
    gated_mlp: bool = False  # SwiGLU when True
    act: str = "gelu"
    remat: bool = False
    norm_eps: float = 1e-6
    # attention backend: 'einsum' (XLA, always available), 'flash' (Pallas
    # blockwise kernel, ops.flash_attention), 'ring' / 'ulysses'
    # (sequence-parallel over `seq_axis` — an error without a mesh in scope
    # whose seq axis size > 1). Which is fastest on the chip is not
    # measured (ROADMAP A3); 'flash' avoids the O(T^2) score buffer.
    attn_impl: str = "einsum"
    seq_axis: str = "seq"
    # mixture-of-experts MLP (switch-transformer routing): 0 = dense MLP.
    # Expert weights carry the 'expert' logical axis, so on a mesh with an
    # expert axis the per-expert matmuls shard and GSPMD inserts the token
    # all-to-alls from the dispatch einsums (expert parallelism).
    moe_experts: int = 0
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    # token->expert routing layout: 'einsum' builds [S, E, C] one-hot
    # dispatch/combine tensors (pure MXU work; right when C is small, i.e.
    # capacity_factor ~1-2 with switch-style dropping). 'scatter' sorts the
    # (token, choice) assignments by expert and scatters rows into [E, C, H]
    # buffers — O(E*C*H) memory and O(S*k*H) index work, never O(S*E*C) —
    # which is the only feasible layout when capacity must be dropless
    # (C = S, e.g. ingested Mixtral checkpoints at real sequence lengths).
    # 'grouped' is dropless at any imbalance and does the routed pairs' work
    # only (ops.grouped_ffn: gated experts, no biases); it is also the one
    # layout that can hold a SHARE of the experts: the router scores
    # `moe_total_experts` (0 -> moe_experts), this module holds the
    # `moe_experts` consecutive ones from `moe_first_expert` and returns their
    # part of the result (what experts held on other chips would add is left
    # out: one chip of an expert-parallel deployment, without its exchange).
    # Router forms by layout: 'einsum' and 'scatter' take the softmax router
    # alone; 'grouped' takes either (`moe_router`). Layer kinds: every layout
    # serves the expert layers of a stack with a dense lead
    # (`moe_dense_layers`) and mixed token mixers (`layer_types`).
    moe_dispatch: str = "einsum"
    moe_total_experts: int = 0
    moe_first_expert: int = 0
    moe_bias: bool = True  # b_up / b_dn on the experts
    # 'softmax': probabilities over all the router's experts, the top-k
    # renormalised (Mixtral; switch for k = 1). 'sigmoid' ('grouped' only):
    # scores by sigmoid, the top-k CHOSEN by score + a per-expert selection
    # bias (a constant of the model: collection 'constants', zeros unless
    # given, no gradient, held by the Trainer's state), the gates
    # `moe_gate_scale` x the unbiased scores over (their sum + `moe_gate_eps`):
    # normalised, then scaled; it sows no load-balance term. The softmax form
    # reads neither of the two (its 1e-9 and its scale of 1 are its own).
    moe_router: str = "softmax"
    moe_gate_scale: float = 1.0
    moe_gate_eps: float = 1e-6
    # width of a shared expert: one gated MLP (`MlpBlock`'s products, under
    # the name 'shared') that EVERY token passes through, added to the routed
    # experts' result; held whole on every chip of an expert-parallel
    # deployment. 0 = none. 'grouped' only.
    moe_shared_mlp_dim: int = 0
    # per-layer kinds. `layer_types`: the token mixer of each layer, 'conv'
    # (gated short convolution of 3 taps, ops.short_conv) or
    # 'full_attention'; () = attention everywhere. `moe_dense_layers`: that
    # many leading layers keep the dense MLP of width `mlp_dim` when
    # `moe_experts > 0`; the experts' width is `moe_mlp_dim` (0 -> mlp_dim).
    layer_types: tuple = ()
    moe_dense_layers: int = 0
    moe_mlp_dim: int = 0
    mlp_bias: bool = True  # biases on the dense MLP's projections
    tie_embeddings: bool = False  # the LM head is the embedding matrix (LlamaLM)
    flash_block: int = 128  # query and key block of attn_impl='flash'
    attn_bias: bool = True  # biases on the q, k, v, o projections
    qk_norm: bool = False  # per-head RMSNorm on q and k, before RoPE
    # latent attention (`LatentAttention`): `kv_latent_rank` > 0 makes every
    # layer's keys and values an up-projection of ONE normed latent of that
    # width a position. `head_dim` is then the whole query/key width a head,
    # whose last `rope_dim` dims are rotary (the key's rotary part is one
    # vector a position, shared by all heads; RoPE turns these dims alone) and
    # whose first `head_dim - rope_dim` come from the latent; `v_head_dim` is
    # the value width a head (0 -> head_dim). Scores scale by 1/sqrt(head_dim).
    # All three are read by the latent form only.
    kv_latent_rank: int = 0
    rope_dim: int = 0
    v_head_dim: int = 0
    # learned sparse attention (ops.sparse_attention): 0 = every causal key.
    # Each query attends to the exact `attn_topk` keys that an indexer of
    # `indexer_heads` heads of `indexer_head_dim` (one key head) scores highest;
    # the indexer's loss is sown under intermediates/sparse_attn_indexer_kl.
    attn_topk: int = 0
    indexer_heads: int = 0
    indexer_head_dim: int = 0
    attn_q_tile: int = 512  # queries a tile on the indexed path

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.hidden // self.n_heads)
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.layer_types and len(self.layer_types) != self.n_layers:
            raise ValueError(f"layer_types names {len(self.layer_types)} layers, "
                             f"n_layers is {self.n_layers}")
        unknown = set(self.layer_types) - {"conv", "full_attention"}
        if unknown:
            raise ValueError(f"layer_types holds 'conv' or 'full_attention', got {unknown}")

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def expert_mlp_dim(self) -> int:
        return self.moe_mlp_dim or self.mlp_dim

    @property
    def value_dim(self) -> int:
        return self.v_head_dim or self.head_dim


def _act_fn(name: str) -> Callable:
    # 'gelu' is the exact erf form (what HF BERT/ViT checkpoints were trained
    # with); 'gelu_tanh' is the cheaper approximation
    return {"gelu": lambda x: nn.gelu(x, approximate=False),
            "gelu_tanh": nn.gelu, "relu": nn.relu, "silu": nn.silu}[name]


class RMSNorm(nn.Module):
    eps: float = 1e-6
    dtype: Dtype = jnp.bfloat16
    axes: tuple = ("embed",)  # logical axis of the scale

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.with_logical_partitioning(nn.initializers.ones, self.axes),
                           (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (normed * scale).astype(self.dtype)


def _norm(cfg: TransformerConfig):
    if cfg.norm == "rmsnorm":
        return RMSNorm(eps=cfg.norm_eps, dtype=cfg.dtype)
    return nn.LayerNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                        scale_init=nn.with_logical_partitioning(nn.initializers.ones, ("embed",)),
                        bias_init=nn.with_logical_partitioning(nn.initializers.zeros, ("embed",)))


def rope_frequencies(head_dim: int, max_len: int, theta: float) -> tuple[np.ndarray, np.ndarray]:
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))
    t = np.arange(max_len, dtype=np.float32)
    freqs = np.outer(t, inv)
    return np.cos(freqs), np.sin(freqs)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array, positions: jax.Array) -> jax.Array:
    """x: [B, T, H, D]; positions: [B, T] absolute positions (decode-time offset aware)."""
    c = cos[positions][:, :, None, :]  # [B,T,1,D/2]
    s = sin[positions][:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


def make_causal_mask(q_len: int, kv_len: int, offset: int = 0) -> jax.Array:
    q_pos = jnp.arange(q_len)[:, None] + offset
    kv_pos = jnp.arange(kv_len)[None, :]
    return (kv_pos <= q_pos)[None, None, :, :]  # [1,1,Q,KV]


class Attention(nn.Module):
    """Multi-head / grouped-query attention with optional rotary embeddings and
    a linen cache collection for autoregressive decode.

    The score/softmax/value core dispatches on ``cfg.attn_impl``:
    'einsum' (XLA), 'flash' (Pallas blockwise kernel), 'ring' (K/V rotation
    over ``cfg.seq_axis``), or 'ulysses' (all-to-all head/token swap over
    ``cfg.seq_axis``) — the long-context paths the reference lacks,
    SURVEY.md §5."""

    cfg: TransformerConfig
    decode: bool = False

    def _attend(self, q, k, v, mask):
        cfg = self.cfg
        D = cfg.head_dim
        # flash/ring support padding (kv-position) masks; arbitrary [.., Q, K]
        # masks (decode-time cache masks) use the einsum path
        kv_mask = None
        mask_is_kv_shaped = (mask is not None and mask.ndim == 4
                             and mask.shape[1] == 1 and mask.shape[2] == 1)
        if mask_is_kv_shaped:
            kv_mask = mask[:, 0, 0, :]
        # init only makes params and the core has none: its example inputs
        # (a (1, 8) batch in shard_inference_params) need not divide a mesh
        impl = "einsum" if self.is_initializing() else cfg.attn_impl
        # NOTE: flash/ring never materialize attention probabilities, so
        # attention-probability dropout does not apply on those paths (standard
        # for fused kernels); residual/MLP dropout is unaffected. Falling back
        # to einsum here would silently reintroduce the O(T^2) score matrix.
        eligible = not self.decode and (mask is None or mask_is_kv_shaped)

        if impl in ("ring", "ulysses") and eligible:
            from ...parallel.mesh import current_mesh

            mesh = current_mesh()
            if mesh is None or mesh.axis_sizes.get(cfg.seq_axis, 1) <= 1:
                # never substitute a local kernel: the caller sized the
                # sequence for the mesh, and a silent swap hides the device
                raise ValueError(
                    f"attn_impl={impl!r} needs a mesh with a "
                    f"'{cfg.seq_axis}' axis of size > 1 in scope (apply the "
                    f"module under MeshContext.scope(), e.g. "
                    f"mesh_config=MeshConfig(data=-1, seq=2)); in scope: "
                    f"{mesh and mesh.axis_sizes}")
            if impl == "ulysses":
                from ...ops import ulysses_attention_sharded

                return ulysses_attention_sharded(
                    mesh, q, k, v, kv_mask=kv_mask, causal=cfg.causal,
                    seq_axis=cfg.seq_axis)
            from ...ops import ring_attention_sharded

            return ring_attention_sharded(mesh, q, k, v, kv_mask=kv_mask,
                                          causal=cfg.causal,
                                          seq_axis=cfg.seq_axis)

        if impl == "flash" and eligible:
            from ...ops import flash_attention

            # the op names its scope, `attn.flash`
            return flash_attention(q, k, v, kv_mask=kv_mask, causal=cfg.causal,
                                   block_q=cfg.flash_block, block_k=cfg.flash_block)

        if cfg.causal and not self.decode:
            causal = make_causal_mask(q.shape[1], k.shape[1])
            mask = causal if mask is None else jnp.logical_and(mask, causal)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(D).astype(cfg.dtype)
        if mask is not None:
            scores = jnp.where(mask, scores, jnp.finfo(cfg.dtype).min)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(cfg.dtype)
        if cfg.dropout > 0:
            probs = nn.Dropout(cfg.dropout, deterministic=not self.has_rng("dropout"))(probs)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    def _attend_indexed(self, x, q, k, v, mask, positions):
        """Learned sparse attention: the indexer reads the layer's input (no
        gradient into the trunk), and every query attends to its exact
        ``cfg.attn_topk`` highest-scored causal keys. Sows the indexer's loss
        and the selected share of the causal candidates."""
        from ...ops.sparse_attention import indexed_attention

        cfg = self.cfg
        if self.decode or not cfg.causal:
            raise ValueError(
                "attn_topk > 0 is the causal training/prefill path: a decode "
                "cache would need the indexer's keys beside the model's")
        if mask is not None and not (mask.ndim == 4 and mask.shape[1] == 1
                                     and mask.shape[2] == 1):
            raise ValueError("the indexed path takes a key-padding mask only")
        HI, DI = cfg.indexer_heads, cfg.indexer_head_dim
        proj = lambda name, feat, axes: nn.DenseGeneral(  # noqa: E731
            features=feat, axis=-1, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            use_bias=False, kernel_init=nn.with_logical_partitioning(
                nn.initializers.xavier_uniform(), axes), name=name)
        h = jax.lax.stop_gradient(x)
        qi = proj("indexer_q", (HI, DI), ("embed", "heads", "kv"))(h)
        ki = proj("indexer_k", DI, ("embed", "kv"))(h)
        wi = proj("indexer_w", HI, ("embed", "heads"))(h)
        if cfg.use_rope:
            cos_np, sin_np = rope_frequencies(DI, cfg.max_len, cfg.rope_theta)
            cos, sin = jnp.asarray(cos_np), jnp.asarray(sin_np)
            qi = apply_rope(qi, cos, sin, positions)
            ki = apply_rope(ki[:, :, None, :], cos, sin, positions)[:, :, 0, :]
        out, kl, share = indexed_attention(
            q, k, v, qi, ki, wi, topk=cfg.attn_topk, q_tile=cfg.attn_q_tile,
            kv_mask=None if mask is None else mask[:, 0, 0, :])
        self.sow("intermediates", "sparse_attn_indexer_kl", kl)
        self.sow("intermediates", "sparse_attn_selected_share", share)
        return out

    @nn.compact
    def __call__(self, x, mask=None, positions=None):
        cfg = self.cfg
        B, T, _ = x.shape
        H, KV, D = cfg.n_heads, cfg.kv_heads, cfg.head_dim
        dense = lambda name, heads: nn.DenseGeneral(  # noqa: E731
            features=(heads, D), axis=-1, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            use_bias=cfg.attn_bias,
            kernel_init=nn.with_logical_partitioning(nn.initializers.xavier_uniform(),
                                                     ("embed", "heads", "kv")),
            bias_init=nn.with_logical_partitioning(nn.initializers.zeros, ("heads", "kv")),
            name=name)
        q = dense("q", H)(x)
        k = dense("k", KV)(x)
        v = dense("v", KV)(x)
        if cfg.qk_norm:
            q = RMSNorm(eps=cfg.norm_eps, dtype=cfg.dtype, axes=("kv",), name="q_norm")(q)
            k = RMSNorm(eps=cfg.norm_eps, dtype=cfg.dtype, axes=("kv",), name="k_norm")(k)

        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        if cfg.use_rope:
            cos_np, sin_np = rope_frequencies(D, cfg.max_len, cfg.rope_theta)
            cos, sin = jnp.asarray(cos_np), jnp.asarray(sin_np)
            q = apply_rope(q, cos, sin, positions)
            k = apply_rope(k, cos, sin, positions)

        if self.decode:
            # linen cache: append at cache_index; the update is skipped on the
            # very first (init) call so a fresh cache starts at index 0
            cache_ready = self.has_variable("cache", "cached_k")
            ck = self.variable("cache", "cached_k", jnp.zeros, (B, cfg.max_len, KV, D), cfg.dtype)
            cv = self.variable("cache", "cached_v", jnp.zeros, (B, cfg.max_len, KV, D), cfg.dtype)
            idx = self.variable("cache", "cache_index", lambda: jnp.zeros((), jnp.int32))
            start = idx.value
            if cache_ready:
                ck.value = jax.lax.dynamic_update_slice(ck.value, k, (0, start, 0, 0))
                cv.value = jax.lax.dynamic_update_slice(cv.value, v, (0, start, 0, 0))
                idx.value = start + T
            k, v = ck.value, cv.value
            kv_len = cfg.max_len
            causal = make_causal_mask(T, kv_len, offset=start)
            mask = causal if mask is None else jnp.logical_and(mask, causal)
        if cfg.attn_topk > 0:
            out = self._attend_indexed(x, q, k, v, mask, positions)
        else:
            if KV != H:
                k = jnp.repeat(k, H // KV, axis=2)
                v = jnp.repeat(v, H // KV, axis=2)
            out = self._attend(q, k, v, mask)
        return nn.DenseGeneral(
            features=cfg.hidden, axis=(-2, -1), dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            use_bias=cfg.attn_bias,
            kernel_init=nn.with_logical_partitioning(nn.initializers.xavier_uniform(),
                                                     ("heads", "kv", "embed")),
            bias_init=nn.with_logical_partitioning(nn.initializers.zeros, ("embed",)),
            name="o")(out)


class LatentAttention(Attention):
    """Multi-head latent attention, the up-projected form that training runs:
    queries projected directly to ``n_heads x head_dim``; ONE down-projection
    of the input to a ``kv_latent_rank``-wide latent plus a ``rope_dim``-wide
    rotary key that all heads share; an RMSNorm on the latent; an
    up-projection of the normed latent to each head's ``head_dim - rope_dim``
    key dims and ``v_head_dim`` value dims. RoPE turns the rotary dims only.
    The core is ``Attention._attend`` (keys wider than values: the flash
    kernel pads each width on its own). Training and prefill only: a decode
    cache would hold the latent and the rotary key, not per-head keys and
    values, with the up-projections absorbed into the query and output
    products. Device scope ``attn.latent``: everything outside the core."""

    @nn.compact
    def __call__(self, x, mask=None, positions=None):
        cfg = self.cfg
        if self.decode:
            raise ValueError("latent attention has no decode cache: training and "
                             "prefill only")
        B, T, _ = x.shape
        H, R = cfg.n_heads, cfg.rope_dim
        N, V = cfg.head_dim - R, cfg.value_dim
        proj = lambda name, feat, axes: nn.DenseGeneral(  # noqa: E731
            features=feat, axis=-1, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            use_bias=False, kernel_init=nn.with_logical_partitioning(
                nn.initializers.xavier_uniform(), axes), name=name)
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        with jax.named_scope("attn.latent"):
            q = proj("q", (H, N + R), ("embed", "heads", "kv"))(x)
            down = proj("kv_a", cfg.kv_latent_rank + R, ("embed", None))(x)
            latent = RMSNorm(eps=cfg.norm_eps, dtype=cfg.dtype, axes=(None,),
                             name="kv_norm")(down[..., :cfg.kv_latent_rank])
            up = proj("kv_b", (H, N + V), (None, "heads", "kv"))(latent)
            cos_np, sin_np = rope_frequencies(R, cfg.max_len, cfg.rope_theta)
            cos, sin = jnp.asarray(cos_np), jnp.asarray(sin_np)
            q_rot = apply_rope(q[..., N:], cos, sin, positions)
            k_rot = apply_rope(down[:, :, None, cfg.kv_latent_rank:], cos, sin, positions)
            q = jnp.concatenate([q[..., :N], q_rot], axis=-1)
            k = jnp.concatenate([up[..., :N], jnp.broadcast_to(k_rot, (B, T, H, R))], axis=-1)
        out = self._attend(q, k, up[..., N:], mask)
        with jax.named_scope("attn.latent"):
            return nn.DenseGeneral(
                features=cfg.hidden, axis=(-2, -1), dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, use_bias=False,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.xavier_uniform(), ("heads", "kv", "embed")),
                name="o")(out)


class MlpBlock(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dense = lambda name, feat, in_axis, out_axis: nn.Dense(  # noqa: E731
            feat, dtype=cfg.dtype, param_dtype=cfg.param_dtype, use_bias=cfg.mlp_bias,
            kernel_init=nn.with_logical_partitioning(nn.initializers.xavier_uniform(),
                                                     (in_axis, out_axis)),
            bias_init=nn.with_logical_partitioning(nn.initializers.zeros, (out_axis,)),
            name=name)
        act = _act_fn(cfg.act)
        if cfg.gated_mlp:
            g = dense("gate", cfg.mlp_dim, "embed", "mlp")(x)
            u = dense("up", cfg.mlp_dim, "embed", "mlp")(x)
            h = act(g) * u
        else:
            h = act(dense("up", cfg.mlp_dim, "embed", "mlp")(x))
        if cfg.dropout > 0:
            h = nn.Dropout(cfg.dropout, deterministic=not self.has_rng("dropout"))(h)
        return dense("down", cfg.hidden, "mlp", "embed")(h)


class MoEBlock(nn.Module):
    """Mixture-of-experts MLP: top-k routing over a float32 router, per-expert
    MLPs with the ``expert`` logical axis, three layouts (``cfg.moe_dispatch``).

    'einsum' / 'scatter' (switch-transformer): capacity-bucketed dispatch and
    combine as one-hot einsums or a sort-and-scatter into ``[E, C, H]``
    buffers (static shapes; expert weights shard over the mesh ``expert`` axis
    and GSPMD derives the token all-to-alls). Tokens overflowing an expert's
    capacity are dropped (the residual connection in :class:`Block` carries
    them through). 'grouped' (``ops.grouped_ffn``): dropless at any imbalance,
    work that follows the routed pairs, and the one layout that can hold a
    share of the experts (``moe_total_experts``, ``moe_first_expert``): the
    router scores all of them (its product at highest precision), the result
    is the held experts' part. The grouped layout also takes the sigmoid
    router with its constant selection bias (``cfg.moe_router``) and a shared
    expert (``cfg.moe_shared_mlp_dim``: a dense gated MLP every token passes
    through, added to the routed result under the device scope ``moe.shared``).

    Sown under ``intermediates``: ``moe_aux_loss`` (softmax router: the
    load-balance term over all the router's experts; mean over layers = the
    switch aux term) and, on the grouped path, ``moe_held_pairs``,
    ``moe_expert_load_max_ratio`` and, from the sigmoid router,
    ``moe_bias_steered_share``.
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        E, k = cfg.moe_experts, cfg.moe_top_k
        # the router's width: all the model's experts, of which E are held
        R = cfg.moe_total_experts or E
        B, T, H = x.shape
        S = B * T
        xf = x.reshape(S, H)
        if cfg.moe_dispatch not in ("einsum", "scatter", "grouped"):
            raise ValueError(
                f"moe_dispatch must be 'einsum', 'scatter' or 'grouped', got "
                f"{cfg.moe_dispatch!r}")
        if cfg.moe_dispatch != "grouped" and (R != E or cfg.moe_first_expert):
            raise ValueError("a share of the experts needs moe_dispatch='grouped'")

        # on the grouped path float32 in earnest: a TPU's default float32
        # product rounds its operands to bfloat16, and a rounded logit flips
        # near-tied choices. 'einsum' / 'scatter' keep the default product
        # (their users' results stay as they were)
        grouped = cfg.moe_dispatch == "grouped"
        if cfg.moe_router not in ("softmax", "sigmoid"):
            raise ValueError(f"moe_router must be 'softmax' or 'sigmoid', got "
                             f"{cfg.moe_router!r}")
        if not grouped and (cfg.moe_router != "softmax" or cfg.moe_shared_mlp_dim):
            raise ValueError("the sigmoid router and a shared expert need "
                             "moe_dispatch='grouped'")
        with jax.named_scope("moe.route"):
            router = nn.Dense(
                R, dtype=jnp.float32, param_dtype=cfg.param_dtype, use_bias=False,
                precision=jax.lax.Precision.HIGHEST if grouped else None,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.xavier_uniform(), ("embed", None)),
                name="router")
            logits = router(xf.astype(jnp.float32))           # [S, R] f32
            if cfg.moe_router == "sigmoid":
                gate_vals, gate_idx = self._sigmoid_route(logits)
            else:
                probs = jax.nn.softmax(logits, axis=-1)
                gate_vals, gate_idx = jax.lax.top_k(probs, k)      # [S, k]
                if k > 1:
                    # renormalize over the selected experts — identical to Mixtral's
                    # softmax-then-topk-then-divide. k=1 keeps the RAW router
                    # probability (switch-transformer semantics: the gate carries
                    # the router gradient); Mixtral never ships k=1 configs.
                    gate_vals = gate_vals / jnp.maximum(
                        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
                # load-balance aux loss: R * sum_e f_e * P_e over ALL the router's
                # experts, with f_e the token fraction averaged over ALL k routing
                # choices (the Mixtral/switch formulation — top-1-only would let
                # second choices escape balancing pressure when k > 1)
                frac_tokens = jnp.mean(
                    jax.nn.one_hot(gate_idx, R, dtype=jnp.float32), axis=(0, 1))
                self.sow("intermediates", "moe_aux_loss",
                         R * jnp.sum(frac_tokens * jnp.mean(probs, axis=0)))

        def w(name, shape, axes):
            return self.param(name, nn.with_logical_partitioning(
                nn.initializers.xavier_uniform(), axes), shape,
                cfg.param_dtype)

        if grouped:
            y = self._grouped(x, xf, gate_vals, gate_idx, w)
            return y + self._shared(x) if cfg.moe_shared_mlp_dim else y

        # capacity per expert, lane-friendly and >= 1
        C = max(int(np.ceil(cfg.moe_capacity_factor * S * k / E)), 1)

        if cfg.moe_dispatch == "scatter":
            # Sort the S*k (choice, token) assignments by expert so each
            # expert's tokens are contiguous, then scatter rows into [E, C, H]
            # buffers. One extra drop row absorbs capacity overflow (indices
            # stay in-bounds under jit). The flat layout is CHOICE-MAJOR and
            # the sort is stable, so capacity fills all first choices before
            # any second choice — the same drop priority as the einsum loop.
            Sk = S * k
            expert_flat = gate_idx.T.reshape(Sk)
            token_flat = jnp.tile(jnp.arange(S), k)
            gates_flat = gate_vals.T.reshape(Sk)
            order = jnp.argsort(expert_flat, stable=True)
            e_sorted = expert_flat[order]
            t_sorted = token_flat[order]
            g_sorted = gates_flat[order]
            counts = jnp.bincount(e_sorted, length=E)
            starts = jnp.cumsum(counts) - counts
            pos = jnp.arange(Sk) - starts[e_sorted]        # slot within expert
            keep = pos < C
            buf_idx = jnp.where(keep, e_sorted * C + pos, E * C)
            expert_in = jnp.zeros((E * C + 1, H), cfg.dtype)
            expert_in = expert_in.at[buf_idx].set(xf[t_sorted].astype(cfg.dtype))
            expert_in = expert_in[:E * C].reshape(E, C, H)
        else:
            dispatch = jnp.zeros((S, E, C), cfg.dtype)
            combine = jnp.zeros((S, E, C), jnp.float32)
            position_fill = jnp.zeros((E,), jnp.int32)
            for choice in range(k):
                e_oh = jax.nn.one_hot(gate_idx[:, choice], E, dtype=jnp.int32)
                # position of each token within its chosen expert's buffer,
                # continuing after slots used by earlier choices
                pos = jnp.cumsum(e_oh, axis=0) - e_oh + position_fill[None, :]
                pos_tok = jnp.sum(pos * e_oh, axis=1)      # [S]
                keep = pos_tok < C
                slot = jax.nn.one_hot(pos_tok, C, dtype=cfg.dtype) \
                    * keep[:, None].astype(cfg.dtype)      # [S, C]
                d = e_oh.astype(cfg.dtype)[:, :, None] * slot[:, None, :]
                dispatch = dispatch + d
                combine = combine + d.astype(jnp.float32) \
                    * gate_vals[:, choice][:, None, None]
                position_fill = position_fill + jnp.sum(e_oh, axis=0)

            expert_in = jnp.einsum("sec,sh->ech", dispatch, xf,
                                   preferred_element_type=cfg.dtype)
        expert_in = nn.with_logical_constraint(expert_in,
                                               ("expert", None, "embed"))

        def bias(name, width, axis):
            if not cfg.moe_bias:
                return jnp.zeros((), cfg.dtype)
            return self.param(name, nn.with_logical_partitioning(
                nn.initializers.zeros, ("expert", axis)), (E, width),
                cfg.param_dtype)[:, None, :].astype(cfg.dtype)

        w_up = w("w_up", (E, H, cfg.expert_mlp_dim), ("expert", "embed", "mlp"))
        b_up = bias("b_up", cfg.expert_mlp_dim, "mlp")
        w_dn = w("w_dn", (E, cfg.expert_mlp_dim, H), ("expert", "mlp", "embed"))
        b_dn = bias("b_dn", H, "embed")

        act = _act_fn(cfg.act)
        up = jnp.einsum("ech,ehm->ecm", expert_in, w_up.astype(cfg.dtype),
                        preferred_element_type=jnp.float32).astype(cfg.dtype) \
            + b_up
        if cfg.gated_mlp:
            # SwiGLU experts (the Mixtral block): act(x W_gate) * (x W_up)
            w_g = w("w_gate", (E, H, cfg.expert_mlp_dim), ("expert", "embed", "mlp"))
            gate = jnp.einsum("ech,ehm->ecm", expert_in, w_g.astype(cfg.dtype),
                              preferred_element_type=jnp.float32).astype(cfg.dtype)
            h = act(gate) * up
        else:
            h = act(up)
        h = nn.with_logical_constraint(h, ("expert", None, "mlp"))
        if cfg.dropout > 0:  # same placement as MlpBlock's hidden dropout
            h = nn.Dropout(cfg.dropout,
                           deterministic=not self.has_rng("dropout"))(h)
        out_e = jnp.einsum("ecm,emh->ech", h, w_dn.astype(cfg.dtype),
                           preferred_element_type=jnp.float32).astype(cfg.dtype) \
            + b_dn

        if cfg.moe_dispatch == "scatter":
            rows = out_e.reshape(E * C, H)[jnp.minimum(buf_idx, E * C - 1)]
            contrib = rows.astype(jnp.float32) \
                * (g_sorted * keep.astype(jnp.float32))[:, None]
            y = jnp.zeros((S, H), jnp.float32).at[t_sorted].add(contrib)
        else:
            y = jnp.einsum("sec,ech->sh", combine.astype(jnp.float32),
                           out_e.astype(jnp.float32),
                           preferred_element_type=jnp.float32)

        return y.reshape(B, T, H).astype(cfg.dtype)

    def _sigmoid_route(self, logits):
        """(gates, chosen experts) of the sigmoid router: the ``moe_top_k``
        largest of score + selection bias, weighed by ``moe_gate_scale`` x the
        unbiased scores over (their sum + ``moe_gate_eps``). The bias is a
        constant of the model: no gradient reaches it, and the choice passes
        none. Sows the share of (token, choice) pairs whose expert the scores
        alone would not have chosen."""
        cfg = self.cfg
        k = cfg.moe_top_k
        scores = jax.nn.sigmoid(logits)
        bias = self.variable("constants", "select_bias", jnp.zeros,
                             (logits.shape[-1],), jnp.float32).value
        _, gate_idx = jax.lax.top_k(
            jax.lax.stop_gradient(scores + bias.astype(jnp.float32)), k)
        gate_vals = jnp.take_along_axis(scores, gate_idx, axis=-1)
        _, unbiased = jax.lax.top_k(jax.lax.stop_gradient(scores), k)
        own = jnp.any(gate_idx[:, :, None] == unbiased[:, None, :], axis=-1)
        self.sow("intermediates", "moe_bias_steered_share",
                 1.0 - jnp.mean(own.astype(jnp.float32)))
        gate_vals = gate_vals / (jnp.sum(gate_vals, axis=-1, keepdims=True) + cfg.moe_gate_eps)
        # a scale of 1 adds no operation: the programs without one stay as they were
        return (gate_vals if cfg.moe_gate_scale == 1.0
                else gate_vals * cfg.moe_gate_scale), gate_idx

    def _shared(self, x):
        """The shared expert's result for every token: ``MlpBlock``'s products
        at width ``moe_shared_mlp_dim``."""
        cfg = self.cfg
        with jax.named_scope("moe.shared"):
            return MlpBlock(dataclasses.replace(cfg, mlp_dim=cfg.moe_shared_mlp_dim),
                            name="shared")(x)

    def _grouped(self, x, xf, gate_vals, gate_idx, w):
        """The held experts' part of the result, dropless (ops.grouped_ffn).
        Sows the pairs routed to the held experts and the most-loaded held
        expert's pairs over their mean."""
        from ...ops.grouped_ffn import expert_share_ffn

        cfg = self.cfg
        E, H = cfg.moe_experts, x.shape[-1]
        if not cfg.gated_mlp or cfg.moe_bias or cfg.dropout > 0:
            raise ValueError("moe_dispatch='grouped' runs gated experts without "
                             "biases or dropout (gated_mlp=True, moe_bias=False)")
        w_up = w("w_up", (E, H, cfg.expert_mlp_dim), ("expert", "embed", "mlp"))
        w_dn = w("w_dn", (E, cfg.expert_mlp_dim, H), ("expert", "mlp", "embed"))
        w_g = w("w_gate", (E, H, cfg.expert_mlp_dim), ("expert", "embed", "mlp"))
        with jax.named_scope("moe.experts"):
            y, counts = expert_share_ffn(
                xf.astype(cfg.dtype), gate_vals, gate_idx, w_g, w_up, w_dn,
                first_expert=cfg.moe_first_expert, act=_act_fn(cfg.act))
        held = jnp.sum(counts).astype(jnp.float32)
        self.sow("intermediates", "moe_held_pairs", held)
        self.sow("intermediates", "moe_expert_load_max_ratio",
                 jnp.max(counts) * E / jnp.maximum(held, 1.0))
        return y.reshape(x.shape).astype(cfg.dtype)


class ShortConv(nn.Module):
    """Gated short convolution (ops.short_conv), the token mixer of a 'conv'
    layer: one input projection to three streams b, c, u (in that order along
    the last axis), ``c * conv(b * u)`` with a depthwise causal convolution of
    ``TAPS`` taps, an output projection. No biases. Training and
    prefill only: a decode cache would hold the last taps' inputs beside the
    attention layers' keys."""

    cfg: TransformerConfig
    TAPS = 3    # every configuration so far; tap TAPS - 1 weighs the current position

    @nn.compact
    def __call__(self, x, mask=None, positions=None):
        # a causal convolution needs no positions, and a padded tail cannot
        # reach the positions before it: both are taken as `Attention` takes them
        from ...ops.short_conv import gated_short_conv

        cfg = self.cfg
        H = cfg.hidden
        proj = lambda name, feat, axes: nn.Dense(  # noqa: E731
            feat, dtype=cfg.dtype, param_dtype=cfg.param_dtype, use_bias=False,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.xavier_uniform(), axes), name=name)
        with jax.named_scope("conv.proj"):
            bcu = proj("in_proj", 3 * H, ("embed", "mlp"))(x)
        taps = self.param("conv", nn.with_logical_partitioning(
            nn.initializers.normal(0.02), ("mlp", None)), (H, self.TAPS),
            cfg.param_dtype)
        with jax.named_scope("conv.mix"):
            y = gated_short_conv(bcu[..., :H], bcu[..., H:2 * H], bcu[..., 2 * H:], taps)
        with jax.named_scope("conv.proj"):
            return proj("out_proj", H, ("mlp", "embed"))(y)


class Block(nn.Module):
    """One layer. ``layer`` is its place in the stack, which names its kinds
    (``cfg.layer_types``, ``cfg.moe_dense_layers``)."""

    cfg: TransformerConfig
    decode: bool = False
    layer: int = 0

    def _parts(self):
        """(token mixer, feed-forward) of this layer, by its kinds."""
        cfg = self.cfg
        conv = bool(cfg.layer_types) and cfg.layer_types[self.layer] == "conv"
        if conv and self.decode:
            raise ValueError("a 'conv' layer has no decode cache: training and "
                             "prefill only")
        experts = cfg.moe_experts > 0 and self.layer >= cfg.moe_dense_layers
        attn_cls = LatentAttention if cfg.kv_latent_rank > 0 else Attention
        return (ShortConv(cfg, name="conv") if conv
                else attn_cls(cfg, decode=self.decode, name="attn"),
                MoEBlock(cfg, name="mlp") if experts else MlpBlock(cfg, name="mlp"))

    @nn.compact
    def __call__(self, x, mask=None, positions=None):
        cfg = self.cfg
        mixer, mlp = self._parts()
        if cfg.norm_position == "post":
            # original-BERT residual structure: add then norm
            h = mixer(x, mask, positions)
            x = _norm(cfg)(x + h)
            with _mlp_scope(mlp):
                h = mlp(x)
            x = _norm(cfg)(x + h)
        else:
            h = _norm(cfg)(x)
            h = mixer(h, mask, positions)
            x = x + h
            h = _norm(cfg)(x)
            with _mlp_scope(mlp):
                h = mlp(h)
            x = x + h
        return nn.with_logical_constraint(x, ("batch", "seq", "embed"))


def _mlp_scope(mlp):
    """The scope `mlp.dense` around a dense MLP; the experts name their own."""
    return jax.named_scope("mlp.dense") if isinstance(mlp, MlpBlock) \
        else contextlib.nullcontext()


class Encoder(nn.Module):
    """Stack of blocks (used by BERT/ViT encoders and, with causal=True +
    decode, by the Llama decoder)."""

    cfg: TransformerConfig
    decode: bool = False

    def _block_cls(self):
        cfg = self.cfg
        if not cfg.remat:
            return Block
        # the backward pass re-runs the block without the attention op the
        # configuration can be seen to run: what that op's own backward pass
        # reads is kept by the names the op gives it (the indexed attention's
        # output, thresholds and row statistics, so no tile loop runs again;
        # the flash op's output and log-sum-exp, so no kernel is launched again)
        from ...ops import attention, sparse_attention

        names = (sparse_attention.REMAT_SAVED_NAMES if cfg.attn_topk > 0 else ()) \
            + (attention.REMAT_SAVED_NAMES if cfg.attn_impl == "flash" else ())
        policy = jax.checkpoint_policies.save_only_these_names(*names) if names else None
        return nn.remat(Block, static_argnums=(), policy=policy)

    @nn.compact
    def __call__(self, x, mask=None, positions=None):
        cfg = self.cfg
        block_cls = self._block_cls()
        for i in range(cfg.n_layers):
            x = block_cls(cfg, decode=self.decode, layer=i, name=f"layer_{i}")(
                x, mask, positions)
        if cfg.norm_position == "post":
            return x  # post-norm blocks already end normalized
        return _norm(cfg)(x)
