"""Llama-family causal LM (Flax) — backbone for sharded batch inference.

Reference analog: ``hf/HuggingFaceCausalLMTransform.py:103-331`` loads torch
models per-partition; here a native Flax decoder (RMSNorm + SwiGLU + RoPE +
GQA) whose weights shard over the tensor/fsdp mesh axes — the Llama-2-7B
sharded-inference target of BASELINE.md rides this module.

Three builders give `LlamaLM` a decoder that is no Llama: `sparse_moe_lm`
(learned sparse attention, routed experts in every layer),
`hybrid_conv_moe_lm` (gated short-convolution layers among full-attention
layers, a dense lead, sigmoid-routed experts, embedding and head tied) and
`latent_moe_lm` (multi-head latent attention, a dense lead, sigmoid-routed
experts beside a shared expert, untied head). A fourth, `looped_lm`, runs one
stack of sandwich-normed layers several times with shared weights, with an
exit gate and the head at every loop step. A fifth, `window_moe_lm`, mixes
sliding-window and full attention layers of different head counts and
rotary, with a sigmoid gate a head, a dense lead and softmax-routed experts
beside a shared expert.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .transformer import (AttnKind, Encoder, MlpBlock, MoEBlock, TransformerConfig,
                          Yarn, _norm, apply_rope, make_causal_mask,
                          rope_frequencies)

__all__ = ["llama2_7b", "llama_tiny", "sparse_moe_lm", "hybrid_conv_moe_lm",
           "latent_moe_lm", "looped_lm", "window_moe_lm", "next_token_labels", "LlamaLM",
           "generate", "greedy_generate",
           "PagedLlamaLM", "paged_prefill", "paged_decode_step",
           "paged_extend", "paged_verify", "early_exit_params"]


# rows of a looped model's head product alive at a time in training
# (`ops.looped_head`): 201 MB of float32 logits at 49,152 vocabulary rows
LOOP_HEAD_ROWS = 1024


def llama2_7b(**kw) -> TransformerConfig:
    defaults = dict(vocab_size=32000, hidden=4096, n_layers=32, n_heads=32,
                    n_kv_heads=32, mlp_dim=11008, max_len=4096, norm="rmsnorm",
                    act="silu", gated_mlp=True, causal=True, use_rope=True)
    defaults.update(kw)
    return TransformerConfig(**defaults)


def llama_tiny(**kw) -> TransformerConfig:
    defaults = dict(vocab_size=256, hidden=64, n_layers=2, n_heads=4, n_kv_heads=2,
                    mlp_dim=128, max_len=128, norm="rmsnorm", act="silu",
                    gated_mlp=True, causal=True, use_rope=True)
    defaults.update(kw)
    return TransformerConfig(**defaults)


def sparse_moe_lm(**kw) -> TransformerConfig:
    """A decoder with learned sparse attention and routed experts only, at
    the published sizes of Keye-VL-2.0-30B-A3B's language model (config.json of
    Kwai-Keye/Keye-VL-2.0-30B-A3B): GQA 32/4 heads of 128 with per-head q/k
    RMSNorm, an indexer of 16 heads of 64 choosing 2048 keys a query, 128
    gated experts of width 768 with 8 a token and no dense MLP, no biases,
    untied embedding and head. One chip's share of an expert-parallel
    deployment is ``moe_experts`` (held) under ``moe_total_experts`` (routed
    over) from ``moe_first_expert``, and a smaller ``vocab_size``."""
    defaults = dict(vocab_size=151936, hidden=2048, n_layers=48, n_heads=32,
                    n_kv_heads=4, head_dim=128, mlp_dim=768, max_len=262144,
                    norm="rmsnorm", norm_eps=1e-6, act="silu", gated_mlp=True,
                    causal=True, use_rope=True, rope_theta=1e7, attn_bias=False,
                    qk_norm=True, attn_topk=2048, indexer_heads=16,
                    indexer_head_dim=64, moe_experts=128, moe_total_experts=128,
                    moe_top_k=8, moe_dispatch="grouped", moe_bias=False)
    defaults.update(kw)
    return TransformerConfig(**defaults)


def hybrid_conv_moe_lm(**kw) -> TransformerConfig:
    """A decoder of gated short-convolution layers with a full-attention layer
    every fourth, at the published sizes of LFM2-24B-A2B (config.json of
    LiquidAI/LFM2-24B-A2B, `lfm2_moe`): 40 layers, 30 'conv' (3 taps) and 10
    GQA 32/8 heads of 64 with per-head q/k RMSNorm through the flash kernel;
    the two leading layers a dense gated MLP of width 11776, the others 64
    gated experts of width 1536, 4 a token, chosen by sigmoid score plus a
    constant selection bias; no biases; embedding and head tied. One chip's
    share is ``moe_experts`` under ``moe_total_experts`` from
    ``moe_first_expert``, a smaller ``vocab_size``, and fewer layers
    (``n_layers``, ``layer_types``, ``moe_dense_layers`` together)."""
    defaults = dict(vocab_size=65536, hidden=2048, n_layers=40, n_heads=32,
                    n_kv_heads=8, head_dim=64, mlp_dim=11776, moe_mlp_dim=1536,
                    max_len=128000, norm="rmsnorm", norm_eps=1e-5, act="silu",
                    gated_mlp=True, mlp_bias=False, causal=True, use_rope=True,
                    rope_theta=1e6, attn_bias=False, qk_norm=True, attn_impl="flash",
                    flash_block=512, moe_dense_layers=2,
                    moe_experts=64, moe_total_experts=64, moe_top_k=4,
                    moe_dispatch="grouped", moe_bias=False, moe_router="sigmoid",
                    tie_embeddings=True)
    defaults.update(kw)
    if "layer_types" not in defaults:   # the published pattern, as far as n_layers goes
        defaults["layer_types"] = tuple(
            "full_attention" if i % 4 == 2 else "conv"
            for i in range(defaults["n_layers"]))
    return TransformerConfig(**defaults)


def latent_moe_lm(**kw) -> TransformerConfig:
    """A decoder with multi-head latent attention and a shared expert beside
    the routed ones, at the published sizes of Moonlight-16B-A3B (config.json
    of moonshotai/Moonlight-16B-A3B, `deepseek_v3`): 27 layers; 16 heads whose
    queries and keys are 128 + 64 rotary dims wide and whose values are 128,
    keys and values up-projected from one 512-wide normed latent a position,
    the 64-dim rotary key shared by all heads, through the flash kernel; the
    leading layer a dense gated MLP of width 11264, the others 64 gated
    experts of width 1408, 6 a token, chosen by sigmoid score plus a constant
    selection bias, their gates normalised (1e-20) and scaled by 2.446, beside
    a shared expert of width 2 x 1408 that every token passes through; no
    biases; head untied. One chip's share is ``moe_experts`` under
    ``moe_total_experts`` from ``moe_first_expert``, a smaller ``vocab_size``
    and fewer layers (``n_layers``)."""
    defaults = dict(vocab_size=163840, hidden=2048, n_layers=27, n_heads=16,
                    head_dim=192, rope_dim=64, v_head_dim=128, kv_latent_rank=512,
                    mlp_dim=11264, moe_mlp_dim=1408, moe_shared_mlp_dim=2816,
                    max_len=8192, norm="rmsnorm", norm_eps=1e-5, act="silu",
                    gated_mlp=True, mlp_bias=False, causal=True, use_rope=True,
                    rope_theta=50000.0, attn_bias=False, attn_impl="flash",
                    flash_block=512, moe_dense_layers=1,
                    moe_experts=64, moe_total_experts=64, moe_top_k=6,
                    moe_dispatch="grouped", moe_bias=False, moe_router="sigmoid",
                    moe_gate_scale=2.446, moe_gate_eps=1e-20)
    defaults.update(kw)
    return TransformerConfig(**defaults)


def looped_lm(**kw) -> TransformerConfig:
    """A looped decoder at the published sizes of Ouro-2.6B (config.json of
    ByteDance/Ouro-2.6B; the Ouro LoopLM, arXiv:2510.25741): one stack of 48
    layers run 4 times with the same weights (``loop_steps``), each layer
    sandwich-normed (four RMSNorms, eps 1e-6); 16 query and 16 key/value heads
    of 128 through the flash kernel, RoPE (theta 1e6) over all 128 dims, full
    causal attention; a gated SiLU MLP of width 5632; no biases; untied head
    over 49152 rows, with an exit gate beside it at every loop step. One
    pipeline stage of a deployment is fewer layers (``n_layers``)."""
    defaults = dict(vocab_size=49152, hidden=2048, n_layers=48, n_heads=16,
                    n_kv_heads=16, head_dim=128, mlp_dim=5632, max_len=65536,
                    norm="rmsnorm", norm_eps=1e-6, norm_position="sandwich",
                    act="silu", gated_mlp=True, mlp_bias=False, causal=True,
                    use_rope=True, rope_theta=1e6, attn_bias=False,
                    attn_impl="flash", flash_block=512, loop_steps=4)
    defaults.update(kw)
    return TransformerConfig(**defaults)


def window_moe_lm(**kw) -> TransformerConfig:
    """A decoder of sliding-window and full attention layers, at the published
    sizes of Laguna-XS.2 (config.json of poolside/Laguna-XS.2, `laguna`): 40
    layers, full every fourth from layer 0 and sliding (a window of 512 keys)
    between; 8 key/value heads of 128 throughout, 48 query heads on the full
    layers and 64 on the sliding ones, each head's output scaled by a sigmoid
    gate of the layer's input; the full layers turn the leading 64 of 128 dims
    at theta 500000 under YaRN (factor 64 over 4096 positions, beta 64 / 1,
    cos and sin x 1.4158883), the sliding ones all 128 at theta 10000; a
    dense gated MLP of width 8192 in layer 0, then 256 gated experts of width
    512, 8 a token by softmax, the top 8 renormalised and scaled by 2.5,
    beside an ungated shared expert of width 512; RMSNorm 1e-6; no biases;
    untied head over 100352 rows. One chip's share is ``moe_experts`` under
    ``moe_total_experts`` from ``moe_first_expert``, a smaller ``vocab_size``
    and fewer layers (``n_layers`` and ``layer_types`` together)."""
    defaults = dict(vocab_size=100352, hidden=2048, n_layers=40, n_heads=48,
                    n_kv_heads=8, head_dim=128, mlp_dim=8192, moe_mlp_dim=512,
                    moe_shared_mlp_dim=512, max_len=262144, norm="rmsnorm",
                    norm_eps=1e-6, act="silu", gated_mlp=True, mlp_bias=False,
                    causal=True, use_rope=True, rope_theta=500000.0, attn_bias=False,
                    attn_impl="flash", flash_block=512, attn_gate=True,
                    attn_kinds=(("full_attention", AttnKind(
                                    rotary_fraction=0.5,
                                    yarn=Yarn(factor=64.0, original_max_len=4096,
                                              beta_fast=64.0, beta_slow=1.0,
                                              attention_factor=1.4158883083359672))),
                                ("sliding_attention", AttnKind(
                                    heads=64, window=512, rope_theta=10000.0))),
                    moe_dense_layers=1, moe_experts=256, moe_total_experts=256,
                    moe_top_k=8, moe_dispatch="grouped", moe_bias=False,
                    moe_router="softmax", moe_gate_scale=2.5)
    defaults.update(kw)
    if "layer_types" not in defaults:   # the published pattern, as far as n_layers goes
        defaults["layer_types"] = tuple(
            "full_attention" if i % 4 == 0 else "sliding_attention"
            for i in range(defaults["n_layers"]))
    return TransformerConfig(**defaults)


def next_token_labels(input_ids, ignore: int = -100):
    """Labels of a packed row for `Trainer`'s loss: ``labels[:, t]`` is
    ``input_ids[:, t + 1]``, the last position ``ignore`` (negative: left out
    of the mean, `trainer.cross_entropy_loss`)."""
    ids = np.asarray(input_ids)
    last = np.full(ids.shape[:-1] + (1,), ignore, ids.dtype)
    return np.concatenate([ids[..., 1:], last], axis=-1)


class LlamaLM(nn.Module):
    """[B,T] ids -> [B,T,V] logits; decode=True enables the KV cache. Trains
    through `Trainer` on batches ``{"input_ids", "labels"}`` with
    `next_token_labels`. A looped stack (``cfg.loop_steps > 1``) adds an exit
    gate and reads the head at every loop step (``_loop_head``)."""

    cfg: TransformerConfig
    decode: bool = False

    @nn.compact
    def __call__(self, input_ids, positions=None, attention_mask=None, labels=None):
        cfg = self.cfg
        embed = nn.Embed(cfg.vocab_size, cfg.hidden, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype,
                         embedding_init=nn.with_logical_partitioning(
                             nn.initializers.normal(0.02), ("vocab", "embed")),
                         name="embed")
        x = embed(input_ids)
        if cfg.learned_pos:  # GPT-2-family absolute position embeddings
            B, T = input_ids.shape
            pos = (positions if positions is not None
                   else jnp.broadcast_to(jnp.arange(T)[None, :], (B, T)))
            x = x + nn.Embed(
                cfg.max_len, cfg.hidden, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                embedding_init=nn.with_logical_partitioning(
                    nn.initializers.normal(0.02), (None, "embed")),
                name="wpe")(pos)
        mask = None
        if attention_mask is not None:
            mask = attention_mask[:, None, None, :].astype(bool)
        x = Encoder(cfg, decode=self.decode, name="decoder")(x, mask, positions)
        if cfg.loop_steps > 1:
            return self._loop_head(x, labels)
        if cfg.tie_embeddings:
            # one leaf, used twice: its gradient is the sum of both uses. The
            # product as the untied head's (float32 operands and result)
            return jnp.einsum("bth,vh->btv", x.astype(jnp.float32),
                              embed.embedding.astype(jnp.float32))
        return nn.Dense(cfg.vocab_size, use_bias=False, dtype=jnp.float32,
                        param_dtype=cfg.param_dtype,
                        kernel_init=nn.with_logical_partitioning(
                            nn.initializers.normal(0.02), ("embed", "vocab")),
                        name="lm_head")(x)

    def _loop_head(self, states, labels):
        """Every loop step's exit gate and head, device scope ``loop.head``,
        over the steps' normed states [R, B, T, H]. Without labels: the last
        step's logits [B, T, V] (every step runs, as an exit threshold of 1
        has it). With labels [B, T] (training): the cross-entropy of every
        step and token [R, B, T], by blocks of ``LOOP_HEAD_ROWS`` rows
        (``ops.looped_head``: no step's logits are ever whole), and the gate
        logits [R, B, T] float32 (`trainer.exit_weighted_loss` takes both)."""
        from ...ops.looped_head import head_nll

        cfg = self.cfg
        if cfg.tie_embeddings:
            raise ValueError("a looped stack reads an untied head (tie_embeddings=False)")
        with jax.named_scope("loop.head"):
            kernel = _HeadKernel(cfg, name="lm_head")()
            f32 = states.astype(jnp.float32)
            gate = nn.Dense(1, dtype=jnp.float32, param_dtype=cfg.param_dtype,
                            kernel_init=nn.with_logical_partitioning(
                                nn.initializers.normal(0.02), ("embed", None)),
                            bias_init=nn.with_logical_partitioning(
                                nn.initializers.zeros, (None,)),
                            name="exit_gate")(f32)[..., 0]
            if labels is None:
                return jnp.einsum("bth,hv->btv", f32[-1], kernel)
            R, B, T, H = states.shape
            nll = head_nll(f32.reshape(R * B * T, H), kernel,
                           jnp.broadcast_to(labels[None], (R, B, T)).reshape(-1),
                           LOOP_HEAD_ROWS).reshape(R, B, T)
            return nll, gate


class _HeadKernel(nn.Module):
    """The untied head's kernel [H, V] under the name ``nn.Dense`` gives it
    (``lm_head/kernel``), for a caller that takes the product itself."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self):
        cfg = self.cfg
        return self.param("kernel", nn.with_logical_partitioning(
            nn.initializers.normal(0.02), ("embed", "vocab")),
            (cfg.hidden, cfg.vocab_size), cfg.param_dtype)


def _make_selector(temperature: float, top_k: int | None, top_p: float | None):
    """Token-selection fn [B,V] logits, key -> [B] ids. temperature<=0 is
    greedy argmax; otherwise categorical sampling with optional top-k then
    nucleus (top-p) filtering — the reference forwards the same HF generate
    kwargs (``hf/HuggingFaceCausalLMTransform.py:284-331``). All branches are
    resolved at trace time (the args are Python constants), so the compiled
    program contains only the selected path."""
    if temperature is None or temperature <= 0.0:
        def select(logits, key):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return select

    def select(logits, key):
        l = logits.astype(jnp.float32) / temperature
        V = l.shape[-1]
        # sort only the surviving support: top_k bounds the sort width, and
        # renormalizing inside the kept set (softmax over the k values) is
        # exactly HF's filter order (top_k mask, then nucleus on the
        # renormalized remainder)
        k = top_k if (top_k is not None and 0 < top_k < V) else V
        if top_p is not None and top_p < 1.0:
            vals, idx = jax.lax.top_k(l, k)  # [B, k] descending
            probs = jax.nn.softmax(vals, axis=-1)
            # keep tokens whose EXCLUSIVE cumulative mass is < top_p (the
            # highest-prob token always survives)
            keep = (jnp.cumsum(probs, axis=-1) - probs) < top_p
            masked = jnp.where(keep, vals, -jnp.inf)
            j = jax.random.categorical(key, masked, axis=-1)
            return jnp.take_along_axis(idx, j[:, None], axis=1)[:, 0].astype(jnp.int32)
        if k < V:
            vals, idx = jax.lax.top_k(l, k)
            j = jax.random.categorical(key, vals, axis=-1)
            return jnp.take_along_axis(idx, j[:, None], axis=1)[:, 0].astype(jnp.int32)
        return jax.random.categorical(key, l, axis=-1).astype(jnp.int32)
    return select


def generate(model: LlamaLM, params, prompt_ids: jax.Array, max_new_tokens: int,
             eos_id: int | None = None,
             prompt_mask: jax.Array | None = None,
             temperature: float = 0.0,
             top_k: int | None = None,
             top_p: float | None = None,
             rng: jax.Array | None = None) -> jax.Array:
    """Prefill + lax.while_loop decode with KV cache — all static shapes.

    prompt_ids: [B, P] padded to a fixed prompt bucket; ``prompt_mask`` [B, P]
    marks real tokens (1) vs right-padding (0). Padded positions are masked out
    of attention and the first generated token reads the logits of the LAST
    REAL prompt token, not the pad tail. Generated tokens land at P, P+1, …
    regardless of per-row prompt length (uniform layout for unpadding).
    Returns [B, P + max_new_tokens].

    temperature<=0 decodes greedily; otherwise sampling runs fully on-device
    (jax.random.categorical with a per-step key folded from ``rng``), with
    optional top_k and nucleus top_p filtering.
    """
    B, P = prompt_ids.shape
    cfg = model.cfg
    if P + max_new_tokens > cfg.max_len:
        raise ValueError(
            f"prompt ({P}) + max_new_tokens ({max_new_tokens}) exceeds the KV "
            f"cache capacity max_len={cfg.max_len}; dynamic_update_slice would "
            f"silently clamp and corrupt the cache")
    if prompt_mask is None:
        prompt_mask = jnp.ones((B, P), jnp.int32)
    prompt_mask = prompt_mask.astype(jnp.int32)
    lengths = jnp.sum(prompt_mask, axis=-1)  # [B]
    select = _make_selector(temperature, top_k, top_p)
    if rng is None:
        rng = jax.random.PRNGKey(0)

    vars0 = model.init(jax.random.PRNGKey(0), jnp.zeros((B, 1), jnp.int32),
                       positions=jnp.zeros((B, 1), jnp.int32))
    cache0 = vars0["cache"]

    # kv-cache-wide validity: prompt pads stay masked for the whole decode
    kv_mask = jnp.zeros((B, cfg.max_len), jnp.int32)
    kv_mask = jax.lax.dynamic_update_slice(kv_mask, prompt_mask, (0, 0))
    kv_mask = kv_mask.at[:, P:].set(1)  # generated positions are always real

    prefill_pos = jnp.broadcast_to(jnp.arange(P)[None, :], (B, P))
    logits, state = model.apply({"params": params, "cache": cache0}, prompt_ids,
                                positions=prefill_pos, mutable=["cache"],
                                attention_mask=kv_mask)
    last_real = jnp.take_along_axis(logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
    next_tok = select(last_real, jax.random.fold_in(rng, 0))

    total = P + max_new_tokens
    out = jnp.zeros((B, total), jnp.int32).at[:, :P].set(prompt_ids)
    out = out.at[:, P].set(next_tok)

    def cond(carry):
        i, _, _, done = carry
        return jnp.logical_and(i < max_new_tokens - 1, ~jnp.all(done))

    def body(carry):
        i, out, cache, done = carry
        tok = jax.lax.dynamic_slice(out, (0, P + i), (B, 1))
        # cache slot is P+i (static layout); RoPE position is the per-row true
        # token index so padded prompts keep correct relative distances
        pos = (lengths + i)[:, None].astype(jnp.int32)
        logits, st = model.apply({"params": params, "cache": cache}, tok,
                                 positions=pos, mutable=["cache"],
                                 attention_mask=kv_mask)
        nxt = select(logits[:, -1, :], jax.random.fold_in(rng, i + 1))
        if eos_id is not None:
            done = jnp.logical_or(done, nxt == eos_id)
            nxt = jnp.where(done, eos_id, nxt)
        out = jax.lax.dynamic_update_slice(out, nxt[:, None], (0, P + i + 1))
        return i + 1, out, st["cache"], done

    done0 = jnp.zeros((B,), bool)
    if eos_id is not None:
        done0 = next_tok == eos_id
    _, out, _, _ = jax.lax.while_loop(cond, body, (jnp.zeros((), jnp.int32), out,
                                                   state["cache"], done0))
    return out


def greedy_generate(model: LlamaLM, params, prompt_ids: jax.Array,
                    max_new_tokens: int, eos_id: int | None = None,
                    prompt_mask: jax.Array | None = None) -> jax.Array:
    """Greedy decode — ``generate`` at temperature 0 (kept as the stable
    name used by serving and tests)."""
    return generate(model, params, prompt_ids, max_new_tokens, eos_id=eos_id,
                    prompt_mask=prompt_mask, temperature=0.0)


# ---------------------------------------------------------------------------
# Paged/block KV cache (token-granular continuous batching)
# ---------------------------------------------------------------------------
#
# The dense decode path above allocates a [B, max_len] KV cache per batch
# row, so a finished sequence's cache stays pinned until the whole batch
# exits the while_loop (run-to-completion). The paged variant keys KV storage
# off a fixed physical pool of (n_blocks, block_len, kv_heads, head_dim)
# pages plus a per-sequence BLOCK TABLE of page indices: sequences of any
# length share one pool, a finished sequence's pages free immediately, and
# the decode step is a single-token program whose only batch dimension is
# the number of ACTIVE SLOTS — the vLLM PagedAttention layout expressed as
# pure gather/scatter XLA (no custom kernel), which is what the TPU/CPU
# backends compile well today. Block id 0 is RESERVED as the trash page:
# padded prompt positions and inactive slots write there, so live pages are
# never aliased (property-tested in tests/test_paged_llm.py).
#
# The modules below mirror LlamaLM's module tree name-for-name (embed /
# decoder.layer_i.{RMSNorm_0,RMSNorm_1,attn.{q,k,v,o},mlp} / lm_head), so
# one param pytree drives both the dense and the paged path — a checkpoint
# published for `LlamaLM` serves paged with zero conversion, and greedy
# paged decode is token-for-token identical to `greedy_generate`.


class PagedAttention(nn.Module):
    """GQA attention over a paged KV pool.

    ``mode='prefill'``: self-attention over the (padded) prompt with a
    causal + pad mask, writing each REAL token's K/V into its page slot.
    ``mode='decode'``: one query token per slot; K/V gathered from the pool
    through the block table (pages in table order hold the sequence's
    contiguous logical token stream).

    Param tree is identical to :class:`~.transformer.Attention` (same
    ``q/k/v/o`` DenseGeneral submodules, same init), so params are shared
    with the dense path."""

    cfg: TransformerConfig
    block_len: int
    mode: str  # 'prefill' | 'decode'

    @nn.compact
    def __call__(self, x, k_pages, v_pages, block_tables, positions,
                 write_pos, kv_mask_len):
        """x: [B,T,hidden] (T=1 in decode). positions: [B,T] RoPE positions.
        write_pos: [B,T] page-slot index per token (-1 = don't write, goes
        to the trash page). kv_mask_len: [B] number of attendable logical
        positions (prefill: the padded prompt width with a pad mask handled
        by caller-supplied write_pos; decode: seq_len+1 incl. this token),
        or [B,T] per-token visibility horizons for multi-token decode-mode
        windows (suffix-extend prefill over a cached prefix, speculative
        verify). Returns (out, k_pages, v_pages)."""
        cfg = self.cfg
        B, T, _ = x.shape
        H, KV, D = cfg.n_heads, cfg.kv_heads, cfg.head_dim
        bl = self.block_len
        dense = lambda name, heads: nn.DenseGeneral(  # noqa: E731
            features=(heads, D), axis=-1, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.xavier_uniform(), ("embed", "heads", "kv")),
            bias_init=nn.with_logical_partitioning(
                nn.initializers.zeros, ("heads", "kv")),
            name=name)
        q = dense("q", H)(x)
        k = dense("k", KV)(x)
        v = dense("v", KV)(x)
        if cfg.use_rope:
            cos_np, sin_np = rope_frequencies(D, cfg.max_len, cfg.rope_theta)
            cos, sin = jnp.asarray(cos_np), jnp.asarray(sin_np)
            q = apply_rope(q, cos, sin, positions)
            k = apply_rope(k, cos, sin, positions)

        # ---- scatter K/V into the pool (trash page 0 absorbs non-writes) --
        n_blocks = k_pages.shape[0]
        block_of = jnp.take_along_axis(
            block_tables, jnp.maximum(write_pos, 0) // bl, axis=1)  # [B,T]
        flat_idx = block_of * bl + jnp.maximum(write_pos, 0) % bl
        flat_idx = jnp.where(write_pos >= 0, flat_idx, 0).reshape(-1)
        k_flat = k_pages.reshape(n_blocks * bl, KV, D)
        v_flat = v_pages.reshape(n_blocks * bl, KV, D)
        k_flat = k_flat.at[flat_idx].set(k.reshape(B * T, KV, D)
                                         .astype(k_flat.dtype))
        v_flat = v_flat.at[flat_idx].set(v.reshape(B * T, KV, D)
                                         .astype(v_flat.dtype))

        if self.mode == "prefill":
            # prompt is self-contained: attend over the in-flight K/V (not
            # the pool), causal + pad mask. Pads carry write_pos=-1.
            mask = (write_pos >= 0)[:, None, None, :]
            causal = make_causal_mask(T, T)
            mask = jnp.logical_and(mask, causal)
            kk, vv = k, v
        else:
            # decode: gather this slot's logical KV stream from the pool
            L = block_tables.shape[1] * bl
            gather_idx = (block_tables[:, :, None] * bl
                          + jnp.arange(bl)[None, None, :]).reshape(B, L)
            kk = k_flat[gather_idx]                      # [B, L, KV, D]
            vv = v_flat[gather_idx]
            if kv_mask_len.ndim == 2:
                # per-token horizon [B,T]: the scatter above runs BEFORE this
                # gather, so an in-window token already sees earlier window
                # tokens through the pool — a growing horizon per token is
                # exactly intra-window causality
                mask = (jnp.arange(L)[None, None, :]
                        < kv_mask_len[:, :, None])[:, None, :, :]
            else:
                mask = (jnp.arange(L)[None, :]
                        < kv_mask_len[:, None])[:, None, None, :]
        if KV != H:
            kk = jnp.repeat(kk, H // KV, axis=2)
            vv = jnp.repeat(vv, H // KV, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk) \
            / jnp.sqrt(D).astype(cfg.dtype)
        scores = jnp.where(mask, scores, jnp.finfo(cfg.dtype).min)
        probs = jax.nn.softmax(scores.astype(jnp.float32),
                               axis=-1).astype(cfg.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, vv)
        out = nn.DenseGeneral(
            features=cfg.hidden, axis=(-2, -1), dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.xavier_uniform(), ("heads", "kv", "embed")),
            bias_init=nn.with_logical_partitioning(
                nn.initializers.zeros, ("embed",)),
            name="o")(out)
        return out, k_flat.reshape(k_pages.shape), v_flat.reshape(v_pages.shape)


class PagedBlock(nn.Module):
    """Pre-norm Block with paged attention; param names match
    :class:`~.transformer.Block` (two anonymous norms in the same creation
    order, ``attn``, ``mlp``)."""

    cfg: TransformerConfig
    block_len: int
    mode: str

    @nn.compact
    def __call__(self, x, k_pages, v_pages, block_tables, positions,
                 write_pos, kv_mask_len):
        cfg = self.cfg
        mlp_cls = MoEBlock if cfg.moe_experts > 0 else MlpBlock
        h = _norm(cfg)(x)
        h, k_pages, v_pages = PagedAttention(
            cfg, self.block_len, self.mode, name="attn")(
                h, k_pages, v_pages, block_tables, positions, write_pos,
                kv_mask_len)
        x = x + h
        h = _norm(cfg)(x)
        h = mlp_cls(cfg, name="mlp")(h)
        return x + h, k_pages, v_pages


class PagedEncoder(nn.Module):
    """Layer stack threading the page pool — a TUPLE of per-layer
    ``[n_blocks, block_len, KV, D]`` arrays, NOT one stacked array: each
    layer's scatter then updates only its own pool leaf, which XLA turns
    into an in-place dynamic-update under buffer donation. A stacked pool
    costs a full-stack copy per layer per step (measured 2.3x on the CPU
    A/B)."""

    cfg: TransformerConfig
    block_len: int
    mode: str

    @nn.compact
    def __call__(self, x, k_pages, v_pages, block_tables, positions,
                 write_pos, kv_mask_len):
        cfg = self.cfg
        k_out, v_out = list(k_pages), list(v_pages)
        for i in range(cfg.n_layers):
            x, k_out[i], v_out[i] = PagedBlock(cfg, self.block_len, self.mode,
                                               name=f"layer_{i}")(
                x, k_pages[i], v_pages[i], block_tables, positions,
                write_pos, kv_mask_len)
        return _norm(cfg)(x), tuple(k_out), tuple(v_out)


class PagedLlamaLM(nn.Module):
    """[B,T] ids -> ([B,T,V] logits, updated page pool). ``k_pages`` /
    ``v_pages`` are tuples of per-layer ``[n_blocks, block_len, KV, D]``
    arrays. Same param pytree as :class:`LlamaLM` — one checkpoint drives
    both engines."""

    cfg: TransformerConfig
    block_len: int
    mode: str = "decode"

    @nn.compact
    def __call__(self, input_ids, k_pages, v_pages, block_tables, positions,
                 write_pos, kv_mask_len):
        cfg = self.cfg
        if cfg.norm_position != "pre" or cfg.learned_pos:
            raise ValueError("the paged engine supports pre-norm RoPE/causal "
                             "decoder configs (the Llama family)")
        x = nn.Embed(cfg.vocab_size, cfg.hidden, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype,
                     embedding_init=nn.with_logical_partitioning(
                         nn.initializers.normal(0.02), ("vocab", "embed")),
                     name="embed")(input_ids)
        x, k_pages, v_pages = PagedEncoder(
            cfg, self.block_len, self.mode, name="decoder")(
                x, k_pages, v_pages, block_tables, positions, write_pos,
                kv_mask_len)
        logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=jnp.float32,
                          param_dtype=cfg.param_dtype,
                          kernel_init=nn.with_logical_partitioning(
                              nn.initializers.normal(0.02), ("embed", "vocab")),
                          name="lm_head")(x)
        return logits, k_pages, v_pages


def paged_prefill(cfg: TransformerConfig, block_len: int, params,
                  prompt_ids: jax.Array, prompt_mask: jax.Array,
                  block_tables: jax.Array, k_pages: jax.Array,
                  v_pages: jax.Array):
    """Prompt -> (last-real-token logits [B,V], updated pages).

    ``prompt_ids``/``prompt_mask``: [B,P] right-padded to a seq-ladder
    bucket; real token t writes K/V into page ``block_tables[b, t//bl]``
    slot ``t%bl`` (pads go to the trash page), so each sequence's pages hold
    its dense logical token stream with no pad holes."""
    B, P = prompt_ids.shape
    t_idx = jnp.broadcast_to(jnp.arange(P)[None, :], (B, P))
    write_pos = jnp.where(prompt_mask > 0, t_idx, -1)
    lengths = jnp.sum(prompt_mask.astype(jnp.int32), axis=-1)
    model = PagedLlamaLM(cfg, block_len, mode="prefill")
    logits, k_pages, v_pages = model.apply(
        {"params": params}, prompt_ids, k_pages, v_pages, block_tables,
        t_idx, write_pos, lengths)
    last = jnp.take_along_axis(
        logits, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)[:, 0]
    return last, k_pages, v_pages


def paged_decode_step(cfg: TransformerConfig, block_len: int, params,
                      tokens: jax.Array, seq_lens: jax.Array,
                      active: jax.Array, block_tables: jax.Array,
                      k_pages: jax.Array, v_pages: jax.Array):
    """One token per active slot -> (logits [S,V], updated pages).

    ``tokens``: [S] current token per slot; ``seq_lens``: [S] tokens already
    in the sequence BEFORE this one (= this token's logical position);
    ``active``: [S] bool — padded slots write to the trash page and produce
    garbage logits the scheduler ignores."""
    S = tokens.shape[0]
    positions = seq_lens[:, None].astype(jnp.int32)
    write_pos = jnp.where(active[:, None], positions, -1)
    kv_mask_len = jnp.where(active, seq_lens + 1, 1)
    model = PagedLlamaLM(cfg, block_len, mode="decode")
    logits, k_pages, v_pages = model.apply(
        {"params": params}, tokens[:, None], k_pages, v_pages, block_tables,
        positions, write_pos, kv_mask_len)
    return logits[:, 0], k_pages, v_pages


def paged_extend(cfg: TransformerConfig, block_len: int, params,
                 suffix_ids: jax.Array, suffix_mask: jax.Array,
                 start_pos: jax.Array, block_tables: jax.Array,
                 k_pages: jax.Array, v_pages: jax.Array):
    """Suffix prefill over a PREFIX-CACHED sequence -> (last-real logits
    [B,V], updated pages).

    ``suffix_ids``/``suffix_mask``: [B,Q] right-padded uncached tail of the
    prompt; ``start_pos``: [B] logical position of the suffix's first token
    (= tokens already resident in the sequence's pages from the prefix
    cache). Runs in decode mode so every suffix token attends over the
    POOLED prefix K/V through the block table; the per-token ``kv_mask_len``
    horizon keeps the window causal while the prompt-style ``write_pos``
    lands each real suffix token in its page slot."""
    B, Q = suffix_ids.shape
    t_idx = jnp.broadcast_to(jnp.arange(Q)[None, :], (B, Q))
    positions = start_pos[:, None].astype(jnp.int32) + t_idx
    write_pos = jnp.where(suffix_mask > 0, positions, -1)
    kv_mask_len = jnp.where(suffix_mask > 0, positions + 1, 1)
    lengths = jnp.sum(suffix_mask.astype(jnp.int32), axis=-1)
    model = PagedLlamaLM(cfg, block_len, mode="decode")
    logits, k_pages, v_pages = model.apply(
        {"params": params}, suffix_ids, k_pages, v_pages, block_tables,
        positions, write_pos, kv_mask_len)
    last = jnp.take_along_axis(
        logits, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)[:, 0]
    return last, k_pages, v_pages


def paged_verify(cfg: TransformerConfig, block_len: int, params,
                 tokens: jax.Array, seq_lens: jax.Array, active: jax.Array,
                 block_tables: jax.Array, k_pages: jax.Array,
                 v_pages: jax.Array):
    """Speculative verify window -> (logits [S,W,V], updated pages).

    ``tokens``: [S,W] per slot — the last committed token followed by W-1
    draft tokens; ``seq_lens``: [S] tokens already in the pages BEFORE this
    window (= the first window token's logical position); ``active``: [S].
    One forward scores every draft position (logits[s,t] predicts the token
    AFTER tokens[s,t]); rejected drafts' page writes sit past the sequence's
    committed ``tokens_in_pages`` and are overwritten by later steps, so no
    rollback scatter is needed."""
    S, W = tokens.shape
    t_idx = jnp.broadcast_to(jnp.arange(W)[None, :], (S, W))
    positions = seq_lens[:, None].astype(jnp.int32) + t_idx
    write_pos = jnp.where(active[:, None], positions, -1)
    kv_mask_len = jnp.where(active[:, None], positions + 1, 1)
    model = PagedLlamaLM(cfg, block_len, mode="decode")
    logits, k_pages, v_pages = model.apply(
        {"params": params}, tokens, k_pages, v_pages, block_tables,
        positions, write_pos, kv_mask_len)
    return logits, k_pages, v_pages


def early_exit_params(params, n_layers: int):
    """Host-side subset of a ``LlamaLM``/``PagedLlamaLM`` param tree for an
    EARLY-EXIT draft model: keeps ``embed``, ``lm_head``, the decoder's
    final norm (``RMSNorm_0``) and only ``layer_i`` for ``i < n_layers``.
    Applying the paged modules with ``dataclasses.replace(cfg,
    n_layers=n_layers)`` over this subset is the self-draft forward — no
    second checkpoint, no re-init."""
    dec = params["decoder"]
    sub = {}
    for k, v in dec.items():
        if k.startswith("layer_"):
            if int(k.split("_", 1)[1]) < n_layers:
                sub[k] = v
        else:
            sub[k] = v
    out = {k: v for k, v in params.items() if k != "decoder"}
    out["decoder"] = sub
    return out
