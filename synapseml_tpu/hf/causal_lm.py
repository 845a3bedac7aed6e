"""HuggingFaceCausalLM (reference ``hf/HuggingFaceCausalLMTransform.py:103-331``).

Batch LLM inference as a Transformer: prompts (or chat message lists) ->
tokenize -> pad to a static prompt bucket -> jitted prefill+decode
(``greedy_generate``: KV cache, lax.while_loop, early EOS exit) -> detokenize.
``engine="paged"`` swaps the decode core for the token-granular paged-KV
engine (``models/paged_engine.py``) — early-EOS rows free their pages and
decode slots mid-batch — and ``serving_engine()`` exposes the SAME engine
to ``io.serving.serve_llm`` for online token streaming.

Model loading: ``set_params`` with a flax param pytree (e.g. restored from an
orbax checkpoint), or random init from the architecture preset for smoke
tests. Tokenization: a transformers tokenizer when available locally
(decode-capable), else token-id passthrough columns.
"""

from __future__ import annotations

import numpy as np

from ..core import batching as cb
from ..core.dataframe import DataFrame
from ..core.params import ComplexParam, Param, TypeConverters
from ..core.pipeline import Transformer
from ..models.flax_nets.llama import LlamaLM, generate, llama2_7b, llama_tiny
__all__ = ["HuggingFaceCausalLM", "CausalLMServingEngine"]

_ARCHS = {"llama2-7b": llama2_7b, "llama-tiny": llama_tiny}


def default_chat_template(messages) -> str:
    """Minimal chat template (reference applies the HF tokenizer's template;
    ``HuggingFaceCausalLMTransform.py`` chat mode)."""
    parts = [f"<|{m['role']}|>\n{m['content']}" for m in messages]
    return "\n".join(parts) + "\n<|assistant|>\n"


class HuggingFaceCausalLM(Transformer):
    feature_name = "hf"

    model_name = Param("model_name", "architecture preset or local HF checkpoint dir",
                       default="llama-tiny")
    model_params = ComplexParam("model_params", "flax param pytree (None = random init)",
                                default=None)
    tokenizer = ComplexParam("tokenizer", "tokenizer spec/object", default=None)
    input_col = Param("input_col", "prompt text column (completion mode)",
                      default="prompt")
    messages_col = Param("messages_col", "chat messages column (chat mode, "
                         "takes precedence when set)", default=None)
    output_col = Param("output_col", "generated text column", default="completions")
    max_new_tokens = Param("max_new_tokens", "tokens to generate", default=32,
                           converter=TypeConverters.to_int)
    prompt_bucket = Param("prompt_bucket", "pad prompts to multiples of this",
                          default=64, converter=TypeConverters.to_int)
    batch_size = Param("batch_size", "rows per padded device batch", default=8,
                       converter=TypeConverters.to_int)
    eos_id = Param("eos_id", "stop token id", default=None)
    do_sample = Param("do_sample", "sample instead of greedy decode (the "
                      "reference forwards HF generate kwargs, "
                      "HuggingFaceCausalLMTransform.py:284-331)", default=False,
                      converter=TypeConverters.to_bool)
    temperature = Param("temperature", "softmax temperature when sampling",
                        default=1.0, converter=TypeConverters.to_float)
    top_k = Param("top_k", "restrict sampling to the k most likely tokens "
                  "(None = no limit)", default=None)
    top_p = Param("top_p", "nucleus sampling: smallest token set with "
                  "cumulative probability >= top_p (None = no limit)",
                  default=None)
    seed = Param("seed", "on-device RNG seed for sampling; a fixed seed makes "
                 "sampled generation deterministic", default=0,
                 converter=TypeConverters.to_int)
    mesh_config = ComplexParam(
        "mesh_config", "MeshConfig for sharded inference: params shard over "
        "tensor/fsdp axes per the partition rule table (the Llama-2-7B "
        "sharded-batch-inference BASELINE config)", default=None)
    partition_rules = ComplexParam(
        "partition_rules", "parallel.partition.PartitionRules regex table "
        "placing the plain param pytree on the mesh (None = the default "
        "Llama table). Rides registry manifests' `sharding` section so a "
        "published sharded model re-applies its placement at /admin/load",
        default=None)
    generation_params_col = Param(
        "generation_params_col", "optional column of per-row dicts of "
        "generate kwargs (max_new_tokens/do_sample/temperature/top_k/top_p/"
        "seed/eos_id) overriding the transformer-level params — the "
        "reference forwards per-call HF generate kwargs "
        "(HuggingFaceCausalLMTransform.py:284-331). Rows are BUCKETED by "
        "identical config so the jit cache stays bounded by the number of "
        "distinct configs, not rows", default=None)
    engine = Param(
        "engine", "decode engine: 'dense' (run-to-completion lax.while_loop "
        "generate) or 'paged' (token-granular paged-KV continuous batching "
        "— models/paged_engine.py; greedy output is token-identical, "
        "early-EOS rows free their KV pages and decode slots immediately). "
        "Online serving (io.serving.serve_llm) always rides the paged "
        "engine; this picks the offline transform() path", default="dense")
    kv_block_len = Param("kv_block_len", "paged engine: tokens per KV page",
                         default=16, converter=TypeConverters.to_int)
    kv_blocks = Param("kv_blocks", "paged engine: physical KV pages in the "
                      "pool (None = enough for decode_slots x max_len)",
                      default=None)
    decode_slots = Param("decode_slots", "paged engine: max concurrently "
                         "decoding sequences (None = batch_size)",
                         default=None)
    prefix_cache = Param(
        "prefix_cache", "paged engine: content-hash full KV pages so "
        "sequences sharing a prompt prefix (chat system prompts, RAG "
        "templates) reuse resident pages and prefill only the uncached "
        "suffix (models/prefix_cache.py; token-identical output)",
        default=False, converter=TypeConverters.to_bool)
    draft_tokens = Param(
        "draft_tokens", "paged engine: greedy speculative decoding — draft "
        "this many tokens per step and verify them in ONE paged forward "
        "(0 = off; requires greedy decode, and accepted tokens are "
        "token-identical to plain decode)", default=0,
        converter=TypeConverters.to_int)
    drafter_ref = Param(
        "drafter_ref", "paged engine: who drafts when draft_tokens > 0 — "
        "None/'self' self-drafts via early exit at half the layers, "
        "'self:<n>' picks the exit layer, any other value resolves a small "
        "drafter model like model_name (architecture preset or local "
        "checkpoint dir)", default=None)

    _CACHE_KEYS = frozenset({"model_name", "model_params", "tokenizer",
                             "mesh_config", "partition_rules",
                             "max_new_tokens", "eos_id",
                             "do_sample", "temperature", "top_k", "top_p",
                             "seed", "engine", "kv_block_len", "kv_blocks",
                             "decode_slots", "prefix_cache", "draft_tokens",
                             "drafter_ref"})

    def set(self, **kw):
        out = super().set(**kw)
        if self._CACHE_KEYS & kw.keys():
            self.__dict__.pop("_cache_model", None)
            self.__dict__.pop("_cache_engines", None)
            cb.invalidate_token(self)  # cached executables captured old state
        return out

    # ---- lazy model/tokenizer ----
    def _model_and_params(self):
        if self.__dict__.get("_cache_model") is None:
            # pretrained-dir or preset (the reference's
            # AutoModelForCausalLM.from_pretrained path,
            # hf/HuggingFaceCausalLMTransform.py:103-331)
            from ..models.convert_hf import pretrained_causal_lm, resolve_model_source

            cfg, loaded, tok = resolve_model_source(
                self.get("model_name"), _ARCHS, self.get("tokenizer"),
                pretrained_causal_lm)
            params = self.get("model_params")
            if params is None:
                params = loaded
            model = LlamaLM(cfg, decode=True)  # KV-cache mode for generate
            if params is None:
                import jax
                import jax.numpy as jnp

                B, T = 1, 8
                variables = LlamaLM(cfg).init(jax.random.PRNGKey(0),
                                              jnp.zeros((B, T), jnp.int32))
                params = variables["params"]
            mesh = None
            if self.get("mesh_config") is not None:
                # sharded batch inference: weights distribute over the mesh
                # per the declarative partition rule table (plain pytree —
                # no eval_shape rebox, no nn.Partitioned metadata needed);
                # XLA inserts the activation collectives during generate
                import jax

                from ..models.convert_hf import shard_pretrained_params
                from flax.core import meta

                plain = jax.tree.map(
                    lambda x: x.value if isinstance(x, meta.Partitioned) else x,
                    params, is_leaf=lambda x: isinstance(x, meta.Partitioned))
                mesh, params = shard_pretrained_params(
                    plain, self.get("mesh_config"),
                    self.get("partition_rules"))
            else:
                import jax

                # ONE device copy shared by the dense and paged engines: a
                # checkpoint directory loads as host numpy, and a host tree
                # passed to a jitted step is re-uploaded on every call
                params = jax.device_put(params)
            self.__dict__["_cache_model"] = (model, params, tok, mesh)
        return self.__dict__["_cache_model"]

    _GEN_KEYS = ("max_new_tokens", "eos_id", "do_sample", "temperature",
                 "top_k", "top_p", "seed")

    def _effective_gen_cfg(self, overrides=None) -> dict:
        """Transformer-level generation params overlaid with a per-row
        override dict (the per-call generate-kwargs surface)."""
        eff = {k: self.get(k) for k in self._GEN_KEYS}
        if overrides:
            unknown = sorted(set(overrides) - set(self._GEN_KEYS))
            if unknown:
                raise ValueError(
                    f"unsupported generation params {unknown}; "
                    f"supported: {list(self._GEN_KEYS)}")
            eff.update(overrides)
        eff["max_new_tokens"] = int(eff["max_new_tokens"])
        return eff

    def _generate_fn(self, B: int, P: int, eff: dict):
        """Per-(batch bucket, prompt bucket, generation config) executable
        through the CompiledCache — the jit population stays bounded by
        ladder size x distinct configs, LRU-evicted, and its misses/trace
        times are observable."""
        eff_key = tuple(eff[k] for k in self._GEN_KEYS)

        def build():
            import jax

            model, params, _, mesh = self._model_and_params()
            sampling = eff["do_sample"]
            temperature = float(eff["temperature"]) if sampling else 0.0
            top_k = eff["top_k"]
            top_p = eff["top_p"]
            rng = jax.random.PRNGKey(int(eff["seed"])) if sampling else None

            def fn(p, ids, mask, offset):
                # fold the batch's global row offset into the stream so
                # identical prompts in different batches draw different
                # samples (same seed + same data stays reproducible)
                r = None if rng is None else jax.random.fold_in(rng, offset)
                return generate(model, p, ids,
                                eff["max_new_tokens"],
                                eos_id=eff["eos_id"],
                                prompt_mask=mask,
                                temperature=temperature,
                                top_k=None if top_k is None else int(top_k),
                                top_p=None if top_p is None else float(top_p),
                                rng=r)

            if mesh is not None:
                dp = mesh.data_parallel_size()
                if B % dp:
                    raise ValueError(
                        f"batch_size ({B}) must be a multiple of the mesh "
                        f"data-parallel size ({dp}) for sharded generation")
                # params ride as a jit ARGUMENT (a closure over weights
                # that span other processes is rejected) and outputs pin
                # replicated, so every process holds the full generated
                # ids even when the weights span hosts
                jitted = jax.jit(fn, out_shardings=mesh.replicated())

                def run(ids, mask, offset, _j=jitted, _m=mesh):
                    with _m.scope():
                        # batch shards over data/fsdp; params already placed
                        return _j(params, _m.shard_batch(ids),
                                  _m.shard_batch(mask), offset)

                return run
            # params are a jit ARGUMENT here too: closed over, the weights
            # would be baked into the program as constants — gigabytes of
            # HLO literal at real widths
            jitted = jax.jit(fn)
            return lambda ids, mask, offset: jitted(params, ids, mask, offset)

        return cb.get_compiled_cache().get(
            "hf_causal_lm", (B, P) + eff_key, build,
            instance=cb.instance_token(self), dtype="int32")

    def _resolve_drafter(self, cfg):
        """Resolve ``drafter_ref`` into engine knobs: (draft_layers,
        drafter). ``None``/``'self'`` self-drafts at half the layers,
        ``'self:<n>'`` picks the early-exit layer, anything else loads a
        small drafter model through the same source-resolution path as
        ``model_name``."""
        if int(self.get("draft_tokens") or 0) <= 0:
            return None, None
        ref = self.get("drafter_ref")
        if ref is None or ref == "self":
            return None, None  # engine default: early exit at n_layers // 2
        if isinstance(ref, str) and ref.startswith("self:"):
            return int(ref.split(":", 1)[1]), None
        from ..models.convert_hf import (pretrained_causal_lm,
                                         resolve_model_source)

        d_cfg, d_params, _tok = resolve_model_source(
            ref, _ARCHS, self.get("tokenizer"), pretrained_causal_lm)
        if d_params is None:
            import jax
            import jax.numpy as jnp

            d_params = LlamaLM(d_cfg).init(
                jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"]
        return None, (d_cfg, d_params)

    def _paged_engine(self, eff: dict):
        """The shared token-granular engine (one per distinct sampling
        config; greedy — the default — shares one). Offline ``transform``
        and online ``serve_llm`` both decode through THIS object: one page
        pool, one set of prefill/decode executables in the CompiledCache,
        keyed by this stage's instance token so ``set(...)`` invalidates
        them with the rest of the stage's programs."""
        key = (bool(eff["do_sample"]),
               float(eff["temperature"]) if eff["do_sample"] else 0.0,
               eff["top_k"], eff["top_p"], int(eff["seed"]), eff["eos_id"])
        engines = self.__dict__.setdefault("_cache_engines", {})
        eng = engines.get(key)
        if eng is None or eng._released:
            from ..models.paged_engine import PagedDecodeEngine

            model, params, _tok, mesh = self._model_and_params()
            if mesh is not None:
                raise ValueError(
                    "engine='paged' does not support mesh_config yet; "
                    "sharded generation rides the dense path")
            sampling = bool(eff["do_sample"])
            slots = self.get("decode_slots") or max(int(self.get("batch_size")), 2)
            draft_layers, drafter = self._resolve_drafter(model.cfg)
            eng = PagedDecodeEngine(
                model.cfg, params,
                block_len=int(self.get("kv_block_len")),
                n_blocks=self.get("kv_blocks"), max_slots=int(slots),
                temperature=float(eff["temperature"]) if sampling else 0.0,
                top_k=None if eff["top_k"] is None else int(eff["top_k"]),
                top_p=None if eff["top_p"] is None else float(eff["top_p"]),
                seed=int(eff["seed"]), eos_id=eff["eos_id"],
                instance=cb.instance_token(self),
                prefix_cache=bool(self.get("prefix_cache")),
                draft_tokens=int(self.get("draft_tokens") or 0),
                draft_layers=draft_layers, drafter=drafter)
            engines[key] = eng
            # each engine owns a full device page pool — per-row
            # generation_params must not accumulate one multi-GB pool per
            # distinct sampling config, so bound the cache and release the
            # oldest IDLE engine (a released engine still decodes, it just
            # recompiles; the cache never hands it out again)
            if len(engines) > 4:
                for k in list(engines):
                    if k != key and not engines[k].has_work():
                        engines.pop(k).release()
                        break
        return eng

    def serving_engine(self) -> "CausalLMServingEngine":
        """The text-level adapter ``io.serving.serve_llm`` schedules tokens
        on (tokenize request -> paged engine -> detokenized chunks)."""
        return CausalLMServingEngine(self)

    def _texts_of(self, p) -> list[str]:
        mc = self.get("messages_col")
        if mc:
            return [default_chat_template(list(m)) for m in p[mc]]
        return [str(t) for t in p[self.get("input_col")]]

    def _transform(self, df: DataFrame) -> DataFrame:
        mc = self.get("messages_col")
        self.require_columns(df, mc if mc else self.get("input_col"))
        if self.get("generation_params_col"):
            self.require_columns(df, self.get("generation_params_col"))
        engine_kind = self.get("engine")
        if engine_kind not in ("dense", "paged"):
            raise ValueError(f"engine must be 'dense' or 'paged', "
                             f"got {engine_kind!r}")
        model, params, tok, _mesh = self._model_and_params()
        B = self.get("batch_size")
        bucket = self.get("prompt_bucket")
        dp = _mesh.data_parallel_size() if _mesh is not None else 1
        bucketer = cb.default_bucketer()

        pcol = self.get("generation_params_col")

        def row_groups(p, n):
            """[(override-dict-or-None, row indices)] — rows bucketed by
            identical per-row config so each distinct config compiles once."""
            if pcol is None:
                return [(None, np.arange(n))]
            buckets: dict = {}
            for i, d in enumerate(p[pcol]):
                d = dict(d) if d else {}
                key = tuple(sorted(
                    (k, tuple(v) if isinstance(v, list) else v)
                    for k, v in d.items()))
                buckets.setdefault(key, (d, []))[1].append(i)
            return [(d, np.asarray(ix)) for d, ix in buckets.values()]

        def per_part(p, part_offset):
            n = len(next(iter(p.values()))) if p else 0
            if n == 0:
                return None
            texts = self._texts_of(p)
            col = np.empty(n, dtype=object)
            decode = getattr(tok, "decode", None)
            for overrides, ix in row_groups(p, n):
                eff = self._effective_gen_cfg(overrides)
                enc = tok([texts[i] for i in ix],
                          max_len=model.cfg.max_len - eff["max_new_tokens"],
                          multiple_of=bucket)
                ids = np.asarray(enc["input_ids"], np.int32)
                mask = np.asarray(enc["attention_mask"], np.int32)
                m = len(ix)
                if engine_kind == "paged":
                    # token-granular continuous decode: early-EOS rows free
                    # their pages/slots mid-batch instead of riding the
                    # while_loop to the last row's finish
                    prompts = [ids[j][mask[j] > 0].tolist() for j in range(m)]
                    # a zero-token prompt has nothing to condition on: emit
                    # an empty completion for that ROW instead of letting
                    # engine.submit's ValueError fail the whole scan
                    live = [j for j, pr in enumerate(prompts) if pr]
                    gen_rows = [np.zeros(0, np.int32)] * m
                    if live:
                        for j, g in zip(live, self._paged_engine(eff).generate(
                                [prompts[j] for j in live],
                                eff["max_new_tokens"],
                                uids=[part_offset + int(ix[j])
                                      for j in live])):
                            gen_rows[j] = g
                else:
                    P = ids.shape[1]
                    outs = []
                    for s, e, row_bucket in bucketer.slices(m, B,
                                                            multiple_of=dp):
                        ib = cb.pad_rows(ids[s:e], row_bucket)
                        mb = cb.pad_rows(mask[s:e], row_bucket,
                                         mode="constant", constant=1)
                        fn = self._generate_fn(row_bucket, P, eff)
                        gen = cb.unpad_rows(
                            fn(ib, mb, np.int32(part_offset + int(ix[s]))),
                            e - s)
                        outs.append(gen[:, P:])             # generated ids only
                    gen_rows = list(np.concatenate(outs, axis=0))
                for j, i in enumerate(ix):
                    toks = np.asarray(gen_rows[j])
                    if eff["eos_id"] is not None:
                        stop = np.nonzero(toks == eff["eos_id"])[0]
                        if len(stop):
                            toks = toks[: stop[0]]
                    col[i] = decode(toks.tolist()) if decode else toks
            q = dict(p)
            q[self.get("output_col")] = col
            return q

        offsets = np.cumsum(
            [0] + [len(next(iter(p.values()))) if p else 0
                   for p in df.partitions[:-1]])
        parts = [per_part(p, int(off))
                 for p, off in zip(df.partitions, offsets)]
        out_parts = []
        for p, q in zip(df.partitions, parts):
            if q is None:
                q = dict(p)
                q[self.get("output_col")] = np.empty(0, dtype=object)
            out_parts.append(q)
        return DataFrame(out_parts)


class CausalLMServingEngine:
    """Text adapter between ``io.serving.serve_llm``'s token scheduler and
    the stage's shared :class:`~..models.paged_engine.PagedDecodeEngine`:
    parses request payloads (``{"prompt"| "input_ids", "max_new_tokens",
    "stream"}``), tokenizes through the stage's tokenizer, and renders
    per-token chunks / terminal replies (detokenized when the tokenizer can
    decode, raw token ids otherwise)."""

    def __init__(self, stage: "HuggingFaceCausalLM"):
        model, _params, tok, mesh = stage._model_and_params()
        if mesh is not None:
            raise ValueError("serve_llm rides the paged engine, which does "
                             "not support mesh_config yet")
        self._tok = tok
        self._decode = getattr(tok, "decode", None)
        self._max_len = model.cfg.max_len
        eff = stage._effective_gen_cfg()
        self._default_max_new = int(eff["max_new_tokens"])
        self._engine = stage._paged_engine(eff)

    # -- scheduling delegation (the serve_llm protocol) --
    def admit(self):
        return self._engine.admit()

    def step(self):
        return self._engine.step()

    def has_work(self) -> bool:
        return self._engine.has_work()

    @property
    def waiting_count(self) -> int:
        return self._engine.waiting_count

    def warmup(self) -> int:
        return self._engine.warmup()

    def abort(self, seq, reason: str = "aborted"):
        return self._engine.abort(seq, reason=reason)

    def abort_all(self, reason: str = "aborted"):
        return self._engine.abort_all(reason=reason)

    def live_requests(self):
        return self._engine.live_sequences()

    def release(self) -> None:
        self._engine.release()

    def stats(self) -> dict:
        return self._engine.stats()

    # -- request surface --
    def _prompt_ids(self, payload) -> list:
        if "input_ids" in payload:
            return [int(t) for t in payload["input_ids"]]
        prompt = payload.get("prompt")
        if not isinstance(prompt, str) or not prompt:
            raise ValueError("need 'prompt' (non-empty string) or "
                             "'input_ids'")
        # keep the prompt whole (up to the model horizon); the engine
        # clamps max_new to the remaining room and reports
        # finish_reason='length' — a large max_new_tokens must not
        # silently truncate the prompt out from under the request
        enc = self._tok([prompt], max_len=self._max_len - 1,
                        multiple_of=1)
        row_ids = np.asarray(enc["input_ids"][0])
        row_mask = np.asarray(enc["attention_mask"][0])
        return row_ids[row_mask > 0].tolist()

    def submit(self, payload, request_id: str, max_new_cap: int = 1024,
               deadline: float | None = None,
               journal_key: str | None = None):
        if not isinstance(payload, dict):
            raise ValueError("body must be a JSON object with 'prompt' or "
                             "'input_ids'")
        stream = bool(payload.get("stream", False))
        max_new = int(payload.get("max_new_tokens", self._default_max_new))
        max_new = max(1, min(max_new, int(max_new_cap)))
        ids = self._prompt_ids(payload)
        return self._engine.submit(ids, max_new, request_id=request_id,
                                   stream=stream, deadline=deadline,
                                   journal_key=journal_key)

    # -- live migration surface (serve_llm drain / front resubmit) --
    def export(self, uid: int) -> "dict | None":
        """JSON-able snapshot of one live sequence (the engine's binary
        npz payload rides base64) — the wire form of
        ``PagedDecodeEngine.export_sequence``."""
        import base64

        snap = self._engine.export_sequence(uid)
        if snap is None:
            return None
        return {"manifest": snap["manifest"],
                "payload_b64": base64.b64encode(snap["payload"]).decode(),
                "digests": snap["digests"]}

    def _seed_emitted_text(self, seq) -> None:
        # the client already received the text of every emitted token —
        # prime the cumulative-decode cursor so the next chunk streams only
        # the NEW delta, never a replay of the whole prefix
        if self._decode is not None and seq.generated:
            full = self._decode(list(seq.generated))
            if not full.endswith("�"):
                seq._emitted_text = full

    def import_snapshot(self, obj, request_id: str,
                        deadline: float | None = None,
                        journal_key: str | None = None):
        """Readmit an exported sequence under THIS worker's exchange: the
        continuation always streams (the front owns client-facing framing)
        and keeps the origin's uid so sampled token streams stay
        deterministic across the migration."""
        import base64

        if not isinstance(obj, dict) or "manifest" not in obj:
            raise ValueError("__import__ needs a snapshot with 'manifest'")
        man = dict(obj["manifest"])
        man["request_id"] = request_id
        man["stream"] = True
        if journal_key is not None:
            man["journal_key"] = journal_key
        if deadline is not None:
            import time as _time

            man["deadline_ms_left"] = (deadline
                                       - _time.perf_counter()) * 1e3
        payload = base64.b64decode(obj.get("payload_b64") or "") \
            if obj.get("payload_b64") else (obj.get("payload") or b"")
        seq = self._engine.import_sequence(
            {"manifest": man, "payload": payload,
             "digests": obj.get("digests") or {}})
        self._seed_emitted_text(seq)
        return seq

    def resume(self, obj, request_id: str, max_new_cap: int = 1024,
               deadline: float | None = None,
               journal_key: str | None = None):
        """Crash-path resubmit (no KV snapshot survived): re-tokenize the
        original request body and re-prefill over prompt + the tokens the
        front already relayed — token-identical under greedy, and
        sample-identical too when the origin uid rides along."""
        if not isinstance(obj, dict) or not isinstance(obj.get("body"),
                                                       dict):
            raise ValueError("__resume__ needs {'body': <original "
                             "request>, 'emitted_ids': [...]}")
        body = obj["body"]
        ids = self._prompt_ids(body)
        emitted = [int(t) for t in obj.get("emitted_ids") or []]
        max_new = int(body.get("max_new_tokens", self._default_max_new))
        max_new = max(1, min(max_new, int(max_new_cap)))
        man = {"uid": int(obj["uid"]) if obj.get("uid") is not None
               else hash(request_id) & 0x7FFFFFFF,
               "prompt_ids": ids, "generated": emitted,
               "max_new_tokens": max_new, "request_id": request_id,
               "stream": True, "journal_key": journal_key,
               "tokens_in_pages": 0}
        if deadline is not None:
            import time as _time

            man["deadline_ms_left"] = (deadline
                                       - _time.perf_counter()) * 1e3
        seq = self._engine.import_sequence({"manifest": man,
                                            "payload": b"", "digests": {}})
        self._seed_emitted_text(seq)
        return seq

    def chunk_for(self, event: dict) -> dict:
        out = {"token": event["token"]}
        if self._decode is not None:
            # byte-level BPE pieces are not independently decodable (a
            # char split across tokens decodes per-token to U+FFFD): decode
            # the cumulative ids and stream the text DELTA instead
            seq = event["seq"]
            full = self._decode(list(seq.generated))
            prev = getattr(seq, "_emitted_text", "")
            if full.endswith("�"):
                # incomplete byte sequence at the tail: hold the text back
                # until a later token completes it (the terminal record's
                # full-sequence decode always carries the complete text)
                out["text"] = ""
            else:
                out["text"] = (full[len(prev):] if full.startswith(prev)
                               else full)
                seq._emitted_text = full
        return out

    def result_for(self, seq) -> dict:
        toks = list(seq.generated)
        if (self._engine.eos_id is not None and toks
                and toks[-1] == self._engine.eos_id):
            toks = toks[:-1]
        out = {"done": True, "n_tokens": len(toks),
               "finish_reason": seq.finish_reason,
               "output_ids": toks}
        if self._decode is not None:
            out["text"] = self._decode(toks)
        if seq.preemptions:
            out["preemptions"] = seq.preemptions
        return out
