"""Token-granular paged-KV decode engine for the causal LM path.

The dense ``generate`` path (``models/flax_nets/llama.py``) is
run-to-completion: one ``lax.while_loop`` decodes a whole batch until every
row finishes, so one long generation holds the batch hostage and a finished
row's ``[max_len]`` KV cache stays pinned to the end. This engine is the
vLLM-style alternative the serving plane schedules tokens on:

* **Paged KV pool** — a fixed physical pool of
  ``(n_blocks, block_len, kv_heads, head_dim)`` pages per layer plus a
  per-sequence block table (``models/flax_nets/llama.py`` paged modules).
  Sequences of any length share one pool; a finished sequence's pages free
  the moment it emits EOS or exhausts ``max_new_tokens``. Block 0 is the
  reserved trash page — never allocated, absorbing masked writes — so live
  pages can never alias.
* **Prefill/decode split** — a jitted prefill program per bucketed prompt
  length (``ShapeBucketer.seq_bucket_for``) and a jitted single-step decode
  program per bucketed active-slot count (``bucket_for``). Both are
  acquired ONLY through the shared :class:`~..core.batching.CompiledCache`
  (enforced statically in ``tests/test_codegen.py``), so a variable request
  stream compiles at most ladder-many executables each, all warmable.
* **Continuous batching** — :meth:`admit` prefills waiting sequences into
  free slots between decode steps and :meth:`step` decodes one token for
  every active slot; the scheduler in ``io/serving.py`` drives the loop.
  When the pool runs dry mid-decode the youngest sequence is preempted
  (pages freed, re-queued for re-prefill over prompt+generated — greedy
  decode makes the recomputation token-identical).

Greedy paged decode is token-for-token identical to ``greedy_generate``
(parity-tested across prompt buckets in ``tests/test_paged_llm.py``); both
paths read the same param pytree.
"""

from __future__ import annotations

import hashlib
import io
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable, Sequence

import numpy as np

from ..core import batching as cb
from ..core import observability as obs
from ..core import serialization

__all__ = ["BlockAllocator", "PagedDecodeEngine", "SequenceState"]


_ENGINE_METRICS = obs.HandleCache(lambda reg: {
    "step_ms": reg.histogram(
        "synapseml_llm_step_ms",
        "wall time of one engine step, split prefill vs decode", ("phase",)),
    "token_ms": reg.histogram(
        "synapseml_llm_token_latency_ms",
        "decode wall time per emitted token (step time / tokens emitted)"),
    "ttft_ms": reg.histogram(
        "synapseml_llm_ttft_ms",
        "submit -> first generated token (queue wait + prefill)"),
    "tokens": reg.counter(
        "synapseml_llm_tokens_total",
        "generated tokens by phase (prefill = first token)", ("phase",)),
    "occupancy": reg.gauge(
        "synapseml_llm_kv_block_occupancy",
        "fraction of the physical KV block pool allocated to live sequences"),
    "fragmentation": reg.gauge(
        "synapseml_llm_kv_fragmentation",
        "unused token slots inside allocated blocks / allocated capacity "
        "(tail waste of the page granularity)"),
    "refilled": reg.counter(
        "synapseml_llm_slots_refilled_total",
        "decode slots handed to a waiting sequence after a finish freed "
        "capacity (the no-run-to-completion-barrier counter)"),
    "preempted": reg.counter(
        "synapseml_llm_slots_preempted_total",
        "sequences evicted mid-decode because the block pool ran dry "
        "(re-queued for re-prefill)"),
    "finished": reg.counter(
        "synapseml_llm_sequences_finished_total",
        "sequences completed, by finish reason", ("reason",)),
    "spec_proposed": reg.counter(
        "synapseml_llm_spec_tokens_proposed_total",
        "draft tokens proposed to the speculative verify step"),
    "spec_accepted": reg.counter(
        "synapseml_llm_spec_tokens_accepted_total",
        "draft tokens the full model confirmed (greedy match)"),
    "spec_steps": reg.counter(
        "synapseml_llm_spec_steps_total",
        "engine steps by decode mode: 'spec' = fused draft+verify, "
        "'fallback' = plain single-token (pool too tight for the window)",
        ("mode",)),
    "spec_accept_rate": reg.gauge(
        "synapseml_llm_spec_acceptance_rate",
        "cumulative accepted / proposed draft tokens"),
})


def _npz_safe(arr: np.ndarray) -> np.ndarray:
    """npz-writable view of one KV chunk: numpy's format cannot serialize
    extension dtypes (bf16), so those ride as raw uint8 bytes and the
    manifest's recorded dtype restores them on import."""
    arr = np.ascontiguousarray(arr)
    if np.dtype(arr.dtype).isbuiltin == 1:  # 2 = extension dtype (bf16)
        return arr
    return np.frombuffer(arr.tobytes(), np.uint8)


class BlockAllocator:
    """Free-list allocator over the physical page pool. Block 0 is the
    reserved trash page and is never handed out; double-free and
    allocate-while-live are hard errors (the no-aliasing invariant the
    property test leans on).

    Blocks are REFERENCE-COUNTED for prefix-KV sharing: ``alloc`` hands out
    blocks at refcount 1, :meth:`ref` lets another holder (the prefix
    cache, a prefix-hit sequence) pin an already-live block, and ``free``
    drops ONE reference per call — the block returns to the free list only
    when the last holder lets go. Freeing a non-live block (refcount
    already zero) is still the same hard error, so a double free cannot
    hide behind sharing."""

    def __init__(self, n_blocks: int):
        if n_blocks < 2:
            raise ValueError(f"need >= 2 blocks (1 is the trash page), "
                             f"got {n_blocks}")
        self.n_blocks = int(n_blocks)
        self._free: list[int] = list(range(self.n_blocks - 1, 0, -1))
        self._live: set[int] = set()
        self._refs: dict[int, int] = {}

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return len(self._live)

    @property
    def capacity(self) -> int:
        """Allocatable blocks (pool minus the trash page)."""
        return self.n_blocks - 1

    def alloc(self, n: int) -> list[int] | None:
        """``n`` blocks or None (never a partial allocation)."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        self._live.update(out)
        for b in out:
            self._refs[b] = 1
        return out

    def ref(self, block: int) -> None:
        """Add one reference to an already-live block (prefix sharing).
        Referencing a non-live block is a hard error — it would resurrect
        freed pages and alias whoever allocates them next."""
        if block not in self._live:
            raise RuntimeError(
                f"ref on block {block} that is not live (use-after-free "
                f"or trash-page share — an aliasing bug)")
        self._refs[block] += 1

    def refcount(self, block: int) -> int:
        return self._refs.get(block, 0)

    def free(self, blocks: Iterable[int]) -> None:
        for b in blocks:
            if b not in self._live:
                raise RuntimeError(
                    f"freeing block {b} that is not live (double free or "
                    f"trash-page free — an aliasing bug)")
            self._refs[b] -= 1
            if self._refs[b] == 0:
                del self._refs[b]
                self._live.remove(b)
                self._free.append(b)


@dataclass
class SequenceState:
    """One request's decode state (host side; device state is the pages)."""

    uid: int
    prompt_ids: list
    max_new_tokens: int
    request_id: str | None = None
    stream: bool = False
    generated: list = field(default_factory=list)
    blocks: list = field(default_factory=list)
    tokens_in_pages: int = 0       # prompt + generated tokens written to pages
    preemptions: int = 0
    submitted_at: float = field(default_factory=time.perf_counter)
    first_token_at: float | None = None
    finish_reason: str | None = None
    deadline: float | None = None  # perf_counter instant; past it the
    #                                engine frees the pages and finishes
    #                                with reason='deadline'
    journal_key: str | None = None  # the RoutingFront's idempotency key —
    #                                 rides exports so a drained worker's
    #                                 handoff can find the front's journal
    #                                 entry (worker request_ids are local)
    registered_blocks: int = 0     # full blocks already chain-hashed into
    prefix_digest: bytes = b""     # the prefix cache, + the chain digest
    #                                at that boundary (incremental hashing)

    @property
    def context_ids(self) -> list:
        """Tokens a (re-)prefill must process: prompt + generated so far."""
        return list(self.prompt_ids) + list(self.generated)

    @property
    def done(self) -> bool:
        return self.finish_reason is not None


class PagedDecodeEngine:
    """Continuous-batching decode engine over a paged KV pool.

    ``submit`` -> waiting queue; ``admit`` prefills waiting sequences into
    capacity (bucketed prompt lengths, fixed prefill batch width);
    ``step`` decodes ONE token for every active sequence (bucketed slot
    count). Both return event dicts
    ``{"seq", "token", "text"?, "done", "finish_reason"}`` the serving
    scheduler turns into streamed chunks / terminal replies.

    Sampling config (``temperature``/``top_k``/``top_p``/``seed``) is fixed
    per engine — it is baked into the compiled programs' cache key; greedy
    (the default) is what the parity guarantee covers. ``eos_id`` and
    per-sequence ``max_new_tokens`` are host-side and never recompile.
    """

    def __init__(self, cfg, params, *, block_len: int = 16,
                 n_blocks: int | None = None, max_slots: int = 8,
                 max_len: int | None = None, prefill_batch: int = 4,
                 temperature: float = 0.0, top_k: int | None = None,
                 top_p: float | None = None, seed: int = 0,
                 eos_id: int | None = None, bucketer=None,
                 instance: Any = None, donate_pages: bool = True,
                 prefix_cache: bool = False, draft_tokens: int = 0,
                 draft_layers: int | None = None,
                 drafter: tuple | None = None):
        import jax.numpy as jnp

        self.cfg = cfg
        self.params = params
        self.block_len = int(block_len)
        self.max_len = int(max_len or cfg.max_len)
        if self.max_len > cfg.max_len:
            raise ValueError(f"max_len {self.max_len} exceeds the model's "
                             f"RoPE/cache horizon {cfg.max_len}")
        self.max_blocks = -(-self.max_len // self.block_len)
        self.max_slots = int(max_slots)
        self.prefill_batch = int(prefill_batch)
        if n_blocks is None:
            # default: every slot can run to max_len concurrently + trash
            n_blocks = 1 + self.max_slots * self.max_blocks
        self.allocator = BlockAllocator(n_blocks)
        self.eos_id = eos_id
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.seed = int(seed)
        self.bucketer = bucketer or cb.default_bucketer()
        self._instance = instance if instance is not None \
            else cb.instance_token(self)
        # decode slot rungs: ladder rungs <= max_slots, plus max_slots itself
        rungs = [r for r in self.bucketer.ladder if r <= self.max_slots]
        if not rungs or rungs[-1] < self.max_slots:
            rungs.append(self.max_slots)
        self.slot_rungs: tuple[int, ...] = tuple(rungs)
        # physical pool: one [n_blocks, bl, KV, D] leaf per layer (a tuple,
        # so each layer's page writes update one leaf in place — see
        # PagedEncoder)
        shape = (n_blocks, self.block_len, cfg.kv_heads, cfg.head_dim)
        self._k_pages = tuple(jnp.zeros(shape, cfg.dtype)
                              for _ in range(cfg.n_layers))
        self._v_pages = tuple(jnp.zeros(shape, cfg.dtype)
                              for _ in range(cfg.n_layers))
        # page pools are DONATED into every prefill/decode call (each call
        # returns the updated pools and the engine rebinds them), so a step
        # updates pages in place instead of copying the whole pool — on the
        # CPU backend this is the difference between winning and losing the
        # continuous-vs-RTC A/B
        self._donate = bool(donate_pages)
        # --- prefix KV cache (OFF by default: zero behavior change) ------
        self._prefix_cache = None
        if prefix_cache:
            from .prefix_cache import PrefixCache
            self._prefix_cache = PrefixCache(self.allocator, self.block_len)
        # --- greedy speculative decoding (OFF by default) ----------------
        self.draft_tokens = int(draft_tokens)
        if self.draft_tokens < 0:
            raise ValueError(f"draft_tokens={draft_tokens}")
        if self.draft_tokens > 0 and temperature is not None \
                and temperature > 0.0:
            raise ValueError(
                "speculative decoding is greedy-only (the acceptance rule "
                "compares argmaxes); temperature > 0 would break the "
                "token-identity guarantee — set draft_tokens=0 to sample")
        self.draft_layers = None
        self._drafter = None
        self._draft_params = None
        if self.draft_tokens > 0:
            if drafter is not None:
                # a registry-resolved small model drafts over a dense
                # LEFT-ALIGNED context window (no second page pool; window
                # truncation only affects draft quality, never correctness
                # — the full model's verify is the ground truth)
                d_cfg = drafter[0]
                if d_cfg.max_len < self.max_len:
                    raise ValueError(
                        f"drafter max_len={d_cfg.max_len} cannot position-"
                        f"encode the engine horizon max_len={self.max_len}")
                self._drafter = (d_cfg, drafter[1])
                self._draft_params = drafter[1]
                self._draft_window = self.bucketer.seq_bucket_for(
                    min(64, self.max_len), cap=self.max_len)
            else:
                # self-draft: early-exit at draft_layers over the SAME
                # params and pool leaves (layers < E)
                from .flax_nets.llama import early_exit_params
                E = draft_layers if draft_layers is not None \
                    else max(1, cfg.n_layers // 2)
                if not 1 <= E <= cfg.n_layers:
                    raise ValueError(
                        f"draft_layers={E} outside [1, {cfg.n_layers}]")
                self.draft_layers = int(E)
                self._draft_params = early_exit_params(params, self.draft_layers)
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_steps = 0
        self._spec_fallbacks = 0
        self._lock = threading.RLock()
        self._waiting: deque[SequenceState] = deque()
        self._active: list[SequenceState] = []
        self._uid = 0
        self._freed_since_admit = 0  # finish/preempt -> refill accounting
        self._released = False
        self._progress_ticks = 0  # engine-WIDE: any token emitted or
        #                           sequence finished, by any caller

    # ------------------------------------------------------------------
    # compiled programs (CompiledCache is the only jit door)
    # ------------------------------------------------------------------
    def _cfg_key(self) -> tuple:
        return (self.temperature, self.top_k, self.top_p, self.block_len)

    def _selector(self):
        """Per-row selector [S,V] logits + [S] uid + [S] step -> [S] ids —
        the dense `_make_selector` vmapped over per-sequence fold_in keys so
        each request's sample stream is a pure function of (seed, uid)."""
        import jax

        from .flax_nets.llama import _make_selector

        base_select = _make_selector(self.temperature, self.top_k, self.top_p)
        base_key = jax.random.PRNGKey(self.seed)

        def select(logits, uids, steps):
            def one(row, uid, step):
                key = jax.random.fold_in(jax.random.fold_in(base_key, uid),
                                         step)
                return base_select(row[None], key)[0]
            return jax.vmap(one)(logits, uids, steps)

        return select

    def _prefill_fn(self, B: int, P: int) -> Callable:
        def _build():
            import jax

            from .flax_nets.llama import paged_prefill

            cfg, bl = self.cfg, self.block_len
            select = self._selector()

            def fn(params, ids, mask, tables, kp, vp, uids, steps):
                logits, kp, vp = paged_prefill(cfg, bl, params, ids, mask,
                                               tables, kp, vp)
                return select(logits, uids, steps), kp, vp

            donate = (4, 5) if self._donate else ()
            return jax.jit(fn, donate_argnums=donate)

        return cb.get_compiled_cache().get(
            "llama_paged_prefill",
            (B, P, self.max_blocks) + self._cfg_key(), _build,
            instance=self._instance, dtype="int32")

    def _decode_fn(self, S: int) -> Callable:
        def _build():
            import jax

            from .flax_nets.llama import paged_decode_step

            cfg, bl = self.cfg, self.block_len
            select = self._selector()

            def fn(params, tokens, seq_lens, active, tables, kp, vp, uids,
                   steps):
                logits, kp, vp = paged_decode_step(cfg, bl, params, tokens,
                                                   seq_lens, active, tables,
                                                   kp, vp)
                return select(logits, uids, steps), kp, vp

            donate = (5, 6) if self._donate else ()
            return jax.jit(fn, donate_argnums=donate)

        return cb.get_compiled_cache().get(
            "llama_paged_decode",
            (S, self.max_blocks) + self._cfg_key(), _build,
            instance=self._instance, dtype="int32")

    def _extend_fn(self, B: int, Q: int) -> Callable:
        """Suffix prefill over a cached prefix: COW-copies each row's
        divergence block (``cow_dst`` < 0 = no copy; the trash page absorbs
        the no-op write), then prefills only the UNCACHED suffix with
        decode-mode attention over the pooled prefix KV."""
        def _build():
            import jax
            import jax.numpy as jnp

            from .flax_nets.llama import paged_extend

            cfg, bl = self.cfg, self.block_len
            select = self._selector()

            def fn(params, ids, mask, start_pos, tables, cow_src, cow_dst,
                   kp, vp, uids, steps):
                src = jnp.maximum(cow_src, 0)
                dst = jnp.maximum(cow_dst, 0)
                do = (cow_dst >= 0)[:, None, None, None]

                def cow(pages):
                    return pages.at[dst].set(
                        jnp.where(do, pages[src], pages[dst]))

                kp = tuple(cow(p) for p in kp)
                vp = tuple(cow(p) for p in vp)
                logits, kp, vp = paged_extend(cfg, bl, params, ids, mask,
                                              start_pos, tables, kp, vp)
                return select(logits, uids, steps), kp, vp

            donate = (7, 8) if self._donate else ()
            return jax.jit(fn, donate_argnums=donate)

        return cb.get_compiled_cache().get(
            "llama_paged_extend",
            (B, Q, self.max_blocks) + self._cfg_key(), _build,
            instance=self._instance, dtype="int32")

    def _spec_fn(self, S: int) -> Callable:
        """Fused greedy draft + verify: K single-token draft steps (early
        exit over the shared pool leaves, or a dense windowed drafter) then
        ONE K+1-token verify forward of the full model. Returns
        (pred [S,K+1], n_accepted [S], pools); the emitted tokens are
        ``pred[:, :n_accepted+1]`` — token-identical to plain greedy decode
        because a draft survives only where the full model's argmax agrees
        and the first disagreement emits the full model's own token."""
        K = self.draft_tokens

        def _build():
            import dataclasses

            import jax
            import jax.numpy as jnp

            from .flax_nets.llama import (LlamaLM, paged_decode_step,
                                          paged_verify)

            cfg, bl = self.cfg, self.block_len

            def _accept(window, logits):
                pred = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                match = (pred[:, :K] == window[:, 1:]).astype(jnp.int32)
                n_acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
                return pred, n_acc

            if self._drafter is not None:
                d_cfg, _ = self._drafter
                W = self._draft_window
                d_model = LlamaLM(d_cfg)

                def fn(params, d_params, last_tok, seq_lens, active, tables,
                       win, pos, L0, kp, vp):
                    S_ = last_tok.shape[0]
                    wm = (jnp.arange(W)[None, :]
                          < L0[:, None]).astype(jnp.int32)
                    drafts = []
                    for j in range(K):
                        logits = d_model.apply({"params": d_params}, win,
                                               positions=pos,
                                               attention_mask=wm)
                        idx = jnp.maximum(L0 + j - 1, 0)
                        last = jnp.take_along_axis(
                            logits, idx[:, None, None], axis=1)[:, 0]
                        d = jnp.argmax(last, axis=-1).astype(jnp.int32)
                        drafts.append(d)
                        rows = jnp.arange(S_)
                        win = win.at[rows, L0 + j].set(d)
                        wm = wm.at[rows, L0 + j].set(1)
                    window = jnp.stack([last_tok] + drafts, axis=1)
                    logits, kp, vp = paged_verify(cfg, bl, params, window,
                                                  seq_lens, active, tables,
                                                  kp, vp)
                    pred, n_acc = _accept(window, logits)
                    return pred, n_acc, kp, vp

                donate = (9, 10) if self._donate else ()
                return jax.jit(fn, donate_argnums=donate)

            E = self.draft_layers
            d_cfg = dataclasses.replace(cfg, n_layers=E)

            def fn(params, d_params, last_tok, seq_lens, active, tables,
                   kp, vp):
                kpE, vpE = kp[:E], vp[:E]
                drafts = []
                d = last_tok
                for j in range(K):
                    logits, kpE, vpE = paged_decode_step(
                        d_cfg, bl, d_params, d, seq_lens + j, active,
                        tables, kpE, vpE)
                    d = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    drafts.append(d)
                kp = kpE + kp[E:]
                vp = vpE + vp[E:]
                window = jnp.stack([last_tok] + drafts, axis=1)
                logits, kp, vp = paged_verify(cfg, bl, params, window,
                                              seq_lens, active, tables,
                                              kp, vp)
                pred, n_acc = _accept(window, logits)
                return pred, n_acc, kp, vp

            donate = (6, 7) if self._donate else ()
            return jax.jit(fn, donate_argnums=donate)

        mode = ("ext", self._draft_window) if self._drafter is not None \
            else ("self", self.draft_layers)
        return cb.get_compiled_cache().get(
            "llama_paged_spec",
            (S, self.max_blocks, K) + mode + self._cfg_key(), _build,
            instance=self._instance, dtype="int32")

    # ------------------------------------------------------------------
    # scheduling surface
    # ------------------------------------------------------------------
    def submit(self, prompt_ids: Sequence[int], max_new_tokens: int,
               request_id: str | None = None, stream: bool = False,
               uid: int | None = None, deadline: float | None = None,
               journal_key: str | None = None) -> SequenceState:
        """Queue a tokenized prompt. ``uid`` seeds the sequence's sampling
        key stream (auto-assigned when None); offline ``transform()`` passes
        the global row offset so sampled generation is a deterministic
        function of (seed, row), not of submission order. ``deadline`` is a
        ``time.perf_counter()`` instant past which the sequence expires with
        ``finish_reason='deadline'`` instead of holding pages for a client
        that stopped waiting."""
        prompt_ids = [int(t) for t in prompt_ids]
        if not prompt_ids:
            raise ValueError("empty prompt")
        if len(prompt_ids) >= self.max_len:
            raise ValueError(f"prompt ({len(prompt_ids)} tokens) must leave "
                             f"room to generate under max_len={self.max_len}")
        # the engine horizon caps generation; the cap is reported as
        # finish_reason='length' rather than rejecting the request
        max_new = max(1, min(int(max_new_tokens),
                             self.max_len - len(prompt_ids)))
        with self._lock:
            if uid is None:
                self._uid += 1
                uid = self._uid
            seq = SequenceState(uid=int(uid), prompt_ids=prompt_ids,
                                max_new_tokens=max_new,
                                request_id=request_id, stream=stream,
                                deadline=deadline, journal_key=journal_key)
            self._waiting.append(seq)
        return seq

    @property
    def prefix_cache(self):
        """The engine's :class:`~.prefix_cache.PrefixCache`, or None when
        prefix caching is off."""
        return self._prefix_cache

    @property
    def active_count(self) -> int:
        return len(self._active)

    @property
    def waiting_count(self) -> int:
        return len(self._waiting)

    def has_work(self) -> bool:
        return bool(self._active or self._waiting)

    def _blocks_for(self, n_tokens: int) -> int:
        return -(-max(n_tokens, 1) // self.block_len)

    def _reclaim(self, n: int) -> None:
        """Make room for ``n`` blocks by evicting cold prefix-cache entries
        — cached pages are strictly cheaper to give up than preempting (and
        recomputing) a live sequence, so every alloc path tries this
        first."""
        if self._prefix_cache is not None and self.allocator.free_count < n:
            self._prefix_cache.evict(n - self.allocator.free_count)

    def _register_blocks(self, seq: SequenceState) -> None:
        """Chain-hash every newly FILLED block of committed tokens into the
        prefix cache (incremental: picks up from the sequence's recorded
        digest). Full blocks are immutable from here on — writes only ever
        target positions >= ``tokens_in_pages`` — so cached pages stay
        byte-stable while shared."""
        pc = self._prefix_cache
        if pc is None:
            return
        bl = self.block_len
        n_full = min(seq.tokens_in_pages // bl, len(seq.blocks))
        if n_full <= seq.registered_blocks:
            return
        ctx = seq.context_ids
        h = seq.prefix_digest
        for i in range(seq.registered_blocks, n_full):
            h = pc.insert(h, ctx[i * bl:(i + 1) * bl], seq.blocks[i])
        seq.registered_blocks = n_full
        seq.prefix_digest = h

    def _update_pool_gauges(self) -> None:
        m = _ENGINE_METRICS.get()
        cap = self.allocator.capacity
        used = self.allocator.used_count
        m["occupancy"].labels().set(used / cap if cap else 0.0)
        live_tokens = sum(s.tokens_in_pages for s in self._active)
        alloc_tokens = used * self.block_len
        m["fragmentation"].labels().set(
            (alloc_tokens - live_tokens) / alloc_tokens if alloc_tokens
            else 0.0)

    def _finish(self, seq: SequenceState, reason: str) -> None:
        self._progress_ticks += 1
        seq.finish_reason = reason
        if seq.blocks:
            self.allocator.free(seq.blocks)
            seq.blocks = []
        if seq in self._active:
            self._active.remove(seq)
            self._freed_since_admit += 1
        _ENGINE_METRICS.get()["finished"].inc(reason=reason)
        self._update_pool_gauges()

    def _emit(self, seq: SequenceState, token: int) -> dict:
        self._progress_ticks += 1
        now = time.perf_counter()
        m = _ENGINE_METRICS.get()
        if seq.first_token_at is None:
            seq.first_token_at = now
            m["ttft_ms"].labels().observe((now - seq.submitted_at) * 1e3)
        done = False
        if self.eos_id is not None and token == self.eos_id:
            done, reason = True, "eos"
        elif len(seq.generated) >= seq.max_new_tokens:
            done, reason = True, "length"
        if done:
            self._finish(seq, reason)
        # "index" is the token's 0-based position in the generation —
        # every _emit call sits right after its generated.append, so this
        # is exact even when one speculative step emits several tokens
        # (consumers reading len(generated) AFTER the step would see only
        # the window's final length)
        return {"seq": seq, "token": int(token), "done": done,
                "index": len(seq.generated) - 1,
                "finish_reason": seq.finish_reason}

    def admit(self) -> list[dict]:
        """Prefill waiting sequences into free capacity. Batches up to
        ``prefill_batch`` sequences per program call, prompts padded to one
        seq-ladder bucket — compile count stays <= len(seq ladder).

        With the prefix cache on, each candidate first looks up its longest
        cached full-block chain: shared blocks are referenced (never
        written), only fresh blocks are allocated, and the sequence rides
        the EXTEND program — prefill over just the uncached suffix,
        attending to the resident prefix KV through the block table. A
        fully-cached prompt COWs its divergence block so the mandatory
        last-token recompute writes a private copy."""
        import jax.numpy as jnp

        events: list[dict] = self.expire_deadlines()
        with self._lock:
            while self._waiting and len(self._active) < self.max_slots:
                # (seq, reuse_tokens, cow_src) triples
                group: list[tuple[SequenceState, int, int]] = []
                while (self._waiting and len(group) < self.prefill_batch
                       and len(self._active) + len(group) < self.max_slots):
                    seq = self._waiting[0]
                    ctx = seq.context_ids
                    need = self._blocks_for(len(ctx))
                    if need > self.allocator.capacity:
                        # no amount of freeing can ever satisfy this
                        # sequence — terminate it instead of wedging the
                        # FIFO head forever
                        self._waiting.popleft()
                        self._finish(seq, "kv_capacity")
                        events.append({"seq": seq, "token": None,
                                       "done": True,
                                       "finish_reason": "kv_capacity"})
                        continue
                    shared: list[int] = []
                    digests: list[bytes] = []
                    reuse, cow_src = 0, -1
                    if self._prefix_cache is not None:
                        cblocks, digests = self._prefix_cache.lookup(ctx)
                        # whole blocks only, and ALWAYS leave >= 1 token of
                        # suffix to prefill (the last-position logits seed
                        # the first generated token)
                        reuse = min(len(cblocks) * self.block_len,
                                    len(ctx) - 1)
                        n_shared = reuse // self.block_len
                        if reuse % self.block_len:
                            # fully-cached prompt: the divergence block is
                            # shared, so the suffix write gets a COW copy
                            cow_src = cblocks[n_shared]
                        shared = cblocks[:n_shared]
                        # pin BEFORE any eviction can run: _reclaim (ours,
                        # or a later group member's) frees refcount-1
                        # cache entries, so without the extra ref it could
                        # evict these very blocks and alloc() would hand
                        # them back as fresh suffix pages — the "shared
                        # prefix" silently aliasing its own suffix writes.
                        # cow_src is pinned too: the extend program reads
                        # it for the divergence-block copy AFTER every
                        # group member has run its own reclaim (unpinned
                        # once the program has executed).
                        for b in shared:
                            self.allocator.ref(b)
                        if cow_src >= 0:
                            self.allocator.ref(cow_src)
                    need_new = need - len(shared)
                    self._reclaim(need_new)
                    got = self.allocator.alloc(need_new)
                    if got is None:
                        for b in shared:  # unpin: the seq stays waiting
                            self.allocator.free([b])
                        if cow_src >= 0:
                            self.allocator.free([cow_src])
                        break  # pool dry: decode must free pages first
                    self._waiting.popleft()
                    seq.blocks = shared + got
                    seq.registered_blocks = len(shared)
                    seq.prefix_digest = digests[len(shared) - 1] \
                        if shared else b""
                    if reuse and self._prefix_cache is not None:
                        self._prefix_cache.note_reused(reuse)
                    group.append((seq, reuse, cow_src))
                if not group:
                    break
                plain = [g for g in group if g[1] == 0]
                hits = [g for g in group if g[1] > 0]
                B = self.prefill_batch
                m = _ENGINE_METRICS.get()
                admitted: list[SequenceState] = []
                if plain:
                    t0 = time.perf_counter()
                    P = self.bucketer.seq_bucket_for(
                        max(len(s.context_ids) for s, _, _ in plain),
                        cap=self.max_len)
                    ids = np.zeros((B, P), np.int32)
                    mask = np.zeros((B, P), np.int32)
                    tables = np.zeros((B, self.max_blocks), np.int32)
                    uids = np.zeros((B,), np.int32)
                    steps = np.zeros((B,), np.int32)
                    for i, (seq, _, _) in enumerate(plain):
                        ctx = seq.context_ids
                        ids[i, :len(ctx)] = ctx
                        mask[i, :len(ctx)] = 1
                        tables[i, :len(seq.blocks)] = seq.blocks
                        uids[i] = seq.uid
                        steps[i] = len(seq.generated)
                    fn = self._prefill_fn(B, P)
                    next_tok, self._k_pages, self._v_pages = fn(
                        self.params, jnp.asarray(ids), jnp.asarray(mask),
                        jnp.asarray(tables), self._k_pages, self._v_pages,
                        jnp.asarray(uids), jnp.asarray(steps))
                    next_tok = np.asarray(next_tok)
                    m["step_ms"].observe((time.perf_counter() - t0) * 1e3,
                                         phase="prefill")
                    for i, (seq, _, _) in enumerate(plain):
                        seq._admit_token = int(next_tok[i])
                        admitted.append(seq)
                if hits:
                    t0 = time.perf_counter()
                    Q = self.bucketer.seq_bucket_for(
                        max(len(s.context_ids) - r for s, r, _ in hits),
                        cap=self.max_len)
                    ids = np.zeros((B, Q), np.int32)
                    mask = np.zeros((B, Q), np.int32)
                    start = np.zeros((B,), np.int32)
                    tables = np.zeros((B, self.max_blocks), np.int32)
                    cow_src = np.full((B,), -1, np.int32)
                    cow_dst = np.full((B,), -1, np.int32)
                    uids = np.zeros((B,), np.int32)
                    steps = np.zeros((B,), np.int32)
                    for i, (seq, r, cs) in enumerate(hits):
                        suffix = seq.context_ids[r:]
                        ids[i, :len(suffix)] = suffix
                        mask[i, :len(suffix)] = 1
                        start[i] = r
                        tables[i, :len(seq.blocks)] = seq.blocks
                        if cs >= 0:
                            cow_src[i] = cs
                            cow_dst[i] = seq.blocks[r // self.block_len]
                        uids[i] = seq.uid
                        steps[i] = len(seq.generated)
                    fn = self._extend_fn(B, Q)
                    next_tok, self._k_pages, self._v_pages = fn(
                        self.params, jnp.asarray(ids), jnp.asarray(mask),
                        jnp.asarray(start), jnp.asarray(tables),
                        jnp.asarray(cow_src), jnp.asarray(cow_dst),
                        self._k_pages, self._v_pages,
                        jnp.asarray(uids), jnp.asarray(steps))
                    next_tok = np.asarray(next_tok)
                    m["step_ms"].observe((time.perf_counter() - t0) * 1e3,
                                         phase="prefill")
                    for i, (seq, _, cs) in enumerate(hits):
                        if cs >= 0:
                            # the divergence-block copy has executed;
                            # release the lookup-time pin on its source
                            self.allocator.free([cs])
                        seq._admit_token = int(next_tok[i])
                        admitted.append(seq)
                m["tokens"].inc(len(admitted), phase="prefill")
                for seq in admitted:
                    tok = seq._admit_token
                    del seq._admit_token
                    seq.tokens_in_pages = len(seq.context_ids)
                    seq.generated.append(tok)
                    self._active.append(seq)
                    if self._freed_since_admit > 0:
                        self._freed_since_admit -= 1
                        m["refilled"].inc()
                    events.append(self._emit(seq, tok))
                    if not seq.done:
                        self._register_blocks(seq)
                self._update_pool_gauges()
        return events

    def _preempt_youngest(self, keep: SequenceState) -> bool:
        """Free the most recently admitted active sequence (other than
        ``keep``) back to the waiting queue; its next prefill recomputes
        prompt+generated (token-identical under greedy)."""
        for victim in reversed(self._active):
            if victim is keep:
                continue
            self._active.remove(victim)
            self.allocator.free(victim.blocks)
            victim.blocks = []
            victim.tokens_in_pages = 0
            victim.registered_blocks = 0
            victim.prefix_digest = b""
            victim.preemptions += 1
            self._waiting.appendleft(victim)
            self._freed_since_admit += 1
            _ENGINE_METRICS.get()["preempted"].inc()
            return True
        return False

    def _try_spec_step(self, events: list[dict]) -> bool:
        """Attempt one fused draft+verify step for every active sequence
        (caller holds the lock). Returns False — telling :meth:`step` to run
        the plain single-token program — when any sequence's K+1-token
        window would cross ``max_len`` or the pool cannot cover the window
        even after prefix-cache eviction; preempting a neighbor just to
        speculate is never worth it."""
        import jax.numpy as jnp

        K = self.draft_tokens
        batch = [s for s in self._active if not s.done]
        if not batch:
            return True
        # every window write position n..n+K must fit the engine horizon
        if any(s.tokens_in_pages + K >= self.max_len for s in batch):
            return False
        # grow tables to cover the whole window (cache eviction only — no
        # preemption on the speculative path)
        for seq in batch:
            need = (seq.tokens_in_pages + K) // self.block_len + 1
            grow = need - len(seq.blocks)
            if grow <= 0:
                continue
            self._reclaim(grow)
            got = self.allocator.alloc(grow)
            if got is None:
                return False
            seq.blocks.extend(got)
        t0 = time.perf_counter()
        S_active = len(batch)
        S = next(r for r in self.slot_rungs if r >= S_active)
        last_tok = np.zeros((S,), np.int32)
        seq_lens = np.zeros((S,), np.int32)
        active = np.zeros((S,), bool)
        tables = np.zeros((S, self.max_blocks), np.int32)
        for i, seq in enumerate(batch):
            last_tok[i] = seq.generated[-1]
            seq_lens[i] = seq.tokens_in_pages
            active[i] = True
            tables[i, :len(seq.blocks)] = seq.blocks
        fn = self._spec_fn(S)
        if self._drafter is not None:
            W = self._draft_window
            win = np.zeros((S, W), np.int32)
            pos = np.zeros((S, W), np.int32)
            L0 = np.zeros((S,), np.int32)
            for i, seq in enumerate(batch):
                ctx = seq.context_ids
                L = min(len(ctx), W - K)
                win[i, :L] = ctx[-L:]
                pos[i, :] = (len(ctx) - L) + np.arange(W)
                L0[i] = L
            pred, n_acc, self._k_pages, self._v_pages = fn(
                self.params, self._draft_params, jnp.asarray(last_tok),
                jnp.asarray(seq_lens), jnp.asarray(active),
                jnp.asarray(tables), jnp.asarray(win), jnp.asarray(pos),
                jnp.asarray(L0), self._k_pages, self._v_pages)
        else:
            pred, n_acc, self._k_pages, self._v_pages = fn(
                self.params, self._draft_params, jnp.asarray(last_tok),
                jnp.asarray(seq_lens), jnp.asarray(active),
                jnp.asarray(tables), self._k_pages, self._v_pages)
        pred = np.asarray(pred)
        n_acc = np.asarray(n_acc)
        m = _ENGINE_METRICS.get()
        dt_ms = (time.perf_counter() - t0) * 1e3
        m["step_ms"].observe(dt_ms, phase="decode")
        emitted = 0
        for i, seq in enumerate(batch):
            a = int(n_acc[i])
            self._spec_proposed += K
            self._spec_accepted += a
            for t in range(a + 1):
                tok = int(pred[i, t])
                seq.tokens_in_pages += 1
                seq.generated.append(tok)
                emitted += 1
                ev = self._emit(seq, tok)
                events.append(ev)
                if ev["done"]:
                    break  # EOS/length inside the window: the tail tokens
                    #        would not exist under plain decode either
            if not seq.done:
                self._register_blocks(seq)
        self._spec_steps += 1
        m["spec_steps"].inc(mode="spec")
        m["spec_proposed"].inc(K * S_active)
        m["spec_accepted"].inc(int(n_acc[:S_active].sum()))
        if self._spec_proposed:
            m["spec_accept_rate"].labels().set(
                self._spec_accepted / self._spec_proposed)
        m["token_ms"].labels().observe(dt_ms / max(emitted, 1))
        m["tokens"].inc(emitted, phase="decode")
        self._update_pool_gauges()
        return True

    def step(self) -> list[dict]:
        """One decode step for every active sequence (bucketed slot count);
        returns per-sequence token events. Finished sequences free their
        pages immediately — the next :meth:`admit` refills the capacity.
        With ``draft_tokens`` > 0 the step runs the fused draft+verify
        program instead (up to ``draft_tokens``+1 tokens per sequence per
        step), falling back to the plain single-token program whenever the
        pool or the ``max_len`` horizon cannot take a full window."""
        import jax.numpy as jnp

        events: list[dict] = self.expire_deadlines()
        with self._lock:
            if not self._active:
                return events
            if self.draft_tokens > 0 and self._try_spec_step(events):
                return events
            # grow block tables where the next token crosses a page boundary
            for seq in list(self._active):
                if seq.done or seq not in self._active:
                    continue  # preempted/finished by an earlier iteration
                pos = seq.tokens_in_pages
                if pos // self.block_len >= len(seq.blocks):
                    self._reclaim(1)
                    grown = self.allocator.alloc(1)
                    while grown is None:
                        if not self._preempt_youngest(keep=seq):
                            # lone sequence exhausted the whole pool
                            self._finish(seq, "kv_capacity")
                            events.append({"seq": seq, "token": None,
                                           "done": True,
                                           "finish_reason": "kv_capacity"})
                            break
                        grown = self.allocator.alloc(1)
                    if grown is not None:
                        seq.blocks.extend(grown)
            batch = list(self._active)
            if not batch:
                return events
            t0 = time.perf_counter()
            S_active = len(batch)
            S = next(r for r in self.slot_rungs if r >= S_active)
            tokens = np.zeros((S,), np.int32)
            seq_lens = np.zeros((S,), np.int32)
            active = np.zeros((S,), bool)
            tables = np.zeros((S, self.max_blocks), np.int32)
            uids = np.zeros((S,), np.int32)
            steps = np.zeros((S,), np.int32)
            for i, seq in enumerate(batch):
                tokens[i] = seq.generated[-1]
                seq_lens[i] = seq.tokens_in_pages
                active[i] = True
                tables[i, :len(seq.blocks)] = seq.blocks
                uids[i] = seq.uid
                steps[i] = len(seq.generated)
            fn = self._decode_fn(S)
            next_tok, self._k_pages, self._v_pages = fn(
                self.params, jnp.asarray(tokens), jnp.asarray(seq_lens),
                jnp.asarray(active), jnp.asarray(tables), self._k_pages,
                self._v_pages, jnp.asarray(uids), jnp.asarray(steps))
            next_tok = np.asarray(next_tok)
            m = _ENGINE_METRICS.get()
            dt_ms = (time.perf_counter() - t0) * 1e3
            m["step_ms"].observe(dt_ms, phase="decode")
            m["token_ms"].labels().observe(dt_ms / max(S_active, 1))
            m["tokens"].inc(S_active, phase="decode")
            if self.draft_tokens > 0:
                self._spec_fallbacks += 1
                m["spec_steps"].inc(mode="fallback")
            for i, seq in enumerate(batch):
                seq.tokens_in_pages += 1
                seq.generated.append(int(next_tok[i]))
                events.append(self._emit(seq, int(next_tok[i])))
                if not seq.done:
                    self._register_blocks(seq)
            self._update_pool_gauges()
        return events

    # ------------------------------------------------------------------
    # offline driver + warmup
    # ------------------------------------------------------------------
    def generate(self, prompts: Sequence[Sequence[int]], max_new_tokens,
                 uids: Sequence[int] | None = None) -> list[list[int]]:
        """Run a list of tokenized prompts to completion through the
        continuous scheduler; returns generated ids per prompt (EOS kept as
        the final token when hit). ``max_new_tokens`` is an int or a
        per-prompt sequence — the offline ``transform()`` surface of the
        SAME engine serving uses online."""
        if isinstance(max_new_tokens, (int, np.integer)):
            max_new_tokens = [int(max_new_tokens)] * len(prompts)
        seqs = [self.submit(p, n, uid=None if uids is None else int(u))
                for p, n, u in zip(prompts, max_new_tokens,
                                   uids if uids is not None
                                   else range(len(prompts)))]
        # progress = the ENGINE's tick counter, not our own calls returning
        # events: when a live serve loop drives the same shared engine
        # concurrently, ITS admit/step may do the work (and may hold every
        # slot for many seconds) — only a wholly-stalled engine raises
        last, idle = -1, 0
        while any(not s.done for s in seqs):
            self.admit()
            self.step()
            now = self._progress_ticks
            if now == last:
                idle += 1
                if idle > 2000:
                    stuck = [s.uid for s in seqs if not s.done]
                    raise RuntimeError(
                        f"paged engine stalled with sequences {stuck} "
                        f"unfinished (pool too small for a single "
                        f"sequence?)")
                if idle > 10:
                    time.sleep(0.001)  # another thread holds the work
            else:
                last, idle = now, 0
        return [list(s.generated) for s in seqs]

    def warmup(self, prompt_lens: Sequence[int] | None = None,
               slot_counts: Sequence[int] | None = None) -> int:
        """Precompile the prefill rungs (seq ladder up to ``max_len``) and
        decode rungs (slot ladder) WITHOUT touching live state: warmup
        programs run over all-trash block tables, so every write lands on
        the reserved page and the returned pools are discarded. Called from
        ``/admin/load`` so a hot-swapped LLM serves its first real request
        with zero compile stalls. Returns the number of programs exercised."""
        import jax.numpy as jnp

        if prompt_lens is None:
            prompt_lens = self.bucketer.seq_buckets_upto(self.max_len)
        if slot_counts is None:
            slot_counts = self.slot_rungs
        n = 0
        B = self.prefill_batch
        with self._lock:
            for P in sorted({self.bucketer.seq_bucket_for(int(p),
                                                          cap=self.max_len)
                             for p in prompt_lens}):
                fn = self._prefill_fn(B, P)
                ids = jnp.zeros((B, P), jnp.int32)
                mask = jnp.zeros((B, P), jnp.int32).at[:, 0].set(1)
                tables = jnp.zeros((B, self.max_blocks), jnp.int32)
                zi = jnp.zeros((B,), jnp.int32)
                # all writes land on the trash page, so reassigning the
                # returned pools is a no-op for live pages — and REQUIRED
                # under buffer donation (the input buffers are consumed)
                _, self._k_pages, self._v_pages = fn(
                    self.params, ids, mask, tables, self._k_pages,
                    self._v_pages, zi, zi)
                n += 1
            if self._prefix_cache is not None:
                for Q in sorted({self.bucketer.seq_bucket_for(
                        int(p), cap=self.max_len) for p in prompt_lens}):
                    fn = self._extend_fn(B, Q)
                    ids = jnp.zeros((B, Q), jnp.int32)
                    mask = jnp.zeros((B, Q), jnp.int32).at[:, 0].set(1)
                    tables = jnp.zeros((B, self.max_blocks), jnp.int32)
                    none = jnp.full((B,), -1, jnp.int32)
                    zi = jnp.zeros((B,), jnp.int32)
                    _, self._k_pages, self._v_pages = fn(
                        self.params, ids, mask, zi, tables, none, none,
                        self._k_pages, self._v_pages, zi, zi)
                    n += 1
            for S in sorted({int(s) for s in slot_counts}):
                fn = self._decode_fn(S)
                zs = jnp.zeros((S,), jnp.int32)
                tables = jnp.zeros((S, self.max_blocks), jnp.int32)
                _, self._k_pages, self._v_pages = fn(
                    self.params, zs, zs, jnp.zeros((S,), bool), tables,
                    self._k_pages, self._v_pages, zs, zs)
                n += 1
            if self.draft_tokens > 0:
                for S in sorted({int(s) for s in slot_counts}):
                    fn = self._spec_fn(S)
                    zs = jnp.zeros((S,), jnp.int32)
                    off = jnp.zeros((S,), bool)
                    tables = jnp.zeros((S, self.max_blocks), jnp.int32)
                    if self._drafter is not None:
                        W = self._draft_window
                        zw = jnp.zeros((S, W), jnp.int32)
                        _, _, self._k_pages, self._v_pages = fn(
                            self.params, self._draft_params, zs, zs, off,
                            tables, zw, zw, zs, self._k_pages,
                            self._v_pages)
                    else:
                        _, _, self._k_pages, self._v_pages = fn(
                            self.params, self._draft_params, zs, zs, off,
                            tables, self._k_pages, self._v_pages)
                    n += 1
        return n

    # ------------------------------------------------------------------
    # sequence migration (live drain / crash handoff)
    # ------------------------------------------------------------------
    def model_digest(self) -> str:
        """sha256 over the param tree (leaf names, shapes, dtypes, bytes)
        plus the generation-determinism knobs (sampling config, seed,
        eos) — two engines with equal digests emit identical token streams
        for the same ``(uid, prompt, generated)``, which is exactly the
        contract :meth:`import_sequence` needs to resume a migrated
        sequence without recompute. Computed once per engine."""
        if getattr(self, "_model_digest_v", None) is None:
            h = hashlib.sha256()
            for name, leaf in sorted(
                    serialization.flatten_pytree(self.params).items()):
                arr = np.ascontiguousarray(np.asarray(leaf))
                h.update(name.encode())
                h.update(repr((arr.shape, str(arr.dtype))).encode())
                h.update(arr.tobytes())
            h.update(repr((self.temperature, self.top_k, self.top_p,
                           self.seed, self.eos_id)).encode())
            self._model_digest_v = h.hexdigest()
        return self._model_digest_v

    def export_sequence(self, uid: int) -> dict | None:
        """Snapshot one live (active or waiting) sequence as a migratable
        artifact and remove it from this engine (pages freed, finish
        reason ``'migrated'``). Returns None for an unknown/finished uid.

        The snapshot is self-contained and wire-friendly::

            {"manifest": <JSON-able>, "payload": <npz bytes>,
             "digests": {"payload": <sha256 hex>}}

        The manifest carries the host state (prompt ids, emitted ids,
        sampling config, model digest) plus a ``chunks`` section in the
        PR-13 index-range format (``parallel/checkpoint.py``): per layer,
        ``kv/{k,v}/NNN`` maps to ``{"shape", "dtype", "parts": [{"key",
        "start", "stop"}]}`` where each part is one KV page's worth of
        token rows and ``payload`` npz key ``c:<name>#<k>`` holds the
        array. Ranges are TOKEN-indexed, not block-indexed, so an engine
        with a different ``block_len`` can still scatter them. The
        ``digests`` entry is the sha256 sidecar: import verifies it and
        falls back to re-prefill on mismatch rather than decoding over a
        torn payload."""
        with self._lock:
            seq = next((s for s in self._active if s.uid == int(uid)), None)
            was_waiting = False
            if seq is None:
                seq = next((s for s in self._waiting
                            if s.uid == int(uid)), None)
                if seq is None:
                    return None
                was_waiting = True
            T = 0 if was_waiting else int(seq.tokens_in_pages)
            manifest: dict = {
                "version": 1,
                "uid": int(seq.uid),
                "prompt_ids": [int(t) for t in seq.prompt_ids],
                "generated": [int(t) for t in seq.generated],
                "max_new_tokens": int(seq.max_new_tokens),
                "request_id": seq.request_id,
                "stream": bool(seq.stream),
                "preemptions": int(seq.preemptions),
                "tokens_in_pages": T,
                "journal_key": seq.journal_key,
                # deadlines are perf_counter instants, meaningless across
                # processes — ship the REMAINING budget instead
                "deadline_ms_left": (
                    None if seq.deadline is None
                    else (seq.deadline - time.perf_counter()) * 1e3),
                "sampling": {"temperature": self.temperature,
                             "top_k": self.top_k, "top_p": self.top_p,
                             "seed": self.seed, "eos_id": self.eos_id},
                "model_digest": self.model_digest(),
                "chunks": {},
            }
            payload: dict[str, np.ndarray] = {}
            if T > 0:
                rows = np.asarray(seq.blocks, np.int64)
                for axis, pool in (("k", self._k_pages),
                                   ("v", self._v_pages)):
                    for L, pages in enumerate(pool):
                        name = f"kv/{axis}/{L:03d}"
                        kvh, hd = int(pages.shape[2]), int(pages.shape[3])
                        flat = np.asarray(pages[rows]).reshape(
                            -1, kvh, hd)[:T]
                        parts = []
                        for k in range(len(seq.blocks)):
                            start = k * self.block_len
                            stop = min(start + self.block_len, T)
                            if start >= stop:
                                break
                            key = f"c:{name}#{k}"
                            payload[key] = _npz_safe(flat[start:stop])
                            parts.append({"key": key,
                                          "start": [start, 0, 0],
                                          "stop": [stop, kvh, hd]})
                        manifest["chunks"][name] = {
                            "shape": [T, kvh, hd],
                            "dtype": str(flat.dtype),
                            "parts": parts}
            buf = io.BytesIO()
            np.savez(buf, **payload)
            blob = buf.getvalue()
            if was_waiting:
                self._waiting.remove(seq)
            self._finish(seq, "migrated")
            return {"manifest": manifest, "payload": blob,
                    "digests": {
                        "payload": hashlib.sha256(blob).hexdigest()}}

    def import_sequence(self, snapshot: dict) -> SequenceState:
        """Readmit an exported sequence. Fast path: verify the model
        digest and the payload's sha256 sidecar, allocate pages, scatter
        the KV chunks in, and resume decode with ZERO recompute. On digest
        mismatch, sidecar mismatch, torn chunks, slot pressure, or page
        exhaustion: deterministic re-prefill over prompt+generated (the
        PR-6 preemption path — token-identical under greedy). Either way
        the next ``admit()``/``step()`` emits only NEW tokens; previously
        emitted ids ride in ``generated`` and are never re-surfaced."""
        import jax.numpy as jnp

        man = snapshot["manifest"]
        blob = snapshot.get("payload") or b""
        want = (snapshot.get("digests") or {}).get("payload")
        intact = man.get("model_digest") == self.model_digest()
        if intact and want is not None \
                and hashlib.sha256(blob).hexdigest() != want:
            intact = False  # torn payload: recompute, never decode garbage
        T = int(man.get("tokens_in_pages") or 0)
        left = man.get("deadline_ms_left")
        seq = SequenceState(
            uid=int(man["uid"]),
            prompt_ids=[int(t) for t in man["prompt_ids"]],
            max_new_tokens=int(man["max_new_tokens"]),
            request_id=man.get("request_id"),
            stream=bool(man.get("stream")),
            generated=[int(t) for t in man.get("generated") or []],
            preemptions=int(man.get("preemptions") or 0),
            journal_key=man.get("journal_key"),
            deadline=(None if left is None
                      else time.perf_counter() + float(left) / 1e3))
        if seq.generated:
            # ttft was observed at the origin engine; don't double-count
            seq.first_token_at = time.perf_counter()

        def _fallback():
            seq.tokens_in_pages = 0
            seq.preemptions += 1
            self._waiting.appendleft(seq)
            _ENGINE_METRICS.get()["preempted"].inc()
            return seq

        with self._lock:
            self._uid = max(self._uid, seq.uid)
            # invariant of an active sequence: pages hold every context
            # token except the newest generated one (which rides as the
            # next decode step's input token)
            resumable = (intact and T > 0 and seq.generated
                         and T == len(seq.context_ids) - 1
                         and len(self._active) < self.max_slots
                         and T < self.max_len)
            if not resumable:
                return _fallback()
            self._reclaim(self._blocks_for(T))
            blocks = self.allocator.alloc(self._blocks_for(T))
            if blocks is None:
                return _fallback()  # import-side page exhaustion
            try:
                data = np.load(io.BytesIO(blob), allow_pickle=False)
                for axis in ("k", "v"):
                    pool = self._k_pages if axis == "k" else self._v_pages
                    new_pool = []
                    for L, pages in enumerate(pool):
                        name = f"kv/{axis}/{L:03d}"
                        entry = man["chunks"][name]
                        kvh, hd = int(pages.shape[2]), int(pages.shape[3])
                        dt = np.dtype(entry["dtype"])
                        staged = np.zeros(
                            (len(blocks) * self.block_len, kvh, hd), dt)
                        for part in entry["parts"]:
                            arr = np.asarray(data[part["key"]])
                            if arr.dtype == np.uint8 and dt != np.uint8:
                                arr = np.frombuffer(arr.tobytes(), dt)
                            lo, hi = part["start"][0], part["stop"][0]
                            staged[lo:hi] = arr.reshape(hi - lo, kvh, hd)
                        staged = staged.reshape(
                            len(blocks), self.block_len, kvh, hd)
                        new_pool.append(pages.at[jnp.asarray(blocks)].set(
                            jnp.asarray(staged)))
                    if axis == "k":
                        self._k_pages = tuple(new_pool)
                    else:
                        self._v_pages = tuple(new_pool)
            except Exception:
                # torn/incomplete chunk set — the freed blocks may hold
                # partial writes, but pages are only read below a live
                # sequence's seq_len and every (re-)prefill overwrites its
                # pages first, so stale rows can never leak into attention
                self.allocator.free(blocks)
                return _fallback()
            seq.blocks = list(blocks)
            seq.tokens_in_pages = T
            self._active.append(seq)
            self._update_pool_gauges()
            return seq

    def live_sequences(self) -> list[SequenceState]:
        """Every active + waiting sequence (a consistent snapshot) — the
        drain path iterates this to export each one."""
        with self._lock:
            return list(self._active) + list(self._waiting)

    def expire_deadlines(self, now: float | None = None) -> list[dict]:
        """Finish every sequence whose client deadline has passed (pages
        freed immediately, ``finish_reason='deadline'``); returns terminal
        events for the serving layer to 504. Runs at the top of every
        :meth:`admit`/:meth:`step`, so an expired sequence never costs
        another device step."""
        now = time.perf_counter() if now is None else now
        events: list[dict] = []
        with self._lock:
            doomed = [s for s in self._active
                      if s.deadline is not None and now >= s.deadline]
            doomed += [s for s in self._waiting
                       if s.deadline is not None and now >= s.deadline]
            for seq in doomed:
                if seq in self._waiting:
                    self._waiting.remove(seq)
                self._finish(seq, "deadline")
                events.append({"seq": seq, "token": None, "done": True,
                               "finish_reason": "deadline"})
        return events

    def abort(self, seq: SequenceState, reason: str = "aborted") -> None:
        """Terminate one sequence (client gone / stream broken), freeing its
        pages and slot immediately so dead connections cannot pin decode
        capacity. ``reason`` distinguishes ``'client_gone'`` (disconnect
        reaping) from a generic ``'aborted'`` in the finished counter."""
        with self._lock:
            if not seq.done:
                if seq in self._waiting:
                    self._waiting.remove(seq)
                self._finish(seq, reason)

    def abort_all(self, reason: str = "aborted") -> list[SequenceState]:
        """Terminate every waiting and active sequence (reason
        ``'aborted'``), freeing all pages — the hot-swap path drains the
        outgoing engine through this so no request stalls silently."""
        with self._lock:
            doomed = list(self._active) + list(self._waiting)
            self._waiting.clear()
            for seq in doomed:
                if not seq.done:
                    self._finish(seq, reason)
            return doomed

    def stats(self) -> dict:
        with self._lock:
            cap = self.allocator.capacity
            out = {"active": len(self._active),
                   "waiting": len(self._waiting),
                   "blocks_used": self.allocator.used_count,
                   "blocks_free": self.allocator.free_count,
                   "occupancy": self.allocator.used_count / cap if cap
                   else 0.0}
            if self._prefix_cache is not None:
                pc = self._prefix_cache.stats()
                pc["occupancy"] = pc["blocks"] / cap if cap else 0.0
                out["prefix_cache"] = pc
            if self.draft_tokens > 0:
                out["speculation"] = {
                    "draft_tokens": self.draft_tokens,
                    "proposed": self._spec_proposed,
                    "accepted": self._spec_accepted,
                    "acceptance_rate": (
                        self._spec_accepted / self._spec_proposed
                        if self._spec_proposed else 0.0),
                    "steps": self._spec_steps,
                    "fallbacks": self._spec_fallbacks}
            return out

    def release(self) -> None:
        """Evict this engine's compiled programs from the shared cache and
        mark the engine dead — a failed device call may have consumed the
        donated page buffers, so a released engine must never be reused
        (``HuggingFaceCausalLM._paged_engine`` rebuilds instead of
        returning it from its cache)."""
        self._released = True
        cb.get_compiled_cache().evict_instance(self._instance)
