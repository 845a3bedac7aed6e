#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process (a chip belongs to one process at a time). First act: resolve
devices and FAIL unless ``jax.devices()[0].platform == "tpu"``. Then, through
the entry points a user calls, with seeded data and weights and no network:

  Leg A  ``Pipeline([DeepTextClassifier(bert-base)]).fit`` at (32, 128) with
         async checkpoints -> transform -> save -> load -> transform
  Leg B  both Pallas kernels lowered to Mosaic and agreeing with their XLA
         paths at real shapes; BERT-base steps with flash attention at T=512;
         LightGBMClassifier on 1,000,000 x 28 with the segment and the pallas
         histogram backends
  Leg C  ``serve_llm`` over a seeded HF-format checkpoint at Llama-2-7B's
         published widths (depth cut to what the chip holds): HTTP requests,
         paged tokens against the dense engine's, no compile after warm-up
  Leg D  (four devices) the same trainer under data-parallel, fsdp x tensor
         and data x seq (ring attention) meshes against a one-chip run

A failed leg is recorded, the remaining legs still run, and the exit code is
non-zero. The last stdout line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Compile/run seconds and peak HBM printed per leg are first facts, not
benchmark results.

``--cpu-tiny`` is the explicit way to drive the same legs at toy sizes on the
CPU (kernels interpreted) while debugging; every line then says ``cpu``. It
is never inferred: without it, finding no accelerator exits 2 before any work.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import math
import os
import resource
import shutil
import sys
import threading
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
LN2 = math.log(2.0)

# Full width of the models the repo supports; only Leg C's depth is cut.
FULL = dict(
    # lr: random-init post-norm BERT-base spikes above 1e-5 and has not
    # recovered by step 24
    text=dict(checkpoint="bert-base", batch=32, seq=128, steps=24,
              ckpt_every=8, rows=512, lr=1e-5),
    flash=dict(shape=(8, 512, 12, 64), fit_batch=8, fit_seq=512, fit_steps=4,
               fit_rows=64),
    hist=dict(rows=1_000_000, segments=32 * 256),
    gbdt=dict(rows=1_000_000, features=28, test_rows=100_000, iterations=5,
              leaves=31, max_bin=255),
    # llama2_7b's published widths (models/flax_nets/llama.py); max_len bounds
    # the warm-up ladder, layers=None is sized from the device's memory
    llm=dict(hidden=4096, heads=32, mlp=11008, vocab=32000, max_len=256,
             layers=None, slots=4, new_tokens=12),
    mesh=dict(steps=4, ring_batch=8, ring_seq=512),
)
TINY = dict(
    text=dict(checkpoint="bert-tiny", batch=8, seq=16, steps=24, ckpt_every=8,
              rows=64, lr=1e-3),
    flash=dict(shape=(2, 32, 2, 16), fit_batch=4, fit_seq=32, fit_steps=2,
               fit_rows=16),
    hist=dict(rows=5000, segments=4 * 64),
    gbdt=dict(rows=4000, features=8, test_rows=1000, iterations=3, leaves=7,
              max_bin=63),
    llm=dict(hidden=64, heads=4, mlp=128, vocab=256, max_len=64, layers=2,
             slots=4, new_tokens=6),
    mesh=dict(steps=4, ring_batch=4, ring_seq=32),
)


# ---------------------------------------------------------------------------
# accounting: compile vs run seconds, cache hits, peak device memory
# ---------------------------------------------------------------------------

def memory_facts(device) -> dict:
    """``peak_bytes_in_use`` is the process's high-water mark so far (the
    backend cannot reset it), so a later leg reports at least an earlier
    leg's peak. None on the CPU."""
    stats = device.memory_stats()
    if not stats:
        return {"peak_bytes_in_use": None}
    return {"peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_in_use": stats.get("bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit")}


class Leg:
    """Facts and checks of one leg. ``check`` raises on a false condition —
    a leg either meets every assertion or is recorded as failed."""

    def __init__(self, name: str, ctx: "Ctx"):
        self.name, self.ctx, self.facts = name, ctx, {}
        # checkpoints, saved models, the LLM weights: removed after the leg
        self.scratch = os.path.join(ctx.out, f"leg_{name.lower()}_scratch")

    def check(self, cond, what: str) -> None:
        if not cond:
            raise AssertionError(f"Leg {self.name}: {what}")

    def fact(self, **kv) -> None:
        self.facts.update(kv)
        print(f"[{self.name}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
              flush=True)

    @contextlib.contextmanager
    def timed(self, label: str):
        """Wall seconds of a phase plus the compile work jax did inside it:
        ``<label>_run_s`` = wall minus trace/lower/compile."""
        before, t0 = self.ctx.meter.snapshot(), time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        d = self.ctx.meter.since(before)
        built = d["trace_s"] + d["lower_s"] + d["compile_s"]
        self.fact(**{f"{label}_wall_s": round(wall, 2),
                     f"{label}_compile_s": round(built, 2),
                     f"{label}_run_s": round(max(wall - built, 0.0), 2),
                     f"{label}_programs": d["programs"],
                     f"{label}_cache_hits": d["cache_hits"]})


class Ctx:
    def __init__(self, sizes, devices, meter, out_dir):
        self.sizes, self.devices = sizes, devices
        self.meter, self.out = meter, out_dir
        self.on_tpu = devices[0].platform == "tpu"
        self.thread_errors: list[str] = []


def lowered_text(fn, *args) -> str:
    import jax

    return jax.jit(fn).lower(*args).as_text()


def check_mosaic(leg: Leg, what: str, text: str) -> None:
    """On the chip a Pallas kernel must be a Mosaic call in the lowered
    program, i.e. not the interpreter. (The CPU mode interprets.)"""
    has = "tpu_custom_call" in text
    leg.fact(**{f"{what}_tpu_custom_call": has})
    if leg.ctx.on_tpu:
        leg.check(has, f"{what} did not lower to a Mosaic tpu_custom_call")


# ---------------------------------------------------------------------------
# seeded data
# ---------------------------------------------------------------------------

def text_corpus(rows: int, min_words: int, max_words: int, seed: int):
    """Two-class corpus whose rows are long enough to fill the sequence:
    ``pad_sequences`` sizes T from the data, so two-word rows would train at
    T=8. Class k draws half its words from its own vocabulary."""
    import numpy as np

    import synapseml_tpu as st

    rs = np.random.default_rng(seed)
    recs = []
    for i in range(rows):
        label = i % 2
        n = int(rs.integers(min_words, max_words + 1))
        own = rs.integers(0, 20, n)
        shared = rs.integers(0, 2000, n)
        pick = rs.random(n) < 0.5
        words = [(f"{'pos' if label else 'neg'}{o}" if p else f"w{s}")
                 for o, s, p in zip(own, shared, pick)]
        recs.append({"text": " ".join(words), "label": label})
    return st.DataFrame.from_rows(recs)


def auc(y, score) -> float:
    """Mann-Whitney AUC with average ranks for ties."""
    import numpy as np

    y = np.asarray(y).astype(bool)
    _, inv, counts = np.unique(score, return_inverse=True, return_counts=True)
    ends = np.cumsum(counts)
    ranks = (ends - (counts - 1) / 2.0)[inv]
    n1, n0 = int(y.sum()), int((~y).sum())
    return float((ranks[y].sum() - n1 * (n1 + 1) / 2.0) / (n1 * n0))


def sequence_len_trained(batch: int) -> float:
    """Tokens per sample the trainer placed on the device in this process's
    last fit, from the fit loop's own spans: the bytes of its `train.place`
    spans over the steps of its `train.dispatch` spans, per sample, less the
    int32 label and the loader's float32 `_valid`, over the bytes a token takes
    in the tokenizer's arrays."""
    import numpy as np

    from synapseml_tpu.core import observability as obs
    from synapseml_tpu.models.tokenizer import resolve_tokenizer

    spans = obs.get_tracer().finished_spans()
    root = [s for s in spans if s.name == "train.fit"][-1]
    kids = [s for s in spans if s.parent_id == root.span_id]
    placed = sum(s.attributes["bytes"] for s in kids if s.name == "train.place")
    steps = sum(s.attributes["steps"] for s in kids if s.name == "train.dispatch")
    token_bytes = sum(np.asarray(v).dtype.itemsize
                      for v in resolve_tokenizer(None)(["a"], max_len=8).values())
    return (placed / (steps * batch) - 8) / token_bytes


# ---------------------------------------------------------------------------
# Leg A — the main path
# ---------------------------------------------------------------------------

def leg_a(leg: Leg) -> None:
    import jax
    import numpy as np

    import synapseml_tpu as st
    from synapseml_tpu.core import observability as obs
    from synapseml_tpu.models import DeepTextClassifier
    from synapseml_tpu.models.tokenizer import resolve_tokenizer
    from synapseml_tpu.parallel.checkpoint import (latest_verified_step,
                                                   restore_checkpoint)

    s, ctx = leg.ctx.sizes["text"], leg.ctx
    df = text_corpus(s["rows"], s["seq"], 2 * s["seq"], seed=0)
    enc = resolve_tokenizer(None)(list(df.collect_column("text")),
                                  max_len=s["seq"])
    leg.check(enc["input_ids"].shape == (s["rows"], s["seq"]),
              f"corpus tokenizes to {enc['input_ids'].shape}, want T={s['seq']}")

    ckdir = os.path.join(leg.scratch, "checkpoints")
    est = DeepTextClassifier(
        checkpoint=s["checkpoint"], num_classes=2, batch_size=s["batch"],
        max_token_len=s["seq"], max_steps=s["steps"], learning_rate=s["lr"],
        checkpoint_dir=ckdir, checkpoint_every=s["ckpt_every"], seed=0)
    with leg.timed("fit"):
        model = st.Pipeline(stages=[est]).fit(df)
    stage = model.get("stages")[0]
    params = stage.get("model_params")
    n_params = sum(int(np.prod(np.shape(x))) for x in jax.tree.leaves(params))
    entry = stage.get("train_metrics")[-1]
    leg.fact(n_params=n_params, steps=entry["step"],
             final_loss=round(entry["loss"], 4),
             samples_per_sec=round(entry["samples_per_sec"], 1))   # first dispatch left out
    leg.check(entry["step"] == s["steps"], f"trained {entry['step']} steps")
    seq_seen = sequence_len_trained(s["batch"])
    leg.fact(trained_batch=(s["batch"], round(seq_seen, 2)))
    leg.check(abs(seq_seen - s["seq"]) < 0.5,
              f"trainer saw T={seq_seen:.2f}, want {s['seq']}")
    snap = obs.get_registry().snapshot()
    leg.check(snap.get("synapseml_train_last_finite_step") == s["steps"]
              and not sum(v for k, v in snap.items()
                          if k.startswith("synapseml_train_nonfinite_total")),
              "a training loss was not finite")
    leg.check(entry["loss"] < LN2 - 0.05,
              f"final loss {entry['loss']:.4f} did not fall below ln2")
    if ctx.on_tpu:
        from synapseml_tpu.core.instrumentation import chip_peak_tflops

        # raises for a device the peak table does not know
        leg.fact(chip_peak_tflops=chip_peak_tflops(ctx.devices[0].device_kind))
        # params + both Adam moments, f32, lived on the device
        peak = memory_facts(ctx.devices[0])["peak_bytes_in_use"]
        leg.check(peak >= 3 * 4 * n_params / len(ctx.devices),
                  f"peak HBM {peak} is below params+optimizer state")

    with leg.timed("transform_first"):
        out = model.transform(df)
        scores = np.asarray(list(out.collect_column("scores")))
    with leg.timed("transform_again"):
        again = np.asarray(list(model.transform(df).collect_column("scores")))
    labels = np.asarray(df.collect_column("label"))
    nll = float(-np.mean(np.log(scores[np.arange(len(labels)), labels] + 1e-9)))
    acc = float(np.mean(np.argmax(scores, -1) == labels))
    leg.fact(scored=scores.shape, train_nll=round(nll, 4), train_acc=round(acc, 3))
    leg.check(scores.shape == (s["rows"], 2) and np.all(np.isfinite(scores)),
              "scores are not finite (rows, 2)")
    leg.check(np.array_equal(scores, again), "two transforms disagree")
    leg.check(nll < LN2 - 0.05, f"post-fit NLL {nll:.4f} is not below ln2")

    path = os.path.join(leg.scratch, "model")
    with leg.timed("save_load_transform"):
        model.save(path)
        loaded = st.PipelineModel.load(path)
        reloaded = np.asarray(list(loaded.transform(df).collect_column("scores")))
    leg.check(np.array_equal(np.argmax(scores, -1), np.argmax(reloaded, -1))
              and np.allclose(scores, reloaded, atol=1e-6),
              "predictions changed across save/load")

    last = latest_verified_step(ckdir)
    leg.check(last == s["steps"], f"newest verified checkpoint is step {last}")
    tree = restore_checkpoint(ckdir, last)
    same = jax.tree.map(lambda a, b: bool(np.array_equal(np.asarray(a), b)),
                        tree["params"], params)
    leg.check(all(jax.tree.leaves(same)) and "data_iter" in tree,
              "the newest checkpoint does not restore the final params")
    leg.fact(checkpoint_step=last)


# ---------------------------------------------------------------------------
# Leg B — kernels compile and agree, at real shapes
# ---------------------------------------------------------------------------

def leg_b(leg: Leg) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import synapseml_tpu as st
    from synapseml_tpu.gbdt import LightGBMClassifier
    from synapseml_tpu.gbdt.pallas_hist import pallas_segment_histogram
    from synapseml_tpu.models import DeepTextClassifier
    from synapseml_tpu.ops import flash_attention, reference_attention

    sizes, ctx = leg.ctx.sizes, leg.ctx
    rs = np.random.default_rng(1)

    # -- flash attention vs the XLA reference: forward and grad, padded mask
    B, T, H, D = sizes["flash"]["shape"]
    q, k, v = (jnp.asarray(rs.normal(size=(B, T, H, D)), jnp.bfloat16)
               for _ in range(3))
    lens = rs.integers(T // 2, T + 1, B)
    mask = jnp.asarray(np.arange(T)[None, :] < lens[:, None])

    def loss_of(attn):
        return lambda q_, k_, v_: jnp.sum(
            attn(q_, k_, v_, kv_mask=mask).astype(jnp.float32) ** 2)

    flash_fwd = lambda q_, k_, v_: flash_attention(q_, k_, v_, kv_mask=mask)
    flash_grad = jax.grad(loss_of(flash_attention), argnums=(0, 1, 2))
    check_mosaic(leg, "flash_fwd", lowered_text(flash_fwd, q, k, v))
    check_mosaic(leg, "flash_grad", lowered_text(flash_grad, q, k, v))
    with leg.timed("flash_first"):
        got = jax.block_until_ready(jax.jit(flash_fwd)(q, k, v))
        got_g = jax.block_until_ready(jax.jit(flash_grad)(q, k, v))
    want = jax.jit(lambda q_, k_, v_: reference_attention(
        q_, k_, v_, kv_mask=mask))(q, k, v)
    want_g = jax.jit(jax.grad(loss_of(reference_attention),
                              argnums=(0, 1, 2)))(q, k, v)
    with leg.timed("flash_again"):
        jax.block_until_ready(jax.jit(flash_fwd)(q, k, v))

    def rel_err(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9))

    fwd_err = rel_err(got, want)
    grad_err = max(rel_err(a, b) for a, b in zip(got_g, want_g))
    leg.fact(flash_shape=(B, T, H, D), flash_fwd_rel_err=round(fwd_err, 5),
             flash_grad_rel_err=round(grad_err, 5), tolerance=0.03)
    # bf16 operands, f32 accumulation on both sides: 3% of the largest value
    leg.check(np.all(np.isfinite(np.asarray(got, np.float32)))
              and fwd_err < 0.03 and grad_err < 0.03,
              f"flash vs reference: fwd {fwd_err:.4f} grad {grad_err:.4f}")

    # -- the estimator with attn_impl="flash" at the long sequence
    f = sizes["flash"]
    df = text_corpus(f["fit_rows"], f["fit_seq"], 2 * f["fit_seq"], seed=2)
    est = DeepTextClassifier(
        checkpoint=sizes["text"]["checkpoint"], num_classes=2,
        batch_size=f["fit_batch"], max_token_len=f["fit_seq"],
        max_steps=f["fit_steps"], attn_impl="flash", seed=0)
    with leg.timed("flash_fit"):
        stage = est.fit(df)
    entry = stage.get("train_metrics")[-1]
    seq_seen = sequence_len_trained(f["fit_batch"])
    leg.fact(flash_fit_steps=entry["step"], flash_fit_loss=round(entry["loss"], 4),
             flash_fit_batch=(f["fit_batch"], round(seq_seen, 2)))
    leg.check(entry["step"] == f["fit_steps"] and np.isfinite(entry["loss"])
              and abs(seq_seen - f["fit_seq"]) < 0.5,
              f"flash fit: {entry}, T seen {seq_seen:.2f}")
    leg.check(stage.get("arch_config").attn_impl == "flash",
              "the fitted model is not a flash-attention model")
    del stage, est

    # -- histogram kernel vs segment_sum
    N, WB = sizes["hist"]["rows"], sizes["hist"]["segments"]
    seg = jnp.asarray(rs.integers(0, WB, N), jnp.int32)
    data = jnp.asarray(rs.normal(size=(N, 3)), jnp.float32)
    check_mosaic(leg, "hist", lowered_text(
        lambda s_, d_: pallas_segment_histogram(s_, d_, WB), seg, data))
    with leg.timed("hist_first"):
        got = jax.block_until_ready(pallas_segment_histogram(seg, data, WB))
    want = jax.jit(lambda s_, d_: jax.ops.segment_sum(
        d_, s_, num_segments=WB))(seg, data)
    with leg.timed("hist_again"):
        jax.block_until_ready(pallas_segment_histogram(seg, data, WB))
    err = float(jnp.max(jnp.abs(got - want)))
    leg.fact(hist_shape=(N, WB), hist_max_abs_err=round(err, 6), tolerance=1e-3)
    # ~N/WB f32 addends of unit scale per bin, summed in another order
    leg.check(got.shape == (WB, 3) and err < 1e-3,
              f"histogram kernel vs segment_sum: max abs err {err}")

    # -- LightGBMClassifier, Higgs-shaped, both backends
    g = sizes["gbdt"]
    n_all = g["rows"] + g["test_rows"]
    X = rs.normal(size=(n_all, g["features"])).astype(np.float32)
    w = rs.normal(size=g["features"])
    w[g["features"] // 2:] = 0
    y = ((X @ w) * 0.5 + rs.normal(size=n_all) * 0.5 > 0).astype(np.int32)
    train = st.DataFrame.from_dict({"features": X[:g["rows"]],
                                    "label": y[:g["rows"]]})
    test = st.DataFrame.from_dict({"features": X[g["rows"]:]})
    aucs = {}
    for impl in ("segment", "pallas"):
        est = LightGBMClassifier(num_iterations=g["iterations"],
                                 num_leaves=g["leaves"], max_bin=g["max_bin"],
                                 histogram_impl=impl, seed=0)
        with leg.timed(f"gbdt_{impl}_fit"):
            booster = est.fit(train)
        with leg.timed(f"gbdt_{impl}_score"):
            prob = np.asarray(list(
                booster.transform(test).collect_column("probability")))
        aucs[impl] = auc(y[g["rows"]:], prob[:, 1])
    leg.fact(gbdt_shape=(g["rows"], g["features"]),
             auc_segment=round(aucs["segment"], 4),
             auc_pallas=round(aucs["pallas"], 4), auc_gate=0.005)
    leg.check(min(aucs.values()) > 0.8
              and abs(aucs["segment"] - aucs["pallas"]) <= 0.005,
              f"GBDT backends disagree or did not learn: {aucs}")


# ---------------------------------------------------------------------------
# Leg C — the server answers
# ---------------------------------------------------------------------------

def llm_depth(cfg: dict, device) -> int:
    """Layers one chip holds beside its KV pool with float32 params: two
    fifths of device memory for the weights (the rest is the bf16 working
    copy XLA hoists, the KV pool and activations)."""
    if cfg["layers"] is not None:
        return cfg["layers"]
    limit = memory_facts(device)["bytes_limit"]
    fixed = 2 * cfg["vocab"] * cfg["hidden"] * 4
    per_layer = (4 * cfg["hidden"] ** 2 + 3 * cfg["hidden"] * cfg["mlp"]) * 4
    return int(max(2, min(32, (0.4 * limit - fixed) // per_layer)))


def write_llama_checkpoint(path: str, cfg: dict, layers: int, seed: int):
    """A seeded HF-format Llama checkpoint: config.json + float32 safetensors
    shards, none larger than the token embedding (0.5 GB), since a machine
    may refuse larger files. Returns (parameter count, largest file bytes)."""
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np
    from safetensors.numpy import save_file

    os.makedirs(path)
    H, M, V = cfg["hidden"], cfg["mlp"], cfg["vocab"]
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump({"model_type": "llama", "hidden_size": H,
                   "intermediate_size": M, "num_attention_heads": cfg["heads"],
                   "num_key_value_heads": cfg["heads"],
                   "num_hidden_layers": layers, "vocab_size": V,
                   "max_position_embeddings": cfg["max_len"],
                   "rms_norm_eps": 1e-5, "rope_theta": 10000.0}, f)

    def tensor(idx, shape):
        w = np.random.default_rng([seed, idx]).standard_normal(shape, np.float32)
        w *= 0.02
        return w

    ones = lambda: np.ones(H, np.float32)
    shapes = {"self_attn.q_proj": (H, H), "self_attn.k_proj": (H, H),
              "self_attn.v_proj": (H, H), "self_attn.o_proj": (H, H),
              "mlp.gate_proj": (M, H), "mlp.up_proj": (M, H),
              "mlp.down_proj": (H, M)}
    wide = ("mlp.gate_proj", "mlp.up_proj")     # their own file per layer

    def layer(i, names):
        return {f"model.layers.{i}.{name}.weight": tensor(10 * i + j, shapes[name])
                for j, name in enumerate(shapes) if name in names}

    files = {"model-embed.safetensors": lambda: {
                 "model.embed_tokens.weight": tensor(1000, (V, H))},
             "model-head.safetensors": lambda: {
                 "lm_head.weight": tensor(1001, (V, H)),
                 "model.norm.weight": ones()}}
    for i in range(layers):
        p = f"model.layers.{i}"
        files[f"model-{i:05d}-a.safetensors"] = lambda i=i, p=p: {
            **layer(i, set(shapes) - set(wide)),
            f"{p}.input_layernorm.weight": ones(),
            f"{p}.post_attention_layernorm.weight": ones()}
        files[f"model-{i:05d}-b.safetensors"] = lambda i=i: layer(i, wide)

    def write(name):
        tensors = files[name]()
        save_file(tensors, os.path.join(path, name))
        return (name, {k: int(v.size) for k, v in tensors.items()},
                os.path.getsize(os.path.join(path, name)))

    weight_map, n_params, largest = {}, 0, 0
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as pool:
        for name, sizes, nbytes in pool.map(write, files):
            weight_map.update({k: name for k in sizes})
            n_params += sum(sizes.values())
            largest = max(largest, nbytes)
    with open(os.path.join(path, "model.safetensors.index.json"), "w") as f:
        json.dump({"weight_map": weight_map}, f)
    return n_params, largest


def http_json(address: str, payload: dict, headers: dict | None = None,
              timeout: float = 300.0):
    """POST one request; returns (status, [json objects]) — one object for a
    buffered reply, one per NDJSON line for a streamed one."""
    import http.client

    host, port = address.split("//")[1].split(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=timeout)
    try:
        conn.request("POST", "/", body=json.dumps(payload).encode(),
                     headers=headers or {})
        r = conn.getresponse()
        return r.status, [json.loads(line) for line in r.read().splitlines()
                          if line.strip()]
    finally:
        conn.close()


def leg_c(leg: Leg) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import synapseml_tpu as st
    from synapseml_tpu.core import batching as cb
    from synapseml_tpu.core import observability as obs
    from synapseml_tpu.core.platform import check_chip_launch
    from synapseml_tpu.hf import HuggingFaceCausalLM
    from synapseml_tpu.io.serving import serve_llm
    from synapseml_tpu.models.flax_nets.llama import LlamaLM

    cfg, ctx = leg.ctx.sizes["llm"], leg.ctx
    layers = llm_depth(cfg, ctx.devices[0])
    ckpt = os.path.join(leg.scratch, "llama")
    t0 = time.perf_counter()
    n_params, largest = write_llama_checkpoint(ckpt, cfg, layers, seed=3)
    leg.fact(widths=dict(hidden=cfg["hidden"], heads=cfg["heads"],
                         mlp_dim=cfg["mlp"], vocab=cfg["vocab"]),
             depth_cut=f"{layers} of 32 layers", max_len=cfg["max_len"],
             n_params=n_params, param_dtype="float32",
             largest_checkpoint_file_bytes=largest,
             checkpoint_write_s=round(time.perf_counter() - t0, 1))

    tok = {"kind": "hashing", "vocab_size": cfg["vocab"], "lowercase": True,
           "add_cls": True}
    new = cfg["new_tokens"]

    def stage(engine):
        return HuggingFaceCausalLM(
            model_name=ckpt, tokenizer=tok, engine=engine, max_new_tokens=new,
            batch_size=cfg["slots"], decode_slots=cfg["slots"],
            prompt_bucket=16)

    prompts = ["the quick brown fox jumps over the lazy dog",
               "to be or not to be that is the question whether tis nobler",
               "chip smoke",
               " ".join(f"token{i}" for i in range(40))]
    cache, reg = cb.get_compiled_cache(), obs.get_registry()
    paged_fns = ("llama_paged_prefill", "llama_paged_decode")
    misses = lambda: sum(cache.miss_count(fn) for fn in paged_fns)

    # the dense engine first, then its weights and executables go: each
    # stage holds its own device copy of the checkpoint, and two do not fit
    dense = stage("dense")
    with leg.timed("dense_load_and_transform"):
        out = dense.transform(st.DataFrame.from_dict({"prompt": prompts}))
        want = [np.asarray(t).tolist() for t in out.collect_column("completions")]
    del dense, out
    cb.reset_compiled_cache()
    gc.collect()

    lm = stage("paged")
    with leg.timed("load_and_warmup"):
        srv = serve_llm(lm, warmup=True)
        deadline = time.monotonic() + 900
        while srv.llm_stats_fn() is None:       # engine built + warmed
            leg.check(not ctx.thread_errors, "the serve loop died: "
                      + " | ".join(ctx.thread_errors))
            leg.check(time.monotonic() < deadline, "warm-up outran 900 s")
            time.sleep(0.2)
    model, params, tokenizer, _ = lm._model_and_params()
    leg.check(all(isinstance(x, jax.Array) and x.devices() == {ctx.devices[0]}
                  for x in jax.tree.leaves(params)),
              "LLM params are not resident on the device")
    warm = misses()
    leg.fact(warmup_executables=warm)
    leg.check(warm > 0, "warmup() compiled nothing")
    before = ctx.meter.snapshot()
    try:
        served = {}
        with leg.timed("requests"):
            status, body = http_json(srv.address, {"prompt": prompts[0]})
            leg.check(status == 200 and body[0]["n_tokens"] == new,
                      f"buffered request: {status} {body}")
            served[0] = body[0]["output_ids"]

            status, chunks = http_json(srv.address, {"prompt": prompts[1],
                                                     "stream": True})
            leg.check(status == 200 and len(chunks) == new + 1
                      and chunks[-1]["done"]
                      and [c["token"] for c in chunks[:-1]]
                      == chunks[-1]["output_ids"],
                      f"streamed request: {status} {chunks[-1:]}")
            served[1] = chunks[-1]["output_ids"]

            # two concurrent requests with different lengths
            replies = {}

            def fire(i, n):
                replies[i] = http_json(srv.address, {"prompt": prompts[i],
                                                     "max_new_tokens": n})

            threads = [threading.Thread(target=fire, args=(2, new)),
                       threading.Thread(target=fire, args=(3, max(new // 2, 1)))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=600)
            leg.check(sorted(replies) == [2, 3]
                      and all(code == 200 for code, _ in replies.values())
                      and replies[3][1][0]["n_tokens"] == max(new // 2, 1),
                      f"concurrent requests: {replies}")
            served[2] = replies[2][1][0]["output_ids"]
            served[3] = replies[3][1][0]["output_ids"]

            status, body = http_json(srv.address, {"prompt": prompts[0]},
                                     headers={"X-Deadline-Ms": "1"})
            leg.check(status == 504 and body[0]["finish_reason"] == "deadline",
                      f"deadline request: {status} {body}")
        leg.check(not ctx.thread_errors, " | ".join(ctx.thread_errors))

        after = ctx.meter.since(before)
        leg.fact(cache_misses_after_warmup=misses() - warm,
                 xla_programs_built_while_serving=after["programs"])
        leg.check(misses() == warm, "a serving executable compiled after "
                  f"warm-up ({misses() - warm} CompiledCache misses)")
        for _ in range(200):     # freed pages land on the gauge
            occ = reg.snapshot().get("synapseml_llm_kv_block_occupancy")
            if occ == 0:
                break
            time.sleep(0.05)
        leg.fact(kv_block_occupancy=occ)
        leg.check(occ == 0, f"KV pages still held after the requests: {occ}")

        # a launcher must refuse to start chip workers from this process
        if ctx.on_tpu:
            env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
            try:
                check_chip_launch(1, env)
            except RuntimeError as e:
                leg.fact(launcher_refusal=str(e)[:60] + "...")
            else:
                leg.check(False, "a worker launch from the chip-holding "
                          "process was not refused")
    finally:
        srv.stop()

    flips = 0
    for i, got in served.items():
        ref = want[i][:len(got)]
        if got == ref:
            continue
        # greedy parity holds up to numerical ties: the first divergence
        # must be two tokens whose reference logits are equal to rounding
        j = next(j for j, (a, b) in enumerate(zip(got, ref)) if a != b)
        enc = tokenizer([prompts[i]], max_len=cfg["max_len"], multiple_of=1)
        ids = enc["input_ids"][0][enc["attention_mask"][0] > 0].tolist() + got[:j]
        logits = np.asarray(LlamaLM(model.cfg).apply(
            {"params": params}, jnp.asarray([ids], jnp.int32))[0, -1], np.float32)
        gap = abs(float(logits[got[j]] - logits[ref[j]]))
        top = float(np.max(logits))
        leg.fact(**{f"prompt{i}_diverges_at": j, f"prompt{i}_logit_gap": gap})
        leg.check(gap <= 0.02 * max(abs(top), 1.0)
                  and max(logits[got[j]], logits[ref[j]]) >= top - gap,
                  f"prompt {i}: paged {got} vs dense {ref} diverge at {j} "
                  f"beyond a numerical tie (gap {gap}, top {top})")
        flips += 1
    leg.fact(prompts_compared=len(served),
             tokens_compared=sum(map(len, served.values())),
             near_tie_divergences=flips)


# ---------------------------------------------------------------------------
# Leg D — four chips
# ---------------------------------------------------------------------------

def leg_d(leg: Leg) -> None:
    import dataclasses

    import jax
    import numpy as np

    from synapseml_tpu.data.source import MemorySource
    from synapseml_tpu.models import DeepTextClassifier
    from synapseml_tpu.models.flax_nets.bert import BertClassifier
    from synapseml_tpu.models.text import _resolve_arch
    from synapseml_tpu.models.tokenizer import resolve_tokenizer
    from synapseml_tpu.models.trainer import Trainer, TrainerConfig, fit_source
    from synapseml_tpu.parallel import MeshConfig, create_mesh
    from synapseml_tpu.parallel.partition import per_device_bytes

    sizes, ctx = leg.ctx.sizes, leg.ctx
    devices = ctx.devices
    steps = sizes["mesh"]["steps"]
    tok = resolve_tokenizer(None)

    def run(name, mesh_cfg, mesh_devices, batch, seq, attn):
        """``steps`` optimizer steps of Leg A's model and optimizer through
        fit_source (DataLoader -> Trainer.fit), per-step losses kept."""
        df = text_corpus(batch * steps, seq, 2 * seq, seed=4)
        data = {**tok(list(df.collect_column("text")), max_len=seq),
                "labels": np.asarray(df.collect_column("label"), np.int32)}
        arch = _resolve_arch(sizes["text"]["checkpoint"])(vocab_size=tok.vocab_size)
        arch = dataclasses.replace(arch, attn_impl=attn)
        mesh = create_mesh(mesh_cfg, devices=mesh_devices, allow_fewer=False)
        want = {k: v for k, v in dataclasses.asdict(mesh_cfg).items() if v > 0}
        leg.check(all(mesh.axis_sizes[k] == v for k, v in want.items())
                  and mesh.n_devices == len(mesh_devices),
                  f"{name}: mesh resolved to {mesh.axis_sizes}, asked {want}")
        trainer = Trainer(BertClassifier(arch, num_classes=2), mesh, TrainerConfig(
            learning_rate=sizes["text"]["lr"], total_steps=steps,
            warmup_steps=1, lr_schedule="linear"))
        losses = []
        with leg.timed(name):
            state = fit_source(
                trainer, MemorySource(data), batch_size=batch, total_steps=steps,
                seed=0, shuffle_rows="none",
                callback=lambda i, m: losses.append(float(m["loss"])))
        leg.fact(**{f"{name}_mesh": {k: v for k, v in mesh.axis_sizes.items() if v > 1},
                    f"{name}_losses": [round(x, 4) for x in losses]})
        leg.check(len(losses) == steps and np.all(np.isfinite(losses)),
                  f"{name}: losses {losses}")
        placed = {"params": state.params, "opt_state": state.opt_state}
        for x in jax.tree.leaves(placed):
            leg.check(len({s.device for s in x.addressable_shards})
                      == len(mesh_devices),
                      f"{name}: a leaf lives on {x.devices()}, not on all of "
                      f"{mesh_devices}")
        return np.asarray(losses), per_device_bytes(placed)

    def same_curve(name, got, want):
        gap = float(np.max(np.abs(got - want)))
        leg.fact(**{f"{name}_max_loss_gap": round(gap, 4), "tolerance": 0.03})
        # bf16 compute, another reduction order: 0.03 on losses near ln 2
        leg.check(gap <= 0.03, f"{name} loss curve departs from the one-chip "
                  f"run by {gap}: {got} vs {want}")

    t = sizes["text"]
    one, one_bytes = run("one_chip", MeshConfig(data=1), devices[:1],
                         t["batch"], t["seq"], "einsum")
    dp, dp_bytes = run("data_parallel", MeshConfig(data=-1), devices,
                       t["batch"], t["seq"], "einsum")
    same_curve("data_parallel", dp, one)
    sh, sh_bytes = run("fsdp_tensor", MeshConfig(data=1, fsdp=2, tensor=2),
                       devices, t["batch"], t["seq"], "einsum")
    same_curve("fsdp_tensor", sh, one)
    ratio = sh_bytes / dp_bytes
    leg.fact(state_bytes_per_device=dict(data_parallel=dp_bytes,
                                         fsdp_tensor=sh_bytes),
             fsdp_tensor_share=round(ratio, 3))
    leg.check(0.2 <= ratio <= 0.4, f"fsdp x tensor holds {ratio:.3f} of the "
              "data-parallel state per device, want about a quarter")

    m = sizes["mesh"]
    one_long, _ = run("one_chip_long", MeshConfig(data=1), devices[:1],
                      m["ring_batch"], m["ring_seq"], "einsum")
    ring, _ = run("data_seq_ring", MeshConfig(data=2, seq=2), devices,
                  m["ring_batch"], m["ring_seq"], "ring")
    same_curve("data_seq_ring", ring, one_long)

    # and through the estimator: fit + score on the seq mesh (a missing seq
    # axis is an error in the model, never a swap to a local kernel)
    df = text_corpus(m["ring_batch"] * 2, m["ring_seq"], 2 * m["ring_seq"], seed=5)
    with leg.timed("ring_estimator"):
        stage = DeepTextClassifier(
            checkpoint=t["checkpoint"], num_classes=2,
            batch_size=m["ring_batch"], max_token_len=m["ring_seq"],
            max_steps=2, attn_impl="ring", seed=0,
            mesh_config=MeshConfig(data=2, seq=2)).fit(df)
        scores = np.asarray(list(stage.transform(df).collect_column("scores")))
    leg.check(stage.get("mesh_config") == MeshConfig(data=2, seq=2)
              and stage.get("arch_config").attn_impl == "ring"
              and scores.shape == (len(scores), 2) and np.all(np.isfinite(scores)),
              "the ring-attention estimator did not fit and score on its mesh")


# ---------------------------------------------------------------------------

def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--devices", type=int, default=0, metavar="N",
                    help="require exactly N devices (4 makes Leg D mandatory)")
    ap.add_argument("--legs", default="A,B,C,D",
                    help="comma-separated subset of A,B,C,D (D runs only "
                         "when exactly four devices are visible)")
    ap.add_argument("--out", default=os.path.join(HERE, "chip_smoke_out"),
                    help="output directory (scratch files + summary.json)")
    ap.add_argument("--cpu-tiny", action="store_true",
                    help="explicit debugging mode: toy sizes on the CPU; "
                         "every line says cpu")
    args = ap.parse_args(argv)

    import jax

    if args.cpu_tiny:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.devices or 1)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.cpu_tiny:
        print(f"chip_smoke: JAX found no accelerator (platform "
              f"{dev.platform!r}); nothing was run", file=sys.stderr)
        return 2
    if args.devices and len(devices) != args.devices:
        print(f"chip_smoke: --devices {args.devices} but JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2

    # the native helper library builds into the output directory, not ~/.cache
    os.makedirs(args.out, exist_ok=True)
    os.environ.setdefault("SYNAPSEML_TPU_NATIVE_DIR",
                          os.path.join(args.out, "native"))
    from perfbench.lib.compile_meter import CompileMeter
    from synapseml_tpu.core import batching as cb
    from synapseml_tpu.core.platform import (_visible_tpu_chips,
                                             enable_compile_cache)

    cache_dir = enable_compile_cache()
    import jaxlib

    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    fsize = resource.getrlimit(resource.RLIMIT_FSIZE)[0]
    print("chip_smoke " + json.dumps({
        "device": device, "mode": "cpu-tiny" if args.cpu_tiny else "full",
        "jax": jax.__version__, "jaxlib": jaxlib.__version__, "libtpu": libtpu,
        "compile_cache_dir": cache_dir,
        "compile_cache_entries": len(os.listdir(cache_dir))
        if os.path.isdir(cache_dir) else 0,
        "pci_tpu_chips": _visible_tpu_chips(),
        "tpu_env": {k: v for k, v in os.environ.items() if k.startswith("TPU_")},
        "disk_free_gb": round(shutil.disk_usage(args.out).free / 1e9, 1),
        # the largest file written is Leg C's 0.5 GB embedding shard
        "file_size_limit_bytes": None if fsize == resource.RLIM_INFINITY else fsize,
        **memory_facts(dev)}), flush=True)

    ctx = Ctx(TINY if args.cpu_tiny else FULL, devices, CompileMeter(),
              args.out)
    # an exception in a library thread (the serve loop) must fail the leg,
    # not vanish with the thread
    threading.excepthook = lambda a: ctx.thread_errors.append(
        f"{a.thread.name}: {a.exc_type.__name__}: {a.exc_value}")

    wanted = [x.strip().upper() for x in args.legs.split(",") if x.strip()]
    legs = [(n, f) for n, f in (("A", leg_a), ("B", leg_b), ("C", leg_c),
                                ("D", leg_d)) if n in wanted]
    if len(devices) != 4:       # Leg D's meshes are laid out for four
        legs = [(n, f) for n, f in legs if n != "D"]
    summary = {"device": device, "mode": "cpu-tiny" if args.cpu_tiny else "full",
               "legs": {}}
    for name, fn in legs:
        leg = Leg(name, ctx)
        shutil.rmtree(leg.scratch, ignore_errors=True)
        os.makedirs(leg.scratch)
        before, t0 = ctx.meter.snapshot(), time.perf_counter()
        try:
            fn(leg)
            ok, error = True, None
        except Exception as e:  # noqa: BLE001 — recorded; exit code is non-zero
            ok, error = False, f"{type(e).__name__}: {e}"
            traceback.print_exc()
        finally:
            shutil.rmtree(leg.scratch, ignore_errors=True)
        record = {"ok": ok, "error": error,
                  "wall_s": round(time.perf_counter() - t0, 1),
                  **{k: round(v, 2) for k, v in ctx.meter.since(before).items()},
                  **memory_facts(dev), **leg.facts}
        summary["legs"][name] = record
        print(f"LEG {name} " + json.dumps(
            {k: record[k] for k in ("ok", "error", "wall_s", "trace_s",
                                    "lower_s", "compile_s", "programs",
                                    "cache_hits", "cache_misses",
                                    "peak_bytes_in_use")}), flush=True)
        # drop executables whose closures hold a leg's weights
        cb.reset_compiled_cache()
        gc.collect()

    ok = bool(legs) and all(r["ok"] for r in summary["legs"].values())
    summary["ok"] = ok
    with open(os.path.join(args.out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1, default=str)
    print(json.dumps({"ok": ok, "device": device,
                      **({"mode": "cpu-tiny"} if args.cpu_tiny else {})}),
          flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
