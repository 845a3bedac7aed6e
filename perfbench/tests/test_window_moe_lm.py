"""The window decoder's configuration, operation count, reference and
comparison: the configuration file keeps every published key or lists it as
reduced, the cut holds the parameters counted by hand, the count agrees with
a hand count, the reference gives the same numbers in blocks and whole, and
`check_latent_lm`'s numbers fail on the fp8 control and on each planted
fault, at a size the CPU holds."""

import json
import os

import pytest

from perfbench.drivers import latent_lm_train_window as driver
from perfbench.drivers import train_window as tw
from perfbench.flops import window_moe_lm as flops
from perfbench.lib import check, check_latent_lm, datagen_lm
from perfbench.lib.manifest import ROOT, Cell, load_manifest
from perfbench.reference import hybrid_conv_moe_lm as hybrid_ref
from perfbench.reference import window_moe_lm as ref

CELL = "laguna_xs2_ep8.lm_8k_window"
FULL, SLIDING = "full_attention", "sliding_attention"
# config.json of poolside/Laguna-XS.2 as the model-configs catalog gives it
# (the per-layer lists by their period, checked below)
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
    "intermediate_size": 8192, "num_hidden_layers": 40, "num_attention_heads": 48,
    "num_key_value_heads": 8, "head_dim": 128, "max_position_embeddings": 262144,
    "attention_bias": False, "rms_norm_eps": 1e-06, "num_experts": 256,
    "num_experts_per_tok": 8, "moe_intermediate_size": 512,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False, "gating": True,
    "sliding_window": 512, "moe_apply_router_weight_on_input": False,
    "partial_rotary_factor": 0.5, "moe_routed_scaling_factor": 2.5,
    "rope_parameters": {
        FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
               "original_max_position_embeddings": 4096, "beta_slow": 1, "beta_fast": 64,
               "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    "layer_types": [FULL if i % 4 == 0 else SLIDING for i in range(40)],
    "mlp_layer_types": ["dense"] + ["sparse"] * 39,
    "num_attention_heads_per_layer": [48 if i % 4 == 0 else 64 for i in range(40)]}


@pytest.fixture(scope="module")
def entry():
    return next(c for c in load_manifest()["configs"] if c["name"] == "laguna_xs2_ep8")


@pytest.fixture(scope="module")
def config(entry):
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


def rehearsal_cell():
    cell = Cell(load_manifest(), CELL, rehearse=True)
    driver.merge_rehearsal(cell)
    return cell


def test_every_published_key_is_kept_or_listed_as_reduced(entry, config):
    assert config["_source"] == entry["source"]
    reduced = set(entry["reduced"])
    assert reduced == {"num_hidden_layers", "layer_types", "num_attention_heads_per_layer",
                       "mlp_layer_types", "num_experts", "vocab_size"}
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert config[key] != value, key
        else:
            assert config[key] == value, key
    assert sorted(config["reduced_notes"]) == sorted(entry["reduced"])
    # published layers 0..4: the per-layer lists are the source's first five entries
    kept = config["published_layers_kept"]
    assert kept == [0, 1, 2, 3, 4] and config["num_hidden_layers"] == 5
    for key in ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer"):
        assert config[key] == [PUBLISHED[key][i] for i in kept], key
    assert (config["published_num_hidden_layers"], config["published_num_experts"],
            config["published_vocab_size"]) == (40, 256, 100352)
    # the floors: a whole period after the dense lead and four layers; 8 experts; 1/8 vocabulary
    assert config["layer_types"][1:] == [SLIDING, SLIDING, SLIDING, FULL]
    assert config["num_experts"] >= 8 and config["vocab_size"] * 8 == 100352
    share, of = (int(x) for x in config["expert_share"].split(" of "))
    assert of * config["num_experts"] == config["published_num_experts"] and 0 <= share < of
    assert "eight chips share each layer" in config["deployment"]
    assert all(isinstance(v, str) and v for v in config["assumed"].values())
    assert {"router", "head_gate", "qk_norm", "shared_expert", "hidden_act", "window_convention",
            "yarn_on_rotary_dims", "moe_aux_weight", "rope_table_len",
            "flash_block"} <= set(config["assumed"])
    assert config["moe_aux_weight"] == 0.0
    traffic = Cell(load_manifest(), CELL).traffic
    assert traffic["text"]["seq_len"] == config["rope_table_len"] == 8192
    assert traffic["batch"] == 1


def test_parameter_count_of_the_cut(config):
    # 691.6M parameters: 8.30 GB at 12 bytes a parameter, 11.07 with the gradient
    leaves = ref.leaf_sizes(ref.sizes(config))
    assert sum(leaves.values()) == pytest.approx(691.6e6, rel=1e-3)

    def attn(i):
        return sum(leaves[f"layer{i}.{k}"] for k in ("wq", "wk", "wv", "wo", "head_gate"))

    assert attn(1) == pytest.approx(37.9e6, abs=0.05e6)          # 37.88M: 64 heads, gates included
    assert attn(0) == pytest.approx(29.5e6, abs=0.05e6)          # 29.46M: 48 heads
    assert attn(4) == attn(0) and leaves["layer1.head_gate"] == 2048 * 64
    assert sum(leaves[f"layer0.{k}"] for k in ("w1", "w3", "w2")) == 3 * 2048 * 8192    # 50.3M
    assert sum(leaves[f"layer1.{k}"] for k in ("wg", "wu", "wd")) \
        == pytest.approx(100.7e6, abs=0.05e6)                                            # 32 experts
    assert sum(leaves[f"layer1.{k}"] for k in ("sg", "su", "sd")) == 3 * 2048 * 512      # 3.15M
    assert leaves["layer1.router"] == 2048 * 256 and "layer0.router" not in leaves
    assert leaves["embed"] + leaves["head"] == pytest.approx(51.4e6, abs=0.05e6)
    # 12 bytes a parameter resident (weights, two moments), 16 with the gradient
    assert 12 * sum(leaves.values()) / 1e9 == pytest.approx(8.30, abs=0.01)


def test_operation_count_against_a_hand_count(config):
    # 8192 tokens, forward, TFLOP a row, by part
    t = 8192
    parts = {k: v / 1e12 for k, v in flops.forward_matmul_flops(config, t).items()}
    causal, band = t * (t + 1) // 2, 512 * 513 // 2 + (t - 512) * 512
    assert flops.band_pairs(t, 512) == band == 4_063_488
    assert parts["full_proj"] == pytest.approx(
        2 * t * 2 * (2 * 2048 * 48 * 128 + 2 * 2048 * 8 * 128 + 2048 * 48) / 1e12)     # 0.97
    assert parts["full_attn"] == pytest.approx(2 * causal * 2 * 48 * 256 / 1e12)       # 1.65
    assert parts["sliding_proj"] == pytest.approx(
        3 * t * 2 * (2 * 2048 * 64 * 128 + 2 * 2048 * 8 * 128 + 2048 * 64) / 1e12)     # 1.86
    assert parts["sliding_attn"] == pytest.approx(3 * band * 2 * 64 * 256 / 1e12)      # 0.40
    assert parts["dense_mlp"] == pytest.approx(t * 6 * 2048 * 8192 / 1e12)             # 0.83
    assert parts["shared_expert"] == pytest.approx(4 * t * 6 * 2048 * 512 / 1e12)      # 0.21
    assert parts["experts"] == pytest.approx(4 * t * 8 * 32 / 256 * 6 * 2048 * 512 / 1e12)
    assert parts["router"] == pytest.approx(4 * t * 2 * 2048 * 256 / 1e12)
    assert parts["head"] == pytest.approx(t * 2 * 2048 * 12544 / 1e12)                 # 0.42
    assert sum(parts.values()) == pytest.approx(6.57, abs=0.01)
    # the two kinds of attention layer hold 74% of the needed work
    attention = sum(parts[k] for k in ("full_proj", "full_attn", "sliding_proj", "sliding_attn"))
    assert attention / sum(parts.values()) == pytest.approx(0.74, abs=0.005)
    # the band against the masked causal square of the three sliding layers: 0.40 vs 3.30
    assert 3 * causal * 2 * 64 * 256 / 1e12 == pytest.approx(3.30, abs=0.005)
    traffic = {"text": {"seq_len": t}}
    assert flops.train_flops_per_sample(config, traffic) == pytest.approx(19.7e12, rel=2e-3)
    # one windowed launch on one row: the band at 64 heads of 128
    assert flops.window_forward_flops(config, traffic) == 2 * 64 * 256 * band
    assert flops.window_forward_flops(config, traffic) == pytest.approx(parts["sliding_attn"]
                                                                        * 1e12 / 3)


def test_the_reference_gives_the_same_in_blocks_and_whole(monkeypatch):
    cell = rehearsal_cell()
    sizes, opt = ref.sizes(cell.config), cell.traffic["optimizer"]
    data = datagen_lm.make_rows(cell.config, cell.traffic, 7)
    batch = {k: v[:2] for k, v in data.items()}
    whole = ref.run_steps(sizes, opt, 7, [batch], rows_per_block=2)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
    monkeypatch.setattr(ref, "TOKEN_BLOCK", 4)
    monkeypatch.setattr(hybrid_ref, "TOKEN_BLOCK", 4)       # the dense MLP's, the shared expert's
    monkeypatch.setattr(ref, "LOGIT_BLOCK", 16)
    blocked = ref.run_steps(sizes, opt, 7, [batch], rows_per_block=1)
    assert blocked["loss"][0] == pytest.approx(whole["loss"][0], rel=1e-6)
    assert blocked["grad_norm"][0] == pytest.approx(whole["grad_norm"][0], rel=1e-5)
    for name, norm in whole["moment_norm"].items():
        assert blocked["moment_norm"][name] == pytest.approx(norm, rel=1e-4), name
    with pytest.raises(TypeError, match="planted fault"):
        ref.run_steps(sizes, opt, 7, [batch], reversed_taps=True)


def _first_dispatch(cell, seed):
    """(program's numbers, rows, fed batches) of one seed at rehearsal size,
    through the driver's own functions."""
    adapter = cell.module("programs", cell.config["program"])
    trainer = driver.build_trainer(cell, adapter)
    probe = tw.DispatchProbe(trainer)
    data = datagen_lm.make_rows(cell.config, cell.traffic, seed)
    state = driver.start_state(cell, adapter, trainer, seed)
    constants = driver.host_constants(state)
    loader = tw.make_loader(trainer, data, cell.traffic, seed)
    fed = tw.FedIterator(iter(loader), 8, keep=8)
    try:
        state = trainer.fit(state, fed.phase(batches=8), max_steps=8, scan_chunk=8)
    finally:
        loader.close()
    program = driver.first_dispatch_numbers(cell, adapter, probe, state, seed, constants)
    assert program["constants_changed"] == 0 and constants == []
    return program, data, fed.kept


def test_control_and_the_planted_faults_fail_the_comparison():
    """By the harness's own comparison (`check_latent_lm.compare_first_steps`)
    the program is `correct` and the fp8 control and each planted fault, put in
    the program's place on the same batches, are not; each lies 3 x or more
    above the program in one of the cell's numbers, not in each."""
    cell = rehearsal_cell()
    sizes, opt = ref.sizes(cell.config), cell.traffic["optimizer"]
    names = ("loss_gap", "grad_norm_gap", "moment_gap", "change_gap")
    others = {"fp8": {"precision": "fp8"}, **{fault: {fault: True} for fault in ref.FAULTS}}
    lower, upper = [], {kind: [] for kind in others}
    for seed in (11, 2 ** 31 + 5):
        program, data, kept = _first_dispatch(cell, seed)
        verdict = check_latent_lm.compare_first_steps(cell, data, kept, program, seed)
        assert verdict["correct"] and set(names) <= set(verdict["checks"]), verdict["checks"]
        assert verdict["checks"]["rows_unmatched"]["value"] == 0
        lower.append(verdict["checks"])
        batches, _ = check.reference_batches(cell.config, data, kept)
        for kind, kw in others.items():
            other = ref.run_steps(sizes, opt, seed, batches, rows_per_block=1, **kw)
            other.update(steps=program["steps"], constants_changed=0)
            verdict = check_latent_lm.compare_first_steps(cell, data, kept, other, seed)
            assert not verdict["correct"], (kind, verdict["checks"])
            upper[kind].append(verdict["checks"])
    for kind, readings in upper.items():
        apart = {n: min(u[n]["value"] for u in readings) / max(g[n]["value"] for g in lower)
                 for n in names}
        assert max(apart.values()) >= 3, (kind, apart)


def test_the_files_sizes_and_loss_weight_reach_the_program():
    cell = rehearsal_cell()
    adapter = cell.module("programs", cell.config["program"])
    trainer = driver.build_trainer(cell, adapter)
    assert trainer.cfg.moe_aux_weight == 0.0
    assert trainer.cfg.learning_rate == cell.traffic["optimizer"]["learning_rate"]
    cfg = trainer.module.cfg
    c = cell.config
    full, sliding = cfg.attn_kind(FULL), cfg.attn_kind(SLIDING)
    assert (cfg.n_heads, sliding.heads, cfg.kv_heads, cfg.head_dim) == (2, 4, 2, 16)
    assert (full.window, sliding.window) == (0, c["sliding_window"])
    assert (full.rotary_fraction, sliding.rotary_fraction) == (0.5, 1.0)
    assert (full.rope_theta, sliding.rope_theta) == (500000.0, 10000.0)
    assert full.yarn.factor == 64 and full.yarn.attention_factor == pytest.approx(1.41588830834)
    assert sliding.yarn is None and cfg.attn_gate and cfg.layer_types == tuple(c["layer_types"])
    assert (cfg.moe_router, cfg.moe_gate_scale, cfg.moe_top_k) == ("softmax", 2.5, 3)
    assert cfg.moe_shared_mlp_dim == c["shared_expert_intermediate_size"]
    assert cfg.moe_dense_layers == 1 and not cfg.tie_embeddings
    assert cfg.attn_impl == "flash" and cfg.remat and cfg.max_len == c["rope_table_len"]
    state = driver.start_state(cell, adapter, trainer, 5)
    assert not __import__("jax").tree.leaves(state.constants)
    # the cell's own scopes are what its readers and its line's breakdown look for
    assert {"attn.flash", "attn.window", "mlp.dense", "moe.shared"} <= set(c["trace_scopes"])
    assert "attn.latent" not in c["trace_scopes"]
    assert c["trace_kernel"] == {"scope": "attn.window", "marks": ["pallas_call"]}
    assert cell.module("lib", c["check"]) is check_latent_lm
    for key, value in (("gating", False), ("attention_bias", True),
                       ("moe_apply_router_weight_on_input", True)):
        with pytest.raises(ValueError, match="window decoder"):
            adapter.build(dict(c, **{key: value}))
