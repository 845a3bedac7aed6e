"""The latent-attention MoE decoder's configuration, operation count,
reference and comparison: the configuration file keeps every published key or
lists it as reduced, the count agrees with a hand count, the reference gives
the same numbers in blocks and whole, and `check_latent_lm`'s numbers fail on
the fp8 control and on each planted fault, at a size the CPU holds."""

import json
import os

import jax
import numpy as np
import pytest

from perfbench.drivers import latent_lm_train_window as driver
from perfbench.drivers import train_window as tw
from perfbench.flops import latent_moe_lm as flops
from perfbench.lib import check, check_latent_lm, datagen_lm
from perfbench.lib.manifest import ROOT, Cell, load_manifest
from perfbench.reference import hybrid_conv_moe_lm as hybrid_ref
from perfbench.reference import latent_moe_lm as ref

CELL = "moonlight_16b_a3b_ep8.lm_8k_latent"
# config.json of moonshotai/Moonlight-16B-A3B as the model-configs catalog gives it
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 11264, "kv_lora_rank": 512,
    "max_position_embeddings": 8192, "model_type": "deepseek_v3", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64, "n_shared_experts": 2,
    "norm_topk_prob": True, "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16, "num_nextn_predict_layers": 0,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 50000, "routed_scaling_factor": 2.446, "scoring_func": "sigmoid",
    "seq_aux": True, "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 163840}


@pytest.fixture(scope="module")
def entry():
    return next(c for c in load_manifest()["configs"] if c["name"] == "moonlight_16b_a3b_ep8")


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
    assert reduced == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert config[key] != value, key
        else:
            assert config[key] == value, key
    assert sorted(config["reduced_notes"]) == sorted(entry["reduced"])
    assert config["published_layers_kept"] == [0, 1, 2, 3, 4] and config["num_hidden_layers"] == 5
    assert (config["published_num_hidden_layers"], config["published_n_routed_experts"],
            config["published_vocab_size"]) == (27, 64, 163840)
    # the floors: the dense lead, then four expert layers; 8 routed experts; 1/8 vocabulary
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["n_routed_experts"] >= 8 and config["vocab_size"] * 8 >= 163840
    share, of = (int(x) for x in config["expert_share"].split(" of "))
    assert of * config["n_routed_experts"] == config["published_n_routed_experts"]
    assert 0 <= share < of
    assert "eight chips share each layer" in config["deployment"]
    assert all(isinstance(v, str) and v for v in config["assumed"].values())
    assert config["moe_aux_weight"] == 0.0 and config["gate_normalisation_eps"] == 1e-20
    assert config["rope_table_len"] == config["max_position_embeddings"]
    traffic = Cell(load_manifest(), CELL).traffic
    assert traffic["text"]["seq_len"] == config["max_position_embeddings"]


def test_operation_count_against_a_hand_count(config):
    # 8192 tokens, forward, MFLOP a token (ISSUE 35's arithmetic)
    t = 8192
    per_token = {k: v / t / 1e6 for k, v in flops.forward_matmul_flops(config, t).items()}
    # W_q 2048 x 3072, W_a 2048 x 576, W_b 512 x 4096, W_o 2048 x 2048: 13.76M, five layers
    assert per_token["latent_proj"] == pytest.approx(
        5 * 2 * (2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048) / 1e6)           # 137.6
    # a causal mean of 4096.5 keys a query: scores at 192 dims, values at 128
    assert per_token["attn_scores"] == pytest.approx(5 * 2 * 16 * 192 * 4096.5 / 1e6)  # 125.8
    assert per_token["attn_values"] == pytest.approx(5 * 2 * 16 * 128 * 4096.5 / 1e6)  # 83.9
    assert per_token["dense_mlp"] == pytest.approx(3 * 2 * 2048 * 11264 / 1e6)          # 138.4
    assert per_token["shared_expert"] == pytest.approx(4 * 3 * 2 * 2048 * 2816 / 1e6)   # 138.4
    # 0.75 held pairs a token and layer (6 choices x 8 of 64), four expert layers
    assert per_token["experts"] == pytest.approx(4 * 0.75 * 3 * 2 * 2048 * 1408 / 1e6)  # 51.9
    assert per_token["router"] == pytest.approx(4 * 2 * 2048 * 64 / 1e6)
    assert per_token["head"] == pytest.approx(2 * 2048 * 20480 / 1e6)                   # 83.9
    assert sum(per_token.values()) == pytest.approx(761.03, abs=0.01)
    traffic = {"text": {"seq_len": t}}
    total = flops.train_flops_per_sample(config, traffic)
    assert 2 * total == pytest.approx(37.4e12, rel=2e-3)         # a step of two rows
    # one launch of the flash forward on one row: the true widths, 192 + 128
    assert flops.flash_forward_flops(config, traffic) == 2 * 16 * 320 * (t * (t + 1) // 2)
    assert flops.flash_forward_flops(config, traffic) == pytest.approx(
        t * 1e6 * (per_token["attn_scores"] + per_token["attn_values"]) / 5)
    # the lanes the kernel pads to (256 + 128) bound the roofline share it can read
    assert (192 + 128) / (256 + 128) == pytest.approx(0.833, abs=1e-3)


def test_parameter_count_of_the_cut(config):
    # ISSUE 35, part 4: 568.5M parameters
    leaves = ref.leaf_sizes(ref.sizes(config))
    assert sum(leaves.values()) == pytest.approx(568.5e6, rel=1e-3)
    assert sum(leaves[f"layer0.{k}"] for k in ("wq", "wa", "wb", "wo")) \
        == 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048                # 13.76M
    assert leaves["layer0.w1"] * 3 == 69_206_016 and leaves["layer1.wg"] * 3 == 69_206_016
    assert sum(leaves[f"layer1.{k}"] for k in ("sg", "su", "sd")) == 17_301_504
    assert leaves["embed"] == leaves["head"] == 20480 * 2048
    assert "layer0.router" not in leaves and leaves["layer4.router"] == 2048 * 64


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
    assert program["constants_changed"] == 0
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
        assert verdict["checks"]["constants_changed"]["value"] == 0
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


def test_a_changed_selection_bias_fails_the_comparison():
    cell = rehearsal_cell()
    program, data, kept = _first_dispatch(cell, 3)
    program["constants_changed"] = 1
    verdict = check_latent_lm.compare_first_steps(cell, data, kept, program, 3)
    assert not verdict["correct"] and verdict["checks"]["constants_changed"]["value"] == 1


def test_the_files_sizes_loss_weight_and_bias_reach_the_program():
    cell = rehearsal_cell()
    adapter = cell.module("programs", cell.config["program"])
    trainer = driver.build_trainer(cell, adapter)
    assert trainer.cfg.moe_aux_weight == 0.0
    assert trainer.cfg.learning_rate == cell.traffic["optimizer"]["learning_rate"]
    cfg = trainer.module.cfg
    c = cell.config
    assert (cfg.head_dim, cfg.rope_dim, cfg.value_dim, cfg.kv_latent_rank) == (
        c["qk_nope_head_dim"] + c["qk_rope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"],
        c["kv_lora_rank"])
    assert len({c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"],
                c["kv_lora_rank"]}) == 4                  # no two equal in the rehearsal
    assert (cfg.moe_router, cfg.moe_gate_scale, cfg.moe_gate_eps) == ("sigmoid", 2.446, 1e-20)
    assert cfg.moe_shared_mlp_dim == 2 * c["moe_intermediate_size"] and cfg.moe_dense_layers == 1
    assert not cfg.tie_embeddings and cfg.attn_impl == "flash" and cfg.remat
    state = driver.start_state(cell, adapter, trainer, 5)
    want = ref.select_bias(ref.sizes(c), ref.fold_seed(5))
    got = state.constants["decoder"]
    assert sorted(got) == ["layer_1", "layer_2"]
    for i in (1, 2):
        # to rounding: the driver draws it inside one jitted call with the weights
        np.testing.assert_allclose(np.asarray(got[f"layer_{i}"]["mlp"]["select_bias"]),
                                   np.asarray(want[f"layer{i}.beta"]), rtol=1e-5, atol=1e-9)
    assert float(np.std(np.asarray(jax.tree.leaves(got)[0]))) == pytest.approx(0.02, rel=0.5)
    # the cell's own scopes are what its readers and its line's breakdown look for
    assert {"attn.latent", "attn.flash", "mlp.dense", "moe.shared"} <= set(c["trace_scopes"])
    assert c["trace_kernel"]["scope"] in c["trace_scopes"]
    assert cell.module("lib", c["check"]) is check_latent_lm
