"""The sparse-attention MoE decoder's configuration, operation count and
comparison: the configuration file keeps every published width (the guard
`test_manifest.py`'s width test meant to be, for this configuration), the
count agrees with a hand count at one shape, and `check_lm`'s numbers fail on
the fp8 control and on each planted fault (half of the batch left out among
them) at a size the CPU holds; the file's loss weights reach the program."""

import json
import os

import numpy as np
import pytest

from perfbench.drivers import lm_train_window as driver
from perfbench.drivers import train_window as tw
from perfbench.flops import sparse_moe_lm as flops
from perfbench.lib import check, datagen_lm
from perfbench.lib.manifest import ROOT, Cell, load_manifest
from perfbench.reference import sparse_moe_lm as ref

CELL = "keye_vl2_30b_a3b_ep8.lm_8k"
# config.json of Kwai-Keye/Keye-VL-2.0-30B-A3B as the model-configs catalog gives it
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 262144,
    "max_window_layers": 48, "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
                  "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}


@pytest.fixture(scope="module")
def entry():
    manifest = load_manifest()
    return next(c for c in manifest["configs"] if c["name"] == "keye_vl2_30b_a3b_ep8")


@pytest.fixture(scope="module")
def config(entry):
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


def test_every_published_key_is_kept_or_listed_as_reduced(entry, config):
    assert config["_source"] == entry["source"]
    reduced = set(entry["reduced"])
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert config[key] != value and config[f"published_{key}"] == value, key
        else:
            assert config[key] == value, key
    assert sorted(config["reduced_notes"]) == sorted(entry["reduced"])
    # no width is cut, and the cut keeps to the guide's floors
    assert not reduced & {"hidden_size", "head_dim", "moe_intermediate_size", "sa_config",
                          "num_experts_per_tok", "num_attention_heads", "num_key_value_heads"}
    assert config["num_hidden_layers"] >= 4 and config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= config["published_vocab_size"]
    share, of = (int(x) for x in config["expert_share"].split(" of "))
    assert of * config["num_experts"] == config["published_num_experts"] and 0 <= share < of
    assert config["deployment"] and all(isinstance(v, str) and v for v in config["assumed"].values())


def test_operation_count_against_a_hand_count(config):
    # 8192 tokens, a layer, forward, MFLOP a token (ISSUE 29's table)
    parts = flops.forward_matmul_flops(config, 8192)
    per_token_layer = {k: v / 8192 / config["num_hidden_layers"] / 1e6 for k, v in parts.items()}
    assert per_token_layer["qkvo"] == pytest.approx(2 * 2048 * 128 * (2 * 32 + 2 * 4) / 1e6)
    assert per_token_layer["indexer_proj"] == pytest.approx(2 * 2048 * (1024 + 64 + 16) / 1e6)
    assert per_token_layer["indexer_scores"] == pytest.approx(2 * 16 * 64 * 4096.5 / 1e6)
    # 2048 queries see t + 1 keys, 6144 see 2048: 1792.125 a query on average
    selected = (2048 * 2049 / 2 + 6144 * 2048) / 8192
    assert selected == pytest.approx(1792.125)
    assert per_token_layer["selected_scores"] + per_token_layer["selected_values"] \
        == pytest.approx(2 * 2 * 32 * 128 * selected / 1e6)
    assert per_token_layer["experts"] == pytest.approx(3 * 2 * 2048 * 768 / 1e6)   # 1 held pair a token
    assert per_token_layer["router"] == pytest.approx(2 * 2048 * 128 / 1e6)
    assert parts["head"] == 8192 * 2 * 2048 * 18992
    layer = sum(v for k, v in per_token_layer.items() if k != "head")
    assert layer == pytest.approx(89.98, abs=0.01)
    total = flops.train_flops_per_sample(config, {"text": {"seq_len": 8192}})
    assert total == pytest.approx(3 * sum(parts.values()) - parts["indexer_proj"])
    assert 12.7e12 < total < 12.9e12
    # a short row: every causal pair is selected
    short = flops.forward_matmul_flops(config, 100)
    assert short["selected_scores"] == config["num_hidden_layers"] * 5050 * 2 * 32 * 128


def test_the_reference_blocks_queries_and_positions_without_changing_the_result(monkeypatch):
    cell = Cell(load_manifest(), CELL, rehearse=True)
    driver.merge_rehearsal(cell)
    sizes, opt = ref.sizes(cell.config), cell.traffic["optimizer"]
    data = datagen_lm.make_rows(cell.config, cell.traffic, 7)
    batch = {k: v[:2] for k, v in data.items()}
    whole = ref.run_steps(sizes, opt, 7, [batch], rows_per_block=2)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
    monkeypatch.setattr(ref, "LOGIT_BLOCK", 16)
    blocked = ref.run_steps(sizes, opt, 7, [batch], rows_per_block=1)
    assert blocked["loss"][0] == pytest.approx(whole["loss"][0], rel=1e-6)
    assert blocked["grad_norm"][0] == pytest.approx(whole["grad_norm"][0], rel=1e-5)


def _first_dispatch(cell, seed):
    """(program's numbers, reference batches) of one seed at rehearsal size,
    through the driver's own functions."""
    adapter = cell.module("programs", cell.config["program"])
    trainer = driver.build_trainer(cell, adapter)
    probe = tw.DispatchProbe(trainer)
    data = datagen_lm.make_rows(cell.config, cell.traffic, seed)
    state = driver.start_state(cell, adapter, trainer, seed)
    loader = tw.make_loader(trainer, data, cell.traffic, seed)
    fed = tw.FedIterator(iter(loader), 8, keep=8)
    try:
        state = trainer.fit(state, fed.phase(batches=8), max_steps=8, scan_chunk=8)
    finally:
        loader.close()
    program = driver.first_dispatch_numbers(cell, adapter, probe, state, seed)
    batches, bad = check.reference_batches(cell.config, data, fed.kept)
    assert bad == 0
    return program, batches


def test_the_files_loss_weights_reach_the_program():
    cell = Cell(load_manifest(), CELL, rehearse=True)
    driver.merge_rehearsal(cell)
    adapter = cell.module("programs", cell.config["program"])
    assert cell.config["moe_aux_weight"] == 0.01 and cell.config["indexer_loss_weight"] == 1.0
    cell.config = dict(cell.config, moe_aux_weight=0.03, indexer_loss_weight=0.5)
    cfg = driver.build_trainer(cell, adapter).cfg
    assert (cfg.moe_aux_weight, cfg.indexer_loss_weight) == (0.03, 0.5)
    assert cfg.learning_rate == cell.traffic["optimizer"]["learning_rate"]
    sizes = ref.sizes(cell.config)
    assert (sizes["aux_weight"], sizes["indexer_weight"]) == (0.03, 0.5)


def test_control_and_every_planted_fault_fail_the_comparison():
    cell = Cell(load_manifest(), CELL, rehearse=True)
    driver.merge_rehearsal(cell)
    sizes, opt = ref.sizes(cell.config), cell.traffic["optimizer"]
    leaves = ref.leaf_sizes(sizes)
    names = ("loss_gap", "grad_norm_gap", "moment_gap", "change_gap")
    lower, upper = [], {"fp8": [], "window_fault": [], "raw_gates": [], "half_batch": []}
    for seed in (11, 2 ** 31 + 5):
        program, batches = _first_dispatch(cell, seed)
        run = lambda **kw: ref.run_steps(sizes, opt, seed, batches,  # noqa: E731
                                         rows_per_block=1, **kw)
        reference = run()
        lower.append(check.gaps(program, reference, leaves))
        upper["fp8"].append(check.gaps(run(precision="fp8"), reference, leaves))
        upper["window_fault"].append(check.gaps(run(window_fault=True), reference, leaves))
        upper["raw_gates"].append(check.gaps(run(raw_gates=True), reference, leaves))
        upper["half_batch"].append(check.gaps(run(half_batch=True), reference, leaves))
    for kind, readings in upper.items():
        # each has to fail one of the cell's numbers, not each; and by the
        # rehearsal's own limits it is not correct. At a width of 64 bfloat16's
        # own rounding lies nearer to fp8's than at the cell's (PERF.md, section 2)
        apart = {n: min(u[n] for u in readings) / max(g[n] for g in lower) for n in names}
        assert max(apart.values()) >= (2 if kind == "fp8" else 3), (kind, apart)
        for u in readings:
            assert any(u[n] > cell.limits[n] for n in names), (kind, u)
    for g in lower:
        assert all(g[n] <= cell.limits[n] for n in names), g


def test_rows_are_packed_and_labelled_with_the_next_id():
    cell = Cell(load_manifest(), CELL, rehearse=True)
    driver.merge_rehearsal(cell)
    data = datagen_lm.make_rows(cell.config, cell.traffic, 2 ** 31 + 99)
    ids, labels = data["input_ids"], data["labels"]
    assert ids.shape == labels.shape == (64, 32) and ids.dtype == labels.dtype == np.int32
    assert ids.min() >= 0 and ids.max() < cell.config["vocab_size"]
    assert np.array_equal(labels[:, :-1], ids[:, 1:]) and (labels[:, -1] == -100).all()
    again = datagen_lm.make_rows(cell.config, cell.traffic, 2 ** 31 + 99)
    assert np.array_equal(again["input_ids"], ids)
