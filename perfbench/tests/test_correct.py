"""`correct` has been shown to fail: the control (the reference computed with
fp8 matrix products, put in the program's place) reads at least three times the
program's own gap at a size the CPU holds, and a run with the timed path broken
underneath comes out as not correct, once for each fault a training cell on one
chip can have."""

import json
import os

import numpy as np
import pytest

from perfbench.lib import check, datagen
from perfbench.lib.manifest import Cell, load_manifest
from perfbench.reference import encoder as ref

SEEDS = (11, 2 ** 31 + 5, 777)


def _first_dispatch(cell, seed):
    """(program's numbers, reference batches) of one seed at rehearsal size,
    through the driver's own functions."""
    from perfbench.drivers import train_window as tw

    adapter = cell.module("programs", cell.config["program"])
    trainer = tw.build_trainer(cell, adapter)
    probe = tw.DispatchProbe(trainer)
    data = datagen.make_rows(cell.config, cell.traffic, seed)
    state = trainer.resume_state(tw._device_weights(cell, adapter, trainer, seed))
    loader = tw.make_loader(trainer, data, cell.traffic, seed)
    fed = tw.FedIterator(iter(loader), 8, keep=8)
    try:
        state = trainer.fit(state, fed.phase(batches=8), max_steps=8, scan_chunk=8)
    finally:
        loader.close()
    program = tw.first_dispatch_numbers(cell, adapter, probe, state, seed)
    batches, bad = check.reference_batches(cell.config, data, fed.kept)
    assert bad == 0
    return program, batches


@pytest.mark.parametrize("workload", ["bert_base.finetune", "vit_b16.finetune"])
def test_control_reads_three_times_the_program(workload):
    cell = Cell(load_manifest(), workload, rehearse=True)
    sizes, opt = ref.sizes(cell.config), cell.traffic["optimizer"]
    leaves = ref.leaf_sizes(sizes)
    lower, upper = [], []
    for seed in SEEDS:
        program, batches = _first_dispatch(cell, seed)
        run = lambda **kw: ref.run_steps(sizes, opt, seed, batches,  # noqa: E731
                                         rows_per_block=4, **kw)
        reference = run(precision="float32")
        lower.append(check.gaps(program, reference, leaves))
        upper.append(check.gaps(run(precision="fp8"), reference, leaves))
        half = check.gaps(run(precision="float32", half_batch=True), reference, leaves)
        assert half["grad_norm_gap"] > 10 * lower[-1]["grad_norm_gap"]
    # the control has to fail one of the cell's numbers, not each
    apart = {name: min(u[name] for u in upper) / max(g[name] for g in lower)
             for name in ("loss_gap", "grad_norm_gap", "moment_gap", "change_gap")}
    assert max(apart.values()) >= 3, apart


def _run_main(monkeypatch, capsys, workload):
    from perfbench import run as bench

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = bench.main(["--workload", workload, "--seed", "4242", "--seconds", "1",
                     "--trace", "0", "--rehearse-cpu"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", ["bert_base.finetune", "vit_b16.finetune"])
def test_sound_run_is_correct(monkeypatch, capsys, workload):
    line = _run_main(monkeypatch, capsys, workload)
    assert line["correct"] is True and line["failed"] == 0


def test_state_left_unchanged_is_not_correct(monkeypatch, capsys):
    from synapseml_tpu.models import trainer as tr

    real = tr.Trainer.train_steps_scan

    def frozen(self, state, stacked):
        copy = tr.TrainState(params=jax_copy(state.params),
                             opt_state=jax_copy(state.opt_state),
                             step=jax_copy(state.step), batch_stats=state.batch_stats)
        _, metrics = real(self, state, stacked)
        return copy, metrics

    def jax_copy(tree):
        import jax
        import jax.numpy as jnp

        return jax.tree.map(jnp.copy, tree)

    monkeypatch.setattr(tr.Trainer, "train_steps_scan", frozen)
    line = _run_main(monkeypatch, capsys, "bert_base.finetune")
    assert line["correct"] is False
    assert line["checks"]["change_gap"]["value"] == pytest.approx(1.0, abs=1e-3)
    assert line["checks"]["steps_missing"]["value"] > 0


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch, capsys):
    from synapseml_tpu.models import trainer as tr

    real = tr.Trainer.train_steps_scan

    def half(self, state, stacked):
        rows = next(iter(stacked.values())).shape[1] // 2
        return real(self, state, {k: np.asarray(v)[:, :rows] for k, v in stacked.items()})

    monkeypatch.setattr(tr.Trainer, "train_steps_scan", half)
    line = _run_main(monkeypatch, capsys, "bert_base.finetune")
    assert line["correct"] is False
    failing = [n for n, c in line["checks"].items() if c["value"] > c["limit"]]
    assert "grad_norm_gap" in failing or "moment_gap" in failing


def test_a_row_altered_in_the_data_plane_is_not_correct(monkeypatch, capsys):
    from synapseml_tpu.data import loader as ld

    real = ld.DataLoader.__next__ if hasattr(ld.DataLoader, "__next__") else None
    if real is None:
        pytest.skip("DataLoader is not its own iterator")

    def altered(self):
        batch = dict(real(self))
        labels = np.array(batch["labels"])
        labels[0] = 1 - labels[0]
        batch["labels"] = labels
        return batch

    monkeypatch.setattr(ld.DataLoader, "__next__", altered)
    line = _run_main(monkeypatch, capsys, "bert_base.finetune")
    assert line["correct"] is False
    assert line["checks"]["rows_unmatched"]["value"] > 0
