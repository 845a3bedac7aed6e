"""The whole step's share of the chip's peak: matmul FLOPs a sample needs
(`perfbench/flops/`) x samples/s of this run's window, over peak x chips."""


def read(facts: dict):
    if facts["peaks"] is None:
        return None
    cell = facts["cell"]
    flops = cell.module("flops", cell.config["flops"]).train_flops_per_sample(
        cell.config, cell.traffic)
    rate = facts["samples"] / facts["window_s"]
    return 100.0 * flops * rate / (facts["peaks"]["bf16_flops_per_s"] * facts["chips"])
