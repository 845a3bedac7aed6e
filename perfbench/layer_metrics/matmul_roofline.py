"""Share of the compute roofline that XLA's matmul fusions reach: the least
time the chip could take for a step's matmul FLOPs (FLOPs over peak; these
products are compute-bound at the cells' shapes) over the device time per step
of the events the trace marks as matrix products."""


def read(facts: dict):
    trace = facts["trace"]
    if facts["peaks"] is None or not trace or not trace["steps"] \
            or not trace["matmul_s"]:
        return None
    cell = facts["cell"]
    flops = cell.module("flops", cell.config["flops"]).train_flops_per_sample(
        cell.config, cell.traffic) * int(cell.traffic["batch"])
    least_s = flops / (facts["peaks"]["bf16_flops_per_s"] * facts["chips"])
    return 100.0 * least_s / (trace["matmul_s"] / trace["steps"])
