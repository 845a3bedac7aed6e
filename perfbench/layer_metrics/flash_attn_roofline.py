"""Share of the compute roofline that the flash kernel's forward reaches: the
least time the chip could take for its launches (launches in the trace x the
causal score and value products of one launch, `perfbench/flops/`, over peak;
compute-bound at the cell's shapes) over those launches' device time. The
kernel pads `head_dim` to the 128 lanes and computes whole blocks on the
diagonal: that work is not counted."""


def read(facts: dict):
    kernel = (facts["trace"] or {}).get("flash_kernel")
    if facts["peaks"] is None or not kernel or not kernel["launches"] \
            or not kernel["seconds"]:
        return None
    cell = facts["cell"]
    flops = cell.module("flops", cell.config["flops"]).flash_forward_flops(
        cell.config, cell.traffic) * int(cell.traffic["batch"])
    least_s = kernel["launches"] * flops / facts["peaks"]["bf16_flops_per_s"]
    return 100.0 * least_s / kernel["seconds"]
