"""Share of the compute roofline that the windowed flash kernel's forward
reaches: the least time the chip could take for its launches (launches in the
trace x the band's score and value products of one launch,
`window_forward_flops` of the configuration's `perfbench/flops/` module, x
rows a step, over peak) over those launches' device time. The configuration's
`trace_kernel` names the scope (`attn.window`) whose kernel is read. The
tiles' pairs outside the band are computed and not counted. Nothing to read
where the operation count has no window or the trace no such kernel."""


def read(facts: dict):
    kernel = (facts["trace"] or {}).get("flash_kernel")
    if facts["peaks"] is None or not kernel or not kernel["launches"] \
            or not kernel["seconds"]:
        return None
    cell = facts["cell"]
    count = getattr(cell.module("flops", cell.config["flops"]), "window_forward_flops", None)
    if count is None:
        return None
    flops = count(cell.config, cell.traffic) * int(cell.traffic["batch"])
    least_s = kernel["launches"] * flops / facts["peaks"]["bf16_flops_per_s"]
    return 100.0 * least_s / kernel["seconds"]
