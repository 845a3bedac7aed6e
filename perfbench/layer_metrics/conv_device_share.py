"""Share of the traced device time under the gated short convolution's scopes
(`conv.proj`, `conv.mix`): the input and output projections, the gates and
the taps, forward, recomputed and backward."""


def read(facts: dict):
    scopes = (facts["trace"] or {}).get("scopes")
    if not scopes:
        return None
    return 100.0 * sum(s for name, s in scopes["scopes"].items()
                       if name.startswith("conv.")) / scopes["total_s"]
