"""Share of the traced device time under the sliding-window attention's scope
(`attn.window`): the windowed flash kernel's forward, once a step under the
block's remat, its blockwise backward pass in XLA over the band's tile pairs,
and the pads around them. Nothing to read where the program has no such
scope."""


def read(facts: dict):
    scopes = (facts["trace"] or {}).get("scopes")
    if not scopes or not scopes["scopes"].get("attn.window"):
        return None
    return 100.0 * scopes["scopes"]["attn.window"] / scopes["total_s"]
