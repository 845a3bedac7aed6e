"""Share of the traced device time under full attention's scope
(`attn.flash`): the flash kernel's forward, run twice a step under the
block's remat, and its blockwise backward pass in XLA."""


def read(facts: dict):
    scopes = (facts["trace"] or {}).get("scopes")
    if not scopes or "attn.flash" not in scopes["scopes"]:
        return None
    return 100.0 * scopes["scopes"]["attn.flash"] / scopes["total_s"]
