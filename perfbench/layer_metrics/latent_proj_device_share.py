"""Share of the traced device time under the latent mixer's scope
(`attn.latent`): everything of multi-head latent attention outside the flash
call (the query projection, the down-projection to the latent and the shared
rotary key, the latent's norm, the up-projection to keys and values, RoPE on
the rotary parts, assembling queries and keys, the output projection),
forward, recomputed and backward. Nothing to read where the program has no
such scope."""


def read(facts: dict):
    scopes = (facts["trace"] or {}).get("scopes")
    if not scopes or not scopes["scopes"].get("attn.latent"):
        return None
    return 100.0 * scopes["scopes"]["attn.latent"] / scopes["total_s"]
