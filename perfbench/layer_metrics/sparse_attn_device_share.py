"""Share of the traced device time under the learned sparse attention's
scopes (`attn.indexer`, `attn.select`, `attn.sparse` in the ops' name paths):
index scores, the exact top-k, and the masked score, softmax and value
products, forward, recomputed and backward."""


def read(facts: dict):
    scopes = (facts["trace"] or {}).get("scopes")
    if not scopes:
        return None
    return 100.0 * sum(s for name, s in scopes["scopes"].items()
                       if name.startswith("attn.")) / scopes["total_s"]
