"""Share of the traced device time under `attn.select`: the exact top-k of the
index scores (the counting passes that find each query's threshold)."""


def read(facts: dict):
    scopes = (facts["trace"] or {}).get("scopes")
    if not scopes:
        return None
    return 100.0 * scopes["scopes"]["attn.select"] / scopes["total_s"]
