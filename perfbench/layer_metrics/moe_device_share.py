"""Share of the traced device time under the expert layer's scopes
(`moe.route`, `moe.experts`): the router, the layout of the held pairs and
the grouped products over them, forward, recomputed and backward."""


def read(facts: dict):
    scopes = (facts["trace"] or {}).get("scopes")
    if not scopes:
        return None
    return 100.0 * sum(s for name, s in scopes["scopes"].items()
                       if name.startswith("moe.")) / scopes["total_s"]
