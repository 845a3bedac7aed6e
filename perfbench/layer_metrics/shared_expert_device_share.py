"""Share of the traced device time under the shared expert's scope
(`moe.shared`): the three products of the gated MLP that every token passes
through beside the routed experts, forward, recomputed and backward. Nothing
to read where the program has no such scope."""


def read(facts: dict):
    scopes = (facts["trace"] or {}).get("scopes")
    if not scopes or not scopes["scopes"].get("moe.shared"):
        return None
    return 100.0 * scopes["scopes"]["moe.shared"] / scopes["total_s"]
