"""Device-busy time inside the traced window over the optimizer steps it held."""


def read(facts: dict):
    trace = facts["trace"]
    if not trace or not trace["steps"]:
        return None
    return 1e3 * trace["busy_s"] / trace["steps"]
