"""1 - union of the device's op intervals over the traced window."""


def read(facts: dict):
    trace = facts["trace"]
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
