"""`memory_stats()["peak_bytes_in_use"]` of the fullest chip after the window,
before the reference runs."""


def read(facts: dict):
    if not facts["memory_peak_bytes"]:
        return None
    return facts["memory_peak_bytes"] / 1e9
