"""Samples the window trained, over the time that really passed from the
window's start to `block_until_ready` on the state after its last step."""


def read(facts: dict):
    return facts["samples"] / facts["window_s"]
