"""Seconds of backend compilation (or cache loading) during set-up, from jax's
monitoring events."""


def read(facts: dict):
    return facts["setup_split"]["compile_s"]
