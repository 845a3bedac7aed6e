"""Share of the window's time that the iterator handed to `Trainer.fit` spent
inside `next()` on the loader (the chunk producer's thread)."""


def read(facts: dict):
    if facts["loader_wait_s"] is None:
        return None
    return 100.0 * facts["loader_wait_s"] / facts["window_s"]
