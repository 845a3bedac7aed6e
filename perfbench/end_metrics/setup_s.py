"""Process start to the window's start: imports, rows and weights from the
seed, the trainer, the job's first dispatch (cache load or compile)."""


def read(facts: dict):
    return facts["setup_s"]
