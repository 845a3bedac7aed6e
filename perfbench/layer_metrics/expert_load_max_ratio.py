"""The most-loaded held expert's (token, choice) pairs over the held experts'
mean, worst layer of the run's last step: the program's own gauge
`synapseml_moe_expert_load_max_ratio`. 1.0 is perfect balance; an expert-
parallel deployment waits for its most-loaded chip."""

from perfbench.lib import program_spans


def read(facts: dict):
    return program_spans.counter(facts, "synapseml_moe_expert_load_max_ratio")
