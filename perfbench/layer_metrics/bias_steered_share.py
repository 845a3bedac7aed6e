"""Share of the (token, choice) pairs whose expert the router's scores alone
would not have chosen: the selection bias steered them. Mean over the expert
layers of the run's last step: the program's own gauge
`synapseml_moe_bias_steered_share`."""

from perfbench.lib import program_spans


def read(facts: dict):
    value = program_spans.counter(facts, "synapseml_moe_bias_steered_share")
    return None if value is None else 100.0 * value
