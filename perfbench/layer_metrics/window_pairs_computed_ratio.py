"""(query, key) pairs the windowed flash launches compute (the tiles of its
step tables x block_q x block_k) over the band's pairs: the program's own
gauge `synapseml_attn_window_pairs_ratio`, set when the op is traced. 1.0
would compute the band alone; a change of tiling shows here before it shows
in time."""

from perfbench.lib import program_spans


def read(facts: dict):
    return program_spans.counter(facts, "synapseml_attn_window_pairs_ratio")
