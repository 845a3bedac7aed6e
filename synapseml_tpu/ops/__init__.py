"""TPU kernel ops: the compute hot paths of the framework.

The reference delegates its hot loops to prebuilt native engines (LightGBM
C++ histograms, VW C++ SGD, ONNX Runtime CUDA kernels — SURVEY.md §1 L0).
Here the hot ops are first-class TPU kernels:

  * :mod:`attention` — blockwise flash attention (Pallas TPU kernel with an
    XLA blockwise fallback) for the on-chip attention hot path;
  * :mod:`ring_attention` — cross-chip sequence parallelism over a named
    mesh axis via ``ppermute`` (net-new capability, SURVEY.md §5
    "long-context"; the reference has none);
  * :mod:`ulysses_attention` — the all-to-all sequence-parallel strategy
    (heads scatter, tokens gather, local full-context attention);
  * :mod:`sparse_attention` — learned sparse attention for training: an
    indexer's exact top-k key set a query, tiled masked attention over it;
  * :mod:`grouped_ffn` — one chip's share of a mixture of experts, dropless:
    a grouped product over the routed (token, choice) pairs;
  * :mod:`short_conv` — the gated short convolution of hybrid conv-attention
    decoders: two gates around a depthwise causal convolution of a few taps.
"""

from .attention import flash_attention, reference_attention
from .grouped_ffn import expert_share_ffn
from .ring_attention import ring_attention, ring_attention_sharded
from .short_conv import gated_short_conv
from .sparse_attention import indexed_attention, topk_mask
from .ulysses_attention import ulysses_attention, ulysses_attention_sharded

__all__ = [
    "expert_share_ffn",
    "flash_attention",
    "gated_short_conv",
    "indexed_attention",
    "reference_attention",
    "ring_attention",
    "ring_attention_sharded",
    "topk_mask",
    "ulysses_attention",
    "ulysses_attention_sharded",
]
