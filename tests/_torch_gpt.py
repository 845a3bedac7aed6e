"""Test helper: GPT-2-style causal decoder for REAL ``torch.onnx.export`` →
converter parity. Complements ``_torch_bert.py`` with the DECODER-side export
surface: Trilu causal masks (``torch.tril``), masked_fill → Where/Not chains,
GatherElements (``torch.gather``), Slice/chunk QKV splits, and the shape-guard
``If`` nodes the TorchScript exporter emits around dynamic dims."""

from __future__ import annotations

import io
import math

import torch
from torch import nn

from _torch_resnet import onnx_shim


class CausalBlock(nn.Module):
    def __init__(self, d: int, h: int):
        super().__init__()
        self.h, self.dk = h, d // h
        self.qkv = nn.Linear(d, 3 * d)
        self.o = nn.Linear(d, d)
        self.ln1, self.ln2 = nn.LayerNorm(d), nn.LayerNorm(d)
        self.mlp = nn.Sequential(nn.Linear(d, 4 * d), nn.GELU(),
                                 nn.Linear(4 * d, d))

    def forward(self, x):
        B, T, D = x.size(0), x.size(1), x.size(2)
        q, k, v = self.qkv(self.ln1(x)).chunk(3, dim=-1)

        def sp(t):
            return t.view(B, T, self.h, self.dk).transpose(1, 2)

        q, k, v = sp(q), sp(k), sp(v)
        att = (q @ k.transpose(-2, -1)) / math.sqrt(self.dk)
        mask = torch.tril(torch.ones(T, T, dtype=torch.bool, device=x.device))
        att = att.masked_fill(~mask, float("-inf"))   # Not + Where export
        y = torch.softmax(att, dim=-1) @ v
        y = y.transpose(1, 2).reshape(B, T, D)
        x = x + self.o(y)
        return x + self.mlp(self.ln2(x))


class TorchTinyGPT(nn.Module):
    def __init__(self, vocab: int = 256, d: int = 32, layers: int = 2,
                 heads: int = 2, max_len: int = 64):
        super().__init__()
        self.tok = nn.Embedding(vocab, d)
        self.pos = nn.Embedding(max_len, d)
        self.blocks = nn.ModuleList(
            CausalBlock(d, heads) for _ in range(layers))
        self.lnf = nn.LayerNorm(d)
        self.head = nn.Linear(d, vocab, bias=False)

    def forward(self, ids, gather_idx):
        T = ids.size(1)
        x = self.tok(ids) + self.pos(
            torch.arange(T, device=ids.device)).unsqueeze(0)
        for b in self.blocks:
            x = b(x)
        logits = self.head(self.lnf(x))
        # per-row logits at each row's own position: torch.gather exports
        # GatherElements (the last-valid-token pick every batched LM does)
        idx = gather_idx.unsqueeze(-1).unsqueeze(-1).expand(
            -1, 1, logits.size(-1))
        return torch.gather(logits, 1, idx).squeeze(1)


def export_gpt_onnx_bytes(model: nn.Module, ids: torch.Tensor,
                          gather_idx: torch.Tensor) -> bytes:
    model.eval()
    buf = io.BytesIO()
    with onnx_shim():
        torch.onnx.export(
            model, (ids, gather_idx), buf, dynamo=False,
            input_names=["ids", "gather_idx"], output_names=["logits"],
            dynamic_axes={"ids": {0: "N", 1: "T"}, "gather_idx": {0: "N"},
                          "logits": {0: "N"}})
    return buf.getvalue()
