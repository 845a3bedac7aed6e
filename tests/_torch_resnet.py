"""Test helper: hand-built torchvision-layout ResNet (no torchvision in the
image) + ONNX export that shims the absent ``onnx`` package with our own
proto codec (torch's exporter only needs it to splice custom onnxscript
functions, which standard convnets don't have)."""

from __future__ import annotations

import contextlib
import io
import sys
import types

import torch
from torch import nn


class Bottleneck(nn.Module):
    expansion = 4

    def __init__(self, cin, width, stride=1, downsample=None):
        super().__init__()
        self.conv1 = nn.Conv2d(cin, width, 1, bias=False)
        self.bn1 = nn.BatchNorm2d(width)
        self.conv2 = nn.Conv2d(width, width, 3, stride, 1, bias=False)
        self.bn2 = nn.BatchNorm2d(width)
        self.conv3 = nn.Conv2d(width, width * 4, 1, bias=False)
        self.bn3 = nn.BatchNorm2d(width * 4)
        self.relu = nn.ReLU(inplace=True)
        self.downsample = downsample

    def forward(self, x):
        idt = x
        out = self.relu(self.bn1(self.conv1(x)))
        out = self.relu(self.bn2(self.conv2(out)))
        out = self.bn3(self.conv3(out))
        if self.downsample is not None:
            idt = self.downsample(x)
        return self.relu(out + idt)


class TorchResNet(nn.Module):
    """torchvision-compatible naming: conv1/bn1/layer{1..4}.{j}.convK/
    downsample.0/fc — the state dict converts via
    convert_hf.resnet_variables_from_torch."""

    def __init__(self, layers=(3, 4, 6, 3), num_classes=1000, width0=64):
        super().__init__()
        self.num_stages = len(layers)
        self.inplanes = width0
        self.conv1 = nn.Conv2d(3, width0, 7, 2, 3, bias=False)
        self.bn1 = nn.BatchNorm2d(width0)
        self.relu = nn.ReLU(inplace=True)
        self.maxpool = nn.MaxPool2d(3, 2, 1)
        for i, n in enumerate(layers):
            setattr(self, f"layer{i + 1}",
                    self._make_layer(width0 * (2 ** i), n, 1 if i == 0 else 2))
        self.avgpool = nn.AdaptiveAvgPool2d((1, 1))
        self.fc = nn.Linear(self.inplanes, num_classes)

    def _make_layer(self, width, blocks, stride):
        down = None
        if stride != 1 or self.inplanes != width * 4:
            down = nn.Sequential(
                nn.Conv2d(self.inplanes, width * 4, 1, stride, bias=False),
                nn.BatchNorm2d(width * 4))
        layers = [Bottleneck(self.inplanes, width, stride, down)]
        self.inplanes = width * 4
        layers += [Bottleneck(self.inplanes, width) for _ in range(blocks - 1)]
        return nn.Sequential(*layers)

    def forward(self, x):
        x = self.maxpool(self.relu(self.bn1(self.conv1(x))))
        for i in range(self.num_stages):
            x = getattr(self, f"layer{i + 1}")(x)
        x = self.avgpool(x).flatten(1)
        return self.fc(x)


def resnet50(num_classes=1000):
    return TorchResNet((3, 4, 6, 3), num_classes)


def resnet_small(num_classes=10):
    return TorchResNet((1, 1), num_classes, width0=8)


@contextlib.contextmanager
def onnx_shim():
    """Minimal stand-in for the ``onnx`` package backed by our proto codec,
    in ``sys.modules`` for the length of a ``torch.onnx.export`` only:
    torch's TorchScript exporter imports it to scan for custom onnxscript
    functions (none in plain convnets). Left behind, a module without a
    ``__spec__`` breaks every later ``importlib.util.find_spec("onnx")`` in
    the process (``transformers`` makes one at import)."""
    if "onnx" in sys.modules:  # the real package: nothing to stand in for
        yield
        return
    from synapseml_tpu.onnx.proto import parse_model

    class _Model:
        def __init__(self, parsed):
            self.graph = parsed.graph
            self.functions = []

    shim = types.ModuleType("onnx")
    shim.load_model_from_string = lambda b: _Model(parse_model(b))
    sys.modules["onnx"] = shim
    try:
        yield
    finally:
        sys.modules.pop("onnx", None)


def export_onnx_bytes(model: nn.Module, example: torch.Tensor) -> bytes:
    model.eval()
    buf = io.BytesIO()
    with onnx_shim():
        torch.onnx.export(model, example, buf, dynamo=False,
                          input_names=["input"], output_names=["logits"],
                          dynamic_axes={"input": {0: "N"}, "logits": {0: "N"}})
    return buf.getvalue()
