"""Modern-vision/mixed op coverage through a REAL torch export: GroupNorm
(lowered to InstanceNormalization), Hardswish, F.interpolate in both nearest
and bilinear modes (Resize with asymmetric / pytorch_half_pixel coordinate
transforms), sinusoidal Sin/Cos features, and a TopK head — all converted
and parity-checked against torch. Reference runs these through ONNX
Runtime's full opset (``onnx/ONNXModel.scala:211``)."""

import io
import math
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent))

torch = pytest.importorskip("torch")
from torch import nn  # noqa: E402
import torch.nn.functional as F  # noqa: E402

from _torch_resnet import onnx_shim  # noqa: E402


class MixedNet(nn.Module):
    def __init__(self):
        super().__init__()
        self.conv = nn.Conv2d(3, 8, 3, padding=1)
        self.gn = nn.GroupNorm(2, 8)
        self.act = nn.Hardswish()
        self.head = nn.Linear(8, 16)

    def forward(self, x):
        h = self.act(self.gn(self.conv(x)))
        h = F.interpolate(h, scale_factor=2.0, mode="nearest")
        h = F.interpolate(h, size=(8, 8), mode="bilinear",
                          align_corners=False)
        pooled = h.mean(dim=(2, 3))
        freq = torch.arange(4, device=x.device, dtype=torch.float32)
        enc = torch.cat([torch.sin(pooled[:, :4] * freq),
                         torch.cos(pooled[:, :4] * freq)], dim=-1)
        logits = self.head(enc)
        vals, idx = torch.topk(logits, k=3, dim=-1)
        return vals, idx


@pytest.fixture(scope="module")
def exported():
    torch.manual_seed(0)
    model = MixedNet().eval()
    buf = io.BytesIO()
    with onnx_shim():
        torch.onnx.export(model, (torch.randn(2, 3, 4, 4),), buf,
                          dynamo=False, input_names=["x"],
                          output_names=["vals", "idx"],
                          dynamic_axes={"x": {0: "N"}})
    return model, buf.getvalue()


def test_mixed_export_ops_all_supported(exported):
    from synapseml_tpu.onnx.convert import OP_REGISTRY
    from synapseml_tpu.onnx.proto import ModelProto

    _, data = exported
    ops = {n.op_type for n in ModelProto.parse(data).graph.node}
    for must in ("Resize", "InstanceNormalization", "HardSwish", "Sin",
                 "Cos", "TopK"):
        assert must in ops, f"export no longer exercises {must}"
    missing = sorted(o for o in ops if o not in OP_REGISTRY)
    assert not missing, f"unsupported mixed ops: {missing}"


def test_mixed_outputs_match_torch(exported):
    import jax

    from synapseml_tpu.onnx import convert_graph

    model, data = exported
    conv = convert_graph(data)
    fn = jax.jit(lambda t: conv(x=t))

    for B in (2, 5):
        gen = torch.Generator().manual_seed(B)
        x = torch.randn(B, 3, 4, 4, generator=gen)
        with torch.no_grad():
            want_vals, want_idx = model(x)
        got = fn(x.numpy())
        np.testing.assert_allclose(np.asarray(got["vals"]),
                                   want_vals.numpy(), rtol=2e-4, atol=2e-5)
        np.testing.assert_array_equal(np.asarray(got["idx"]),
                                      want_idx.numpy())


def test_resize_modes_match_torch_interpolate():
    """Direct Resize-op checks against torch.nn.functional.interpolate for
    each mode/coordinate-transform pair torch exports."""
    from synapseml_tpu.onnx.convert import OP_REGISTRY

    x = np.arange(2 * 3 * 5 * 7, dtype=np.float32).reshape(2, 3, 5, 7)
    t = torch.from_numpy(x)

    # nearest + asymmetric + floor (torch nearest export)
    got = np.asarray(OP_REGISTRY["Resize"](
        [x, None, np.array([1.0, 1.0, 2.0, 2.0], np.float32), None],
        {"mode": "nearest", "coordinate_transformation_mode": "asymmetric",
         "nearest_mode": "floor"}))
    want = F.interpolate(t, scale_factor=2.0, mode="nearest").numpy()
    np.testing.assert_array_equal(got, want)

    # linear + pytorch_half_pixel (align_corners=False export)
    got = np.asarray(OP_REGISTRY["Resize"](
        [x, None, None, np.array([2, 3, 9, 13], np.int64)],
        {"mode": "linear",
         "coordinate_transformation_mode": "pytorch_half_pixel"}))
    want = F.interpolate(t, size=(9, 13), mode="bilinear",
                         align_corners=False).numpy()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    # linear + align_corners (align_corners=True export)
    got = np.asarray(OP_REGISTRY["Resize"](
        [x, None, None, np.array([2, 3, 10, 4], np.int64)],
        {"mode": "linear",
         "coordinate_transformation_mode": "align_corners"}))
    want = F.interpolate(t, size=(10, 4), mode="bilinear",
                         align_corners=True).numpy()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_conv_transpose_parity_with_torch():
    """ConvTranspose (the UNet upsampling op) vs torch, incl. the classic
    stride-2/pad-1/output_padding-1 doubling config, groups, and dilation."""
    import torch

    rs = np.random.default_rng(11)
    from synapseml_tpu.onnx.convert import OP_REGISTRY

    configs = [
        dict(cin=4, cout=6, k=3, stride=2, pad=1, out_pad=1, groups=1, dil=1),
        dict(cin=4, cout=4, k=2, stride=2, pad=0, out_pad=0, groups=1, dil=1),
        dict(cin=4, cout=8, k=3, stride=1, pad=1, out_pad=0, groups=2, dil=1),
        dict(cin=3, cout=3, k=3, stride=2, pad=2, out_pad=1, groups=1, dil=2),
    ]
    for c in configs:
        x = rs.normal(size=(2, c["cin"], 7, 7)).astype(np.float32)
        m = torch.nn.ConvTranspose2d(
            c["cin"], c["cout"], c["k"], stride=c["stride"], padding=c["pad"],
            output_padding=c["out_pad"], groups=c["groups"],
            dilation=c["dil"])
        with torch.no_grad():
            want = m(torch.tensor(x)).numpy()
        got = np.asarray(OP_REGISTRY["ConvTranspose"](
            [x, m.weight.detach().numpy(), m.bias.detach().numpy()],
            {"strides": [c["stride"]] * 2, "pads": [c["pad"]] * 4,
             "output_padding": [c["out_pad"]] * 2, "group": c["groups"],
             "dilations": [c["dil"]] * 2}))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4,
                                   err_msg=str(c))


def test_unet_style_export_parity(tmp_path):
    """A torch-exported encoder-decoder (conv down, ConvTranspose up, skip
    concat) through the full ONNX->JAX conversion."""
    import torch

    class MiniUNet(torch.nn.Module):
        def __init__(self):
            super().__init__()
            self.down = torch.nn.Conv2d(3, 8, 3, stride=2, padding=1)
            self.mid = torch.nn.Conv2d(8, 8, 3, padding=1)
            self.up = torch.nn.ConvTranspose2d(8, 4, 3, stride=2, padding=1,
                                               output_padding=1)
            self.out = torch.nn.Conv2d(7, 2, 1)  # 4 up + 3 skip channels

        def forward(self, x):
            d = torch.relu(self.down(x))
            m = torch.relu(self.mid(d))
            u = torch.relu(self.up(m))
            return self.out(torch.cat([u, x], dim=1))

    torch.manual_seed(0)
    model = MiniUNet().eval()
    x = np.random.default_rng(12).normal(size=(1, 3, 16, 16)).astype(np.float32)
    buf = io.BytesIO()
    with onnx_shim():
        torch.onnx.export(model, (torch.tensor(x),), buf, input_names=["x"],
                          output_names=["y"], dynamo=False)
    with torch.no_grad():
        want = model(torch.tensor(x)).numpy()
    from synapseml_tpu.onnx import convert_graph

    conv = convert_graph(buf.getvalue())
    got = np.asarray(conv(x=x)["y"])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
