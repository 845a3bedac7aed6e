"""Recurrent model family through the ONNX path: torch nn.LSTM / nn.GRU
export as native ONNX LSTM/GRU nodes, lowered here to ``lax.scan``
recurrences (the TPU-idiomatic form — static shapes, no per-step Python).
Covers bidirectional LSTM, GRU with linear_before_reset (the torch export
default), and end-to-end parity of a stacked recurrent classifier.
Reference runs these through ONNX Runtime (``onnx/ONNXModel.scala:211``)."""

import io
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent))

torch = pytest.importorskip("torch")
from torch import nn  # noqa: E402

from _torch_resnet import onnx_shim  # noqa: E402


class RecNet(nn.Module):
    def __init__(self):
        super().__init__()
        self.lstm = nn.LSTM(8, 16, num_layers=1, bidirectional=True)
        self.gru = nn.GRU(32, 12)
        self.head = nn.Linear(12, 4)

    def forward(self, x):
        h, _ = self.lstm(x)
        g, _ = self.gru(h)
        return self.head(g[-1])


def _export(model, args, **kw):
    model.eval()
    buf = io.BytesIO()
    with onnx_shim():
        torch.onnx.export(model, args, buf, dynamo=False, **kw)
    return buf.getvalue()


@pytest.fixture(scope="module")
def exported():
    torch.manual_seed(0)
    model = RecNet()
    data = _export(model, (torch.randn(10, 3, 8),), input_names=["x"],
                   output_names=["y"])
    return model, data


def test_export_leaves_sys_modules_as_it_found_them():
    """The ``onnx`` stand-in lives for the export only: afterwards the name
    resolves (or fails to) exactly as before, and ``find_spec`` does not meet
    a module without a ``__spec__`` (what ``transformers`` died of when it
    shared a worker with an exporting file)."""
    import importlib.util

    before = sys.modules.get("onnx")
    _export(nn.Linear(4, 2), (torch.randn(3, 4),), input_names=["x"],
            output_names=["y"])
    assert sys.modules.get("onnx") is before
    importlib.util.find_spec("onnx")  # ValueError at the parent


def test_rnn_export_ops_all_supported(exported):
    from synapseml_tpu.onnx.convert import OP_REGISTRY
    from synapseml_tpu.onnx.proto import ModelProto

    _, data = exported
    ops = {n.op_type for n in ModelProto.parse(data).graph.node}
    assert {"LSTM", "GRU"} <= ops
    missing = sorted(o for o in ops if o not in OP_REGISTRY)
    assert not missing, f"unsupported recurrent ops: {missing}"


def test_stacked_bilstm_gru_matches_torch(exported):
    import jax

    from synapseml_tpu.onnx import convert_graph

    model, data = exported
    conv = convert_graph(data)
    fn = jax.jit(lambda t: conv(x=t)["y"])
    x = torch.randn(10, 3, 8, generator=torch.Generator().manual_seed(1))
    with torch.no_grad():
        want = model(x).numpy()
    np.testing.assert_allclose(np.asarray(fn(x.numpy())), want,
                               rtol=2e-4, atol=2e-5)


def test_lstm_all_outputs_and_initial_state():
    """Y / Y_h / Y_c all match a direct torch LSTM given a nonzero initial
    state passed as graph inputs."""
    import jax

    from synapseml_tpu.onnx import convert_graph

    class Bare(nn.Module):
        def __init__(self):
            super().__init__()
            self.lstm = nn.LSTM(5, 7)

        def forward(self, x, h0, c0):
            y, (h, c) = self.lstm(x, (h0, c0))
            return y, h, c

    torch.manual_seed(2)
    m = Bare()
    x = torch.randn(6, 2, 5)
    h0, c0 = torch.randn(1, 2, 7), torch.randn(1, 2, 7)
    data = _export(m, (x, h0, c0), input_names=["x", "h0", "c0"],
                   output_names=["y", "h", "c"])
    conv = convert_graph(data)
    out = jax.jit(lambda *a: conv(x=a[0], h0=a[1], c0=a[2]))(
        x.numpy(), h0.numpy(), c0.numpy())
    with torch.no_grad():
        wy, (wh, wc) = m.lstm(x, (h0, c0))
    # torch's exporter already squeezes Y to the [T, B, H] torch layout
    np.testing.assert_allclose(np.asarray(out["y"]), wy.numpy(),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(out["h"]), wh.numpy(),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(out["c"]), wc.numpy(),
                               rtol=2e-4, atol=2e-5)
