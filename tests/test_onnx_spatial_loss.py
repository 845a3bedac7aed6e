"""Spatial-sampling, normalization, and training-loss ONNX ops: GridSample
and the losses parity-checked against REAL torch exports; RoiAlign and the
opset-18 tail vs numpy spec oracles (no torchvision in the image).
Reference runs these through ONNX Runtime (``onnx/ONNXModel.scala:211``)."""

import io
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent))

torch = pytest.importorskip("torch")
import torch.nn.functional as F  # noqa: E402
from torch import nn  # noqa: E402

from _torch_resnet import onnx_shim  # noqa: E402

from synapseml_tpu.onnx.convert import OP_REGISTRY  # noqa: E402


def run_op(op, ins, **attrs):
    return OP_REGISTRY[op]([None if x is None else np.asarray(x)
                            for x in ins], attrs)


# ---------------------------------------------------------------------------
# GridSample vs a real torch export
# ---------------------------------------------------------------------------

class SamplerNet(nn.Module):
    def __init__(self, mode, padding_mode, align_corners):
        super().__init__()
        self.kw = dict(mode=mode, padding_mode=padding_mode,
                       align_corners=align_corners)

    def forward(self, x, grid):
        return F.grid_sample(x, grid, **self.kw)


@pytest.mark.parametrize("mode", ["bilinear", "nearest"])
@pytest.mark.parametrize("padding_mode", ["zeros", "border", "reflection"])
@pytest.mark.parametrize("align_corners", [False, True])
def test_grid_sample_matches_torch_export(mode, padding_mode, align_corners):
    from synapseml_tpu.onnx import convert_graph

    torch.manual_seed(0)
    model = SamplerNet(mode, padding_mode, align_corners).eval()
    x = torch.randn(2, 3, 5, 7)
    # grid spills past [-1, 1] so the padding mode actually matters
    grid = (torch.rand(2, 4, 6, 2) * 2.6 - 1.3)
    buf = io.BytesIO()
    with onnx_shim():
        torch.onnx.export(model, (x, grid), buf, dynamo=False,
                          input_names=["x", "grid"], output_names=["y"],
                          opset_version=16)
    conv = convert_graph(buf.getvalue())
    got = np.asarray(conv(x=x.numpy(), grid=grid.numpy())["y"])
    with torch.no_grad():
        want = model(x, grid).numpy()
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# losses vs real torch exports
# ---------------------------------------------------------------------------

class CELossNet(nn.Module):
    def __init__(self, weight=None, ignore_index=-100, reduction="mean"):
        super().__init__()
        self.kw = dict(ignore_index=ignore_index, reduction=reduction)
        self.weight = weight

    def forward(self, scores, labels):
        return F.cross_entropy(scores, labels, weight=self.weight, **self.kw)


@pytest.mark.parametrize("reduction", ["mean", "sum", "none"])
@pytest.mark.parametrize("weighted", [False, True])
def test_softmax_ce_loss_matches_torch_export(reduction, weighted):
    from synapseml_tpu.onnx import convert_graph

    torch.manual_seed(1)
    weight = torch.rand(5) + 0.5 if weighted else None
    model = CELossNet(weight=weight, ignore_index=3,
                      reduction=reduction).eval()
    scores = torch.randn(8, 5)
    labels = torch.tensor([0, 1, 2, 3, 4, 0, 3, 2])  # two ignored rows
    buf = io.BytesIO()
    with onnx_shim():
        torch.onnx.export(model, (scores, labels), buf, dynamo=False,
                          input_names=["scores", "labels"],
                          output_names=["loss"])
    conv = convert_graph(buf.getvalue())
    got = np.asarray(conv(scores=scores.numpy(), labels=labels.numpy())["loss"])
    with torch.no_grad():
        want = model(scores, labels).numpy()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_nll_loss_direct_matches_torch():
    torch.manual_seed(2)
    log_prob = F.log_softmax(torch.randn(6, 4), dim=1)
    labels = torch.tensor([0, 1, 2, 3, 1, 0])
    for reduction in ("mean", "sum", "none"):
        got = run_op("NegativeLogLikelihoodLoss",
                     [log_prob.numpy(), labels.numpy()],
                     reduction=reduction)
        want = F.nll_loss(log_prob, labels, reduction=reduction).numpy()
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-6)


# ---------------------------------------------------------------------------
# RoiAlign vs a numpy spec oracle
# ---------------------------------------------------------------------------

def roi_align_oracle(x, rois, batch_idx, out_h, out_w, ratio, scale,
                     mode="avg", half_pixel=True):
    """ONNX Runtime RoiAlign semantics: samples past the 1-pixel halo
    contribute zero, everything else clamps into the image; the legacy >=1
    ROI-size clamp applies only in output_half_pixel mode; max mode maxes
    the WEIGHTED corner contributions."""
    N, C, H, W = x.shape
    out = np.zeros((len(rois), C, out_h, out_w), np.float32)
    off = 0.5 if half_pixel else 0.0

    def sample(b, yy, xx):
        if yy < -1.0 or yy > H or xx < -1.0 or xx > W:
            return [np.zeros(C, np.float32)] * 4
        yy, xx = min(max(yy, 0.0), H - 1), min(max(xx, 0.0), W - 1)
        x0, y0 = int(np.floor(xx)), int(np.floor(yy))
        wx, wy = xx - x0, yy - y0
        cs = []
        for dy, fy in ((0, 1 - wy), (1, wy)):
            for dx, fx in ((0, 1 - wx), (1, wx)):
                ix = min(x0 + dx, W - 1)
                iy = min(y0 + dy, H - 1)
                cs.append(x[b, :, iy, ix] * fx * fy)
        return cs

    for r, (roi, b) in enumerate(zip(rois, batch_idx)):
        x1, y1, x2, y2 = roi * scale - off
        rw, rh = x2 - x1, y2 - y1
        if not half_pixel:
            rw, rh = max(rw, 1.0), max(rh, 1.0)
        bw, bh = rw / out_w, rh / out_h
        for oy in range(out_h):
            for ox in range(out_w):
                corners = [sample(
                    b, y1 + (oy * ratio + sy + 0.5) * bh / ratio,
                    x1 + (ox * ratio + sx + 0.5) * bw / ratio)
                    for sy in range(ratio) for sx in range(ratio)]
                if mode == "max":
                    agg = np.max([c for cs in corners for c in cs], axis=0)
                else:
                    agg = np.mean([np.sum(cs, axis=0) for cs in corners],
                                  axis=0)
                out[r, :, oy, ox] = agg
    return out


@pytest.mark.parametrize("mode", ["avg", "max"])
@pytest.mark.parametrize("half_pixel", [True, False])
def test_roi_align_matches_oracle(mode, half_pixel):
    rs = np.random.default_rng(0)
    x = rs.normal(size=(2, 3, 10, 12)).astype(np.float32)
    # includes an edge-touching ROI (border clamp) and a tiny sub-pixel ROI
    # (exercises the mode-dependent legacy size clamp)
    rois = np.asarray([[1.0, 1.0, 8.0, 7.0], [0.0, 2.0, 11.0, 9.0],
                       [3.0, 0.5, 6.0, 4.0], [0.0, 0.0, 3.0, 2.0],
                       [2.0, 2.0, 2.4, 2.4]], np.float32)
    bidx = np.asarray([0, 1, 0, 1, 0], np.int64)
    ctm = b"half_pixel" if half_pixel else b"output_half_pixel"
    got = np.asarray(run_op("RoiAlign", [x, rois, bidx], output_height=4,
                            output_width=3, sampling_ratio=2,
                            spatial_scale=1.0, mode=mode.encode(),
                            coordinate_transformation_mode=ctm))
    want = roi_align_oracle(x, rois, bidx, 4, 3, 2, 1.0, mode=mode,
                            half_pixel=half_pixel)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# opset-18 tail vs oracles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("align", [False, True])
def test_affine_grid_matches_torch(align):
    torch.manual_seed(13)
    theta = torch.randn(2, 2, 3)
    want = F.affine_grid(theta, (2, 3, 5, 7), align_corners=align).numpy()
    got = np.asarray(run_op("AffineGrid",
                            [theta.numpy(), np.asarray([2, 3, 5, 7])],
                            align_corners=int(align)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # 3D volumetric grids too
    theta3 = torch.randn(1, 3, 4)
    want3 = F.affine_grid(theta3, (1, 2, 3, 4, 5),
                          align_corners=align).numpy()
    got3 = np.asarray(run_op("AffineGrid",
                             [theta3.numpy(), np.asarray([1, 2, 3, 4, 5])],
                             align_corners=int(align)))
    np.testing.assert_allclose(got3, want3, rtol=1e-5, atol=1e-6)


def test_roi_align_max_is_weighted_corner_max():
    # constant image, sample centered in a cell (all corner weights 0.25):
    # ORT max mode yields 0.25 * value, NOT the interpolated value
    x = np.full((1, 1, 6, 6), 4.0, np.float32)
    rois = np.asarray([[1.0, 1.0, 3.0, 3.0]], np.float32)
    got = np.asarray(run_op("RoiAlign", [x, rois, np.asarray([0])],
                            output_height=1, output_width=1,
                            sampling_ratio=1, spatial_scale=1.0,
                            mode=b"max",
                            coordinate_transformation_mode=b"half_pixel"))
    np.testing.assert_allclose(got, [[[[1.0]]]], rtol=1e-6)


def test_grid_sample_size_one_dim_reflection():
    # H=1 with align_corners reflection: the reflect span is 0 — must return
    # the single row, never NaN
    x = np.arange(5, dtype=np.float32).reshape(1, 1, 1, 5)
    grid = np.stack(np.meshgrid(np.linspace(-1.2, 1.2, 4),
                                np.asarray([0.3])), axis=-1)[None].astype(
        np.float32)
    got = np.asarray(run_op("GridSample", [x, grid], mode=b"bilinear",
                            padding_mode=b"reflection", align_corners=1))
    assert np.all(np.isfinite(got)), got


def test_group_normalization_both_param_shapes():
    rs = np.random.default_rng(1)
    x = rs.normal(size=(2, 6, 4, 4)).astype(np.float32)
    G = 3
    # per-channel params (opset 21 / torch GroupNorm semantics)
    scale_c = rs.normal(size=6).astype(np.float32)
    bias_c = rs.normal(size=6).astype(np.float32)
    got = np.asarray(run_op("GroupNormalization", [x, scale_c, bias_c],
                            num_groups=G, epsilon=1e-5))
    want = F.group_norm(torch.from_numpy(x), G, torch.from_numpy(scale_c),
                        torch.from_numpy(bias_c), eps=1e-5).numpy()
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    # per-group params (opset-18 shape [num_groups]) = repeat to channels
    scale_g = rs.normal(size=G).astype(np.float32)
    bias_g = rs.normal(size=G).astype(np.float32)
    got_g = np.asarray(run_op("GroupNormalization", [x, scale_g, bias_g],
                              num_groups=G, epsilon=1e-5))
    want_g = F.group_norm(torch.from_numpy(x), G,
                          torch.from_numpy(np.repeat(scale_g, 2)),
                          torch.from_numpy(np.repeat(bias_g, 2)),
                          eps=1e-5).numpy()
    np.testing.assert_allclose(got_g, want_g, rtol=1e-4, atol=1e-5)


def test_mean_variance_normalization():
    rs = np.random.default_rng(2)
    x = rs.normal(loc=3.0, scale=2.0, size=(2, 3, 4, 5)).astype(np.float32)
    got = np.asarray(run_op("MeanVarianceNormalization", [x]))
    mean = x.mean(axis=(0, 2, 3), keepdims=True)
    std = x.std(axis=(0, 2, 3), keepdims=True)
    np.testing.assert_allclose(got, (x - mean) / (std + 1e-9), rtol=1e-4,
                               atol=1e-5)


def test_bitwise_family():
    rs = np.random.default_rng(3)
    a = rs.integers(0, 255, (4, 5)).astype(np.int32)
    b = rs.integers(0, 255, (4, 5)).astype(np.int32)
    np.testing.assert_array_equal(run_op("BitwiseAnd", [a, b]), a & b)
    np.testing.assert_array_equal(run_op("BitwiseOr", [a, b]), a | b)
    np.testing.assert_array_equal(run_op("BitwiseXor", [a, b]), a ^ b)
    np.testing.assert_array_equal(run_op("BitwiseNot", [a]), ~a)


def test_dft_matches_numpy():
    rs = np.random.default_rng(12)
    x = rs.normal(size=(2, 16, 1)).astype(np.float32)
    # forward full FFT along axis 1
    got = np.asarray(run_op("DFT", [x], axis=1))
    want = np.fft.fft(x[..., 0], axis=1)
    np.testing.assert_allclose(got[..., 0] + 1j * got[..., 1], want,
                               rtol=1e-4, atol=1e-4)
    # onesided on real input
    got1 = np.asarray(run_op("DFT", [x], axis=1, onesided=1))
    want1 = np.fft.rfft(x[..., 0], axis=1)
    np.testing.assert_allclose(got1[..., 0] + 1j * got1[..., 1], want1,
                               rtol=1e-4, atol=1e-4)
    # inverse on complex input round-trips
    xc = np.stack([want.real, want.imag], axis=-1).astype(np.float32)
    back = np.asarray(run_op("DFT", [xc], axis=1, inverse=1))
    np.testing.assert_allclose(back[..., 0], x[..., 0], rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(back[..., 1], 0.0, atol=1e-4)
    # dft_length pads the axis
    got_pad = np.asarray(run_op("DFT", [x, np.asarray(32, np.int64)], axis=1))
    want_pad = np.fft.fft(np.pad(x[..., 0], ((0, 0), (0, 16))), axis=1)
    np.testing.assert_allclose(got_pad[..., 0] + 1j * got_pad[..., 1],
                               want_pad, rtol=1e-4, atol=1e-4)
    # negative axis counts against the FULL rank (component dim included):
    # axis=-2 on [B, T, 1] is the T axis
    got_neg = np.asarray(run_op("DFT", [x], axis=-2))
    np.testing.assert_allclose(got_neg, got, rtol=1e-6)
    # the component dim itself is not a transform axis; complex+onesided
    # is rejected like ORT
    with pytest.raises(NotImplementedError, match="component"):
        run_op("DFT", [x], axis=2)
    xc2 = np.stack([x[..., 0], x[..., 0]], axis=-1)
    with pytest.raises(NotImplementedError, match="onesided"):
        run_op("DFT", [xc2], axis=1, onesided=1)


def test_stft_matches_torch():
    torch.manual_seed(5)
    B, L, n_fft, hop = 2, 64, 16, 4
    sig = torch.randn(B, L)
    win = torch.hann_window(n_fft)
    want = torch.stft(sig, n_fft=n_fft, hop_length=hop, win_length=n_fft,
                      window=win, center=False, onesided=True,
                      return_complex=True)
    got = np.asarray(run_op("STFT", [sig.numpy(),
                                     np.asarray(hop, np.int64),
                                     win.numpy()], onesided=1))
    # ONNX layout [B, frames, bins, 2]; torch returns [B, bins, frames]
    got_c = got[..., 0] + 1j * got[..., 1]
    np.testing.assert_allclose(got_c.transpose(0, 2, 1), want.numpy(),
                               rtol=1e-4, atol=1e-4)
    # 3D real-input layout [B, L, 1] is the spec's canonical signal shape
    got3 = np.asarray(run_op("STFT", [sig.numpy()[..., None],
                                      np.asarray(hop, np.int64),
                                      win.numpy()], onesided=1))
    np.testing.assert_allclose(got3, got, rtol=1e-6)


def test_stft_complex_input():
    # complex [B, L, 2] layout with onesided=0: full FFT of the COMPLEX
    # signal, never the FFT of just the real part; onesided=1 on complex
    # input is rejected like ORT does
    torch.manual_seed(7)
    B, L, n_fft, hop = 1, 32, 8, 4
    sig_c = torch.randn(B, L, dtype=torch.complex64)
    win = torch.hann_window(n_fft)
    want = torch.stft(sig_c, n_fft=n_fft, hop_length=hop, win_length=n_fft,
                      window=win, center=False, onesided=False,
                      return_complex=True)
    sig_ri = np.stack([sig_c.real.numpy(), sig_c.imag.numpy()], axis=-1)
    got = np.asarray(run_op("STFT", [sig_ri, np.asarray(hop, np.int64),
                                     win.numpy()], onesided=0))
    got_c = got[..., 0] + 1j * got[..., 1]
    np.testing.assert_allclose(got_c.transpose(0, 2, 1), want.numpy(),
                               rtol=1e-4, atol=1e-4)
    with pytest.raises(NotImplementedError, match="onesided"):
        run_op("STFT", [sig_ri, np.asarray(hop, np.int64), win.numpy()],
               onesided=1)


def test_col2im_inverts_unfold():
    # fold(unfold(x)) multiplies each pixel by its patch coverage count —
    # the torch F.fold oracle, including stride/padding/dilation
    torch.manual_seed(6)
    x = torch.randn(2, 3, 8, 10)
    for kw_args in (dict(kernel_size=(3, 3), stride=(2, 2), padding=(1, 1),
                         dilation=(1, 1)),
                    dict(kernel_size=(2, 4), stride=(1, 2), padding=(0, 1),
                         dilation=(2, 1))):
        cols = F.unfold(x, **kw_args)
        want = F.fold(cols, output_size=(8, 10), **kw_args).numpy()
        k = kw_args["kernel_size"]
        p = kw_args["padding"]
        got = np.asarray(run_op(
            "Col2Im",
            [cols.numpy(), np.asarray([8, 10]), np.asarray(k)],
            strides=list(kw_args["stride"]),
            dilations=list(kw_args["dilation"]),
            pads=[p[0], p[1], p[0], p[1]]))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_random_sampling_family():
    # deterministic under a seed; statistics match the declared law
    a = np.asarray(run_op("RandomNormal", [], shape=[2000],
                          mean=3.0, scale=2.0, seed=1.0))
    b = np.asarray(run_op("RandomNormal", [], shape=[2000],
                          mean=3.0, scale=2.0, seed=1.0))
    np.testing.assert_array_equal(a, b)  # same seed, same draw
    assert abs(a.mean() - 3.0) < 0.2 and abs(a.std() - 2.0) < 0.2

    u = np.asarray(run_op("RandomUniform", [], shape=[2000],
                          low=-1.0, high=5.0, seed=2.0))
    assert u.min() >= -1.0 and u.max() <= 5.0
    assert abs(u.mean() - 2.0) < 0.3

    like = np.asarray(run_op("RandomNormalLike",
                             [np.zeros((3, 4), np.float32)], seed=3.0))
    assert like.shape == (3, 4) and like.dtype == np.float32

    p = np.full((4000,), 0.3, np.float32)
    bern = np.asarray(run_op("Bernoulli", [p], seed=4.0))
    assert set(np.unique(bern)) <= {0.0, 1.0}
    assert abs(bern.mean() - 0.3) < 0.05
    bern_bool = np.asarray(run_op("Bernoulli", [p], seed=4.0, dtype=9))
    assert bern_bool.dtype == np.bool_  # spec dtype=9 (bool) honored

    # two UNSEEDED nodes must draw independently (ORT draws per node)
    u1 = np.asarray(run_op("RandomNormalLike", [np.zeros((64,), np.float32)]))
    u2 = np.asarray(run_op("RandomNormalLike", [np.zeros((64,), np.float32)]))
    assert not np.array_equal(u1, u2)

    # multinomial: heavily peaked logits pick the peak class almost always
    logits = np.log(np.asarray([[0.01, 0.98, 0.01],
                                [0.98, 0.01, 0.01]], np.float32))
    m = np.asarray(run_op("Multinomial", [logits], sample_size=200, seed=5.0))
    assert m.shape == (2, 200) and m.dtype == np.int32
    assert (m[0] == 1).mean() > 0.9 and (m[1] == 0).mean() > 0.9


def test_center_crop_pad():
    rs = np.random.default_rng(4)
    x = rs.normal(size=(3, 8, 5)).astype(np.float32)
    # crop dim 1 (8 -> 4, center), pad dim 2 (5 -> 9, center)
    got = np.asarray(run_op("CenterCropPad", [x, np.asarray([4, 9])],
                            axes=[1, 2]))
    assert got.shape == (3, 4, 9)
    np.testing.assert_allclose(got[:, :, 2:7], x[:, 2:6, :])
    assert np.all(got[:, :, :2] == 0) and np.all(got[:, :, 7:] == 0)
    # all-axes form with odd crop: extra element comes off the end
    got2 = np.asarray(run_op("CenterCropPad", [x, np.asarray([3, 3, 3])]))
    np.testing.assert_allclose(got2, x[:, 2:5, 1:4])
