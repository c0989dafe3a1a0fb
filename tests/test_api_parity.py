"""API-parity batch tests: ops added to close the reference __all__ audit
(root / nn / nn.functional / sparse). Numeric ground truth is torch (CPU)
where available — the same oracle the reference tests use for new kernels."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.nn import functional as F

torch = pytest.importorskip("torch")
import torch.nn.functional as TF  # noqa: E402


def _t(x):
    return paddle.to_tensor(x)


@pytest.mark.needs_reference
def test_root_surface_complete():
    import ast

    tree = ast.parse(open("/root/reference/python/paddle/__init__.py").read())
    names = None
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                if getattr(tgt, "id", None) == "__all__":
                    names = [e.value for e in node.value.elts
                             if isinstance(e, ast.Constant)]
    missing = [n for n in names if not hasattr(paddle, n)]
    assert missing == [], missing


def test_math_parity_ops():
    rng = np.random.RandomState(0)
    x = rng.rand(3, 4).astype(np.float32)
    np.testing.assert_allclose(np.asarray(paddle.trace(_t(x))._value),
                               np.trace(x), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(paddle.lgamma(_t(x))._value),
                               torch.lgamma(torch.tensor(x)).numpy(), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(paddle.digamma(_t(x))._value),
                               torch.digamma(torch.tensor(x)).numpy(), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(paddle.erfinv(_t(x * 0.9))._value),
                               torch.erfinv(torch.tensor(x * 0.9)).numpy(),
                               rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(paddle.quantile(_t(x), 0.3, axis=1)._value),
        np.quantile(x, 0.3, axis=1), rtol=1e-5)
    a = rng.randint(1, 50, (10,))
    b = rng.randint(1, 50, (10,))
    np.testing.assert_array_equal(np.asarray(paddle.gcd(_t(a), _t(b))._value),
                                  np.gcd(a, b))
    m = rng.rand(3, 5).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(paddle.addmm(_t(x @ np.zeros((4, 5), np.float32)), _t(x),
                                _t(rng.rand(4, 5).astype(np.float32)),
                                beta=0.5, alpha=2.0)._value).shape, (3, 5))
    del m


def test_renorm_caps_subtensor_norms():
    rng = np.random.RandomState(1)
    x = rng.randn(4, 6).astype(np.float32) * 10
    out = np.asarray(paddle.renorm(_t(x), p=2.0, axis=0, max_norm=1.0)._value)
    norms = np.linalg.norm(out, axis=1)
    assert (norms <= 1.0 + 1e-4).all()


def test_manipulation_parity_ops():
    rng = np.random.RandomState(2)
    x = rng.randn(3, 4, 5).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(paddle.diagonal(_t(x), offset=1, axis1=1, axis2=2)._value),
        np.diagonal(x, offset=1, axis1=1, axis2=2))
    outs = paddle.broadcast_tensors([_t(np.ones((1, 4))), _t(np.ones((3, 1)))])
    assert [list(o.shape) for o in outs] == [[3, 4], [3, 4]]
    u, inv, cnt = paddle.unique_consecutive(
        _t(np.array([1, 1, 2, 2, 2, 3, 1])), return_inverse=True,
        return_counts=True)
    np.testing.assert_array_equal(np.asarray(u._value), [1, 2, 3, 1])
    np.testing.assert_array_equal(np.asarray(cnt._value), [2, 3, 1, 1])
    # shard_index maps global ids into the shard or ignore_value
    out = paddle.shard_index(_t(np.array([1, 5, 9])), index_num=12, nshards=3,
                             shard_id=1)
    np.testing.assert_array_equal(np.asarray(out._value), [-1, 1, -1])
    # scatter_nd accumulates duplicates
    out = paddle.scatter_nd(_t(np.array([[1], [1], [3]])),
                            _t(np.array([1.0, 2.0, 4.0], np.float32)), [5])
    np.testing.assert_allclose(np.asarray(out._value), [0, 3, 0, 4, 0])


def test_pool3d_and_unpool_match_torch():
    rng = np.random.RandomState(3)
    x = rng.randn(2, 3, 8, 8, 8).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(F.max_pool3d(_t(x), 2)._value),
        TF.max_pool3d(torch.tensor(x), 2).numpy())
    np.testing.assert_allclose(
        np.asarray(F.avg_pool3d(_t(x), 2)._value),
        TF.avg_pool3d(torch.tensor(x), 2).numpy(), rtol=1e-5, atol=1e-6)
    x2 = rng.randn(2, 3, 8, 8).astype(np.float32)
    out, idx = F.max_pool2d(_t(x2), 2, return_mask=True)
    t_out, t_idx = TF.max_pool2d(torch.tensor(x2), 2, return_indices=True)
    np.testing.assert_allclose(np.asarray(out._value), t_out.numpy())
    np.testing.assert_array_equal(np.asarray(idx._value), t_idx.numpy())
    un = F.max_unpool2d(out, idx, 2)
    np.testing.assert_allclose(np.asarray(un._value),
                               TF.max_unpool2d(t_out, t_idx, 2).numpy())


def test_conv_transpose_1d_3d_match_torch():
    rng = np.random.RandomState(4)
    x = rng.randn(2, 4, 9).astype(np.float32)
    w = rng.randn(4, 3, 5).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(F.conv1d_transpose(_t(x), _t(w), stride=2, padding=1)._value),
        torch.conv_transpose1d(torch.tensor(x), torch.tensor(w), stride=2,
                               padding=1).numpy(), rtol=2e-4, atol=1e-4)
    x3 = rng.randn(1, 4, 5, 6, 7).astype(np.float32)
    w3 = rng.randn(4, 2, 3, 3, 3).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(F.conv3d_transpose(_t(x3), _t(w3), stride=2, padding=1)._value),
        torch.conv_transpose3d(torch.tensor(x3), torch.tensor(w3), stride=2,
                               padding=1).numpy(), rtol=2e-4, atol=1e-4)


@pytest.mark.slow  # re-tiered 2026-08 (PR 8): tier-1 crossed its 870 s budget on the 1-core box; --durations top mover
def test_ctc_loss_matches_torch_fwd_and_grad():
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(5)
    T, B, C, L = 12, 3, 6, 4
    logits = rng.randn(T, B, C).astype(np.float32)
    labels = rng.randint(1, C, (B, L)).astype(np.int32)
    in_len = np.array([12, 10, 8], np.int32)
    lab_len = np.array([4, 3, 2], np.int32)
    mine = F.ctc_loss(_t(logits), _t(labels), _t(in_len), _t(lab_len),
                      blank=0, reduction="none")
    ref = TF.ctc_loss(torch.log_softmax(torch.tensor(logits), -1),
                      torch.tensor(labels.astype(np.int64)),
                      torch.tensor(in_len.astype(np.int64)),
                      torch.tensor(lab_len.astype(np.int64)),
                      blank=0, reduction="none")
    np.testing.assert_allclose(np.asarray(mine._value), ref.numpy(), rtol=1e-4)

    g = jax.grad(lambda lg: F.ctc_loss(
        Tensor(lg), _t(labels), _t(in_len), _t(lab_len),
        reduction="mean")._value)(jnp.asarray(logits))
    tt = torch.tensor(logits, requires_grad=True)
    TF.ctc_loss(torch.log_softmax(tt, -1),
                torch.tensor(labels.astype(np.int64)),
                torch.tensor(in_len.astype(np.int64)),
                torch.tensor(lab_len.astype(np.int64)),
                blank=0, reduction="mean").backward()
    np.testing.assert_allclose(np.asarray(g), tt.grad.numpy(), rtol=1e-3,
                               atol=1e-5)


def test_affine_grid_and_shuffles_match_torch():
    rng = np.random.RandomState(6)
    theta = rng.randn(2, 2, 3).astype(np.float32)
    for ac in (True, False):
        np.testing.assert_allclose(
            np.asarray(F.affine_grid(_t(theta), [2, 3, 5, 7],
                                     align_corners=ac)._value),
            TF.affine_grid(torch.tensor(theta), [2, 3, 5, 7],
                           align_corners=ac).numpy(), rtol=1e-4, atol=1e-5)
    x = rng.randn(1, 4, 6, 6).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(F.pixel_unshuffle(_t(x), 2)._value),
        TF.pixel_unshuffle(torch.tensor(x), 2).numpy())
    np.testing.assert_allclose(
        np.asarray(F.channel_shuffle(_t(x), 2)._value),
        TF.channel_shuffle(torch.tensor(x), 2).numpy())
    cols = rng.randn(2, 3 * 4, 9).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(F.fold(_t(cols), (4, 4), (2, 2))._value),
        TF.fold(torch.tensor(cols), (4, 4), (2, 2)).numpy(), rtol=1e-5)


def test_small_losses():
    rng = np.random.RandomState(7)
    p = rng.rand(4, 1).astype(np.float32)
    y = (rng.rand(4, 1) > 0.5).astype(np.float32)
    ll = np.asarray(F.log_loss(_t(p), _t(y))._value)
    assert ll.shape == (4, 1) and (ll >= 0).all()

    z = rng.randn(5, 3).astype(np.float32)
    t = (rng.rand(5, 3) > 0.5).astype(np.float32)
    mine = float(np.asarray(F.sigmoid_focal_loss(_t(z), _t(t),
                                                 reduction="sum")._value))
    # torch's sigmoid_focal_loss lives in torchvision; verify against a
    # hand-rolled reference instead
    pt = 1 / (1 + np.exp(-z))
    ce = -(t * np.log(pt) + (1 - t) * np.log(1 - pt))
    ptt = pt * t + (1 - pt) * (1 - t)
    at = 0.25 * t + 0.75 * (1 - t)
    ref = (at * (1 - ptt) ** 2 * ce).sum()
    np.testing.assert_allclose(mine, ref, rtol=1e-4)

    x = rng.randn(4, 8).astype(np.float32)
    lab = rng.randint(0, 6, (4,)).astype(np.int64)
    hs = nn.HSigmoidLoss(8, 6)
    out = hs(_t(x), _t(lab))
    assert list(out.shape) == [4, 1]
    assert np.isfinite(np.asarray(out._value)).all()

    d = nn.PairwiseDistance(p=2.0)
    a, b = rng.randn(3, 5).astype(np.float32), rng.randn(3, 5).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(d(_t(a), _t(b))._value),
        torch.pairwise_distance(torch.tensor(a), torch.tensor(b)).numpy(),
        rtol=1e-4)


def test_margin_cross_entropy_reduces_to_ce_without_margin():
    rng = np.random.RandomState(8)
    cos = np.clip(rng.randn(4, 10).astype(np.float32) * 0.3, -1, 1)
    y = rng.randint(0, 10, (4,)).astype(np.int64)
    loss = F.margin_cross_entropy(_t(cos), _t(y), margin1=1.0, margin2=0.0,
                                  margin3=0.0, scale=1.0, reduction="mean")
    ref = TF.cross_entropy(torch.tensor(cos), torch.tensor(y)).numpy()
    np.testing.assert_allclose(float(np.asarray(loss._value)), ref, rtol=1e-5)


def test_class_center_sample():
    y = _t(np.array([3, 7, 3, 1], np.int64))
    remapped, sampled = F.class_center_sample(y, num_classes=20, num_samples=8)
    s = np.asarray(sampled._value)
    r = np.asarray(remapped._value)
    assert len(s) == 8 and set([1, 3, 7]) <= set(s.tolist())
    np.testing.assert_array_equal(s[r], [3, 7, 3, 1])


def test_rnn_family():
    paddle.seed(0)
    cell = nn.SimpleRNNCell(4, 6)
    rnn = nn.RNN(cell)
    x = _t(np.random.RandomState(9).randn(2, 5, 4).astype(np.float32))
    y, h = rnn(x)
    assert list(y.shape) == [2, 5, 6] and list(h.shape) == [2, 6]
    bi = nn.BiRNN(nn.GRUCell(4, 6), nn.GRUCell(4, 6))
    yb, (hf, hb) = bi(x)
    assert list(yb.shape) == [2, 5, 12]
    # masked outputs past sequence_length are zero
    y2, _ = rnn(x, sequence_length=_t(np.array([3, 5])))
    assert np.allclose(np.asarray(y2._value)[0, 3:], 0)
    assert not np.allclose(np.asarray(y2._value)[1, 4], 0)


def test_layers_wrap_functionals():
    rng = np.random.RandomState(10)
    x3 = _t(rng.randn(1, 2, 4, 4, 4).astype(np.float32))
    assert list(nn.MaxPool3D(2)(x3).shape) == [1, 2, 2, 2, 2]
    assert list(nn.AvgPool3D(2)(x3).shape) == [1, 2, 2, 2, 2]
    assert list(nn.AdaptiveAvgPool3D(2)(x3).shape) == [1, 2, 2, 2, 2]
    assert list(nn.AdaptiveMaxPool3D(2)(x3).shape) == [1, 2, 2, 2, 2]
    x1 = _t(rng.randn(1, 2, 9).astype(np.float32))
    assert list(nn.AdaptiveMaxPool1D(3)(x1).shape) == [1, 2, 3]
    assert list(nn.Conv1DTranspose(2, 3, 3)(x1).shape)[1] == 3
    assert list(nn.Conv3DTranspose(2, 3, 3)(x3).shape)[1] == 3
    x = _t(rng.randn(1, 4, 6, 6).astype(np.float32))
    assert list(nn.ChannelShuffle(2)(x).shape) == [1, 4, 6, 6]
    assert list(nn.PixelUnshuffle(2)(x).shape) == [1, 16, 3, 3]
    assert list(nn.ZeroPad2D([1, 2, 3, 4])(x).shape) == [1, 4, 13, 9]
    assert list(nn.Softmax2D()(x).shape) == [1, 4, 6, 6]
    out = nn.ThresholdedReLU(0.5)(x)
    v = np.asarray(out._value)
    assert ((v == 0) | (v > 0.5)).all()


@pytest.mark.slow
def test_sparse_layers():
    import paddle_tpu.sparse as sp

    d = np.zeros((1, 4, 4, 4, 2), np.float32)
    d[0, 1, 1, 1] = [1.0, -2.0]
    d[0, 2, 3, 0] = [3.0, 4.0]
    idx = np.stack(np.nonzero(d))
    x = sp.sparse_coo_tensor(idx, d[np.nonzero(d)], d.shape)
    y = sp.SubmConv3D(2, 5, 3)(x)
    assert y.shape == [1, 4, 4, 4, 5]
    # submanifold: support restricted to input sites (2 sites x 5 channels max)
    assert y.nnz() <= 10
    z = sp.MaxPool3D(2)(x)
    assert z.shape == [1, 2, 2, 2, 2]
    w = sp.BatchNorm(2)(x)
    assert w.nnz() == 4
    assert np.isfinite(np.asarray(w.values().numpy())).all()


def test_flops_counts_conv_and_linear():
    net = nn.Sequential(nn.Conv2D(3, 8, 3, padding=1), nn.ReLU(),
                        nn.Flatten(), nn.Linear(8 * 32 * 32, 10))
    n = paddle.flops(net, [1, 3, 32, 32])
    # reference convention: MACs without doubling for conv/linear
    # (dynamic_flops.py count_convNd/count_linear), elementwise for ReLU
    expected = 8 * 32 * 32 * 27 + 8 * 32 * 32 + 8192 * 10
    assert n == expected, (n, expected)


def test_conv2d_transpose_output_padding_matches_torch():
    rng = np.random.RandomState(11)
    x = rng.randn(2, 4, 7, 7).astype(np.float32)
    w = rng.randn(4, 3, 3, 3).astype(np.float32)
    for s, p, op in [(2, 1, 0), (2, 1, 1), (3, 0, 2)]:
        mine = np.asarray(F.conv2d_transpose(
            _t(x), _t(w), stride=s, padding=p, output_padding=op)._value)
        ref = torch.conv_transpose2d(torch.tensor(x), torch.tensor(w),
                                     stride=s, padding=p,
                                     output_padding=op).numpy()
        assert mine.shape == ref.shape
        np.testing.assert_allclose(mine, ref, rtol=2e-4, atol=1e-4)


def test_adaptive_max_pool_return_mask_matches_torch():
    rng = np.random.RandomState(12)
    xa = rng.randn(2, 3, 10).astype(np.float32)
    o, i = F.adaptive_max_pool1d(_t(xa), 4, return_mask=True)
    to, ti = TF.adaptive_max_pool1d(torch.tensor(xa), 4, return_indices=True)
    np.testing.assert_allclose(np.asarray(o._value), to.numpy())
    np.testing.assert_array_equal(np.asarray(i._value), ti.numpy())
    x3 = rng.randn(1, 2, 6, 6, 6).astype(np.float32)
    o3, i3 = F.adaptive_max_pool3d(_t(x3), 3, return_mask=True)
    to3, ti3 = TF.adaptive_max_pool3d(torch.tensor(x3), 3, return_indices=True)
    np.testing.assert_allclose(np.asarray(o3._value), to3.numpy())
    np.testing.assert_array_equal(np.asarray(i3._value), ti3.numpy())


def test_reverse_rnn_masks_padded_steps():
    """Backward RNN over a padded batch must equal a per-row reverse over
    each row's valid prefix (pad steps must not pollute state)."""
    paddle.seed(13)
    cell = nn.SimpleRNNCell(3, 5)
    r = nn.RNN(cell, is_reverse=True)
    rng = np.random.RandomState(13)
    xx = rng.randn(2, 4, 3).astype(np.float32)
    y, st = r(_t(xx), sequence_length=_t(np.array([2, 4])))
    y_ref, st_ref = r(_t(xx[0:1, :2]))
    np.testing.assert_allclose(np.asarray(y._value)[0, :2],
                               np.asarray(y_ref._value)[0], rtol=1e-5)
    np.testing.assert_allclose(np.asarray(st._value)[0],
                               np.asarray(st_ref._value)[0], rtol=1e-5)
    assert np.allclose(np.asarray(y._value)[0, 2:], 0)


def test_pool_mask_grad_flows_through_values():
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(14)
    x = rng.randn(1, 1, 4, 4).astype(np.float32)

    def loss(a):
        out, idx = F.max_pool2d(Tensor(a), 2, return_mask=True)
        return jnp.sum(out._value ** 2)

    g = jax.grad(loss)(jnp.asarray(x))
    # gradient lands exactly on the 4 window maxima
    assert int((np.asarray(g) != 0).sum()) == 4


def test_inplace_ops_mutate():
    t = _t(np.array([0.5], np.float32))
    r = paddle.tanh_(t)
    assert r is t
    np.testing.assert_allclose(t.numpy(), np.tanh(0.5), rtol=1e-6)


def test_inplace_ops_have_correct_gradients():
    """Regression: in-place ops must graft the op's autograd node, not just
    rebind the buffer (which silently made them identity in backward)."""
    import paddle_tpu.tensor_ops.math as M

    x = _t(np.array([1., 4.], np.float32))
    x.stop_gradient = False
    paddle.sqrt_(x)
    paddle.exp_(x)
    x.sum().backward()
    ref = np.exp(np.sqrt([1., 4.])) * 0.5 / np.sqrt([1., 4.])
    np.testing.assert_allclose(np.asarray(x.grad._value), ref, rtol=1e-5)

    a = _t(np.array([1., 2.], np.float32))
    a.stop_gradient = False
    b = _t(np.array([3., 4.], np.float32))
    b.stop_gradient = False
    c = a * 2
    M.add_(c, b)
    c.sum().backward()
    np.testing.assert_allclose(np.asarray(a.grad._value), [2., 2.])
    np.testing.assert_allclose(np.asarray(b.grad._value), [1., 1.])

    w = _t(np.array([0.5], np.float32))
    w.stop_gradient = False
    h = w * 3
    paddle.tanh_(h)
    (h * 5).backward()
    ref = 5 * (1 - np.tanh(1.5) ** 2) * 3
    np.testing.assert_allclose(np.asarray(w.grad._value), [ref], rtol=1e-5)


def test_lu_unpack_batched_and_flags():
    rng = np.random.RandomState(15)
    x = _t(rng.randn(3, 4, 4).astype(np.float32) + 4 * np.eye(4, dtype=np.float32))
    lu_d, piv = paddle.lu(x)
    P, L, U = paddle.linalg.lu_unpack(lu_d, piv)
    rec = np.asarray(P._value) @ np.asarray(L._value) @ np.asarray(U._value)
    np.testing.assert_allclose(rec, np.asarray(x._value), rtol=1e-4, atol=1e-5)
    P2, _, _ = paddle.linalg.lu_unpack(lu_d, piv, unpack_pivots=False)
    assert P2 is None
    P3, L3, _ = paddle.linalg.lu_unpack(lu_d, piv, unpack_ludata=False)
    assert L3 is None and P3 is not None
