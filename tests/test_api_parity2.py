"""Second parity batch: vision transforms, incubate ops/optimizers, device,
distribution registry, io worker info, fleet/distributed exports."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor


@pytest.mark.needs_reference
def test_submodule_surfaces_complete():
    import ast
    import os

    def get_all(path):
        tree = ast.parse(open(path).read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if getattr(t, "id", None) == "__all__":
                        return [e.value for e in node.value.elts
                                if isinstance(e, ast.Constant)]

    ref = "/root/reference/python/paddle/"
    for sub, mp in [("vision.transforms", "vision/transforms/__init__.py"),
                    ("vision.models", "vision/models/__init__.py"),
                    ("optimizer.lr", "optimizer/lr.py"),
                    ("io", "io/__init__.py"),
                    ("distribution", "distribution/__init__.py"),
                    ("jit", "jit/__init__.py"),
                    ("distributed", "distributed/__init__.py"),
                    ("distributed.fleet", "distributed/fleet/__init__.py"),
                    ("utils", "utils/__init__.py"),
                    ("incubate", "incubate/__init__.py"),
                    ("device", "device/__init__.py")]:
        names = get_all(os.path.join(ref, mp))
        mod = paddle_tpu
        for part in sub.split("."):
            mod = getattr(mod, part)
        missing = sorted(n for n in names if not hasattr(mod, n))
        assert missing == [], (sub, missing)


import paddle_tpu  # noqa: E402


def test_segment_ops_match_manual():
    from paddle_tpu import incubate

    data = Tensor(np.array([[1., 2.], [3., 4.], [5., 6.], [7., 8.]], np.float32))
    ids = Tensor(np.array([0, 0, 1, 1]))
    np.testing.assert_allclose(
        np.asarray(incubate.segment_sum(data, ids)._value), [[4, 6], [12, 14]])
    np.testing.assert_allclose(
        np.asarray(incubate.segment_mean(data, ids)._value), [[2, 3], [6, 7]])
    np.testing.assert_allclose(
        np.asarray(incubate.segment_max(data, ids)._value), [[3, 4], [7, 8]])
    np.testing.assert_allclose(
        np.asarray(incubate.segment_min(data, ids)._value), [[1, 2], [5, 6]])
    # empty segment -> 0 (reference convention), not -inf
    ids2 = Tensor(np.array([0, 0, 2, 2]))
    out = np.asarray(incubate.segment_max(data, ids2)._value)
    np.testing.assert_allclose(out[1], [0, 0])


def test_graph_send_recv():
    from paddle_tpu import incubate

    x = Tensor(np.array([[1.], [2.], [4.]], np.float32))
    src = Tensor(np.array([0, 1, 2, 0]))
    dst = Tensor(np.array([1, 2, 1, 0]))
    out = np.asarray(incubate.graph_send_recv(x, src, dst, "sum")._value)
    np.testing.assert_allclose(out, [[1.], [5.], [2.]])
    out = np.asarray(incubate.graph_send_recv(x, src, dst, "mean")._value)
    np.testing.assert_allclose(out, [[1.], [2.5], [2.]])


def test_softmax_mask_fuse_upper_triangle_is_causal():
    from paddle_tpu import incubate

    x = Tensor(np.zeros((1, 1, 4, 4), np.float32))
    out = np.asarray(incubate.softmax_mask_fuse_upper_triangle(x)._value)
    np.testing.assert_allclose(out[0, 0, 0], [1, 0, 0, 0], atol=1e-6)
    np.testing.assert_allclose(out[0, 0, 3], [0.25] * 4, atol=1e-6)


def test_lookahead_and_model_average():
    from paddle_tpu import nn
    from paddle_tpu.incubate import LookAhead, ModelAverage

    paddle.seed(0)
    m = nn.Linear(4, 4)
    inner = paddle.optimizer.SGD(learning_rate=0.1, parameters=m.parameters())
    opt = LookAhead(inner, alpha=0.5, k=2)
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    w0 = np.asarray(m.weight._value).copy()
    for _ in range(4):
        loss = (m(x) * m(x)).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
    assert not np.allclose(np.asarray(m.weight._value), w0)

    ma = ModelAverage(0.5, parameters=m.parameters())
    w_pre = np.asarray(m.weight._value).copy()
    ma.step()
    ma.apply()
    w_avg = np.asarray(m.weight._value).copy()
    ma.restore()
    np.testing.assert_allclose(np.asarray(m.weight._value), w_pre)
    np.testing.assert_allclose(w_avg, w_pre, rtol=1e-5)  # 1-step avg == current


def test_device_module():
    from paddle_tpu import device

    assert device.is_compiled_with_cuda() is False
    assert device.get_cudnn_version() is None
    assert "cpu" in device.get_all_device_type()
    assert device.get_available_device()
    assert isinstance(device.XPUPlace(0), paddle.TPUPlace)


def test_vision_transform_classes_run():
    from paddle_tpu.vision import transforms as T

    img = (np.random.RandomState(0).rand(3, 16, 16) * 255).astype(np.uint8)
    pipeline = T.Compose([
        T.ColorJitter(0.2, 0.2, 0.2, 0.1), T.RandomRotation(15),
        T.RandomResizedCrop(8), T.Grayscale(3)])
    out = pipeline(img)
    assert out.shape == (3, 8, 8)
    np.testing.assert_array_equal(T.hflip(img), img[:, :, ::-1])
    np.testing.assert_array_equal(T.vflip(img), img[:, ::-1])
    assert T.center_crop(img, 8).shape == (3, 8, 8)
    assert T.pad(img, 2).shape == (3, 20, 20)


@pytest.mark.slow
def test_voc2012_and_vgg_variants():
    from paddle_tpu.vision.datasets import VOC2012
    from paddle_tpu.vision.models import vgg11, vgg13

    ds = VOC2012(synthetic_size=4)
    img, mask = ds[0]
    assert img.shape == (3, 64, 64) and mask.shape == (64, 64)
    assert mask.max() >= 1
    m = vgg11(num_classes=10)
    n_convs = sum(1 for lyr in m.sublayers()
                  if type(lyr).__name__ == "Conv2D")
    assert n_convs == 8  # VGG-A has 8 conv layers


def test_get_worker_info_inside_workers():
    from paddle_tpu.io import DataLoader, get_worker_info

    assert get_worker_info() is None  # main process

    class DS:
        def __len__(self):
            return 8

        def __getitem__(self, i):
            from paddle_tpu.io import get_worker_info as gwi

            info = gwi()
            assert info is not None and info.num_workers == 2
            return np.asarray([info.id], np.int64)

    dl = DataLoader(DS(), batch_size=2, num_workers=2, use_shared_memory=False)
    seen = [np.asarray(b[0] if isinstance(b, (list, tuple)) else b)
            for b in dl]
    assert len(seen) == 4


def test_program_translator_disables_to_static():
    from paddle_tpu import jit

    calls = []

    @jit.to_static
    def f(x):
        calls.append(1)
        return x * 2

    x = paddle.to_tensor(np.ones((2,), np.float32))
    jit.ProgramTranslator().enable(False)
    try:
        out = f(x)
        np.testing.assert_allclose(np.asarray(out._value), 2.0)
    finally:
        jit.ProgramTranslator().enable(True)


def test_distributed_split_and_entries():
    from paddle_tpu import distributed as dist

    x = paddle.to_tensor(np.ones((2, 8), np.float32))
    out = dist.split(x, (8, 4), operation="linear", axis=1)
    assert list(out.shape) == [2, 4]
    emb = dist.split(paddle.to_tensor(np.array([[1, 2]], np.int64)),
                     (16, 6), operation="embedding")
    assert list(emb.shape) == [1, 2, 6]
    assert "count_filter" in dist.CountFilterEntry(3).attr()
    assert dist.ParallelMode.TENSOR_PARALLEL == 1
    with pytest.raises(ValueError):
        dist.ProbabilityEntry(2.0)


def test_utils_helpers():
    from paddle_tpu import utils

    @utils.deprecated(update_to="paddle.new_api", since="2.0")
    def old():
        return 7

    with pytest.warns(DeprecationWarning):
        assert old() == 7
    utils.require_version("0.0.1")
    with pytest.raises(RuntimeError):
        utils.require_version("99.0.0")


def test_multiplicative_decay():
    from paddle_tpu.optimizer.lr import MultiplicativeDecay

    sched = MultiplicativeDecay(1.0, lambda e: 0.5)
    lrs = []
    for _ in range(3):
        lrs.append(sched())
        sched.step()
    assert lrs[0] == pytest.approx(1.0)
    assert lrs[1] == pytest.approx(0.5)
    assert lrs[2] == pytest.approx(0.25)
