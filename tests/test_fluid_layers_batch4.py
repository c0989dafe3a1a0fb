"""fluid.layers batch 4: decode family, distributions, legacy classes,
detection tail, selected-rows/LoD utilities (reference fluid/layers/*).
Full-name coverage gate at the bottom."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.fluid as fluid

L = fluid.layers


def _t(a):
    return paddle.to_tensor(np.asarray(a, "float32"))


@pytest.mark.slow
def test_basic_decoder_greedy_roundtrip():
    """GreedyEmbeddingHelper + BasicDecoder + dynamic_decode produce
    end-token-terminated sequences."""
    paddle.seed(3)
    vocab, d = 12, 8
    emb = paddle.nn.Embedding(vocab, d)
    cell = paddle.nn.GRUCell(d, d)
    proj = paddle.nn.Linear(d, vocab)
    helper = L.GreedyEmbeddingHelper(
        lambda ids: emb(ids), paddle.to_tensor(np.zeros(2, "int64")),
        end_token=1)
    decoder = L.BasicDecoder(cell, helper, output_fn=proj)
    init = paddle.to_tensor(np.zeros((2, d), "float32"))
    outputs, final_states, seq_len = L.dynamic_decode(
        decoder, inits=init, max_step_num=6, return_length=True)
    cell_out, sample_ids = outputs
    assert sample_ids.shape[0] == 2  # batch-major [B, T]
    assert cell_out.shape[-1] == vocab


def test_training_helper_teacher_forcing():
    d, vocab = 4, 7
    cell = paddle.nn.SimpleRNNCell(d, d)
    proj = paddle.nn.Linear(d, vocab)
    inputs = _t(np.random.rand(2, 5, d))
    helper = L.TrainingHelper(inputs, paddle.to_tensor(
        np.array([5, 3], "int64")))
    dec = L.BasicDecoder(cell, helper, output_fn=proj)
    outputs, _ = L.dynamic_decode(
        dec, inits=paddle.to_tensor(np.zeros((2, d), "float32")),
        max_step_num=5)
    assert outputs[0].shape[1] <= 5


def test_beam_search_step_and_decode():
    """beam_search top-k over beam*V and the gather_tree backtrace."""
    beam, v = 2, 5
    sc = _t(np.log([[0.1, 0.5, 0.2, 0.1, 0.1],
                    [0.3, 0.1, 0.4, 0.1, 0.1]]))  # batch=1, beam=2
    pre = _t(np.zeros((2, 1)))
    ids, scores, parents = L.beam_search(
        None, pre, None, sc, beam_size=beam, end_id=0,
        return_parent_idx=True)
    assert tuple(ids.shape) == (2, 1)
    # the global best candidate is token 1 from beam 0
    assert int(ids.numpy()[0, 0]) == 1
    step2_ids, step2_sc, step2_par = L.beam_search(
        None, scores, None, sc, beam_size=beam, end_id=0,
        return_parent_idx=True)
    seqs, out_sc = L.beam_search_decode(
        [(ids, parents), (step2_ids, step2_par)], [scores, step2_sc],
        beam_size=beam, end_id=0)
    assert tuple(seqs.shape) == (2, 2)  # [T, batch*beam]


def test_distribution_aliases():
    n = L.Normal(0.0, 1.0)
    assert float(n.entropy().numpy()) == pytest.approx(1.4189, rel=1e-3)
    u = L.Uniform(0.0, 2.0)
    assert float(u.sample([4]).numpy().max()) <= 2.0
    c = L.Categorical(_t([0.25, 0.25, 0.5]))
    assert c.sample([3]).shape[0] == 3
    mvn = L.MultivariateNormalDiag(_t([0.0, 0.0]),
                                   _t([[1.0, 0.0], [0.0, 1.0]]))
    ent = float(mvn.entropy().numpy())
    assert ent == pytest.approx(2 * 1.4189, rel=1e-3)
    kl = L.MultivariateNormalDiag(_t([1.0, 0.0]),
                                  _t([[1.0, 0.0], [0.0, 1.0]])).kl_divergence(mvn)
    assert float(kl.numpy()) == pytest.approx(0.5, rel=1e-3)


def test_misc_tail():
    assert float(L.identity_loss(_t([1.0, 3.0]), "mean").numpy()) == 2.0
    miou, wrong, correct = L.mean_iou(
        paddle.to_tensor(np.array([0, 1, 1], "int64")),
        paddle.to_tensor(np.array([0, 1, 0], "int64")), 2)
    assert 0 < float(miou.numpy()) < 1
    h = L.hash(paddle.to_tensor(np.array([[1, 2], [1, 2], [3, 4]], "int64")),
               hash_size=100)
    hv = h.numpy()
    assert hv[0, 0] == hv[1, 0] and hv[0, 0] != hv[2, 0]
    rc = L.random_crop(_t(np.random.rand(8, 8)), [4, 4], seed=1)
    assert tuple(rc.shape) == (4, 4)
    cvm = L.continuous_value_model(_t(np.random.rand(3, 6)), None,
                                   use_cvm=False)
    assert tuple(cvm.shape) == (3, 4)
    f = L.fill_constant_batch_size_like(_t(np.zeros((5, 2))), [1, 3],
                                        "float32", 7.0)
    assert tuple(f.shape) == (5, 3) and f.numpy()[0, 0] == 7.0


def test_selected_rows_and_lod_utils():
    from paddle_tpu.core.selected_rows import SelectedRows

    sr = SelectedRows(rows=[1, 1, 3], value=np.ones((3, 2), "float32"),
                      height=5)
    merged = L.merge_selected_rows(sr)
    assert list(merged.rows) == [1, 3]
    np.testing.assert_allclose(np.asarray(merged.value)[0], [2, 2])
    dense = L.get_tensor_from_selected_rows(merged)
    assert tuple(dense.shape) == (5, 2)
    np.testing.assert_allclose(dense.numpy()[1], [2, 2])

    lt = L.lod_reset(_t(np.random.rand(6, 2)), target_lod=[2, 4])
    assert lt.lod() == [[0, 2, 6]]
    # append a finer level: the old [2,2,2] level now counts inner seqs
    lt2 = L.lod_append(L.lod_reset(_t(np.random.rand(6, 2)),
                                   target_lod=[2, 2, 2]), [1] * 6)
    assert len(lt2.lod()) == 2


def test_sequence_scatter_and_spectral_norm():
    from paddle_tpu.core.ragged import LoDTensor

    x = _t(np.zeros((2, 5)))
    idx = LoDTensor(paddle.to_tensor(np.array([1, 3, 0], "int64")), [[2, 1]])
    upd = _t([10.0, 20.0, 30.0])
    out = L.sequence_scatter(x, idx, upd)
    np.testing.assert_allclose(out.numpy()[0], [0, 10, 0, 20, 0])
    np.testing.assert_allclose(out.numpy()[1], [30, 0, 0, 0, 0])

    w = _t(np.random.randn(4, 6))
    wn = L.spectral_norm(w, power_iters=20)
    s = np.linalg.svd(wn.numpy(), compute_uv=False)
    assert s[0] == pytest.approx(1.0, rel=1e-2)


def test_chunk_eval_iob():
    # IOB, 1 chunk type: tags B=0, I=1, O=-? use num types=1, n=2: B=0 I=1
    inf = paddle.to_tensor(np.array([0, 1, 0, 1, 1], "int64"))
    lab = paddle.to_tensor(np.array([0, 1, 0, 1, 1], "int64"))
    p, r, f1, n_inf, n_lab, n_cor = L.chunk_eval(inf, lab, "IOB", 1)
    assert float(f1.numpy()) == 1.0 and int(n_cor.numpy()) == 2


def test_detection_tail():
    # matrix_nms keeps the dominant box, soft-decays the overlapper
    boxes = _t([[0, 0, 10, 10], [1, 1, 11, 11], [20, 20, 30, 30]])
    scores = _t([[0.05, 0.02, 0.01], [0.9, 0.8, 0.7]])
    out, n = L.matrix_nms(boxes, scores, score_threshold=0.1,
                          post_threshold=0.05, nms_top_k=3, keep_top_k=5)
    assert int(n.numpy()[0]) >= 2
    # detection_output composes decode + nms without error
    pb = _t([[0.1, 0.1, 0.3, 0.3], [0.5, 0.5, 0.9, 0.9]])
    pbv = _t(np.ones((2, 4)) * 0.1)
    loc = _t(np.zeros((2, 4)))
    sc = _t([[0.1, 0.9], [0.8, 0.2]])  # [P, C]
    det = L.detection_output(loc, paddle.transpose(sc, [1, 0]), pb, pbv,
                             background_label=-1)
    assert det.shape[-1] == 6
    # target_assign gathers by match index
    out_t, w = L.target_assign(_t(np.arange(8).reshape(4, 2)),
                               paddle.to_tensor(
                                   np.array([[0, -1, 2]], "int64")),
                               mismatch_value=0)
    np.testing.assert_allclose(out_t.numpy()[0, 0], [0, 1])
    assert w.numpy()[0, 1, 0] == 0.0
    # density_prior_box shapes
    feat = paddle.to_tensor(np.zeros((1, 4, 2, 2), "float32"))
    img = paddle.to_tensor(np.zeros((1, 3, 32, 32), "float32"))
    db, dv = L.density_prior_box(feat, img, densities=[2],
                                 fixed_sizes=[8.0], fixed_ratios=[1.0])
    assert db.shape[2] == 4  # density^2 boxes per cell
    # psroi_pool: position-sensitive averaging
    x = _t(np.random.rand(1, 8, 8, 8))
    rois = _t([[0, 0, 8, 8]])
    ps = L.psroi_pool(x, rois, output_channels=2, spatial_scale=1.0,
                      pooled_height=2, pooled_width=2)
    assert tuple(ps.shape) == (1, 2, 2, 2)


@pytest.mark.slow
def test_ssd_and_yolo_losses_finite():
    paddle.seed(0)
    loc = _t(np.random.rand(4, 4) * 0.1)
    conf = _t(np.random.rand(4, 3))
    gt_box = _t([[0.1, 0.1, 0.4, 0.4], [0.5, 0.5, 0.9, 0.9]])
    gt_label = paddle.to_tensor(np.array([1, 2], "int64"))
    pb = _t([[0.1, 0.1, 0.4, 0.4], [0.5, 0.5, 0.9, 0.9],
             [0.0, 0.0, 0.2, 0.2], [0.7, 0.7, 1.0, 1.0]])
    loss = L.ssd_loss(loc, conf, gt_box, gt_label, pb,
                      background_label=0)
    assert np.isfinite(float(loss.numpy()))
    x = _t(np.random.rand(1, 3 * 7, 4, 4))  # 3 anchors, 2 classes: 5+2=7
    yl = L.yolov3_loss(x, _t([[[0.5, 0.5, 0.3, 0.3]]]),
                       paddle.to_tensor(np.array([[1]], "int64")),
                       anchors=[10, 13, 16, 30, 33, 23],
                       anchor_mask=[0, 1, 2], class_num=2,
                       ignore_thresh=0.7, downsample_ratio=32)
    assert np.isfinite(float(yl.numpy()))


def test_legacy_gates_are_loud():
    with pytest.raises(NotImplementedError, match="while_loop"):
        L.While(_t([1.0])).block()
    with pytest.raises(NotImplementedError, match="cond"):
        L.IfElse(_t([1.0]))
    with pytest.raises(NotImplementedError, match="DataLoader"):
        L.py_reader(64, [[1]], ["float32"])
    with pytest.raises(NotImplementedError, match="rnn"):
        rnn = L.StaticRNN()
        rnn()
    with pytest.raises(NotImplementedError):
        L.rpn_target_assign(None, None, None, None, None, None, None)


def test_codegen_helpers():
    relu_fn = L.generate_activation_fn("relu")
    np.testing.assert_allclose(relu_fn(_t([-1.0, 2.0])).numpy(), [0, 2])
    assert L.templatedoc()(test_codegen_helpers) is test_codegen_helpers


@pytest.mark.needs_reference
def test_full_name_coverage_vs_reference():
    """Every name in the reference fluid.layers __all__ resolves here."""
    import ast
    import os

    base = "/root/reference/python/paddle/fluid/layers"
    names = set()
    for fn in os.listdir(base):
        if not fn.endswith(".py"):
            continue
        tree = ast.parse(open(os.path.join(base, fn)).read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if getattr(t, "id", None) == "__all__":
                        try:
                            names.update(ast.literal_eval(node.value))
                        except Exception:
                            pass
    missing = sorted(n for n in names if not hasattr(L, n))
    assert missing == [], f"fluid.layers missing: {missing}"


def test_beam_search_decode_backtrace_regression():
    """Code-review r4 (reproduced): parents must actually backtrace.
    Both step-2 beams descend from step-1 beam 1 → beam histories share
    token 4, not the raw per-slot tokens."""
    ids = [
        (paddle.to_tensor(np.array([[3], [4]], "int64")),
         paddle.to_tensor(np.array([0, 1], "int64"))),
        (paddle.to_tensor(np.array([[5], [6]], "int64")),
         paddle.to_tensor(np.array([1, 1], "int64"))),
    ]
    scores = [paddle.to_tensor(np.zeros((2, 1), "float32"))] * 2
    seqs, _ = L.beam_search_decode(ids, scores, beam_size=2, end_id=0)
    out = seqs.numpy()  # [T=2, beam=2]
    assert out[:, 0].tolist() == [4, 5]
    assert out[:, 1].tolist() == [4, 6]


def test_beam_search_holds_finished_beams():
    """A finished beam (pre_ids == end_id) re-emits end_id at its frozen
    score instead of expanding."""
    v, beam = 4, 2
    pre_ids = paddle.to_tensor(np.array([[0], [2]], "int64"))  # beam0 done
    pre_sc = _t([[-0.1], [-2.0]])  # finished beam outranks the actives
    sc = _t(np.full((2, v), -0.5))
    ids, scores, parents = L.beam_search(
        pre_ids, pre_sc, None, sc, beam_size=beam, end_id=0,
        return_parent_idx=True)
    rows = {(int(i), round(float(s), 3))
            for i, s in zip(ids.numpy().ravel(), scores.numpy().ravel())}
    # held hypothesis: end_id re-emitted at its frozen score, ranked first
    assert (0, -0.1) in rows
    assert int(ids.numpy()[0, 0]) == 0  # the held beam wins the top slot


def test_random_crop_trailing_and_density_ratios_regression():
    x = _t(np.random.rand(4, 20, 20))
    out = L.random_crop(x, [10, 10], seed=0)
    assert tuple(out.shape) == (4, 10, 10)
    feat = paddle.to_tensor(np.zeros((1, 4, 2, 2), "float32"))
    img = paddle.to_tensor(np.zeros((1, 3, 32, 32), "float32"))
    db, _ = L.density_prior_box(feat, img, densities=[2],
                                fixed_sizes=[16.0],
                                fixed_ratios=[1.0, 2.0])
    assert db.shape[2] == 8  # density^2 * len(ratios)
    wh = db.numpy()[0, 0]
    w = wh[:, 2] - wh[:, 0]
    h = wh[:, 3] - wh[:, 1]
    assert not np.allclose(w[4:], h[4:])  # ratio-2 boxes are non-square


def test_prroi_default_and_data_norm_isolation():
    x = _t(np.random.rand(1, 4, 8, 8))
    rois = _t([[0, 0, 8, 8]])
    out = L.prroi_pool(x, rois, 1.0, 2, 2)  # default batch_roi_nums
    assert tuple(out.shape) == (1, 4, 2, 2)
    # anonymous data_norm calls don't share accumulators
    a = L.data_norm(_t(np.full((4, 3), 100.0)))
    b = L.data_norm(_t(np.full((4, 3), -100.0)))
    assert np.isfinite(a.numpy()).all() and np.isfinite(b.numpy()).all()
    # named calls accumulate under their own key
    c1 = L.data_norm(_t(np.random.rand(4, 3)), name="dn_test")
    from paddle_tpu.fluid.layers import data_norm as _dn
    assert ("dn_test", 3) in _dn.stats


def test_host_ops_fail_loudly_in_static_mode():
    """Host-computed legacy ops must not silently compute on placeholder
    zeros under static build (the silent-failure class from VERDICT r2/r3)."""
    paddle.enable_static()
    try:
        from paddle_tpu import static

        main, startup = static.Program(), static.Program()
        with static.program_guard(main, startup):
            x = static.data("xh", [3, 4], "float32")
            with pytest.raises(NotImplementedError, match="dygraph"):
                L.hash(x, 100)
            with pytest.raises(NotImplementedError, match="dygraph"):
                L.mean_iou(x, x, 4)
            with pytest.raises(NotImplementedError, match="dygraph"):
                L.random_crop(x, [2, 2])
    finally:
        paddle.disable_static()


@pytest.mark.slow
def test_roi_perspective_transform_identity_and_crop():
    """Homography warp: identity quad reproduces the image; half-width quad
    samples the left half (reference roi_perspective_transform_op)."""
    x = paddle.to_tensor(
        np.arange(16, dtype="float32").reshape(1, 1, 4, 4))
    quad = paddle.to_tensor(np.array([[0, 0, 3, 0, 3, 3, 0, 3]], "float32"))
    out, mask, hs = L.roi_perspective_transform(x, quad, 4, 4)
    np.testing.assert_allclose(out.numpy()[0, 0], x.numpy()[0, 0], atol=1e-4)
    assert int(mask.numpy().sum()) == 16
    half = paddle.to_tensor(
        np.array([[0, 0, 1.5, 0, 1.5, 3, 0, 3]], "float32"))
    out2, _, _ = L.roi_perspective_transform(x, half, 4, 4)
    np.testing.assert_allclose(out2.numpy()[0, 0, 0, :2], [0.0, 0.5],
                               atol=1e-4)
