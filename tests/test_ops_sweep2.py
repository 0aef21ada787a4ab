"""Systematic op sweep, part 2: optimizer update rules, metrics, RNN cells,
detection ops, 3-D conv/pool, sequence-structure ops, collectives, tensor
arrays, SelectedRows host ops — plus the registry-completeness check that
asserts EVERY registered op has a test (here, part 1, or a named dedicated
test file).

Reference parity: op_test.py-driven unittests plus the per-family tests
(test_adam_op.py, test_bipartite_match_op.py, test_edit_distance_op.py, ...).
"""

import glob
import os
import re

import numpy as np
import pytest

import paddle_tpu as fluid
from op_test import check_output, check_grad, run_op


def _r(*shape, lo=0.0, hi=1.0, seed=0, dtype=np.float32):
    rng = np.random.RandomState(abs(hash((shape, lo, hi, seed))) % (2**31))
    return (rng.uniform(lo, hi, size=shape)).astype(dtype)


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


# --------------------------------------------------------------------------
# optimizer update rules (operators/{sgd,momentum,adam,...}_op.cc)
def _opt_base(seed=0):
    p = _r(3, 4, lo=-1, hi=1, seed=seed)
    g = _r(3, 4, lo=-1, hi=1, seed=seed + 1)
    lr = np.array([0.1], np.float32)
    return p, g, lr


def test_sgd_op():
    p, g, lr = _opt_base(120)
    check_output("sgd", {"Param": p, "Grad": g, "LearningRate": lr}, {},
                 {"ParamOut": p - lr * g}, rtol=1e-5)


def test_momentum_op():
    p, g, lr = _opt_base(121)
    v = _r(3, 4, seed=122)
    mu = 0.9
    vn = mu * v + g
    check_output("momentum",
                 {"Param": p, "Grad": g, "Velocity": v, "LearningRate": lr},
                 {"mu": mu},
                 {"ParamOut": p - lr * vn, "VelocityOut": vn}, rtol=1e-5)
    # nesterov variant
    check_output("momentum",
                 {"Param": p, "Grad": g, "Velocity": v, "LearningRate": lr},
                 {"mu": mu, "use_nesterov": True},
                 {"ParamOut": p - (g + mu * vn) * lr}, rtol=1e-5)


def test_adagrad_op():
    p, g, lr = _opt_base(123)
    m = _r(3, 4, lo=0, hi=1, seed=124)
    eps = 1e-6
    mn = m + g * g
    check_output("adagrad",
                 {"Param": p, "Grad": g, "Moment": m, "LearningRate": lr},
                 {"epsilon": eps},
                 {"ParamOut": p - lr * g / (np.sqrt(mn) + eps),
                  "MomentOut": mn}, rtol=1e-5)


def test_adam_op():
    p, g, lr = _opt_base(125)
    m1, m2 = _r(3, 4, seed=126), _r(3, 4, lo=0, hi=1, seed=127)
    b1, b2, eps = 0.9, 0.999, 1e-8
    b1p = np.array([b1 ** 3], np.float32)
    b2p = np.array([b2 ** 3], np.float32)
    m1n = b1 * m1 + (1 - b1) * g
    m2n = b2 * m2 + (1 - b2) * g * g
    lr_t = lr * np.sqrt(1 - b2p) / (1 - b1p)
    check_output("adam",
                 {"Param": p, "Grad": g, "Moment1": m1, "Moment2": m2,
                  "LearningRate": lr, "Beta1Pow": b1p, "Beta2Pow": b2p},
                 {"beta1": b1, "beta2": b2, "epsilon": eps,
                  "update_beta_pow": True},
                 {"ParamOut": p - lr_t * m1n / (np.sqrt(m2n) + eps),
                  "Moment1Out": m1n, "Moment2Out": m2n,
                  "Beta1PowOut": b1p * b1, "Beta2PowOut": b2p * b2},
                 rtol=1e-5)


def test_adamax_op():
    p, g, lr = _opt_base(128)
    m = _r(3, 4, seed=129)
    inf = _r(3, 4, lo=0.1, hi=1, seed=130)
    b1, b2, eps = 0.9, 0.999, 1e-8
    b1p = np.array([b1 ** 2], np.float32)
    mn = b1 * m + (1 - b1) * g
    infn = np.maximum(b2 * inf, np.abs(g) + eps)
    check_output("adamax",
                 {"Param": p, "Grad": g, "Moment": m, "InfNorm": inf,
                  "LearningRate": lr, "Beta1Pow": b1p},
                 {"beta1": b1, "beta2": b2, "epsilon": eps},
                 {"ParamOut": p - (lr / (1 - b1p)) * mn / infn,
                  "MomentOut": mn, "InfNormOut": infn}, rtol=1e-5)


def test_decayed_adagrad_op():
    p, g, lr = _opt_base(131)
    m = _r(3, 4, lo=0, hi=1, seed=132)
    decay, eps = 0.95, 1e-6
    mn = decay * m + (1 - decay) * g * g
    check_output("decayed_adagrad",
                 {"Param": p, "Grad": g, "Moment": m, "LearningRate": lr},
                 {"decay": decay, "epsilon": eps},
                 {"ParamOut": p - lr * g / (np.sqrt(mn) + eps),
                  "MomentOut": mn}, rtol=1e-5)


def test_adadelta_op():
    p, g, _ = _opt_base(133)
    asg = _r(3, 4, lo=0, hi=1, seed=134)
    asu = _r(3, 4, lo=0, hi=1, seed=135)
    rho, eps = 0.95, 1e-6
    asgn = rho * asg + (1 - rho) * g * g
    upd = -np.sqrt((asu + eps) / (asgn + eps)) * g
    asun = rho * asu + (1 - rho) * upd * upd
    check_output("adadelta",
                 {"Param": p, "Grad": g, "AvgSquaredGrad": asg,
                  "AvgSquaredUpdate": asu},
                 {"rho": rho, "epsilon": eps},
                 {"ParamOut": p + upd, "AvgSquaredGradOut": asgn,
                  "AvgSquaredUpdateOut": asun}, rtol=1e-5)


def test_rmsprop_op():
    p, g, lr = _opt_base(136)
    ms = _r(3, 4, lo=0.1, hi=1, seed=137)
    mom = _r(3, 4, seed=138)
    rho, eps, momentum = 0.9, 1e-10, 0.5
    msn = rho * ms + (1 - rho) * g * g
    momn = momentum * mom + lr * g / np.sqrt(msn + eps)
    check_output("rmsprop",
                 {"Param": p, "Grad": g, "MeanSquare": ms, "Moment": mom,
                  "LearningRate": lr},
                 {"decay": rho, "epsilon": eps, "momentum": momentum},
                 {"ParamOut": p - momn, "MeanSquareOut": msn,
                  "MomentOut": momn}, rtol=1e-5)


def test_ftrl_op():
    p, g, lr = _opt_base(139)
    sq = _r(3, 4, lo=0.1, hi=1, seed=140)
    lin = _r(3, 4, seed=141)
    l1, l2, power = 0.1, 0.2, -0.5
    sqn = sq + g * g
    sigma = (sqn ** 0.5 - sq ** 0.5) / lr
    linn = lin + g - sigma * p
    x = l1 * np.sign(linn) - linn
    y = sqn ** 0.5 / lr + 2 * l2
    pn = np.where(np.abs(linn) > l1, x / y, 0.0)
    check_output("ftrl",
                 {"Param": p, "Grad": g, "SquaredAccumulator": sq,
                  "LinearAccumulator": lin, "LearningRate": lr},
                 {"l1": l1, "l2": l2, "lr_power": power},
                 {"ParamOut": pn, "SquaredAccumOut": sqn,
                  "LinearAccumOut": linn}, rtol=1e-4)


def test_proximal_gd_op():
    p, g, lr = _opt_base(142)
    l1, l2 = 0.05, 0.1
    prox = p - lr * g
    pn = np.sign(prox) * np.maximum(np.abs(prox) - lr * l1, 0) \
        / (1 + lr * l2)
    check_output("proximal_gd",
                 {"Param": p, "Grad": g, "LearningRate": lr},
                 {"l1": l1, "l2": l2}, {"ParamOut": pn}, rtol=1e-5)


def test_proximal_adagrad_op():
    p, g, lr = _opt_base(143)
    m = _r(3, 4, lo=0.1, hi=1, seed=144)
    l1, l2 = 0.05, 0.1
    mn = m + g * g
    lr_t = lr / np.sqrt(mn)
    prox = p - lr_t * g
    pn = np.sign(prox) * np.maximum(np.abs(prox) - lr_t * l1, 0) \
        / (1 + lr_t * l2)
    check_output("proximal_adagrad",
                 {"Param": p, "Grad": g, "Moment": m, "LearningRate": lr},
                 {"l1": l1, "l2": l2},
                 {"ParamOut": pn, "MomentOut": mn}, rtol=1e-5)


def test_average_accumulates_op():
    p = _r(3, 4, seed=145)
    s1, s2, s3 = (_r(3, 4, seed=s) for s in (146, 147, 148))
    num_acc = np.array([1], np.int64)
    old_num = np.array([0], np.int64)
    num_upd = np.array([1], np.int64)
    # window = clip(avg_window*num_upd, min_w, max_w) = 100 -> no rollover
    got = run_op("average_accumulates",
                 {"param": p, "in_sum_1": s1, "in_sum_2": s2, "in_sum_3": s3,
                  "in_num_accumulates": num_acc,
                  "in_old_num_accumulates": old_num,
                  "in_num_updates": num_upd},
                 {"average_window": 10.0, "max_average_window": 100,
                  "min_average_window": 100},
                 ["out_sum_1", "out_num_accumulates"])
    np.testing.assert_allclose(np.asarray(got["out_sum_1"]), s1 + p,
                               rtol=1e-5)
    assert int(np.asarray(got["out_num_accumulates"])) == 2


# --------------------------------------------------------------------------
# metrics (operators/{accuracy,edit_distance,precision_recall}_op.cc)
def test_accuracy_op():
    # top-k membership semantics (accuracy_op.cc): a row counts as correct
    # if the label appears anywhere in its top-k indices
    indices = np.array([[1, 0], [2, 3], [0, 2], [1, 2]], np.int64)
    label = np.array([[1], [1], [0], [2]], np.int64)
    got = run_op("accuracy", {"Indices": indices, "Label": label}, {},
                 ["Accuracy", "Correct", "Total"])
    np.testing.assert_allclose(float(np.asarray(got["Accuracy"])), 0.75)
    assert int(np.asarray(got["Correct"])) == 3
    assert int(np.asarray(got["Total"])) == 4


def _levenshtein(a, b):
    m, n = len(a), len(b)
    d = np.zeros((m + 1, n + 1))
    d[:, 0] = np.arange(m + 1)
    d[0, :] = np.arange(n + 1)
    for i in range(1, m + 1):
        for j in range(1, n + 1):
            cost = 0 if a[i - 1] == b[j - 1] else 1
            d[i, j] = min(d[i - 1, j] + 1, d[i, j - 1] + 1,
                          d[i - 1, j - 1] + cost)
    return d[m, n]


def test_edit_distance_op():
    hyp = np.array([[1, 2, 3, 4], [5, 6, 7, 8]], np.int64)
    ref = np.array([[1, 3, 3, 9], [5, 6, 8, 8]], np.int64)
    want = np.array([[_levenshtein(hyp[i], ref[i])] for i in range(2)],
                    np.float32)
    check_output("edit_distance", {"Hyps": hyp, "Refs": ref},
                 {"normalized": False}, {"Out": want})
    check_output("edit_distance", {"Hyps": hyp, "Refs": ref},
                 {"normalized": True}, {"Out": want / 4.0}, rtol=1e-5)


def test_precision_recall_shapes():
    indices = np.array([[0], [1], [2], [1]], np.int64)
    labels = np.array([[0], [1], [1], [2]], np.int64)
    got = run_op("precision_recall",
                 {"Indices": indices, "Labels": labels},
                 {"class_number": 3}, ["BatchMetrics"])
    bm = np.asarray(got["BatchMetrics"])
    assert bm.shape == (6,)
    assert np.all(bm >= 0) and np.all(bm <= 1.0 + 1e-6)


# --------------------------------------------------------------------------
# RNN cells (operators/{lstm_unit,gru_unit}_op.cc)
def test_lstm_unit_op():
    b, d = 3, 4
    x = _r(b, 4 * d, lo=-1, hi=1, seed=150)
    c_prev = _r(b, d, lo=-1, hi=1, seed=151)
    fb = 0.5
    gi, gf, gc, go = np.split(x, 4, axis=-1)
    c = _sigmoid(gf + fb) * c_prev + _sigmoid(gi) * np.tanh(gc)
    h = _sigmoid(go) * np.tanh(c)
    check_output("lstm_unit", {"X": x, "C_prev": c_prev},
                 {"forget_bias": fb}, {"C": c, "H": h}, rtol=1e-4)
    check_grad("lstm_unit", {"X": _r(2, 8, lo=-1, hi=1, seed=152),
                             "C_prev": _r(2, 2, lo=-1, hi=1, seed=153)},
               {"forget_bias": fb}, wrt=["X", "C_prev"], out="H",
               out_slots=["C", "H"])


def test_gru_unit_op():
    b, d = 3, 4
    x = _r(b, 3 * d, lo=-1, hi=1, seed=154)
    h_prev = _r(b, d, lo=-1, hi=1, seed=155)
    w = _r(d, 3 * d, lo=-0.5, hi=0.5, seed=156)
    xu, xr, xc = x[:, :d], x[:, d:2 * d], x[:, 2 * d:]
    gh = h_prev @ w[:, :2 * d]
    u = _sigmoid(xu + gh[:, :d])
    r = _sigmoid(xr + gh[:, d:])
    c = np.tanh(xc + (r * h_prev) @ w[:, 2 * d:])
    h = u * c + (1 - u) * h_prev
    check_output("gru_unit",
                 {"Input": x, "HiddenPrev": h_prev, "Weight": w}, {},
                 {"Hidden": h, "ResetHiddenPrev": r * h_prev}, rtol=1e-4)


# --------------------------------------------------------------------------
# detection (operators/detection/*.cc)
def test_iou_similarity_op():
    x = np.array([[0, 0, 2, 2], [1, 1, 3, 3]], np.float32)
    y = np.array([[0, 0, 2, 2], [2, 2, 4, 4]], np.float32)

    def iou(a, b):
        ix = max(0, min(a[2], b[2]) - max(a[0], b[0]))
        iy = max(0, min(a[3], b[3]) - max(a[1], b[1]))
        inter = ix * iy
        ua = (a[2] - a[0]) * (a[3] - a[1]) + \
             (b[2] - b[0]) * (b[3] - b[1]) - inter
        return inter / ua if ua > 0 else 0.0

    want = np.array([[iou(a, b) for b in y] for a in x], np.float32)
    check_output("iou_similarity", {"X": x, "Y": y}, {}, {"Out": want},
                 rtol=1e-5)


def test_box_coder_decode():
    prior = np.array([[0, 0, 4, 4], [2, 2, 6, 8]], np.float32)
    var = np.ones((2, 4), np.float32) * 0.5
    deltas = _r(3, 2, 4, lo=-0.3, hi=0.3, seed=160)
    pw = prior[:, 2] - prior[:, 0]
    ph = prior[:, 3] - prior[:, 1]
    pcx = prior[:, 0] + pw / 2
    pcy = prior[:, 1] + ph / 2
    dcx = deltas[..., 0] * var[None, :, 0] * pw[None] + pcx[None]
    dcy = deltas[..., 1] * var[None, :, 1] * ph[None] + pcy[None]
    dw = np.exp(deltas[..., 2] * var[None, :, 2]) * pw[None]
    dh = np.exp(deltas[..., 3] * var[None, :, 3]) * ph[None]
    want = np.stack([dcx - dw / 2, dcy - dh / 2,
                     dcx + dw / 2, dcy + dh / 2], axis=-1)
    check_output("box_coder",
                 {"PriorBox": prior, "PriorBoxVar": var, "TargetBox": deltas},
                 {"code_type": "decode_center_size"},
                 {"OutputBox": want}, rtol=1e-4)


def test_bipartite_match_op():
    dist = np.array([[0.1, 0.9, 0.3],
                     [0.8, 0.2, 0.7]], np.float32)
    # greedy global: (0,1)=0.9 then (1,0)=0.8; col 2 unmatched
    got = run_op("bipartite_match", {"DistMat": dist}, {},
                 ["ColToRowMatchIndices", "ColToRowMatchDist"])
    np.testing.assert_array_equal(
        np.asarray(got["ColToRowMatchIndices"]), [[1, 0, -1]])
    np.testing.assert_allclose(
        np.asarray(got["ColToRowMatchDist"]), [[0.8, 0.9, 0.0]], rtol=1e-6)


def test_target_assign_op():
    x = _r(3, 5, seed=161)          # N_gt=3, K=5
    match = np.array([[0, -1, 2, 1]], np.int32)
    got = run_op("target_assign", {"X": x, "MatchIndices": match},
                 {"mismatch_value": 0.0}, ["Out", "OutWeight"])
    out = np.asarray(got["Out"])[0]
    wt = np.asarray(got["OutWeight"])[0, :, 0]
    np.testing.assert_allclose(out[0], x[0], rtol=1e-6)
    np.testing.assert_allclose(out[2], x[2], rtol=1e-6)
    np.testing.assert_allclose(out[3], x[1], rtol=1e-6)
    np.testing.assert_array_equal(wt, [1, 0, 1, 1])


def test_mine_hard_examples_shapes():
    cls_loss = _r(2, 6, seed=162)
    match = np.array([[0, -1, -1, 1, -1, -1],
                      [-1, 0, -1, -1, -1, 1]], np.int32)
    got = run_op("mine_hard_examples",
                 {"ClsLoss": cls_loss, "MatchIndices": match},
                 {"neg_pos_ratio": 1.0, "mining_type": "max_negative"},
                 ["NegIndices", "UpdatedMatchIndices"])
    assert np.asarray(got["UpdatedMatchIndices"]).shape == (2, 6)


def test_prior_box_shapes():
    feat = _r(1, 8, 4, 4, seed=163)
    img = _r(1, 3, 32, 32, seed=164)
    got = run_op("prior_box", {"Input": feat, "Image": img},
                 {"min_sizes": [4.0], "max_sizes": [8.0],
                  "aspect_ratios": [1.0], "variances": [0.1, 0.1, 0.2, 0.2]},
                 ["Boxes", "Variances"])
    boxes = np.asarray(got["Boxes"])
    assert boxes.shape[-1] == 4 and boxes.shape[0] == 4  # H,W,priors,4
    assert np.asarray(got["Variances"]).shape == boxes.shape


def test_detection_map_shapes():
    det = np.array([[0, 0.9, 0, 0, 2, 2], [1, 0.8, 1, 1, 3, 3]], np.float32)
    gt = np.array([[0, 0, 0, 2, 2, 0], [1, 1, 1, 3, 3, 0]], np.float32)
    got = run_op("detection_map", {"DetectRes": det, "Label": gt}, {},
                 ["MAP"])
    v = float(np.asarray(got["MAP"]))
    assert 0.0 <= v <= 1.0


# --------------------------------------------------------------------------
# 3-D conv/pool + pyramid/row/sequence-image ops (torch-referenced where a
# closed-form numpy ref would re-implement the kernel)
def test_conv3d_vs_torch():
    import torch
    import torch.nn.functional as F
    x = _r(1, 2, 4, 5, 5, lo=-1, hi=1, seed=165)
    w = _r(3, 2, 2, 3, 3, lo=-1, hi=1, seed=166)
    want = F.conv3d(torch.tensor(x), torch.tensor(w),
                    stride=(1, 2, 2), padding=(0, 1, 1)).numpy()
    check_output("conv3d", {"Input": x, "Filter": w},
                 {"strides": [1, 2, 2], "paddings": [0, 1, 1]},
                 {"Output": want}, rtol=1e-3, atol=1e-4)


def test_conv3d_transpose_vs_torch():
    import torch
    import torch.nn.functional as F
    x = _r(1, 3, 3, 4, 4, lo=-1, hi=1, seed=167)
    w = _r(3, 2, 2, 3, 3, lo=-1, hi=1, seed=168)   # [Cin, Cout, kd, kh, kw]
    want = F.conv_transpose3d(torch.tensor(x), torch.tensor(w),
                              stride=(1, 2, 2)).numpy()
    check_output("conv3d_transpose", {"Input": x, "Filter": w},
                 {"strides": [1, 2, 2], "paddings": [0, 0, 0]},
                 {"Output": want}, rtol=1e-3, atol=1e-4)


def test_pool3d_vs_torch():
    import torch
    import torch.nn.functional as F
    x = _r(1, 2, 4, 6, 6, lo=-1, hi=1, seed=169)
    t = torch.tensor(x)
    want_max = F.max_pool3d(t, kernel_size=2, stride=2).numpy()
    check_output("pool3d", {"X": x},
                 {"pooling_type": "max", "ksize": [2, 2, 2],
                  "strides": [2, 2, 2]},
                 {"Out": want_max}, rtol=1e-5)
    want_avg = F.avg_pool3d(t, kernel_size=2, stride=2).numpy()
    check_output("pool3d", {"X": x},
                 {"pooling_type": "avg", "ksize": [2, 2, 2],
                  "strides": [2, 2, 2]},
                 {"Out": want_avg}, rtol=1e-4)


def test_spp_op():
    # pyramid_height=2 -> level 0: global pool (1 bin), level 1: 2x2 bins
    x = _r(2, 3, 4, 4, lo=-1, hi=1, seed=170)
    lvl0 = x.max(axis=(2, 3)).reshape(2, -1)
    lvl1 = np.stack([x[:, :, :2, :2].max(axis=(2, 3)),
                     x[:, :, :2, 2:].max(axis=(2, 3)),
                     x[:, :, 2:, :2].max(axis=(2, 3)),
                     x[:, :, 2:, 2:].max(axis=(2, 3))], axis=2)
    lvl1 = lvl1.reshape(2, -1)
    # reference layout per level: [N, C*bins] with bins fastest — build via
    # reshape of [N, C, bins]
    want = np.concatenate([lvl0, lvl1], axis=1)
    got = run_op("spp", {"X": x}, {"pyramid_height": 2,
                                   "pooling_type": "max"}, ["Out"])
    g = np.asarray(got["Out"])
    assert g.shape == (2, 3 + 12)
    np.testing.assert_allclose(g[:, :3], lvl0, rtol=1e-5)
    np.testing.assert_allclose(np.sort(g[:, 3:], 1), np.sort(lvl1, 1),
                               rtol=1e-5)


def test_row_conv_op():
    t, d, k = 6, 3, 3
    x = _r(t, d, lo=-1, hi=1, seed=171)
    w = _r(k, d, lo=-1, hi=1, seed=172)
    xp = np.pad(x, ((0, k - 1), (0, 0)))
    want = sum(xp[i:i + t] * w[i] for i in range(k))
    check_output("row_conv", {"X": x, "Filter": w}, {}, {"Out": want},
                 rtol=1e-4)


def test_im2sequence_op():
    x = _r(1, 2, 4, 4, lo=-1, hi=1, seed=173)
    got = run_op("im2sequence", {"X": x},
                 {"kernels": [2, 2], "strides": [2, 2],
                  "paddings": [0, 0, 0, 0]}, ["Out"])
    out = np.asarray(got["Out"])
    # 2x2 windows over 4x4 stride 2 -> 4 windows, each C*kh*kw = 8 wide
    assert out.shape == (4, 8)
    # first window must contain x[0,:, :2, :2]
    np.testing.assert_allclose(np.sort(out[0]),
                               np.sort(x[0, :, :2, :2].reshape(-1)),
                               rtol=1e-5)


# --------------------------------------------------------------------------
# collectives — identity semantics outside a mesh (documented contract;
# in-mesh semantics are covered by tests/test_parallel.py)
@pytest.mark.parametrize("op", ["c_allreduce_sum", "c_allreduce_max",
                                "c_allgather", "c_reducescatter",
                                "c_broadcast", "all_to_all"])
def test_collective_identity_outside_mesh(op):
    x = _r(4, 3, seed=174)
    check_output(op, {"X": x}, {"ring_id": 0}, {"Out": x})


def test_c_sync_comm_stream():
    x = _r(2, 2, seed=175)
    check_output("c_sync_comm_stream", {"X": x}, {}, {"Out": x})


# --------------------------------------------------------------------------
# LoDTensorArray ops + rank-table ops (tensor_array_read_write.cc,
# lod_rank_table_op.cc, max_sequence_len_op.cc, shrink_rnn_memory_op.cc)
def test_tensor_array_write_read_length():
    prog = fluid.Program()
    blk = prog.global_block()
    for nm, arr in (("x0", np.ones((2, 3), np.float32)),
                    ("x1", 2 * np.ones((2, 3), np.float32))):
        blk.create_var(name=nm, shape=(2, 3), dtype="float32", is_data=True)
    blk.create_var(name="i0")
    blk.append_op("fill_constant", {}, {"Out": ["i0"]},
                  {"shape": [1], "value": 0.0, "dtype": "int64"})
    blk.create_var(name="i1")
    blk.append_op("fill_constant", {}, {"Out": ["i1"]},
                  {"shape": [1], "value": 1.0, "dtype": "int64"})
    blk.create_var(name="arr")
    blk.append_op("write_to_array", {"X": ["x0"], "I": ["i0"]},
                  {"Out": ["arr"]}, {})
    blk.append_op("write_to_array", {"X": ["x1"], "I": ["i1"]},
                  {"Out": ["arr"]}, {})
    blk.create_var(name="read1")
    blk.append_op("read_from_array", {"X": ["arr"], "I": ["i1"]},
                  {"Out": ["read1"]}, {})
    blk.create_var(name="alen")
    blk.append_op("lod_array_length", {"X": ["arr"]}, {"Out": ["alen"]}, {})
    exe = fluid.Executor(fluid.CPUPlace())
    # TensorArray indices must be trace-time constants: the array ops are
    # host-tier, so the PUBLIC run() path routes this program through the
    # interpreter (index-producing segments still compile)
    with fluid.scope_guard(fluid.Scope()):
        r, n = exe.run(
            prog,
            feed={"x0": np.ones((2, 3), np.float32),
                  "x1": 2 * np.ones((2, 3), np.float32)},
            fetch_list=["read1", "alen"])
    np.testing.assert_allclose(np.asarray(r), 2.0)
    assert int(np.asarray(n)[0]) == 2


def test_rank_table_and_max_sequence_len():
    prog = fluid.Program()
    blk = prog.global_block()
    blk.create_var(name="x", shape=(7, 2), dtype="float32", is_data=True,
                   lod_level=1)
    blk.create_var(name="table")
    blk.append_op("lod_rank_table", {"X": ["x"]}, {"Out": ["table"]}, {})
    blk.create_var(name="maxlen")
    blk.append_op("max_sequence_len", {"RankTable": ["table"]},
                  {"Out": ["maxlen"]}, {})
    blk.create_var(name="shrunk")
    blk.append_op("shrink_rnn_memory", {"X": ["x"], "RankTable": ["table"],
                                        "I": ["maxlen"]},
                  {"Out": ["shrunk"]}, {})
    x = np.arange(14, dtype=np.float32).reshape(7, 2)
    lod = fluid.LoDTensor(x)
    lod.set_recursive_sequence_lengths([[3, 4]])
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        ml, sh = exe.run(prog, feed={"x": lod},
                         fetch_list=["maxlen", "shrunk"])
    assert int(np.asarray(ml)) == 4
    np.testing.assert_allclose(np.asarray(sh), x)


def test_select_rows_by_mask_op():
    mask = np.array([1, 0, 1], np.float32)
    t = _r(3, 2, seed=176)
    f = _r(3, 2, seed=177)
    want = np.where(mask[:, None] > 0, t, f)
    check_output("select_rows_by_mask",
                 {"Mask": mask, "TrueOut": t, "FalseOut": f}, {},
                 {"Out": want})


# --------------------------------------------------------------------------
# SelectedRows host ops (split/merge/lookup — operators/
# {split_selected_rows,merge_selected_rows,lookup_sparse_table}_op.cc).
# These are host ops: the program runs in the eager interpreter with
# SelectedRows values living in the scope.
def _sr(rows, value, height):
    from paddle_tpu.core.selected_rows import SelectedRows
    return SelectedRows(np.asarray(rows, np.int64),
                        np.asarray(value, np.float32), height)


def test_split_and_merge_selected_rows_ops():
    from paddle_tpu.core.selected_rows import SelectedRows
    prog = fluid.Program()
    blk = prog.global_block()
    blk.create_var(name="sr_in", persistable=True,
                   type=fluid.core.program.VarType.SELECTED_ROWS)
    for nm in ("part0", "part1", "merged"):
        blk.create_var(name=nm)
    blk.append_op("split_selected_rows", {"X": ["sr_in"]},
                  {"Out": ["part0", "part1"]}, {"height_sections": [4, 4]})
    blk.append_op("merge_selected_rows", {"X": ["dup"]},
                  {"Out": ["merged"]}, {})
    blk.create_var(name="dup", persistable=True,
                   type=fluid.core.program.VarType.SELECTED_ROWS)
    # make the program a host-op program by construction (split/merge are
    # host ops), run through the scope
    scope = fluid.Scope()
    scope.set("sr_in", _sr([1, 5, 6], np.arange(6).reshape(3, 2), 8))
    scope.set("dup", _sr([2, 2, 3], [[1, 1], [2, 2], [5, 5]], 8))
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        p0, p1, merged = exe.run(prog, feed={},
                                 fetch_list=["part0", "part1", "merged"],
                                 return_numpy=False)
    assert isinstance(p0, SelectedRows)
    np.testing.assert_array_equal(p0.rows, [1])
    np.testing.assert_array_equal(p1.rows, [1, 2])  # 5-4, 6-4
    np.testing.assert_array_equal(merged.rows, [2, 3])
    np.testing.assert_allclose(merged.value, [[3, 3], [5, 5]])


def test_lookup_sparse_table_op():
    prog = fluid.Program()
    blk = prog.global_block()
    blk.create_var(name="w", persistable=True,
                   type=fluid.core.program.VarType.SELECTED_ROWS)
    blk.create_var(name="ids", shape=(3, 1), dtype="int64", is_data=True)
    blk.create_var(name="out")
    blk.append_op("lookup_sparse_table", {"W": ["w"], "Ids": ["ids"]},
                  {"Out": ["out"]}, {})
    scope = fluid.Scope()
    scope.set("w", _sr([3, 7], [[1, 2], [3, 4]], 10))
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        out, = exe.run(prog,
                       feed={"ids": np.array([[7], [3], [9]], np.int64)},
                       fetch_list=["out"])
    np.testing.assert_allclose(np.asarray(out),
                               [[3, 4], [1, 2], [0, 0]])


def test_lstmp_op():
    # LSTM with recurrent projection (lstmp_op.cc), no peepholes, no bias:
    # numpy step-by-step reference over one sequence
    t, d, p = 5, 3, 2
    x = _r(t, 4 * d, lo=-0.5, hi=0.5, seed=180)
    w = _r(p, 4 * d, lo=-0.5, hi=0.5, seed=181)
    w_proj = _r(d, p, lo=-0.5, hi=0.5, seed=182)
    r = np.zeros(p, np.float32)
    c = np.zeros(d, np.float32)
    want = np.zeros((t, p), np.float32)
    for i in range(t):
        gates = x[i] + r @ w
        gi, gf, gc, go = np.split(gates, 4)
        cn = _sigmoid(gf) * c + _sigmoid(gi) * np.tanh(gc)
        h = _sigmoid(go) * np.tanh(cn)
        r = np.tanh(h @ w_proj)
        c = cn
        want[i] = r
    check_output("lstmp",
                 {"Input": (x, [t]), "Weight": w, "ProjWeight": w_proj},
                 {"use_peepholes": False}, {"Projection": want}, rtol=1e-4,
                 atol=1e-5)


def test_conditional_block_op():
    prog = fluid.Program()
    blk = prog.global_block()
    blk.create_var(name="x", shape=(2, 3), dtype="float32", is_data=True)
    blk.create_var(name="c", shape=(1,), dtype="bool", is_data=True)
    blk.create_var(name="y")
    blk.append_op("fill_constant", {}, {"Out": ["y"]},
                  {"shape": [2, 3], "value": 0.0})
    sub = prog.create_block(parent_idx=0)
    sub.append_op("scale", {"X": ["x"]}, {"Out": ["y"]}, {"scale": 2.0})
    blk.append_op("conditional_block", {"Condition": ["c"]},
                  {"Out": ["y"]},
                  {"sub_block": sub, "written_names": ["y"]})
    x = _r(2, 3, seed=183)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        yt, = exe.run(prog, feed={"x": x, "c": np.array([True])},
                      fetch_list=["y"])
        yf, = exe.run(prog, feed={"x": x, "c": np.array([False])},
                      fetch_list=["y"])
    np.testing.assert_allclose(np.asarray(yt), 2 * x, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(yf), 0.0)


def test_sequence_concat_op():
    # LoD path: sequences interleave — seq i of every input, inputs in order
    a = np.arange(6, dtype=np.float32).reshape(3, 2)        # lens [1, 2]
    b = 10 + np.arange(8, dtype=np.float32).reshape(4, 2)   # lens [2, 2]
    want = np.concatenate([a[:1], b[:2], a[1:], b[2:]], axis=0)
    check_output("sequence_concat", {"X": [(a, [1, 2]), (b, [2, 2])]}, {},
                 {"Out": want})


def test_sequence_scatter_op():
    x = _r(5, 2, seed=184)
    ids = np.array([0, 3, 1], np.int64)
    upd = _r(3, 2, seed=185)
    want = x.copy()
    for i, u in zip(ids, upd):
        want[i] += u
    check_output("sequence_scatter", {"X": x, "Ids": ids, "Updates": upd},
                 {}, {"Out": want}, rtol=1e-5)


def test_lod_reset_op():
    # rebind [2,4] lengths to [3,3] via target_lod OFFSETS, then pool:
    # the downstream sequence op must see the NEW segmentation
    x = np.arange(12, dtype=np.float32).reshape(6, 2)
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        blk = prog.global_block()
        for nm in ("x_in", "reset", "pooled"):
            blk.create_var(name=nm)
        blk.append_op("lod_reset", {"X": ["x_in"]}, {"Out": ["reset"]},
                      {"target_lod": [0, 3, 6]})
        blk.append_op("sequence_pool", {"X": ["reset"]},
                      {"Out": ["pooled"]}, {"pooltype": "SUM"})
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            pooled, = exe.run(
                prog, feed={"x_in": fluid.LoDTensor(x, [[0, 2, 6]])},
                fetch_list=["pooled"])
    want = np.stack([x[:3].sum(0), x[3:].sum(0)])
    np.testing.assert_allclose(np.asarray(pooled), want)

    # Y-input form: Out must ADOPT Y's LoD — prove it via a chained pool
    def pooled_after_reset(y_val, y_feed_key, feed_extra):
        prog, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(prog, startup):
            blk = prog.global_block()
            for nm in ("x_in2", y_feed_key, "reset2", "pooled2"):
                blk.create_var(name=nm)
            blk.append_op("lod_reset",
                          {"X": ["x_in2"], "Y": [y_feed_key]},
                          {"Out": ["reset2"]}, {})
            blk.append_op("sequence_pool", {"X": ["reset2"]},
                          {"Out": ["pooled2"]}, {"pooltype": "SUM"})
            exe = fluid.Executor(fluid.CPUPlace())
            with fluid.scope_guard(fluid.Scope()):
                out, = exe.run(
                    prog,
                    feed={"x_in2": fluid.LoDTensor(x, [[0, 2, 6]]),
                          y_feed_key: y_val, **feed_extra},
                    fetch_list=["pooled2"])
        return np.asarray(out)

    # (a) Y carries a LoD: lengths [1, 5] replace x's [2, 4]
    y = fluid.LoDTensor(np.zeros((6, 1), np.float32), [[0, 1, 6]])
    got = pooled_after_reset(y, "y_lod", {})
    np.testing.assert_allclose(got, np.stack([x[:1].sum(0), x[1:].sum(0)]))

    # (b) Y without LoD: its VALUES are level-0 offsets
    y_off = np.array([0, 4, 6], np.int32)
    got = pooled_after_reset(y_off, "y_off", {})
    np.testing.assert_allclose(got, np.stack([x[:4].sum(0), x[4:].sum(0)]))


def test_sequence_slice_op():
    # per-sequence sub-slices: seq0 = rows 0-2 (take offset 1 len 2),
    # seq1 = rows 3-6 (take offset 0 len 1)
    x = np.arange(14, dtype=np.float32).reshape(7, 2)
    offset = np.array([[1], [0]], np.int64)
    length = np.array([[2], [1]], np.int64)
    want = np.concatenate([x[1:3], x[3:4]], axis=0)
    got = run_op("sequence_slice",
                 {"X": (x, [3, 4]), "Offset": offset, "Length": length},
                 {}, ["Out"])
    # kept rows first; the executor trims to sum(Length) via the output's
    # propagated @LOD lengths
    np.testing.assert_allclose(np.asarray(got["Out"]), want)


# --------------------------------------------------------------------------
# finite-difference gradient checks for the hand-built scans — the analytic
# side is jax.value_and_grad through lax.scan, which per-op numpy refs do
# not exercise (reference: test_linear_chain_crf_op.py check_grad,
# test_warpctc_op.py check_grad, test_lstm_op.py reverse-direction grads)
def test_linear_chain_crf_grad():
    d = 3
    emission = _r(5, d, lo=-0.5, hi=0.5, seed=190)
    label = np.array([[0], [2], [1], [1], [0]], np.int64)
    trans = _r(d + 2, d, lo=-0.5, hi=0.5, seed=191)
    check_grad("linear_chain_crf",
               {"Emission": (emission, [2, 3]), "Label": (label, [2, 3]),
                "Transition": trans},
               {}, wrt=["Emission", "Transition"], out="LogLikelihood",
               out_slots=["LogLikelihood", "Alpha", "EmissionExps",
                          "TransitionExps"],
               delta=1e-2, rtol=5e-2, atol=1e-3)


def test_warpctc_grad():
    c = 4  # classes incl. blank 0
    logits = _r(6, c, lo=-1, hi=1, seed=192)
    label = np.array([[1], [2], [3]], np.int64)
    check_grad("warpctc",
               {"Logits": (logits, [3, 3]), "Label": (label, [2, 1])},
               {"blank": 0}, wrt=["Logits"], out="Loss",
               out_slots=["Loss", "WarpCTCGrad"],
               delta=1e-2, rtol=5e-2, atol=1e-3)


def test_fused_lstm_reverse_grad():
    d = 2
    x = _r(5, 4 * d, lo=-0.5, hi=0.5, seed=193)
    w = _r(d, 4 * d, lo=-0.5, hi=0.5, seed=194)
    check_grad("lstm", {"Input": (x, [2, 3]), "Weight": w},
               {"use_peepholes": False, "is_reverse": True},
               wrt=["Input", "Weight"], out="Hidden",
               delta=1e-2, rtol=5e-2, atol=1e-3)


def test_fused_gru_reverse_grad():
    d = 2
    x = _r(5, 3 * d, lo=-0.5, hi=0.5, seed=195)
    w = _r(d, 3 * d, lo=-0.5, hi=0.5, seed=196)
    check_grad("gru", {"Input": (x, [2, 3]), "Weight": w},
               {"is_reverse": True}, wrt=["Input", "Weight"], out="Hidden",
               delta=1e-2, rtol=5e-2, atol=1e-3)


# --------------------------------------------------------------------------
# registry completeness: every registered op must be tested somewhere —
# in the two sweep files or in a named dedicated test file (verified to
# actually mention the op). New ops without tests fail here.
COVERED_ELSEWHERE = {
    # conv/pool/vision — torch-referenced in tests/test_conv_ops.py
    "conv2d": "test_conv_ops.py", "conv2d_transpose": "test_conv_ops.py",
    "depthwise_conv2d": "test_conv_ops.py", "pool2d": "test_conv_ops.py",
    "max_pool2d_with_index": "test_conv_ops.py",
    "unpool": "test_conv_ops.py", "roi_pool": "test_conv_ops.py",
    # sequence family — LoD semantics in tests/test_sequence_ops.py
    "sequence_pool": "test_sequence_ops.py",
    "sequence_first_step": "test_sequence_ops.py",
    "sequence_last_step": "test_sequence_ops.py",
    "sequence_expand": "test_sequence_ops.py",
    "sequence_reshape": "test_sequence_ops.py",
    "sequence_erase": "test_sequence_ops.py",
    "sequence_conv": "test_sequence_ops.py",
    "sequence_pad": "test_sequence_ops.py",
    "sequence_unpad": "test_sequence_ops.py",
    "sequence_softmax": "test_sequence_ops.py",
    # CRF / CTC / detection e2e — tests/test_detection_crf_ctc.py
    "linear_chain_crf": "test_detection_crf_ctc.py",
    "crf_decoding": "test_detection_crf_ctc.py",
    "warpctc": "test_detection_crf_ctc.py",
    "ctc_align": "test_detection_crf_ctc.py",
    "multiclass_nms": "test_detection_crf_ctc.py",
    "chunk_eval": "test_detection_crf_ctc.py",
    "auc": "test_io_and_m2.py",
    # recurrent/control flow — tests/test_control_flow_rnn.py
    "lstm": "test_control_flow_rnn.py", "gru": "test_control_flow_rnn.py",
    "recurrent": "test_control_flow_rnn.py",
    "while": "test_control_flow_rnn.py",
    # beam search — tests/test_beam_search.py
    "beam_search": "test_beam_search.py",
    "beam_search_decode": "test_beam_search.py",
    # rematerialization regions — tests/test_recompute.py
    "recompute_block": "test_recompute.py",
    # parallel/distributed subsystems — dedicated suites
    "sp_attention": "test_parallel_integration.py",
    "moe_ffn": "test_pipeline_moe.py",
    "send": "test_distributed.py", "recv": "test_distributed.py",
    "listen_and_serv": "test_distributed.py",
    "prefetch": "test_distributed.py",
    "split_ids": "test_distributed.py",
    "send_sparse": "test_dist_lookup_table.py",
    "ssd_loss": "test_ssd.py",
    # fused ops (ISSUE 15) — only ever emitted by transform/fusion.py;
    # their lowerings delegate to the component ops covered above, and
    # the fusion tier pins golden rewrites + bitwise execution identity
    "fused_matmul_bias_act": "test_specialize.py",
    "fused_scale_cast": "test_specialize.py",
    # the pre-norm block's pieces, the dropless expert layer and the
    # block-diffusion objective (ISSUE 32), each against jax.numpy
    "rms_norm": "test_block_diffusion.py",
    "rope": "test_block_diffusion.py",
    "qk_norm_rope": "test_block_diffusion.py",
    "mla_attention": "test_latent_moe.py",
    "hyper_connection": "test_latent_moe.py",
    "step_sum": "test_op_ledger.py",
    "causal_attention": "test_windowed_moe.py",
    "sigmoid_mul": "test_windowed_moe.py",
    "diff_attention": "test_hybrid_ssm.py",
    "diff_attn": "test_hybrid_ssm.py",
    "gmu_gate": "test_selective_scan.py",
    "selective_scan": "test_selective_scan.py",
    "ssm_conv": "test_selective_scan.py",
    "ssm_dt": "test_selective_scan.py",
    "ssm_gate": "test_selective_scan.py",
    "gated_short_conv": "test_short_conv.py",
    "gated_delta_rule": "test_delta_rule.py",
    "l2_norm_scale": "test_delta_rule.py",
    "delta_gates": "test_delta_rule.py",
    "gated_rms_norm": "test_delta_rule.py",
    "ssd_scan": "test_ssd_scan.py",
    "gated_group_norm": "test_ssd_scan.py",
    "silu_mul": "test_block_diffusion.py",
    "block_diffusion_noise": "test_block_diffusion.py",
    "block_diffusion_attention": "test_block_diffusion.py",
    "routed_experts": "test_block_diffusion.py",
    # a looped stack (ISSUE 59): the block written out, the reference
    "repeat": "test_looped_lm.py",
    "exit_distribution": "test_looped_lm.py",
}

# ops with no one-op test by design; each entry documents why
EXEMPT = {
    "print": "side-effect op (jax.debug.print); smoke-run only",
    "delete_var": "env mutation only; exercised by While-loop cleanup",
    "range": "requires static (trace-time constant) Start/End/Step; "
             "exercised via layers that emit constant inputs",
    "send_barrier": "emitted by DistributeTranspiler; exercised end-to-end "
                    "by test_distributed.py pserver-mode parity tests",
    "pipeline_stack": "emitted by transformer_lm_parallel(pp>1); exercised "
                      "end-to-end by test_parallel_integration.py "
                      "test_flagship_pp_parity",
}


def test_registry_completeness():
    from paddle_tpu.core import registry
    here = os.path.dirname(os.path.abspath(__file__))
    sweep_text = open(os.path.join(here, "test_ops_sweep.py")).read() + \
        open(os.path.join(here, "test_ops_sweep2.py")).read()
    missing, stale = [], []
    for op in sorted(registry.registered_ops()):
        if op in EXEMPT:
            continue
        if op in COVERED_ELSEWHERE:
            path = os.path.join(here, COVERED_ELSEWHERE[op])
            text = open(path).read()
            # substring, not word-boundary: op names legitimately appear
            # inside test identifiers (test_sp_attention_...)
            if op not in text:
                stale.append("%s -> %s" % (op, COVERED_ELSEWHERE[op]))
            continue
        if not re.search(r'"%s"' % re.escape(op), sweep_text):
            missing.append(op)
    assert not stale, "COVERED_ELSEWHERE entries not found in file: %s" % stale
    assert not missing, (
        "registered ops with no test coverage (add a sweep case or a "
        "COVERED_ELSEWHERE/EXEMPT entry): %s" % missing)


def test_print_op_smoke():
    x = _r(2, 2, seed=178)
    got = run_op("print", {"In": x}, {"message": "sweep"}, ["Out"])
    np.testing.assert_allclose(np.asarray(got["Out"]), x)


def test_positive_negative_pair_op():
    # query 0: labels 2,1 scores 0.9,0.4 -> positive; query 1: labels
    # (2,1),(2,0),(1,0): one wrong order -> 2 pos 1 neg
    score = np.array([[0.9], [0.4], [0.3], [0.7], [0.5]], np.float32)
    label = np.array([[2], [1], [2], [1], [0]], np.float32)
    qid = np.array([[0], [0], [1], [1], [1]], np.int64)
    got = run_op("positive_negative_pair",
                 {"Score": score, "Label": label, "QueryID": qid}, {},
                 ["PositivePair", "NegativePair", "NeutralPair"])
    assert float(np.asarray(got["PositivePair"])[0]) == 2.0
    assert float(np.asarray(got["NegativePair"])[0]) == 2.0
    assert float(np.asarray(got["NeutralPair"])[0]) == 0.0


def test_reorder_lod_tensor_by_rank_op():
    prog = fluid.Program()
    blk = prog.global_block()
    blk.create_var(name="x", shape=(6, 2), dtype="float32", is_data=True,
                   lod_level=1)
    blk.create_var(name="table")
    blk.append_op("lod_rank_table", {"X": ["x"]}, {"Out": ["table"]}, {})
    blk.create_var(name="out")
    blk.append_op("reorder_lod_tensor_by_rank",
                  {"X": ["x"], "RankTable": ["table"]}, {"Out": ["out"]},
                  {})
    x = np.arange(12, dtype=np.float32).reshape(6, 2)
    t = fluid.LoDTensor(x)
    t.set_recursive_sequence_lengths([[2, 4]])
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        out, = exe.run(prog, feed={"x": t}, fetch_list=["out"])
    # longer sequence (rows 2..5) first, then rows 0..1
    want = np.concatenate([x[2:], x[:2]])
    np.testing.assert_allclose(np.asarray(out), want)


def test_positive_negative_pair_weighted():
    score = np.array([[0.9], [0.4]], np.float32)
    label = np.array([[2], [1]], np.float32)
    qid = np.array([[0], [0]], np.int64)
    weight = np.array([[3.0], [1.0]], np.float32)
    got = run_op("positive_negative_pair",
                 {"Score": score, "Label": label, "QueryID": qid,
                  "Weight": weight}, {}, ["PositivePair", "NegativePair"])
    # one correctly-ordered pair with weight (3+1)/2
    assert float(np.asarray(got["PositivePair"])[0]) == 2.0
    assert float(np.asarray(got["NegativePair"])[0]) == 0.0


def test_reorder_lod_tensor_by_rank_rowwise():
    # LoD-less X: rows reorder by the rank table's decreasing-length order
    prog = fluid.Program()
    blk = prog.global_block()
    blk.create_var(name="seq", shape=(5, 1), dtype="float32", is_data=True,
                   lod_level=1)
    blk.create_var(name="x", shape=(2, 3), dtype="float32", is_data=True)
    blk.create_var(name="table")
    blk.append_op("lod_rank_table", {"X": ["seq"]}, {"Out": ["table"]}, {})
    blk.create_var(name="out")
    blk.append_op("reorder_lod_tensor_by_rank",
                  {"X": ["x"], "RankTable": ["table"]}, {"Out": ["out"]},
                  {})
    seq = fluid.LoDTensor(np.zeros((5, 1), np.float32))
    seq.set_recursive_sequence_lengths([[2, 3]])
    x = np.array([[1, 1, 1], [2, 2, 2]], np.float32)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        out, = exe.run(prog, feed={"seq": seq, "x": x},
                       fetch_list=["out"])
    np.testing.assert_allclose(np.asarray(out), x[[1, 0]])


def test_positive_negative_pair_chunked_matches_direct():
    # >2048 rows exercises the chunked [chunk, N] path; counts must match
    # the direct computation
    rng = np.random.RandomState(7)
    n = 2500
    score = rng.rand(n, 1).astype(np.float32)
    label = rng.randint(0, 3, (n, 1)).astype(np.float32)
    qid = rng.randint(0, 50, (n, 1)).astype(np.int64)
    got = run_op("positive_negative_pair",
                 {"Score": score, "Label": label, "QueryID": qid}, {},
                 ["PositivePair", "NegativePair", "NeutralPair"])
    s, l, q = score.ravel(), label.ravel(), qid.ravel()
    pos = neg = 0
    for i in range(n):
        same = (q == q[i]) & (l[i] > l)
        pos += int(np.sum(same & (s[i] > s)))
        neg += int(np.sum(same & (s[i] < s)))
    assert float(np.asarray(got["PositivePair"])[0]) == pos
    assert float(np.asarray(got["NegativePair"])[0]) == neg
