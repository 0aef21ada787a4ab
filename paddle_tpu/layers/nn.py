"""Neural-network layers.

Reference parity: python/paddle/fluid/layers/nn.py (~60 layers). Each builds
graph ops through LayerHelper; the heavy lifting happens in the op lowerings
(paddle_tpu/ops/*) at trace time.
"""

import numpy as np

from ..core.program import Variable
from .layer_helper import LayerHelper


def _prod(xs):
    out = 1
    for x in xs:
        out *= int(x)
    return out


# ---------------------------------------------------------------------------
# dense / embedding
# ---------------------------------------------------------------------------

def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None,
       act=None, is_test=False, name=None):
    """Fully connected (nn.py fc). Multiple inputs are each matmul'd then
    summed, like the reference."""
    helper = LayerHelper("fc", input=input, param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    inputs = input if isinstance(input, (list, tuple)) else [input]
    param_attrs = helper.param_attr
    if not isinstance(param_attrs, (list, tuple)):
        param_attrs = [param_attrs] * len(inputs)

    mul_results = []
    for x, pattr in zip(inputs, param_attrs):
        in_features = _prod(x.shape[num_flatten_dims:])
        w = helper.create_parameter(pattr, shape=[in_features, size],
                                    dtype=x.dtype)
        out_shape = tuple(x.shape[:num_flatten_dims]) + (size,)
        tmp = helper.create_variable_for_type_inference(x.dtype,
                                                        shape=out_shape)
        helper.append_op(
            type="mul", inputs={"X": [x], "Y": [w]}, outputs={"Out": [tmp]},
            attrs={"x_num_col_dims": num_flatten_dims, "y_num_col_dims": 1})
        mul_results.append(tmp)

    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_variable_for_type_inference(
            mul_results[0].dtype, shape=mul_results[0].shape)
        helper.append_op(type="sum", inputs={"X": mul_results},
                         outputs={"Out": [pre_bias]})
    pre_act = helper.append_bias_op(pre_bias, dim_start=num_flatten_dims)
    return helper.append_activation(pre_act)


def embedding(input, size, is_sparse=False, is_distributed=False,
              padding_idx=None, param_attr=None, dtype="float32", name=None):
    helper = LayerHelper("embedding", param_attr=param_attr, name=name)
    w = helper.create_parameter(helper.param_attr, shape=list(size),
                                dtype=dtype)
    in_shape = tuple(input.shape) if input.shape else (-1,)
    if in_shape and in_shape[-1] == 1:
        in_shape = in_shape[:-1]
    out = helper.create_variable_for_type_inference(
        dtype, shape=in_shape + (size[1],))
    helper.append_op(
        type="lookup_table", inputs={"W": [w], "Ids": [input]},
        outputs={"Out": [out]},
        attrs={"is_sparse": is_sparse, "is_distributed": is_distributed,
               "padding_idx": -1 if padding_idx is None else padding_idx})
    return out


# ---------------------------------------------------------------------------
# losses / classification heads
# ---------------------------------------------------------------------------

def softmax(input, use_cudnn=True, name=None):
    helper = LayerHelper("softmax", name=name)
    out = helper.create_variable_for_type_inference(input.dtype,
                                                    shape=input.shape)
    helper.append_op(type="softmax", inputs={"X": [input]},
                     outputs={"Out": [out]})
    return out


def cross_entropy(input, label, soft_label=False, ignore_index=-100):
    helper = LayerHelper("cross_entropy")
    shape = tuple(input.shape[:-1]) + (1,) if input.shape else None
    out = helper.create_variable_for_type_inference(input.dtype, shape=shape)
    helper.append_op(type="cross_entropy",
                     inputs={"X": [input], "Label": [label]},
                     outputs={"Y": [out]},
                     attrs={"soft_label": soft_label,
                            "ignore_index": ignore_index})
    return out


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               return_softmax=False):
    helper = LayerHelper("softmax_with_cross_entropy")
    sm = helper.create_variable_for_type_inference(logits.dtype,
                                                   shape=logits.shape)
    shape = tuple(logits.shape[:-1]) + (1,) if logits.shape else None
    loss = helper.create_variable_for_type_inference(logits.dtype,
                                                     shape=shape)
    helper.append_op(type="softmax_with_cross_entropy",
                     inputs={"Logits": [logits], "Label": [label]},
                     outputs={"Softmax": [sm], "Loss": [loss]},
                     attrs={"soft_label": soft_label})
    if return_softmax:
        return loss, sm
    return loss


def sigmoid_cross_entropy_with_logits(x, label, name=None):
    helper = LayerHelper("sigmoid_cross_entropy_with_logits", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    helper.append_op(type="sigmoid_cross_entropy_with_logits",
                     inputs={"X": [x], "Label": [label]},
                     outputs={"Out": [out]})
    return out


def square_error_cost(input, label):
    helper = LayerHelper("square_error_cost")
    minus = helper.create_variable_for_type_inference(input.dtype,
                                                      shape=input.shape)
    helper.append_op(type="elementwise_sub",
                     inputs={"X": [input], "Y": [label]},
                     outputs={"Out": [minus]})
    out = helper.create_variable_for_type_inference(input.dtype,
                                                    shape=input.shape)
    helper.append_op(type="square", inputs={"X": [minus]},
                     outputs={"Out": [out]})
    return out


def mean(x, name=None):
    helper = LayerHelper("mean", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, shape=())
    helper.append_op(type="mean", inputs={"X": [x]}, outputs={"Out": [out]})
    return out


def accuracy(input, label, k=1, correct=None, total=None):
    helper = LayerHelper("accuracy")
    topk_out = helper.create_variable_for_type_inference(
        input.dtype, shape=tuple(input.shape[:-1]) + (k,))
    topk_idx = helper.create_variable_for_type_inference(
        "int64", shape=tuple(input.shape[:-1]) + (k,))
    helper.append_op(type="top_k", inputs={"X": [input]},
                     outputs={"Out": [topk_out], "Indices": [topk_idx]},
                     attrs={"k": k})
    acc_out = helper.create_variable_for_type_inference("float32", shape=(1,))
    correct = correct or helper.create_variable_for_type_inference(
        "int64", shape=(1,))
    total = total or helper.create_variable_for_type_inference(
        "int64", shape=(1,))
    helper.append_op(type="accuracy",
                     inputs={"Out": [topk_out], "Indices": [topk_idx],
                             "Label": [label]},
                     outputs={"Accuracy": [acc_out], "Correct": [correct],
                              "Total": [total]})
    return acc_out


def auc(input, label, curve="ROC", num_thresholds=200, topk=1):
    helper = LayerHelper("auc")
    out = helper.create_variable_for_type_inference("float32", shape=(1,))
    helper.append_op(type="auc",
                     inputs={"Out": [input], "Label": [label]},
                     outputs={"AUC": [out]},
                     attrs={"curve": curve,
                            "num_thresholds": num_thresholds})
    return out


# ---------------------------------------------------------------------------
# regularization-ish layers
# ---------------------------------------------------------------------------

def dropout(x, dropout_prob, is_test=False, seed=None, name=None,
            dropout_implementation="downgrade_in_infer"):
    helper = LayerHelper("dropout", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    mask = helper.create_variable_for_type_inference(
        x.dtype, shape=x.shape, stop_gradient=True)
    helper.append_op(
        type="dropout", inputs={"X": [x]},
        outputs={"Out": [out], "Mask": [mask]},
        attrs={"dropout_prob": dropout_prob, "is_test": is_test,
               "seed": seed if seed is not None else 0,
               "dropout_implementation": dropout_implementation})
    return out


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5,
               param_attr=None, bias_attr=None, data_layout="NCHW",
               in_place=False, name=None, moving_mean_name=None,
               moving_variance_name=None, do_model_average_for_mean_and_var=False):
    from ..initializer import ConstantInitializer
    helper = LayerHelper("batch_norm", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    ch = (input.shape[1] if data_layout == "NCHW" and len(input.shape) > 1
          else input.shape[-1])
    pshape = [ch]
    scale = helper.create_parameter(
        helper.param_attr, shape=pshape, dtype=dtype,
        default_initializer=ConstantInitializer(1.0))
    bias = helper.create_parameter(helper.bias_attr, shape=pshape,
                                   dtype=dtype, is_bias=True)
    mean = helper.create_parameter(
        _nt_attr(moving_mean_name), shape=pshape, dtype=dtype,
        default_initializer=ConstantInitializer(0.0))
    mean.stop_gradient = True
    variance = helper.create_parameter(
        _nt_attr(moving_variance_name), shape=pshape, dtype=dtype,
        default_initializer=ConstantInitializer(1.0))
    variance.stop_gradient = True

    saved_mean = helper.create_variable_for_type_inference(
        dtype, shape=pshape, stop_gradient=True)
    saved_var = helper.create_variable_for_type_inference(
        dtype, shape=pshape, stop_gradient=True)
    out = helper.create_variable_for_type_inference(dtype, shape=input.shape)

    helper.append_op(
        type="batch_norm",
        inputs={"X": [input], "Scale": [scale], "Bias": [bias],
                "Mean": [mean], "Variance": [variance]},
        outputs={"Y": [out], "MeanOut": [mean], "VarianceOut": [variance],
                 "SavedMean": [saved_mean], "SavedVariance": [saved_var]},
        attrs={"momentum": momentum, "epsilon": epsilon,
               "is_test": is_test, "data_layout": data_layout})
    return helper.append_activation(out)


def _nt_attr(name):
    from ..param_attr import ParamAttr
    a = ParamAttr(name=name, trainable=False)
    return a


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1,
               epsilon=1e-05, param_attr=None, bias_attr=None, act=None,
               name=None):
    from ..initializer import ConstantInitializer
    helper = LayerHelper("layer_norm", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    pshape = [_prod(input.shape[begin_norm_axis:])]
    inputs = {"X": [input]}
    if scale:
        s = helper.create_parameter(
            helper.param_attr, shape=pshape, dtype=dtype,
            default_initializer=ConstantInitializer(1.0))
        inputs["Scale"] = [s]
    if shift:
        b = helper.create_parameter(helper.bias_attr, shape=pshape,
                                    dtype=dtype, is_bias=True)
        inputs["Bias"] = [b]
    mean = helper.create_variable_for_type_inference(
        dtype, shape=input.shape[:begin_norm_axis], stop_gradient=True)
    var = helper.create_variable_for_type_inference(
        dtype, shape=input.shape[:begin_norm_axis], stop_gradient=True)
    out = helper.create_variable_for_type_inference(dtype, shape=input.shape)
    helper.append_op(
        type="layer_norm", inputs=inputs,
        outputs={"Y": [out], "Mean": [mean], "Variance": [var]},
        attrs={"epsilon": epsilon, "begin_norm_axis": begin_norm_axis})
    return helper.append_activation(out)


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    helper = LayerHelper("l2_normalize", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    norm = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    helper.append_op(type="norm", inputs={"X": [x]},
                     outputs={"Out": [out], "Norm": [norm]},
                     attrs={"axis": 1 if axis is None else axis,
                            "epsilon": epsilon})
    return out


# ---------------------------------------------------------------------------
# matmul / misc
# ---------------------------------------------------------------------------

def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None):
    helper = LayerHelper("matmul", name=name)
    xs = list(x.shape) if x.shape else None
    ys = list(y.shape) if y.shape else None
    shape = None
    if xs and ys:
        a = xs[:-2] + [xs[-1], xs[-2]] if transpose_x else list(xs)
        b = ys[:-2] + [ys[-1], ys[-2]] if transpose_y else list(ys)
        shape = tuple(a[:-1] + b[-1:])
    out = helper.create_variable_for_type_inference(x.dtype, shape=shape)
    helper.append_op(type="matmul", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]},
                     attrs={"transpose_X": transpose_x,
                            "transpose_Y": transpose_y, "alpha": alpha})
    return out


def topk(input, k, name=None):
    helper = LayerHelper("top_k", name=name)
    shape = tuple(input.shape[:-1]) + (k,) if input.shape else None
    values = helper.create_variable_for_type_inference(input.dtype,
                                                       shape=shape)
    indices = helper.create_variable_for_type_inference("int64", shape=shape)
    helper.append_op(type="top_k", inputs={"X": [input]},
                     outputs={"Out": [values], "Indices": [indices]},
                     attrs={"k": k})
    return values, indices


def clip(x, min, max, name=None):
    helper = LayerHelper("clip", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    helper.append_op(type="clip", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"min": min, "max": max})
    return out


def clip_by_norm(x, max_norm, name=None):
    helper = LayerHelper("clip_by_norm", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    helper.append_op(type="clip_by_norm", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs={"max_norm": max_norm})
    return out


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32",
                 name=None):
    helper = LayerHelper("label_smooth", name=name)
    out = helper.create_variable_for_type_inference(dtype, shape=label.shape)
    inputs = {"X": [label]}
    if prior_dist is not None:
        inputs["PriorDist"] = [prior_dist]
    helper.append_op(type="label_smooth", inputs=inputs,
                     outputs={"Out": [out]}, attrs={"epsilon": epsilon})
    return out


def one_hot(input, depth, name=None):
    helper = LayerHelper("one_hot", name=name)
    shape = input.shape
    if shape and shape[-1] == 1:
        shape = shape[:-1]
    out = helper.create_variable_for_type_inference(
        "float32", shape=tuple(shape or ()) + (depth,))
    helper.append_op(type="one_hot", inputs={"X": [input]},
                     outputs={"Out": [out]}, attrs={"depth": depth})
    return out


def reduce_op_layer(op_type):
    def layer(input, dim=None, keep_dim=False, name=None):
        helper = LayerHelper(op_type, name=name)
        reduce_all = dim is None
        if dim is None:
            dims = [0]
            shape = ()
        else:
            dims = [dim] if isinstance(dim, int) else list(dim)
            if input.shape is not None:
                nd = len(input.shape)
                axes = {d % nd for d in dims}
                if keep_dim:
                    shape = tuple(1 if i in axes else s
                                  for i, s in enumerate(input.shape))
                else:
                    shape = tuple(s for i, s in enumerate(input.shape)
                                  if i not in axes)
            else:
                shape = None
        out = helper.create_variable_for_type_inference(input.dtype,
                                                        shape=shape)
        helper.append_op(type=op_type, inputs={"X": [input]},
                         outputs={"Out": [out]},
                         attrs={"dim": dims, "keep_dim": keep_dim,
                                "reduce_all": reduce_all})
        return out
    layer.__name__ = op_type
    return layer


reduce_sum = reduce_op_layer("reduce_sum")
reduce_mean = reduce_op_layer("reduce_mean")
reduce_max = reduce_op_layer("reduce_max")
reduce_min = reduce_op_layer("reduce_min")
reduce_prod = reduce_op_layer("reduce_prod")


# ---------------------------------------------------------------------------
# CRF layers (python/paddle/fluid/layers/nn.py linear_chain_crf/crf_decoding)
# ---------------------------------------------------------------------------

def linear_chain_crf(input, label, param_attr=None, name=None):
    """CRF negative log-likelihood over emission `input` [T, D] (LoD).

    Creates the Transition parameter [D+2, D] (row 0 start, row 1 end,
    rows 2.. transitions — linear_chain_crf_op.cc layout) and returns the
    per-sequence NLL [N, 1]; train with mean(nll)."""
    helper = LayerHelper("linear_chain_crf", param_attr=param_attr,
                         name=name)
    size = input.shape[-1]
    transition = helper.create_parameter(
        helper.param_attr, shape=[size + 2, size], dtype=input.dtype)
    ll = helper.create_variable_for_type_inference(input.dtype)
    alpha = helper.create_variable_for_type_inference(input.dtype)
    eexp = helper.create_variable_for_type_inference(input.dtype)
    texp = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="linear_chain_crf",
        inputs={"Emission": [input], "Label": [label],
                "Transition": [transition]},
        outputs={"LogLikelihood": [ll], "Alpha": [alpha],
                 "EmissionExps": [eexp], "TransitionExps": [texp]})
    return ll


def crf_decoding(input, param_attr=None, name=None, label=None):
    """Viterbi decode against the transition parameter created by
    linear_chain_crf (share via param_attr name)."""
    helper = LayerHelper("crf_decoding", param_attr=param_attr, name=name)
    size = input.shape[-1]
    transition = helper.create_parameter(
        helper.param_attr, shape=[size + 2, size], dtype=input.dtype)
    path = helper.create_variable_for_type_inference("int64")
    inputs = {"Emission": [input], "Transition": [transition]}
    if label is not None:
        inputs["Label"] = [label]
    helper.append_op(type="crf_decoding", inputs=inputs,
                     outputs={"ViterbiPath": [path]})
    return path


def cos_sim(X, Y, name=None):
    """Row-wise cosine similarity (operators/cos_sim_op.cc)."""
    helper = LayerHelper("cos_sim", name=name)
    out = helper.create_variable_for_type_inference(
        X.dtype, shape=(X.shape[0] if X.shape else -1, 1))
    xn = helper.create_variable_for_type_inference(X.dtype)
    yn = helper.create_variable_for_type_inference(X.dtype)
    helper.append_op(type="cos_sim", inputs={"X": [X], "Y": [Y]},
                     outputs={"Out": [out], "XNorm": [xn], "YNorm": [yn]})
    return out


# ---------------------------------------------------------------------------
# the pre-norm block's pieces and block-diffusion training (ISSUE 32)
# ---------------------------------------------------------------------------

def _norm_weight(helper, size):
    """An RMSNorm's learned weight ``[size]``, float32, ones."""
    from ..initializer import ConstantInitializer
    return helper.create_parameter(
        helper.param_attr, shape=[size], dtype="float32",
        default_initializer=ConstantInitializer(1.0))


def rms_norm(input, epsilon=1e-6, groups=1, param_attr=None, name=None):
    """RMSNorm over the last dimension with a learned weight, computed
    in float32: ``x * rsqrt(mean(x^2) + eps) * w``. `groups` G > 1
    normalises each of G equal parts of the last dimension with ONE
    weight of their size: QK-norm over the heads of a projection's
    output ``[B, T, H * D]`` with G = H."""
    helper = LayerHelper("rms_norm", param_attr=param_attr, name=name)
    scale = _norm_weight(helper, input.shape[-1] // groups)
    out = helper.create_variable_for_type_inference(input.dtype,
                                                    shape=input.shape)
    helper.append_op(type="rms_norm", inputs={"X": [input],
                                              "Scale": [scale]},
                     outputs={"Out": [out]}, attrs={"epsilon": epsilon})
    return out


def exit_distribution(gates, name=None):
    """The log of a looped model's exit distribution from its exit
    gate's logits `gates` ``[R, ...]``, visit by visit, float32: ``log
    p_t = log sigmoid(g_t) + sum_{j<t} log(1 - sigmoid(g_j))`` for t <
    R, and the remainder ``sum_{j<R} log(1 - sigmoid(g_j))`` at R."""
    helper = LayerHelper("exit_distribution", name=name)
    out = helper.create_variable_for_type_inference("float32",
                                                    shape=gates.shape)
    helper.append_op(type="exit_distribution", inputs={"X": [gates]},
                     outputs={"Out": [out]})
    return out


def rope(input, n_head, theta=10000.0, wrap=0, name=None):
    """Rotary position embedding (rotate-half form) of ``[B, T, H * D]``
    by each row's position: its index in T, modulo `wrap` where given
    (two halves of T that share positions 0..wrap-1)."""
    helper = LayerHelper("rope", name=name)
    out = helper.create_variable_for_type_inference(input.dtype,
                                                    shape=input.shape)
    helper.append_op(type="rope", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"n_head": int(n_head), "theta": float(theta),
                            "wrap": int(wrap)})
    return out


def qk_norm_rope(input, n_head, theta=10000.0, wrap=0, epsilon=1e-6,
                 param_attr=None, name=None, rotate=True):
    """``rope(rms_norm(input, groups=n_head), n_head, theta, wrap)`` as
    ONE op on ``[B, T, H * D]``: QK-norm under one learned weight ``[D]``
    (named by `param_attr`, as ``rms_norm``'s) and the rotary embedding
    of each head, float32 from end to end, in the layout a projection
    leaves (``ops/rotary.py``). `rotate` False leaves the rotation out:
    the QK-norm of a layer that carries no position signal, under the
    same op type."""
    helper = LayerHelper("qk_norm_rope", param_attr=param_attr, name=name)
    scale = _norm_weight(helper, input.shape[-1] // n_head)
    out = helper.create_variable_for_type_inference(input.dtype,
                                                    shape=input.shape)
    helper.append_op(type="qk_norm_rope",
                     inputs={"X": [input], "Scale": [scale]},
                     outputs={"Out": [out]},
                     attrs={"n_head": int(n_head), "theta": float(theta),
                            "wrap": int(wrap), "epsilon": epsilon,
                            **({} if rotate else {"rotate": False})})
    return out


def causal_attention(q, k, v, n_head, n_kv_head, window=0, scale=0.0,
                     name=None):
    """Causal attention over grouped heads (``ops/causal_attention.py``):
    q ``[B, T, H * D]``, k and v ``[B, T, Hkv * D]``, query head h
    reading key/value head ``h // (H / Hkv)``; under `window` w > 0 a
    query sees its own key and the w - 1 before it, and the flash
    kernels do not walk the key blocks under that band. Returns a
    variable of q's shape."""
    helper = LayerHelper("causal_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype, shape=q.shape)
    helper.append_op(
        type="causal_attention",
        inputs={"Q": [q], "K": [k], "V": [v]}, outputs={"Out": [out]},
        attrs={"n_head": int(n_head), "n_kv_head": int(n_kv_head),
               "window": int(window), "scale": float(scale)})
    return out


def sigmoid_mul(x, y, name=None):
    """``x * sigmoid(y)``: an output gate."""
    helper = LayerHelper("sigmoid_mul", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    helper.append_op(type="sigmoid_mul", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]})
    return out


def silu_mul(x, y, name=None):
    """``silu(x) * y``: the gated FFN's hidden activation."""
    helper = LayerHelper("silu_mul", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    helper.append_op(type="silu_mul", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]})
    return out


def block_diffusion_noise(tokens, block, mask_id, name=None):
    """Block-diffusion noise of ``tokens`` [B, L]: ``(noised, weight,
    step)``. Each block of `block` tokens draws t ~ U(1e-3, 1) and each
    of its tokens becomes `mask_id` with probability t; ``weight`` is
    1/t where masked, else 0. The draw is a pure function of the salt
    (the program's ``random_seed``, kept as a persistable variable
    ``<name>.salt``), the persistable ``step`` counter, which every TRAIN run of the program advances (a
    ``for_test`` clone does not), and the batch row
    (``ops/block_diffusion.draw_noise``)."""
    from .tensor import create_global_var
    helper = LayerHelper("block_diffusion_noise", name=name)
    step = create_global_var([1], 0, "int32", persistable=True,
                             name=helper.name + ".step")
    salt = create_global_var(
        [1], int(helper.main_program.random_seed) % 2 ** 31, "int32",
        persistable=True, name=helper.name + ".salt")
    noised = helper.create_variable_for_type_inference(
        tokens.dtype, shape=tokens.shape, stop_gradient=True)
    weight = helper.create_variable_for_type_inference(
        "float32", shape=tokens.shape, stop_gradient=True)
    helper.append_op(
        type="block_diffusion_noise",
        inputs={"X": [tokens], "Salt": [salt], "Step": [step]},
        outputs={"Noised": [noised], "Weight": [weight], "StepOut": [step]},
        attrs={"block": int(block), "mask_id": int(mask_id)})
    return noised, weight, step


def block_diffusion_attention(q, k, v, n_head, n_kv_head, block, scale=0.0,
                              name=None):
    """Attention over ``[noised; clean]`` rows under the block-diffusion
    training mask (``ops/block_diffusion.py``): q ``[B, 2L, H * D]``, k
    and v ``[B, 2L, Hkv * D]``, query head h reading key/value head
    ``h // (H / Hkv)``. Returns a variable of q's shape."""
    helper = LayerHelper("block_diffusion_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype, shape=q.shape)
    helper.append_op(
        type="block_diffusion_attention",
        inputs={"Q": [q], "K": [k], "V": [v]}, outputs={"Out": [out]},
        attrs={"n_head": int(n_head), "n_kv_head": int(n_kv_head),
               "block": int(block), "scale": float(scale)})
    return out


def hyper_connection(x, lanes, stage, y=None, coefficients=None,
                     sinkhorn_iters=20, sinkhorn_eps=1e-6,
                     clamp=(-30.0, 30.0), epsilon=1e-6, alpha_init=0.01,
                     res_diagonal=4.0, name=None):
    """One stage of a residual stream of `lanes` lanes ``[B, T, n * d]``
    under manifold-constrained hyper-connections
    (``ops/hyper_connection.py``), all in float32:

    * ``"widen"``: x ``[B, T, d]`` copied to the n lanes;
    * ``"mix"``: the stream's coefficients and the sublayer's input:
      returns ``(H_pre X [B, T, d], (post, res, through))``, what to hand
      to "merge": the coefficients and the stream itself, which "mix"
      passes through so that the two stages' gradients to it are summed
      where "mix"'s is made (``ops/hyper_connection.py``). Parameters
      ``<name>.proj`` [n d, n (n + 2)] (N(0, 0.02)), ``<name>.alpha`` [3]
      (`alpha_init`) and ``<name>.bias`` [n (n + 2)] (zero but
      `res_diagonal` on the residual mix's diagonal, so that it starts
      near the identity);
    * ``"merge"``: ``H_res X + H_post^T y`` for the sublayer's output y
      ``[B, T, d]`` and `coefficients` from "mix" (the stream is read
      from them where they carry it, else from x);
    * ``"narrow"``: the lanes summed, ``[B, T, d]``."""
    from ..initializer import (ConstantInitializer, NormalInitializer,
                               NumpyArrayInitializer)
    from ..param_attr import ParamAttr
    helper = LayerHelper("hyper_connection", name=name)
    n = int(lanes)
    lead, width = tuple(x.shape[:-1]), int(x.shape[-1])
    attrs = {"stage": stage, "lanes": n}
    new = lambda shape: helper.create_variable_for_type_inference(
        "float32", shape=shape)
    if stage in ("widen", "narrow"):
        out = new(lead + (width * n if stage == "widen" else width // n,))
        helper.append_op(type="hyper_connection", inputs={"X": [x]},
                         outputs={"Out": [out]}, attrs=attrs)
        return out
    if stage == "mix":
        param = lambda suffix, shape, init: helper.create_parameter(
            ParamAttr(name="%s.%s" % (helper.name, suffix)), shape=shape,
            dtype="float32", default_initializer=init)
        bias = np.zeros(n * (n + 2), np.float32)
        bias[2 * n:] = res_diagonal * np.eye(n, dtype=np.float32).reshape(-1)
        proj = param("proj", [width, n * (n + 2)], NormalInitializer(0., 0.02))
        alpha = param("alpha", [3], ConstantInitializer(alpha_init))
        bias = param("bias", [n * (n + 2)], NumpyArrayInitializer(bias))
        out = new(lead + (width // n,))
        rows = int(np.prod(lead)) if all(int(s) > 0 for s in lead) else -1
        post, res = new((rows, n)), new((n, n, rows))
        through = new(lead + (width,))
        attrs.update(sinkhorn_iters=int(sinkhorn_iters),
                     sinkhorn_eps=float(sinkhorn_eps),
                     clamp_min=float(clamp[0]), clamp_max=float(clamp[1]),
                     epsilon=float(epsilon))
        helper.append_op(
            type="hyper_connection",
            inputs={"X": [x], "Proj": [proj], "Alpha": [alpha],
                    "Bias": [bias]},
            outputs={"Out": [out], "Post": [post], "Res": [res],
                     "Through": [through]}, attrs=attrs)
        return out, (post, res, through)
    if stage != "merge":
        raise ValueError("hyper_connection: no stage %r" % (stage,))
    post, res, x = (tuple(coefficients) + (x,))[:3]
    out = new(lead + (width,))
    helper.append_op(
        type="hyper_connection",
        inputs={"X": [x], "Post": [post], "Res": [res], "Y": [y]},
        outputs={"Out": [out]}, attrs=attrs)
    return out


def mla_attention(q_nope, q_pe, k_nope, k_pe, v, n_head, inv_freq, scale,
                  name=None):
    """Causal latent attention over the five projections as they come
    (``ops/latent_attention.py``): q_nope, k_nope, v ``[B, T, H * D]``,
    q_pe ``[B, T, H * Dr]`` and ONE rotary key k_pe ``[B, T, Dr]`` that
    every head reads; q_pe and k_pe are turned by their rows' positions
    with the given frequencies `inv_freq` (Dr / 2 floats), and a score
    is ``(q_nope k_nope^T + q_pe k_pe^T) * scale``. Returns a variable
    of v's shape."""
    helper = LayerHelper("mla_attention", name=name)
    out = helper.create_variable_for_type_inference(v.dtype, shape=v.shape)
    helper.append_op(
        type="mla_attention",
        inputs={"QNope": [q_nope], "QPe": [q_pe], "KNope": [k_nope],
                "KPe": [k_pe], "V": [v]},
        outputs={"Out": [out]},
        attrs={"n_head": int(n_head), "scale": float(scale),
               "inv_freq": [float(f) for f in inv_freq]})
    return out
