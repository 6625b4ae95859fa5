"""Neural-network layers DSL (reference: python/paddle/fluid/layers/nn.py —
fc :81, embedding :188, conv2d :1120, pool2d :1425, batch_norm :1478,
layer_norm :1567, dropout :846, cross_entropy :892, reduces :2055-2239,
matmul :2428, softmax_with_cross_entropy :3135, one_hot :3254 …)."""

from __future__ import annotations

import functools
import math

import numpy as np

from ..framework.framework import Variable, name_scope
from ..initializer import (ConstantInitializer, LogOfUniformInitializer,
                           NormalInitializer,
                           SoftplusInverseLogUniformInitializer,
                           ValuesInitializer)
from ..layer_helper import LayerHelper

__all__ = [
    "fc", "embedding", "dropout", "cross_entropy", "square_error_cost",
    "conv2d", "conv3d", "conv2d_transpose", "pool2d", "batch_norm",
    "layer_norm", "softmax_with_cross_entropy", "accuracy",
    "auc", "mean", "mul", "elementwise_add", "elementwise_sub",
    "elementwise_mul", "elementwise_div", "elementwise_max", "elementwise_min",
    "elementwise_pow", "reduce_sum", "reduce_mean", "reduce_max", "reduce_min",
    "reduce_prod", "matmul", "transpose", "reverse", "reshape", "split",
    "topk",
    "one_hot", "lrn", "l2_normalize", "clip", "clip_by_norm", "scale",
    "cast", "dropout", "autoincreased_step_counter", "smooth_l1", "log_loss",
    "label_smooth", "cos_sim", "expand", "squeeze", "unsqueeze", "gather",
    "scatter", "pad", "nce", "row_conv", "im2sequence", "multiplex",
    "sigmoid_cross_entropy_with_logits", "maxout",
    "linear_chain_crf", "crf_decoding", "beam_search", "beam_search_decode",
    "warpctc", "ctc_greedy_decoder", "ctc_align", "edit_distance", "chunk_eval",
    "precision_recall", "positive_negative_pair", "pool3d", "roi_pool",
    "prelu", "crop", "spp", "unpool", "conv3d_transpose",
    "max_pool2d_with_index", "conv_shift", "l1_norm",
    "fused_attention", "block_diffusion_attention", "sparse_moe", "rms_norm",
    "mamba2_mixer", "kda_mixer", "gdn_mixer", "short_conv_mixer",
    "moe_block",
    "rotary_embedding", "gated_mlp", "latent_attention", "mtp_block",
    "hyper_connection_maps", "sinkhorn_knopp", "hc_pre_mix",
    "hc_post_res_mix",

    "hsigmoid", "bilinear_interp", "selective_fc",
]


def _simple(op_type, x, attrs=None, extra_inputs=None, out_dtype=None,
            name=None, outs=("Out",), in_slot="X"):
    helper = LayerHelper(op_type, name=name)
    inputs = {in_slot: [x]}
    if extra_inputs:
        inputs.update({k: v if isinstance(v, list) else [v]
                       for k, v in extra_inputs.items() if v is not None})
    outvars = [helper.create_tmp_variable(dtype=out_dtype or x.dtype)
               for _ in outs]
    helper.append_op(type=op_type, inputs=inputs,
                     outputs={s: [v] for s, v in zip(outs, outvars)},
                     attrs=attrs or {})
    return outvars[0] if len(outvars) == 1 else tuple(outvars)


# --- fully connected --------------------------------------------------------

def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None,
       use_mkldnn=False, act=None, is_test=False, name=None):
    """Fully connected layer (reference nn.py:81): out = act(sum_i X_i W_i + b).
    Lowers to `mul` (MXU matmul) + broadcast add; XLA fuses bias+activation."""
    helper = LayerHelper("fc", input=input, param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = helper.input_dtype()
    mul_results = []
    for input_var, p_attr in helper.iter_inputs_and_params():
        input_shape = input_var.shape
        param_shape = [
            int(np.prod(input_shape[num_flatten_dims:]))
        ] + [size]
        w = helper.create_parameter(attr=p_attr, shape=param_shape,
                                    dtype=dtype, is_bias=False)
        tmp = helper.create_tmp_variable(dtype)
        helper.append_op(type="mul",
                         inputs={"X": [input_var], "Y": [w]},
                         outputs={"Out": [tmp]},
                         attrs={"x_num_col_dims": num_flatten_dims,
                                "y_num_col_dims": 1})
        mul_results.append(tmp)
    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_tmp_variable(dtype)
        helper.append_op(type="sum", inputs={"X": mul_results},
                         outputs={"Out": [pre_bias]})
    pre_act = helper.append_bias_op(pre_bias, dim_start=num_flatten_dims)
    return helper.append_activation(pre_act)


def embedding(input, size, is_sparse=False, is_distributed=False,
              padding_idx=None, param_attr=None, dtype="float32",
              shard_axis=None, cache_rows=None):
    """Embedding lookup (reference nn.py:188).

    is_sparse=True keeps the gradient a SelectedRows value end-to-end:
    lookup_table_grad emits (rows, values) and the sgd/momentum/adam
    scatter-apply kernels (ops/sparse_ops.py) update only the touched
    rows — the table never materializes a dense gradient.

    is_distributed (the reference's pserver-sharded table) maps to
    row-sharding the table over the program's mesh: the table partitions
    over `shard_axis` (default PADDLE_TPU_EMB_SHARD_AXIS, "fsdp") and
    lookups mod-shard-route ids under pd.coll.emb_lookup. Pass
    shard_axis explicitly (an axis name or tuple) to shard without the
    is_distributed flag.

    cache_rows=N opts the table into the beyond-HBM hot-row cache
    (parallel/emb_cache.py): only N rows live on device, the full table
    stays authoritative in host DRAM, and ids remap to cache slots at
    feed time. The request is recorded here; emb_cache.enable(program)
    activates it after the startup program ran (requires is_sparse=True
    and is mutually exclusive with sharding/padding_idx)."""
    helper = LayerHelper("embedding", param_attr=param_attr)
    w = helper.create_parameter(attr=helper.param_attr, shape=size,
                                dtype=dtype, is_bias=False)
    tmp = helper.create_tmp_variable(dtype)
    padding_idx = -1 if padding_idx is None else (
        padding_idx if padding_idx >= 0 else size[0] + padding_idx)
    helper.append_op(type="lookup_table",
                     inputs={"W": [w], "Ids": [input]},
                     outputs={"Out": [tmp]},
                     attrs={"is_sparse": is_sparse, "padding_idx": padding_idx})
    if shard_axis is not None or is_distributed:
        from ..parallel import embedding as embedding_mod
        embedding_mod.shard_table(helper.main_program, w.name, shard_axis)
    if cache_rows is not None:
        from ..parallel import emb_cache as emb_cache_mod
        emb_cache_mod.request_cache(helper.main_program, w.name,
                                    cache_rows)
    return tmp


# --- losses -----------------------------------------------------------------

def cross_entropy(input, label, soft_label=False):
    return _simple("cross_entropy", input,
                   attrs={"soft_label": soft_label},
                   extra_inputs={"Label": label}, outs=("Y",))


def square_error_cost(input, label):
    """(input - label)^2, built from elementwise ops (reference nn.py:965)."""
    helper = LayerHelper("square_error_cost", input=input)
    minus_out = helper.create_tmp_variable(dtype=input.dtype)
    helper.append_op(type="elementwise_sub",
                     inputs={"X": [input], "Y": [label]},
                     outputs={"Out": [minus_out]})
    square_out = helper.create_tmp_variable(dtype=input.dtype)
    helper.append_op(type="square", inputs={"X": [minus_out]},
                     outputs={"Out": [square_out]})
    return square_out


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               seq_mask=False):
    """Padded-sequence logits are masked automatically via the SEQLEN side
    channel. seq_mask=True additionally asserts the logits ARE a sequence
    (lod/rank-3), catching silent no-mask situations at build time."""
    if seq_mask:
        assert logits.shape is not None and len(logits.shape) >= 3, (
            "seq_mask=True but logits are not sequence-shaped [B,T,V]; "
            "feed the sequence through LoD data vars so lengths ride along")
    helper = LayerHelper("softmax_with_cross_entropy")
    softmax = helper.create_tmp_variable(dtype=logits.dtype)
    loss = helper.create_tmp_variable(dtype=logits.dtype)
    helper.append_op(type="softmax_with_cross_entropy",
                     inputs={"Logits": [logits], "Label": [label]},
                     outputs={"Softmax": [softmax], "Loss": [loss]},
                     attrs={"soft_label": soft_label})
    return loss


def sigmoid_cross_entropy_with_logits(x, label):
    return _simple("sigmoid_cross_entropy_with_logits", x,
                   extra_inputs={"Label": label})


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=None):
    helper = LayerHelper("smooth_l1_loss")
    diff = helper.create_tmp_variable(dtype=x.dtype)
    loss = helper.create_tmp_variable(dtype=x.dtype)
    helper.append_op(type="smooth_l1_loss",
                     inputs={"X": [x], "Y": [y],
                             **({"InsideWeight": [inside_weight]}
                                if inside_weight is not None else {}),
                             **({"OutsideWeight": [outside_weight]}
                                if outside_weight is not None else {})},
                     outputs={"Diff": [diff], "Out": [loss]},
                     attrs={"sigma": sigma or 1.0})
    return loss


def log_loss(input, label, epsilon=1e-4, name=None):
    helper = LayerHelper("log_loss", name=name)
    loss = helper.create_tmp_variable(dtype=input.dtype)
    helper.append_op(type="log_loss",
                     inputs={"Predicted": [input], "Labels": [label]},
                     outputs={"Loss": [loss]}, attrs={"epsilon": epsilon})
    return loss


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32",
                 name=None):
    helper = LayerHelper("label_smooth", name=name)
    out = helper.create_tmp_variable(dtype)
    inputs = {"X": [label]}
    if prior_dist is not None:
        inputs["PriorDist"] = [prior_dist]
    helper.append_op(type="label_smooth", inputs=inputs,
                     outputs={"Out": [out]}, attrs={"epsilon": float(epsilon)})
    return out


def nce(input, label, num_total_classes, sample_weight=None, param_attr=None,
        bias_attr=None, num_neg_samples=None):
    """Noise-contrastive estimation loss (reference nce_op.cc, nn.py:2806)."""
    helper = LayerHelper("nce", param_attr=param_attr, bias_attr=bias_attr)
    dim = input.shape[1]
    num_neg_samples = 10 if num_neg_samples is None else num_neg_samples
    w = helper.create_parameter(attr=helper.param_attr,
                                shape=[num_total_classes, dim],
                                dtype=input.dtype, is_bias=False)
    b = helper.create_parameter(attr=helper.bias_attr,
                                shape=[num_total_classes, 1],
                                dtype=input.dtype, is_bias=True)
    cost = helper.create_tmp_variable(dtype=input.dtype)
    sample_logits = helper.create_tmp_variable(dtype=input.dtype)
    sample_labels = helper.create_tmp_variable(dtype="int64")
    inputs = {"Input": [input], "Label": [label], "Weight": [w], "Bias": [b]}
    if sample_weight is not None:
        inputs["SampleWeight"] = [sample_weight]
    helper.append_op(type="nce", inputs=inputs,
                     outputs={"Cost": [cost], "SampleLogits": [sample_logits],
                              "SampleLabels": [sample_labels]},
                     attrs={"num_total_classes": num_total_classes,
                            "num_neg_samples": num_neg_samples})
    return cost


# --- conv / pool ------------------------------------------------------------

def _pair(v):
    return list(v) if isinstance(v, (list, tuple)) else [v, v]


def conv2d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=None, param_attr=None, bias_attr=None, use_cudnn=True,
           use_mkldnn=False, act=None, name=None):
    """2-D convolution, NCHW (reference nn.py:1120). use_cudnn is accepted and
    ignored: XLA picks the MXU convolution algorithm."""
    helper = LayerHelper("conv2d", param_attr=param_attr, bias_attr=bias_attr,
                         act=act, name=name)
    dtype = input.dtype
    groups = groups or 1
    num_channels = input.shape[1]
    filter_size = _pair(filter_size)
    filter_shape = [num_filters, num_channels // groups] + filter_size
    fan_in = (num_channels // groups) * filter_size[0] * filter_size[1]
    std = math.sqrt(2.0 / fan_in)
    w = helper.create_parameter(
        attr=helper.param_attr, shape=filter_shape, dtype=dtype,
        default_initializer=NormalInitializer(0.0, std))
    pre_bias = helper.create_tmp_variable(dtype)
    helper.append_op(type="conv2d",
                     inputs={"Input": [input], "Filter": [w]},
                     outputs={"Output": [pre_bias]},
                     attrs={"strides": _pair(stride), "paddings": _pair(padding),
                            "dilations": _pair(dilation), "groups": groups})
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def conv3d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=None, param_attr=None, bias_attr=None, act=None, name=None):
    helper = LayerHelper("conv3d", param_attr=param_attr, bias_attr=bias_attr,
                         act=act, name=name)
    def _triple(v):
        return list(v) if isinstance(v, (list, tuple)) else [v] * 3
    dtype = input.dtype
    groups = groups or 1
    num_channels = input.shape[1]
    fs = _triple(filter_size)
    filter_shape = [num_filters, num_channels // groups] + fs
    fan_in = (num_channels // groups) * fs[0] * fs[1] * fs[2]
    w = helper.create_parameter(
        attr=helper.param_attr, shape=filter_shape, dtype=dtype,
        default_initializer=NormalInitializer(0.0, math.sqrt(2.0 / fan_in)))
    pre_bias = helper.create_tmp_variable(dtype)
    helper.append_op(type="conv3d",
                     inputs={"Input": [input], "Filter": [w]},
                     outputs={"Output": [pre_bias]},
                     attrs={"strides": _triple(stride),
                            "paddings": _triple(padding),
                            "dilations": _triple(dilation), "groups": groups})
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def conv2d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, param_attr=None,
                     bias_attr=None, use_cudnn=True, act=None, name=None):
    helper = LayerHelper("conv2d_transpose", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    num_channels = input.shape[1]
    padding_, stride_, dilation_ = _pair(padding), _pair(stride), _pair(dilation)
    if filter_size is None:
        assert output_size is not None
        output_size = _pair(output_size)
        h, w_ = input.shape[2], input.shape[3]
        filter_size = [
            (output_size[0] - (h - 1) * stride_[0] + 2 * padding_[0] - 1)
            // dilation_[0] + 1,
            (output_size[1] - (w_ - 1) * stride_[1] + 2 * padding_[1] - 1)
            // dilation_[1] + 1]
    else:
        filter_size = _pair(filter_size)
    filter_shape = [num_channels, num_filters] + filter_size
    w = helper.create_parameter(attr=helper.param_attr, shape=filter_shape,
                                dtype=dtype, is_bias=False)
    pre_bias = helper.create_tmp_variable(dtype)
    helper.append_op(type="conv2d_transpose",
                     inputs={"Input": [input], "Filter": [w]},
                     outputs={"Output": [pre_bias]},
                     attrs={"strides": stride_, "paddings": padding_,
                            "dilations": dilation_})
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def pool2d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, use_cudnn=True,
           ceil_mode=False, use_mkldnn=False, name=None):
    helper = LayerHelper("pool2d", name=name)
    out = helper.create_tmp_variable(dtype=input.dtype)
    helper.append_op(type="pool2d", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"pooling_type": pool_type,
                            "ksize": _pair(pool_size),
                            "global_pooling": global_pooling,
                            "strides": _pair(pool_stride),
                            "paddings": _pair(pool_padding),
                            "ceil_mode": ceil_mode})
    return out


def maxout(x, groups, name=None):
    helper = LayerHelper("maxout", name=name)
    out = helper.create_tmp_variable(dtype=x.dtype)
    helper.append_op(type="maxout", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs={"groups": groups})
    return out


# --- normalization ----------------------------------------------------------

def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5,
               param_attr=None, bias_attr=None, data_layout="NCHW",
               in_place=False, use_mkldnn=False, name=None,
               moving_mean_name=None, moving_variance_name=None,
               do_model_average_for_mean_and_var=False):
    """Batch normalization (reference nn.py:1478, batch_norm_op.cc)."""
    helper = LayerHelper("batch_norm", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    input_shape = input.shape
    channel_num = input_shape[1] if data_layout == "NCHW" else input_shape[-1]
    param_shape = [channel_num]
    scale = helper.create_parameter(
        attr=helper.param_attr, shape=param_shape, dtype=dtype,
        default_initializer=ConstantInitializer(1.0))
    bias = helper.create_parameter(attr=helper.bias_attr, shape=param_shape,
                                   dtype=dtype, is_bias=True)
    mean = helper.create_parameter(
        attr=_non_trainable_attr(moving_mean_name),
        shape=param_shape, dtype=dtype,
        default_initializer=ConstantInitializer(0.0))
    variance = helper.create_parameter(
        attr=_non_trainable_attr(moving_variance_name),
        shape=param_shape, dtype=dtype,
        default_initializer=ConstantInitializer(1.0))
    saved_mean = helper.create_tmp_variable(dtype=dtype, stop_gradient=True)
    saved_variance = helper.create_tmp_variable(dtype=dtype, stop_gradient=True)
    out = input if in_place else helper.create_tmp_variable(dtype)
    helper.append_op(
        type="batch_norm",
        inputs={"X": [input], "Scale": [scale], "Bias": [bias],
                "Mean": [mean], "Variance": [variance]},
        outputs={"Y": [out], "MeanOut": [mean], "VarianceOut": [variance],
                 "SavedMean": [saved_mean], "SavedVariance": [saved_variance]},
        attrs={"momentum": momentum, "epsilon": epsilon, "is_test": is_test})
    return helper.append_activation(out)


def _non_trainable_attr(name):
    from ..param_attr import ParamAttr
    return ParamAttr(name=name, trainable=False)


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1,
               epsilon=1e-5, param_attr=None, bias_attr=None, act=None,
               name=None):
    helper = LayerHelper("layer_norm", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    input_shape = input.shape
    param_shape = [int(np.prod(input_shape[begin_norm_axis:]))]
    inputs = {"X": [input]}
    if scale:
        s = helper.create_parameter(
            attr=helper.param_attr, shape=param_shape, dtype=dtype,
            default_initializer=ConstantInitializer(1.0))
        inputs["Scale"] = [s]
    if shift:
        b = helper.create_parameter(attr=helper.bias_attr, shape=param_shape,
                                    dtype=dtype, is_bias=True)
        inputs["Bias"] = [b]
    mean_out = helper.create_tmp_variable(dtype=dtype, stop_gradient=True)
    variance_out = helper.create_tmp_variable(dtype=dtype, stop_gradient=True)
    out = helper.create_tmp_variable(dtype)
    helper.append_op(type="layer_norm", inputs=inputs,
                     outputs={"Y": [out], "Mean": [mean_out],
                              "Variance": [variance_out]},
                     attrs={"epsilon": epsilon,
                            "begin_norm_axis": begin_norm_axis})
    return helper.append_activation(out)


def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None):
    helper = LayerHelper("lrn", name=name)
    out = helper.create_tmp_variable(dtype=input.dtype)
    mid = helper.create_tmp_variable(dtype=input.dtype, stop_gradient=True)
    helper.append_op(type="lrn", inputs={"X": [input]},
                     outputs={"Out": [out], "MidOut": [mid]},
                     attrs={"n": n, "k": k, "alpha": alpha, "beta": beta})
    return out


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    helper = LayerHelper("l2_normalize", name=name)
    out = helper.create_tmp_variable(dtype=x.dtype)
    norm = helper.create_tmp_variable(dtype=x.dtype, stop_gradient=True)
    helper.append_op(type="norm", inputs={"X": [x]},
                     outputs={"Out": [out], "Norm": [norm]},
                     attrs={"axis": axis, "epsilon": epsilon})
    return out


# --- dropout ----------------------------------------------------------------

def dropout(x, dropout_prob, is_test=False, seed=None, name=None):
    helper = LayerHelper("dropout", name=name)
    out = helper.create_tmp_variable(dtype=x.dtype)
    mask = helper.create_tmp_variable(dtype=x.dtype, stop_gradient=True)
    helper.append_op(type="dropout", inputs={"X": [x]},
                     outputs={"Out": [out], "Mask": [mask]},
                     attrs={"dropout_prob": dropout_prob, "is_test": is_test,
                            "seed": seed if seed is not None else 0})
    return out


# --- metrics ----------------------------------------------------------------

def accuracy(input, label, k=1, correct=None, total=None):
    helper = LayerHelper("accuracy")
    topk_out = helper.create_tmp_variable(dtype=input.dtype)
    topk_indices = helper.create_tmp_variable(dtype="int64")
    helper.append_op(type="top_k", inputs={"X": [input]},
                     outputs={"Out": [topk_out], "Indices": [topk_indices]},
                     attrs={"k": k})
    acc_out = helper.create_tmp_variable(dtype="float32")
    correct = correct or helper.create_tmp_variable(dtype="int32")
    total = total or helper.create_tmp_variable(dtype="int32")
    helper.append_op(type="accuracy",
                     inputs={"Out": [topk_out], "Indices": [topk_indices],
                             "Label": [label]},
                     outputs={"Accuracy": [acc_out], "Correct": [correct],
                              "Total": [total]})
    return acc_out


def auc(input, label, curve="ROC", num_thresholds=200, topk=1):
    helper = LayerHelper("auc")
    auc_out = helper.create_tmp_variable(dtype="float32")
    helper.append_op(type="auc",
                     inputs={"Out": [input], "Label": [label]},
                     outputs={"AUC": [auc_out]},
                     attrs={"curve": curve, "num_thresholds": num_thresholds})
    return auc_out


# --- math wrappers ----------------------------------------------------------

def mean(x, name=None):
    return _simple("mean", x, name=name)


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1, name=None):
    return _simple("mul", x, attrs={"x_num_col_dims": x_num_col_dims,
                                    "y_num_col_dims": y_num_col_dims},
                   extra_inputs={"Y": y}, name=name)


def _ew(op_type):
    def f(x, y, axis=-1, act=None, name=None):
        helper = LayerHelper(op_type, act=act, name=name)
        out = helper.create_tmp_variable(dtype=x.dtype)
        helper.append_op(type=op_type, inputs={"X": [x], "Y": [y]},
                         outputs={"Out": [out]}, attrs={"axis": axis})
        return helper.append_activation(out)
    f.__name__ = op_type
    return f


elementwise_add = _ew("elementwise_add")
elementwise_sub = _ew("elementwise_sub")
elementwise_mul = _ew("elementwise_mul")
elementwise_div = _ew("elementwise_div")
elementwise_max = _ew("elementwise_max")
elementwise_min = _ew("elementwise_min")
elementwise_pow = _ew("elementwise_pow")


def _reduce(op_type):
    def f(input, dim=None, keep_dim=False, name=None):
        helper = LayerHelper(op_type, name=name)
        out = helper.create_tmp_variable(dtype=input.dtype)
        if dim is not None and not isinstance(dim, (list, tuple)):
            dim = [dim]
        helper.append_op(type=op_type, inputs={"X": [input]},
                         outputs={"Out": [out]},
                         attrs={"dim": dim if dim is not None else [0],
                                "keep_dim": keep_dim,
                                "reduce_all": dim is None})
        return out
    f.__name__ = op_type
    return f


reduce_sum = _reduce("reduce_sum")
reduce_mean = _reduce("reduce_mean")
reduce_max = _reduce("reduce_max")
reduce_min = _reduce("reduce_min")
reduce_prod = _reduce("reduce_prod")


def matmul(x, y, transpose_x=False, transpose_y=False, name=None):
    return _simple("matmul", x,
                   attrs={"transpose_X": transpose_x,
                          "transpose_Y": transpose_y},
                   extra_inputs={"Y": y}, name=name)


def transpose(x, perm, name=None):
    return _simple("transpose", x, attrs={"axis": list(perm)}, name=name)


def reverse(x, axis, name=None):
    """Flip x along `axis` (int or list of ints)."""
    axis = [axis] if isinstance(axis, int) else list(axis)
    return _simple("reverse", x, attrs={"axis": axis}, name=name)


def reshape(x, shape, actual_shape=None, act=None, inplace=True, name=None):
    helper = LayerHelper("reshape", act=act, name=name)
    out = helper.create_tmp_variable(dtype=x.dtype)
    helper.append_op(type="reshape", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs={"shape": list(shape)})
    return helper.append_activation(out)


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper("split", name=name)
    input_shape = input.shape
    dim = dim if dim >= 0 else dim + len(input_shape)
    if isinstance(num_or_sections, int):
        num = num_or_sections
        sections = []
    else:
        num = len(num_or_sections)
        sections = list(num_or_sections)
    outs = [helper.create_tmp_variable(dtype=input.dtype) for _ in range(num)]
    helper.append_op(type="split", inputs={"X": [input]},
                     outputs={"Out": outs},
                     attrs={"axis": dim, "sections": sections, "num": 0 if sections else num})
    return outs


def topk(input, k):
    helper = LayerHelper("top_k")
    values = helper.create_tmp_variable(dtype=input.dtype)
    indices = helper.create_tmp_variable(dtype="int64")
    helper.append_op(type="top_k", inputs={"X": [input]},
                     outputs={"Out": [values], "Indices": [indices]},
                     attrs={"k": k})
    return values, indices


def one_hot(input, depth):
    return _simple("one_hot", input, attrs={"depth": depth},
                   out_dtype="float32")


def clip(x, min, max, name=None):
    return _simple("clip", x, attrs={"min": float(min), "max": float(max)},
                   name=name)


def clip_by_norm(x, max_norm, name=None):
    return _simple("clip_by_norm", x, attrs={"max_norm": float(max_norm)},
                   name=name)


def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None, name=None):
    helper = LayerHelper("scale", act=act, name=name)
    out = helper.create_tmp_variable(dtype=x.dtype)
    helper.append_op(type="scale", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"scale": float(scale), "bias": float(bias),
                            "bias_after_scale": bias_after_scale})
    return helper.append_activation(out)


def cast(x, dtype):
    from .tensor import cast as _cast
    return _cast(x, dtype)


def expand(x, expand_times, name=None):
    return _simple("expand", x, attrs={"expand_times": list(expand_times)},
                   name=name)


def squeeze(input, axes, name=None):
    return _simple("squeeze", input, attrs={"axes": list(axes)}, name=name)


def unsqueeze(input, axes, name=None):
    return _simple("unsqueeze", input, attrs={"axes": list(axes)}, name=name)


def gather(input, index):
    return _simple("gather", input, extra_inputs={"Index": index})


def scatter(input, index, updates, name=None):
    return _simple("scatter", input,
                   extra_inputs={"Ids": index, "Updates": updates}, name=name)


def pad(x, paddings, pad_value=0.0, name=None):
    return _simple("pad", x, attrs={"paddings": list(paddings),
                                    "pad_value": float(pad_value)}, name=name)


def multiplex(inputs, index):
    helper = LayerHelper("multiplex")
    out = helper.create_tmp_variable(dtype=inputs[0].dtype)
    helper.append_op(type="multiplex",
                     inputs={"X": inputs, "Ids": [index]},
                     outputs={"Out": [out]})
    return out


def cos_sim(X, Y):
    helper = LayerHelper("cos_sim")
    out = helper.create_tmp_variable(dtype=X.dtype)
    xnorm = helper.create_tmp_variable(dtype=X.dtype, stop_gradient=True)
    ynorm = helper.create_tmp_variable(dtype=X.dtype, stop_gradient=True)
    helper.append_op(type="cos_sim", inputs={"X": [X], "Y": [Y]},
                     outputs={"Out": [out], "XNorm": [xnorm],
                              "YNorm": [ynorm]})
    return out


def row_conv(input, future_context_size, param_attr=None, act=None):
    helper = LayerHelper("row_conv", param_attr=param_attr, act=act)
    filter_shape = [future_context_size + 1, input.shape[-1]]
    w = helper.create_parameter(attr=helper.param_attr, shape=filter_shape,
                                dtype=input.dtype, is_bias=False)
    out = helper.create_tmp_variable(dtype=input.dtype)
    helper.append_op(type="row_conv",
                     inputs={"X": [input], "Filter": [w]},
                     outputs={"Out": [out]})
    return helper.append_activation(out)


def im2sequence(input, filter_size=1, stride=1, padding=0, name=None):
    helper = LayerHelper("im2sequence", name=name)
    out = helper.create_tmp_variable(dtype=input.dtype)
    helper.append_op(type="im2sequence", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"kernels": _pair(filter_size),
                            "strides": _pair(stride),
                            "paddings": _pair(padding) + _pair(padding)})
    return out


def autoincreased_step_counter(counter_name=None, begin=1, step=1):
    """Global step counter as a graph var (reference nn.py:3291); LR schedules
    read it."""
    helper = LayerHelper("global_step_counter")
    counter_name = counter_name or "@STEP_COUNTER@"
    gblock = helper.main_program.global_block()
    if gblock.has_var(counter_name):
        return gblock.var(counter_name)
    counter = gblock.create_var(name=counter_name, dtype="int64", shape=[1],
                                persistable=True)
    from ..initializer import ConstantInitializer
    helper.set_variable_initializer(
        counter, ConstantInitializer(float(begin - 1)))
    gblock.prepend_op(type="increment", inputs={"X": [counter]},
                      outputs={"Out": [counter]}, attrs={"step": float(step)})
    counter.stop_gradient = True
    counter.desc.stop_gradient = True
    return counter


# --- CRF --------------------------------------------------------------------

def linear_chain_crf(input, label, param_attr=None):
    """Linear-chain CRF cost (reference nn.py:787, linear_chain_crf_op.cc).
    input: padded emissions [B,T,D]; label: [B,T,1] int. Returns the per-
    sequence negative log-likelihood [B,1]. The transition parameter is
    [D+2, D] (start row, stop row, pairwise matrix)."""
    helper = LayerHelper("linear_chain_crf", param_attr=param_attr)
    size = input.shape[-1]
    transition = helper.create_parameter(
        attr=helper.param_attr, shape=[size + 2, size], dtype=input.dtype)
    log_likelihood = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="linear_chain_crf",
                     inputs={"Emission": [input],
                             "Transition": [transition],
                             "Label": [label]},
                     outputs={"LogLikelihood": [log_likelihood]})
    return log_likelihood


def crf_decoding(input, param_attr, label=None):
    """Viterbi decoding (reference crf_decoding_op.cc). With label, emits a
    per-token correctness indicator instead of the path."""
    helper = LayerHelper("crf_decoding", param_attr=param_attr)
    transition = helper.get_parameter(helper.param_attr.name)
    viterbi_path = helper.create_tmp_variable("int64")
    inputs = {"Emission": [input], "Transition": [transition]}
    if label is not None:
        inputs["Label"] = [label]
    helper.append_op(type="crf_decoding", inputs=inputs,
                     outputs={"ViterbiPath": [viterbi_path]})
    return viterbi_path


# --- beam search ------------------------------------------------------------

def beam_search(pre_ids, pre_scores, scores, beam_size, end_id, level=0):
    """One beam-search step over dense [B,K] lanes (reference nn.py:1903,
    beam_search_op.cc; the reference tracks beams in LoD levels — here
    parent indices are returned explicitly). Returns
    (selected_ids [B,K], selected_scores [B,K], parent_idx [B,K])."""
    helper = LayerHelper("beam_search")
    selected_ids = helper.create_tmp_variable("int64")
    selected_scores = helper.create_tmp_variable(scores.dtype)
    parent_idx = helper.create_tmp_variable("int32")
    helper.append_op(type="beam_search",
                     inputs={"pre_ids": [pre_ids],
                             "pre_scores": [pre_scores],
                             "scores": [scores]},
                     outputs={"selected_ids": [selected_ids],
                              "selected_scores": [selected_scores],
                              "parent_idx": [parent_idx]},
                     attrs={"beam_size": beam_size, "end_id": end_id,
                            "level": level})
    return selected_ids, selected_scores, parent_idx


def beam_search_decode(ids, parent_idx, scores=None, beam_size=None,
                       end_id=1):
    """Backtrack beam TensorArrays into final hypotheses (reference
    beam_search_decode_op.cc). Returns (sentence_ids [B,K,T],
    sentence_scores [B,K])."""
    helper = LayerHelper("beam_search_decode")
    sentence_ids = helper.create_tmp_variable("int64")
    sentence_scores = helper.create_tmp_variable("float32")
    inputs = {"Ids": [ids], "ParentIdx": [parent_idx]}
    if scores is not None:
        inputs["Scores"] = [scores]
    helper.append_op(type="beam_search_decode", inputs=inputs,
                     outputs={"SentenceIds": [sentence_ids],
                              "SentenceScores": [sentence_scores]},
                     attrs={"end_id": end_id})
    return sentence_ids, sentence_scores


# --- CTC / sequence metrics ---------------------------------------------------

def warpctc(input, label, blank=0, norm_by_times=False):
    """CTC loss over padded-LoD logits (reference nn.py:2696, warpctc_op.cc;
    softmax applied internally). Returns [num_sequences, 1] loss."""
    helper = LayerHelper("warpctc")
    loss = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="warpctc",
                     inputs={"Logits": [input], "Label": [label]},
                     outputs={"Loss": [loss]},
                     attrs={"blank": blank, "norm_by_times": norm_by_times})
    return loss


def ctc_greedy_decoder(input, blank, name=None):
    """Greedy CTC decode: per-step argmax, then merge repeats + drop blanks
    (reference nn.py:2616: top_k -> ctc_align)."""
    helper = LayerHelper("ctc_greedy_decoder")
    _, idx = topk(input, k=1)
    out = helper.create_tmp_variable(idx.dtype)
    helper.append_op(type="ctc_align", inputs={"Input": [idx]},
                     outputs={"Output": [out]},
                     attrs={"blank": blank, "merge_repeated": True})
    return out


def ctc_align(input, blank=0, merge_repeated=True):
    """Raw ctc_align on an id sequence (reference ctc_align_op.h)."""
    helper = LayerHelper("ctc_align")
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="ctc_align", inputs={"Input": [input]},
                     outputs={"Output": [out]},
                     attrs={"blank": blank, "merge_repeated": merge_repeated})
    return out


def edit_distance(input, label, normalized=True, ignored_tokens=None,
                  name=None):
    """Levenshtein distance per sequence pair (reference nn.py:2534,
    edit_distance_op.h). Returns (distance [B,1], sequence_num [1])."""
    helper = LayerHelper("edit_distance")
    if ignored_tokens is not None and len(ignored_tokens) > 0:
        erased_input = helper.create_tmp_variable(input.dtype)
        erased_label = helper.create_tmp_variable(label.dtype)
        helper.append_op(type="sequence_erase", inputs={"X": [input]},
                         outputs={"Out": [erased_input]},
                         attrs={"tokens": list(ignored_tokens)})
        helper.append_op(type="sequence_erase", inputs={"X": [label]},
                         outputs={"Out": [erased_label]},
                         attrs={"tokens": list(ignored_tokens)})
        input, label = erased_input, erased_label
    out = helper.create_tmp_variable("float32")
    seq_num = helper.create_tmp_variable("int32")
    helper.append_op(type="edit_distance",
                     inputs={"Hyps": [input], "Refs": [label]},
                     outputs={"Out": [out], "SequenceNum": [seq_num]},
                     attrs={"normalized": normalized})
    return out, seq_num


def chunk_eval(input, label, chunk_scheme, num_chunk_types,
               excluded_chunk_types=None):
    """Chunking precision/recall/F1 (reference nn.py:1015, chunk_eval_op.h).
    Returns (precision, recall, f1, num_infer_chunks, num_label_chunks,
    num_correct_chunks)."""
    helper = LayerHelper("chunk_eval")
    precision = helper.create_tmp_variable("float32")
    recall = helper.create_tmp_variable("float32")
    f1_score = helper.create_tmp_variable("float32")
    num_infer_chunks = helper.create_tmp_variable("int32")
    num_label_chunks = helper.create_tmp_variable("int32")
    num_correct_chunks = helper.create_tmp_variable("int32")
    helper.append_op(
        type="chunk_eval",
        inputs={"Inference": [input], "Label": [label]},
        outputs={"Precision": [precision], "Recall": [recall],
                 "F1-Score": [f1_score],
                 "NumInferChunks": [num_infer_chunks],
                 "NumLabelChunks": [num_label_chunks],
                 "NumCorrectChunks": [num_correct_chunks]},
        attrs={"num_chunk_types": num_chunk_types,
               "chunk_scheme": chunk_scheme,
               "excluded_chunk_types": excluded_chunk_types or []})
    return (precision, recall, f1_score, num_infer_chunks, num_label_chunks,
            num_correct_chunks)


def precision_recall(indices, labels, class_number, weights=None,
                     states_info=None):
    """Multi-class precision/recall metrics op wrapper (reference
    precision_recall_op.h). Returns (batch_metrics [6], accum_metrics [6],
    accum_states_info [C,4])."""
    helper = LayerHelper("precision_recall")
    batch_metrics = helper.create_tmp_variable("float32")
    accum_metrics = helper.create_tmp_variable("float32")
    accum_states = helper.create_tmp_variable("float32")
    inputs = {"Indices": [indices], "Labels": [labels]}
    if weights is not None:
        inputs["Weights"] = [weights]
    if states_info is not None:
        inputs["StatesInfo"] = [states_info]
    helper.append_op(type="precision_recall", inputs=inputs,
                     outputs={"BatchMetrics": [batch_metrics],
                              "AccumMetrics": [accum_metrics],
                              "AccumStatesInfo": [accum_states]},
                     attrs={"class_number": class_number})
    return batch_metrics, accum_metrics, accum_states


def positive_negative_pair(score, label, query_id, weight=None, column=-1):
    """Ranking pair counts per query (reference positive_negative_pair_op.h).
    Returns (positive_pair, negative_pair, neutral_pair)."""
    helper = LayerHelper("positive_negative_pair")
    pos, neg, neu = (helper.create_tmp_variable("float32") for _ in range(3))
    inputs = {"Score": [score], "Label": [label], "QueryID": [query_id]}
    if weight is not None:
        inputs["Weight"] = [weight]
    helper.append_op(type="positive_negative_pair", inputs=inputs,
                     outputs={"PositivePair": [pos], "NegativePair": [neg],
                              "NeutralPair": [neu]},
                     attrs={"column": column})
    return pos, neg, neu


# --- vision layer wrappers ----------------------------------------------------

def pool3d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, ceil_mode=False, name=None):
    """NCDHW pooling (reference pool_op.cc pool3d)."""
    def _t(v):
        return list(v) if isinstance(v, (list, tuple)) else [v, v, v]
    helper = LayerHelper("pool3d")
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="pool3d", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"pooling_type": pool_type,
                            "ksize": _t(pool_size),
                            "strides": _t(pool_stride),
                            "paddings": _t(pool_padding),
                            "global_pooling": global_pooling,
                            "ceil_mode": ceil_mode})
    return out


def max_pool2d_with_index(input, pool_size, pool_stride=1, pool_padding=0):
    """Max pool returning (out, argmax-mask) (reference
    pool_with_index_op.cc)."""
    helper = LayerHelper("max_pool2d_with_index")
    out = helper.create_tmp_variable(input.dtype)
    mask = helper.create_tmp_variable("int32")
    helper.append_op(type="max_pool2d_with_index", inputs={"X": [input]},
                     outputs={"Out": [out], "Mask": [mask]},
                     attrs={"ksize": _pair(pool_size),
                            "strides": _pair(pool_stride),
                            "paddings": _pair(pool_padding)})
    return out, mask


def unpool(input, indices, unpooled_size):
    """Max unpooling from argmax indices (reference unpool_op.cc)."""
    helper = LayerHelper("unpool")
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="unpool",
                     inputs={"X": [input], "Indices": [indices]},
                     outputs={"Out": [out]},
                     attrs={"unpooled_size": list(unpooled_size)})
    return out


def spp(input, pyramid_height, pool_type="max"):
    """Spatial pyramid pooling (reference spp_op.cc)."""
    helper = LayerHelper("spp")
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="spp", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"pyramid_height": pyramid_height,
                            "pooling_type": pool_type})
    return out


def roi_pool(input, rois, pooled_height, pooled_width, spatial_scale=1.0,
             rois_batch_id=None):
    """ROI max pooling (reference roi_pool_op.cc)."""
    helper = LayerHelper("roi_pool")
    out = helper.create_tmp_variable(input.dtype)
    argmax = helper.create_tmp_variable("int32")
    inputs = {"X": [input], "ROIs": [rois]}
    if rois_batch_id is not None:
        inputs["RoiBatchId"] = [rois_batch_id]
    helper.append_op(type="roi_pool", inputs=inputs,
                     outputs={"Out": [out], "Argmax": [argmax]},
                     attrs={"pooled_height": pooled_height,
                            "pooled_width": pooled_width,
                            "spatial_scale": spatial_scale})
    return out


def crop(x, shape=None, offsets=None, name=None):
    """Crop x to shape at offsets (reference crop_op.cc); shape/offsets may
    be lists or Variables."""
    helper = LayerHelper("crop")
    out = helper.create_tmp_variable(x.dtype)
    inputs = {"X": [x]}
    attrs = {}
    if isinstance(shape, Variable):
        inputs["Y"] = [shape]
    else:
        attrs["shape"] = list(shape)
    if isinstance(offsets, Variable):
        inputs["Offsets"] = [offsets]
    elif offsets is not None:
        attrs["offsets"] = list(offsets)
    helper.append_op(type="crop", inputs=inputs, outputs={"Out": [out]},
                     attrs=attrs)
    return out


def prelu(x, mode="all", param_attr=None, name=None):
    """Parametric ReLU with learned alpha (reference prelu_op.cc)."""
    helper = LayerHelper("prelu", param_attr=param_attr)
    if mode == "all":
        alpha_shape = [1]
    elif mode == "channel":
        alpha_shape = [x.shape[1]]
    else:
        # element mode: one alpha per feature element, broadcast over the
        # batch dim (which is -1 for data vars and must not size a param)
        alpha_shape = [1] + list(x.shape[1:])
    from ..initializer import Constant
    alpha = helper.create_parameter(attr=helper.param_attr,
                                    shape=alpha_shape, dtype=x.dtype,
                                    default_initializer=Constant(0.25))
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="prelu",
                     inputs={"X": [x], "Alpha": [alpha]},
                     outputs={"Out": [out]}, attrs={"mode": mode})
    return out


def conv3d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, param_attr=None,
                     bias_attr=None, act=None, name=None):
    """Transposed 3D convolution (reference conv_transpose_op.cc)."""
    def _t(v):
        return list(v) if isinstance(v, (list, tuple)) else [v, v, v]
    helper = LayerHelper("conv3d_transpose", param_attr=param_attr,
                         bias_attr=bias_attr, act=act)
    cin = input.shape[1]
    stride_, padding_, dilation_ = _t(stride), _t(padding), _t(dilation)
    if filter_size is None:
        assert output_size is not None, \
            "conv3d_transpose needs filter_size or output_size"
        output_size = _t(output_size)
        filter_size = [
            (output_size[i] - (input.shape[2 + i] - 1) * stride_[i]
             + 2 * padding_[i] - 1) // dilation_[i] + 1
            for i in range(3)]
    else:
        filter_size = _t(filter_size)
    f = helper.create_parameter(
        attr=helper.param_attr,
        shape=[cin, num_filters] + filter_size, dtype=input.dtype)
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="conv3d_transpose",
                     inputs={"Input": [input], "Filter": [f]},
                     outputs={"Output": [out]},
                     attrs={"strides": stride_, "paddings": padding_,
                            "dilations": dilation_})
    out = helper.append_bias_op(out, dim_start=1, dim_end=2)
    return helper.append_activation(out)


def conv_shift(x, y, name=None):
    """Circular convolution (reference conv_shift_op.cc)."""
    helper = LayerHelper("conv_shift")
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="conv_shift", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]}, attrs={})
    return out


def l1_norm(x, name=None):
    """Sum of absolute values (reference l1_norm_op.cc)."""
    helper = LayerHelper("l1_norm")
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="l1_norm", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs={})
    return out


def fused_attention(q, k, v, causal=False,
                    sequence_parallel=False, use_flash="auto", name=None,
                    window=0):
    """Fused attention over [B, T, H, D] tensors. K and V may have fewer
    heads than Q (grouped-query attention): H_kv must divide H, and query
    head j reads K/V head j // (H / H_kv). The op repeats K and V to H
    heads before it chooses a path, so the flash kernels' gate sees equal
    head counts and books its hit or fallback reason as for full
    attention, and the grad op sums dK and dV over each group.
    sequence_parallel=True runs ring attention over the program mesh's 'sp' axis
    (parallel/ring_attention.py) for long-context training; use_flash=True
    runs the Pallas online-softmax VMEM kernel (ops/pallas_attention.py) —
    O(T) memory, scores never hit HBM. The default 'auto' picks per shape:
    XLA einsum at short T (fuses into neighbors), flash from the per-device
    sequence length at which the kernels won on the chip
    (ops/nn_ops._flash_wins); a shape the kernels do not tile keeps einsum
    with a counted reason; False forces einsum. The masks this op knows
    are none, causal, and causal under a sliding `window`: a query sees
    the last `window` keys up to and with its own (0: every earlier key);
    the kernels neither fetch nor walk the tiles wholly before the window
    and the einsum path masks alike; ring attention takes no window and
    says so. A window shorter than two tiles (512 keys under tiles of 512
    rows: Laguna-XS.2's) leaves a layer no open tile: every walked tile
    is masked by the diagonal, the window's far edge or both. Layers of
    one model may differ in H (48 and 64 query heads over 8 K/V heads in
    models/gated_window_moe.py): each op repeats K and V by its own
    ratio. Block-diffusion training's three-part mask at
    the grain of a block of tokens is block_diffusion_attention's, on the
    same kernels. (Named fused_attention because reference-parity
    nets.scaled_dot_product_attention already takes [B, T, D] with
    num_heads and different semantics.)"""
    helper = LayerHelper("fused_attention")
    out = helper.create_tmp_variable(q.dtype)
    # per-row logsumexp residual for the explicit backward (dropout-Mask
    # pattern); stop_gradient — it carries no cotangent of its own
    lse = helper.create_tmp_variable("float32", stop_gradient=True)
    attrs = {"causal": causal, "sequence_parallel": sequence_parallel,
             "use_flash": use_flash}
    if window:      # written only when set: every other program is the
        attrs["window"] = int(window)   # one it was
    helper.append_op(type="scaled_dot_product_attention",
                     inputs={"Q": [q], "K": [k], "V": [v]},
                     outputs={"Out": [out], "LSE": [lse]}, attrs=attrs)
    return out


def sparse_moe(x, num_experts, hidden_size, capacity_factor=1.25,
               param_attr=None, name=None):
    """Top-1 gated mixture-of-experts FFN over [N, D] tokens (GShard-style
    dispatch with a capacity that drops; see ops/nn_ops.py moe_ffn). Shard
    the returned layer's W1/W2 over an 'ep' mesh axis with
    parallel.shard_parameter for expert parallelism. For top-k routing
    that drops no token, a shared expert and a layer that holds a share
    of the experts, see moe_block."""
    helper = LayerHelper("sparse_moe", param_attr=param_attr)
    d = x.shape[-1]
    # one ParamAttr instance per parameter: create_parameter binds the
    # attr's name, so sharing one attr across gate/W1/W2 would collide
    import copy as _copy

    def _attr(suffix):
        a = helper.param_attr
        a = _copy.deepcopy(a)
        if getattr(a, "name", None):
            a.name = f"{a.name}.{suffix}"
        return a

    gate_w = helper.create_parameter(attr=_attr("gate"),
                                     shape=[d, num_experts], dtype=x.dtype)
    w1 = helper.create_parameter(attr=_attr("w1"),
                                 shape=[num_experts, d, hidden_size],
                                 dtype=x.dtype)
    w2 = helper.create_parameter(attr=_attr("w2"),
                                 shape=[num_experts, hidden_size, d],
                                 dtype=x.dtype)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="moe_ffn",
                     inputs={"X": [x], "GateW": [gate_w],
                             "W1": [w1], "W2": [w2]},
                     outputs={"Out": [out]},
                     attrs={"capacity_factor": capacity_factor})
    return out


def rms_norm(input, gate=None, groups=1, epsilon=1e-5, param_attr=None,
             name=None, unit_offset=False, gate_behind=False):
    """weight * x / sqrt(mean(x^2) + epsilon) over the last axis, or over
    each of `groups` equal slices of it (one weight of the full width
    either way). With `gate` the input is x * silu(gate) first: Mamba-2's
    gated norm; with `gate_behind` the gate comes behind the norm, weight
    * norm(x) * silu(gate): Gated DeltaNet's. `unit_offset`: the weight is
    1 + w with w from zeros (qwen3_next's norms). Statistics in float32
    under AMP. The two attributes are written only when set, so a call
    without them builds the op it built."""
    helper = LayerHelper("rms_norm", param_attr=param_attr, name=name)
    width = int(input.shape[-1])
    assert width % groups == 0, (width, groups)
    assert gate is not None or not gate_behind
    scale = helper.create_parameter(
        attr=helper.param_attr, shape=[width], dtype=input.dtype,
        default_initializer=ConstantInitializer(0.0 if unit_offset else 1.0))
    inputs = {"X": [input], "Scale": [scale]}
    if gate is not None:
        inputs["Gate"] = [gate]
    attrs = {"epsilon": epsilon, "groups": groups}
    if unit_offset:
        attrs["unit_offset"] = True
    if gate_behind:
        attrs["gate_behind"] = True
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="rms_norm", inputs=inputs, outputs={"Out": [out]},
                     attrs=attrs)
    return out


def _under_its_name(layer):
    """Build the layer's ops under fluid.name_scope(<the layer's name>), so
    that a device trace books every op of it, its `mul`s too, and their
    gradients to the layer (executor._exec_op's `pd_scope.<name>`)."""
    @functools.wraps(layer)
    def build(*args, **kwargs):
        with name_scope(layer.__name__):
            return layer(*args, **kwargs)
    return build


def _linear(x, size, scale=0.02, act=None, name=None):
    """A [B, T, in] -> [B, T, size] map with no bias, N(0, scale) weights;
    `name` names the weight, so that a second map can read the same one."""
    from ..param_attr import ParamAttr
    return fc(input=x, size=size, num_flatten_dims=2, bias_attr=False, act=act,
              param_attr=ParamAttr(name=name,
                                   initializer=NormalInitializer(scale=scale)))


@_under_its_name
def block_diffusion_attention(q, k, v, block_length, use_flash="auto",
                              name=None):
    """Attention of block-diffusion training (arXiv:2503.09573; how the
    SDAR family's models are trained, arXiv:2510.06303) over q
    [2B, L, H, D] and k, v [2B, L, H_kv, D] (H_kv divides H, as
    fused_attention): every sequence is two streams of L positions, its
    noised copy and its clean one, the B noisy streams first along the
    batch and their clean streams behind them in the same order. With
    b(i) = i // block_length:

        a noisy query at i sees the noisy keys j with b(j) = b(i) (its own
        block, both directions) and the clean keys j with b(j) < b(i);
        a clean query at i sees the clean keys j with b(j) <= b(i) and no
        noisy key.

    block_length = 1 makes the clean half plain causal attention. 'auto'
    takes the flash kernels from the sequence length fused_attention
    takes them, where the shape tiles and block_length divides 128
    (ops/pallas_attention.py: the mask lives in the kernels' walk ranges,
    dead tiles are neither fetched nor walked, nothing of [L, L] reaches
    HBM), else a masked einsum with the reason counted
    (pallas_fallback_total{op="block_diffusion_attention"}); False
    forces the einsum. Its ops lower under
    `pd_scope.block_diffusion_attention`."""
    helper = LayerHelper("block_diffusion_attention", name=name)
    out = helper.create_tmp_variable(q.dtype)
    lse = helper.create_tmp_variable("float32", stop_gradient=True)
    helper.append_op(type="block_diffusion_attention",
                     inputs={"Q": [q], "K": [k], "V": [v]},
                     outputs={"Out": [out], "LSE": [lse]},
                     attrs={"block_length": int(block_length),
                            "use_flash": use_flash})
    return out


@_under_its_name
def mamba2_mixer(x, num_heads, head_dim, n_groups, state_size, conv_kernel=4,
                 chunk_size=128, epsilon=1e-5, out_scale=0.02, name=None):
    """Mamba-2 mixer (Dao & Gu 2024, as nemotron_h's) over x [B, T, D]:
    [z | xBC | dt] = x W_in of widths d_inner | d_inner + 2 G N | H with
    d_inner = num_heads * head_dim; xBC through a causal depthwise conv
    and silu (built `time_on_lanes`: the scan's kernels read time along
    the lanes, and the conv's take their blocks the same way),
    split into x_s [T, H, P], B and C [T, G, N]; the selective
    scan (ops/hybrid_ops.py ssd_scan: dt = softplus(dt + dt_bias), A =
    -exp(A_log), skip D); rms_norm(y, gate=z) in G groups; W_out back to
    D. A_log, dt_bias and D start as published (log U(1, 16); softplus^-1
    of a log-uniform step in [0.001, 0.1]; 1). No bias in either map."""
    helper = LayerHelper("mamba2_mixer", name=name)
    seqlen, d_model = int(x.shape[1]), int(x.shape[2])
    d_inner, bc = num_heads * head_dim, n_groups * state_size
    proj = _linear(x, 2 * d_inner + 2 * bc + num_heads)
    z, xbc, dt = split(proj, [d_inner, d_inner + 2 * bc, num_heads], dim=2)

    dtype = x.dtype
    conv_w = helper.create_parameter(
        attr=None, shape=[d_inner + 2 * bc, conv_kernel], dtype=dtype,
        default_initializer=NormalInitializer(scale=conv_kernel ** -0.5))
    conv_b = helper.create_parameter(attr=None, shape=[d_inner + 2 * bc],
                                     dtype=dtype, is_bias=True)
    conved = helper.create_tmp_variable(dtype)
    helper.append_op(type="causal_conv1d",
                     inputs={"X": [xbc], "Filter": [conv_w],
                             "Bias": [conv_b]},
                     # the scan behind it reads time along the lanes
                     outputs={"Out": [conved]}, attrs={"time_on_lanes": True})
    xs, b, c = split(conved, [d_inner, bc, bc], dim=2)

    dt_bias = helper.create_parameter(
        attr=None, shape=[num_heads], dtype=dtype,
        default_initializer=SoftplusInverseLogUniformInitializer())
    a_log = helper.create_parameter(
        attr=None, shape=[num_heads], dtype=dtype,
        default_initializer=LogOfUniformInitializer())
    skip = helper.create_parameter(
        attr=None, shape=[num_heads], dtype=dtype,
        default_initializer=ConstantInitializer(1.0))
    y = helper.create_tmp_variable(dtype)
    helper.append_op(
        type="ssd_scan",
        inputs={"X": [reshape(xs, [-1, seqlen, num_heads, head_dim])],
                "Dt": [dt], "DtBias": [dt_bias], "ALog": [a_log],
                "B": [reshape(b, [-1, seqlen, n_groups, state_size])],
                "C": [reshape(c, [-1, seqlen, n_groups, state_size])],
                "D": [skip]},
        outputs={"Out": [y]}, attrs={"chunk_size": chunk_size})
    y = rms_norm(reshape(y, [-1, seqlen, d_inner]), gate=z, groups=n_groups,
                 epsilon=epsilon)
    return _linear(y, d_model, scale=out_scale)


def _silu_conv(helper, t, conv_kernel):
    """silu of a causal depthwise convolution of `conv_kernel` taps over t
    [B, T, C], no bias: one causal_conv1d op, taps N(0, K^-1/2) created
    by the layer's `helper`."""
    taps = helper.create_parameter(
        attr=None, shape=[int(t.shape[-1]), conv_kernel], dtype=t.dtype,
        default_initializer=NormalInitializer(scale=conv_kernel ** -0.5))
    out = helper.create_tmp_variable(t.dtype)
    helper.append_op(type="causal_conv1d",
                     inputs={"X": [t], "Filter": [taps]},
                     outputs={"Out": [out]}, attrs={})
    return out


def _kda_scan(helper, inputs, chunk_size, l2_epsilon):
    """Append the delta rule's op over `inputs` and return its Out. Where
    the kernels take the shapes the op is built with the float32 outputs
    they write for its gradient op (hybrid_ops.kda_scan_outputs): each
    chunk's inverse, which outlives the forward where a checkpointed
    segment replays the op, and the state entering each chunk."""
    from ..ops.hybrid_ops import kda_scan_outputs
    q, v, gate = (inputs[s][0] for s in ("Q", "V", "Gate"))
    out = helper.create_tmp_variable(v.dtype)
    kept = kda_scan_outputs(
        int(chunk_size), int(q.shape[3]), int(v.shape[3]),
        int(v.shape[2]) // int(q.shape[2]), len(gate.shape) == 3)
    helper.append_op(
        type="kda_scan", inputs=inputs,
        outputs={"Out": [out],
                 **{s: [helper.create_tmp_variable("float32")] for s in kept}},
        attrs={"chunk_size": int(chunk_size), "epsilon": float(l2_epsilon)})
    return out


@_under_its_name
def kda_mixer(x, num_heads, head_dim, conv_kernel=4, gate_rank=None,
              chunk_size=64, epsilon=1e-5, l2_epsilon=1e-6, out_scale=0.02,
              name=None):
    """Kimi Delta Attention (arXiv:2510.26692) over x [B, T, D], H =
    `num_heads` heads of K = V = `head_dim`:

        q~, k~, v = silu(conv(x W_q)), silu(conv(x W_k)), silu(conv(x W_v))
        gate = (x W_f1) W_f2;  beta = x W_b          [T, H, K] and [T, H], raw
        o = kda_scan(q~, k~, v, gate, A_log, dt_bias, beta)
        y = concat_h(rms_norm(o_h; w) * sigmoid((x W_g1) W_g2)_h) W_o

    A map, a causal depthwise convolution of `conv_kernel` taps (no bias:
    causal_conv1d without its Bias) and silu for each of q, k and v; the
    decay gate and the output gate through low-rank maps of `gate_rank`
    (default the head's width); the op (ops/hybrid_ops.py kda_scan: the
    L2 norm of q~ and k~ a head, g = -exp(A_log) softplus(gate + dt_bias)
    a channel, beta's sigmoid, the gated delta rule in chunks of
    `chunk_size`, all in float32 around bf16 products); one norm weight
    [K] for all heads. A_log [H] and dt_bias [H K] start as Mamba-2's
    (log U(1, 16); softplus^-1 of a log-uniform step in [0.001, 0.1]). No
    bias in any map. Parameters in the order created: W_q, W_k, W_v, the
    three filters, W_f1, W_f2, W_b, A_log, dt_bias, w, W_g1, W_g2, W_o."""
    helper = LayerHelper("kda_mixer", name=name)
    seqlen, d_model = int(x.shape[1]), int(x.shape[2])
    width, rank = num_heads * head_dim, gate_rank or head_dim
    dtype = x.dtype

    def by_head(t):
        return reshape(t, [-1, seqlen, num_heads, head_dim])

    def short_conv(t):
        return by_head(_silu_conv(helper, t, conv_kernel))

    q, k, v = map(short_conv, [_linear(x, width) for _ in range(3)])
    gate = by_head(_linear(_linear(x, rank), width))
    beta = _linear(x, num_heads)
    a_log = helper.create_parameter(
        attr=None, shape=[num_heads], dtype=dtype,
        default_initializer=LogOfUniformInitializer())
    dt_bias = helper.create_parameter(
        attr=None, shape=[width], dtype=dtype,
        default_initializer=SoftplusInverseLogUniformInitializer())
    o = _kda_scan(helper, {"Q": [q], "K": [k], "V": [v], "Gate": [gate],
                           "ALog": [a_log], "DtBias": [dt_bias],
                           "Beta": [beta]}, chunk_size, l2_epsilon)
    o = elementwise_mul(
        rms_norm(o, epsilon=epsilon),
        by_head(_linear(_linear(x, rank), width, act="sigmoid")))
    return _linear(reshape(o, [-1, seqlen, width]), d_model, scale=out_scale)


@_under_its_name
def gdn_mixer(x, num_key_heads, num_value_heads, key_dim, value_dim,
              conv_kernel=4, chunk_size=64, epsilon=1e-6, l2_epsilon=1e-6,
              out_scale=0.02, name=None):
    """Gated DeltaNet (arXiv:2412.06464, as qwen3_next sizes it) over x
    [B, T, D]: Hk = `num_key_heads` heads of K = `key_dim` under Hv =
    `num_value_heads` heads of V = `value_dim`, value head j reading key
    head j // (Hv / Hk):

        q~, k~, v = silu(conv(x W_q)), silu(conv(x W_k)), silu(conv(x W_v))
        z = x W_z [T, Hv, V];  b, alpha = x W_b, x W_alpha     [T, Hv], raw
        o = kda_scan(q~, k~, v, alpha, A_log, dt_bias, b)      a decay a head
        y = concat_j(w * o_j / sqrt(mean o_j^2 + `epsilon`) * silu(z_j)) W_o

    The op (ops/hybrid_ops.py kda_scan in its head-decay form: the L2
    norm of q~ and k~ a head, q over sqrt(K), g = -exp(A_log)
    softplus(alpha + dt_bias) and beta = sigmoid(b) a value head, the
    gated delta rule in chunks of `chunk_size`) reads q~ and k~ at Hk
    heads: nothing is repeated or broadcast ahead of it. The published
    in_proj_qkvz [D, 2 Hk K + 2 Hv V] (grouped by key head) and
    in_proj_ba [D, 2 Hv] are held as the six maps their columns are, and
    the one depthwise convolution over the 2 Hk K + Hv V channels of [q |
    k | v] as the three its taps are (causal_conv1d without its Bias), so
    that each reaches its kernel as the array its product wrote: a
    permutation of columns, the same values and the same count. The norm
    a head is rms_norm with the gate behind it and one plain weight [V].
    A_log [Hv] and dt_bias [Hv] start as Mamba-2's. No bias in any map.
    Parameters in the order created: W_q, W_k, W_v, the three filters,
    W_z, W_b, W_alpha, A_log, dt_bias, w, W_o."""
    helper = LayerHelper("gdn_mixer", name=name)
    seqlen, d_model = int(x.shape[1]), int(x.shape[2])
    heads = (num_key_heads, num_key_heads, num_value_heads)
    dims = (key_dim, key_dim, value_dim)
    dtype = x.dtype

    def short_conv(t, n, width):
        return reshape(_silu_conv(helper, t, conv_kernel),
                       [-1, seqlen, n, width])

    q, k, v = map(short_conv,
                  [_linear(x, n * w) for n, w in zip(heads, dims)], heads,
                  dims)
    z = reshape(_linear(x, num_value_heads * value_dim),
                [-1, seqlen, num_value_heads, value_dim])
    beta, alpha = (_linear(x, num_value_heads) for _ in range(2))
    a_log = helper.create_parameter(
        attr=None, shape=[num_value_heads], dtype=dtype,
        default_initializer=LogOfUniformInitializer())
    dt_bias = helper.create_parameter(
        attr=None, shape=[num_value_heads], dtype=dtype,
        default_initializer=SoftplusInverseLogUniformInitializer())
    o = _kda_scan(helper, {"Q": [q], "K": [k], "V": [v], "Gate": [alpha],
                           "ALog": [a_log], "DtBias": [dt_bias],
                           "Beta": [beta]}, chunk_size, l2_epsilon)
    o = rms_norm(o, gate=z, epsilon=epsilon, gate_behind=True)
    return _linear(reshape(o, [-1, seqlen, num_value_heads * value_dim]),
                   d_model, scale=out_scale)


@_under_its_name
def short_conv_mixer(x, conv_kernel=3, out_scale=0.02, name=None):
    """LFM2's gated short convolution (the `conv` layers of lfm2 /
    lfm2_moe) over x [B, T, D]:

        Bg, Cg, xs = x W_B, x W_C, x W_x        three maps [D, D]: the
                                                thirds of the published
                                                in_proj [D, 3 D], in its
                                                order (B, C, x)
        z[t] = sum_j w[:, j] * (Bg * xs)[t - (K-1) + j]     depthwise,
                                zeros before t = 0, no bias, no activation
        y = (Cg * z) W_out

    One causal_conv1d op with both gates and `activation` "identity":
    the gate ahead, the taps and the gate behind run in one kernel that
    reads the three maps' rows once and writes once
    (ops/pallas_conv1d.py), and its gradient op another. The thirds are
    three maps, not slices of one projection, so that each reaches the
    kernel as the array its product wrote (a slice of [T, 3 D] handed to
    a kernel is a copy); the values, the parameters' count and the
    published order are the one projection's. No bias in any map; taps
    N(0, K^-1/2) as the other mixers'. Parameters in the order created:
    W_B, W_C, W_x, the taps [D, K], W_out."""
    helper = LayerHelper("short_conv_mixer", name=name)
    d_model = int(x.shape[2])
    dtype = x.dtype
    gate_ahead, gate_behind, xs = (_linear(x, d_model) for _ in range(3))
    taps = helper.create_parameter(
        attr=None, shape=[d_model, conv_kernel], dtype=dtype,
        default_initializer=NormalInitializer(scale=conv_kernel ** -0.5))
    out = helper.create_tmp_variable(dtype)
    helper.append_op(type="causal_conv1d",
                     inputs={"X": [xs], "Filter": [taps],
                             "PreGate": [gate_ahead],
                             "PostGate": [gate_behind]},
                     outputs={"Out": [out]},
                     attrs={"activation": "identity"})
    return _linear(out, d_model, scale=out_scale)


@_under_its_name
def moe_block(x, num_experts, top_k, expert_width, shared_width=0,
              experts_held=None, expert_offset=0, scaling=1.0,
              norm_topk_prob=True, out_scale=0.02, stats=None, name=None,
              gated=False, scoring="sigmoid", router_input=None,
              gate_act="silu", norm_epsilon=1e-20, shared_gate=False):
    """Mixture-of-experts feed-forward over x [B, T, D] with a top-k
    router, squared-ReLU experts (`gated`: gated SiLU experts,
    f(x) = (silu(x G) * (x U)) V, three matrices an expert, the shared
    expert likewise; `gate_act` "relu": ReGLU routed experts,
    relu(x G) * (x U)) and, with `shared_width`, a shared expert.
    `router_input` (a Variable of x's shape; default x): what the router
    reads where that is not what the experts read (SmallThinker's router
    reads the attention's normed input, its experts the attention's
    normed output); s below is then computed from it:

        s = sigmoid(x W_r) in float32 (`scoring` "sigmoid", the default:
        nemotron_h's and glm4_moe_lite's router) or softmax(x W_r) over
        all `num_experts` ("softmax": sdar_moe's, the Qwen3-MoE block's);
        the top_k of s + b (sigmoid) or s * exp(b) (softmax) are chosen
        (b: a selection bias, a buffer that starts at zero and takes no
        gradient; models.balance_routers moves it against the load);
        g_i = scaling * s_i / (sum of the chosen s + `norm_epsilon`)
        out = sum_i g_i f_{e_i}(x) + f_shared(x),  f(x) = relu(x U)^2 V

    The layer is told its share: it holds `experts_held` of `num_experts`
    from `expert_offset` on (default: all), routes over all of them, and
    adds only what its own experts give (ops/hybrid_ops.py moe_experts:
    no token dropped, static shapes, a grouped product over the rows
    actually routed here); the shared expert is applied to every token.
    Without one (`shared_width` 0) a token none of whose choices is held
    here gets exactly zero from the layer. `shared_gate`: the shared
    expert's output is times sigmoid(x w_sg), one scalar a token (w_sg
    [D, 1], created behind the shared expert's three maps;
    qwen2_moe's and qwen3_next's shared expert).
    `stats`: a list that receives this layer's (rows routed to held
    experts, rows combined, busiest held expert over their mean, rows
    handled: the capacity the step's routed rows were given) Variables,
    each [1]."""
    from ..param_attr import ParamAttr
    helper = LayerHelper("moe_block", name=name)
    seqlen, d_model = int(x.shape[1]), int(x.shape[2])
    held = num_experts if experts_held is None else experts_held
    assert 0 <= expert_offset and expert_offset + held <= num_experts
    assert gate_act in ("silu", "relu") and (gated or gate_act == "silu")
    assert not (shared_width and gate_act != "silu"), \
        "the shared expert is gated_mlp's silu form"
    dtype = x.dtype
    tokens = reshape(x, [-1, d_model])
    routed_by = tokens if router_input is None \
        else reshape(router_input, [-1, d_model])

    router_w = helper.create_parameter(
        attr=None, shape=[d_model, num_experts], dtype=dtype,
        default_initializer=NormalInitializer(scale=0.02))
    router_b = helper.create_parameter(
        attr=ParamAttr(trainable=False), shape=[num_experts], dtype=dtype,
        default_initializer=ConstantInitializer(0.0))
    idx = helper.create_tmp_variable("int32", stop_gradient=True)
    weight = helper.create_tmp_variable("float32")
    router_attrs = {"top_k": top_k, "scaling": scaling,
                    "norm_topk_prob": norm_topk_prob}
    if scoring != "sigmoid":    # the op's default: a sigmoid router's
        router_attrs["scoring"] = scoring   # program is the one it was
    if norm_epsilon != 1e-20:   # likewise (lfm2_moe's routers: 1e-6)
        router_attrs["norm_epsilon"] = float(norm_epsilon)
    helper.append_op(type="moe_router",
                     inputs={"X": [routed_by], "W": [router_w],
                             "Bias": [router_b]},
                     outputs={"TopkIdx": [idx], "TopkWeight": [weight]},
                     attrs=router_attrs)

    inputs = {"X": [tokens], "TopkIdx": [idx], "TopkWeight": [weight]}
    if gated:
        inputs["WGate"] = [helper.create_parameter(
            attr=None, shape=[held, d_model, expert_width], dtype=dtype,
            default_initializer=NormalInitializer(scale=0.02))]
    up = helper.create_parameter(
        attr=None, shape=[held, d_model, expert_width], dtype=dtype,
        default_initializer=NormalInitializer(scale=0.02))
    down = helper.create_parameter(
        attr=None, shape=[held, expert_width, d_model], dtype=dtype,
        default_initializer=NormalInitializer(scale=out_scale))
    routed = helper.create_tmp_variable(dtype)
    rows, combined, load, handled = (
        helper.create_tmp_variable("float32", stop_gradient=True)
        for _ in range(4))
    expert_attrs = {"num_experts": num_experts, "experts_held": held,
                    "expert_offset": expert_offset, "top_k": top_k}
    if gate_act != "silu":      # the op's default: a silu gate's program
        expert_attrs["gate_act"] = gate_act     # is the one it was
    # what the op keeps for its gradient op: the up product's rows, and
    # the gate's
    kept = {slot: [helper.create_tmp_variable(dtype, stop_gradient=True)]
            for slot in (("Up", "GateUp") if gated else ("Up",))}
    helper.append_op(type="moe_experts",
                     inputs=dict(inputs, W1=[up], W2=[down]),
                     outputs={"Out": [routed], "RowsRouted": [rows],
                              "RowsCombined": [combined],
                              "LoadMaxOverMean": [load],
                              "RowsHandled": [handled], **kept},
                     attrs=expert_attrs)
    if stats is not None:
        stats.append((rows, combined, load, handled))
    out = reshape(routed, [-1, seqlen, d_model])
    assert not shared_gate or (shared_width and gated)
    if shared_width and gated:
        shared = gated_mlp(x, shared_width, out_scale=out_scale)
        if shared_gate:     # [B, T, D] times [B, T]: a scalar a token
            shared = elementwise_mul(shared, reshape(
                _linear(x, 1, act="sigmoid"), [-1, seqlen]), axis=0)
        out = elementwise_add(out, shared)
    elif shared_width:
        hidden = _linear(x, shared_width, act="relu2")
        out = elementwise_add(out, _linear(hidden, d_model, scale=out_scale))
    return out


def yarn_mscale(factor, mscale=1.0):
    """YaRN's magnitude factor 0.1 mscale ln(factor) + 1 (1 at a factor
    of 1 or less): arXiv:2309.00071 section 3.4 at mscale 1, and
    DeepSeek-V3's `yarn_get_mscale`."""
    return 0.1 * float(mscale) * math.log(factor) + 1.0 if factor > 1 else 1.0


@_under_its_name
def rotary_embedding(x, theta=10000.0, rotary_dims=None, name=None,
                     scaling=None, rotate_first=False):
    """Rotary position embedding over x [B, T, H, D]: the last
    `rotary_dims` of every head (default all D) are rotated by the
    position t along axis 1, the pair (i, i + r/2) of those r dims by the
    angle t * theta^(-2i/r); the dims before them pass through. With
    `rotate_first` the FIRST `rotary_dims` are the rotated ones and the
    dims behind them pass through (qwen3_next: 64 of 256; the attribute
    is written only when set). Angles
    and the rotation are float32 under AMP (ops/hybrid_ops.py).

    `scaling`: a published `rope_parameters` / `rope_scaling` group.
    `rope_type` "yarn" (arXiv:2309.00071) blends each pair's frequency
    between theta^(-2i/r) and that over `factor`, by how often the pair
    turns over `original_max_position_embeddings` (`beta_fast`, default
    32, and `beta_slow`, default 1, bound the blend), and multiplies the
    cosines and sines by `attention_factor` (default 0.1 ln(factor) + 1):
    the rotated dims of queries and keys come out scaled by it, the
    others do not. A group in the DeepSeek keys (`type` where `rope_type`
    is absent, `mscale` and `mscale_all_dim`) has no `attention_factor`
    and gives the cosines and sines m(mscale) / m(mscale_all_dim), m(s) =
    0.1 s ln(factor) + 1, as DeepSeek-V3's modelling code reads them
    (yarn_mscale; the scores' own factor is latent_attention's). None, or
    `rope_type` "default": the plain angles. The
    op's new attributes are written only when set, so a program without
    `scaling` is the one it was."""
    attrs = {"theta": float(theta),
             "rotary_dims": int(rotary_dims or x.shape[-1])}
    if rotate_first:
        attrs["rotate_first"] = True
    scaling = scaling or {}
    kind = scaling.get("rope_type", scaling.get("type", "default"))
    if kind == "yarn":
        factor = float(scaling["factor"])
        if "mscale_all_dim" in scaling:
            default = yarn_mscale(factor, scaling.get("mscale", 1)) \
                / yarn_mscale(factor, scaling["mscale_all_dim"])
        else:
            default = yarn_mscale(factor)
        attrs.update(
            yarn_factor=factor,
            yarn_original_positions=float(
                scaling["original_max_position_embeddings"]),
            yarn_beta_fast=float(scaling.get("beta_fast") or 32),
            yarn_beta_slow=float(scaling.get("beta_slow") or 1),
            attention_factor=float(scaling.get("attention_factor")
                                   or default))
    elif kind != "default":
        raise ValueError(f"rotary_embedding knows rope_type 'default' and "
                         f"'yarn', not {kind!r}")
    return _simple("rotary_embedding", x, name=name, attrs=attrs)


@_under_its_name
def gated_mlp(x, width, out_scale=0.02, name=None):
    """Gated (SwiGLU) feed-forward over x [B, T, D]:
    (silu(x W_g) * (x W_u)) W_d, no bias. `name` names the three weights
    `<name>.gate`, `<name>.up` and `<name>.down`, so that a second
    feed-forward can read the same ones."""
    gate, up, down = (name and f"{name}.{part}"
                      for part in ("gate", "up", "down"))
    hidden = elementwise_mul(_linear(x, width, act="silu", name=gate),
                             _linear(x, width, name=up))
    return _linear(hidden, int(x.shape[-1]), scale=out_scale, name=down)


@_under_its_name
def latent_attention(x, num_heads, q_lora_rank, kv_lora_rank,
                     qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                     rope_theta=10000.0, epsilon=1e-5, out_scale=0.02,
                     use_flash="auto", rotate=True, rope_scaling=None):
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 section
    2.1) over x [B, T, D], in its expanded (training) form:

        c_q = rms_norm(x W_qa);  [q_nope_j | q_rope_j] = (c_q W_qb)_j
        [c_kv | k_rope] = x W_kva;  [k_nope_j | v_j] = (rms_norm(c_kv) W_kvb)_j
        q_j = [q_nope_j | R_t q_rope_j],  k_j = [k_nope_j | R_t k_rope]
        out = concat_j(softmax(q_j k_j^T / sqrt(nope + rope), causal) v_j) W_o

    Queries and keys/values come through low-rank latents with an
    rms_norm on each; the rotary part of the key is ONE head, read by all
    `num_heads` query heads, and is broadcast to them here before the
    attention op, so q, k, v reach it as [B, T, H, nope + rope] and
    [B, T, H, v_head_dim] and 'auto' takes the flash kernels where they
    tile. No bias in any map.

    `q_lora_rank` None (Kimi-Linear's `q_lora_rank: null`): the queries
    are one direct map, [q_nope_j | q_rope_j] = (x W_q)_j, with no latent
    and no norm. `rotate` False (its `mla_use_nope`): no rotation; the
    one shared key head is carried to every query head unturned, and the
    layer has no positions. Where the keys' width differs from the
    values' (its 192 beside 128, which the flash kernels' gate answers
    `shape`), q, k and v reach the op with zero lanes up to the next
    multiple of 128 (256), q scaled by sqrt(lanes / (nope + rope)) for
    the op's 1 / sqrt(lanes), and the output's leading v_head_dim lanes
    are taken: scores and outputs are exact, the kernels walk 1.6 times
    the live products. `rope_scaling`: the published group, handed to
    both rotations (rotary_embedding's `scaling`); where it carries
    `mscale_all_dim` (DeepSeek-V3's YaRN) the scores are also times
    m(mscale_all_dim)^2 over 1 / sqrt(nope + rope), which q carries.
    Every departure is built only when asked for: a program with a
    latent query, a plain rotation and equal widths is the one it was."""
    from .tensor import concat
    seqlen, d_model = int(x.shape[1]), int(x.shape[2])
    qk_dim = qk_nope_head_dim + qk_rope_head_dim
    q_in = x if q_lora_rank is None \
        else rms_norm(_linear(x, q_lora_rank), epsilon=epsilon)
    q = reshape(_linear(q_in, num_heads * qk_dim),
                [-1, seqlen, num_heads, qk_dim])
    if rotate:
        q = rotary_embedding(q, theta=rope_theta,
                             rotary_dims=qk_rope_head_dim,
                             scaling=rope_scaling)
    c_kv, k_rope = split(_linear(x, kv_lora_rank + qk_rope_head_dim),
                         [kv_lora_rank, qk_rope_head_dim], dim=2)
    k_rope = reshape(k_rope, [-1, seqlen, 1, qk_rope_head_dim])
    if rotate:
        k_rope = rotary_embedding(k_rope, theta=rope_theta,
                                  scaling=rope_scaling)
    kv = reshape(_linear(rms_norm(c_kv, epsilon=epsilon),
                         num_heads * (qk_nope_head_dim + v_head_dim)),
                 [-1, seqlen, num_heads, qk_nope_head_dim + v_head_dim])
    k_nope, v = split(kv, [qk_nope_head_dim, v_head_dim], dim=3)
    k = concat([k_nope, expand(k_rope, [1, 1, num_heads, 1])], axis=3)
    lanes = max(qk_dim, v_head_dim)
    # what q carries beside the op's 1 / sqrt(lanes): YaRN's factor of
    # the scores, and the lanes' own where zeros widen a head
    q_scale = 1.0
    if rotate and "mscale_all_dim" in (rope_scaling or {}):
        q_scale = yarn_mscale(float(rope_scaling["factor"]),
                              rope_scaling["mscale_all_dim"]) ** 2
    if qk_dim != v_head_dim:
        lanes = -(-lanes // 128) * 128 if lanes > 128 else lanes
        q_scale *= (lanes / qk_dim) ** 0.5
    if q_scale != 1.0:
        q = scale(q, scale=q_scale)
    if qk_dim != v_head_dim:
        def widened(t, width):
            return t if width == lanes else pad(
                t, [0, 0, 0, 0, 0, 0, 0, lanes - width])

        q, k, v = widened(q, qk_dim), widened(k, qk_dim), \
            widened(v, v_head_dim)
    attn = fused_attention(q, k, v, causal=True, use_flash=use_flash)
    if lanes != v_head_dim:
        attn = split(attn, [v_head_dim, lanes - v_head_dim], dim=3)[0]
    return _linear(reshape(attn, [-1, seqlen, num_heads * v_head_dim]),
                   d_model, scale=out_scale)


@_under_its_name
def mtp_block(hidden, next_tokens, vocab_size, embedding_name, head_name,
              block, epsilon=1e-5):
    """One multi-token-prediction module (DeepSeek-V3, arXiv:2412.19437
    section 2.2) over the main model's last hidden state `hidden`
    [B, T, D] (before its final norm) and the ids one position on,
    `next_tokens` [B, T]:

        u = [rms_norm_e(E[next_tokens]) ; rms_norm_h(hidden)] W_eh
        u = block(u);  logits = rms_norm(u) W_head

    E and W_head are the main model's embedding table and head, named by
    `embedding_name` and `head_name` and read here a second time: the
    program holds each once (LayerHelper.create_parameter). `block` maps
    [B, T, D] to [B, T, D] and builds the module's own decoder block.
    Returns the logits [B, T, vocab_size], for the ids two positions on."""
    from ..param_attr import ParamAttr
    from .tensor import concat
    d_model = int(hidden.shape[-1])
    emb = embedding(next_tokens, size=[vocab_size, d_model],
                    param_attr=ParamAttr(name=embedding_name))
    u = concat([rms_norm(emb, epsilon=epsilon),
                rms_norm(hidden, epsilon=epsilon)], axis=2)
    u = block(_linear(u, d_model))
    return _linear(rms_norm(u, epsilon=epsilon), vocab_size, name=head_name)


def sinkhorn_knopp(logits, iters=20, eps=1e-6, clamp=(-30.0, 30.0),
                   stats=None, name=None):
    """[..., n, n] logits -> the map `iters` Sinkhorn-Knopp sweeps make
    of exp(clamp(logits)), float32: every sweep divides the columns by
    their sums + `eps`, then the rows (ops/hyper_connection_ops.py; the
    gradient op runs the sweeps again and keeps none). `stats`: a list
    that receives this map's (largest |row or column sum - 1|, mean
    trace / n) Variables, each [1]."""
    helper = LayerHelper("sinkhorn_knopp", name=name)
    out = helper.create_tmp_variable("float32")
    error, mass = (helper.create_tmp_variable("float32", stop_gradient=True)
                   for _ in range(2))
    with name_scope("sinkhorn_knopp"):
        helper.append_op(
            type="sinkhorn_knopp", inputs={"Logits": [logits]},
            outputs={"Out": [out], "SumError": [error],
                     "DiagonalMass": [mass]},
            attrs={"iters": int(iters), "eps": float(eps),
                   "clamp_min": float(clamp[0]),
                   "clamp_max": float(clamp[1])})
    if stats is not None:
        stats.append((error, mass))
    return out


@_under_its_name
def hyper_connection_maps(streams, sinkhorn_iters=20, eps=1e-6,
                          res_clamp=(-30.0, 30.0), epsilon=1e-6, stats=None,
                          name=None):
    """The three maps one sublayer's hyper-connection reads from the
    token's n residual streams `streams` [B, T, n, C] (manifold-
    constrained hyper-connections, arXiv:2512.24880):

        u = vec(X) / sqrt(mean(vec(X)^2) + epsilon)     float32, no weight
        [p | q | r] = u phi                             widths n, n, n^2
        H_pre  = sigmoid(alpha_pre p + b_pre)           [B, T, n]
        H_post = 2 sigmoid(alpha_post q + b_post)       [B, T, n]
        H_res  = sinkhorn_knopp(alpha_res mat(r) + b_res)   [B, T, n, n]

    -> (H_pre, H_post, H_res), float32. Parameters, created in this
    order: phi [n C, 2 n + n^2] from N(0, 0.02); b [2 n + n^2] = b_pre
    logit(1 / n) (H_pre starts at 1 / n), b_post 0 (H_post at 1), b_res 4
    I (H_res near the identity: 0.948 on the diagonal at n = 4); alpha
    [3] = 0.01 each. The sweeps are built under
    `hyper_connection_maps.sinkhorn_knopp`."""
    helper = LayerHelper("hyper_connection_maps", name=name)
    n, width = int(streams.shape[-2]), int(streams.shape[-1])
    maps = 2 * n + n * n
    phi = helper.create_parameter(
        attr=None, shape=[n * width, maps], dtype="float32",
        default_initializer=NormalInitializer(scale=0.02))
    bias = helper.create_parameter(
        attr=None, shape=[maps], dtype="float32",
        default_initializer=ValuesInitializer(
            [-math.log(n - 1.0)] * n + [0.0] * n
            + [4.0 * (i == j) for i in range(n) for j in range(n)]))
    alpha = helper.create_parameter(
        attr=None, shape=[3], dtype="float32",
        default_initializer=ConstantInitializer(0.01))
    pre, post, logits = (helper.create_tmp_variable("float32")
                         for _ in range(3))
    helper.append_op(
        type="hyper_connection_maps",
        inputs={"X": [streams], "Phi": [phi], "Bias": [bias],
                "Alpha": [alpha]},
        outputs={"Pre": [pre], "Post": [post], "ResLogits": [logits]},
        attrs={"epsilon": float(epsilon)})
    return pre, post, sinkhorn_knopp(logits, sinkhorn_iters, eps, res_clamp,
                                     stats=stats)


@_under_its_name
def hc_pre_mix(streams, pre, name=None):
    """What a sublayer behind a hyper-connection reads: sum_j pre[j]
    streams[j], [B, T, n, C] and [B, T, n] -> [B, T, C] in the
    activations' dtype, summed in float32 (op hc_pre_mix, with its own
    gradient op)."""
    helper = LayerHelper("hc_pre_mix", name=name)
    out = helper.create_tmp_variable(streams.dtype)
    helper.append_op(type="hc_pre_mix",
                     inputs={"X": [streams], "Pre": [pre]},
                     outputs={"Out": [out]})
    return out


@_under_its_name
def hc_post_res_mix(streams, y, res, post, name=None):
    """What a sublayer behind a hyper-connection writes back: X'[i] =
    sum_j res[i, j] streams[j] + post[i] y, with y [B, T, C] the
    sublayer's output, -> [B, T, n, C] in the activations' dtype (bf16
    under AMP O2 whatever the embedding's rows arrive in), summed in
    float32 (op hc_post_res_mix, with its own gradient op)."""
    helper = LayerHelper("hc_post_res_mix", name=name)
    out = helper.create_tmp_variable(streams.dtype)
    helper.append_op(type="hc_post_res_mix",
                     inputs={"X": [streams], "Y": [y], "Res": [res],
                             "Post": [post]},
                     outputs={"Out": [out]})
    return out


def hsigmoid(input, label, num_classes, param_attr=None, bias_attr=None,
             name=None):
    """Hierarchical sigmoid classification cost over a complete binary tree
    (reference gserver HierarchicalSigmoidLayer.cpp; fluid hsigmoid). Cost
    is -log P(label) under the tree factorization; O(log C) tree nodes per
    sample instead of a C-way softmax. Returns [B, 1]."""
    helper = LayerHelper("hierarchical_sigmoid", param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    dim = input.shape[-1]
    w = helper.create_parameter(attr=helper.param_attr,
                                shape=[num_classes - 1, dim],
                                dtype=input.dtype)
    inputs = {"X": [input], "W": [w], "Label": [label]}
    if bias_attr is not False:
        b = helper.create_parameter(attr=helper.bias_attr,
                                    shape=[1, num_classes - 1],
                                    dtype=input.dtype, is_bias=True)
        inputs["Bias"] = [b]
    cost = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="hierarchical_sigmoid", inputs=inputs,
                     outputs={"Cost": [cost]},
                     attrs={"num_classes": int(num_classes)})
    return cost


def bilinear_interp(input, out_h, out_w, name=None):
    """Bilinear upsampling of NCHW feature maps (reference gserver
    BilinearInterpLayer.cpp; corners-aligned ratio (in-1)/(out-1))."""
    helper = LayerHelper("bilinear_interp", name=name)
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="bilinear_interp", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"out_h": int(out_h), "out_w": int(out_w)})
    return out


def selective_fc(input, select, size, act=None, param_attr=None,
                 bias_attr=None, name=None):
    """Fully-connected layer computing only selected output columns per
    sample (reference gserver SelectiveFullyConnectedLayer.cpp: with a
    selection the layer evaluates just those columns; the TPU-native dense
    form computes the full gemm on the MXU and masks — identical outputs,
    zeros at unselected columns, and XLA fuses the mask into the gemm
    epilogue). `select` is a [B, size] 0/1 mask."""
    out = fc(input=input, size=size, act=act, param_attr=param_attr,
             bias_attr=bias_attr, name=name)
    helper = LayerHelper("selective_fc", name=name)
    masked = helper.create_tmp_variable(out.dtype)
    helper.append_op(type="elementwise_mul",
                     inputs={"X": [out], "Y": [select]},
                     outputs={"Out": [masked]}, attrs={"axis": -1})
    return masked
