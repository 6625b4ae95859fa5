"""Gated-short-convolution / grouped-query-attention mixture-of-experts
decoder on the layers DSL: the `lfm2_moe` architecture (LiquidAI
LFM2-24B-A2B; https://huggingface.co/LiquidAI/LFM2-24B-A2B). Layer l, by
its published index:

    u = rms_norm(h);  h <- h + mixer_l(u);  f = rms_norm(h);  h <- h + ffn_l(f)

`mixer_l` by `layer_types[l]`: "conv" the gated short convolution
(layers.short_conv_mixer: C * conv_3(B * x) between an input and an
output map, no activation), "full_attention" causal grouped-query
attention with an rms_norm over the dims of every query and key head (one
weight of head_dim for all heads) ahead of a rotate-half rotation of the
whole head. `ffn_l` is layers.gated_mlp where l < `num_dense_layers` and
layers.moe_block behind them: a sigmoid router whose top-k is chosen on
the score plus a selection bias (the family's `use_expert_bias`: the
buffer models.balance_routers moves), whose weights are the unbiased
scores over their sum + 1e-6 times `routed_scaling_factor`, gated SiLU
experts, no shared expert. After the last layer the family's
`embedding_norm` and the head, which is the embedding itself; the loss is
the mean next-token cross-entropy. No bias in any map, embedding unscaled.
"""

from __future__ import annotations

from .. import layers
from ..framework.framework import name_scope
from ..initializer import NormalInitializer
from ..layers.nn import _linear
from ..param_attr import ParamAttr
from .common import mark_routing_stats, side_fetch_marks

EMBEDDING = "conv_moe_lm.embedding"
LOSS_METRIC = "loss"
CONV, FULL = "conv", "full_attention"
# the name scope of an attention layer's QK-norm, rotation and attention op
ATTENTION_SCOPE = "gqa_attention"
# lfm2_moe's router: the chosen scores over (their sum + this)
ROUTER_NORM_EPSILON = 1e-6


def conv_moe_lm(tokens, labels, vocab_size, hidden_size, layer_types,
                num_dense_layers, num_attention_heads, num_key_value_heads,
                intermediate_size, num_experts, num_experts_per_tok,
                moe_intermediate_size, layers_held=None, conv_kernel=3,
                experts_held=None, expert_offset=0, routed_scaling_factor=1.0,
                norm_topk_prob=True, rope_theta=1000000.0, epsilon=1e-5,
                initializer_range=0.02, residual_layers=None,
                use_flash="auto", recompute=False):
    """tokens / labels [B, T] int (labels the ids one position on) ->
    (the mean next-token cross-entropy, the checkpoints).

    `layer_types`: "conv" or "full_attention", one a published layer.
    `layers_held`: the published indices of the layers this program
    builds, in order (default all of `layer_types`); a layer's mixer is
    `layer_types[l]`'s and its feed-forward dense where l <
    `num_dense_layers`, whatever else is held. `head_dim` is hidden_size /
    num_attention_heads. `experts_held` / `expert_offset`: the share of
    the `num_experts` this program holds in every expert layer (default
    all); the router keeps its full width, and with no shared expert a
    token none of whose choices is held here gets zero from the layer.
    `residual_layers`: the depth the output maps' (W_out, W_o, every
    down map) initial scale `initializer_range` / sqrt(depth) is reckoned
    from (default len(layer_types)). `recompute`: the checkpoints are the
    residual stream at every layer's input, for `minimize(loss,
    checkpoints=)` to keep and to replay each layer's forward ops ahead of
    its gradient ops; else there are none."""
    seqlen = int(tokens.shape[-1])
    heads, kv_heads = num_attention_heads, num_key_value_heads
    head_dim = hidden_size // heads
    std = initializer_range
    out_scale = std / (residual_layers or len(layer_types)) ** 0.5
    held = range(len(layer_types)) if layers_held is None else layers_held
    stats, kept = [], []

    def by_head(x, n):
        return layers.reshape(x, [-1, seqlen, n, head_dim])

    x = layers.embedding(
        tokens, size=[vocab_size, hidden_size],
        param_attr=ParamAttr(name=EMBEDDING,
                             initializer=NormalInitializer(scale=std)))
    table = x.block.program.global_block().var(EMBEDDING)
    for l in held:
        kind = layer_types[l]
        if recompute:
            kept.append(x)
        u = layers.rms_norm(x, epsilon=epsilon)
        if kind == CONV:
            mixed = layers.short_conv_mixer(u, conv_kernel=conv_kernel,
                                            out_scale=out_scale)
        elif kind == FULL:
            q, k, v = (by_head(_linear(u, n * head_dim, scale=std), n)
                       for n in (heads, kv_heads, kv_heads))
            # QK-norm, the rotation and the attention op are the layer a
            # trace books under its scope; the four maps stay the model's
            with name_scope(ATTENTION_SCOPE):
                q, k = (layers.rotary_embedding(
                    layers.rms_norm(t, epsilon=epsilon), theta=rope_theta)
                    for t in (q, k))
                attn = layers.fused_attention(q, k, v, causal=True,
                                              use_flash=use_flash)
            mixed = _linear(
                layers.reshape(attn, [-1, seqlen, heads * head_dim]),
                hidden_size, scale=out_scale)
        else:
            raise ValueError(f"layer {l} of type {kind!r}")
        x = layers.elementwise_add(x, mixed)
        f = layers.rms_norm(x, epsilon=epsilon)
        if l < num_dense_layers:
            h = layers.gated_mlp(f, intermediate_size, out_scale=out_scale)
        else:
            h = layers.moe_block(
                f, num_experts, num_experts_per_tok, moe_intermediate_size,
                experts_held=experts_held, expert_offset=expert_offset,
                scaling=routed_scaling_factor, norm_topk_prob=norm_topk_prob,
                out_scale=out_scale, stats=stats, gated=True,
                norm_epsilon=ROUTER_NORM_EPSILON)
        x = layers.elementwise_add(x, h)

    logits = layers.matmul(layers.rms_norm(x, epsilon=epsilon), table,
                           transpose_y=True)
    loss = layers.mean(layers.softmax_with_cross_entropy(
        logits=layers.reshape(logits, [-1, vocab_size]),
        label=layers.reshape(labels, [-1, 1])))
    program = loss.block.program
    # a copy of the loss: the executor side-fetches no variable the user
    # fetches, and every training loop fetches the loss itself
    side_fetch_marks(program)[LOSS_METRIC] = layers.scale(
        loss, scale=1.0).name
    if stats:
        mark_routing_stats(program, stats)
    return loss, kept
