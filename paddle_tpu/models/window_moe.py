"""Mixture-of-experts decoder whose attention layers are of two kinds, on
the layers DSL: the `smallthinker` architecture
(SmallThinker-21BA3B-Instruct;
https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct,
arXiv:2507.20984). Per layer two published layouts say which kind it is:
`sliding_window_layout[l]` 1 = the layer's causal attention sees the last
`sliding_window_size` keys only, 0 = every earlier key; `rope_layout[l]`
1 = queries and keys are rotated (rotate-half RoPE over the whole head),
0 = the layer has no positional encoding at all. Every block is

    a = rms_norm(x)
    (idx, g) = router(a)           softmax over the chosen top-k, BEFORE
                                   the attention and from its input
    x <- x + W_o attention([rope](W_q a), [rope](W_k a), W_v a [, window])
    b = rms_norm(x)
    x <- x + sum_{e in idx} g_e (relu(b G_e) * (b U_e)) D_e

with grouped-query heads, ReGLU experts that read the attention's normed
OUTPUT under the weights the router made from its normed INPUT
(layers.moe_block's `router_input`), no shared expert and no dense layer.
After the last block a final rms_norm and an untied head; the loss is the
mean next-token cross-entropy. No bias in any linear map, embedding
unscaled.
"""

from __future__ import annotations

from .. import layers
from ..framework.framework import name_scope
from ..initializer import NormalInitializer
from ..layers.nn import _linear
from ..param_attr import ParamAttr
from .common import mark_routing_stats, side_fetch_marks

LOSS_METRIC = "loss"
# the name scope of a layer's rotations and attention op, by its kind
WINDOW_SCOPE, GLOBAL_SCOPE = "window_attention", "global_attention"


def window_moe_lm(tokens, labels, vocab_size, hidden_size, num_hidden_layers,
                  num_attention_heads, num_key_value_heads, head_dim,
                  num_experts, num_experts_per_tok, moe_intermediate_size,
                  sliding_window_layout, rope_layout, sliding_window_size,
                  experts_held=None, expert_offset=0, norm_topk_prob=True,
                  rope_theta=10000.0, epsilon=1e-6, residual_layers=None,
                  use_flash="auto", embedding_std=0.02):
    """tokens / labels [B, T] int (labels the ids one position on) -> the
    mean next-token cross-entropy.

    `sliding_window_layout` / `rope_layout`: a flag a layer, read for the
    first `num_hidden_layers` (a cut model passes the published lists
    whole). `experts_held` / `expert_offset`: the share of the
    `num_experts` this program holds in every layer (default all); the
    router keeps its full width, and with no shared expert a token none
    of whose choices is held here gets zero from the layer.
    `residual_layers`: the depth the output maps' initial scale
    0.02 / sqrt(depth) is reckoned from (default num_hidden_layers; a cut
    model passes the published depth)."""
    seqlen = int(tokens.shape[-1])
    out_scale = 0.02 / (residual_layers or num_hidden_layers) ** 0.5
    heads, kv_heads = num_attention_heads, num_key_value_heads
    assert min(len(sliding_window_layout), len(rope_layout)) >= \
        num_hidden_layers
    stats = []

    def by_head(x, n):
        return layers.reshape(x, [-1, seqlen, n, head_dim])

    x = layers.embedding(
        tokens, size=[vocab_size, hidden_size],
        param_attr=ParamAttr(
            initializer=NormalInitializer(scale=embedding_std)))
    for windowed, rotated in zip(sliding_window_layout[:num_hidden_layers],
                                 rope_layout[:num_hidden_layers]):
        a = layers.rms_norm(x, epsilon=epsilon)
        q, k, v = (by_head(_linear(a, n * head_dim), n)
                   for n in (heads, kv_heads, kv_heads))
        # the rotations and the attention op are the layer a trace books
        # under its kind's scope; the four maps stay the model's
        with name_scope(WINDOW_SCOPE if windowed else GLOBAL_SCOPE):
            if rotated:
                q, k = (layers.rotary_embedding(t, theta=rope_theta)
                        for t in (q, k))
            attn = layers.fused_attention(
                q, k, v, causal=True, use_flash=use_flash,
                window=sliding_window_size if windowed else 0)
        x = layers.elementwise_add(x, _linear(
            layers.reshape(attn, [-1, seqlen, heads * head_dim]),
            hidden_size, scale=out_scale))
        x = layers.elementwise_add(x, layers.moe_block(
            layers.rms_norm(x, epsilon=epsilon), num_experts,
            num_experts_per_tok, moe_intermediate_size,
            experts_held=experts_held, expert_offset=expert_offset,
            norm_topk_prob=norm_topk_prob, out_scale=out_scale, stats=stats,
            gated=True, scoring="softmax", router_input=a, gate_act="relu"))

    logits = _linear(layers.rms_norm(x, epsilon=epsilon), vocab_size)
    loss = layers.mean(layers.softmax_with_cross_entropy(
        logits=layers.reshape(logits, [-1, vocab_size]),
        label=layers.reshape(labels, [-1, 1])))
    program = loss.block.program
    # a copy of the loss: the executor side-fetches no variable the user
    # fetches, and every training loop fetches the loss itself
    side_fetch_marks(program)[LOSS_METRIC] = layers.scale(
        loss, scale=1.0).name
    mark_routing_stats(program, stats)
    return loss
