"""Mixture-of-experts decoder whose attention layers differ in kind, head
count and positions, with a gate a head on the attention's output, on the
layers DSL: the `laguna` architecture (poolside Laguna-XS.2;
https://huggingface.co/poolside/Laguna-XS.2). Layer l of kind
`layer_types[l]` has H_l = `num_attention_heads_per_layer[l]` query heads
over `num_key_value_heads` key/value heads:

    a = rms_norm(x)
    q, k, v = a W_q [T, H_l, hd], a W_k, a W_v [T, kv, hd]
    s = attention(rope_l(q), rope_l(k), v [, window])
    g = sigmoid(a W_g)  [T, H_l]       one gate a head, from the normed input
    x <- x + concat_h(g_h * s_h) W_o
    b = rms_norm(x)
    x <- x + ffn_l(b)

"full_attention": causal, `rope_l` the group `rope_parameters[
"full_attention"]` (Laguna-XS.2: YaRN over the trailing half of a head).
"sliding_attention": causal under `sliding_window` keys, the query's own
among them, `rope_l` the group `rope_parameters["sliding_attention"]`
(the default rotary over the whole head). `ffn_l` by `mlp_layer_types[l]`:
"dense" layers.gated_mlp, "sparse" layers.moe_block with a sigmoid router
whose chosen scores are normalised and scaled, gated SiLU experts and a
gated shared expert. After the last layer a final rms_norm and an untied
head; the loss is the mean next-token cross-entropy. No bias in any map,
no QK-norm, embedding unscaled.
"""

from __future__ import annotations

from .. import layers
from ..framework.framework import name_scope
from ..initializer import NormalInitializer
from ..layers.nn import _linear
from ..param_attr import ParamAttr
from .common import mark_routing_stats, side_fetch_marks
from .window_moe import GLOBAL_SCOPE, WINDOW_SCOPE

LOSS_METRIC = "loss"
FULL, SLIDING = "full_attention", "sliding_attention"


def gated_window_moe_lm(tokens, labels, vocab_size, hidden_size,
                        num_hidden_layers, layer_types, mlp_layer_types,
                        num_attention_heads_per_layer, num_key_value_heads,
                        head_dim, rope_parameters, sliding_window,
                        intermediate_size, num_experts, num_experts_per_tok,
                        moe_intermediate_size, shared_width,
                        experts_held=None, expert_offset=0,
                        routed_scaling_factor=1.0, norm_topk_prob=True,
                        router_scoring="sigmoid", gating="per-head",
                        epsilon=1e-6, residual_layers=None, use_flash="auto",
                        recompute=False):
    """tokens / labels [B, T] int (labels the ids one position on) ->
    (the mean next-token cross-entropy, the checkpoints).

    `layer_types`, `mlp_layer_types`, `num_attention_heads_per_layer`: an
    entry a layer, read for the first `num_hidden_layers` (a cut model
    passes the published lists whole). `rope_parameters`: {kind: a group
    with `rope_theta`, `partial_rotary_factor` and what
    layers.rotary_embedding's `scaling` reads}. `gating`: "per-head", one
    gate a head; "per-element", one an element of a head (W_g [D, H_l *
    hd]); False, none. `router_scoring`: layers.moe_block's `scoring`.
    `experts_held` / `expert_offset`: the share of the `num_experts` this
    program holds in every expert layer (default all); the router keeps
    its full width and the shared expert is whole.
    `residual_layers`: the depth the output maps' initial scale
    0.02 / sqrt(depth) is reckoned from (default num_hidden_layers; a cut
    model passes the published depth). `recompute`: the checkpoints are
    the residual stream at every layer's input, for
    `minimize(loss, checkpoints=)` to keep and to replay each layer's
    forward ops ahead of its gradient ops; else there are none."""
    seqlen = int(tokens.shape[-1])
    out_scale = 0.02 / (residual_layers or num_hidden_layers) ** 0.5
    kv_heads = num_key_value_heads
    assert gating in (False, "per-head", "per-element"), gating
    assert min(map(len, (layer_types, mlp_layer_types,
                         num_attention_heads_per_layer))) >= num_hidden_layers
    stats, kept = [], []

    def by_head(x, n):
        return layers.reshape(x, [-1, seqlen, n, head_dim])

    x = layers.embedding(
        tokens, size=[vocab_size, hidden_size],
        param_attr=ParamAttr(initializer=NormalInitializer(scale=0.02)))
    for kind, ffn, heads in list(zip(
            layer_types, mlp_layer_types,
            num_attention_heads_per_layer))[:num_hidden_layers]:
        if kind not in (FULL, SLIDING) or ffn not in ("dense", "sparse"):
            raise ValueError(f"layer of kind {kind!r} with a {ffn!r} "
                             f"feed-forward")
        if recompute:
            kept.append(x)
        rope = rope_parameters[kind]
        a = layers.rms_norm(x, epsilon=epsilon)
        q, k, v = (by_head(_linear(a, n * head_dim), n)
                   for n in (heads, kv_heads, kv_heads))
        # the rotations, the attention op and the gate are the layer a
        # trace books under its kind's scope; the four maps stay the model's
        with name_scope(WINDOW_SCOPE if kind == SLIDING else GLOBAL_SCOPE):
            q, k = (layers.rotary_embedding(
                t, theta=rope["rope_theta"], scaling=rope,
                rotary_dims=int(head_dim * rope.get("partial_rotary_factor",
                                                    1))) for t in (q, k))
            attn = layers.fused_attention(
                q, k, v, causal=True, use_flash=use_flash,
                window=sliding_window if kind == SLIDING else 0)
            if gating == "per-head":
                # [B, T, H] against [B, T, H, hd]: one gate a head
                attn = layers.elementwise_mul(
                    attn, _linear(a, heads, act="sigmoid"), axis=0)
            elif gating:
                attn = layers.elementwise_mul(attn, by_head(
                    _linear(a, heads * head_dim, act="sigmoid"), heads))
        x = layers.elementwise_add(x, _linear(
            layers.reshape(attn, [-1, seqlen, heads * head_dim]),
            hidden_size, scale=out_scale))
        b = layers.rms_norm(x, epsilon=epsilon)
        if ffn == "dense":
            h = layers.gated_mlp(b, intermediate_size, out_scale=out_scale)
        else:
            h = layers.moe_block(
                b, num_experts, num_experts_per_tok, moe_intermediate_size,
                shared_width=shared_width, experts_held=experts_held,
                expert_offset=expert_offset, scaling=routed_scaling_factor,
                norm_topk_prob=norm_topk_prob, out_scale=out_scale,
                stats=stats, gated=True, scoring=router_scoring)
        x = layers.elementwise_add(x, h)

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
    return loss, kept
