"""Hybrid linear-attention / latent-attention mixture-of-experts decoder
on the layers DSL: the `kimi_linear` architecture (moonshotai
Kimi-Linear-48B-A3B-Instruct;
https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct; the
family's report is arXiv:2510.26692). Layer l, counted from 1 as the
published lists count:

    a = rms_norm(x);  x <- x + mixer_l(a);  b = rms_norm(x);  x <- x + ffn_l(b)

`mixer_l` is Kimi Delta Attention (layers.kda_mixer) where l is in
`kda_layers` and latent attention with one direct query map and no
positions (layers.latent_attention with `q_lora_rank` None and `rotate`
False) where l is in `full_attn_layers`: three to one as published.
`ffn_l` is layers.gated_mlp in the first `first_k_dense_replace` layers
and layers.moe_block in every `moe_layer_freq`-th layer behind them: a
sigmoid router whose chosen scores are renormalised and scaled, gated
SiLU experts and a gated shared expert. After the last layer a final
rms_norm and an untied head; the loss is the mean next-token
cross-entropy. No bias in any map, embedding unscaled.
"""

from __future__ import annotations

from .. import layers
from ..initializer import NormalInitializer
from ..layers.nn import _linear
from ..param_attr import ParamAttr
from .common import mark_routing_stats, side_fetch_marks

LOSS_METRIC = "loss"
KDA, FULL = "kda", "full_attention"


def mixer_kinds(num_hidden_layers, kda_layers, full_attn_layers):
    """[KDA or FULL] for layers 1 to `num_hidden_layers` from the two
    published 1-based lists; a layer in neither or in both is an error."""
    kinds = []
    for l in range(1, num_hidden_layers + 1):
        if (l in kda_layers) == (l in full_attn_layers):
            raise ValueError(f"layer {l} is in both or in neither of "
                             f"kda_layers and full_attn_layers")
        kinds.append(KDA if l in kda_layers else FULL)
    return kinds


def kda_moe_lm(tokens, labels, vocab_size, hidden_size, num_hidden_layers,
               kda_layers, full_attn_layers, kda_num_heads, kda_head_dim,
               num_attention_heads, kv_lora_rank, qk_nope_head_dim,
               qk_rope_head_dim, v_head_dim, intermediate_size, num_experts,
               num_experts_per_token, moe_intermediate_size,
               q_lora_rank=None, mla_use_nope=True, rope_theta=10000.0,
               short_conv_kernel_size=4, kda_gate_rank=None,
               kda_chunk_size=64, l2_norm_epsilon=1e-6,
               first_k_dense_replace=1, moe_layer_freq=1,
               num_shared_experts=1, experts_held=None, expert_offset=0,
               routed_scaling_factor=1.0, moe_renormalize=True,
               router_scoring="sigmoid", epsilon=1e-5, residual_layers=None,
               use_flash="auto", recompute=False):
    """tokens / labels [B, T] int (labels the ids one position on) ->
    (the mean next-token cross-entropy, the checkpoints).

    `kda_layers` / `full_attn_layers`: the published 1-based lists, read
    for the first `num_hidden_layers` layers (a cut model passes them
    whole). `kda_gate_rank`: the rank of the mixer's two low-rank maps
    (default the head's width); `kda_chunk_size`: the delta rule's chunk
    (its result does not depend on it). `experts_held` /
    `expert_offset`: the share of the `num_experts` this program holds in
    every expert layer (default all); the router keeps its full width and
    the shared expert is whole. `residual_layers`: the depth the output
    maps' initial scale 0.02 / sqrt(depth) is reckoned from (default
    num_hidden_layers; a cut model passes the published depth).
    `recompute`: the checkpoints are the residual stream at every layer's
    input, for `minimize(loss, checkpoints=)` to keep and to replay each
    layer's forward ops ahead of its gradient ops; else there are none."""
    out_scale = 0.02 / (residual_layers or num_hidden_layers) ** 0.5
    stats, kept = [], []
    x = layers.embedding(
        tokens, size=[vocab_size, hidden_size],
        param_attr=ParamAttr(initializer=NormalInitializer(scale=0.02)))
    kinds = mixer_kinds(num_hidden_layers, kda_layers, full_attn_layers)
    for index, kind in enumerate(kinds):
        if recompute:
            kept.append(x)
        a = layers.rms_norm(x, epsilon=epsilon)
        if kind == KDA:
            mixed = layers.kda_mixer(
                a, kda_num_heads, kda_head_dim,
                conv_kernel=short_conv_kernel_size, gate_rank=kda_gate_rank,
                chunk_size=kda_chunk_size, epsilon=epsilon,
                l2_epsilon=l2_norm_epsilon, out_scale=out_scale)
        else:
            mixed = layers.latent_attention(
                a, num_attention_heads, q_lora_rank, kv_lora_rank,
                qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                rope_theta=rope_theta, epsilon=epsilon, out_scale=out_scale,
                use_flash=use_flash, rotate=not mla_use_nope)
        x = layers.elementwise_add(x, mixed)
        b = layers.rms_norm(x, epsilon=epsilon)
        sparse = index >= first_k_dense_replace \
            and (index - first_k_dense_replace) % moe_layer_freq == 0
        if sparse:
            h = layers.moe_block(
                b, num_experts, num_experts_per_token, moe_intermediate_size,
                shared_width=num_shared_experts * moe_intermediate_size,
                experts_held=experts_held, expert_offset=expert_offset,
                scaling=routed_scaling_factor,
                norm_topk_prob=moe_renormalize, out_scale=out_scale,
                stats=stats, gated=True, scoring=router_scoring)
        else:
            h = layers.gated_mlp(b, intermediate_size, out_scale=out_scale)
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
