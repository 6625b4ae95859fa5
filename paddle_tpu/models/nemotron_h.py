"""Hybrid Mamba-2 / mixture-of-experts / attention language model on the
layers DSL: the `nemotron_h` architecture (NVIDIA Nemotron-H and Nemotron 3
Nano; https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16).

Every layer is x <- x + mixer(rms_norm(x)), one mixer a layer, its kind
read from the pattern string: `M` a Mamba-2 mixer (layers.mamba2_mixer),
`E` a mixture of experts with a shared expert (layers.moe_block), `*`
causal grouped-query attention with no position embedding
(layers.fused_attention). After the last layer a final rms_norm and an untied head. No bias in any
linear map, embedding unscaled.
"""

from __future__ import annotations

from .. import layers
from ..layers.nn import _linear
from ..initializer import NormalInitializer
from ..param_attr import ParamAttr

# telemetry side-fetches of a model with expert layers: one series per
# expert layer (label `layer` = its index among them), a sample a step
SIDE_METRICS = ("moe_rows_routed", "moe_rows_combined",
                "moe_load_max_over_mean")


def nemotron_h_lm(tokens, labels, vocab_size, pattern, hidden_size,
                  mamba_num_heads, mamba_head_dim, n_groups, ssm_state_size,
                  num_attention_heads, num_key_value_heads, head_dim,
                  n_routed_experts, num_experts_per_tok, moe_intermediate_size,
                  moe_shared_expert_intermediate_size=0, experts_held=None,
                  expert_offset=0, routed_scaling_factor=1.0,
                  norm_topk_prob=True, conv_kernel=4,
                  chunk_size=128, epsilon=1e-5, residual_layers=None,
                  use_flash="auto"):
    """tokens/labels [B, T] int -> mean next-token cross-entropy loss.

    `pattern`: one character a layer (`M`, `E`, `*`). `experts_held`
    / `expert_offset`: the share of the `n_routed_experts` this program
    holds in every expert layer (default all); the router keeps its full
    width. `residual_layers`: the depth the output maps' initial scale
    0.02 / sqrt(depth) is reckoned from (default len(pattern); a cut
    model passes the published depth)."""
    seqlen = int(tokens.shape[-1])
    depth = residual_layers or len(pattern)
    out_scale = 0.02 / depth ** 0.5

    x = layers.embedding(tokens, size=[vocab_size, hidden_size],
                         param_attr=ParamAttr(
                             initializer=NormalInitializer(scale=0.02)))
    stats = []
    for kind in pattern:
        h = layers.rms_norm(x, epsilon=epsilon)
        if kind == "M":
            h = layers.mamba2_mixer(
                h, mamba_num_heads, mamba_head_dim, n_groups, ssm_state_size,
                conv_kernel=conv_kernel, chunk_size=chunk_size,
                epsilon=epsilon, out_scale=out_scale)
        elif kind == "E":
            h = layers.moe_block(
                h, n_routed_experts, num_experts_per_tok,
                moe_intermediate_size,
                shared_width=moe_shared_expert_intermediate_size,
                experts_held=experts_held, expert_offset=expert_offset,
                scaling=routed_scaling_factor, norm_topk_prob=norm_topk_prob,
                out_scale=out_scale, stats=stats)
        elif kind == "*":
            q = _linear(h, num_attention_heads * head_dim)
            k = _linear(h, num_key_value_heads * head_dim)
            v = _linear(h, num_key_value_heads * head_dim)
            attn = layers.fused_attention(
                layers.reshape(q, [-1, seqlen, num_attention_heads, head_dim]),
                layers.reshape(k, [-1, seqlen, num_key_value_heads, head_dim]),
                layers.reshape(v, [-1, seqlen, num_key_value_heads, head_dim]),
                causal=True, use_flash=use_flash)
            h = _linear(
                layers.reshape(attn, [-1, seqlen,
                                      num_attention_heads * head_dim]),
                hidden_size, scale=out_scale)
        else:
            raise ValueError(f"layer kind {kind!r} in pattern {pattern!r}")
        x = layers.elementwise_add(x, h)

    x = layers.rms_norm(x, epsilon=epsilon)
    logits = _linear(x, vocab_size)
    loss = layers.mean(layers.softmax_with_cross_entropy(
        logits=layers.reshape(logits, [-1, vocab_size]),
        label=layers.reshape(labels, [-1, 1])))
    if stats:
        program = loss.block.program
        marks = getattr(program, "_telemetry_fetch_extra", None)
        if marks is None:
            marks = program._telemetry_fetch_extra = {}
        for metric, by_layer in zip(SIDE_METRICS, zip(*stats)):
            marks[metric] = layers.concat(list(by_layer), axis=0).name
    return loss
