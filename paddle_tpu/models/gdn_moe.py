"""Hybrid Gated-DeltaNet / gated-attention mixture-of-experts decoder on
the layers DSL: the `qwen3_next` architecture (Qwen
Qwen3-Next-80B-A3B-Instruct;
https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct; the mixer is
arXiv:2412.06464's). Layer l, counted from 0 as published:

    a = norm(x);  x <- x + mixer_l(a);  b = norm(x);  x <- x + experts(b)

Every norm outside the linear mixer is x / sqrt(mean x^2 + eps) * (1 + w)
with w from zeros (layers.rms_norm's `unit_offset`). `mixer_l` is gated
softmax attention where (l + 1) % `full_attention_interval` == 0 and
Gated DeltaNet (layers.gdn_mixer: a decay a head, key heads under groups
of value heads) elsewhere, three to one as published. The attention:

    q, gate = a W_q, a W_g [T, H, hd];  k, v = a W_k, a W_v [T, kv, hd]
    q, k <- rope(norm(q)), rope(norm(k))       a norm a head, one weight
        [hd] for all heads; the FIRST `partial_rotary_factor` of a head
        rotated, the rest passed through
    y = concat_h(attention(q, k, v, causal)_h * sigmoid(gate_h)) W_o

(the published q_proj [D, H x 2 hd] holds a head's query and gate side by
side; here two maps, a permutation of its columns). Every layer's
feed-forward is layers.moe_block: a softmax router over all experts whose
top-k weights are normalised over the chosen, gated SiLU experts and a
shared expert behind a sigmoid gate a token. After the last layer a final
norm and an untied head; the loss is the mean next-token cross-entropy.
No bias in any map, embedding unscaled.
"""

from __future__ import annotations

from .. import layers
from ..framework.framework import name_scope
from ..initializer import NormalInitializer
from ..layers.nn import _linear
from ..param_attr import ParamAttr
from .common import mark_routing_stats, side_fetch_marks

LOSS_METRIC = "loss"
LINEAR, FULL = "linear_attention", "full_attention"
# the name scope of an attention layer's QK-norm, rotation, attention op
# and output gate
ATTENTION_SCOPE = "gated_attention"


def mixer_kinds(layers_held, full_attention_interval):
    """[LINEAR or FULL] for the published 0-based indices `layers_held`:
    every `full_attention_interval`-th layer is softmax attention."""
    return [FULL if (l + 1) % full_attention_interval == 0 else LINEAR
            for l in layers_held]


def gdn_moe_lm(tokens, labels, vocab_size, hidden_size, num_hidden_layers,
               full_attention_interval, linear_num_key_heads,
               linear_num_value_heads, linear_key_head_dim,
               linear_value_head_dim, num_attention_heads,
               num_key_value_heads, head_dim, num_experts,
               num_experts_per_tok, moe_intermediate_size,
               shared_expert_intermediate_size, layers_held=None,
               linear_conv_kernel_dim=4, gdn_chunk_size=64,
               l2_norm_epsilon=1e-6, partial_rotary_factor=1.0,
               rope_theta=10000.0, experts_held=None, expert_offset=0,
               norm_topk_prob=True, epsilon=1e-6, initializer_range=0.02,
               residual_layers=None, use_flash="auto", recompute=False):
    """tokens / labels [B, T] int (labels the ids one position on) ->
    (the mean next-token cross-entropy, the checkpoints).

    `layers_held`: the published 0-based indices of the layers this
    program builds, in order (default the first `num_hidden_layers`); a
    layer's mixer is its index's (mixer_kinds). `experts_held` /
    `expert_offset`: the share of the `num_experts` this program holds in
    every expert layer (default all); the router keeps its full width and
    the shared expert and its gate are whole. `residual_layers`: the
    depth the output maps' initial scale `initializer_range` /
    sqrt(depth) is reckoned from (default the layers built; a cut model
    passes the published depth). `recompute`: the checkpoints are the
    residual stream at every layer's input, for `minimize(loss,
    checkpoints=)` to keep and to replay each layer's forward ops ahead
    of its gradient ops; else there are none."""
    seqlen = int(tokens.shape[-1])
    heads, kv_heads = num_attention_heads, num_key_value_heads
    held = list(range(num_hidden_layers) if layers_held is None
                else layers_held)
    std = initializer_range
    out_scale = std / (residual_layers or len(held)) ** 0.5
    rotary_dims = int(head_dim * partial_rotary_factor)
    stats, kept = [], []

    def norm(t):
        return layers.rms_norm(t, epsilon=epsilon, unit_offset=True)

    def by_head(t, n):
        return layers.reshape(t, [-1, seqlen, n, head_dim])

    x = layers.embedding(
        tokens, size=[vocab_size, hidden_size],
        param_attr=ParamAttr(initializer=NormalInitializer(scale=std)))
    for kind in mixer_kinds(held, full_attention_interval):
        if recompute:
            kept.append(x)
        a = norm(x)
        if kind == LINEAR:
            mixed = layers.gdn_mixer(
                a, linear_num_key_heads, linear_num_value_heads,
                linear_key_head_dim, linear_value_head_dim,
                conv_kernel=linear_conv_kernel_dim, chunk_size=gdn_chunk_size,
                epsilon=epsilon, l2_epsilon=l2_norm_epsilon,
                out_scale=out_scale)
        else:
            q, k, v, gate = (by_head(_linear(a, n * head_dim, scale=std), n)
                             for n in (heads, kv_heads, kv_heads, heads))
            # QK-norm, the rotation, the attention op and the gate are the
            # layer a trace books under its scope; the five maps stay the
            # model's
            with name_scope(ATTENTION_SCOPE):
                q, k = (layers.rotary_embedding(
                    norm(t), theta=rope_theta, rotary_dims=rotary_dims,
                    rotate_first=rotary_dims < head_dim) for t in (q, k))
                attn = layers.elementwise_mul(
                    layers.fused_attention(q, k, v, causal=True,
                                           use_flash=use_flash),
                    layers.sigmoid(gate))
            mixed = _linear(
                layers.reshape(attn, [-1, seqlen, heads * head_dim]),
                hidden_size, scale=out_scale)
        x = layers.elementwise_add(x, mixed)
        x = layers.elementwise_add(x, layers.moe_block(
            norm(x), num_experts, num_experts_per_tok, moe_intermediate_size,
            shared_width=shared_expert_intermediate_size,
            experts_held=experts_held, expert_offset=expert_offset,
            norm_topk_prob=norm_topk_prob, out_scale=out_scale, stats=stats,
            gated=True, scoring="softmax", shared_gate=True))

    logits = _linear(norm(x), vocab_size, scale=std)
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
