"""Block-diffusion mixture-of-experts decoder on the layers DSL: the
`sdar_moe` architecture (SDAR-30B-A3B-Chat;
https://huggingface.co/JetLM/SDAR-30B-A3B-Chat), a Qwen3-MoE block
trained by diffusion over blocks of tokens (arXiv:2503.09573,
arXiv:2510.06303).

Every sequence runs as two streams of L positions, its noised copy (some
ids replaced by the mask token) and its clean one, stacked along the
batch, noisy first: the embedding, the norms, the projections, the
experts and the residual adds see [2B, L, .] and tell no stream from the
other; only attention does (layers.block_diffusion_attention). Every
block is

    x <- x + W_o attention(rope(qk_norm(W_q a)), rope(qk_norm(W_k a)), W_v a),
         a = rms_norm(x)
    x <- x + moe_block(rms_norm(x))      softmax router, gated experts,
                                         no shared expert

with grouped-query heads, an rms_norm over the dims of every query and
key head before the rotation (one weight of head_dim for all heads), and
rotate-half RoPE over the whole head by the position in its own stream.
After the last block a final rms_norm and an untied head read the noisy
half alone, and the loss is the position-weighted cross-entropy against
the clean ids, summed over the noisy stream and divided by all B * L data
tokens. No bias in any linear map, embedding unscaled.
"""

from __future__ import annotations

from .. import layers
from ..framework.framework import name_scope
from ..initializer import NormalInitializer
from ..layers.nn import _linear
from ..param_attr import ParamAttr
from .common import mark_routing_stats, side_fetch_marks

# telemetry side-fetches, a gauge each: the loss, and the share of the
# step's data tokens that were masked (about a half under U(0, 1) rates)
LOSS_METRIC, MASKED_METRIC = "loss_diffusion", "masked_share"
ATTENTION_SCOPE = "block_diffusion_attention"


def block_diffusion_moe_lm(tokens, noisy, weight, vocab_size, hidden_size,
                           num_hidden_layers, num_attention_heads,
                           num_key_value_heads, head_dim, num_experts,
                           num_experts_per_tok, moe_intermediate_size,
                           block_length, experts_held=None, expert_offset=0,
                           norm_topk_prob=True, rope_theta=10000.0,
                           epsilon=1e-6, residual_layers=None,
                           use_flash="auto", embedding_std=0.02):
    """tokens / noisy [B, L] int (the clean ids and their noised copy) and
    weight [B, L] float32 (m_i / p of the position's block: 0 where the
    id was kept) -> sum_i weight_i * nll_i(tokens_i) / (B * L), the
    logits from the noisy stream.

    `experts_held` / `expert_offset`: the share of the `num_experts` this
    program holds in every layer (default all); the router keeps its full
    width, and with no shared expert a token none of whose choices is
    held here gets zero from the layer. `residual_layers`: the depth the
    output maps' initial scale 0.02 / sqrt(depth) is reckoned from
    (default num_hidden_layers; a cut model passes the published
    depth). `embedding_std`: the embedding's N(0, std); the other
    matrices' 0.02 is the default, and at it the mask token, one
    embedding for half the noisy stream, is a component common to all
    positions that every attention layer passes on and no averaging
    shrinks: from the second block on every position looks alike to the
    router (the configuration file's `assumed` has the numbers)."""
    seqlen = int(tokens.shape[-1])
    out_scale = 0.02 / (residual_layers or num_hidden_layers) ** 0.5
    heads, kv_heads = num_attention_heads, num_key_value_heads
    stats = []

    def by_head(x, n):
        return layers.reshape(x, [-1, seqlen, n, head_dim])

    x = layers.embedding(
        layers.concat([noisy, tokens], axis=0),
        size=[vocab_size, hidden_size],
        param_attr=ParamAttr(
            initializer=NormalInitializer(scale=embedding_std)))
    for _ in range(num_hidden_layers):
        a = layers.rms_norm(x, epsilon=epsilon)
        q, k, v = (by_head(_linear(a, n * head_dim), n)
                   for n in (heads, kv_heads, kv_heads))
        # QK-norm, the rotation and the attention op are the layer a
        # trace books under `block_diffusion_attention`; the four maps
        # are plain products and stay the model's
        with name_scope(ATTENTION_SCOPE):
            q, k = (layers.rotary_embedding(
                layers.rms_norm(t, epsilon=epsilon), theta=rope_theta)
                for t in (q, k))
            attn = layers.block_diffusion_attention(
                q, k, v, block_length, use_flash=use_flash)
        x = layers.elementwise_add(x, _linear(
            layers.reshape(attn, [-1, seqlen, heads * head_dim]),
            hidden_size, scale=out_scale))
        x = layers.elementwise_add(x, layers.moe_block(
            layers.rms_norm(x, epsilon=epsilon), num_experts,
            num_experts_per_tok, moe_intermediate_size,
            experts_held=experts_held, expert_offset=expert_offset,
            norm_topk_prob=norm_topk_prob, out_scale=out_scale, stats=stats,
            gated=True, scoring="softmax"))

    # the clean stream's last hidden state is read by nothing
    x_noisy, _ = layers.split(x, 2, dim=0)
    logits = _linear(layers.rms_norm(x_noisy, epsilon=epsilon), vocab_size)
    nll = layers.softmax_with_cross_entropy(
        logits=layers.reshape(logits, [-1, vocab_size]),
        label=layers.reshape(tokens, [-1, 1]))
    loss = layers.mean(layers.elementwise_mul(
        nll, layers.reshape(weight, [-1, 1])))
    kept = layers.mean(layers.cast(layers.equal(noisy, tokens), "float32"))
    masked = layers.scale(kept, scale=-1.0, bias=1.0)
    program = loss.block.program
    # a copy of the loss: the executor side-fetches no variable the user
    # fetches, and every training loop fetches the loss itself
    side_fetch_marks(program).update(
        {LOSS_METRIC: layers.scale(loss, scale=1.0).name,
         MASKED_METRIC: masked.name})
    mark_routing_stats(program, stats)
    return loss
