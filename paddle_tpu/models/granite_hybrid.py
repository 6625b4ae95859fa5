"""Dense hybrid Mamba-2 / attention language model with a feed-forward in
every layer, on the layers DSL: the `granitemoehybrid` architecture with no
routed expert (IBM Granite 4.0-H Micro;
https://huggingface.co/ibm-granite/granite-4.0-h-micro). With the four
muP multipliers of the family:

    h   = embedding_multiplier * E[tok]
    h  <- h + residual_multiplier * mixer_l(rms_norm(h))
    h  <- h + residual_multiplier * (silu(u W_g) * (u W_u)) W_d,  u = rms_norm(h)
    logits = rms_norm(h) E^T / logits_scaling        (the head is E itself)

`mixer_l` by `layer_types[l]`: "mamba" a Mamba-2 mixer
(layers.mamba2_mixer), "attention" causal grouped-query attention with no
position embedding and softmax(attention_multiplier * q k^T). No bias in
any linear map; the loss is the mean next-token cross-entropy.

Departures from the published modelling code, none of which changes a
value: the feed-forward's published `input_linear` [D, 2 F] is its two
halves W_g and W_u (layers.gated_mlp); attention_multiplier reaches the
attention op, which divides the scores by sqrt(head_dim), as q scaled by
attention_multiplier * sqrt(head_dim) ahead of it (0.125 for the
published 1/64 over heads of 64: exact in bf16, and the op's lowering and
kernels stay every other caller's); logits_scaling divides the normed
hidden state ahead of the head's product, not the [T, V] logits behind it.
"""

from __future__ import annotations

from .. import layers
from ..initializer import NormalInitializer
from ..layers.nn import _linear
from ..param_attr import ParamAttr
from .common import side_fetch_marks

EMBEDDING = "granite_hybrid_lm.embedding"
LOSS_METRIC = "loss"


def granite_hybrid_lm(tokens, labels, vocab_size, hidden_size, layer_types,
                      mamba_n_heads, mamba_d_head, mamba_n_groups,
                      mamba_d_state, num_attention_heads, num_key_value_heads,
                      shared_intermediate_size, embedding_multiplier=1.0,
                      residual_multiplier=1.0, attention_multiplier=None,
                      logits_scaling=1.0, mamba_d_conv=4, mamba_chunk_size=256,
                      epsilon=1e-5, initializer_range=0.02, use_flash="auto",
                      recompute=False):
    """tokens / labels [B, T] int (labels the ids one position on) ->
    (the mean next-token cross-entropy, the checkpoints).

    `layer_types`: "mamba" or "attention", one a layer. `head_dim` is
    hidden_size / num_attention_heads; `attention_multiplier` None is
    1 / sqrt(head_dim). Every linear map and the embedding start at
    N(0, initializer_range): the multipliers stand where other models
    rescale output maps by depth. `mamba_chunk_size`: the scan's chunk
    (its result does not depend on it). `recompute`: the checkpoints are
    the residual stream at every layer's input, for
    `minimize(loss, checkpoints=)` to keep and to replay each layer's
    forward ops ahead of its gradient ops; else there are none."""
    seqlen = int(tokens.shape[-1])
    heads, kv_heads = num_attention_heads, num_key_value_heads
    head_dim = hidden_size // heads
    q_scale = (head_dim ** -0.5 if attention_multiplier is None
               else attention_multiplier) * head_dim ** 0.5
    std = initializer_range

    def by_head(x, n):
        return layers.reshape(x, [-1, seqlen, n, head_dim])

    def branch(x, out):
        return layers.elementwise_add(
            x, layers.scale(out, scale=residual_multiplier))

    x = layers.scale(
        layers.embedding(tokens, size=[vocab_size, hidden_size],
                         param_attr=ParamAttr(
                             name=EMBEDDING,
                             initializer=NormalInitializer(scale=std))),
        scale=embedding_multiplier)
    table = x.block.program.global_block().var(EMBEDDING)
    kept = []
    for kind in layer_types:
        if recompute:
            kept.append(x)
        u = layers.rms_norm(x, epsilon=epsilon)
        if kind == "mamba":
            mixed = layers.mamba2_mixer(
                u, mamba_n_heads, mamba_d_head, mamba_n_groups,
                mamba_d_state, conv_kernel=mamba_d_conv,
                chunk_size=mamba_chunk_size, epsilon=epsilon, out_scale=std)
        elif kind == "attention":
            q = layers.scale(_linear(u, heads * head_dim, scale=std),
                             scale=q_scale)
            k, v = (_linear(u, kv_heads * head_dim, scale=std)
                    for _ in range(2))
            attn = layers.fused_attention(
                by_head(q, heads), by_head(k, kv_heads), by_head(v, kv_heads),
                causal=True, use_flash=use_flash)
            mixed = _linear(
                layers.reshape(attn, [-1, seqlen, heads * head_dim]),
                hidden_size, scale=std)
        else:
            raise ValueError(f"layer type {kind!r} in {layer_types!r}")
        x = branch(x, mixed)
        x = branch(x, layers.gated_mlp(layers.rms_norm(x, epsilon=epsilon),
                                       shared_intermediate_size,
                                       out_scale=std))

    x = layers.scale(layers.rms_norm(x, epsilon=epsilon),
                     scale=1.0 / logits_scaling)
    logits = layers.matmul(x, table, transpose_y=True)
    loss = layers.mean(layers.softmax_with_cross_entropy(
        logits=layers.reshape(logits, [-1, vocab_size]),
        label=layers.reshape(labels, [-1, 1])))
    # a copy of the loss: the executor side-fetches no variable the user
    # fetches, and every training loop fetches the loss itself
    side_fetch_marks(loss.block.program)[LOSS_METRIC] = layers.scale(
        loss, scale=1.0).name
    return loss, kept
