"""Latent-attention mixture-of-experts decoder with a multi-token-
prediction module on the layers DSL: the `glm4_moe_lite` architecture
(GLM-4.7-Flash; https://huggingface.co/zai-org/GLM-4.7-Flash), which is
DeepSeek-V3's block (arXiv:2412.19437) at smaller sizes.

Every block is x <- x + MLA(rms_norm(x)); x <- x + FFN(rms_norm(x)):
layers.latent_attention, and as FFN layers.gated_mlp in the first
`first_k_dense_replace` blocks and layers.moe_block(gated=True) (sigmoid
top-k router, gated experts, a gated shared expert) in the others. After
the last block a final rms_norm and an untied head give the next-token
loss; `num_nextn_predict_layers` multi-token-prediction modules
(layers.mtp_block: one more block of the expert kind each, reading the
main model's embedding table and head) give the loss of the token after
it. No bias in any linear map, embedding unscaled.

With `hc_mult` = n the residual path is n streams a token mixed by
manifold-constrained hyper-connections (mHC, arXiv:2512.24880, on
Hyper-Connections, arXiv:2409.19606; the `xing4_0` architecture,
Xing4.0-29B-A4B, https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B).
A token's state is X in R^{n x C}, X_0 the token's embedding in each of
the n rows. Every sublayer F (latent attention; the gated feed-forward
or the expert layer), each behind its own rms_norm as above, has its own
phi [n C, n + n + n^2], b [n + n + n^2] and alpha [3]:

    u        = vec(X) / sqrt(mean(vec(X)^2) + epsilon)     float32, no weight
    [p|q|r]  = u phi                                       widths n, n, n^2
    H_pre    = sigmoid(alpha_pre p + b_pre)                [n]
    H_post   = 2 sigmoid(alpha_post q + b_post)            [n]
    M_0      = exp(clamp(alpha_res mat(r) + b_res, -30, 30))   [n, n]
    M_t      = rows(cols(M_{t-1})), t = 1..hc_sinkhorn_iters
               cols: M / (column sums + hc_eps); rows alike
    H_res    = M_20
    x_in     = sum_j H_pre[j] X[j]                         [C]
    y        = F(rms_norm(x_in))                           [C]
    X'[i]    = sum_j H_res[i, j] X[j] + H_post[i] y        [n, C]

and behind the last block x_out = sum_j X[j], then the final norm and the
head (layers.hyper_connection_maps, hc_pre_mix, hc_post_res_mix; the maps,
the sweeps and both mixes' sums float32 under AMP, the streams in the
activations' dtype). No prediction module is built with it: no source at
hand says how the module reads n streams.
"""

from __future__ import annotations

from .. import layers
from ..initializer import NormalInitializer
from ..layers.nn import _linear
from ..param_attr import ParamAttr
from .common import mark_routing_stats, side_fetch_marks

# the two parameters the prediction modules read beside the main model
EMBEDDING, HEAD = "mla_moe_lm.embedding", "mla_moe_lm.head"
# telemetry side-fetches of the two losses, a gauge each
LOSS_METRICS = ("loss_main", "loss_mtp")
# and of the hyper-connections' residual maps, a float32 scalar each a
# step: the largest |row sum - 1| or |column sum - 1| of any H_res, and
# the mean of trace(H_res) / n (how far the streams are from mixing evenly)
HC_METRICS = ("hc_res_sum_error", "hc_res_diagonal_mass")


def _mean_nll(logits, labels, vocab_size):
    return layers.mean(layers.softmax_with_cross_entropy(
        logits=layers.reshape(logits, [-1, vocab_size]),
        label=layers.reshape(labels, [-1, 1])))


def mla_moe_lm(tokens, labels, labels_next, vocab_size, hidden_size,
               num_hidden_layers, first_k_dense_replace, num_attention_heads,
               q_lora_rank, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
               v_head_dim, intermediate_size, n_routed_experts,
               num_experts_per_tok, moe_intermediate_size, n_shared_experts=1,
               experts_held=None, expert_offset=0, routed_scaling_factor=1.0,
               norm_topk_prob=True, rope_theta=10000.0,
               num_nextn_predict_layers=1, mtp_loss_weight=0.3, epsilon=1e-5,
               residual_layers=None, use_flash="auto", hc_mult=None,
               hc_sinkhorn_iters=20, hc_eps=1e-6, hc_res_clamp=(-30.0, 30.0),
               rope_scaling=None, recompute=False):
    """tokens / labels / labels_next [B, T] int (labels the ids one
    position on, labels_next two on; unread without a prediction module)
    -> L_main + mtp_loss_weight * the mean of the prediction modules'
    losses, L_main the mean next-token cross-entropy.

    `experts_held` / `expert_offset`: the share of the `n_routed_experts`
    this program holds in every expert layer (default all); the router
    keeps its full width. `residual_layers`: the depth the output maps'
    initial scale 0.02 / sqrt(depth) is reckoned from (default
    num_hidden_layers; a cut model passes the published depth). One
    prediction module is what the published models have and what this
    builder takes (a second would read the first one's hidden state and
    the ids three positions on).

    `hc_mult`: the residual streams a token (None: one, the plain
    residual path, and the program is the one it always was);
    `hc_sinkhorn_iters`, `hc_eps`, `hc_res_clamp`: the sweeps of the
    residual map, the term added to every sum a sweep divides by, and the
    bounds of its logits (module docstring). `rope_scaling`: the
    published group (YaRN in the DeepSeek keys), handed to
    layers.latent_attention. `recompute`: the result is (loss,
    checkpoints), the checkpoints the residual state at every block's
    input, for `minimize(loss, checkpoints=)` to keep and to replay each
    block's forward ops ahead of its gradient ops."""
    assert num_nextn_predict_layers in (0, 1), num_nextn_predict_layers
    assert not (hc_mult and num_nextn_predict_layers), \
        "no prediction module is written for hyper-connections"
    out_scale = 0.02 / (residual_layers or num_hidden_layers) ** 0.5
    stats, hc_stats, kept = [], [], []

    def sublayer(x, f):
        """x <- x + f(rms_norm(x)), or its form over `hc_mult` streams."""
        if not hc_mult:
            return layers.elementwise_add(
                x, f(layers.rms_norm(x, epsilon=epsilon)))
        pre, post, res = layers.hyper_connection_maps(
            x, hc_sinkhorn_iters, hc_eps, hc_res_clamp, epsilon=epsilon,
            stats=hc_stats)
        y = f(layers.rms_norm(layers.hc_pre_mix(x, pre), epsilon=epsilon))
        return layers.hc_post_res_mix(x, y, res, post)

    def block(x, dense=False):
        x = sublayer(x, lambda a: layers.latent_attention(
            a, num_attention_heads,
            q_lora_rank, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
            v_head_dim, rope_theta=rope_theta, epsilon=epsilon,
            out_scale=out_scale, use_flash=use_flash,
            rope_scaling=rope_scaling))
        if dense:
            return sublayer(x, lambda h: layers.gated_mlp(
                h, intermediate_size, out_scale=out_scale))
        return sublayer(x, lambda h: layers.moe_block(
            h, n_routed_experts, num_experts_per_tok,
            moe_intermediate_size,
            shared_width=n_shared_experts * moe_intermediate_size,
            experts_held=experts_held, expert_offset=expert_offset,
            scaling=routed_scaling_factor, norm_topk_prob=norm_topk_prob,
            out_scale=out_scale, stats=stats, gated=True))

    x = layers.embedding(tokens, size=[vocab_size, hidden_size],
                         param_attr=ParamAttr(
                             name=EMBEDDING,
                             initializer=NormalInitializer(scale=0.02)))
    if hc_mult:     # the embedding in each of the n rows
        x = layers.expand(
            layers.reshape(x, [-1, int(x.shape[1]), 1, hidden_size]),
            [1, 1, hc_mult, 1])
    for i in range(num_hidden_layers):
        if recompute:
            kept.append(x)
        x = block(x, dense=i < first_k_dense_replace)
    if hc_mult:     # the last state's rows summed
        x = layers.reduce_sum(x, dim=2)
    logits = _linear(layers.rms_norm(x, epsilon=epsilon), vocab_size,
                     name=HEAD)
    loss = loss_main = _mean_nll(logits, labels, vocab_size)
    if num_nextn_predict_layers:
        logits_next = layers.mtp_block(x, labels, vocab_size, EMBEDDING, HEAD,
                                       block, epsilon=epsilon)
        loss_mtp = _mean_nll(logits_next, labels_next, vocab_size)
        loss = layers.elementwise_add(
            loss_main, layers.scale(loss_mtp, scale=float(mtp_loss_weight)))
        side_fetch_marks(loss.block.program).update(
            zip(LOSS_METRICS, (loss_main.name, loss_mtp.name)))
    mark_routing_stats(loss.block.program, stats)
    if hc_stats:
        errors, masses = (layers.concat(list(by_sublayer), axis=0)
                          for by_sublayer in zip(*hc_stats))
        side_fetch_marks(loss.block.program).update(zip(
            HC_METRICS, (layers.reduce_max(errors).name,
                         layers.reduce_mean(masses).name)))
    return (loss, kept) if recompute else loss
