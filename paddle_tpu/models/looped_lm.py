"""Looped decoder on the layers DSL: ONE stack of layers run several times
over ONE set of weights, an exit at the end of every pass, and the loss
the exits' gate mixes (the `ouro` architecture: ByteDance Ouro-2.6B;
https://huggingface.co/ByteDance/Ouro-2.6B; the family's report is
arXiv:2510.25741, "Scaling Latent Reasoning via Looped Language Models").
With x the residual stream and R the rotation over a whole head:

    layer l:   a = rms_norm_{l,1}(x)
               q, k, v = by head (a W_q, a W_k, a W_v)
               o = softmax(R q (R k)^T / sqrt(head_dim), causal) v
               x <- x + rms_norm_{l,2}(o W_o)
               b = rms_norm_{l,3}(x)
               x <- x + rms_norm_{l,4}((silu(b W_g) * (b W_u)) W_d)

    pass t = 1..T, the SAME layers and the SAME final norm every pass:
               x^(0) = embedding(tokens)
               h^(t) = rms_norm_f(layers(x^(t-1)));   x^(t) = h^(t)
    exit t:    CE^(t) = next-token cross-entropy of h^(t) W_head, a token
               z_t = h^(t) w_gate + b_gate;   lambda_t = sigmoid(z_t)
               p_t = lambda_t prod_{j<t}(1 - lambda_j)  for t < T
               p_T = prod_{j<T}(1 - lambda_j)
    loss:      mean over tokens of  sum_t p_t CE^(t) - beta H(p),
               H(p) = -sum_t p_t log p_t

Four norms a layer (one ahead of and one behind each sub-layer), the final
norm inside the loop and a gate of one output are the family's published
implementation; the loss is its report's first-stage objective. No bias in
any map but the gate's, embedding unscaled, head untied.

The layers are built once as parameters and T times as ops: every
parameter has an explicit name (`looped_lm.layer_<l>.<part>`), and
LayerHelper.create_parameter hands the one the program holds to its
second reader; append_backward sums a weight's gradient over its readers.
The gate's logits, the log-survival sums, p, H(p) and the mix are float32
whatever the activations' dtype: log lambda_t = -softplus(-z_t) and
log(1 - lambda_t) = -softplus(z_t), so p_t = exp of a sum, with no log of
a product. The last exit's gate is read at inference alone
(`early_exit_threshold`): the training program holds its parameters and
builds no op over them there, and with T = 1 the loss is the plain
cross-entropy of a sandwich-norm decoder.
"""

from __future__ import annotations

from .. import layers
from ..framework.framework import name_scope
from ..initializer import ConstantInitializer, NormalInitializer
from ..layer_helper import LayerHelper
from ..layers.nn import _linear
from ..param_attr import ParamAttr
from .common import side_fetch_marks

LOSS_METRIC = "loss"
# a vector over the exits, a sample a step: each exit's mean cross-entropy
# and the mean mass the gate gives it (the masses sum to 1)
EXIT_LOSS_METRIC, EXIT_MASS_METRIC = "loop_exit_loss", "loop_exit_mass"
# the name scopes a trace books by: the rotations and the attention op of
# every layer application; an exit's head, cross-entropy, gate and mix
ATTENTION_SCOPE, EXIT_SCOPE = "loop_attention", "loop_exit"
PREFIX = "looped_lm"


def _norm(x, name, epsilon):
    return layers.rms_norm(x, epsilon=epsilon,
                           param_attr=ParamAttr(name=f"{PREFIX}.{name}"))


def _gate_parameters(hidden_size, dtype):
    """(w [D], b [1]) of the exits' gate: made before any exit, so that
    the program holds them whatever the number of passes."""
    helper = LayerHelper("early_exit_gate")
    w = helper.create_parameter(
        attr=ParamAttr(name=f"{PREFIX}.gate.w",
                       initializer=NormalInitializer(scale=0.02)),
        shape=[hidden_size], dtype=dtype)
    b = helper.create_parameter(
        attr=ParamAttr(name=f"{PREFIX}.gate.b",
                       initializer=ConstantInitializer(0.0)),
        shape=[1], dtype=dtype, is_bias=True)
    return w, b


def looped_lm(tokens, labels, vocab_size, hidden_size, num_hidden_layers,
              num_attention_heads, num_key_value_heads, head_dim,
              intermediate_size, total_ut_steps=4, exit_entropy_weight=0.1,
              rope_theta=10000.0, epsilon=1e-6, residual_layers=None,
              use_flash="auto", recompute=False):
    """tokens / labels [B, T] int (labels the ids one position on) ->
    (the loss above, the checkpoints).

    `total_ut_steps`: the passes over the `num_hidden_layers` layers;
    the program holds one set of weights whatever it is.
    `exit_entropy_weight`: beta. `residual_layers`: the layer
    applications the output maps' initial scale 0.02 / sqrt(applications)
    is reckoned from (default total_ut_steps x num_hidden_layers; a cut
    model passes the published depth's). `recompute`: the checkpoints are
    the residual stream at the input of each of the total_ut_steps x
    num_hidden_layers layer applications, for `minimize(loss,
    checkpoints=)` to keep and to replay each application's forward ops
    ahead of its gradient ops; else there are none. An exit's ops are
    emitted where its pass ends, behind the final norm that writes the
    next application's checkpoint: exit t < T is replayed with the first
    layer application of pass t + 1, and no exit's logits live across
    the backward."""
    seqlen = int(tokens.shape[-1])
    passes, heads, kv_heads = total_ut_steps, num_attention_heads, \
        num_key_value_heads
    out_scale = 0.02 / (residual_layers or passes * num_hidden_layers) ** 0.5
    kept = []

    def by_head(x, n):
        return layers.reshape(x, [-1, seqlen, n, head_dim])

    def layer(x, l):
        part = f"{PREFIX}.layer_{l}"
        a = _norm(x, f"layer_{l}.norm_1", epsilon)
        q, k, v = (by_head(_linear(a, n * head_dim, name=f"{part}.{w}"), n)
                   for w, n in (("q", heads), ("k", kv_heads),
                                ("v", kv_heads)))
        with name_scope(ATTENTION_SCOPE):
            q, k = (layers.rotary_embedding(t, theta=rope_theta)
                    for t in (q, k))
            attn = layers.fused_attention(q, k, v, causal=True,
                                          use_flash=use_flash)
        mixed = _linear(layers.reshape(attn, [-1, seqlen, heads * head_dim]),
                        hidden_size, scale=out_scale, name=f"{part}.o")
        x = layers.elementwise_add(
            x, _norm(mixed, f"layer_{l}.norm_2", epsilon))
        b = _norm(x, f"layer_{l}.norm_3", epsilon)
        fed = layers.gated_mlp(b, intermediate_size, out_scale=out_scale,
                               name=f"{part}.mlp")
        return layers.elementwise_add(
            x, _norm(fed, f"layer_{l}.norm_4", epsilon))

    def token_loss(h):
        logits = _linear(h, vocab_size, name=f"{PREFIX}.head")
        return layers.softmax_with_cross_entropy(
            logits=layers.reshape(logits, [-1, vocab_size]),
            label=layers.reshape(labels, [-1, 1]))          # [N, 1] float32

    def gate_logit(h):
        """z [N, 1] in float32: the product is a float32 multiply and
        sum over the width (a `mul` would take bf16 operands under AMP
        and round its result to bf16)."""
        wide = layers.elementwise_mul(layers.cast(h, "float32"), gate_w)
        z = layers.elementwise_add(layers.reduce_sum(wide, dim=-1), gate_b)
        return layers.reshape(z, [-1, 1])

    x = layers.embedding(
        tokens, size=[vocab_size, hidden_size],
        param_attr=ParamAttr(name=f"{PREFIX}.embedding",
                             initializer=NormalInitializer(scale=0.02)))
    gate_w, gate_b = _gate_parameters(hidden_size, x.dtype)
    # log prod_{j<t}(1 - lambda_j), None for the empty product; the terms
    # p_t (CE^(t) + beta log p_t) of the mix; each exit's mean CE and mass
    survived, terms, exit_losses, exit_masses = None, [], [], []
    for t in range(1, passes + 1):
        for l in range(num_hidden_layers):
            if recompute:
                kept.append(x)
            x = layer(x, l)
        x = _norm(x, "final_norm", epsilon)
        with name_scope(EXIT_SCOPE):
            ce = token_loss(x)
            if passes == 1:
                loss = layers.mean(ce)
                break
            if t < passes:
                z = gate_logit(x)
                # log lambda_t and log(1 - lambda_t)
                leaves = layers.scale(layers.softplus(
                    layers.scale(z, scale=-1.0)), scale=-1.0)
                stays = layers.scale(layers.softplus(z), scale=-1.0)
                log_p = leaves if survived is None \
                    else layers.elementwise_add(survived, leaves)
                survived = stays if survived is None \
                    else layers.elementwise_add(survived, stays)
            else:       # the last exit takes the mass that is left
                log_p = survived
            p = layers.exp(log_p)
            terms.append(layers.elementwise_mul(p, layers.elementwise_add(
                layers.scale(log_p, scale=float(exit_entropy_weight)), ce)))
            exit_losses.append(layers.mean(ce))
            exit_masses.append(layers.mean(p))
    program = x.block.program
    if passes > 1:
        with name_scope(EXIT_SCOPE):
            loss = layers.mean(layers.sums(terms))
        marks = side_fetch_marks(program)
        marks[EXIT_LOSS_METRIC] = layers.concat(exit_losses, axis=0).name
        marks[EXIT_MASS_METRIC] = layers.concat(exit_masses, axis=0).name
    # a copy of the loss: the executor side-fetches no variable the user
    # fetches, and every training loop fetches the loss itself
    side_fetch_marks(program)[LOSS_METRIC] = layers.scale(
        loss, scale=1.0).name
    return loss, kept
