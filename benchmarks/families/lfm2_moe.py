"""Family `lfm2_moe`: a decoder of gated short convolutions and
grouped-query attention, three to one, with a top-4 router over gated
experts and no shared expert behind leading dense layers (LiquidAI
LFM2-24B-A2B), through paddle_tpu.models.conv_moe_lm, with the sizes read
from the configuration file; reference_loss() is the same model in plain
jax.numpy float32, written from the layer equations (ISSUE 62, "The
layers") and not from the program's ops.

The configuration is one chip's share of a deployment (its `deployment`):
`num_experts` experts of `num_experts_published` are held here from
`expert_offset` on, the router keeps its published width and its four
choices, and what the absent experts would add is left out, in the
program and in the reference alike; `vocab_size` rows of the vocabulary
(tied: one matrix is embedding and head); the layers whose published
indices `layers_held` names, each with the mixer `layer_types` gives its
index and a dense feed-forward where the index is under
`num_dense_layers_published`.

`router_balance_rate` (`assumed`): the family's `use_expert_bias` is a
selection bias a router, which models.balance_routers moves against the
load after each step's update (arXiv:2408.15664); it starts at zero and
moves the choice only, so the first step, which reference_loss() is held
to, has none of it.
"""

import numpy as np

CONV, FULL = "conv", "full_attention"


def _sizes(config):
    """The model's sizes under the names this file uses."""
    held = config["layers_held"]
    dense = [l < config["num_dense_layers_published"] for l in held]
    assert len(held) == config["num_hidden_layers"]
    assert sum(dense) == config["num_dense_layers"]
    assert not config["conv_bias"] and config["use_expert_bias"]
    assert config["tie_word_embeddings"]
    assert config["rope_parameters"]["rope_type"] == "default"
    d, heads = config["hidden_size"], config["num_attention_heads"]
    return dict(
        d=d, held_layers=held,
        kinds=[config["layer_types"][l] for l in held], dense=dense,
        heads=heads, kv=config["num_key_value_heads"], hd=d // heads,
        taps=config["conv_L_cache"], inter=config["intermediate_size"],
        experts=config["num_experts_published"],
        held=config["num_experts"], offset=config["expert_offset"],
        top_k=config["num_experts_per_tok"],
        f=config["moe_intermediate_size"],
        scaling=config["routed_scaling_factor"],
        theta=config["rope_parameters"]["rope_theta"],
        eps=config["norm_eps"], v=config["vocab_size"],
        t=config["sequence_length"])


FEEDS = ("tok", "lab")


def build(config):
    """(main, startup, loss) of one AMP train step. The programs'
    random_seed is fixed: the run's seed reaches the weights through the
    executor's PRNG counter (traffic/train_steps.py says why)."""
    import paddle_tpu as fluid
    from paddle_tpu import models
    from paddle_tpu.framework import unique_name

    s = _sizes(config)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with unique_name.guard(), fluid.program_guard(main, startup):
        tok, lab = (
            fluid.layers.data(name=name, shape=[-1, s["t"]], dtype="int64",
                              append_batch_size=False) for name in FEEDS)
        loss, kept = models.conv_moe_lm(
            tok, lab, vocab_size=s["v"], hidden_size=s["d"],
            layer_types=config["layer_types"],
            num_dense_layers=config["num_dense_layers_published"],
            num_attention_heads=s["heads"], num_key_value_heads=s["kv"],
            intermediate_size=s["inter"], num_experts=s["experts"],
            num_experts_per_tok=s["top_k"], moe_intermediate_size=s["f"],
            layers_held=s["held_layers"], conv_kernel=s["taps"],
            experts_held=s["held"], expert_offset=s["offset"],
            routed_scaling_factor=s["scaling"],
            norm_topk_prob=config["norm_topk_prob"], rope_theta=s["theta"],
            epsilon=s["eps"], initializer_range=config["initializer_range"],
            residual_layers=config["num_hidden_layers_published"],
            use_flash=config["use_flash"],
            recompute=config.get("recompute", False))
        opt = fluid.amp.decorate(
            fluid.optimizer.Adam(learning_rate=config["learning_rate"],
                                 beta1=config["adam_beta1"],
                                 beta2=config["adam_beta2"],
                                 epsilon=config["adam_epsilon"]),
            level=config["amp_level"])
        opt.minimize(loss, startup_program=startup, checkpoints=kept)
        if config["router_balance_rate"]:
            models.balance_routers(main, config["router_balance_rate"])
    return main, startup, loss


def make_batch(config, batch, rng):
    """One host batch of `batch` sequences of T + 1 int32 ids drawn
    uniform from the vocabulary's slice: `tok` the first T, `lab` the ids
    one position on."""
    t = config["sequence_length"]
    ids = rng.integers(0, config["vocab_size"], (batch, t + 1)).astype(
        np.int32)
    return {"tok": ids[:, :t], "lab": ids[:, 1:]}


def items_per_batch(feed):
    """An item is a token position (each gets a loss)."""
    return int(feed["tok"].size)


def live_pairs(length):
    """(query, key) pairs the causal mask of one sequence leaves alive."""
    return length * (length + 1) // 2


def part_flops_per_item(config):
    """{part: forward FLOPs one token needs in one such part}. Nothing
    recomputed, nothing for experts held elsewhere. conv: the three input
    maps, the output map and the operator (a gate, the taps, a gate).
    attention: W_q, W_k, W_v, W_o and the scores and values at the causal
    mask's live pairs, a token's mean. dense: the leading layer's gated
    feed-forward. experts: the router and the EXPECTED rows routed here,
    top_k x held / experts of a token's, three maps each. head: the
    sliced tied head."""
    s = _sizes(config)
    d, hd, t = s["d"], s["hd"], s["t"]
    return {
        CONV: 8 * d * d + (2 * s["taps"] + 2) * d,
        FULL: (2 * d * hd * (2 * s["heads"] + 2 * s["kv"])
               + 4 * (live_pairs(t) / t) * s["heads"] * hd),
        "dense": 6 * d * s["inter"],
        "experts": (2 * d * s["experts"]
                    + s["top_k"] * s["held"] / s["experts"] * 6 * d * s["f"]),
        "head": 2 * d * s["v"]}


def required_flops_per_item(config):
    """FLOPs one token needs in a train step (forward x 3), each held
    layer's mixer and feed-forward by its kind."""
    s, per = _sizes(config), part_flops_per_item(config)
    by_layer = sum(per[kind] + per["dense" if dense else "experts"]
                   for kind, dense in zip(s["kinds"], s["dense"]))
    return 3.0 * (by_layer + per["head"])


def conv_layers(config):
    """Gated short convolutions of a step: one a `conv` layer held."""
    return _sizes(config)["kinds"].count(CONV)


def conv_kernel_cost(config, tokens):
    """(FLOPs, bytes) of ONE gated short convolution of a train step over
    `tokens` positions, forward and backward, whatever implements it: the
    forward reads the gate ahead, the gate behind and x and writes the
    result, the backward reads the three and the result's cotangent and
    writes the three gradients, eleven [tokens, D] arrays in bf16 (the
    taps and their gradient are [D, K]); a gate, K multiply-adds and a
    gate an element forward and twice that backward. A replayed forward
    and what the gradient computes again are time and not work."""
    s = _sizes(config)
    elements = float(tokens) * s["d"]
    return 3 * (2 * s["taps"] + 2) * elements, 11 * 2.0 * elements


def attention_ops_per_step(config):
    """Attention ops of a step: one a `full_attention` layer held."""
    return _sizes(config)["kinds"].count(FULL)


def attention_kernel_cost(config, tokens=None):
    """(FLOPs, bytes) of ONE attention op of a train step over one
    sequence of `tokens` positions (default the configuration's), forward
    and backward, AT THE LIVE PAIRS ONLY and whatever implements it: six
    products of live pairs x head_dim x the query heads (the scores and
    P V forward; dV, dP, dQ and dK backward; scores computed again and
    masked tiles walked are time and not work); q, o and dO read and o
    and dQ written once at the query heads, k and v read and dK and dV
    written once at the PUBLISHED key/value heads, in bf16 (K and V
    widened to the query heads ahead of the kernels is traffic and not
    work)."""
    s = _sizes(config)
    t = tokens or s["t"]
    flops = 6 * 2.0 * live_pairs(t) * s["hd"] * s["heads"]
    return flops, 2.0 * t * s["hd"] * (5 * s["heads"] + 4 * s["kv"])


def expert_layers(config):
    """Expert layers of a step: the held layers behind the dense ones."""
    return _sizes(config)["dense"].count(False)


def expert_product_cost(config, rows):
    """(FLOPs, bytes) of one train step's grouped expert products in ONE
    expert layer when `rows` (token, slot) pairs were routed to the held
    experts: as glm4_moe_lite.expert_product_cost (nine products of rows
    x d x f; each reads its two operands and writes its result once in
    bf16, the held experts' weights once a product)."""
    s = _sizes(config)
    d, f, held = s["d"], s["f"], s["held"]
    flops = 9 * 2.0 * rows * d * f
    return flops, 9 * 2.0 * (rows * d + rows * f + held * d * f)


# query rows of one block of the reference's attention; rows of one block
# of its dense feed-forward and of its head
_QUERY_ROWS = 128
_FFN_ROWS = 2048
# parameters of a mixer by kind, and of a feed-forward, dense or sparse
_MIXER_PARAMS = {CONV: 5, FULL: 6}
_FFN_PARAMS = {True: 3, False: 4}


def reference_loss(config, params, feed):
    """The mean next-token cross-entropy of the forward pass in float32,
    from the layer equations (ISSUE 62), one sequence at a time; each
    layer is a jax.checkpoint, and so are each block of _QUERY_ROWS query
    rows of attention, each block of _FFN_ROWS rows of the dense
    feed-forward (its hidden rows are 11,776 wide) and of the head, and
    each held expert's share of the output, so jax.grad keeps one layer's
    activations, one [heads, rows, T] slab of scores, one block's hidden
    rows and one expert's at a time. Independent of the program's ops:
    the gated short convolution is the published one projection [D, 3 D]
    cut in its thirds (B, C, x) and the sum over the taps of the shifted
    B * x; attention is a masked softmax over an explicit boolean mask,
    K and V heads repeated to the query heads; QK-norm is ahead of the
    rotation, which is written from the angle formula over the whole
    head; the router is jax.lax.top_k over the sigmoid scores, its
    weights the chosen scores over their sum + 1e-6; the experts are a
    scan over the held experts with a mask.

    `params`: the trainable parameters in the order the program created
    them: the embedding (also the head); per held layer operator_norm,
    then the mixer's (conv: W_B, W_C, W_x [D, D], the thirds of the
    published in_proj in its order; the taps [D, K]; W_out. attention:
    W_q [D, H hd], W_k and W_v [D, kv hd], the query heads' norm and the
    key heads' [hd], W_o), ffn_norm, and then W1, W3, W2 (a dense layer:
    gate, up, down) or the router [D, E] and the held experts' W1, W3
    [held, D, F] and W2 [held, F, D]; the embedding norm's weight. The
    routers' selection bias is a buffer, not among them: taken as the
    zeros it starts from."""
    import jax
    import jax.numpy as jnp

    s = _sizes(config)
    eps, d, length = s["eps"], s["d"], s["t"]
    heads, kv, hd = s["heads"], s["kv"], s["hd"]
    position = jnp.arange(length)

    def rms(x, w):
        return w * x * jax.lax.rsqrt((x ** 2).mean(-1, keepdims=True) + eps)

    def by_rows(block, arrays, *weights):
        """block(*rows of each of `arrays`, *weights) over blocks of
        _FFN_ROWS rows, each a jax.checkpoint; block keeps the rows."""
        rows = min(_FFN_ROWS, length)
        assert length % rows == 0
        out = jax.lax.map(
            lambda parts: jax.checkpoint(block)(*parts, *weights),
            tuple(a.reshape((length // rows, rows) + a.shape[1:])
                  for a in arrays))
        return out.reshape((length,) + out.shape[2:])

    def short_conv(u, w_b, w_c, w_x, taps, w_out):
        proj = u @ jnp.concatenate([w_b, w_c, w_x], axis=1)   # [T, 3 D]
        gate_b, gate_c, x = proj[:, :d], proj[:, d:2 * d], proj[:, 2 * d:]
        n = taps.shape[1]
        early = jnp.concatenate([jnp.zeros((n - 1, d)), gate_b * x])
        z = sum(early[j:j + length] * taps[:, j] for j in range(n))
        return (gate_c * z) @ w_out

    def rotate(x):
        """x [T, n, hd]: the pair (j, j + hd/2) turned by the row's
        position times theta^(-2j/hd)."""
        half = hd // 2
        freq = float(s["theta"]) ** (-np.arange(half) / half)
        angle = (position.astype(jnp.float32)[:, None]
                 * jnp.asarray(freq, jnp.float32))[:, None, :]
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def attention(u, w_q, w_k, w_v, norm_q, norm_k, w_o):
        q = rotate(rms((u @ w_q).reshape(length, heads, hd), norm_q))
        k = rotate(rms((u @ w_k).reshape(length, kv, hd), norm_k))
        v = (u @ w_v).reshape(length, kv, hd)
        # query head i reads key/value head i // (heads / kv)
        k, v = (jnp.repeat(x, heads // kv, axis=1) for x in (k, v))
        rows = min(_QUERY_ROWS, length)
        assert length % rows == 0

        @jax.checkpoint
        def some_rows(first):
            q_rows = jax.lax.dynamic_slice_in_dim(q, first, rows)
            keep = position[None, :] <= (first + jnp.arange(rows))[:, None]
            scores = jnp.einsum("qhd,khd->hqk", q_rows, k) / np.sqrt(hd)
            prob = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), -1)
            return jnp.einsum("hqk,khd->qhd", prob, v)

        out = jax.lax.map(some_rows, jnp.arange(0, length, rows))
        return out.reshape(length, heads * hd) @ w_o

    def gated(x, w1, w3, w2):
        return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2

    def dense(x, w1, w3, w2):
        return by_rows(gated, (x,), w1, w3, w2)

    def experts(x, w_r, w1, w3, w2):
        scores = jax.nn.sigmoid(x @ w_r)                    # [T, experts]
        top, idx = jax.lax.top_k(scores, s["top_k"])        # bias: zeros
        weight = s["scaling"] * top / (
            top.sum(-1, keepdims=True) + 1e-6
            if config["norm_topk_prob"] else 1.0)

        @jax.checkpoint
        def share_of(expert):
            e, w1_e, w3_e, w2_e = expert
            mine = (weight * (idx == s["offset"] + e)).sum(-1, keepdims=True)
            return mine * gated(x, w1_e, w3_e, w2_e)

        def add_expert(out, expert):
            return out + share_of(expert), None

        return jax.lax.scan(add_expert, jnp.zeros_like(x),
                            (jnp.arange(s["held"]), w1, w3, w2))[0]

    def layer(kind, is_dense):
        mixer_n = _MIXER_PARAMS[kind]

        @jax.checkpoint
        def run(h, weights):
            norm_1, mixer_w = weights[0], weights[1:1 + mixer_n]
            norm_2, ffn_w = weights[1 + mixer_n], weights[2 + mixer_n:]
            h = h + (short_conv if kind == CONV else attention)(
                rms(h, norm_1), *mixer_w)
            return h + (dense if is_dense else experts)(rms(h, norm_2),
                                                        *ffn_w)
        return run

    def nll_rows(x, labels, norm_w, table):
        logp = jax.nn.log_softmax(rms(x, norm_w) @ table.T, axis=-1)
        return -jnp.take_along_axis(logp, labels[:, None], axis=-1)

    def sequence_loss_sum(one):
        tok, lab = one
        table, *rest = params
        it = iter(rest)
        h = table[tok]                                      # [T, D]
        for kind, is_dense in zip(s["kinds"], s["dense"]):
            count = 2 + _MIXER_PARAMS[kind] + _FFN_PARAMS[is_dense]
            h = layer(kind, is_dense)(h, [next(it) for _ in range(count)])
        return by_rows(nll_rows, (h, lab), next(it), table).sum()

    with jax.default_matmul_precision("highest"):
        feeds = tuple(jnp.asarray(feed[n]) for n in FEEDS)
        return jax.lax.map(sequence_loss_sum, feeds).sum() / feeds[0].size
