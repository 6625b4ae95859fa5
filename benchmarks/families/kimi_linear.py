"""Family `kimi_linear`: a mixture-of-experts decoder whose mixers are
three Kimi-Delta-Attention layers to one latent-attention layer without
positions (moonshotai Kimi-Linear-48B-A3B-Instruct; arXiv:2510.26692),
through paddle_tpu.models.kda_moe_lm, with the sizes read from the
configuration file; reference_loss() is the same model in plain jax.numpy
float32, written from the layer equations (ISSUE 55, "The layers") and
not from the program's ops: its Kimi Delta Attention is the recurrence
TOKEN BY TOKEN, where the program runs the chunked algebra.

The configuration is one chip's share of a deployment (its `deployment`):
`num_experts` experts of `num_experts_published` are held here from
`expert_offset` on, the router keeps its published width, the shared
expert and both mixers are whole, and what the absent experts would add
is left out, in the program and in the reference alike; `vocab_size` rows
of the vocabulary; the first `num_hidden_layers` layers, whose mixers are
read from the two published 1-based lists of `linear_attn_config`, which
the file keeps whole.

What the published config does not spell out are keys of the file
(`assumed` says why each), read here by the program and the reference
alike: `kda_gate_rank`, `kda_chunk_size` (the program's alone: the
reference has no chunk), `l2_norm_epsilon`, `router_balance_rate`.

`router_balance_rate` (`assumed`): only held experts lower the loss here,
so the cut's router walks towards them, which no rank of a deployment
sees; after each step's update every router's selection bias moves
against the load the step saw over all the published experts
(models.balance_routers: arXiv:2408.15664). The bias starts at zero and
moves the choice only, so the first step, which reference_loss() is held
to, has none of it.
"""

import numpy as np

KDA, FULL = "kda", "full_attention"


def _sizes(config):
    """The model's sizes under the names this file uses."""
    layers = config["num_hidden_layers"]
    linear = config["linear_attn_config"]
    assert config["q_lora_rank"] is None and config["mla_use_nope"], \
        "the family is written for one direct query map and no rotation"
    assert config["num_expert_group"] == 1 and config["topk_group"] == 1, \
        "grouped top-k over one group is plain top-k; no other is written"
    assert config["moe_router_activation_func"] == "sigmoid"
    kinds = []
    for l in range(1, layers + 1):
        in_kda, in_full = (l in linear[key]
                           for key in ("kda_layers", "full_attn_layers"))
        assert in_kda != in_full, l
        kinds.append(KDA if in_kda else FULL)
    dense = config["first_k_dense_replace"]
    return dict(
        d=config["hidden_size"], layers=layers, kinds=kinds,
        sparse=[i >= dense and (i - dense) % config["moe_layer_freq"] == 0
                for i in range(layers)],
        kda_heads=linear["num_heads"], kda_hd=linear["head_dim"],
        conv=linear["short_conv_kernel_size"], rank=config["kda_gate_rank"],
        heads=config["num_attention_heads"], kvr=config["kv_lora_rank"],
        nope=config["qk_nope_head_dim"], rope=config["qk_rope_head_dim"],
        vd=config["v_head_dim"], inter=config["intermediate_size"],
        experts=config["num_experts_published"], held=config["num_experts"],
        offset=config["expert_offset"],
        top_k=config["num_experts_per_token"],
        f=config["moe_intermediate_size"],
        fs=config["num_shared_experts"] * config["moe_intermediate_size"],
        scaling=config["routed_scaling_factor"],
        eps=config["rms_norm_eps"], l2_eps=config["l2_norm_epsilon"],
        v=config["vocab_size"], t=config["sequence_length"])


FEEDS = ("tok", "lab")


def build(config):
    """(main, startup, loss) of one AMP train step. The programs'
    random_seed is fixed: the run's seed reaches the weights through the
    executor's PRNG counter (traffic/train_steps.py says why)."""
    import paddle_tpu as fluid
    from paddle_tpu import models
    from paddle_tpu.framework import unique_name

    s = _sizes(config)
    linear = config["linear_attn_config"]
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with unique_name.guard(), fluid.program_guard(main, startup):
        tok, lab = (
            fluid.layers.data(name=name, shape=[-1, s["t"]], dtype="int64",
                              append_batch_size=False) for name in FEEDS)
        loss, kept = models.kda_moe_lm(
            tok, lab, vocab_size=s["v"], hidden_size=s["d"],
            num_hidden_layers=s["layers"], kda_layers=linear["kda_layers"],
            full_attn_layers=linear["full_attn_layers"],
            kda_num_heads=s["kda_heads"], kda_head_dim=s["kda_hd"],
            num_attention_heads=s["heads"], kv_lora_rank=s["kvr"],
            qk_nope_head_dim=s["nope"], qk_rope_head_dim=s["rope"],
            v_head_dim=s["vd"], intermediate_size=s["inter"],
            num_experts=s["experts"], num_experts_per_token=s["top_k"],
            moe_intermediate_size=s["f"], q_lora_rank=config["q_lora_rank"],
            mla_use_nope=config["mla_use_nope"],
            rope_theta=config["rope_theta"], short_conv_kernel_size=s["conv"],
            kda_gate_rank=s["rank"], kda_chunk_size=config["kda_chunk_size"],
            l2_norm_epsilon=s["l2_eps"],
            first_k_dense_replace=config["first_k_dense_replace"],
            moe_layer_freq=config["moe_layer_freq"],
            num_shared_experts=config["num_shared_experts"],
            experts_held=s["held"], expert_offset=s["offset"],
            routed_scaling_factor=s["scaling"],
            moe_renormalize=config["moe_renormalize"],
            router_scoring=config["moe_router_activation_func"],
            epsilon=s["eps"],
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


def part_flops_per_item(config):
    """{part: forward FLOPs one token needs in one such part}. Nothing
    recomputed, nothing for experts held elsewhere. kda_maps: W_q, W_k,
    W_v, W_o, the two low-rank gates, beta's map and the three short
    convolutions. kda_rule: the recurrence's three products of a head's
    [K, V] state a token (k^T S, the rank-one correction, q^T S), which
    no chunk length moves. mla_maps: W_q, W_kva, W_kvb, W_o. mla_pairs:
    scores at nope + rope and values at v_head_dim over the causal mask's
    live pairs, a token's mean. dense, experts, head as the other expert
    families count them."""
    s = _sizes(config)
    d, t = s["d"], s["t"]
    wide = s["kda_heads"] * s["kda_hd"]
    return {
        "kda_maps": 2 * (4 * d * wide + 2 * (d * s["rank"] + s["rank"] * wide)
                         + d * s["kda_heads"] + 3 * s["conv"] * wide),
        "kda_rule": 3 * 2 * s["kda_heads"] * s["kda_hd"] ** 2,
        "mla_maps": 2 * (d * s["heads"] * (s["nope"] + s["rope"])
                         + d * (s["kvr"] + s["rope"])
                         + s["kvr"] * s["heads"] * (s["nope"] + s["vd"])
                         + s["heads"] * s["vd"] * d),
        "mla_pairs": 2 * ((t + 1) / 2) * s["heads"] * (
            s["nope"] + s["rope"] + s["vd"]),
        "dense": 6 * d * s["inter"],
        "experts": (2 * d * s["experts"] + 6 * d * s["fs"]
                    + s["top_k"] * s["held"] / s["experts"] * 6 * d * s["f"]),
        "head": 2 * d * s["v"]}


def required_flops_per_item(config):
    """FLOPs one token needs in a train step (forward x 3), each layer's
    mixer and feed-forward by its kind."""
    s, per = _sizes(config), part_flops_per_item(config)
    mixer = {KDA: per["kda_maps"] + per["kda_rule"],
             FULL: per["mla_maps"] + per["mla_pairs"]}
    by_layer = sum(mixer[kind] + per["experts" if sparse else "dense"]
                   for kind, sparse in zip(s["kinds"], s["sparse"]))
    return 3.0 * (by_layer + per["head"])


def kda_layers(config):
    """Kimi-Delta-Attention layers of a step: the held layers the
    published `kda_layers` names."""
    return _sizes(config)["kinds"].count(KDA)


def kda_scan_cost(config, tokens):
    """(FLOPs, bytes) of one train step's delta rule in ONE such layer
    over `tokens` positions, whatever implements it: the recurrence's
    three [K, V] products a token a head forward and twice that backward
    (the chunked form's within-chunk products and solves, a replayed
    forward and what the gradient computes again are time and not work);
    q, k, v, the raw gate [H K] and o read or written once and their
    gradients once, in the bf16 the program holds them in, beta and its
    gradient [H] likewise."""
    s = _sizes(config)
    h, k = s["kda_heads"], s["kda_hd"]
    flops = 3.0 * tokens * 3 * 2 * h * k * k
    return flops, 2 * 2.0 * tokens * (5 * h * k + h)


def attention_ops_per_step(config):
    """Attention ops of a step: one a latent-attention layer."""
    return _sizes(config)["kinds"].count(FULL)


def attention_kernel_cost(config, tokens=None):
    """(FLOPs, bytes) of ONE attention op of a train step over one
    sequence of `tokens` positions (default the configuration's), forward
    and backward, AT THE PUBLISHED WIDTHS whatever lanes the program
    hands the kernels: over the causal mask's live pairs, the scores and
    dQ and dK at nope + rope (192), P V, dV and dP at v_head_dim (128);
    q and k read and dQ and dK written at 192, v read and dV written and
    o written and o and dO read at 128, in bf16. Lanes of zeros the layer
    pads with are time and not work, so they show as a lower share."""
    s = _sizes(config)
    t = tokens or s["t"]
    qk, vd, heads = s["nope"] + s["rope"], s["vd"], s["heads"]
    live = t * (t + 1) / 2
    flops = 2.0 * live * heads * 3 * (qk + vd)
    return flops, 2.0 * t * heads * (4 * qk + 5 * vd)


def expert_layers(config):
    """Expert layers of a step: the held layers whose feed-forward is
    sparse."""
    return sum(_sizes(config)["sparse"])


def expert_product_cost(config, rows):
    """(FLOPs, bytes) of one train step's grouped expert products in ONE
    expert layer when `rows` (token, slot) pairs were routed to the held
    experts: as glm4_moe_lite.expert_product_cost (nine products of rows
    x d x f; each reads its two operands and writes its result once in
    bf16, the held experts' weights once a product). The shared expert's
    products are XLA's and not counted."""
    s = _sizes(config)
    d, f, held = s["d"], s["f"], s["held"]
    flops = 9 * 2.0 * rows * d * f
    return flops, 9 * 2.0 * (rows * d + rows * f + held * d * f)


# tokens of one block of the reference's recurrence, and query rows of
# one block of its attention
_TOKEN_BLOCK = 64
_QUERY_ROWS = 128
# parameters of a mixer, by kind, and of a feed-forward, dense or sparse
_MIXER_PARAMS = {KDA: 15, FULL: 5}
_FFN_PARAMS = {False: 3, True: 7}


def reference_loss(config, params, feed):
    """The mean next-token cross-entropy of the forward pass in float32,
    from the layer equations (ISSUE 55), one sequence at a time; each
    layer and the head are a jax.checkpoint. Independent of the program's
    ops: Kimi Delta Attention is the recurrence S_t = (I - beta_t k_t
    k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T, o_t = S_t^T q_t /
    sqrt(K), a lax.scan over single tokens, in blocks of _TOKEN_BLOCK
    tokens under a jax.checkpoint, so that jax.grad keeps one [H, K, V]
    state a block and replays a block's steps (kept whole the states of
    one layer at the cell's size are 17 GB); the short convolution is
    the sum over its taps of the shifted input; latent attention is a
    masked softmax over an explicit boolean mask, a block of _QUERY_ROWS
    query rows a jax.checkpoint, the one shared key head repeated to
    every query head and NOT turned; the router is jax.lax.top_k over the
    sigmoid scores; the experts are a scan over the held experts with a
    mask, each expert's share a jax.checkpoint.

    `params`: the trainable parameters in the order the program created
    them: embedding; per layer norm_1, then the mixer's (KDA: W_q, W_k,
    W_v [D, H K], the three filters [H K, taps], W_f1 [D, r], W_f2 [r, H
    K], W_b [D, H], A_log [H], dt_bias [H K], the head norm's w [K], W_g1,
    W_g2, W_o; latent: W_q [D, H (nope + rope)], W_kva [D, kvr + rope],
    the latent's norm [kvr], W_kvb [kvr, H (nope + v)], W_o), norm_2, and
    then W_g, W_u, W_d (a dense layer) or the router [D, E], the held
    experts' G, U [held, D, F] and D [held, F, D] and the shared expert's
    W_g, W_u, W_d; the final norm's weight and the head. The router's
    selection bias is a buffer, not among them: taken as the zeros the
    configuration assumes."""
    import jax
    import jax.numpy as jnp

    s = _sizes(config)
    eps, length = s["eps"], s["t"]
    heads, hd = s["kda_heads"], s["kda_hd"]

    def rms(x, w):
        return w * x * jax.lax.rsqrt((x ** 2).mean(-1, keepdims=True) + eps)

    def short_conv(x, taps):
        """x [T, C], taps [C, n]: silu(sum_j taps[:, j] x[t - (n-1) + j]),
        zeros before the first token."""
        n = taps.shape[1]
        early = jnp.concatenate([jnp.zeros((n - 1, x.shape[1])), x])
        return jax.nn.silu(sum(early[j:j + length] * taps[:, j]
                               for j in range(n)))

    def unit(x):
        return x * jax.lax.rsqrt((x ** 2).sum(-1, keepdims=True)
                                 + s["l2_eps"])

    def delta_rule(q, k, v, g, beta):
        """q, k, g [T, H, K], v [T, H, V], beta [T, H] -> o [T, H, V],
        a token at a time."""
        block = min(_TOKEN_BLOCK, length)
        assert length % block == 0

        def token(state, now):
            q_t, k_t, v_t, g_t, b_t = now
            state = state * jnp.exp(g_t)[..., None]
            seen = jnp.einsum("hk,hkv->hv", k_t, state)
            state = state + b_t[:, None, None] * k_t[..., None] * (
                v_t - seen)[:, None, :]
            return state, jnp.einsum("hk,hkv->hv", q_t, state)

        @jax.checkpoint
        def some_tokens(state, tokens):
            return jax.lax.scan(token, state, tokens)

        blocks = tuple(x.reshape((length // block, block) + x.shape[1:])
                       for x in (q, k, v, g, beta))
        _, out = jax.lax.scan(some_tokens,
                              jnp.zeros((heads, hd, v.shape[-1])), blocks)
        return out.reshape(length, heads, -1) / np.sqrt(hd)

    def kda(a, w_q, w_k, w_v, c_q, c_k, c_v, w_f1, w_f2, w_b, a_log,
            dt_bias, w_norm, w_g1, w_g2, w_o):
        def head_rows(x):
            return x.reshape(length, heads, hd)

        q, k, v = (head_rows(short_conv(a @ w, taps)) for w, taps in (
            (w_q, c_q), (w_k, c_k), (w_v, c_v)))
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
            head_rows((a @ w_f1) @ w_f2 + dt_bias))
        o = delta_rule(unit(q), unit(k), v, g, jax.nn.sigmoid(a @ w_b))
        gate = jax.nn.sigmoid(head_rows((a @ w_g1) @ w_g2))
        return (rms(o, w_norm) * gate).reshape(length, heads * hd) @ w_o

    def latent(a, w_q, w_kva, norm_kv, w_kvb, w_o):
        n, nope, rope, vd = s["heads"], s["nope"], s["rope"], s["vd"]
        rows = min(_QUERY_ROWS, length)
        assert length % rows == 0
        position = jnp.arange(length)
        q = (a @ w_q).reshape(length, n, nope + rope)
        down = a @ w_kva
        c_kv, k_pe = down[:, :s["kvr"]], down[:, s["kvr"]:]
        kv = (rms(c_kv, norm_kv) @ w_kvb).reshape(length, n, nope + vd)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            k_pe[:, None, :], (length, n, rope))], -1)
        v = kv[..., nope:]

        @jax.checkpoint
        def some_rows(first):
            q_rows = jax.lax.dynamic_slice_in_dim(q, first, rows)
            keep = position[None, :] <= (first + jnp.arange(rows))[:, None]
            scores = jnp.einsum("qhd,khd->hqk", q_rows, k) \
                / np.sqrt(nope + rope)
            prob = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), -1)
            return jnp.einsum("hqk,khd->qhd", prob, v)

        out = jax.lax.map(some_rows, jnp.arange(0, length, rows))
        return out.reshape(length, n * vd) @ w_o

    def gated(x, w_g, w_u, w_d):
        return (jax.nn.silu(x @ w_g) * (x @ w_u)) @ w_d

    def experts(x, w_r, gate_w, up, down, s_g, s_u, s_d):
        scores = jax.nn.sigmoid(x @ w_r)                    # [T, experts]
        top, idx = jax.lax.top_k(scores, s["top_k"])
        weight = s["scaling"] * top / (
            top.sum(-1, keepdims=True) + 1e-20
            if config["moe_renormalize"] else 1.0)

        @jax.checkpoint
        def share_of(expert):
            e, g_e, u_e, d_e = expert
            mine = (weight * (idx == s["offset"] + e)).sum(-1, keepdims=True)
            return mine * gated(x, g_e, u_e, d_e)

        def add_expert(out, expert):
            return out + share_of(expert), None

        return jax.lax.scan(add_expert, gated(x, s_g, s_u, s_d),
                            (jnp.arange(s["held"]), gate_w, up, down))[0]

    def layer(kind, sparse):
        mixer_n = _MIXER_PARAMS[kind]

        @jax.checkpoint
        def run(x, weights):
            norm_1, mixer_w = weights[0], weights[1:1 + mixer_n]
            norm_2, ffn_w = weights[1 + mixer_n], weights[2 + mixer_n:]
            x = x + (kda if kind == KDA else latent)(rms(x, norm_1),
                                                     *mixer_w)
            return x + (experts if sparse else gated)(rms(x, norm_2), *ffn_w)
        return run

    @jax.checkpoint
    def nll_sum(x, norm_w, w_head, labels):
        logp = jax.nn.log_softmax(rms(x, norm_w) @ w_head, axis=-1)
        return -jnp.take_along_axis(logp, labels[:, None], axis=-1).sum()

    def sequence_loss_sum(one):
        tok, lab = one
        it = iter(params)
        x = next(it)[tok]                                   # [T, D]
        for kind, sparse in zip(s["kinds"], s["sparse"]):
            count = 2 + _MIXER_PARAMS[kind] + _FFN_PARAMS[sparse]
            x = layer(kind, sparse)(x, [next(it) for _ in range(count)])
        return nll_sum(x, next(it), next(it), lab)

    with jax.default_matmul_precision("highest"):
        feeds = tuple(jnp.asarray(feed[n]) for n in FEEDS)
        return jax.lax.map(sequence_loss_sum, feeds).sum() / feeds[0].size
