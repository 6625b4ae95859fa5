"""Family `xing4`: a latent-attention mixture-of-experts decoder whose
residual path is `hc_mult` streams a token mixed by manifold-constrained
hyper-connections (XingChen-AGI Xing4.0-29B-A4B, model_type `xing4_0`;
mHC, arXiv:2512.24880, on Hyper-Connections, arXiv:2409.19606; the block
inside them is DeepSeek-V3's, arXiv:2412.19437, under YaRN), through
paddle_tpu.models.mla_moe_lm with `hc_mult`, with the sizes read from the
configuration file; reference_loss() is the same model in plain jax.numpy
float32, written from the equations below and not from the program's ops.

n = `hc_mult`, C = `hidden_size`, a token's state X in R^{n x C}; X_0 =
the token's embedding in each of the n rows. Every sublayer F (latent
attention; the gated feed-forward or the expert layer), each behind its
own rms_norm, has its own phi [n C, n + n + n^2], b [n + n + n^2] and
alpha [3]:

    u        = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)    float32, no weight
    [p|q|r]  = u phi                                           widths n, n, n^2
    H_pre    = sigmoid(alpha_pre p + b_pre)                    [n]
    H_post   = 2 sigmoid(alpha_post q + b_post)                [n]
    M_0      = exp(clamp(alpha_res mat(r) + b_res, clamp_min, clamp_max))
    M_t      = rows(cols(M_{t-1})), t = 1..hc_sinkhorn_iters   cols: M / (column sums + hc_eps); rows alike
    H_res    = M_20
    x_in     = sum_j H_pre[j] X[j]                             [C]
    y        = F(rms_norm(x_in))                               [C]
    X'[i]    = sum_j H_res[i, j] X[j] + H_post[i] y            [n, C]

and after the last block x_out = sum_j X[j], then the final norm and the
untied head. YaRN as DeepSeek-V3's modelling code reads the published
keys: the rotary frequencies blended by beta_fast / beta_slow over
`original_max_position_embeddings` at `factor`, cosines and sines times
m(mscale) / m(mscale_all_dim), scores times m(mscale_all_dim)^2 over
1 / sqrt(nope + rope), m(s) = 0.1 s ln(factor) + 1.

The configuration is one chip's share of a deployment (its `deployment`):
`n_routed_experts` experts of `n_routed_experts_published` are held here
from `expert_offset` on, the router keeps its published width, the shared
expert, latent attention and the hyper-connections are whole, and what
the absent experts would add is left out, in the program and in the
reference alike; `vocab_size` rows of the vocabulary; the first
`num_hidden_layers` blocks, `first_k_dense_replace` of them dense; no
prediction module (`num_nextn_predict_layers` 0: a layer of the last
pipeline stage, left out with the other blocks).

`router_balance_rate` (`assumed`): only held experts lower the loss here,
so the cut's router walks towards them, which no rank of a deployment
sees; after each step's update every router's selection bias moves
against the load the step saw over all the published experts
(models.balance_routers: arXiv:2408.15664, the rule `topk_method`
noaux_tc names). The bias starts at zero and moves the choice only, so
the first step, which reference_loss() is held to, has none of it.
"""

import numpy as np


def _sizes(config):
    """The model's sizes under the names this file uses."""
    assert config["n_group"] == 1 and config["topk_group"] == 1, \
        "grouped top-k over one group is plain top-k; no other is written"
    assert config["scoring_func"] == "sigmoid"
    assert config["moe_layer_freq"] == 1
    assert not config["num_nextn_predict_layers"], \
        "no prediction module is written for hyper-connections"
    return dict(
        d=config["hidden_size"], layers=config["num_hidden_layers"],
        dense=config["first_k_dense_replace"], n=config["hc_mult"],
        iters=config["hc_sinkhorn_iters"], hc_eps=config["hc_eps"],
        clamp=(config["mhc_h_res_clamp_min"], config["mhc_h_res_clamp_max"]),
        heads=config["num_attention_heads"], qr=config["q_lora_rank"],
        kvr=config["kv_lora_rank"], nope=config["qk_nope_head_dim"],
        rope=config["qk_rope_head_dim"], vd=config["v_head_dim"],
        theta=config["rope_theta"], yarn=config["rope_scaling"],
        inter=config["intermediate_size"],
        experts=config["n_routed_experts_published"],
        held=config["n_routed_experts"], offset=config["expert_offset"],
        top_k=config["num_experts_per_tok"],
        f=config["moe_intermediate_size"],
        fs=config["n_shared_experts"] * config["moe_intermediate_size"],
        scaling=config["routed_scaling_factor"],
        eps=config["rms_norm_eps"], v=config["vocab_size"],
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
        loss, kept = models.mla_moe_lm(
            tok, lab, None, vocab_size=s["v"], hidden_size=s["d"],
            num_hidden_layers=s["layers"], first_k_dense_replace=s["dense"],
            num_attention_heads=s["heads"], q_lora_rank=s["qr"],
            kv_lora_rank=s["kvr"], qk_nope_head_dim=s["nope"],
            qk_rope_head_dim=s["rope"], v_head_dim=s["vd"],
            intermediate_size=s["inter"], n_routed_experts=s["experts"],
            num_experts_per_tok=s["top_k"], moe_intermediate_size=s["f"],
            n_shared_experts=config["n_shared_experts"],
            experts_held=s["held"], expert_offset=s["offset"],
            routed_scaling_factor=s["scaling"],
            norm_topk_prob=config["norm_topk_prob"], rope_theta=s["theta"],
            num_nextn_predict_layers=0, epsilon=s["eps"],
            residual_layers=config["num_hidden_layers_published"],
            use_flash=config["use_flash"], hc_mult=s["n"],
            hc_sinkhorn_iters=s["iters"], hc_eps=s["hc_eps"],
            hc_res_clamp=s["clamp"], rope_scaling=s["yarn"], recompute=True)
        if not config.get("recompute", True):
            kept = []
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


def parameters_here(config):
    """Trainable parameters of the cut, from the configuration's sizes
    alone (tests hold the program's own count to it): the embedding and
    the head, the final norm, and by block latent attention with its two
    latent norms, two block norms, two sublayers' hyper-connections (phi,
    b, alpha) and the gated feed-forward, or the router, the held experts
    and the shared expert. The routers' selection biases are buffers."""
    s = _sizes(config)
    d, n = s["d"], s["n"]
    attention = (d * s["qr"] + s["qr"]
                 + s["qr"] * s["heads"] * (s["nope"] + s["rope"])
                 + d * (s["kvr"] + s["rope"]) + s["kvr"]
                 + s["kvr"] * s["heads"] * (s["nope"] + s["vd"])
                 + s["heads"] * s["vd"] * d)
    hyper = 2 * (n * d * (2 * n + n * n) + (2 * n + n * n) + 3)
    dense = attention + hyper + 2 * d + 3 * d * s["inter"]
    sparse = (attention + hyper + 2 * d + d * s["experts"]
              + 3 * d * s["fs"] + s["held"] * 3 * d * s["f"])
    return (s["dense"] * dense + (s["layers"] - s["dense"]) * sparse
            + 2 * s["v"] * d + d)


def part_flops_per_item(config):
    """{part: forward FLOPs one token needs in one such part}. Nothing
    recomputed, nothing for experts held elsewhere. mla: the five
    projections and causal attention at half the T x T pairs, scores at
    nope + rope and values at v_head_dim. hyper: ONE sublayer's
    hyper-connection: the maps' projection [n C] x [2 n + n^2] and the
    two mixes, n and n^2 + n multiply-adds a channel (the sweeps are 40
    passes over 16 numbers a token: not FLOPs of note). dense, experts,
    head as the other expert families count them."""
    s = _sizes(config)
    d, t, heads, n = s["d"], s["t"], s["heads"], s["n"]
    qk = s["nope"] + s["rope"]
    projections = 2 * (d * s["qr"] + s["qr"] * heads * qk
                       + d * (s["kvr"] + s["rope"])
                       + s["kvr"] * heads * (s["nope"] + s["vd"])
                       + heads * s["vd"] * d)
    return {
        "mla": projections + 2 * ((t + 1) / 2) * heads * (qk + s["vd"]),
        "hyper": 2 * n * d * (2 * n + n * n) + 2 * d * (n + n * n + n),
        "dense": 6 * d * s["inter"],
        "experts": (2 * d * s["experts"] + 6 * d * s["fs"]
                    + s["top_k"] * s["held"] / s["experts"] * 6 * d * s["f"]),
        "head": 2 * d * s["v"]}


def required_flops_per_item(config):
    """FLOPs one token needs in a train step (forward x 3): every block's
    attention and feed-forward by its kind, each behind a
    hyper-connection, and the head."""
    s, per = _sizes(config), part_flops_per_item(config)
    block = per["mla"] + 2 * per["hyper"]
    return 3.0 * (s["dense"] * (block + per["dense"])
                  + (s["layers"] - s["dense"]) * (block + per["experts"])
                  + per["head"])


def hyper_connection_sublayers(config):
    """Sublayers of a step behind a hyper-connection: two a block."""
    return 2 * config["num_hidden_layers"]


def hyper_connection_cost(config, tokens=None, replayed_share=None):
    """The LEAST bytes of HBM traffic one train step's hyper-connections
    can move under any implementation, over `tokens` positions (default
    the configuration's), given that the sublayer F lies between a
    hyper-connection's two halves and that `replayed_share` of the
    sublayers run their forward a second time in the backward (default:
    a checkpoint at each block's input replays every block but the last,
    which lies behind the last checkpoint; fewer where the executor keeps
    a segment). With s the bytes of a stream element (bf16: 2), a token
    and sublayer:

    forward (and a replay, the same): X read twice (ahead of F for the
      maps and x_in, behind it for X'), X' written once, x_in written and
      y read once: (3 n + 2) C s, 100 KB at n = 4, C = 3584;
    backward: X' 's cotangent read twice (ahead of F's backward for y's
      cotangent, behind it with x_in's for X's), X read once (the maps',
      H_pre's and H_res's cotangents are sums over it), X's cotangent
      written once, y read once (H_post's cotangent), y's cotangent
      written and x_in's read once: (4 n + 3) C s;
    the maps' own: H_pre, H_post and H_res written and read once a pass
      and their cotangents once, float32, and phi read a pass and its
      gradient written once.

    A kernel that fused a whole half into one pass over a token's rows
    would reach it and none can move less: what XLA's unfused passes move
    beyond it, the sweeps' 40 dependent steps and every float32 copy are
    time and not work."""
    s = _sizes(config)
    n, d = s["n"], s["d"]
    t = tokens or s["t"]
    if replayed_share is None:
        replayed_share = (s["layers"] - 1.0) / s["layers"]
    maps = 2 * n + n * n
    a_pass = (3 * n + 2) * d * 2.0
    backward = (4 * n + 3) * d * 2.0
    a_token = (1.0 + replayed_share) * (a_pass + 2 * maps * 4.0) \
        + backward + 4 * maps * 4.0
    phi = n * d * maps * 4.0
    return hyper_connection_sublayers(config) * (
        t * a_token + (3.0 + replayed_share) * phi)


def attention_ops_per_step(config):
    """Attention ops of a step: one a block."""
    return config["num_hidden_layers"]


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
    """Expert layers of a step: every block behind the leading dense
    ones."""
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


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


# parameters of a sublayer's hyper-connection (phi, b, alpha) and norm, of
# latent attention, and of a feed-forward, dense or sparse
_HYPER_PARAMS, _ATTENTION_PARAMS = 4, 7
_FFN_PARAMS = {True: 3, False: 7}


def yarn_frequencies(rope, theta, yarn):
    """[rope / 2] float64: the rotary pairs' frequencies as DeepSeek-V3's
    YaRN reads the published group: pair j turns by theta^(-2j/rope)
    where it turns more than beta_fast times over the original context,
    by that over `factor` where it turns fewer than beta_slow times, and
    by a linear blend between (the bounds are the floor and the ceiling
    of rope ln(original / (2 pi beta)) / (2 ln theta))."""
    pairs = np.arange(rope // 2, dtype=np.float64)
    plain = float(theta) ** (-2.0 * pairs / rope)
    if not yarn:
        return plain
    assert yarn["type"] == "yarn", yarn

    def pair_turning(times):
        return rope * np.log(yarn["original_max_position_embeddings"]
                             / (times * 2 * np.pi)) / (2 * np.log(theta))

    low = max(np.floor(pair_turning(yarn["beta_fast"])), 0)
    high = min(np.ceil(pair_turning(yarn["beta_slow"])), rope - 1)
    ramp = np.clip((pairs - low) / max(high - low, 0.001), 0.0, 1.0)
    return plain * (1 - ramp) + plain / yarn["factor"] * ramp


def yarn_magnitude(yarn, key):
    """m(yarn[key]) = 0.1 yarn[key] ln(factor) + 1; 1 without YaRN."""
    if not yarn or yarn["factor"] <= 1:
        return 1.0
    return 0.1 * yarn[key] * np.log(yarn["factor"]) + 1.0


def reference_loss(config, params, feed):
    """The mean next-token cross-entropy of the forward pass in float32,
    from the module docstring's equations line for line, one sequence at
    a time; each block and the head are a jax.checkpoint, so jax.grad
    keeps one block's activations at a time. Independent of the program's
    ops: the sweeps are a Python loop over whole [T, n, n] arrays with
    sums along an axis (the program works entry by entry, no reduction),
    the mixes are einsums; latent attention is a masked softmax one head
    at a time at the published 192 | 128, no lanes of padding, the
    rotation written from the angle formula with YaRN's blend; the
    experts are a scan over the held experts with a mask.

    `params`: the trainable parameters in the order the program created
    them: embedding; per block, for the attention sublayer phi, b, alpha,
    norm_1, W_qa, the query latent's norm, W_qb, W_kva, the key/value
    latent's norm, W_kvb, W_o; for the feed-forward sublayer phi, b,
    alpha, norm_2 and then W_g, W_u, W_d (a dense block) or the router
    [D, E], the held experts' G, U [held, D, F] and D [held, F, D] and
    the shared expert's W_g, W_u, W_d; the final norm's weight and the
    head. The router's selection bias is a buffer, not among them: taken
    as the zeros the configuration assumes."""
    import jax
    import jax.numpy as jnp

    s = _sizes(config)
    eps, heads, nope, rope, vd, n = (s["eps"], s["heads"], s["nope"],
                                     s["rope"], s["vd"], s["n"])
    inv_freq = yarn_frequencies(rope, s["theta"], s["yarn"]).astype(
        np.float32)
    turn_scale = np.float32(yarn_magnitude(s["yarn"], "mscale")
                            / yarn_magnitude(s["yarn"], "mscale_all_dim"))
    score_scale = np.float32(yarn_magnitude(s["yarn"], "mscale_all_dim") ** 2
                             / np.sqrt(nope + rope))

    def rms(x, w):
        return w * x * jax.lax.rsqrt((x ** 2).mean(-1, keepdims=True) + eps)

    def rotate(x):
        """x [t, ..., rope]: the pair (i, i + rope/2) turned by t *
        omega_i, cosines and sines times YaRN's ratio."""
        angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
        angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
        cos, sin = turn_scale * jnp.cos(angle), turn_scale * jnp.sin(angle)
        a, b = x[..., :rope // 2], x[..., rope // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def mla(x, w_qa, qn, w_qb, w_kva, kvn, w_kvb, w_o):
        t = x.shape[0]
        q = (rms(x @ w_qa, qn) @ w_qb).reshape(t, heads, nope + rope)
        kva = x @ w_kva
        k_rope = rotate(kva[:, s["kvr"]:])                  # one head
        kv = (rms(kva[:, :s["kvr"]], kvn) @ w_kvb).reshape(
            t, heads, nope + vd)
        q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:])], -1)
        causal = jnp.tril(jnp.ones((t, t), bool))

        @jax.checkpoint
        def head(args):
            q_j, kv_j = args
            k_j = jnp.concatenate([kv_j[:, :nope], k_rope], -1)
            scores = (q_j @ k_j.T) * score_scale
            prob = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
            return prob @ kv_j[:, nope:]

        out = jax.lax.map(head, (q.transpose(1, 0, 2), kv.transpose(1, 0, 2)))
        return out.transpose(1, 0, 2).reshape(t, heads * vd) @ w_o

    def gated(x, w_g, w_u, w_d):
        return (jax.nn.silu(x @ w_g) * (x @ w_u)) @ w_d

    def experts(x, w_r, gate_w, up, down, s_g, s_u, s_d):
        scores = jax.nn.sigmoid(x @ w_r)                    # [t, experts]
        top, idx = jax.lax.top_k(scores, s["top_k"])
        weight = s["scaling"] * top / (
            top.sum(-1, keepdims=True) + 1e-20
            if config["norm_topk_prob"] else 1.0)

        @jax.checkpoint
        def share_of(expert):
            e, g_e, u_e, d_e = expert
            mine = (weight * (idx == s["offset"] + e)).sum(-1, keepdims=True)
            return mine * gated(x, g_e, u_e, d_e)

        def add_expert(out, expert):
            return out + share_of(expert), None

        return jax.lax.scan(add_expert, gated(x, s_g, s_u, s_d),
                            (jnp.arange(s["held"]), gate_w, up, down))[0]

    def sinkhorn(m):
        """m [t, n, n] -> hc_sinkhorn_iters sweeps: the columns over
        their sums + hc_eps, then the rows."""
        for _ in range(s["iters"]):
            m = m / (m.sum(-2, keepdims=True) + s["hc_eps"])
            m = m / (m.sum(-1, keepdims=True) + s["hc_eps"])
        return m

    def hyper(x, phi, b, alpha, norm_w, f, weights):
        """One sublayer behind its hyper-connection: x [t, n, C]."""
        t = x.shape[0]
        flat = x.reshape(t, n * s["d"])
        u = flat * jax.lax.rsqrt((flat ** 2).mean(-1, keepdims=True) + eps)
        z = u @ phi
        h_pre = jax.nn.sigmoid(alpha[0] * z[:, :n] + b[:n])
        h_post = 2.0 * jax.nn.sigmoid(alpha[1] * z[:, n:2 * n] + b[n:2 * n])
        h_res = sinkhorn(jnp.exp(jnp.clip(
            (alpha[2] * z[:, 2 * n:] + b[2 * n:]).reshape(t, n, n),
            s["clamp"][0], s["clamp"][1])))
        y = f(rms(jnp.einsum("tj,tjc->tc", h_pre, x), norm_w), *weights)
        return jnp.einsum("tij,tjc->tic", h_res, x) \
            + h_post[:, :, None] * y[:, None, :]

    def block(dense):
        first = _HYPER_PARAMS + _ATTENTION_PARAMS

        @jax.checkpoint
        def run(x, weights):
            x = hyper(x, *weights[:_HYPER_PARAMS], mla,
                      weights[_HYPER_PARAMS:first])
            return hyper(x, *weights[first:first + _HYPER_PARAMS],
                         gated if dense else experts,
                         weights[first + _HYPER_PARAMS:])
        return run

    @jax.checkpoint
    def nll_sum(x, norm_w, w_head, labels):
        logp = jax.nn.log_softmax(rms(x.sum(1), norm_w) @ w_head, axis=-1)
        return -jnp.take_along_axis(logp, labels[:, None], axis=-1).sum()

    def sequence_loss_sum(one):
        tok, lab = one
        it = iter(params)
        x = jnp.repeat(next(it)[tok][:, None, :], n, axis=1)    # [T, n, C]
        for i in range(s["layers"]):
            dense = i < s["dense"]
            count = 2 * _HYPER_PARAMS + _ATTENTION_PARAMS + _FFN_PARAMS[dense]
            x = block(dense)(x, [next(it) for _ in range(count)])
        return nll_sum(x, next(it), next(it), lab)

    with jax.default_matmul_precision("highest"):
        feeds = tuple(jnp.asarray(feed[name]) for name in FEEDS)
        return jax.lax.map(sequence_loss_sum, feeds).sum() / feeds[0].size
