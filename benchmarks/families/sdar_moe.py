"""Family `sdar_moe`: a Qwen3-MoE decoder (grouped-query attention with
QK-norm and rotary embedding, a softmax top-k router over gated experts,
no shared expert) trained by diffusion over blocks of tokens
(SDAR-30B-A3B-Chat; arXiv:2510.06303, arXiv:2503.09573) through
paddle_tpu.models.block_diffusion_moe_lm, with the sizes read from the
configuration file; reference_loss() is the same model in plain jax.numpy
float32, written from the layer equations (ISSUE 42, section 1) and not
from the program's ops.

The configuration is one chip's share of a deployment (its `deployment`):
`num_experts` experts of `num_experts_published` are held here from
`expert_offset` on, the router keeps its published width, and what the
absent experts would add is left out, in the program and in the reference
alike (no shared expert: a token none of whose choices is held here gets
zero from the layer); `vocab_size` rows of the vocabulary, the last of
them the mask token; the first `num_hidden_layers` blocks.

`router_balance_rate` (`assumed`): only held experts lower the loss here,
so the cut's router walks towards them, which no rank of a deployment
sees (there every expert answers). After each step's update every
router's selection bias therefore moves against the load the step saw
over all the published experts (models.balance_routers, op
moe_balance_bias: arXiv:2408.15664). The bias starts at zero and moves the
choice only, so the first step, which reference_loss() is held to, has
none of it. `embedding_std` (`assumed`): the embedding's N(0, std).

The input pipeline makes the noise: make_batch() draws, a block of
`block_length` positions, a mask rate p = (1 - eps) u + eps, u ~ U(0, 1),
masks each position of the block with probability p, and feeds the clean
ids, the noised ids and the weight m / p a position. An item is a data
token: the model runs two positions for it (its noised and its clean
copy) and the rate is not doubled for that.
"""

import numpy as np


def _sizes(config):
    """The model's sizes under the names this file uses."""
    return dict(
        d=config["hidden_size"], layers=config["num_hidden_layers"],
        heads=config["num_attention_heads"],
        kv=config["num_key_value_heads"], hd=config["head_dim"],
        theta=config["rope_theta"],
        experts=config["num_experts_published"], held=config["num_experts"],
        offset=config["expert_offset"], top_k=config["num_experts_per_tok"],
        f=config["moe_intermediate_size"], eps=config["rms_norm_eps"],
        v=config["vocab_size"], t=config["sequence_length"],
        block=config["block_length"])


FEEDS = ("tok", "noisy", "weight")


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
        tok, noisy, weight = (
            fluid.layers.data(name=name, shape=[-1, s["t"]], dtype=dtype,
                              append_batch_size=False)
            for name, dtype in zip(FEEDS, ("int64", "int64", "float32")))
        loss = models.block_diffusion_moe_lm(
            tok, noisy, weight, vocab_size=s["v"], hidden_size=s["d"],
            num_hidden_layers=s["layers"], num_attention_heads=s["heads"],
            num_key_value_heads=s["kv"], head_dim=s["hd"],
            num_experts=s["experts"], num_experts_per_tok=s["top_k"],
            moe_intermediate_size=s["f"], block_length=s["block"],
            experts_held=s["held"], expert_offset=s["offset"],
            norm_topk_prob=config["norm_topk_prob"], rope_theta=s["theta"],
            epsilon=s["eps"],
            residual_layers=config["num_hidden_layers_published"],
            use_flash=config["use_flash"],
            embedding_std=config["embedding_std"])
        opt = fluid.amp.decorate(
            fluid.optimizer.Adam(learning_rate=config["learning_rate"],
                                 beta1=config["adam_beta1"],
                                 beta2=config["adam_beta2"],
                                 epsilon=config["adam_epsilon"]),
            level=config["amp_level"])
        opt.minimize(loss, startup_program=startup)
        if config["router_balance_rate"]:
            models.balance_routers(main, config["router_balance_rate"])
    return main, startup, loss


def make_batch(config, batch, rng):
    """One host batch of `batch` sequences of L ids: `tok` uniform over
    the vocabulary's slice less its last row (the mask token's); a mask
    rate a block, p = (1 - eps) u + eps with u ~ U(0, 1); every position
    masked with its block's p; `noisy` the ids with the masked ones
    replaced by the mask token, `weight` m / p in float32 (0 where the id
    was kept)."""
    s = _sizes(config)
    eps = config["mask_epsilon"]
    tok = rng.integers(0, s["v"] - 1, (batch, s["t"])).astype(np.int32)
    u = rng.random((batch, s["t"] // s["block"]))
    p = np.repeat((1.0 - eps) * u + eps, s["block"], axis=1)
    masked = rng.random((batch, s["t"])) < p
    return {"tok": tok,
            "noisy": np.where(masked, s["v"] - 1, tok).astype(np.int32),
            "weight": (masked / p).astype(np.float32)}


def items_per_batch(feed):
    """An item is a data token (the model runs two positions for it)."""
    return int(feed["tok"].size)


def live_pairs(length, block):
    """(query, key) pairs the mask of one sequence leaves alive: a noisy
    query of block b sees `block` noisy keys and b * block clean ones, a
    clean query (b + 1) * block clean ones: 2 * block * (b + 1) a
    position pair, L (L + block) in all, where the [2L, 2L] square has
    4 L^2."""
    return length * (length + block)


def part_flops_per_item(config):
    """{part: forward FLOPs one DATA token needs in one such part}, its
    two positions counted where both run. Nothing recomputed, nothing for
    experts held elsewhere. projections: W_q, W_k, W_v, W_o on two
    positions. attention: scores and values at the mask's live pairs,
    L + block a token. experts: the router on two positions and the
    EXPECTED rows routed here, top_k x held / experts of a position's,
    three maps each. head: the sliced output head, on the noisy position
    alone."""
    s = _sizes(config)
    d, hd = s["d"], s["hd"]
    maps = d * hd * (2 * s["heads"] + 2 * s["kv"])
    return {
        "projections": 2 * 2 * maps,
        "attention": 4 * (live_pairs(s["t"], s["block"]) / s["t"])
        * s["heads"] * hd,
        "experts": 2 * (2 * d * s["experts"] + s["top_k"] * s["held"]
                        / s["experts"] * 6 * d * s["f"]),
        "head": 2 * d * s["v"]}


def required_flops_per_item(config):
    """FLOPs one data token needs in a train step (forward x 3)."""
    per = part_flops_per_item(config)
    return 3.0 * (config["num_hidden_layers"] * (
        per["projections"] + per["attention"] + per["experts"])
        + per["head"])


def attention_ops_per_step(config):
    """Attention ops of a step: one a block."""
    return config["num_hidden_layers"]


def attention_kernel_cost(config, tokens=None):
    """(FLOPs, bytes) of ONE attention op of a train step over one
    sequence of `tokens` data tokens (default the configuration's), both
    streams, forward and backward, AT THE LIVE PAIRS ONLY and whatever
    implements it: six products of live_pairs x head_dim x heads (the
    scores and P V forward; dV, dP, dQ and dK backward; scores computed
    again are time and not work, and a kernel that walks dead tiles
    lowers its own share); q, o and dO read and o and dQ written once at
    the query's heads, k and v read and dK and dV written once at the
    key/value heads, in bf16, 2 x tokens positions each."""
    s = _sizes(config)
    t = tokens or s["t"]
    flops = 6 * 2.0 * live_pairs(t, s["block"]) * s["hd"] * s["heads"]
    return flops, 2.0 * (2 * t) * s["hd"] * (5 * s["heads"] + 4 * s["kv"])


def expert_layers(config):
    """Expert layers of a step: every block."""
    return config["num_hidden_layers"]


def expert_product_cost(config, rows):
    """(FLOPs, bytes) of one train step's grouped expert products in ONE
    expert layer when `rows` (position, slot) pairs were routed to the
    held experts: as glm4_moe_lite.expert_product_cost (nine products of
    rows x d x f; each reads its two operands and writes its result once
    in bf16, the held experts' weights once a product)."""
    s = _sizes(config)
    d, f, held = s["d"], s["f"], s["held"]
    flops = 9 * 2.0 * rows * d * f
    return flops, 9 * 2.0 * (rows * d + rows * f + held * d * f)


def reference_loss(config, params, feed):
    """sum_i weight_i * nll_i / (B L) of the forward pass in float32, from
    the layer equations (ISSUE 42, section 1), one sequence at a time;
    each block and the head are a jax.checkpoint, and so is each head of
    attention, so jax.grad keeps one block's activations and one head's
    [2L, 2L] scores at a time. Independent of the program's ops: the two
    streams are 2L rows, noisy first; attention is a masked softmax one
    head at a time over an explicit [2L, 2L] boolean mask built from the
    rows' streams and block ids; the rotation is written from the angle
    formula; the experts are a scan over the held experts with a mask;
    the router is a softmax and jax.lax.top_k.

    `params`: the trainable parameters in the order the program created
    them: embedding; per block norm_1, W_q [D, heads * hd], W_k and W_v
    [D, kv * hd], the query heads' norm [hd], the key heads' norm [hd],
    W_o, norm_2, the router [D, E], the held experts' G, U [held, D, F]
    and D [held, F, D]; the final norm's weight and the head. The
    router's selection bias is a buffer, not among them: taken as the
    zeros the configuration assumes."""
    import jax
    import jax.numpy as jnp

    s = _sizes(config)
    eps, heads, kv, hd, length, bd = (s["eps"], s["heads"], s["kv"], s["hd"],
                                      s["t"], s["block"])
    # theta^(-2j/hd) for the pairs (j, j + hd/2)
    inv_freq = np.power(float(s["theta"]),
                        -2.0 * np.arange(hd // 2) / hd).astype(np.float32)
    position = np.tile(np.arange(length), 2)           # in its own stream
    noisy_row = np.arange(2 * length) < length
    block_of = position // bd
    q_noisy, k_noisy = noisy_row[:, None], noisy_row[None, :]
    q_block, k_block = block_of[:, None], block_of[None, :]
    keep = jnp.where(
        q_noisy & k_noisy, q_block == k_block,
        jnp.where(q_noisy & ~k_noisy, q_block > k_block,
                  jnp.where(~q_noisy & ~k_noisy, q_block >= k_block, False)))

    def rms(x, w):
        return w * x * jax.lax.rsqrt((x ** 2).mean(-1, keepdims=True) + eps)

    def rotate(x):
        """x [rows, n, hd]: the pair (j, j + hd/2) turned by the row's
        position times theta^(-2j/hd)."""
        angle = (jnp.asarray(position, jnp.float32)[:, None]
                 * inv_freq)[:, None, :]
        a, b = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                                b * jnp.cos(angle) + a * jnp.sin(angle)], -1)

    def attention(a, w_q, w_k, w_v, q_norm, k_norm, w_o):
        rows = a.shape[0]
        q = rotate(rms((a @ w_q).reshape(rows, heads, hd), q_norm))
        k = rotate(rms((a @ w_k).reshape(rows, kv, hd), k_norm))
        v = (a @ w_v).reshape(rows, kv, hd)

        @jax.checkpoint
        def head(j):
            q_j, k_j, v_j = q[:, j], k[:, j // (heads // kv)], \
                v[:, j // (heads // kv)]
            scores = q_j @ k_j.T / np.sqrt(hd)
            prob = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), -1)
            return prob @ v_j

        out = jax.lax.map(head, jnp.arange(heads))        # [heads, rows, hd]
        return out.transpose(1, 0, 2).reshape(rows, heads * hd) @ w_o

    def gated(x, w_g, w_u, w_d):
        return (jax.nn.silu(x @ w_g) * (x @ w_u)) @ w_d

    def experts(x, w_r, gate_w, up, down):
        prob = jax.nn.softmax(x @ w_r, axis=-1)             # [rows, experts]
        top, idx = jax.lax.top_k(prob, s["top_k"])
        weight = top / (top.sum(-1, keepdims=True)
                        if config["norm_topk_prob"] else 1.0)

        def add_expert(out, expert):
            e, g_e, u_e, d_e = expert
            mine = (weight * (idx == s["offset"] + e)).sum(-1, keepdims=True)
            return out + mine * gated(x, g_e, u_e, d_e), None

        return jax.lax.scan(add_expert, jnp.zeros_like(x),
                            (jnp.arange(s["held"]), gate_w, up, down))[0]

    @jax.checkpoint
    def block(x, weights):
        norm_1, *attn = weights[:7]
        norm_2, *ffn = weights[7:]
        x = x + attention(rms(x, norm_1), *attn)
        return x + experts(rms(x, norm_2), *ffn)

    @jax.checkpoint
    def weighted_nll_sum(x, norm_w, w_head, labels, weight):
        logp = jax.nn.log_softmax(rms(x, norm_w) @ w_head, axis=-1)
        nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
        return (weight * nll).sum()

    def sequence_loss_sum(one):
        tok, noisy, weight = one
        it = iter(params)
        x = next(it)[jnp.concatenate([noisy, tok])]        # [2L, D]
        for _ in range(s["layers"]):
            x = block(x, [next(it) for _ in range(12)])
        return weighted_nll_sum(x[:length], next(it), next(it), tok, weight)

    with jax.default_matmul_precision("highest"):
        feeds = tuple(jnp.asarray(feed[n]) for n in FEEDS)
        return jax.lax.map(sequence_loss_sum, feeds).sum() / feeds[0].size
