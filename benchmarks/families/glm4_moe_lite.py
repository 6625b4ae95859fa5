"""Family `glm4_moe_lite`: latent-attention (MLA) mixture-of-experts
decoder with a multi-token-prediction module (GLM-4.7-Flash; the block of
DeepSeek-V3, arXiv:2412.19437) through paddle_tpu.models.mla_moe_lm, with
the sizes read from the configuration file; reference_loss() is the same
model in plain jax.numpy float32, written from the layer equations
(ISSUE 33, section 1) and not from the program's ops.

The configuration is one chip's share of a deployment (its `deployment`):
`n_routed_experts` experts of `n_routed_experts_published` are held here
from `expert_offset` on, the router keeps its published width, and what
the absent experts would add is left out, in the program and in the
reference alike; `vocab_size` rows of the vocabulary; the first
`num_hidden_layers` blocks, and the prediction module behind them.
"""

import numpy as np


def _sizes(config):
    """The model's sizes under the names this file uses."""
    return dict(
        d=config["hidden_size"], layers=config["num_hidden_layers"],
        dense=config["first_k_dense_replace"],
        mtp=config["num_nextn_predict_layers"],
        heads=config["num_attention_heads"], qr=config["q_lora_rank"],
        kvr=config["kv_lora_rank"], nope=config["qk_nope_head_dim"],
        rope=config["qk_rope_head_dim"], vd=config["v_head_dim"],
        theta=config["rope_theta"], inter=config["intermediate_size"],
        experts=config["n_routed_experts_published"],
        held=config["n_routed_experts"], offset=config["expert_offset"],
        top_k=config["num_experts_per_tok"],
        f=config["moe_intermediate_size"],
        fs=config["n_shared_experts"] * config["moe_intermediate_size"],
        eps=config["rms_norm_eps"], v=config["vocab_size"],
        t=config["sequence_length"])


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
        tok, lab, lab2 = (
            fluid.layers.data(name=name, shape=[-1, s["t"]], dtype="int64",
                              append_batch_size=False)
            for name in ("tok", "lab", "lab2"))
        loss = models.mla_moe_lm(
            tok, lab, lab2, vocab_size=s["v"], hidden_size=s["d"],
            num_hidden_layers=s["layers"], first_k_dense_replace=s["dense"],
            num_attention_heads=s["heads"], q_lora_rank=s["qr"],
            kv_lora_rank=s["kvr"], qk_nope_head_dim=s["nope"],
            qk_rope_head_dim=s["rope"], v_head_dim=s["vd"],
            intermediate_size=s["inter"], n_routed_experts=s["experts"],
            num_experts_per_tok=s["top_k"], moe_intermediate_size=s["f"],
            n_shared_experts=config["n_shared_experts"],
            experts_held=s["held"], expert_offset=s["offset"],
            routed_scaling_factor=config["routed_scaling_factor"],
            norm_topk_prob=config["norm_topk_prob"], rope_theta=s["theta"],
            num_nextn_predict_layers=s["mtp"],
            mtp_loss_weight=config["mtp_loss_weight"], epsilon=s["eps"],
            residual_layers=config["num_hidden_layers_published"],
            use_flash=config["use_flash"])
        opt = fluid.amp.decorate(
            fluid.optimizer.Adam(learning_rate=config["learning_rate"],
                                 beta1=config["adam_beta1"],
                                 beta2=config["adam_beta2"],
                                 epsilon=config["adam_epsilon"]),
            level=config["amp_level"])
        opt.minimize(loss, startup_program=startup)
    return main, startup, loss


def make_batch(config, batch, rng):
    """One host batch of `batch` sequences of T + 2 int32 ids drawn from
    the vocabulary's slice: `tok` the first T, `lab` the ids one position
    on, `lab2` two on. Every position has both labels: no loss ignores
    one."""
    t = config["sequence_length"]
    ids = rng.integers(0, config["vocab_size"], (batch, t + 2)).astype(
        np.int32)
    return {"tok": ids[:, :t], "lab": ids[:, 1:t + 1], "lab2": ids[:, 2:]}


def items_per_batch(feed):
    """An item is a token position that gets a main loss."""
    return int(feed["tok"].size)


def part_flops_per_item(config):
    """{part: forward FLOPs one token needs in one such part}. Nothing
    recomputed, nothing for experts held elsewhere.
    mla: the five projections and causal attention at half the T x T
    scores, at widths nope + rope (scores) and v_head_dim (values).
    dense: the gated feed-forward's three maps. experts: the router, the
    gated shared expert, and the EXPECTED rows routed here, top_k x held
    / experts of a token's, three maps each. eh_proj: the prediction
    module's [2 d, d] map. head: the sliced output head."""
    s = _sizes(config)
    d, t, heads = s["d"], s["t"], s["heads"]
    qk = s["nope"] + s["rope"]
    projections = 2 * (d * s["qr"] + s["qr"] * heads * qk
                       + d * (s["kvr"] + s["rope"])
                       + s["kvr"] * heads * (s["nope"] + s["vd"])
                       + heads * s["vd"] * d)
    return {
        "mla": projections + t * heads * (qk + s["vd"]),
        "dense": 6 * d * s["inter"],
        "experts": (2 * d * s["experts"] + 6 * d * s["fs"]
                    + s["top_k"] * s["held"] / s["experts"] * 6 * d * s["f"]),
        "eh_proj": 2 * 2 * d * d,
        "head": 2 * d * s["v"]}


def required_flops_per_item(config):
    """FLOPs one token needs in a train step (forward x 3): the main
    model's blocks and head, and each prediction module's projection,
    expert block and head product."""
    s, per = _sizes(config), part_flops_per_item(config)
    block = per["mla"] + per["experts"]
    return 3.0 * (s["dense"] * (per["mla"] + per["dense"])
                  + (s["layers"] - s["dense"]) * block + per["head"]
                  + s["mtp"] * (per["eh_proj"] + block + per["head"]))


def attention_ops_per_step(config):
    """Attention ops of a step: one a block, the prediction modules' too."""
    return config["num_hidden_layers"] + config["num_nextn_predict_layers"]


def attention_kernel_cost(config, tokens=None):
    """(FLOPs, bytes) of ONE attention op of a train step over one
    sequence of `tokens` positions (default the configuration's), forward
    and backward, in its expanded form [T, heads, nope + rope]: six
    causal products of T^2 x width x heads, each at half the square (the
    scores and P V forward; dV, dP, dQ and dK backward; the score
    product the backward kernels run again is time and not work); q, k,
    v, o and dO read and o, dQ, dK, dV written once, in bf16."""
    s = _sizes(config)
    t = tokens or s["t"]
    width = s["nope"] + s["rope"]
    assert width == s["vd"], "priced for equal score and value widths"
    flops = 6 * 2.0 * (t * t / 2) * width * s["heads"]
    return flops, 9 * 2.0 * t * s["heads"] * width


def expert_layers(config):
    """Expert layers of a step: every block behind the leading dense
    ones, and each prediction module's own block."""
    return (config["num_hidden_layers"] - config["first_k_dense_replace"]
            + config["num_nextn_predict_layers"])


def expert_product_cost(config, rows):
    """(FLOPs, bytes) of one train step's grouped expert products in ONE
    expert layer when `rows` (token, slot) pairs were routed to the held
    experts: the gate, up and down products forward, and for each its two
    backward products (9 products of rows x d x f), nothing recomputed.
    Bytes as nemotron_h.expert_product_cost counts them: each product
    reads its two operands and writes its result once, in bf16, the held
    experts' weights once a product."""
    s = _sizes(config)
    d, f, held = s["d"], s["f"], s["held"]
    flops = 9 * 2.0 * rows * d * f
    per_product = 2.0 * (rows * d + rows * f + held * d * f)
    return flops, 9 * per_product


def reference_loss(config, params, feed):
    """L_main + mtp_loss_weight * L_mtp of the forward pass in float32,
    from the layer equations (ISSUE 33, section 1), one sequence at a
    time; each block and each head is a jax.checkpoint, so jax.grad keeps
    one block's activations at a time. Independent of the program's ops:
    attention is a masked softmax one head at a time over keys assembled
    per head, the rotation is written from the angle formula, the experts
    are a loop over the held experts with a mask.

    `params`: the trainable parameters in the order the program created
    them: embedding; per block norm_1, W_qa, the query latent's norm,
    W_qb, W_kva, the key/value latent's norm, W_kvb, W_o, norm_2 and then
    W_g, W_u, W_d (a dense block) or the router [D, E], the held experts'
    G, U [held, D, F] and D [held, F, D] and the shared expert's W_g,
    W_u, W_d; the final norm's weight and the head; then the prediction
    module: the embedding's norm, the hidden state's norm, W_eh [2 D, D],
    one block of the expert kind, its final norm. The embedding and the
    head appear once: the module reads the main model's. The router's
    selection bias is a buffer, not among them: taken as the zeros the
    configuration assumes."""
    import jax
    import jax.numpy as jnp

    s = _sizes(config)
    eps, heads, nope, rope, vd = (s["eps"], s["heads"], s["nope"], s["rope"],
                                  s["vd"])
    # theta^(-2i/rope) for the pairs (i, i + rope/2)
    inv_freq = np.power(float(s["theta"]),
                        -2.0 * np.arange(rope // 2) / rope).astype(np.float32)

    def rms(x, w):
        return w * x * jax.lax.rsqrt((x ** 2).mean(-1, keepdims=True) + eps)

    def rotate(x):
        """x [t, ..., rope]: the pair (i, i + rope/2) turned by t *
        theta^(-2i/rope)."""
        angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
        angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
        a, b = x[..., :rope // 2], x[..., rope // 2:]
        return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                                b * jnp.cos(angle) + a * jnp.sin(angle)], -1)

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
            scores = q_j @ k_j.T / np.sqrt(nope + rope)
            prob = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
            return prob @ kv_j[:, nope:]

        out = jax.lax.map(head, (q.transpose(1, 0, 2), kv.transpose(1, 0, 2)))
        return out.transpose(1, 0, 2).reshape(t, heads * vd) @ w_o

    def gated(x, w_g, w_u, w_d):
        return (jax.nn.silu(x @ w_g) * (x @ w_u)) @ w_d

    def experts(x, w_r, gate_w, up, down, s_g, s_u, s_d):
        scores = jax.nn.sigmoid(x @ w_r)                    # [t, experts]
        top, idx = jax.lax.top_k(scores, s["top_k"])
        weight = config["routed_scaling_factor"] * top / (
            top.sum(-1, keepdims=True) + 1e-20
            if config["norm_topk_prob"] else 1.0)

        def add_expert(out, expert):
            e, g_e, u_e, d_e = expert
            mine = (weight * (idx == s["offset"] + e)).sum(-1, keepdims=True)
            return out + mine * gated(x, g_e, u_e, d_e), None

        return jax.lax.scan(add_expert, gated(x, s_g, s_u, s_d),
                            (jnp.arange(s["held"]), gate_w, up, down))[0]

    @jax.checkpoint
    def block(x, weights):
        norm_1, *attn = weights[:8]
        norm_2, *ffn = weights[8:]
        x = x + mla(rms(x, norm_1), *attn)
        return x + (gated if len(ffn) == 3 else experts)(rms(x, norm_2), *ffn)

    @jax.checkpoint
    def nll_sum(x, norm_w, w_head, labels):
        logp = jax.nn.log_softmax(rms(x, norm_w) @ w_head, axis=-1)
        return -jnp.take_along_axis(logp, labels[:, None], axis=-1).sum()

    def sequence_loss_sums(triple):
        tok, lab, lab2 = triple
        it = iter(params)
        take = lambda n: [next(it) for _ in range(n)]       # noqa: E731
        table = next(it)
        x = table[tok]
        for i in range(s["layers"]):
            x = block(x, take(12 if i < s["dense"] else 16))
        norm_f, w_head = take(2)
        main = nll_sum(x, norm_f, w_head, lab)
        if not s["mtp"]:
            return main, jnp.zeros(())
        norm_e, norm_h, w_eh = take(3)
        u = jnp.concatenate([rms(table[lab], norm_e), rms(x, norm_h)],
                            -1) @ w_eh
        u = block(u, take(16))
        return main, nll_sum(u, next(it), w_head, lab2)

    with jax.default_matmul_precision("highest"):
        feeds = tuple(jnp.asarray(feed[n]) for n in ("tok", "lab", "lab2"))
        main, mtp = jax.lax.map(sequence_loss_sums, feeds)
        return (main.sum() + config["mtp_loss_weight"] * mtp.sum()) \
            / feeds[0].size
