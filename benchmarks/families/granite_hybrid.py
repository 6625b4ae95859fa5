"""Family `granite_hybrid`: dense hybrid Mamba-2 / attention decoder with a
gated feed-forward in every layer, the four muP multipliers and a tied
head (IBM Granite 4.0-H, `granitemoehybrid` with no routed expert) through
paddle_tpu.models.granite_hybrid_lm, with the sizes read from the
configuration file; reference_loss() is the same model in plain jax.numpy
float32, written from the layer equations and not from the program's ops.

The configuration is one pipeline stage of a deployment (its
`deployment`): the first `num_hidden_layers` layers of the published
`layer_types`, `vocab_size` rows of the vocabulary (tied: one matrix is
embedding and head), and both ends of the pipeline on this chip. With
`recompute` the program keeps the residual stream at each layer's input
and replays a layer's forward ops ahead of its gradient ops
(paddle_tpu/backward.py); the reference recomputes nothing it reports.
"""

import numpy as np


def _sizes(config):
    """The model's sizes under the names this file uses."""
    d, q_heads = config["hidden_size"], config["num_attention_heads"]
    kinds = config["layer_types"]
    assert len(kinds) == config["num_hidden_layers"]
    return dict(
        d=d, kinds=kinds, heads=config["mamba_n_heads"],
        p=config["mamba_d_head"], g=config["mamba_n_groups"],
        n=config["mamba_d_state"], k=config["mamba_d_conv"],
        q_heads=q_heads, kv_heads=config["num_key_value_heads"],
        hd=d // q_heads, f=config["shared_intermediate_size"],
        eps=config["rms_norm_eps"], v=config["vocab_size"],
        t=config["sequence_length"],
        # the chunk the scan is lowered with; the published one unless the
        # file says the kernels take another (the result is the same)
        chunk=config.get("scan_chunk", config["mamba_chunk_size"]))


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
        tok = fluid.layers.data(name="tok", shape=[-1, s["t"]],
                                dtype="int64", append_batch_size=False)
        lab = fluid.layers.data(name="lab", shape=[-1, s["t"]],
                                dtype="int64", append_batch_size=False)
        loss, kept = models.granite_hybrid_lm(
            tok, lab, vocab_size=s["v"], hidden_size=s["d"],
            layer_types=s["kinds"], mamba_n_heads=s["heads"],
            mamba_d_head=s["p"], mamba_n_groups=s["g"],
            mamba_d_state=s["n"], num_attention_heads=s["q_heads"],
            num_key_value_heads=s["kv_heads"],
            shared_intermediate_size=s["f"],
            embedding_multiplier=config["embedding_multiplier"],
            residual_multiplier=config["residual_multiplier"],
            attention_multiplier=config["attention_multiplier"],
            logits_scaling=config["logits_scaling"],
            mamba_d_conv=s["k"], mamba_chunk_size=s["chunk"],
            epsilon=s["eps"], use_flash=config["use_flash"],
            recompute=config.get("recompute", False))
        opt = fluid.amp.decorate(
            fluid.optimizer.Adam(learning_rate=config["learning_rate"],
                                 beta1=config["adam_beta1"],
                                 beta2=config["adam_beta2"],
                                 epsilon=config["adam_epsilon"]),
            level=config["amp_level"])
        opt.minimize(loss, startup_program=startup, checkpoints=kept)
    return main, startup, loss


def make_batch(config, batch, rng):
    """One host batch of `batch` sequences: int32 ids drawn from the
    vocabulary's slice, and as labels the ids one position on."""
    t = config["sequence_length"]
    ids = rng.integers(0, config["vocab_size"], (batch, t + 1))
    return {"tok": ids[:, :-1].astype(np.int32),
            "lab": ids[:, 1:].astype(np.int32)}


def items_per_batch(feed):
    """An item is a token position that gets a loss."""
    return int(feed["tok"].size)


def _scan_flops_per_item(s):
    """The chunked scan's four products forward, a token: scores C B^T
    per group and the masked product with x at half their chunk x chunk
    blocks, which is what causality needs; the chunk states; the entering
    state's read-out."""
    di, gn = s["heads"] * s["p"], s["g"] * s["n"]
    return s["chunk"] * gn + s["chunk"] * di + 4 * di * s["n"]


def layer_flops_per_item(config):
    """{part: forward FLOPs one token needs in one such part}: "mamba" and
    "attention" the two mixers (projections and the scan, or q, k, v, o
    and causal attention at half the T x T scores), "mlp" the gated
    feed-forward every layer has, "head" the sliced tied head. Nothing
    recomputed."""
    s = _sizes(config)
    d, t = s["d"], s["t"]
    di, gn = s["heads"] * s["p"], s["g"] * s["n"]
    qo, kv = s["q_heads"] * s["hd"], s["kv_heads"] * s["hd"]
    return {
        "mamba": (2 * d * (2 * di + 2 * gn + s["heads"]) + 2 * di * d
                  + _scan_flops_per_item(s)),
        "attention": 2 * d * (2 * qo + 2 * kv) + 2 * t * qo,
        "mlp": 6 * d * s["f"],
        "head": 2 * d * s["v"]}


def required_flops_per_item(config):
    """FLOPs one token needs in a train step: every product once forward
    and twice backward. A layer's replayed forward is time, not work."""
    per = layer_flops_per_item(config)
    return 3.0 * (sum(per[kind] + per["mlp"]
                      for kind in config["layer_types"]) + per["head"])


def scan_layers(config):
    """Mamba layers of a step."""
    return config["layer_types"].count("mamba")


def scan_cost(config, tokens):
    """(FLOPs, bytes) of one train step's selective scan in ONE Mamba
    layer over `tokens` positions: the four products forward and twice
    that backward; bytes: x, B, C, dt read and y written forward (bf16,
    dt float32), and backward the same operands and dy read and their
    four gradients written. One forward and one gradient: what a replay
    runs again is not counted."""
    s = _sizes(config)
    di, gn = s["heads"] * s["p"], s["g"] * s["n"]
    flops = 3.0 * tokens * _scan_flops_per_item(s)
    row = 2.0 * (di + 2 * gn) + 4.0 * s["heads"]       # x, B, C, dt
    return flops, tokens * ((row + 2.0 * di) + (2 * row + 2.0 * di))


MIXER_WEIGHTS = {"mamba": 8, "attention": 4}


def reference_loss(config, params, feed, head=None):
    """Mean next-token cross-entropy of the forward pass in float32, from
    the layer equations (ISSUE 49, section A), one sequence at a time;
    each residual branch is a jax.checkpoint, so jax.grad keeps one
    branch's activations at a time. Independent of the program's ops: the
    scan is the step-by-step recurrence (lax.scan over t, a
    jax.checkpoint around each block of steps), attention a masked
    softmax one query head at a time with the published multiplier on the
    scores, the feed-forward in blocks of tokens, the head the
    embedding's own matrix transposed.

    `params`: the trainable parameters in the order the program created
    them: the embedding (which is the head); per layer its first norm's
    weight, then "mamba": in_proj, conv filter [C, K] and bias, dt_bias,
    A_log, D, the gated norm's weight, out_proj; "attention": q, k, v, o;
    then the second norm's weight and the feed-forward's gate, up and down
    maps; last the final norm's weight. The four multipliers are read
    from `config` here and nowhere else in this function: a test sets one
    to 1 for the reference alone to show that the program applies it.
    `head`: the head's matrix [V, D] where a test unties it from the
    embedding to see the two gradients apart (default: the embedding)."""
    import jax
    import jax.numpy as jnp

    s = _sizes(config)
    eps, heads, p, g, n = s["eps"], s["heads"], s["p"], s["g"], s["n"]
    di, gn = heads * p, g * n
    residual = config["residual_multiplier"]
    block = 64          # steps of the recurrence inside one checkpoint
    rows = 1024         # tokens of the feed-forward inside one checkpoint

    def rms(x, w, groups=1):
        xg = x.reshape(x.shape[:-1] + (groups, -1))
        xg = xg * jax.lax.rsqrt((xg ** 2).mean(-1, keepdims=True) + eps)
        return xg.reshape(x.shape) * w

    def mamba(x, w_in, conv_w, conv_b, dt_bias, a_log, skip, norm_w, w_out):
        t = x.shape[0]
        proj = x @ w_in
        z, xbc, dt = proj[:, :di], proj[:, di:2 * di + 2 * gn], \
            proj[:, 2 * di + 2 * gn:]
        k = conv_w.shape[1]
        padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc])
        xbc = jax.nn.silu(conv_b + sum(
            padded[j:j + t] * conv_w[:, j] for j in range(k)))
        xs = xbc[:, :di].reshape(t, heads, p)
        # every head of a group reads the group's B and C
        b = jnp.repeat(xbc[:, di:di + gn].reshape(t, g, n), heads // g, 1)
        c = jnp.repeat(xbc[:, di + gn:].reshape(t, g, n), heads // g, 1)
        dt = jax.nn.softplus(dt + dt_bias)                    # [t, heads]
        decay = jnp.exp(dt * -jnp.exp(a_log))

        def step(h, inp):
            x_t, b_t, c_t, dt_t, a_t = inp
            h = a_t[:, None, None] * h + (dt_t[:, None] * x_t)[:, :, None] \
                * b_t[:, None, :]
            return h, jnp.einsum("hpn,hn->hp", h, c_t)

        @jax.checkpoint
        def steps(h, inp):
            return jax.lax.scan(step, h, inp)

        pad = (-t) % block
        seq = [jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
               .reshape((-1, block) + v.shape[1:])
               for v in (xs, b, c, dt, decay)]
        _, y = jax.lax.scan(steps, jnp.zeros((heads, p, n)), tuple(seq))
        y = y.reshape(-1, heads, p)[:t] + skip[:, None] * xs
        y = rms(y.reshape(t, di) * jax.nn.silu(z), norm_w, groups=g)
        return y @ w_out

    def attention(x, w_q, w_k, w_v, w_o):
        t, hd = x.shape[0], s["hd"]
        q = (x @ w_q).reshape(t, s["q_heads"], hd).transpose(1, 0, 2)
        k = (x @ w_k).reshape(t, s["kv_heads"], hd).transpose(1, 0, 2)
        v = (x @ w_v).reshape(t, s["kv_heads"], hd).transpose(1, 0, 2)
        causal = jnp.tril(jnp.ones((t, t), bool))
        per_kv = s["q_heads"] // s["kv_heads"]

        @jax.checkpoint
        def head(args):
            q_h, j = args
            scores = config["attention_multiplier"] * (q_h @ k[j // per_kv].T)
            prob = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
            return prob @ v[j // per_kv]

        out = jax.lax.map(head, (q, jnp.arange(s["q_heads"])))
        return out.transpose(1, 0, 2).reshape(t, -1) @ w_o

    def mlp(x, w_g, w_u, w_d):
        @jax.checkpoint
        def some(x):
            return (jax.nn.silu(x @ w_g) * (x @ w_u)) @ w_d

        t = x.shape[0]
        pad = (-t) % rows
        out = jax.lax.map(some, jnp.pad(x, ((0, pad), (0, 0)))
                          .reshape(-1, rows, x.shape[1]))
        return out.reshape(-1, x.shape[1])[:t]

    mixers = {"mamba": mamba, "attention": attention}

    def branch(fn, x, norm_w, weights):
        return jax.checkpoint(
            lambda x, norm_w, weights:
            x + residual * fn(rms(x, norm_w), *weights))(x, norm_w, weights)

    def sequence_loss_sum(pair):
        tok, lab = pair
        it = iter(params)
        table = next(it)
        x = config["embedding_multiplier"] * table[tok]
        for kind in s["kinds"]:
            norm_w = next(it)
            weights = [next(it) for _ in range(MIXER_WEIGHTS[kind])]
            x = branch(mixers[kind], x, norm_w, weights)
            x = branch(mlp, x, next(it), [next(it) for _ in range(3)])

        @jax.checkpoint
        def loss_sum(x, norm_w, table):
            logits = rms(x, norm_w) @ table.T / config["logits_scaling"]
            logp = jax.nn.log_softmax(logits, axis=-1)
            return -jnp.take_along_axis(logp, lab[:, None], axis=-1).sum()

        return loss_sum(x, next(it), table if head is None else head)

    with jax.default_matmul_precision("highest"):
        tok, lab = jnp.asarray(feed["tok"]), jnp.asarray(feed["lab"])
        return jax.lax.map(sequence_loss_sum, (tok, lab)).sum() / tok.size
