"""Family `gpt2`: decoder-only pre-LN transformer LM (Radford et al. 2019)
through paddle_tpu.models.transformer_lm, as chip_smoke._build_lm builds
it, with the sizes read from the configuration file; reference_loss() is
the same model in plain jax.numpy float32.
"""

import numpy as np

def build(config):
    """(main, startup, loss) of one AMP train step. The programs'
    random_seed is fixed: the run's seed reaches the weights through the
    executor's PRNG counter (traffic/train_steps.py says why)."""
    import paddle_tpu as fluid
    from paddle_tpu import models
    from paddle_tpu.framework import unique_name

    seqlen = config["n_positions"]
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with unique_name.guard(), fluid.program_guard(main, startup):
        tok = fluid.layers.data(name="tok", shape=[-1, seqlen],
                                dtype="int64", append_batch_size=False)
        lab = fluid.layers.data(name="lab", shape=[-1, seqlen],
                                dtype="int64", append_batch_size=False)
        loss = models.transformer_lm(
            tok, lab, vocab_size=config["vocab_size"],
            d_model=config["n_embd"], n_head=config["n_head"],
            n_layer=config["n_layer"],
            ffn_mult=config["n_inner"] // config["n_embd"],
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
    """One host batch of `batch` sequences: int32 ids, and as labels the
    ids one position on. batch x n_positions items (tokens)."""
    seqlen = config["n_positions"]
    ids = rng.integers(0, config["vocab_size"], (batch, seqlen + 1))
    return {"tok": ids[:, :-1].astype(np.int32),
            "lab": ids[:, 1:].astype(np.int32)}


def items_per_batch(feed):
    """An item is a token position that gets a loss."""
    return int(feed["tok"].size)


def required_flops_per_item(config):
    """FLOPs one token needs in a train step (forward x 3). Per layer the
    q, k, v, o and two MLP matmuls (2 x 12 d^2 with n_inner = 4d) and
    causal attention, which needs half of the full T x T scores and
    values (2 x 2 x T x d / 2); plus the output head. Recomputation, the
    masked half of the scores, layer norms and softmax count nothing."""
    d, t = config["n_embd"], config["n_positions"]
    per_layer = 2 * (4 * d * d + 2 * d * config["n_inner"]) + 2 * t * d
    fwd = config["n_layer"] * per_layer + 2 * config["vocab_size"] * d
    return 3.0 * fwd


def reference_loss(config, params, feed):
    """Mean next-token cross-entropy of the forward pass in float32, one
    sequence at a time (sequences do not interact, and the float32 logits
    of a whole batch would not fit beside the training state). Each
    sequence is a jax.checkpoint, so that jax.grad of this keeps one
    sequence's activations at a time and not the batch's.
    `params`: the trainable parameters in the order the program created
    them (token embedding, position embedding, then per block ln, q, k,
    v, o, ln, fc1, fc2 — weight [in, out] then bias — final ln, head)."""
    import jax
    import jax.numpy as jnp

    eps = config["layer_norm_epsilon"]
    n_head = config["n_head"]

    @jax.checkpoint
    def sequence_loss_sum(pair):
        tok, lab = pair
        it = iter(params)
        t = tok.shape[0]

        def ln(x):
            scale, bias = next(it), next(it)
            mean = x.mean(-1, keepdims=True)
            var = ((x - mean) ** 2).mean(-1, keepdims=True)
            return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias

        def proj(x):
            w, b = next(it), next(it)
            return x @ w + b

        x = next(it)[tok] + next(it)[:t]
        causal = jnp.tril(jnp.ones((t, t), bool))
        for _ in range(config["n_layer"]):
            h = ln(x)
            q, k, v = (proj(h).reshape(t, n_head, -1) for _ in range(3))
            s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(q.shape[-1])
            p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            x = x + proj(jnp.einsum("hqk,khd->qhd", p, v).reshape(t, -1))
            x = x + proj(jax.nn.gelu(proj(ln(x)), approximate=False))
        logp = jax.nn.log_softmax(proj(ln(x)), axis=-1)
        return -jnp.take_along_axis(logp, lab[:, None], axis=-1).sum()

    with jax.default_matmul_precision("highest"):
        tok, lab = jnp.asarray(feed["tok"]), jnp.asarray(feed["lab"])
        return jax.lax.map(sequence_loss_sum, (tok, lab)).sum() / tok.size
