"""Family `ouro`: a looped decoder, ONE stack of layers run
`total_ut_steps` times over ONE set of weights with an exit at the end of
every pass and a loss that the exits' gate mixes (ByteDance Ouro-2.6B;
arXiv:2510.25741), through paddle_tpu.models.looped_lm, with the sizes
read from the configuration file; reference_loss() is the same model in
plain jax.numpy float32, written from the layer equations (ISSUE 59) and
not from the program's ops.

The configuration is one pipeline stage of a deployment (its
`deployment`): the first `num_hidden_layers` of the published layers and
both ends of the pipeline on this chip, the vocabulary and every width
whole. All `total_ut_steps` passes run over the layers held, so a step is
`total_ut_steps x num_hidden_layers` layer applications and
`total_ut_steps` exits. With `recompute` the program keeps the residual
stream at each application's input and replays its forward ops ahead of
its gradient ops (paddle_tpu/backward.py); the reference recomputes
nothing it reports.
"""

import numpy as np


def _sizes(config):
    """The model's sizes under the names this file uses."""
    return dict(
        d=config["hidden_size"], layers=config["num_hidden_layers"],
        passes=config["total_ut_steps"],
        heads=config["num_attention_heads"],
        kv=config["num_key_value_heads"], hd=config["head_dim"],
        f=config["intermediate_size"], theta=config["rope_theta"],
        eps=config["rms_norm_eps"], v=config["vocab_size"],
        t=config["sequence_length"], beta=config["exit_entropy_weight"])


FEEDS = ("tok", "lab")
# a layer's tensors in the order the program creates them
LAYER_TENSORS = ("norm_1", "w_q", "w_k", "w_v", "w_o", "norm_2", "norm_3",
                 "w_g", "w_u", "w_d", "norm_4")


def build(config):
    """(main, startup, loss) of one AMP train step. The programs'
    random_seed is fixed: the run's seed reaches the weights through the
    executor's PRNG counter (traffic/train_steps.py says why)."""
    import paddle_tpu as fluid
    from paddle_tpu import models
    from paddle_tpu.framework import unique_name

    s = _sizes(config)
    assert config["hidden_act"] == "silu" and not config["rope_scaling"] \
        and not config["tie_word_embeddings"]
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with unique_name.guard(), fluid.program_guard(main, startup):
        tok, lab = (
            fluid.layers.data(name=name, shape=[-1, s["t"]], dtype="int64",
                              append_batch_size=False) for name in FEEDS)
        loss, kept = models.looped_lm(
            tok, lab, vocab_size=s["v"], hidden_size=s["d"],
            num_hidden_layers=s["layers"], num_attention_heads=s["heads"],
            num_key_value_heads=s["kv"], head_dim=s["hd"],
            intermediate_size=s["f"], total_ut_steps=s["passes"],
            exit_entropy_weight=s["beta"], rope_theta=s["theta"],
            epsilon=s["eps"],
            residual_layers=(config["num_hidden_layers_published"]
                             * s["passes"]),
            use_flash=config["use_flash"], recompute=config["recompute"])
        opt = fluid.amp.decorate(
            fluid.optimizer.Adam(learning_rate=config["learning_rate"],
                                 beta1=config["adam_beta1"],
                                 beta2=config["adam_beta2"],
                                 epsilon=config["adam_epsilon"]),
            level=config["amp_level"])
        opt.minimize(loss, startup_program=startup, checkpoints=kept)
    return main, startup, loss


def make_batch(config, batch, rng):
    """One host batch of `batch` sequences of T + 1 int32 ids drawn
    uniform from the whole vocabulary: `tok` the first T, `lab` the ids
    one position on."""
    t = config["sequence_length"]
    ids = rng.integers(0, config["vocab_size"], (batch, t + 1)).astype(
        np.int32)
    return {"tok": ids[:, :t], "lab": ids[:, 1:]}


def items_per_batch(feed):
    """An item is a token position (each gets a loss)."""
    return int(feed["tok"].size)


def live_pairs(length):
    """(query, key) pairs the causal mask of one sequence leaves alive,
    the query's own among them."""
    return length * (length + 1) // 2


def part_flops_per_item(config):
    """{part: forward FLOPs one token needs in one such part}. Nothing
    recomputed. projections: W_q, W_k, W_v, W_o of one layer application.
    attention: its scores and values at the causal mask's live pairs, a
    token's mean. feed_forward: its three maps. head: one exit's output
    head over the whole vocabulary. gate: one exit's gate."""
    s = _sizes(config)
    d, hd, t = s["d"], s["hd"], s["t"]
    return {
        "projections": 2 * d * hd * (2 * s["heads"] + 2 * s["kv"]),
        "attention": 4 * (live_pairs(t) / t) * s["heads"] * hd,
        "feed_forward": 6 * d * s["f"],
        "head": 2 * d * s["v"],
        "gate": 2 * d}


def required_flops_per_item(config):
    """FLOPs one token needs in a train step (forward x 3): every one of
    the `total_ut_steps x num_hidden_layers` layer applications, every
    exit's head, and the gate of every exit but the last. A replayed
    forward is time, not work."""
    s, per = _sizes(config), part_flops_per_item(config)
    layer = per["projections"] + per["attention"] + per["feed_forward"]
    return 3.0 * (s["passes"] * (s["layers"] * layer + per["head"])
                  + (s["passes"] - 1) * per["gate"])


def attention_ops_per_step(config):
    """Attention ops of a step whose kernels run: one a layer
    application. The replayed op runs none (PR 54: it is handed the
    first forward's output and row statistics)."""
    return config["total_ut_steps"] * config["num_hidden_layers"]


def attention_kernel_cost(config, tokens=None):
    """(FLOPs, bytes) of one attention op of a train step over one
    sequence of `tokens` positions (default the configuration's), forward
    and backward, AT THE CAUSAL MASK'S LIVE PAIRS and whatever implements
    it: six products of live pairs x head_dim x heads (the scores and P V
    forward; dV, dP, dQ and dK backward; scores computed again are time
    and not work); q, o and dO read and o and dQ written once at the
    query's heads, k and v read and dK and dV written once at the
    key/value heads, in bf16."""
    s = _sizes(config)
    t = tokens or s["t"]
    flops = 6 * 2.0 * live_pairs(t) * s["hd"] * s["heads"]
    return flops, 2.0 * t * s["hd"] * (5 * s["heads"] + 4 * s["kv"])


# query rows of one block of the reference's attention
_QUERY_ROWS = 1024


def parameter_dict(config, params):
    """The reference's own parameter dict from the trainable parameters
    in the order the program created them: the embedding [V, D]; the
    gate's w [D] and b [1]; per layer LAYER_TENSORS (the four norms [D],
    W_q [D, heads * hd], W_k and W_v [D, kv * hd], W_o, W_g and W_u
    [D, F], W_d [F, D]); the final norm's weight and the head [D, V]."""
    it = iter(params)
    held = {"embedding": next(it), "gate_w": next(it), "gate_b": next(it)}
    held["layers"] = [{name: next(it) for name in LAYER_TENSORS}
                      for _ in range(config["num_hidden_layers"])]
    held["final_norm"], held["head"] = next(it), next(it)
    assert next(it, None) is None
    return held


def reference_loss(config, params, feed):
    """The loss of the forward pass in float32, from the layer equations
    (ISSUE 59), one sequence at a time: a Python loop over the layers
    inside ONE jax.lax.scan over the passes, both reading ONE parameter
    dict (parameter_dict), so a weight's gradient is jax's own sum over
    its readers. (ISSUE 59 asked for two Python loops; unrolled four
    times the value_and_grad compiled to a 315 MB executable in 131 s of
    every run's set-up, with one pass as the scan's body in 64 s: my
    chip runs, PR 59, calls 1 and 2.) Each layer
    application and each exit are a jax.checkpoint, and so is each block
    of _QUERY_ROWS query rows of attention, so jax.grad keeps one
    application's activations, one exit's [T, V] logits and one
    [heads, rows, T] slab of scores at a time. Independent of the
    program's ops: attention is a masked softmax over an explicit boolean
    mask built from the positions; the rotation is written from the angle
    formula; the exit distribution is written as the published product
    of sigmoids, lambda_t prod_{j<t}(1 - lambda_j) (the program takes the
    exponential of a sum of -softplus terms), and H(p) as -sum p log p.

    Departures from the published description: none in the forward pass;
    the loss is the report's first-stage objective alone (the second
    stage trains the gate against a detached improvement signal and is
    not built); `early_exit_threshold` is inference's and unread."""
    import jax
    import jax.numpy as jnp

    s = _sizes(config)
    eps, heads, kv, hd, length = (s["eps"], s["heads"], s["kv"], s["hd"],
                                  s["t"])
    rows = min(_QUERY_ROWS, length)
    assert length % rows == 0
    # theta^(-2j/hd) for the pairs (j, j + hd/2)
    inv_freq = np.power(float(s["theta"]),
                        -2.0 * np.arange(hd // 2) / hd).astype(np.float32)
    position = jnp.arange(length)
    held = parameter_dict(config, params)

    def rms(x, w):
        return w * x * jax.lax.rsqrt((x ** 2).mean(-1, keepdims=True) + eps)

    def rotate(x):
        """x [T, n, hd]: the pair (j, j + hd/2) turned by the row's
        position times theta^(-2j/hd)."""
        angle = (position.astype(jnp.float32)[:, None]
                 * inv_freq)[:, None, :]
        a, b = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                                b * jnp.cos(angle) + a * jnp.sin(angle)], -1)

    def attention(a, w):
        q = rotate((a @ w["w_q"]).reshape(length, heads, hd))
        k = rotate((a @ w["w_k"]).reshape(length, kv, hd))
        v = (a @ w["w_v"]).reshape(length, kv, hd)
        # query head j reads key/value head j // (heads / kv)
        k, v = (jnp.repeat(x, heads // kv, axis=1) for x in (k, v))

        @jax.checkpoint
        def some_rows(first):
            q_rows = jax.lax.dynamic_slice_in_dim(q, first, rows)
            at = first + jnp.arange(rows)
            keep = position[None, :] <= at[:, None]
            scores = jnp.einsum("qhd,khd->hqk", q_rows, k) / np.sqrt(hd)
            prob = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), -1)
            return jnp.einsum("hqk,khd->qhd", prob, v)

        out = jax.lax.map(some_rows, jnp.arange(0, length, rows))
        return out.reshape(length, heads * hd) @ w["w_o"]

    @jax.checkpoint
    def layer(x, w):
        x = x + rms(attention(rms(x, w["norm_1"]), w), w["norm_2"])
        b = rms(x, w["norm_3"])
        fed = (jax.nn.silu(b @ w["w_g"]) * (b @ w["w_u"])) @ w["w_d"]
        return x + rms(fed, w["norm_4"])

    @jax.checkpoint
    def exit_of(h, w_head, gate_w, gate_b, labels):
        """(CE [T], lambda [T]) of one exit."""
        logp = jax.nn.log_softmax(h @ w_head, axis=-1)
        nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
        return nll, jax.nn.sigmoid(h @ gate_w + gate_b[0])

    def sequence_loss_sum(one):
        tok, lab = one

        def one_pass(carry, last):
            x, survive, total = carry
            for w in held["layers"]:
                x = layer(x, w)
            x = rms(x, held["final_norm"])
            nll, leave = exit_of(x, held["head"], held["gate_w"],
                                 held["gate_b"], lab)
            # the last exit takes the mass that is left, whatever its gate
            p = jnp.where(last, survive, survive * leave)
            # -beta H(p) = beta sum_t p_t log p_t; where p is 0 so is
            # p log p
            total = total + p * nll + s["beta"] * jnp.where(
                p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0)
            return (x, survive * (1.0 - leave), total), None

        start = (held["embedding"][tok],                        # [T, D]
                 jnp.ones((length,), jnp.float32),
                 jnp.zeros((length,), jnp.float32))
        (_, _, total), _ = jax.lax.scan(
            one_pass, start, jnp.arange(s["passes"]) == s["passes"] - 1)
        return total.sum()

    with jax.default_matmul_precision("highest"):
        feeds = tuple(jnp.asarray(feed[n]) for n in FEEDS)
        return jax.lax.map(sequence_loss_sum, feeds).sum() / feeds[0].size
