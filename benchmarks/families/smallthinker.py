"""Family `smallthinker`: a mixture-of-experts decoder whose attention
layers are of two kinds, three sliding-window layers with rotary positions
to one full-attention layer with no positional encoding at all, with a
router that reads the attention's normed input and ReGLU experts that read
its normed output (SmallThinker-21BA3B-Instruct; arXiv:2507.20984) through
paddle_tpu.models.window_moe_lm, with the sizes read from the
configuration file; reference_loss() is the same model in plain jax.numpy
float32, written from the layer equations (ISSUE 46, section 1) and not
from the program's ops.

The configuration is one chip's share of a deployment (its `deployment`):
`moe_num_primary_experts` experts of `moe_num_primary_experts_published`
are held here from `expert_offset` on, the router keeps its published
width, and what the absent experts would add is left out, in the program
and in the reference alike (no shared expert: a token none of whose
choices is held here gets zero from the layer); `vocab_size` rows of the
vocabulary; the first `num_hidden_layers` blocks, whose kinds are the
first entries of the two published layouts, which the file keeps whole.

`router_balance_rate` (`assumed`): only held experts lower the loss here,
so the cut's router walks towards them, which no rank of a deployment
sees. After each step's update every router's selection bias therefore
moves against the load the step saw over all the published experts
(models.balance_routers: arXiv:2408.15664). The bias starts at zero and
moves the choice only, so the first step, which reference_loss() is held
to, has none of it.
"""

import numpy as np


def _sizes(config):
    """The model's sizes under the names this file uses."""
    layers = config["num_hidden_layers"]
    return dict(
        d=config["hidden_size"], layers=layers,
        heads=config["num_attention_heads"],
        kv=config["num_key_value_heads"], hd=config["head_dim"],
        theta=config["rope_theta"],
        experts=config["moe_num_primary_experts_published"],
        held=config["moe_num_primary_experts"],
        offset=config["expert_offset"],
        top_k=config["moe_num_active_primary_experts"],
        f=config["moe_ffn_hidden_size"], eps=config["rms_norm_eps"],
        v=config["vocab_size"], t=config["sequence_length"],
        window=config["sliding_window_size"],
        windowed=config["sliding_window_layout"][:layers],
        rotated=config["rope_layout"][:layers])


FEEDS = ("tok", "lab")


def build(config):
    """(main, startup, loss) of one AMP train step. The programs'
    random_seed is fixed: the run's seed reaches the weights through the
    executor's PRNG counter (traffic/train_steps.py says why)."""
    import paddle_tpu as fluid
    from paddle_tpu import models
    from paddle_tpu.framework import unique_name

    s = _sizes(config)
    assert config["moe_primary_router_apply_softmax"]
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with unique_name.guard(), fluid.program_guard(main, startup):
        tok, lab = (
            fluid.layers.data(name=name, shape=[-1, s["t"]], dtype="int64",
                              append_batch_size=False) for name in FEEDS)
        loss = models.window_moe_lm(
            tok, lab, vocab_size=s["v"], hidden_size=s["d"],
            num_hidden_layers=s["layers"], num_attention_heads=s["heads"],
            num_key_value_heads=s["kv"], head_dim=s["hd"],
            num_experts=s["experts"], num_experts_per_tok=s["top_k"],
            moe_intermediate_size=s["f"],
            sliding_window_layout=config["sliding_window_layout"],
            rope_layout=config["rope_layout"],
            sliding_window_size=s["window"], experts_held=s["held"],
            expert_offset=s["offset"],
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


def live_pairs(length, window):
    """(query, key) pairs the causal mask of one sequence leaves alive
    under a window of `window` keys, the query's own among them (0: no
    window): query i sees min(i + 1, window) keys."""
    w = min(window or length, length)
    return w * (w + 1) // 2 + (length - w) * w


def part_flops_per_item(config):
    """{part: forward FLOPs one token needs in one such part}. Nothing
    recomputed, nothing for experts held elsewhere. projections: W_q,
    W_k, W_v, W_o. global_attention / window_attention: scores and values
    at the mask's live pairs of a full-attention / a windowed layer, a
    token's mean. experts: the router and the EXPECTED rows routed here,
    top_k x held / experts of a token's, three maps each. head: the
    sliced output head."""
    s = _sizes(config)
    d, hd, t = s["d"], s["hd"], s["t"]

    def attention(window):
        return 4 * (live_pairs(t, window) / t) * s["heads"] * hd

    return {
        "projections": 2 * d * hd * (2 * s["heads"] + 2 * s["kv"]),
        "global_attention": attention(0),
        "window_attention": attention(s["window"]),
        "experts": (2 * d * s["experts"] + s["top_k"] * s["held"]
                    / s["experts"] * 6 * d * s["f"]),
        "head": 2 * d * s["v"]}


def required_flops_per_item(config):
    """FLOPs one token needs in a train step (forward x 3), each layer's
    attention at its own kind's live pairs."""
    s, per = _sizes(config), part_flops_per_item(config)
    attention = sum(per["window_attention" if w else "global_attention"]
                    for w in s["windowed"])
    return 3.0 * (s["layers"] * (per["projections"] + per["experts"])
                  + attention + per["head"])


def attention_ops_per_step(config):
    """Attention ops of a step: one a block."""
    return config["num_hidden_layers"]


def attention_kernel_cost(config, tokens=None):
    """(FLOPs, bytes) of the MEAN attention op of a train step over one
    sequence of `tokens` positions (default the configuration's), forward
    and backward, AT THE LIVE PAIRS ONLY and whatever implements it, so
    that attention_ops_per_step times it is the step's sum over the
    layers of both kinds: six products of live pairs x head_dim x heads
    (the scores and P V forward; dV, dP, dQ and dK backward; scores
    computed again are time and not work, and a kernel that walks the
    tiles before the window lowers its own share); q, o and dO read and o
    and dQ written once at the query's heads, k and v read and dK and dV
    written once at the key/value heads, in bf16."""
    s = _sizes(config)
    t = tokens or s["t"]
    pairs = np.mean([live_pairs(t, s["window"] if w else 0)
                     for w in s["windowed"]])
    flops = 6 * 2.0 * pairs * s["hd"] * s["heads"]
    return flops, 2.0 * t * s["hd"] * (5 * s["heads"] + 4 * s["kv"])


def expert_layers(config):
    """Expert layers of a step: every block."""
    return config["num_hidden_layers"]


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


# query rows of one block of the reference's attention
_QUERY_ROWS = 1024


def reference_loss(config, params, feed):
    """The mean next-token cross-entropy of the forward pass in float32,
    from the layer equations (ISSUE 46, section 1), one sequence at a
    time; each block and the head are a jax.checkpoint, and so is each
    block of _QUERY_ROWS query rows of attention, so jax.grad keeps one
    block's activations and one [heads, rows, T] slab of scores at a
    time. Independent of the program's ops: attention is a masked softmax
    over an explicit boolean mask built from the positions (key <= query,
    and query - key < window in a windowed layer); the rotation is
    written from the angle formula and absent where the layout says 0;
    the router is jax.lax.top_k over the logits of the attention's normed
    input and a softmax over the chosen (the published order); the
    experts are a scan over the held experts with a mask.

    `params`: the trainable parameters in the order the program created
    them: embedding; per block norm_1, W_q [D, heads * hd], W_k and W_v
    [D, kv * hd], W_o, norm_2, the router [D, E], the held experts' G, U
    [held, D, F] and D [held, F, D]; the final norm's weight and the
    head. The router's selection bias is a buffer, not among them: taken
    as the zeros the configuration assumes."""
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

    def attention(a, w_q, w_k, w_v, w_o, windowed, rotated):
        q = (a @ w_q).reshape(length, heads, hd)
        k = (a @ w_k).reshape(length, kv, hd)
        v = (a @ w_v).reshape(length, kv, hd)
        if rotated:
            q, k = rotate(q), rotate(k)
        # query head j reads key/value head j // (heads / kv)
        k, v = (jnp.repeat(x, heads // kv, axis=1) for x in (k, v))

        @jax.checkpoint
        def some_rows(first):
            q_rows = jax.lax.dynamic_slice_in_dim(q, first, rows)
            at = first + jnp.arange(rows)
            keep = position[None, :] <= at[:, None]
            if windowed:
                keep &= at[:, None] - position[None, :] < s["window"]
            scores = jnp.einsum("qhd,khd->hqk", q_rows, k) / np.sqrt(hd)
            prob = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), -1)
            return jnp.einsum("hqk,khd->qhd", prob, v)

        out = jax.lax.map(some_rows, jnp.arange(0, length, rows))
        return out.reshape(length, heads * hd) @ w_o

    def reglu(x, w_g, w_u, w_d):
        return (jax.nn.relu(x @ w_g) * (x @ w_u)) @ w_d

    def route(a, w_r):
        """The chosen experts and their weights, from the attention's
        input: the top k logits, then a softmax over them."""
        top, idx = jax.lax.top_k(a @ w_r, s["top_k"])
        return idx, jax.nn.softmax(top, axis=-1)

    def experts(x, idx, weight, gate_w, up, down):
        def add_expert(out, expert):
            e, g_e, u_e, d_e = expert
            mine = (weight * (idx == s["offset"] + e)).sum(-1, keepdims=True)
            return out + mine * reglu(x, g_e, u_e, d_e), None

        return jax.lax.scan(add_expert, jnp.zeros_like(x),
                            (jnp.arange(s["held"]), gate_w, up, down))[0]

    def block(windowed, rotated):
        @jax.checkpoint
        def run(x, weights):
            norm_1, w_q, w_k, w_v, w_o, norm_2, w_r, *ffn = weights
            a = rms(x, norm_1)
            idx, weight = route(a, w_r)
            x = x + attention(a, w_q, w_k, w_v, w_o, windowed, rotated)
            return x + experts(rms(x, norm_2), idx, weight, *ffn)
        return run

    @jax.checkpoint
    def nll_sum(x, norm_w, w_head, labels):
        logp = jax.nn.log_softmax(rms(x, norm_w) @ w_head, axis=-1)
        return -jnp.take_along_axis(logp, labels[:, None], axis=-1).sum()

    def sequence_loss_sum(one):
        tok, lab = one
        it = iter(params)
        x = next(it)[tok]                                   # [T, D]
        for windowed, rotated in zip(s["windowed"], s["rotated"]):
            x = block(windowed, rotated)(x, [next(it) for _ in range(10)])
        return nll_sum(x, next(it), next(it), lab)

    assert config["norm_topk_prob"] and \
        config["moe_primary_router_apply_softmax"]
    with jax.default_matmul_precision("highest"):
        feeds = tuple(jnp.asarray(feed[n]) for n in FEEDS)
        return jax.lax.map(sequence_loss_sum, feeds).sum() / feeds[0].size
