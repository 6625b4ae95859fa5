"""Family `laguna`: a mixture-of-experts decoder whose attention layers
differ in kind, head count and positions (poolside Laguna-XS.2): one full
layer of 48 heads under a YaRN rotation of half of each head to three
sliding-window layers of 64 heads under the default rotation, a sigmoid
gate a head on the attention's output, a leading dense layer, and in every
other layer a sigmoid router over gated experts beside a shared expert,
through paddle_tpu.models.gated_window_moe_lm, with the sizes read from
the configuration file; reference_loss() is the same model in plain
jax.numpy float32, written from the layer equations (ISSUE 53, "The
layers") and not from the program's ops.

The configuration is one chip's share of a deployment (its `deployment`):
`num_experts` experts of `num_experts_published` are held here from
`expert_offset` on, the router keeps its published width, the shared
expert is whole, and what the absent experts would add is left out, in
the program and in the reference alike; `vocab_size` rows of the
vocabulary; the first `num_hidden_layers` layers, whose kinds, head counts
and feed-forwards are the first entries of the three published lists,
which the file keeps whole.

Three readings the published config does not spell out are keys of the
file (`assumed` says why each), read here by the program and the
reference alike: `gating_granularity` ("per-head" | "per-element"),
`router_scoring` ("sigmoid" | "softmax", with `norm_topk_prob`), and
`qk_norm` / `shared_expert_gate`, which only false is written for.

`router_balance_rate` (`assumed`): only held experts lower the loss here,
so the cut's router walks towards them, which no rank of a deployment
sees; after each step's update every router's selection bias moves
against the load the step saw over all the published experts
(models.balance_routers: arXiv:2408.15664). The bias starts at zero and
moves the choice only, so the first step, which reference_loss() is held
to, has none of it.
"""

import numpy as np

SLIDING = "sliding_attention"     # the other kind is "full_attention"
GATE_WIDTHS = {"per-head": lambda heads, hd: heads,
               "per-element": lambda heads, hd: heads * hd}


def _sizes(config):
    """The model's sizes under the names this file uses."""
    layers = config["num_hidden_layers"]
    if config["qk_norm"] or config["shared_expert_gate"] \
            or config["attention_bias"]:
        raise NotImplementedError(
            "the family is written without QK-norm, without a gate on the "
            "shared expert and without bias (the configuration's `assumed`)")
    assert not config["moe_apply_router_weight_on_input"]
    return dict(
        d=config["hidden_size"], layers=layers,
        kinds=config["layer_types"][:layers],
        ffns=config["mlp_layer_types"][:layers],
        heads=config["num_attention_heads_per_layer"][:layers],
        kv=config["num_key_value_heads"], hd=config["head_dim"],
        window=config["sliding_window"], rope=config["rope_parameters"],
        gate=(GATE_WIDTHS[config["gating_granularity"]]
              if config["gating"] else None),
        inter=config["intermediate_size"],
        experts=config["num_experts_published"],
        held=config["num_experts"], offset=config["expert_offset"],
        top_k=config["num_experts_per_tok"],
        f=config["moe_intermediate_size"],
        fs=config["shared_expert_intermediate_size"],
        scaling=config["moe_routed_scaling_factor"],
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
        loss, kept = models.gated_window_moe_lm(
            tok, lab, vocab_size=s["v"], hidden_size=s["d"],
            num_hidden_layers=s["layers"], layer_types=config["layer_types"],
            mlp_layer_types=config["mlp_layer_types"],
            num_attention_heads_per_layer=config[
                "num_attention_heads_per_layer"],
            num_key_value_heads=s["kv"], head_dim=s["hd"],
            rope_parameters=s["rope"], sliding_window=s["window"],
            intermediate_size=s["inter"], num_experts=s["experts"],
            num_experts_per_tok=s["top_k"], moe_intermediate_size=s["f"],
            shared_width=s["fs"], experts_held=s["held"],
            expert_offset=s["offset"], routed_scaling_factor=s["scaling"],
            norm_topk_prob=config["norm_topk_prob"],
            router_scoring=config["router_scoring"],
            gating=config["gating"] and config["gating_granularity"],
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


def live_pairs(length, window):
    """(query, key) pairs the causal mask of one sequence leaves alive
    under a window of `window` keys, the query's own among them (0: no
    window): query i sees min(i + 1, window) keys."""
    w = min(window or length, length)
    return w * (w + 1) // 2 + (length - w) * w


def _window_of(s, kind):
    return s["window"] if kind == SLIDING else 0


def part_flops_per_item(config):
    """{part: forward FLOPs one token needs in one such part}, the parts
    that differ by layer keyed by the layer's kind. Nothing recomputed,
    nothing for experts held elsewhere. projections: W_q, W_k, W_v, W_o
    and the gate's map at the kind's head count. attention: scores and
    values at the mask's live pairs, a token's mean, at the kind's head
    count. dense: the leading layer's gated feed-forward. experts: the
    router, the shared expert and the EXPECTED rows routed here, top_k x
    held / experts of a token's, three maps each. head: the sliced
    output head."""
    s = _sizes(config)
    d, hd, t = s["d"], s["hd"], s["t"]
    parts = {
        "dense": 6 * d * s["inter"],
        "experts": (2 * d * s["experts"] + 6 * d * s["fs"]
                    + s["top_k"] * s["held"] / s["experts"] * 6 * d * s["f"]),
        "head": 2 * d * s["v"]}
    for kind, heads in set(zip(s["kinds"], s["heads"])):
        gate = s["gate"](heads, hd) if s["gate"] else 0
        parts["projections." + kind] = 2 * d * (
            hd * (2 * heads + 2 * s["kv"]) + gate)
        parts["attention." + kind] = 4 * (
            live_pairs(t, _window_of(s, kind)) / t) * heads * hd
    return parts


def required_flops_per_item(config):
    """FLOPs one token needs in a train step (forward x 3), each layer's
    maps and attention at its own kind's head count and live pairs."""
    s, per = _sizes(config), part_flops_per_item(config)
    by_layer = sum(per["projections." + kind] + per["attention." + kind]
                   + per["dense" if ffn == "dense" else "experts"]
                   for kind, ffn in zip(s["kinds"], s["ffns"]))
    return 3.0 * (by_layer + per["head"])


def attention_ops_per_step(config):
    """Attention ops of a step: one a layer."""
    return config["num_hidden_layers"]


def attention_kernel_cost(config, tokens=None):
    """(FLOPs, bytes) of the MEAN attention op of a train step over one
    sequence of `tokens` positions (default the configuration's), forward
    and backward, AT THE LIVE PAIRS ONLY and whatever implements it, so
    that attention_ops_per_step times it is the step's sum over the
    layers of both kinds, each at its own head count: six products of
    live pairs x head_dim x the layer's heads (the scores and P V
    forward; dV, dP, dQ and dK backward; scores computed again, a forward
    replayed and masked tiles walked are time and not work); q, o and dO
    read and o and dQ written once at the layer's query heads, k and v
    read and dK and dV written once at the key/value heads, in bf16."""
    s = _sizes(config)
    t = tokens or s["t"]
    flops = np.mean([6 * 2.0 * live_pairs(t, _window_of(s, kind)) * s["hd"]
                     * heads for kind, heads in zip(s["kinds"], s["heads"])])
    bytes_ = np.mean([2.0 * t * s["hd"] * (5 * heads + 4 * s["kv"])
                      for heads in s["heads"]])
    return float(flops), float(bytes_)


def expert_layers(config):
    """Expert layers of a step: the layers whose feed-forward is sparse."""
    s = _sizes(config)
    return sum(ffn == "sparse" for ffn in s["ffns"])


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


def yarn_frequencies(group, dims):
    """The `dims` / 2 frequencies of a `rope_parameters` group whose
    rope_type is "yarn", from its keys (arXiv:2309.00071 as transformers'
    _compute_yarn_parameters computes it, in float64): f_j =
    theta^(-2j/dims); c(n) = dims ln(original / (2 pi n)) / (2 ln theta);
    low = floor(c(beta_fast)), high = ceil(c(beta_slow)); ramp_j =
    clip((j - low) / (high - low), 0, 1); f_j (1 - ramp_j) + f_j / factor
    * ramp_j. Written here from the formula, not read from the program's
    op."""
    theta, original = float(group["rope_theta"]), float(
        group["original_max_position_embeddings"])
    j = np.arange(dims // 2, dtype=np.float64)
    f = theta ** (-2 * j / dims)

    def c(turns):
        return dims * np.log(original / (2 * np.pi * turns)) \
            / (2 * np.log(theta))

    low = max(np.floor(c(group["beta_fast"])), 0)
    high = min(np.ceil(c(group["beta_slow"])), dims - 1)
    ramp = np.clip((j - low) / (high - low), 0, 1)
    return f * (1 - ramp) + f / group["factor"] * ramp


# query rows of one block of the reference's attention
_QUERY_ROWS = 128


def reference_loss(config, params, feed):
    """The mean next-token cross-entropy of the forward pass in float32,
    from the layer equations (ISSUE 53), one sequence at a time; each
    layer and the head are a jax.checkpoint, and so are each block of
    _QUERY_ROWS query rows of attention and each held expert's share of
    the output, so jax.grad keeps one layer's activations, one [heads,
    rows, T] slab of scores and one expert's hidden rows at a time (3.35e9
    B of temporaries in the described compile beside 2.77e9 B of
    gradients, in the 5.8e9 B the state leaves; at 256 rows and the
    experts' rows kept it was 8.0e9 and the chip refused it: PR 53).
    Independent of the program's ops: attention is a masked softmax over
    an explicit boolean mask built from the positions (key <= query, and
    query - key < window in a sliding layer); the rotation is written
    from the angle formula over the trailing `partial_rotary_factor` of a
    head, its frequencies yarn_frequencies() where the layer's group says
    yarn, cosines and sines times the group's attention_factor; the gate
    is a sigmoid of the normed input's own map; the router is
    jax.lax.top_k over the scores; the experts are a scan over the held
    experts with a mask.

    `params`: the trainable parameters in the order the program created
    them: embedding; per layer norm_1, W_q [D, H_l * hd], W_k and W_v
    [D, kv * hd], W_g [D, H_l] (absent without `gating`), W_o, norm_2
    and then W_g, W_u, W_d (a dense layer) or the router [D, E], the held
    experts' G, U [held, D, F] and D [held, F, D] and the shared expert's
    W_g, W_u, W_d; the final norm's weight and the head. The router's
    selection bias is a buffer, not among them: taken as the zeros the
    configuration assumes."""
    import jax
    import jax.numpy as jnp

    s = _sizes(config)
    eps, kv, hd, length = s["eps"], s["kv"], s["hd"], s["t"]
    rows = min(_QUERY_ROWS, length)
    assert length % rows == 0
    position = jnp.arange(length)

    def rms(x, w):
        return w * x * jax.lax.rsqrt((x ** 2).mean(-1, keepdims=True) + eps)

    def rotation(group):
        """x [T, n, hd] -> x with its trailing r dims turned: the pair
        (j, j + r/2) of them by the row's position times the j-th
        frequency, cos and sin times the group's factor."""
        r = int(hd * group.get("partial_rotary_factor", 1))
        if group["rope_type"] == "yarn":
            freq, factor = yarn_frequencies(group, r), \
                group["attention_factor"]
        else:
            assert group["rope_type"] == "default", group
            freq, factor = float(group["rope_theta"]) ** (
                -2.0 * np.arange(r // 2) / r), 1.0
        angle = (position.astype(jnp.float32)[:, None]
                 * jnp.asarray(freq, jnp.float32))[:, None, :]
        cos, sin = factor * jnp.cos(angle), factor * jnp.sin(angle)

        def rotate(x):
            still, a, b = (x[..., :hd - r], x[..., hd - r:hd - r // 2],
                           x[..., hd - r // 2:])
            return jnp.concatenate(
                [still, a * cos - b * sin, b * cos + a * sin], -1)
        return rotate

    def attention(a, w_q, w_k, w_v, w_g, w_o, kind, heads):
        rotate = rotation(s["rope"][kind])
        q = rotate((a @ w_q).reshape(length, heads, hd))
        k = rotate((a @ w_k).reshape(length, kv, hd))
        v = (a @ w_v).reshape(length, kv, hd)
        # query head j reads key/value head j // (heads / kv)
        k, v = (jnp.repeat(x, heads // kv, axis=1) for x in (k, v))

        @jax.checkpoint
        def some_rows(first):
            q_rows = jax.lax.dynamic_slice_in_dim(q, first, rows)
            at = first + jnp.arange(rows)
            keep = position[None, :] <= at[:, None]
            if kind == SLIDING:
                keep &= at[:, None] - position[None, :] < s["window"]
            scores = jnp.einsum("qhd,khd->hqk", q_rows, k) / np.sqrt(hd)
            prob = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), -1)
            return jnp.einsum("hqk,khd->qhd", prob, v)

        out = jax.lax.map(some_rows, jnp.arange(0, length, rows)).reshape(
            length, heads, hd)
        if w_g is not None:
            out = out * jax.nn.sigmoid(a @ w_g).reshape(length, heads, -1)
        return out.reshape(length, heads * hd) @ w_o

    def gated(x, w_g, w_u, w_d):
        return (jax.nn.silu(x @ w_g) * (x @ w_u)) @ w_d

    def experts(x, w_r, gate_w, up, down, s_g, s_u, s_d):
        logits = x @ w_r                                    # [T, experts]
        scores = jax.nn.sigmoid(logits) \
            if config["router_scoring"] == "sigmoid" \
            else jax.nn.softmax(logits, -1)
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

    def layer(kind, heads):
        @jax.checkpoint
        def run(x, weights):
            it = iter(weights)
            norm_1, w_q, w_k, w_v = (next(it) for _ in range(4))
            w_g = next(it) if s["gate"] else None
            w_o, norm_2, *ffn = it
            x = x + attention(rms(x, norm_1), w_q, w_k, w_v, w_g, w_o, kind,
                              heads)
            return x + (gated if len(ffn) == 3 else experts)(
                rms(x, norm_2), *ffn)
        return run

    @jax.checkpoint
    def nll_sum(x, norm_w, w_head, labels):
        logp = jax.nn.log_softmax(rms(x, norm_w) @ w_head, axis=-1)
        return -jnp.take_along_axis(logp, labels[:, None], axis=-1).sum()

    def sequence_loss_sum(one):
        tok, lab = one
        it = iter(params)
        x = next(it)[tok]                                   # [T, D]
        for kind, ffn, heads in zip(s["kinds"], s["ffns"], s["heads"]):
            count = 6 + bool(s["gate"]) + (3 if ffn == "dense" else 7)
            x = layer(kind, heads)(x, [next(it) for _ in range(count)])
        return nll_sum(x, next(it), next(it), lab)

    with jax.default_matmul_precision("highest"):
        feeds = tuple(jnp.asarray(feed[n]) for n in FEEDS)
        return jax.lax.map(sequence_loss_sum, feeds).sum() / feeds[0].size
