"""Family `qwen3_next`: a mixture-of-experts decoder whose mixers are three
Gated DeltaNet layers (a decay a head, key heads under groups of value
heads) to one gated grouped-query softmax attention layer (QK-norm, the
first quarter of a head rotated, a sigmoid gate an element of the
output), every layer's feed-forward a softmax top-k of routed experts
beside a gated shared expert (Qwen Qwen3-Next-80B-A3B-Instruct; the mixer
is arXiv:2412.06464's), through paddle_tpu.models.gdn_moe_lm, with the
sizes read from the configuration file; reference_loss() is the same
model in plain jax.numpy float32, written from the layer equations
(ISSUE 64, "The layer equations") and not from the program's ops: its
delta rule is the recurrence TOKEN BY TOKEN, where the program runs the
chunked algebra on kernels.

The configuration is one chip's share of a deployment (its `deployment`):
`num_experts` experts of `num_experts_published` are held here from
`expert_offset` on, the router keeps its published width, the shared
expert, its gate and both mixers are whole, and what the absent experts
would add is left out, in the program and in the reference alike;
`vocab_size` rows of the vocabulary; the layers `layers_held` (default the
first `num_hidden_layers`), each with the mixer its published index has.

Where the program departs from the published FORM (never from its
values or its count), the reference keeps the published one:
- the published in_proj_qkvz [D, 2 Hk K + 2 Hv V] and in_proj_ba [D, 2
  Hv] are held by the program as the six maps W_q, W_k, W_v, W_z, W_b,
  W_alpha (the published matrix groups its columns by key head: [q_i |
  k_i | v_2i v_2i+1 | z_2i z_2i+1] for key head i; the six maps are those
  columns gathered by kind, a fixed permutation), and the one depthwise
  convolution over [q | k | v] as three; the reference multiplies by the
  one concatenated matrix and convolves the one concatenated array;
- the published q_proj [D, H x 2 hd] holds a head's query and gate side
  by side; the program holds W_q and W_g, the same columns by kind;
- `router_balance_rate` (`assumed`): the config gives no balancing rule.
  Only held experts lower the loss here, so the cut's router walks
  towards them, which no rank of a deployment sees; after each step's
  update every router's selection bias moves against the load the step
  saw over all the published experts (models.balance_routers:
  arXiv:2408.15664). The bias starts at zero and moves the choice only
  (the weights stay the published normalised softmax), so the first
  step, which reference_loss() is held to, has none of it.
No prediction module: the catalog row's `described_as` says "MTP 1", its
`config` has no key for one.
"""

import numpy as np

LINEAR, FULL = "linear_attention", "full_attention"


def _sizes(config):
    """The model's sizes under the names this file uses."""
    assert config["decoder_sparse_step"] == 1 \
        and not config["mlp_only_layers"], "every layer's ffn is sparse"
    held = config.get("layers_held",
                      list(range(config["num_hidden_layers"])))
    assert len(held) == config["num_hidden_layers"]
    every = config["full_attention_interval"]
    hd = config["head_dim"]
    return dict(
        d=config["hidden_size"], held_layers=held,
        kinds=[FULL if (l + 1) % every == 0 else LINEAR for l in held],
        hk=config["linear_num_key_heads"],
        hv=config["linear_num_value_heads"],
        kd=config["linear_key_head_dim"],
        vd=config["linear_value_head_dim"],
        conv=config["linear_conv_kernel_dim"],
        heads=config["num_attention_heads"],
        kv=config["num_key_value_heads"], hd=hd,
        rotary=int(hd * config["partial_rotary_factor"]),
        theta=config["rope_theta"],
        experts=config["num_experts_published"], held=config["num_experts"],
        offset=config["expert_offset"], top_k=config["num_experts_per_tok"],
        f=config["moe_intermediate_size"],
        fs=config["shared_expert_intermediate_size"],
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
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with unique_name.guard(), fluid.program_guard(main, startup):
        tok, lab = (
            fluid.layers.data(name=name, shape=[-1, s["t"]], dtype="int64",
                              append_batch_size=False) for name in FEEDS)
        loss, kept = models.gdn_moe_lm(
            tok, lab, vocab_size=s["v"], hidden_size=s["d"],
            num_hidden_layers=config["num_hidden_layers"],
            full_attention_interval=config["full_attention_interval"],
            linear_num_key_heads=s["hk"], linear_num_value_heads=s["hv"],
            linear_key_head_dim=s["kd"], linear_value_head_dim=s["vd"],
            num_attention_heads=s["heads"], num_key_value_heads=s["kv"],
            head_dim=s["hd"], num_experts=s["experts"],
            num_experts_per_tok=s["top_k"], moe_intermediate_size=s["f"],
            shared_expert_intermediate_size=s["fs"],
            layers_held=s["held_layers"], linear_conv_kernel_dim=s["conv"],
            gdn_chunk_size=config["gdn_chunk_size"],
            l2_norm_epsilon=s["l2_eps"],
            partial_rotary_factor=config["partial_rotary_factor"],
            rope_theta=s["theta"], experts_held=s["held"],
            expert_offset=s["offset"],
            norm_topk_prob=config["norm_topk_prob"], epsilon=s["eps"],
            initializer_range=config["initializer_range"],
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
    recomputed, nothing for experts held elsewhere. gdn_maps: the
    published in_proj_qkvz and in_proj_ba, W_o and the short convolution
    over [q | k | v]. gdn_rule: the recurrence's three products of a
    value head's [K, V] state a token (k^T S, the rank-one correction,
    q^T S), which no chunk length moves. attn_maps: q and its gate, k, v,
    W_o. attn_pairs: scores and values at head_dim over the causal mask's
    live pairs, a token's mean. experts: the router over all published
    experts, the shared expert and its gate, and the routed experts'
    share held here. head as the other families count it."""
    s = _sizes(config)
    d, t = s["d"], s["t"]
    qk, vz = s["hk"] * s["kd"], s["hv"] * s["vd"]
    wide = s["heads"] * s["hd"]
    return {
        "gdn_maps": 2 * (d * (2 * qk + 2 * vz) + 2 * d * s["hv"]
                         + s["conv"] * (2 * qk + vz) + vz * d),
        "gdn_rule": 3 * 2 * s["hv"] * s["kd"] * s["vd"],
        "attn_maps": 2 * (2 * d * wide + 2 * d * s["kv"] * s["hd"]
                          + wide * d),
        "attn_pairs": 2 * ((t + 1) / 2) * s["heads"] * 2 * s["hd"],
        "experts": (2 * d * s["experts"] + 6 * d * s["fs"] + 2 * d
                    + s["top_k"] * s["held"] / s["experts"] * 6 * d * s["f"]),
        "head": 2 * d * s["v"]}


def required_flops_per_item(config):
    """FLOPs one token needs in a train step (forward x 3), each layer's
    mixer by its kind and its expert layer."""
    s, per = _sizes(config), part_flops_per_item(config)
    mixer = {LINEAR: per["gdn_maps"] + per["gdn_rule"],
             FULL: per["attn_maps"] + per["attn_pairs"]}
    return 3.0 * (sum(mixer[kind] + per["experts"] for kind in s["kinds"])
                  + per["head"])


def kda_layers(config):
    """Delta-rule layers of a step: the held layers whose mixer is Gated
    DeltaNet."""
    return _sizes(config)["kinds"].count(LINEAR)


def kda_scan_cost(config, tokens):
    """(FLOPs, bytes) of one train step's delta rule in ONE such layer
    over `tokens` positions, whatever implements it, at a decay a head
    and Hk key heads under Hv value heads: the recurrence's three [K, V]
    products a token a VALUE head forward and twice that backward (the
    chunked form's within-chunk products and solves, a replayed forward
    and what the gradient computes again are time and not work); q and k
    at their Hk heads, v and o at Hv, read or written once and their
    gradients once, in the bf16 the program holds them in, the raw gate
    and beta [Hv] and their gradients likewise (a gate broadcast over a
    head's channels or keys repeated to Hv heads would be time, not
    work)."""
    s = _sizes(config)
    flops = 3.0 * tokens * 3 * 2 * s["hv"] * s["kd"] * s["vd"]
    return flops, 2 * 2.0 * tokens * (2 * s["hk"] * s["kd"]
                                      + 2 * s["hv"] * s["vd"] + 2 * s["hv"])


def attention_ops_per_step(config):
    """Attention ops of a step: one a gated-attention layer."""
    return _sizes(config)["kinds"].count(FULL)


def attention_kernel_cost(config, tokens=None):
    """(FLOPs, bytes) of ONE attention op of a train step over one
    sequence of `tokens` positions (default the configuration's), forward
    and backward, over the causal mask's live pairs at 16 query heads of
    256: the scores, dQ and dK, and P V, dV and dP, six products of a
    pair a head; q read and dQ written, o written, o and dO read at the
    query heads, k and v read and dK and dV written at their own 2 heads
    (a K or V widened to the query heads would be time, not work), in
    bf16."""
    s = _sizes(config)
    t = tokens or s["t"]
    live = t * (t + 1) / 2
    flops = 2.0 * live * s["heads"] * 6 * s["hd"]
    return flops, 2.0 * t * s["hd"] * (5 * s["heads"] + 4 * s["kv"])


def expert_layers(config):
    """Expert layers of a step: every held layer."""
    return len(_sizes(config)["kinds"])


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


# tokens of one block of the reference's recurrence, query rows of one
# block of its attention, rows of one block of its head
_TOKEN_BLOCK = 64
_QUERY_ROWS = 128
_HEAD_ROWS = 2048
# parameters of a mixer by kind, and of an expert layer
_MIXER_PARAMS = {LINEAR: 13, FULL: 7}
_FFN_PARAMS = 8


def reference_loss(config, params, feed):
    """The mean next-token cross-entropy of the forward pass in float32,
    from the layer equations (ISSUE 64), one sequence at a time; each
    layer is a jax.checkpoint, and so are each key head's share of the
    linear mixer's projection and convolution, each block of
    _TOKEN_BLOCK tokens of the recurrence, the gated norm with the output
    map, each block of _QUERY_ROWS query rows of attention, each block of
    _HEAD_ROWS rows of the head and each held expert's share of the
    output (the reference shares the chip with 7.5e9 B of state and its
    own 2.5e9 B of gradients: kept whole, a linear mixer's float32
    activations alone were 7.1e9 B and the chip refused to load it). Independent of the program's ops:

    the delta rule is the recurrence TOKEN BY TOKEN, S_t = exp(g_t)
    S_{t-1} + k_t (beta_t (v_t - (exp(g_t) S_{t-1})^T k_t))^T, o_t = S_t^T
    q_t, a lax.scan over single tokens (no chunk, no triangular system,
    no inverse: nothing of hybrid_ops.kda_chunked), in blocks of 64 under
    a jax.checkpoint so that jax.grad keeps one [Hv, K, V] state a block
    (256 x 2 MB a layer at 16,384 tokens, inside that layer's own
    checkpoint) and replays a block's steps; kept whole a layer's states
    are 34 GB. The published one projection in_proj_qkvz and the one
    depthwise convolution over [q | k | v] are the concatenations of the
    program's maps and taps, a key head's columns side by side as
    published (this file's docstring has the permutation);
    attention is a masked softmax over an explicit boolean mask, the
    query heads grouped over their K and V head, QK-norm ahead of the rotation,
    which is written from the angle formula over the first `rotary`
    dims; the router is jax.lax.top_k over the float32 softmax of all
    published experts, its weights the chosen probabilities over their
    sum; the experts are a scan over the held experts with a mask.

    `params`: the trainable parameters in the order the program created
    them: embedding; per held layer norm_1, then the mixer's (Gated
    DeltaNet: W_q, W_k [D, Hk K], W_v [D, Hv V], the three filters
    [channels, taps], W_z [D, Hv V], W_b, W_alpha [D, Hv], A_log, dt_bias
    [Hv], the head norm's w [V], W_o; attention: W_q [D, H hd], W_k, W_v
    [D, kv hd], W_g [D, H hd], the query heads' norm and the key heads'
    [hd], W_o), norm_2, the router [D, E], the held experts' G, U [held,
    D, F] and D [held, F, D], the shared expert's W_g, W_u, W_d and its
    gate w_sg [D, 1]; the final norm's weight and the head. Every norm
    weight but the head norm's w is the published 1 + w form's w. The
    routers' selection bias is a buffer, not among them: taken as the
    zeros it starts from."""
    import jax
    import jax.numpy as jnp

    s = _sizes(config)
    eps, length = s["eps"], s["t"]
    hk, hv, kd, vd = s["hk"], s["hv"], s["kd"], s["vd"]
    heads, kv, hd = s["heads"], s["kv"], s["hd"]
    position = jnp.arange(length)

    def rms(x, w):
        return (1.0 + w) * x * jax.lax.rsqrt(
            (x ** 2).mean(-1, keepdims=True) + eps)

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
        """q, k [T, Hk, K], v [T, Hv, V], g, beta [T, Hv] -> o [T, Hv,
        V], a token at a time; value head j reads key head j // (Hv /
        Hk)."""
        block = min(_TOKEN_BLOCK, length)
        assert length % block == 0

        def token(state, now):
            q_t, k_t, v_t, g_t, b_t = now
            q_t, k_t = (jnp.repeat(x, hv // hk, axis=0) for x in (q_t, k_t))
            state = state * jnp.exp(g_t)[:, None, None]
            seen = jnp.einsum("hk,hkv->hv", k_t, state)
            state = state + k_t[..., None] * (
                b_t[:, None] * (v_t - seen))[:, None, :]
            return state, jnp.einsum("hk,hkv->hv", q_t, state)

        @jax.checkpoint
        def some_tokens(state, tokens):
            return jax.lax.scan(token, state, tokens)

        blocks = tuple(x.reshape((length // block, block) + x.shape[1:])
                       for x in (q, k, v, g, beta))
        _, out = jax.lax.scan(some_tokens, jnp.zeros((hv, kd, vd)), blocks)
        return out.reshape(length, hv, vd)

    def gated_delta_net(a, w_q, w_k, w_v, c_q, c_k, c_v, w_z, w_b, w_alpha,
                        a_log, dt_bias, w_norm, w_o):
        d, r = a.shape[1], hv // hk
        # the published in_proj_qkvz and conv1d, a key head's columns side
        # by side: [q_i | k_i | v of its r value heads | z of them]
        in_proj = jnp.concatenate(
            [w_q.reshape(d, hk, kd), w_k.reshape(d, hk, kd),
             w_v.reshape(d, hk, r * vd), w_z.reshape(d, hk, r * vd)], axis=2)
        taps = jnp.concatenate(
            [c.reshape(hk, -1, c.shape[1]) for c in (c_q, c_k, c_v)], axis=1)

        @jax.checkpoint
        def key_head(w, c):
            """One key head's q, k [T, K] and its value heads' v, z [T, r,
            V]: a checkpoint a key head, so that the [T, 12288]
            projection and its convolution never stand whole."""
            proj = a @ w
            mixed = short_conv(proj[:, :2 * kd + r * vd], c)
            return (unit(mixed[:, :kd]) / np.sqrt(kd),
                    unit(mixed[:, kd:2 * kd]),
                    mixed[:, 2 * kd:].reshape(length, r, vd),
                    proj[:, 2 * kd + r * vd:].reshape(length, r, vd))

        q, k, v, z = (jnp.moveaxis(x, 0, 1) for x in jax.lax.map(
            lambda one: key_head(*one), (jnp.moveaxis(in_proj, 1, 0), taps)))
        v, z = (x.reshape(length, hv, vd) for x in (v, z))
        ba = a @ jnp.concatenate([w_b, w_alpha], axis=1)
        beta = jax.nn.sigmoid(ba[:, :hv])
        g = -jnp.exp(a_log) * jax.nn.softplus(ba[:, hv:] + dt_bias)

        @jax.checkpoint
        def gated_norm_and_map(o, z):
            o = w_norm * o * jax.lax.rsqrt((o ** 2).mean(-1, keepdims=True)
                                           + eps) * jax.nn.silu(z)
            return o.reshape(length, hv * vd) @ w_o

        return gated_norm_and_map(delta_rule(q, k, v, g, beta), z)

    def rotate(x):
        """x [T, n, hd]: of the first `rotary` dims the pair (i, i +
        rotary / 2) turned by the row's position times theta^(-2 i /
        rotary); the dims behind them pass."""
        half = s["rotary"] // 2
        freq = float(s["theta"]) ** (-np.arange(half) / half)
        angle = (position.astype(jnp.float32)[:, None]
                 * jnp.asarray(freq, jnp.float32))[:, None, :]
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                               -1)

    def gated_attention(a, w_q, w_k, w_v, w_g, norm_q, norm_k, w_o):
        q = rotate(rms((a @ w_q).reshape(length, heads, hd), norm_q))
        k = rotate(rms((a @ w_k).reshape(length, kv, hd), norm_k))
        v = (a @ w_v).reshape(length, kv, hd)
        # query head i reads key/value head i // (heads / kv): the query
        # heads as [kv, heads / kv], K and V at their own count
        q = q.reshape(length, kv, heads // kv, hd)
        rows = min(_QUERY_ROWS, length)
        assert length % rows == 0

        @jax.checkpoint
        def some_rows(first):
            q_rows = jax.lax.dynamic_slice_in_dim(q, first, rows)
            keep = position[None, :] <= (first + jnp.arange(rows))[:, None]
            scores = jnp.einsum("qgrd,kgd->grqk", q_rows, k) / np.sqrt(hd)
            prob = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), -1)
            return jnp.einsum("grqk,kgd->qgrd", prob, v)

        out = jax.lax.map(some_rows, jnp.arange(0, length, rows))

        @jax.checkpoint
        def gate_and_map(out):
            return (out.reshape(length, heads * hd)
                    * jax.nn.sigmoid(a @ w_g)) @ w_o

        return gate_and_map(out)

    def gated(x, w_g, w_u, w_d):
        return (jax.nn.silu(x @ w_g) * (x @ w_u)) @ w_d

    def experts(x, w_r, gate_w, up, down, s_g, s_u, s_d, w_sg):
        prob = jax.nn.softmax(x @ w_r, axis=-1)             # [T, experts]
        top, idx = jax.lax.top_k(prob, s["top_k"])          # bias: zeros
        weight = top / top.sum(-1, keepdims=True) \
            if config["norm_topk_prob"] else top

        @jax.checkpoint
        def share_of(expert):
            e, g_e, u_e, d_e = expert
            mine = (weight * (idx == s["offset"] + e)).sum(-1, keepdims=True)
            return mine * gated(x, g_e, u_e, d_e)

        def add_expert(out, expert):
            return out + share_of(expert), None

        shared = jax.nn.sigmoid(x @ w_sg) * gated(x, s_g, s_u, s_d)
        return jax.lax.scan(add_expert, shared,
                            (jnp.arange(s["held"]), gate_w, up, down))[0]

    def layer(kind):
        mixer_n = _MIXER_PARAMS[kind]

        @jax.checkpoint
        def run(x, weights):
            norm_1, mixer_w = weights[0], weights[1:1 + mixer_n]
            norm_2, ffn_w = weights[1 + mixer_n], weights[2 + mixer_n:]
            x = x + (gated_delta_net if kind == LINEAR else gated_attention)(
                rms(x, norm_1), *mixer_w)
            return x + experts(rms(x, norm_2), *ffn_w)
        return run

    @jax.checkpoint
    def nll_rows(x, labels, norm_w, w_head):
        logp = jax.nn.log_softmax(rms(x, norm_w) @ w_head, axis=-1)
        return -jnp.take_along_axis(logp, labels[:, None], axis=-1)

    def sequence_loss_sum(one):
        tok, lab = one
        it = iter(params)
        x = next(it)[tok]                                   # [T, D]
        for kind in s["kinds"]:
            count = 2 + _MIXER_PARAMS[kind] + _FFN_PARAMS
            x = layer(kind)(x, [next(it) for _ in range(count)])
        norm_w, w_head = next(it), next(it)
        rows = min(_HEAD_ROWS, length)
        assert length % rows == 0
        return jax.lax.map(
            lambda part: nll_rows(*part, norm_w, w_head),
            (x.reshape(length // rows, rows, -1),
             lab.reshape(length // rows, rows))).sum()

    with jax.default_matmul_precision("highest"):
        feeds = tuple(jnp.asarray(feed[n]) for n in FEEDS)
        return jax.lax.map(sequence_loss_sum, feeds).sum() / feeds[0].size
