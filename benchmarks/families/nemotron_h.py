"""Family `nemotron_h`: hybrid Mamba-2 / mixture-of-experts / attention
decoder (NVIDIA Nemotron-H, Nemotron 3 Nano) through
paddle_tpu.models.nemotron_h_lm, with the sizes read from the
configuration file; reference_loss() is the same model in plain jax.numpy
float32, written from the layer equations and not from the program's ops.

The configuration is one chip's share of a deployment (its `deployment`):
`n_routed_experts` experts of `n_routed_experts_published` are held here
from `expert_offset` on, the router keeps its published width, and what
the absent experts would add is left out, in the program and in the
reference alike; `vocab_size` rows of the vocabulary; the first
`num_hidden_layers` layers of the published pattern.
"""

import numpy as np


def _sizes(config):
    """The model's sizes under the names this file uses."""
    return dict(
        d=config["hidden_size"], pattern=config["hybrid_override_pattern"],
        heads=config["mamba_num_heads"], p=config["mamba_head_dim"],
        g=config["n_groups"], n=config["ssm_state_size"],
        k=config["conv_kernel"], q_heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], hd=config["head_dim"],
        experts=config["n_routed_experts_published"],
        held=config["n_routed_experts"], offset=config["expert_offset"],
        top_k=config["num_experts_per_tok"],
        f=config["moe_intermediate_size"],
        fs=config["moe_shared_expert_intermediate_size"],
        eps=config["layer_norm_epsilon"], v=config["vocab_size"],
        t=config["sequence_length"])


def build(config):
    """(main, startup, loss) of one AMP train step. The programs'
    random_seed is fixed: the run's seed reaches the weights through the
    executor's PRNG counter (traffic/train_steps.py says why)."""
    import paddle_tpu as fluid
    from paddle_tpu import models
    from paddle_tpu.framework import unique_name

    s = _sizes(config)
    assert len(s["pattern"]) == config["num_hidden_layers"]
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with unique_name.guard(), fluid.program_guard(main, startup):
        tok = fluid.layers.data(name="tok", shape=[-1, s["t"]],
                                dtype="int64", append_batch_size=False)
        lab = fluid.layers.data(name="lab", shape=[-1, s["t"]],
                                dtype="int64", append_batch_size=False)
        loss = models.nemotron_h_lm(
            tok, lab, vocab_size=s["v"], pattern=s["pattern"],
            hidden_size=s["d"], mamba_num_heads=s["heads"],
            mamba_head_dim=s["p"], n_groups=s["g"], ssm_state_size=s["n"],
            num_attention_heads=s["q_heads"],
            num_key_value_heads=s["kv_heads"], head_dim=s["hd"],
            n_routed_experts=s["experts"], num_experts_per_tok=s["top_k"],
            moe_intermediate_size=s["f"],
            moe_shared_expert_intermediate_size=s["fs"],
            experts_held=s["held"], expert_offset=s["offset"],
            routed_scaling_factor=config["routed_scaling_factor"],
            norm_topk_prob=config["norm_topk_prob"], conv_kernel=s["k"],
            chunk_size=config["chunk_size"], epsilon=s["eps"],
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
    """One host batch of `batch` sequences: int32 ids drawn from the
    vocabulary's slice, and as labels the ids one position on."""
    t = config["sequence_length"]
    ids = rng.integers(0, config["vocab_size"], (batch, t + 1))
    return {"tok": ids[:, :-1].astype(np.int32),
            "lab": ids[:, 1:].astype(np.int32)}


def items_per_batch(feed):
    """An item is a token position that gets a loss."""
    return int(feed["tok"].size)


def layer_flops_per_item(config):
    """{layer kind: forward FLOPs one token needs in one such layer},
    and under "head" the sliced output head's. Nothing recomputed,
    nothing for experts held elsewhere.
    M: both projections, and the chunked scan's four products (scores
    C B^T per group and the masked product with x at half their chunk x
    chunk blocks, which is what causality needs; the chunk states; the
    entering state's read-out). E: the router, the shared expert, and the
    EXPECTED rows routed here: top_k x held / experts of a token's.
    *: q, k, v, o and causal attention at half the T x T scores."""
    s = _sizes(config)
    d, t, chunk = s["d"], s["t"], config["chunk_size"]
    di, gn = s["heads"] * s["p"], s["g"] * s["n"]
    scan = (chunk * gn + chunk * di) + 2 * 2 * di * s["n"]
    qo, kv = s["q_heads"] * s["hd"], s["kv_heads"] * s["hd"]
    return {
        "M": 2 * d * (2 * di + 2 * gn + s["heads"]) + 2 * di * d + scan,
        "E": (2 * d * s["experts"] + 4 * d * s["fs"]
              + s["top_k"] * s["held"] / s["experts"] * 4 * d * s["f"]),
        "*": 2 * d * (2 * qo + 2 * kv) + 2 * t * qo,
        "head": 2 * d * s["v"]}


def required_flops_per_item(config):
    """FLOPs one token needs in a train step (forward x 3)."""
    per = layer_flops_per_item(config)
    return 3.0 * (sum(per[kind] for kind in config["hybrid_override_pattern"])
                  + per["head"])


def expert_layers(config):
    """Expert layers of a step: the pattern's "E"s."""
    return config["hybrid_override_pattern"].count("E")


def expert_product_cost(config, rows):
    """(FLOPs, bytes) of one train step's grouped expert products in ONE
    expert layer when `rows` (token, slot) pairs were routed to the held
    experts: the up and down products forward, and for each its two
    backward products (6 products of rows x d x f), nothing recomputed.
    Bytes: each product reads its two operands and writes its result
    once, in bf16, the held experts' weights once a product."""
    s = _sizes(config)
    d, f, held = s["d"], s["f"], s["held"]
    flops = 6 * 2.0 * rows * d * f
    per_product = 2.0 * (rows * d + rows * f + held * d * f)
    return flops, 6 * per_product


def scan_layers(config):
    """Mamba layers of a step: the pattern's "M"s."""
    return config["hybrid_override_pattern"].count("M")


def scan_cost(config, tokens):
    """(FLOPs, bytes) of one train step's selective scan in ONE Mamba
    layer over `tokens` positions: the four products of
    layer_flops_per_item forward and twice that backward; bytes: x, B, C,
    dt read and y written forward (bf16, dt float32), and backward the
    same operands and dy read and their four gradients written."""
    s = _sizes(config)
    chunk = config["chunk_size"]
    di, gn = s["heads"] * s["p"], s["g"] * s["n"]
    flops = 3.0 * tokens * ((chunk * gn + chunk * di) + 4 * di * s["n"])
    row = 2.0 * (di + 2 * gn) + 4.0 * s["heads"]       # x, B, C, dt
    return flops, tokens * ((row + 2.0 * di) + (2 * row + 2.0 * di))


def reference_loss(config, params, feed):
    """Mean next-token cross-entropy of the forward pass in float32, from
    the layer equations (ISSUE 30, section 1), one sequence at a time;
    each layer is a jax.checkpoint, so jax.grad keeps one layer's
    activations at a time. Independent of the program's ops: the scan is
    the step-by-step recurrence (lax.scan over t, a jax.checkpoint around
    each block of steps so its gradient keeps a state a block and not
    one a step), the experts a loop over the held experts with a mask,
    attention a masked softmax one query head at a time.

    `params`: the trainable parameters in the order the program created
    them: embedding; per layer its norm's weight and then, M: in_proj,
    conv filter [C, K] and bias, dt_bias, A_log, D, the gated norm's
    weight, out_proj; E: router [D, E], the held experts' up [held, D, F]
    and down [held, F, D], the shared expert's up and down; *: q, k, v,
    o; then the final norm's weight and the head. The router's selection
    bias is a buffer, not among them: taken as the zeros the
    configuration assumes.

    Departures from the published model, all in the configuration's
    `assumed`: no rotary embedding (nemotron_h's attention applies none);
    the held share of experts, vocabulary and depth."""
    import jax
    import jax.numpy as jnp

    s = _sizes(config)
    eps, heads, p, g, n = s["eps"], s["heads"], s["p"], s["g"], s["n"]
    di, gn = heads * p, g * n
    block = 64          # steps of the recurrence inside one checkpoint

    def rms(x, w, groups=1):
        xg = x.reshape(x.shape[:-1] + (groups, -1))
        xg = xg * jax.lax.rsqrt((xg ** 2).mean(-1, keepdims=True) + eps)
        return xg.reshape(x.shape) * w

    def relu2(x):
        return jnp.maximum(x, 0.0) ** 2

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

    def experts(x, w_r, up, down, s_up, s_down):
        scores = jax.nn.sigmoid(x @ w_r)                      # [t, experts]
        top, idx = jax.lax.top_k(scores, s["top_k"])
        gate = config["routed_scaling_factor"] * top / (
            top.sum(-1, keepdims=True) + 1e-20
            if config["norm_topk_prob"] else 1.0)
        def add_expert(out, expert):
            e, up_e, down_e = expert
            mine = (gate * (idx == s["offset"] + e)).sum(-1, keepdims=True)
            return out + mine * (relu2(x @ up_e) @ down_e), None

        return jax.lax.scan(add_expert, relu2(x @ s_up) @ s_down,
                            (jnp.arange(s["held"]), up, down))[0]

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
            scores = q_h @ k[j // per_kv].T / np.sqrt(hd)
            prob = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
            return prob @ v[j // per_kv]

        out = jax.lax.map(head, (q, jnp.arange(s["q_heads"])))
        return out.transpose(1, 0, 2).reshape(t, -1) @ w_o

    mixers = {"M": (mamba, 8), "E": (experts, 5), "*": (attention, 4)}

    def sequence_loss_sum(pair):
        tok, lab = pair
        it = iter(params)
        x = next(it)[tok]
        for kind in s["pattern"]:
            fn, count = mixers[kind]
            norm_w, weights = next(it), [next(it) for _ in range(count)]
            x = jax.checkpoint(
                lambda x, norm_w, weights, fn=fn:
                x + fn(rms(x, norm_w), *weights))(x, norm_w, weights)

        @jax.checkpoint
        def head(x, norm_w, w):
            logp = jax.nn.log_softmax(rms(x, norm_w) @ w, axis=-1)
            return -jnp.take_along_axis(logp, lab[:, None], axis=-1).sum()

        return head(x, next(it), next(it))

    with jax.default_matmul_precision("highest"):
        tok, lab = jnp.asarray(feed["tok"]), jnp.asarray(feed["lab"])
        return jax.lax.map(sequence_loss_sum, (tok, lab)).sum() / tok.size
