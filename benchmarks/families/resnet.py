"""Family `resnet`: ImageNet ResNets (He et al. 2015) on the layers DSL.

build() is chip_smoke._build_resnet with the sizes read from the
configuration file; reference_loss() is the same architecture written out
in plain jax.numpy float32 (no kernels, no fusion, no AMP), which the run
holds the program's first loss to.
"""

import functools

import numpy as np

def build(config):
    """(main, startup, loss) of one AMP train step. The programs'
    random_seed is fixed: the run's seed reaches the weights through the
    executor's PRNG counter (traffic/train_steps.py says why)."""
    import paddle_tpu as fluid
    from paddle_tpu import models
    from paddle_tpu.framework import unique_name

    side, chans = config["image_size"], config["image_channels"]
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with unique_name.guard(), fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[chans, side, side],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        loss, _, _ = models.build_image_classifier(
            functools.partial(models.resnet_imagenet,
                              depth=config["depth"]),
            img, label, class_dim=config["num_classes"])
        opt = fluid.amp.decorate(
            fluid.optimizer.Momentum(learning_rate=config["learning_rate"],
                                     momentum=config["momentum"]),
            level=config["amp_level"])
        opt.minimize(loss, startup_program=startup)
    return main, startup, loss


def make_batch(config, batch, rng):
    """One host batch: float32 images, int32 labels; `batch` items."""
    side, chans = config["image_size"], config["image_channels"]
    return {"img": rng.standard_normal((batch, chans, side, side),
                                       dtype=np.float32),
            "label": rng.integers(0, config["num_classes"], (batch, 1))
            .astype(np.int32)}


def items_per_batch(feed):
    """An item is an image."""
    return int(feed["img"].shape[0])


# Multiply-adds of one 224x224 forward pass by depth (the paper's Table 1
# rounds these to 1.8, 3.6, 3.8, 7.6 and 11.3e9 and calls them FLOPs;
# bench.py's 4.09e9 for depth 50 is this count too). A multiply-add is two
# floating-point operations, as the chip's peak counts them.
_FWD_MACS_224 = {18: 1.82e9, 34: 3.67e9, 50: 4.09e9, 101: 7.85e9,
                 152: 11.58e9}


def required_flops_per_item(config):
    """FLOPs one image needs in a train step: forward, gradient of the
    inputs and gradient of the weights, three forward passes' worth.
    Scaled by area from the 224x224 table."""
    area = (config["image_size"] / 224.0) ** 2
    return 3.0 * 2.0 * _FWD_MACS_224[config["depth"]] * area


def reference_loss(config, params, feed):
    """Mean softmax cross-entropy of the train-mode forward pass (batch
    norm on batch statistics) in float32. `params`: the trainable
    parameters in the order the program created them (conv filter OIHW,
    bn scale, bn bias, ..., fc weight [in, out], fc bias) — the one thing
    taken from the program besides the arrays themselves."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    eps = 1e-5

    def conv_bn(it, x, stride, pad, relu):
        w, scale, bias = next(it), next(it), next(it)
        y = lax.conv_general_dilated(
            x, w, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        mean = y.mean((0, 2, 3), keepdims=True)
        var = ((y - mean) ** 2).mean((0, 2, 3), keepdims=True)
        y = (y - mean) * lax.rsqrt(var + eps)
        y = y * scale[None, :, None, None] + bias[None, :, None, None]
        return jnp.maximum(y, 0) if relu else y

    expansion = config["bottleneck_expansion"]

    # a block is a jax.checkpoint: jax.grad of this loss then keeps the
    # blocks' inputs and one block's activations, not the whole network's
    # (which at float32 and batch 256 would not fit the chip)
    @functools.partial(jax.checkpoint, static_argnums=(2, 3))
    def block(x, block_params, stride, projected):
        it = iter(block_params)
        short = conv_bn(it, x, stride, 0, False) if projected else x
        if expansion == 1:
            y = conv_bn(it, x, stride, 1, True)
            y = conv_bn(it, y, 1, 1, False)
        else:
            y = conv_bn(it, x, stride, 0, True)
            y = conv_bn(it, y, 1, 1, True)
            y = conv_bn(it, y, 1, 0, False)
        return jnp.maximum(short + y, 0)

    it = iter(params)
    with jax.default_matmul_precision("highest"):
        x = conv_bn(it, jnp.asarray(feed["img"], jnp.float32), 2, 3, True)
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2),
                              [(0, 0), (0, 0), (1, 1), (1, 1)])
        for i, (n, ch) in enumerate(zip(config["stage_blocks"],
                                        config["stage_channels"])):
            for j in range(n):
                stride = 2 if (j == 0 and i > 0) else 1
                projected = x.shape[1] != ch * expansion or stride != 1
                convs = (2 if expansion == 1 else 3) + int(projected)
                x = block(x, [next(it) for _ in range(3 * convs)], stride,
                          projected)
        x = x.mean((2, 3))
        w, b = next(it), next(it)
        logits = x @ w + b
        logp = jax.nn.log_softmax(logits, axis=-1)
        label = jnp.asarray(feed["label"]).reshape(-1)
        return -jnp.take_along_axis(logp, label[:, None], axis=1).mean()
