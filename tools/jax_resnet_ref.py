"""Framework-independent ceiling probe: hand-rolled pure-JAX ResNet-50
training step (NHWC, bf16 compute, f32 master weights + momentum), the
model of the benchmark's `resnet50.train-bs256` cell. Used to separate
framework overhead from the chip/XLA ceiling when tuning that cell.

    python3 tools/jax_resnet_ref.py [batch]    (768; the benchmark's cell: 256)
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

BATCH = 768
STEPS = 20
WARMUP = 3


def conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def bn(x, scale, bias):
    # training-mode batch stats in f32, like the framework's batch_norm
    xf = x.astype(jnp.float32)
    m = jnp.mean(xf, axis=(0, 1, 2))
    v = jnp.maximum(jnp.mean(jnp.square(xf), axis=(0, 1, 2)) - m * m, 0.0)
    y = (xf - m) * jax.lax.rsqrt(v + 1e-5) * scale + bias
    return y.astype(x.dtype)


CFG = [(3, 64, 256, 1), (4, 128, 512, 2), (6, 256, 1024, 2),
       (3, 512, 2048, 2)]


def init_params(rng):
    p = {}

    def cw(key, kh, kw, ci, co):
        k = rng.standard_normal((kh, kw, ci, co)).astype(np.float32)
        p[key] = k * np.sqrt(2.0 / (kh * kw * ci))

    def bnp(key, c):
        p[key + "/s"] = np.ones((c,), np.float32)
        p[key + "/b"] = np.zeros((c,), np.float32)

    cw("stem", 7, 7, 3, 64)
    bnp("stem_bn", 64)
    ci = 64
    for si, (n, mid, out, _stride) in enumerate(CFG):
        for bi in range(n):
            pre = f"s{si}b{bi}"
            cw(pre + "/c1", 1, 1, ci if bi == 0 else out, mid)
            cw(pre + "/c2", 3, 3, mid, mid)
            cw(pre + "/c3", 1, 1, mid, out)
            for j in (1, 2, 3):
                bnp(pre + f"/bn{j}", [mid, mid, out][j - 1])
            if bi == 0:
                cw(pre + "/proj", 1, 1, ci, out)
                bnp(pre + "/bnp", out)
        ci = out
    p["fc/w"] = rng.standard_normal((2048, 1000)).astype(np.float32) * 0.01
    p["fc/b"] = np.zeros((1000,), np.float32)
    return p


def forward(params, x):
    h = x.astype(jnp.bfloat16)
    h = conv(h, params["stem"].astype(jnp.bfloat16), 2)
    h = jax.nn.relu(bn(h, params["stem_bn/s"], params["stem_bn/b"]))
    h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    for si, (n, mid, out, stride) in enumerate(CFG):
        for bi in range(n):
            pre = f"s{si}b{bi}"
            st = stride if bi == 0 else 1
            y = conv(h, params[pre + "/c1"].astype(jnp.bfloat16), st)
            y = jax.nn.relu(bn(y, params[pre + "/bn1/s"],
                               params[pre + "/bn1/b"]))
            y = conv(y, params[pre + "/c2"].astype(jnp.bfloat16), 1)
            y = jax.nn.relu(bn(y, params[pre + "/bn2/s"],
                               params[pre + "/bn2/b"]))
            y = conv(y, params[pre + "/c3"].astype(jnp.bfloat16), 1)
            y = bn(y, params[pre + "/bn3/s"], params[pre + "/bn3/b"])
            if bi == 0:
                h = conv(h, params[pre + "/proj"].astype(jnp.bfloat16), st)
                h = bn(h, params[pre + "/bnp/s"], params[pre + "/bnp/b"])
            h = jax.nn.relu(h + y)
    h = jnp.mean(h.astype(jnp.float32), axis=(1, 2))
    return h @ params["fc/w"] + params["fc/b"]


def loss_fn(params, x, y):
    logits = forward(params, x)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, y, axis=-1))


@jax.jit
def step(params, mom, x, y):
    loss, g = jax.value_and_grad(loss_fn)(params, x, y)
    new_m = {k: 0.9 * mom[k] + g[k] for k in g}
    new_p = {k: params[k] - 0.1 * new_m[k] for k in params}
    return loss, new_p, new_m


def main(batch=BATCH):
    dev = jax.devices()[0]
    rng = np.random.default_rng(0)
    params = {k: jax.device_put(v, dev)
              for k, v in init_params(rng).items()}
    mom = {k: jax.device_put(np.zeros_like(np.asarray(v)), dev)
           for k, v in params.items()}
    x = jax.device_put(
        rng.standard_normal((batch, 224, 224, 3), dtype=np.float32), dev)
    y = jax.device_put(rng.integers(0, 1000, (batch, 1)).astype(np.int32),
                       dev)
    for _ in range(WARMUP):
        loss, params, mom = step(params, mom, x, y)
    float(np.asarray(loss))
    t0 = time.perf_counter()
    for _ in range(STEPS):
        loss, params, mom = step(params, mom, x, y)
    final = float(np.asarray(loss))
    dt = time.perf_counter() - t0
    img_s = batch * STEPS / dt
    mfu = img_s * 3 * 4.09e9 / 197e12
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    print(f"pure-jax resnet50 ({dev.device_kind}, batch {batch}): "
          f"{img_s:.1f} img/s  ({dt / STEPS * 1000:.1f} ms/step, "
          f"mfu {mfu:.3f}, loss {final:.3f}, peak bytes {peak})")


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else BATCH)
