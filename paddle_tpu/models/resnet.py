"""ResNet for ImageNet (ResNet-50/101/152) and CIFAR-10.

Reference: benchmark/paddle/image/resnet.py (v2 config DSL) and
tests/book/test_image_classification.py resnet_cifar10. Rebuilt on the fluid
layers DSL: conv+bn blocks map to single XLA fusions; all matmuls/convs land
on the MXU. resnet50 is the benchmark's `resnet50.train-bs256` cell.
"""

from __future__ import annotations

from .. import layers


def conv_bn_layer(input, ch_out, filter_size, stride, padding, act="relu",
                  is_test=False):
    conv = layers.conv2d(input=input, num_filters=ch_out,
                         filter_size=filter_size, stride=stride,
                         padding=padding, act=None, bias_attr=False)
    return layers.batch_norm(input=conv, act=act, is_test=is_test)


def shortcut(input, ch_in, ch_out, stride, is_test=False):
    if ch_in != ch_out or stride != 1:
        return conv_bn_layer(input, ch_out, 1, stride, 0, act=None,
                             is_test=is_test)
    return input


def basicblock(input, ch_in, ch_out, stride, is_test=False):
    short = shortcut(input, ch_in, ch_out, stride, is_test=is_test)
    conv1 = conv_bn_layer(input, ch_out, 3, stride, 1, is_test=is_test)
    conv2 = conv_bn_layer(conv1, ch_out, 3, 1, 1, act=None, is_test=is_test)
    return layers.elementwise_add(x=short, y=conv2, act="relu")


def bottleneck(input, ch_in, ch_out, stride, is_test=False):
    short = shortcut(input, ch_in, ch_out * 4, stride, is_test=is_test)
    conv1 = conv_bn_layer(input, ch_out, 1, stride, 0, is_test=is_test)
    conv2 = conv_bn_layer(conv1, ch_out, 3, 1, 1, is_test=is_test)
    conv3 = conv_bn_layer(conv2, ch_out * 4, 1, 1, 0, act=None,
                          is_test=is_test)
    return layers.elementwise_add(x=short, y=conv3, act="relu")


def layer_warp(block_fn, input, ch_in, ch_out, count, stride, is_test=False):
    out = block_fn(input, ch_in, ch_out, stride, is_test=is_test)
    ch_in = out.shape[1]
    for _ in range(count - 1):
        out = block_fn(out, ch_in, ch_out, 1, is_test=is_test)
    return out


def resnet_imagenet(input, class_dim=1000, depth=50, is_test=False):
    """ResNet for 224x224 ImageNet (reference benchmark/paddle/image/resnet.py)."""
    cfg = {
        18: ([2, 2, 2, 2], basicblock),
        34: ([3, 4, 6, 3], basicblock),
        50: ([3, 4, 6, 3], bottleneck),
        101: ([3, 4, 23, 3], bottleneck),
        152: ([3, 8, 36, 3], bottleneck),
    }
    stages, block_fn = cfg[depth]
    conv1 = conv_bn_layer(input, ch_out=64, filter_size=7, stride=2,
                          padding=3, is_test=is_test)
    pool1 = layers.pool2d(input=conv1, pool_size=3, pool_stride=2,
                          pool_padding=1, pool_type="max")
    res1 = layer_warp(block_fn, pool1, 64, 64, stages[0], 1, is_test=is_test)
    res2 = layer_warp(block_fn, res1, res1.shape[1], 128, stages[1], 2,
                      is_test=is_test)
    res3 = layer_warp(block_fn, res2, res2.shape[1], 256, stages[2], 2,
                      is_test=is_test)
    res4 = layer_warp(block_fn, res3, res3.shape[1], 512, stages[3], 2,
                      is_test=is_test)
    pool2 = layers.pool2d(input=res4, pool_size=7, pool_type="avg",
                          global_pooling=True)
    out = layers.fc(input=pool2, size=class_dim, act=None)
    return out


def resnet50(input, class_dim=1000, is_test=False):
    """The benchmark's ResNet-50 (BASELINE.json north star)."""
    return resnet_imagenet(input, class_dim=class_dim, depth=50,
                           is_test=is_test)


def resnet_cifar10(input, class_dim=10, depth=32, is_test=False):
    """ResNet for 32x32 CIFAR-10 (reference tests/book/
    test_image_classification.py resnet_cifar10)."""
    assert (depth - 2) % 6 == 0
    n = (depth - 2) // 6
    conv1 = conv_bn_layer(input, ch_out=16, filter_size=3, stride=1,
                          padding=1, is_test=is_test)
    res1 = layer_warp(basicblock, conv1, 16, 16, n, 1, is_test=is_test)
    res2 = layer_warp(basicblock, res1, 16, 32, n, 2, is_test=is_test)
    res3 = layer_warp(basicblock, res2, 32, 64, n, 2, is_test=is_test)
    pool = layers.pool2d(input=res3, pool_size=8, pool_type="avg",
                         global_pooling=True)
    out = layers.fc(input=pool, size=class_dim, act=None)
    return out
