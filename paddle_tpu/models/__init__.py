"""Model zoo: the reference's benchmark + book models, built on the layers DSL.

Reference model scripts: benchmark/paddle/image/{alexnet,googlenet,resnet,vgg,
smallnet_mnist_cifar}.py and python/paddle/fluid/tests/book/*. Each builder
takes the input Variable(s) and returns logits/prediction Variables; training
glue (loss, optimizer) stays in user code or in `build_classifier`.
"""

from .alexnet import alexnet
from .block_diffusion_moe import block_diffusion_moe_lm
from .conv_moe import conv_moe_lm
from .gated_window_moe import gated_window_moe_lm
from .gdn_moe import gdn_moe_lm
from .googlenet import googlenet
from .granite_hybrid import granite_hybrid_lm
from .kda_moe import kda_moe_lm
from .looped_lm import looped_lm
from .mla_moe import mla_moe_lm
from .mnist import mnist_conv, mnist_mlp
from .nemotron_h import nemotron_h_lm
from .resnet import resnet_cifar10, resnet_imagenet, resnet50
from .smallnet import smallnet_mnist_cifar
from .transformer import transformer_lm
from .vgg import vgg16, vgg19
from .window_moe import window_moe_lm
from .common import balance_routers, build_image_classifier

__all__ = [
    "alexnet", "block_diffusion_moe_lm", "conv_moe_lm",
    "gated_window_moe_lm", "gdn_moe_lm", "googlenet",
    "granite_hybrid_lm", "kda_moe_lm", "looped_lm", "mla_moe_lm",
    "mnist_conv", "mnist_mlp",
    "nemotron_h_lm",
    "resnet_cifar10", "resnet_imagenet", "resnet50",
    "smallnet_mnist_cifar", "transformer_lm",
    "vgg16", "vgg19", "window_moe_lm", "balance_routers",
    "build_image_classifier",
]
