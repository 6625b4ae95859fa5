"""paddle_tpu: a TPU-native deep-learning framework with PaddlePaddle-Fluid's
capabilities (reference: /root/reference, see SURVEY.md).

The public surface mirrors `paddle.fluid` (reference python/paddle/fluid/
__init__.py) so reference training scripts port by changing the import:
Program/Block IR + layers DSL, append_backward autodiff, an Executor that
compiles program blocks to XLA, TPUPlace alongside CPUPlace (CUDAPlace is a
source-compat alias), optimizers-as-ops, save/load, readers and datasets.
"""

from .framework import (Program, Block, Variable, Parameter, program_guard,
                        name_scope,
                        default_main_program, default_startup_program,
                        switch_main_program, switch_startup_program,
                        unique_name)
from .executor import (CPUPlace, CUDAPlace, TPUPlace, Executor, LoDTensor,
                       Scope, global_scope, scope_guard)
from .backward import append_backward, calc_gradient
from . import ops
from . import layers
from . import initializer
from .initializer import (Constant, ConstantInitializer, Normal,
                          NormalInitializer, Uniform, UniformInitializer,
                          Xavier, XavierInitializer, MSRA, MSRAInitializer)
from . import optimizer
from .optimizer import (SGD, SGDOptimizer, Momentum, MomentumOptimizer,
                        Adagrad, AdagradOptimizer, Adam, AdamOptimizer,
                        Adamax, AdamaxOptimizer, DecayedAdagrad,
                        DecayedAdagradOptimizer, Adadelta, AdadeltaOptimizer,
                        RMSProp, RMSPropOptimizer, Ftrl, FtrlOptimizer)
from .param_attr import ParamAttr
from . import regularizer
from . import clip
from .data_feeder import DataFeeder
from . import io
from . import nets
from . import models
from . import reader
from . import dataset
from .minibatch import batch
from . import parallel
from . import debugger
from . import profiler
from . import amp
from . import compat
from . import metrics
from . import average
from . import errors
from . import v2
from . import flags
from . import concurrency
from .concurrency import (make_channel, channel_send, channel_recv,
                          channel_close, Go, Select)
from . import telemetry
from . import tracing
from . import serving
from . import inspector
from . import roofline
from . import obs_server
obs_server.maybe_start_from_env()
from . import sentinel
sentinel.maybe_start_from_env()
from .parallel import transpiler
from .parallel.transpiler import DistributeTranspiler

__version__ = "0.1.0"
