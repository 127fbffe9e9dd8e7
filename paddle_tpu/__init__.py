"""paddle_tpu — a TPU-native deep-learning framework with the
capabilities of PaddlePaddle (Fluid era).

The public surface mirrors ``paddle.fluid`` (see SURVEY.md for the layer
map of the reference at /root/reference): Program/Block/Op static-graph
IR, Executor, dygraph, layers/optimizers, distributed fleet — built
TPU-first on JAX/XLA (whole-program compilation, mesh collectives over
ICI, Pallas kernels) rather than ported from CUDA/C++.

Both import styles work:
    import paddle_tpu as fluid;  fluid.layers.fc(...)
    import paddle_tpu.fluid as fluid  (alias package)
"""
from . import framework
from . import ir  # noqa: F401
from .ir import IrGraph  # noqa: F401
from .framework import (  # noqa: F401
    Program,
    Variable,
    default_main_program,
    default_startup_program,
    in_dygraph_mode,
    program_guard,
    name_scope,
)
from .core import (  # noqa: F401
    CPUPlace,
    CUDAPinnedPlace,
    CUDAPlace,
    LoDTensor,
    LoDTensorArray,
    Scope,
    TPUPlace,
    global_scope,
    scope_guard,
)
from .core import dtypes as _dtypes  # noqa: F401
from .core import enforce  # noqa: F401
from .core.flags import get_flags, set_flags  # noqa: F401
from .executor import Executor  # noqa: F401
from .async_executor import AsyncExecutor, DataFeedDesc  # noqa: F401
from . import trainer_factory  # noqa: F401
from . import nets  # noqa: F401
from . import lod_tensor  # noqa: F401
from .lod_tensor import (  # noqa: F401
    create_lod_tensor,
    create_random_int_lodtensor,
)
from . import average  # noqa: F401
from . import debugger  # noqa: F401
from . import communicator  # noqa: F401
from .communicator import Communicator  # noqa: F401
from . import evaluator  # noqa: F401
from . import input  # noqa: F401
from .param_attr import ParamAttr, WeightNormParamAttr  # noqa: F401
from . import initializer  # noqa: F401
from . import layers  # noqa: F401
from . import optimizer  # noqa: F401
from . import regularizer  # noqa: F401
from . import clip  # noqa: F401
from . import backward  # noqa: F401
from .backward import gradients  # noqa: F401
from .layers.io import data as _layers_data  # noqa: F401
from .layers.io import fluid_data as data  # noqa: F401
from .compiler import CompiledProgram, ExecutionStrategy, BuildStrategy  # noqa: F401
from . import io  # noqa: F401
from .io import save, load  # noqa: F401
from . import checkpoint  # noqa: F401
from . import dygraph  # noqa: F401
from . import nn  # noqa: F401
from . import metrics  # noqa: F401
from . import observability  # noqa: F401
from . import profiler  # noqa: F401
from .reader import DataLoader  # noqa: F401
from .data_feeder import DataFeeder  # noqa: F401
from . import unique_name_api as unique_name  # noqa: F401
from . import install_check  # noqa: F401
from . import transpiler  # noqa: F401
# NOTE: `paddle_tpu.dataset` is the readers package (paddle.dataset in
# the reference); the fluid Dataset FACTORY surface lives at top level
# (fluid.DatasetFactory) and as `dataset_module`.
from . import dataset  # noqa: F401
from . import dataset_module  # noqa: F401
from .dataset_module import DatasetFactory  # noqa: F401
from .transpiler import DistributeTranspiler, DistributeTranspilerConfig  # noqa: F401
from . import incubate  # noqa: F401
from . import contrib  # noqa: F401
from . import inference  # noqa: F401
from . import serving  # noqa: F401
from . import distribution  # noqa: F401
from . import metric_api as metric  # noqa: F401
from . import tensor_api as tensor  # noqa: F401

__version__ = "0.1.0"

# `fluid`-style sub-namespace so that `import paddle_tpu as paddle;
# paddle.fluid.layers...` also works.
import sys as _sys

fluid = _sys.modules[__name__]
_sys.modules[__name__ + ".fluid"] = fluid


def set_global_seed(seed: int):
    default_main_program().random_seed = seed
    default_startup_program().random_seed = seed
