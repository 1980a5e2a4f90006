"""paddle_tpu: a TPU-native deep-learning framework.

Capability-parity with the reference (pangyoki/Paddle ~v2.0) redesigned for
TPU: JAX/XLA is the compute substrate (eager ops over jnp + tape autograd,
compiled training steps via jit/pjit over device meshes), Pallas for hot
kernels, XLA collectives over ICI for distribution. The public API mirrors
paddle 2.x so reference users can switch with minimal edits.
"""
from __future__ import annotations

__version__ = "0.1.0"

from . import core
from .core import (CPUPlace, CUDAPinnedPlace, CUDAPlace, TPUPlace,
                   XPUPlace, get_device,
                   set_device, is_compiled_with_tpu, seed, set_flags,
                   get_flags, set_default_dtype, get_default_dtype)
from .core.dtypes import (bool_ as bool8, bfloat16, complex128, complex64,
                          float16, float32, float64, int16, int32, int64,
                          int8, uint8)
from .framework import (Tensor, to_tensor, no_grad, enable_grad,
                        is_grad_enabled, set_grad_enabled, in_dygraph_mode)
from .framework import Parameter  # noqa: F401
from .ops import *  # noqa: F401,F403
from .ops import registry as _registry  # noqa: F401

# namespace-style access: paddle_tpu.tensor.xxx mirrors paddle.tensor
from . import ops as tensor  # noqa: F401
from . import linalg  # noqa: F401
from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import amp  # noqa: F401
from . import jit  # noqa: F401
from . import static  # noqa: F401
from . import io  # noqa: F401
from . import metric  # noqa: F401
from . import distributed  # noqa: F401
from . import vision  # noqa: F401
from . import text  # noqa: F401
from . import inference  # noqa: F401
from . import utils  # noqa: F401
from . import models  # noqa: F401
from . import distribution  # noqa: F401
from . import compat  # noqa: F401
from . import device  # noqa: F401
from . import regularizer  # noqa: F401
from . import sysconfig  # noqa: F401
from . import incubate  # noqa: F401
from . import quant  # noqa: F401
from .batch import batch  # noqa: F401  (paddle.batch is the function)
from . import hapi  # noqa: F401
from . import observability  # noqa: F401
from . import profiler  # noqa: F401
from . import onnx  # noqa: F401
from .hapi import Model  # noqa: F401
from .distributed.parallel import DataParallel  # noqa: F401
from .nn.param_attr import ParamAttr  # noqa: F401


def is_tensor(x):
    return isinstance(x, Tensor)


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_npu() -> bool:
    return False


def disable_static(place=None):
    return None


def enable_static():
    from .static import _enable_static_mode
    _enable_static_mode()


def grad(outputs, inputs, grad_outputs=None, retain_graph=None,
         create_graph=False, only_inputs=True, allow_unused=False,
         no_grad_vars=None):
    """paddle.grad equivalent (PartialGradEngine analogue,
    /root/reference/paddle/fluid/imperative/partial_grad_engine.cc)."""
    from .autograd_utils import partial_grad
    return partial_grad(outputs, inputs, grad_outputs, retain_graph,
                        create_graph, allow_unused, no_grad_vars)


def save(obj, path, protocol=4):
    from .serialization import save as _save
    return _save(obj, path, protocol)


def load(path, **kwargs):
    from .serialization import load as _load
    return _load(path, **kwargs)


def summary(net, input_size=None, dtypes=None, input=None):
    from .hapi.summary import summary as _summary
    return _summary(net, input_size, dtypes, input)


def flops(net, input_size, custom_ops=None, print_detail=False):
    return 0

# reference top-level re-exports: hapi callbacks namespace + platform
# introspection shims (python/paddle/__init__.py)
from .hapi import callbacks  # noqa: F401,E402


def get_cudnn_version():
    """Reference paddle.get_cudnn_version: None — no cuDNN on TPU/XLA
    (the reference returns None when CUDA is absent too)."""
    return None


def monkey_patch_math_varbase():
    """Reference internal: Tensor operator overloads. Applied at import
    here (framework.py patches Tensor); kept as an explicit no-op."""


def monkey_patch_variable():
    """Reference internal: static Variable operator overloads. Applied
    at import (static/program.py Var); kept as an explicit no-op."""
