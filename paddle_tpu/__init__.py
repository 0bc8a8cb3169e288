"""paddle_tpu — a TPU-native deep-learning framework.

Brand-new framework with the capability surface of the reference
(liwanfei999/Paddle, PaddlePaddle ~v2.0) re-designed TPU-first:
JAX/XLA is the compiler+executor, Pallas provides custom kernels,
jax.sharding/pjit provides the distributed runtime. See SURVEY.md for the
reference layer map this mirrors.

Top-level namespace mirrors `paddle.*` so reference users can switch.
"""
from __future__ import annotations

import time as _time

_import_t0_ns = _time.perf_counter_ns()     # the package's first line

__version__ = "0.1.0"

# The program's set-up begins here (profiler/trace.py, ``phase``): the
# process's age now is what lay before the program (the interpreter, jax,
# the device runtime's start, the caller's own files), and the package's
# own import is the first phase.
from .profiler import trace as _trace  # noqa: E402

_import_phase = _trace.import_began(_import_t0_ns)

from . import autograd, compat, core, framework  # noqa: F401
from .autograd import enable_grad, grad, no_grad, set_grad_enabled  # noqa: F401
from .core import (CPUPlace, CUDAPinnedPlace, CUDAPlace, TPUPlace,  # noqa: F401
                   XPUPlace, get_default_dtype, get_flags,
                   is_compiled_with_cuda, is_compiled_with_tpu, seed,
                   set_default_dtype, set_flags)
from .core.place import device_count, get_device, set_device  # noqa: F401
from .core.rng import get_rng_state, set_rng_state  # noqa: F401
# the reference's CUDA-named rng accessors map to the device rng stream
from .core.rng import get_rng_state as get_cuda_rng_state  # noqa: F401
from .core.rng import set_rng_state as set_cuda_rng_state  # noqa: F401
from .device import get_cudnn_version, is_compiled_with_xpu  # noqa: F401
from .framework import ParamAttr, Parameter, Tensor, to_tensor  # noqa: F401
from .framework.lazy import LazyGuard  # noqa: F401
from .framework.printoptions import set_printoptions  # noqa: F401

# dtype names at top level (paddle.float32 style)
from .core.dtype import (bfloat16, bool_, complex64, complex128,  # noqa: F401
                         float16, float32, float64, int8, int16, int32, int64,
                         uint8)

# the op library — import * exposes every paddle.tensor op at top level,
# matching paddle's `from .tensor.math import *` pattern.
from . import tensor  # noqa: F401
from .tensor import *  # noqa: F401,F403
from .tensor import (abs, all, any, max, min, pow, round, slice, sum)  # noqa: F401,A004

from . import nn  # noqa: F401,E402
from . import optimizer  # noqa: F401,E402
from . import io  # noqa: F401,E402
from . import metric  # noqa: F401,E402
from . import amp  # noqa: F401,E402
from . import device  # noqa: F401,E402


def __getattr__(name):  # PEP 562: lazy fluid (it imports back into here)
    if name == "fluid":
        import importlib

        mod = importlib.import_module(".fluid", __name__)
        globals()["fluid"] = mod
        return mod
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
from . import jit  # noqa: F401,E402
from .hapi import callbacks  # noqa: F401,E402
from . import static  # noqa: F401,E402
from .static import create_parameter  # noqa: F401,E402
from . import distributed  # noqa: F401,E402
from . import resilience  # noqa: F401,E402
from . import vision  # noqa: F401,E402
from . import ops  # noqa: F401,E402
from . import incubate  # noqa: F401,E402
from . import text  # noqa: F401,E402
from . import quantization  # noqa: F401,E402
from . import onnx  # noqa: F401,E402
from . import batch as _batch_mod  # noqa: E402
from .batch import batch  # noqa: F401,E402
from . import dataset  # noqa: F401,E402
from . import distribution  # noqa: F401,E402
from . import reader  # noqa: F401,E402
from . import regularizer  # noqa: F401,E402
from . import sysconfig  # noqa: F401,E402
from . import utils  # noqa: F401,E402
from .distributed.parallel import DataParallel  # noqa: F401,E402
from .framework.io import load, save  # noqa: F401,E402
from .hapi.model import Model  # noqa: F401,E402
from .jit.api import to_static  # noqa: F401,E402
from . import inference  # noqa: F401,E402
from . import profiler  # noqa: F401,E402
from . import serving  # noqa: F401,E402

# paddle.disable_static / enable_static compat: this framework is always
# "dygraph" at the API level; jit/pjit is the static path.


def disable_static(place=None):
    return None


def enable_static():
    return None


def in_dynamic_mode() -> bool:
    return True


class NoGradGuard(no_grad):
    pass


def is_grad_enabled():
    from .autograd import tape

    return tape.is_grad_enabled()


def summary(net, input_size=None, dtypes=None, input=None):  # noqa: A002
    from .hapi.model_summary import summary as _summary

    return _summary(net, input_size, dtypes, input)


def flops(net, input_size, custom_ops=None, print_detail=False):
    from .hapi.model_summary import flops as _flops

    return _flops(net, input_size, custom_ops, print_detail)


_import_phase.end()
del _import_phase
