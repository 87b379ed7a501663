"""paddle_tpu — a TPU-native deep-learning framework.

Capability surface of PaddlePaddle (~v2.1, see SURVEY.md), designed
TPU-first: jax/XLA is the compute path (everything lowers to HLO and runs on
the MXU), `jax.sharding.Mesh` + named axes replace NCCL ring-ids, functional
transforms replace the imperative autograd engine, and Pallas kernels replace
hand-written CUDA where fusion matters.

Top-level namespace mirrors `python/paddle/__init__.py` of the reference.
"""
from __future__ import annotations

import time as _time

_import_t0 = _time.perf_counter_ns()

__version__ = "0.1.0"

from . import core  # noqa: F401
from .core import (  # noqa: F401
    CPUPlace,
    TPUPlace,
    get_default_dtype,
    get_device,
    get_flags,
    is_compiled_with_cuda,
    is_compiled_with_tpu,
    set_default_dtype,
    set_device,
    set_flags,
)
from .core.dtypes import (  # noqa: F401
    bfloat16,
    bool_,
    complex64,
    complex128,
    float16,
    float32,
    float64,
    int8,
    int16,
    int32,
    int64,
    uint8,
)
from .framework import get_rng_state, seed, set_rng_state  # noqa: F401
from .tensor import *  # noqa: F401,F403
from . import tensor  # noqa: F401

# Subpackages imported lazily to keep `import paddle_tpu` light are still
# eagerly wired for API parity (paddle exposes paddle.nn etc. on import).
from . import autograd  # noqa: F401  (isort: skip)
from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import amp  # noqa: F401
from . import io  # noqa: F401
from . import metric  # noqa: F401
from . import distributed  # noqa: F401
from . import vision  # noqa: F401
from . import jit  # noqa: F401
from . import static  # noqa: F401
from . import models  # noqa: F401
from . import profiler  # noqa: F401
from . import inference  # noqa: F401
from . import distribution  # noqa: F401
from . import text  # noqa: F401
from . import onnx  # noqa: F401
from . import utils  # noqa: F401
from . import incubate  # noqa: F401
from . import regularizer  # noqa: F401
from . import quantization  # noqa: F401
from . import ir  # noqa: F401
from .autograd import grad, no_grad, value_and_grad  # noqa: F401
from .framework.io import load, save  # noqa: F401
from .hapi.model import Model, summary  # noqa: F401
from .hapi.dynamic_flops import flops  # noqa: F401
from .hapi import callbacks  # noqa: F401
from .nn.layer import Layer, Parameter  # noqa: F401

# ------------------------------------------------------------- 2.x parity
# Names reference scripts use from the top level (python/paddle/__init__.py).
import jax as _jax
import numpy as _np

#: the array type: `isinstance(x, paddle.Tensor)` works on any jax array
Tensor = _jax.Array
#: dtype objects are numpy dtypes end-to-end
dtype = _np.dtype
bool = bool_  # noqa: A001  (paddle.bool is the bool dtype, like the ref)

from .batch import batch  # noqa: F401,E402
from . import hub  # noqa: F401,E402
from .core.device import (  # noqa: F401,E402
    CUDAPinnedPlace,
    CUDAPlace,
    NPUPlace,
    XPUPlace,
    get_cudnn_version,
    is_compiled_with_npu,
    is_compiled_with_rocm,
    is_compiled_with_xpu,
)
from .distributed.parallel import DataParallel  # noqa: F401,E402
from .framework import (  # noqa: F401,E402
    ParamAttr,
    create_parameter,
    disable_static,
    enable_static,
    get_cuda_rng_state,
    in_dynamic_mode,
    set_cuda_rng_state,
    set_grad_enabled,
)
from .tensor.random import check_shape  # noqa: F401,E402


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """Reference: `paddle.set_printoptions` (tensor/to_string.py). Arrays
    print via numpy, so this forwards to `np.set_printoptions`."""
    kwargs = {}
    if precision is not None:
        kwargs["precision"] = precision
    if threshold is not None:
        kwargs["threshold"] = threshold
    if edgeitems is not None:
        kwargs["edgeitems"] = edgeitems
    if linewidth is not None:
        kwargs["linewidth"] = linewidth
    if sci_mode is not None:
        kwargs["suppress"] = not sci_mode
    _np.set_printoptions(**kwargs)


from .framework.tensor_patch import monkey_patch_tensor  # noqa: E402


def monkey_patch_variable():
    """Reference: fluid Variable operator patching. Operators work
    natively on jax arrays; this installs the METHOD spellings
    (`t.numpy()`, `t.unsqueeze(0)`, ...) — see framework/tensor_patch."""
    monkey_patch_tensor()


def monkey_patch_math_varbase():  # reference: dygraph VarBase patching
    """Same patch as monkey_patch_variable (one tensor class here)."""
    monkey_patch_tensor()


monkey_patch_tensor()   # like the reference, patch at import


# install static-mode dispatch last: wraps the curated op set so calls on
# static.Variable record into the Program (see static/program.py)
from .static.program import _install_dispatch as _isd  # noqa: E402
_isd()
del _isd

# what importing the package took, first line to last, as a span of the
# program's profiler (`profiler.spans()`)
profiler.record_span("import.paddle_tpu", _import_t0,
                     _time.perf_counter_ns())
del _import_t0
