"""paddle_tpu — a TPU-native deep-learning framework.

A ground-up JAX/XLA/Pallas re-design with the full capability surface of the
reference framework (PaddlePaddle 2.3, Graphcore-IPU fork): eager + static graph,
hybrid-parallel distributed training (dp / mp / pp / sharding / moe / sp), AMP,
high-level Model API, and an inference path — all lowering to single XLA
computations per step (the whole-graph compile model the reference uses for IPU,
reference: paddle/fluid/platform/device/ipu/).
"""
from __future__ import annotations

__version__ = "0.1.0"

import os as _os

import jax as _jax

# paddle dtype parity: int64 default for ints, float64 representable
_jax.config.update("jax_enable_x64", True)

# Honor JAX_PLATFORMS=cpu through the config API as well, so that a caller
# who asked for the CPU gets it even where something imported earlier has
# already set jax_platforms (worker subprocesses inherit the variable).
if _os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
    _jax.config.update("jax_platforms", "cpu")

# one persistent compilation cache for every entry point
from . import _compile_cache  # noqa: E402

_compile_cache.configure()

# ---- core
from .core import (  # noqa: F401
    CPUPlace,
    CUDAPlace,
    Generator,
    Place,
    TPUPlace,
    Tensor,
    device_count,
    get_default_dtype,
    get_device,
    no_grad,
    enable_grad,
    seed,
    set_default_dtype,
    set_device,
    to_tensor,
)
from .core.tape import is_grad_enabled  # noqa: F401
from .core import memory  # noqa: F401 (allocator stats/flags surface)
from .core.ragged import (  # noqa: F401
    LoDTensor,
    RaggedTensor,
    create_lod_tensor,
)

# ---- functional op surface (paddle.* functions)
from .tensor_ops import *  # noqa: F401,F403
from .tensor_ops import methods as _methods

_methods.install()

from .tensor_ops import creation as _creation  # noqa: E402
from .tensor_ops import math as _math  # noqa: E402

# modules (populated lazily below to avoid import cycles)
from . import nn  # noqa: E402,F401
from . import optimizer  # noqa: E402,F401
from . import io  # noqa: E402,F401
from . import metric  # noqa: E402,F401
from . import vision  # noqa: E402,F401
from . import amp  # noqa: E402,F401
from . import autograd  # noqa: E402,F401
from . import distributed  # noqa: E402,F401
from . import static  # noqa: E402,F401
from . import jit  # noqa: E402,F401
from . import incubate  # noqa: E402,F401
from . import text  # noqa: E402,F401
from . import serving  # noqa: E402,F401
from . import profiler  # noqa: E402,F401
from . import sparse  # noqa: E402,F401
from . import utils  # noqa: E402,F401
from . import distribution  # noqa: E402,F401
from . import inference  # noqa: E402,F401
from . import fft  # noqa: E402,F401
from . import signal  # noqa: E402,F401
from . import quantization  # noqa: E402,F401
from . import device  # noqa: E402,F401
from . import onnx  # noqa: E402,F401
from . import regularizer  # noqa: E402,F401
from . import compat  # noqa: E402,F401
from . import sysconfig  # noqa: E402,F401
from . import callbacks  # noqa: E402,F401
from . import hub  # noqa: E402,F401
from . import reader  # noqa: E402,F401
from . import dataset  # noqa: E402,F401
from . import cost_model  # noqa: E402,F401
from . import _C_ops  # noqa: E402,F401

from .framework.io import load, save  # noqa: E402,F401
from .framework import grad, in_dynamic_mode, LazyGuard  # noqa: E402,F401
from .hapi import Model, summary  # noqa: E402,F401
from .nn.layer import ParamAttr  # noqa: E402,F401
from .batch import batch  # noqa: E402,F401

# paddle.disable_static/enable_static
from .static.mode import disable_static, enable_static, in_static_mode  # noqa: E402,F401


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_ipu() -> bool:
    return False


def is_compiled_with_npu() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True


def is_grad_enabled_():  # pragma: no cover - alias
    return is_grad_enabled()


def set_grad_enabled(flag: bool):
    from .core import tape

    class _Ctx:
        def __init__(self):
            self._prev = tape.is_grad_enabled()
            tape._set_grad_enabled(flag)

        def __enter__(self):
            return self

        def __exit__(self, *a):
            tape._set_grad_enabled(self._prev)

    return _Ctx()


def get_flags(flags=None):
    from .utils import flags as _flags

    return _flags.get_flags(flags)


def set_flags(flags):
    from .utils import flags as _flags

    return _flags.set_flags(flags)


# ---- parity batch (reference root __all__: python/paddle/__init__.py) ----
# dtype aliases: canonical dtype strings (Tensor.dtype returns these, so
# `x.dtype == paddle.float32` compares equal)
bool = "bool"  # noqa: A001 — parity with paddle.bool shadowing builtins
uint8 = "uint8"
int8 = "int8"
int16 = "int16"
int32 = "int32"
int64 = "int64"
float16 = "float16"
bfloat16 = "bfloat16"
float32 = "float32"
float64 = "float64"
complex64 = "complex64"
complex128 = "complex128"
dtype = str  # dtypes are canonical strings in this framework

from .core.place import CUDAPinnedPlace, NPUPlace  # noqa: E402,F401
from .distributed.parallel import DataParallel  # noqa: E402,F401
from .tensor_ops.math import bincount  # noqa: E402,F401
from .hapi.dynamic_flops import flops  # noqa: E402,F401


def shape(input):
    """Runtime shape as an int32 Tensor (reference: fluid.layers.shape)."""
    import jax.numpy as _jnp

    v = input._value if isinstance(input, Tensor) else _jnp.asarray(input)
    return Tensor(_jnp.asarray(v.shape, _jnp.int32))


def check_shape(shape):  # noqa: A002 — parity signature
    """Validate a shape argument (reference: fluid/layers/utils.py:376)."""
    if isinstance(shape, Tensor):
        if shape.dtype not in ("int32", "int64"):
            raise TypeError(f"shape tensor must be int32/int64, got {shape.dtype}")
        return
    for ele in shape:
        if isinstance(ele, Tensor):
            continue
        if not isinstance(ele, int):
            raise TypeError("All elements in `shape` must be integers")
        if ele < 0:
            raise ValueError("All elements in `shape` must be positive")


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """Numpy-backed print options (Tensors repr through numpy)."""
    import numpy as _np

    kw = {}
    if precision is not None:
        kw["precision"] = int(precision)
    if threshold is not None:
        kw["threshold"] = int(threshold)
    if edgeitems is not None:
        kw["edgeitems"] = int(edgeitems)
    if linewidth is not None:
        kw["linewidth"] = int(linewidth)
    if sci_mode is not None:
        kw["suppress"] = not sci_mode
    _np.set_printoptions(**kw)


def disable_signal_handler():
    """Parity no-op: the reference unhooks its C++ fault handlers; this
    runtime installs none."""


def get_cuda_rng_state():
    """Accelerator RNG state (maps to the global threefry key on TPU)."""
    from .core import rng as _rng

    return [_rng.default_generator().get_state()]


def set_cuda_rng_state(state):
    from .core import rng as _rng

    _rng.default_generator().set_state(
        state[0] if isinstance(state, (list, tuple)) else state)
