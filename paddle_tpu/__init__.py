"""paddle_tpu — a TPU-native deep-learning framework with PaddlePaddle's
capability surface, built from scratch on JAX/XLA/PJRT/Pallas.

Two execution universes, like the reference (SURVEY.md §1) but collapsed onto
XLA: eager = per-op compiled HLO dispatch with a GradNode tape; static =
whole-program compilation via `paddle_tpu.jit` (to_static / TrainStep) with
GSPMD partitioning over device meshes (`paddle_tpu.parallel`).
"""

from __future__ import annotations

# first, so that its `paddle_tpu.import` span (closed at the bottom of this
# file) covers everything the package loads
from paddle_tpu import profiler  # noqa: F401

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core import dtype as _dtype_mod
from paddle_tpu.core.dtype import (  # noqa: F401
    bfloat16, bool_, complex64, complex128, float16, float32, float64,
    int8, int16, int32, int64, uint8,
)
from paddle_tpu.core.place import (  # noqa: F401
    CPUPlace, Place, TPUPlace, device_count, expected_place, get_device,
    set_device,
)
from paddle_tpu.core.random import get_rng_state, seed, set_rng_state  # noqa: F401
from paddle_tpu.core.tensor import Parameter, Tensor  # noqa: F401
from paddle_tpu.autograd.engine import (  # noqa: F401
    enable_grad, grad, is_grad_enabled, no_grad, set_grad_enabled,
)
from paddle_tpu.ops.registry import C_OPS as _C_ops  # noqa: F401
from paddle_tpu.ops.registry import OPS as _OPS
from paddle_tpu.utils.flags import get_flags, set_flags  # noqa: F401

__version__ = "0.1.0"

# ---------------------------------------------------------------- creation


def _default_float():
    from paddle_tpu.utils.flags import flag

    return _dtype_mod.to_jax_dtype(flag("FLAGS_default_dtype"))


def get_default_dtype():
    return _dtype_mod.dtype_name(_default_float())


def set_default_dtype(d):
    set_flags({"FLAGS_default_dtype": _dtype_mod.dtype_name(_dtype_mod.to_jax_dtype(d))})


def _place_device():
    return expected_place().jax_device()


def to_tensor(data, dtype=None, place=None, stop_gradient=True) -> Tensor:
    """paddle.to_tensor — host data -> device tensor."""
    if isinstance(data, Tensor):
        v = data._value
        if dtype is not None:
            v = v.astype(_dtype_mod.to_jax_dtype(dtype))
        return Tensor(v, stop_gradient=stop_gradient)
    arr = np.asarray(data)
    if dtype is not None:
        arr = arr.astype(_dtype_mod.to_jax_dtype(dtype))
    elif arr.dtype == np.float64:
        arr = arr.astype(np.float32)
    elif arr.dtype == np.int64 and arr.dtype.kind == "i":
        pass
    dev = place.jax_device() if place is not None else _place_device()
    return Tensor(jax.device_put(arr, dev), stop_gradient=stop_gradient)


def _creation(fn):
    def wrapper(*args, dtype=None, **kwargs):
        d = _dtype_mod.to_jax_dtype(dtype) if dtype is not None else None
        out = fn(*args, dtype=d, **kwargs)
        return Tensor(jax.device_put(out, _place_device()))

    return wrapper


@_creation
def zeros(shape, dtype=None):
    return jnp.zeros(shape, dtype or _default_float())


@_creation
def ones(shape, dtype=None):
    return jnp.ones(shape, dtype or _default_float())


@_creation
def full(shape, fill_value, dtype=None):
    return jnp.full(shape, fill_value, dtype or _default_float())


@_creation
def empty(shape, dtype=None):
    return jnp.zeros(shape, dtype or _default_float())


@_creation
def arange(start, end=None, step=1, dtype=None):
    if end is None:
        start, end = 0, start
    return jnp.arange(start, end, step, dtype)


@_creation
def linspace(start, stop, num, dtype=None):
    return jnp.linspace(start, stop, num, dtype=dtype or _default_float())


@_creation
def eye(num_rows, num_columns=None, dtype=None):
    return jnp.eye(num_rows, num_columns, dtype=dtype or _default_float())


def zeros_like(x, dtype=None):
    return Tensor(jnp.zeros_like(x._value, dtype=_dtype_mod.to_jax_dtype(dtype)))


def ones_like(x, dtype=None):
    return Tensor(jnp.ones_like(x._value, dtype=_dtype_mod.to_jax_dtype(dtype)))


def full_like(x, fill_value, dtype=None):
    return Tensor(jnp.full_like(x._value, fill_value, dtype=_dtype_mod.to_jax_dtype(dtype)))


def empty_like(x, dtype=None):
    return zeros_like(x, dtype)


# ---------------------------------------------------------------- random


def _next_key():
    from paddle_tpu.core.random import default_generator

    return default_generator.next_key()


def rand(shape, dtype=None):
    d = _dtype_mod.to_jax_dtype(dtype) or _default_float()
    return Tensor(jax.random.uniform(_next_key(), tuple(shape), dtype=d))


def randn(shape, dtype=None):
    d = _dtype_mod.to_jax_dtype(dtype) or _default_float()
    return Tensor(jax.random.normal(_next_key(), tuple(shape), dtype=d))


def uniform(shape, dtype=None, min=-1.0, max=1.0, seed=0):  # noqa: A002
    d = _dtype_mod.to_jax_dtype(dtype) or _default_float()
    return Tensor(jax.random.uniform(_next_key(), tuple(shape), dtype=d,
                                     minval=min, maxval=max))


def normal(mean=0.0, std=1.0, shape=None):
    out = jax.random.normal(_next_key(), tuple(shape)) * std + mean
    return Tensor(out.astype(_default_float()))


def randint(low, high=None, shape=(1,), dtype="int64"):
    if high is None:
        low, high = 0, low
    d = _dtype_mod.to_jax_dtype(dtype)
    return Tensor(jax.random.randint(_next_key(), tuple(shape), low, high, dtype=d))


def randperm(n, dtype="int64"):
    return Tensor(jax.random.permutation(_next_key(), n).astype(_dtype_mod.to_jax_dtype(dtype)))


def bernoulli(x):
    return Tensor(jax.random.bernoulli(_next_key(), x._value).astype(x.dtype))


def multinomial(x, num_samples=1, replacement=False):
    logits = jnp.log(jnp.clip(x._value, 1e-30, None))
    out = jax.random.categorical(_next_key(), logits, axis=-1,
                                 shape=logits.shape[:-1] + (num_samples,))
    return Tensor(out.astype(jnp.int64))


# ------------------------------------------------- top-level op functions

# Every yaml op becomes paddle_tpu.<op> (reference: python/paddle/tensor/*
# wrappers over _C_ops).
_g = globals()
for _name in _OPS:
    if not _name.startswith("_") and _name not in _g:
        _g[_name] = getattr(_C_ops, _name)

# paddle-style aliases
mm = _g["matmul"]
concat_ = None
del concat_


def numel(x):
    return to_tensor(x.size, dtype="int64")


def shape(x):
    return to_tensor(np.asarray(x.shape, dtype=np.int32))


def is_tensor(x):
    return isinstance(x, Tensor)


def allclose(x, y, rtol=1e-5, atol=1e-8, equal_nan=False):
    return _C_ops.isclose(x, y, rtol=rtol, atol=atol, equal_nan=equal_nan).numpy().all()


def equal_all(x, y):
    return to_tensor(bool((x._value == y._value).all()))


def assign(x, output=None):
    v = x._value if isinstance(x, Tensor) else jnp.asarray(x)
    if output is not None:
        output._inplace_update(v)
        return output
    return Tensor(v)


def clone(x):
    return x.clone()


def increment(x, value=1.0):
    x._inplace_update(x._value + value)
    return x


# Tensor methods for every yaml op marked method: true
from paddle_tpu.core import tensor as _tensor_mod  # noqa: E402


def _install_methods():
    for name, opdef in _OPS.items():
        if not opdef.method or name.startswith("_"):
            continue
        if hasattr(Tensor, name):
            continue
        setattr(Tensor, name, _make_method(name))
        if opdef.inplace:
            setattr(Tensor, opdef.inplace, _make_inplace_method(name))


def _make_method(name):
    fn = getattr(_C_ops, name)

    def method(self, *args, **kwargs):
        return fn(self, *args, **kwargs)

    method.__name__ = name
    return method


def _make_inplace_method(name):
    fn = getattr(_C_ops, name)

    def method(self, *args, **kwargs):
        out = fn(self.detach(), *args, **kwargs)
        self._inplace_update(out._value)
        return self

    method.__name__ = name + "_"
    return method


_install_methods()

# ---------------------------------------------------------------- subpackages

from paddle_tpu import amp  # noqa: E402,F401
from paddle_tpu import autograd  # noqa: E402,F401
from paddle_tpu import io  # noqa: E402,F401
from paddle_tpu import jit  # noqa: E402,F401
from paddle_tpu import nn  # noqa: E402,F401
from paddle_tpu import optimizer  # noqa: E402,F401
from paddle_tpu import parallel  # noqa: E402,F401
from paddle_tpu import audio  # noqa: E402,F401
from paddle_tpu import device  # noqa: E402,F401
from paddle_tpu import distribution  # noqa: E402,F401
from paddle_tpu import hub  # noqa: E402,F401
from paddle_tpu import onnx  # noqa: E402,F401
from paddle_tpu import sysconfig  # noqa: E402,F401
from paddle_tpu import incubate  # noqa: E402,F401
from paddle_tpu import text  # noqa: E402,F401
from paddle_tpu import inference  # noqa: E402,F401
from paddle_tpu import metric  # noqa: E402,F401
from paddle_tpu import geometric  # noqa: E402,F401
from paddle_tpu import regularizer  # noqa: E402,F401
from paddle_tpu import signal  # noqa: E402,F401
from paddle_tpu import sparse  # noqa: E402,F401
from paddle_tpu.tensor import fft, linalg  # noqa: E402,F401
from paddle_tpu.tensor.array import (  # noqa: E402,F401
    array_length, array_read, array_write, create_array,
)
from paddle_tpu import static  # noqa: E402,F401
from paddle_tpu import vision  # noqa: E402,F401
from paddle_tpu import quantization  # noqa: E402,F401
from paddle_tpu import hapi  # noqa: E402,F401
from paddle_tpu.hapi import Model, summary  # noqa: E402,F401
from paddle_tpu.utils.flops import flops  # noqa: E402,F401
from paddle_tpu.framework import io_api as _io_api  # noqa: E402
save = _io_api.save
load = _io_api.load

distributed = parallel  # paddle.distributed-compatible alias


def DataParallel(model, *args, **kwargs):
    from paddle_tpu.parallel.data_parallel import DataParallel as _DP

    return _DP(model, *args, **kwargs)

# top-level surface completion (numpy-alikes, constants, finfo/iinfo,
# ParamAttr/create_parameter, paddle.batch, generated in-place variants)
from paddle_tpu import extras as _extras  # noqa: E402

_extras.install_extras(globals())

import sys as _sys  # noqa: E402

_extras.bind_tensor_methods(_sys.modules[__name__])

from paddle_tpu import callbacks  # noqa: F401,E402
from paddle_tpu import utils  # noqa: F401,E402
from paddle_tpu import version  # noqa: F401,E402
from paddle_tpu import strings  # noqa: F401,E402
from paddle_tpu.core.selected_rows import (  # noqa: F401,E402
    SelectedRows, get_tensor_from_selected_rows, merge_selected_rows,
)

profiler.record("paddle_tpu.import", profiler.LOADED_NS)
