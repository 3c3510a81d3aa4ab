"""paddle_tpu: a TPU-native deep-learning framework with the reference's
(92lqllearning/Paddle) capability surface.

Compute path: jax/XLA (+ Pallas kernels); eager DyGraph autograd on a vjp
tape; jitted functional training steps for performance; distribution via
jax.sharding Mesh + XLA collectives over ICI.
"""
from __future__ import annotations

import time as _time

_T_IMPORT = _time.perf_counter()   # set-up's first phase counts from here

from . import autograd, dtype as _dtype_module, framework
from .autograd import enable_grad, no_grad, set_grad_enabled, grad
from .dtype import (bfloat16, bool_, complex64, complex128, finfo, float16,
                    float32, float64, iinfo, int8, int16, int32, int64, uint8)
from .framework import (CPUPlace, CUDAPlace, Generator, Place, TPUPlace,
                        XLAPlace, device_guard, get_default_dtype, get_device,
                        seed, set_default_dtype, set_device)
from .tensor import Parameter, Tensor, set_printoptions

# full op surface (also attaches Tensor methods/operators)
from .ops import *  # noqa: F401,F403
from .ops import linalg

from . import device
from . import jit
from . import nn
from . import optimizer
from . import distributed
from . import nlp
from . import vision
from . import amp
from . import utils
from . import io
from . import observability
from . import profiler
from . import debug
from . import resilience
from . import serving
from . import metric
from . import hapi
from .hapi import Model
from .hapi import callbacks_mod as callbacks
from .serialization import load, save
from .nn.layer import LazyGuard, ParamAttr
from .optimizer import L1Decay, L2Decay

from . import hub
from . import sysconfig
from . import regularizer
from . import audio
from . import geometric
from . import incubate
from . import onnx
from . import text
from . import static
from . import sparse
from . import quantization
from . import fft
from . import signal
from . import distribution
from . import version
from .utils.flops import flops, summary

bool = bool_  # paddle.bool

__version__ = '0.1.0'

disable_static = static.disable_static
enable_static = static.enable_static


# single source for the CUDA-compat shims: framework.py
from .framework import is_compiled_with_cuda  # noqa: E402


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_custom_device(device_type: str = '') -> bool:
    return False


def get_cudnn_version():
    return None  # no CUDA in this build


def in_dynamic_mode() -> bool:
    return not static.in_static_mode()


def is_grad_enabled():
    return autograd.is_grad_enabled()


def get_flags(flags=None):
    from . import flags as _flags
    return _flags.get_flags(flags)


def set_flags(flags):
    from . import flags as _flags
    return _flags.set_flags(flags)


# the package's own body and whatever it was first to import, as
# `paddle_setup_seconds_total{phase="import"}`: the last line
observability.telemetry.note_setup('import',
                                   _time.perf_counter() - _T_IMPORT)
