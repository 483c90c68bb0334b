"""paddle_tpu: a TPU-native deep-learning framework with the PaddlePaddle
API surface, built on jax/XLA/Pallas.

Architecture (vs. reference /root/reference, see SURVEY.md §8):
  * Tensor        = handle over jax.Array (framework/tensor.py)
  * autograd      = tape over jax.vjp (autograd/tape.py)
  * op layer      = one registry of pure-jax bodies (ops/)
  * static graph  = jax.jit tracing (jit/), StableHLO export
  * distributed   = jax.sharding.Mesh + GSPMD (distributed/)
  * hot kernels   = Pallas TPU (ops/pallas/)
"""
from __future__ import annotations

import jax as _jax

# Paddle's default integer dtype is int64 (python/paddle/tensor/creation.py
# to_tensor); jax's x32 mode would silently truncate. Enable x64 — the
# framework's own creation logic keeps float defaults at float32/bfloat16,
# so TPU matmuls stay on the MXU.
_jax.config.update("jax_enable_x64", True)

# -- core types ------------------------------------------------------------
from .framework import dtype as _dtype_mod
from .framework.dtype import (  # noqa: F401
    bool_, uint8, int8, int16, int32, int64, float16, bfloat16, float32,
    float64, complex64, complex128, DType)
bool = bool_  # paddle.bool
from .framework.tensor import Tensor, to_tensor, is_tensor  # noqa: F401
from .framework import tensor_methods as _tensor_methods  # noqa: F401  (patches Tensor)
from .framework.random import seed, get_rng_state, set_rng_state  # noqa: F401

# -- autograd --------------------------------------------------------------
from .autograd import no_grad, enable_grad, is_grad_enabled, \
    set_grad_enabled, grad  # noqa: F401
from . import autograd  # noqa: F401

# -- ops into the flat namespace ------------------------------------------
from .ops.math import (  # noqa: F401
    add, subtract, multiply, divide, floor_divide, remainder, mod, pow,
    matmul, scale, neg, abs, exp, expm1, log, log2, log10, log1p, sqrt,
    rsqrt, square, sin, cos, tan, asin, acos, atan, sinh, cosh, tanh, asinh,
    acosh, atanh, erf, erfinv, floor, ceil, round, trunc, sign, reciprocal,
    sigmoid, digamma, lgamma, i0, frac, deg2rad, rad2deg, angle, conj, real,
    imag, clip, maximum, minimum, fmax, fmin, atan2, hypot, lerp, stanh,
    logit, multiplex, isnan, isinf, isfinite, nan_to_num, cumsum, cumprod,
    cummax, cummin, logcumsumexp, addmm, inner, outer, heaviside, gcd, lcm,
    diff, trace, kron, cross, dot, polygamma)
from .ops.reduction import (  # noqa: F401
    mean, amax, amin, prod, var, std, nansum, nanmean, count_nonzero,
    logsumexp, argmax, argmin, median, nanmedian, quantile, kthvalue, mode)
from .ops.reduction import sum_ as sum, max_ as max, min_ as min, \
    all_ as all, any_ as any  # noqa: F401
from .ops.manipulation import (  # noqa: F401
    reshape, transpose, concat, stack, unstack, split, chunk, squeeze,
    unsqueeze, flatten, tile, expand, expand_as, broadcast_to,
    broadcast_tensors, gather, gather_nd, scatter, scatter_nd_add,
    scatter_nd, index_select, index_sample, index_add, index_put,
    take_along_axis, put_along_axis, flip, roll, rot90, where, nonzero,
    masked_select, masked_fill, topk, sort, argsort, searchsorted, bucketize,
    unique, unique_consecutive, one_hot, tril, triu, tril_indices,
    triu_indices, diag, diagflat, diagonal, diag_embed, meshgrid, cast, pad,
    repeat_interleave, as_strided, moveaxis, swapaxes, atleast_1d,
    atleast_2d, atleast_3d, view, unfold, tensordot, crop, slice,
    strided_slice, numel, shape, increment, assign, bincount, histogram)
from .ops.manipulation import unstack as unbind  # noqa: F401
from .ops.creation import (  # noqa: F401
    zeros, ones, full, empty, zeros_like, ones_like, full_like, empty_like,
    arange, linspace, logspace, eye, clone, complex, polar, rand, randn,
    uniform, normal, gaussian, randint, randint_like, randperm, multinomial,
    bernoulli, poisson, standard_normal, standard_gamma)
from .ops.linalg import (  # noqa: F401
    mm, bmm, mv, t, einsum, norm, dist, cholesky, cholesky_solve, qr, svd,
    pinv, det, slogdet, solve, triangular_solve, lstsq, lu, eig, eigh,
    eigvals, eigvalsh, matrix_power, matrix_rank, corrcoef, cov,
    histogramdd, bitwise_and, bitwise_or, bitwise_xor, bitwise_not,
    bitwise_left_shift, bitwise_right_shift)
from .ops.linalg import inv as inverse  # noqa: F401
from .ops.comparison import (  # noqa: F401
    equal, not_equal, greater_than, greater_equal, less_than, less_equal,
    equal_all, allclose, isclose, logical_and, logical_or, logical_xor,
    logical_not, is_empty)
from .ops.math_extra import (  # noqa: F401
    logaddexp, copysign, ldexp, nextafter, signbit, sinc, frexp, gammaln,
    gammainc, gammaincc, multigammaln, i0e, i1, i1e, sgn, isneginf,
    isposinf, isreal, isin, take, trapezoid, cumulative_trapezoid, vander,
    renorm, nanquantile, histogram_bin_edges, floor_mod, reduce_as, add_n,
    cdist, pdist, hsplit, vsplit, dsplit, tensor_split, hstack, vstack,
    dstack, row_stack, column_stack, block_diag, cartesian_prod,
    combinations, diagonal_scatter, select_scatter, slice_scatter,
    masked_scatter, index_fill, reverse, unflatten, view_as, as_complex,
    as_real, rank, broadcast_shape, shard_index, log_normal, binomial,
    is_complex, is_floating_point, is_integer)

# -- subpackages -----------------------------------------------------------
from . import ops  # noqa: F401
from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import amp  # noqa: F401
from . import io  # noqa: F401
from . import linalg  # noqa: F401  (namespace module below)
from . import framework  # noqa: F401
from .framework.io import save, load  # noqa: F401
from . import jit  # noqa: F401
from . import device  # noqa: F401
from .device import set_device, get_device, CPUPlace, CUDAPlace, \
    CUDAPinnedPlace, XPUPlace, TPUPlace  # noqa: F401
from . import flags as _flags_mod
from .flags import set_flags, get_flags  # noqa: F401
from . import vision  # noqa: F401
from . import models  # noqa: F401
from . import metric  # noqa: F401
from . import observability  # noqa: F401
from . import profiler  # noqa: F401
from .hapi import Model, summary  # noqa: F401
from . import hapi  # noqa: F401
from . import distribution  # noqa: F401
from . import fft  # noqa: F401
from . import signal  # noqa: F401
from . import sparse  # noqa: F401
from . import static  # noqa: F401
from . import inference  # noqa: F401
from . import quantization  # noqa: F401
from . import audio  # noqa: F401
from . import text  # noqa: F401
from . import strings  # noqa: F401
from . import geometric  # noqa: F401
from . import incubate  # noqa: F401
from . import utils  # noqa: F401
from . import onnx  # noqa: F401
from . import version  # noqa: F401
from . import regularizer  # noqa: F401


# -- surface part 2: misc top-level API -----------------------------------
from .framework.dtype import dtype, float8_e4m3fn, float8_e5m2  # noqa: F401
from .nn.layer import ParamAttr  # noqa: F401
from .distributed.fleet.meta_parallel.parallel_wrappers import \
    DataParallel  # noqa: F401
from .framework.random import (  # noqa: F401
    get_rng_state as get_cuda_rng_state, set_rng_state as set_cuda_rng_state)


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """Printing options for Tensor repr (reference
    python/paddle/tensor/to_string.py:38); maps onto numpy printoptions."""
    import numpy as np
    kw = {}
    if precision is not None:
        kw["precision"] = precision
    if threshold is not None:
        kw["threshold"] = threshold
    if edgeitems is not None:
        kw["edgeitems"] = edgeitems
    if linewidth is not None:
        kw["linewidth"] = linewidth
    if sci_mode is not None:
        kw["suppress"] = not sci_mode
    np.set_printoptions(**kw)


class LazyGuard:
    """Parameter-init deferral scope (reference python/paddle/nn/initializer/
    lazy_init.py).  Initialization here is cheap jax host arrays, so the
    guard is a no-op context kept for API parity."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def create_parameter(shape, dtype, name=None, attr=None, is_bias=False,
                     default_initializer=None):
    from .nn.layer import Layer
    helper = Layer()
    p = helper.create_parameter(shape, attr=attr, dtype=dtype,
                                is_bias=is_bias,
                                default_initializer=default_initializer)
    if name:
        p.name = name
    return p


def batch(reader, batch_size, drop_last=False):
    """Batch a sample reader into a batched reader (legacy fluid API,
    reference python/paddle/reader/decorator.py)."""
    def batched():
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf
    return batched


def flops(net, input_size, custom_ops=None, print_detail=False):
    """Estimate FLOPs of a network at the given input size (reference
    python/paddle/hapi/dynamic_flops.py): conv/linear dominate; counts
    multiply-adds as 2 ops like the reference."""
    from . import nn as _nn
    x = zeros(input_size, dtype="float32")
    counts = [0]

    def make_post(layer):
        def post(lyr, inputs, outputs):
            import numpy as _np
            out_shape = getattr(outputs, "shape", None)
            if custom_ops and type(lyr) in custom_ops:  # replaces builtin
                counts[0] += int(custom_ops[type(lyr)](lyr, inputs, outputs))
            elif isinstance(lyr, _nn.Linear):
                counts[0] += 2 * int(_np.prod(out_shape)) * \
                    lyr.weight.shape[0]
            elif isinstance(lyr, (_nn.Conv1D, _nn.Conv2D, _nn.Conv3D)):
                w = lyr.weight
                kernel_ops = int(_np.prod(w.shape[1:]))
                counts[0] += 2 * int(_np.prod(out_shape)) * kernel_ops
        return post

    handles = []
    for lyr in net.sublayers(include_self=True):
        handles.append(lyr.register_forward_post_hook(make_post(lyr)))
    was_training = net.training
    net.eval()
    net(x)
    if was_training:
        net.train()
    for h in handles:
        h.remove()
    if print_detail:
        print(f"Total FLOPs: {counts[0]}")
    return counts[0]


def check_shape(shape):
    """Validate a shape argument (reference python/paddle/utils/
    layers_utils.py:474)."""
    if isinstance(shape, (list, tuple)):
        for s in shape:
            if isinstance(s, int) and s < -1:
                raise ValueError(f"invalid dim {s} in shape {shape}")
    return shape


def tolist(x):
    """Return the tensor data as (nested) python lists (reference
    python/paddle/tensor/to_string.py tolist)."""
    return x.tolist()


def disable_signal_handler():
    """Paddle installs C++ signal handlers; there are none here (jax/XLA
    runtime) so this is a documented no-op."""


def iinfo(dtype):
    import numpy as np
    from .framework.dtype import to_np_dtype
    return np.iinfo(to_np_dtype(dtype))


def finfo(dtype):
    import ml_dtypes
    from .framework.dtype import to_np_dtype
    return ml_dtypes.finfo(to_np_dtype(dtype))

__version__ = "0.1.0"

# paddle.disable_static / enable_static compat: this framework is always
# "dygraph" at the API level; jit.to_static provides the compiled path.
_static_mode = False


def disable_static(place=None):
    global _static_mode
    _static_mode = False


def enable_static():
    """Switch to static-graph building (paddle.static.*); ops applied to
    static Variables record a Program DAG instead of executing."""
    global _static_mode
    _static_mode = True


def in_dynamic_mode():
    return not _static_mode


def in_dynamic_or_pir_mode():
    return True


def is_compiled_with_cuda():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_cinn():
    return False


def is_compiled_with_distribute():
    return True


def is_compiled_with_tpu():
    return True


def get_default_dtype():
    return _dtype_mod.dtype(_default_dtype[0])


def set_default_dtype(d):
    _default_dtype[0] = _dtype_mod.dtype(d).name


_default_dtype = ["float32"]


# -- top-level in-place function forms (paddle.sin_(x) etc.) ---------------
def _export_inplace_functions():
    import sys
    mod = sys.modules[__name__]
    names = [
        "abs", "acos", "add", "addmm", "asin", "atan", "bernoulli", "bitwise_and",
        "bitwise_left_shift", "bitwise_not", "bitwise_or",
        "bitwise_right_shift", "bitwise_xor", "cast", "cauchy", "ceil",
        "clip", "copysign", "cos", "cumprod", "cumsum", "digamma", "divide",
        "equal", "erf", "erfinv", "exp", "expm1", "exponential", "fill",
        "flatten", "floor", "floor_divide", "floor_mod", "frac", "gammainc",
        "gammaincc", "gammaln", "gcd", "geometric", "greater_equal",
        "greater_than", "hypot", "i0", "index_add", "index_fill",
        "index_put", "lcm", "ldexp", "lerp", "less_equal", "less_than",
        "lgamma", "log", "log10", "log1p", "log2", "log_normal", "logical_and",
        "logical_not", "logical_or", "logical_xor", "logit", "masked_fill",
        "masked_scatter", "mod", "multigammaln", "multiply", "nan_to_num",
        "neg", "normal", "not_equal", "polygamma", "pow", "put_along_axis",
        "reciprocal", "remainder", "renorm", "reshape", "round", "rsqrt",
        "scale", "scatter", "sigmoid", "sign", "sin", "sinc", "sinh",
        "sqrt", "square", "squeeze", "subtract", "t", "tan", "tanh",
        "transpose", "tril", "triu", "trunc", "uniform", "unsqueeze",
        "where", "zero",
    ]
    from .framework.tensor import Tensor as _T

    def make(n):
        method = n + "_"

        def fn(x, *args, **kwargs):
            return getattr(x, method)(*args, **kwargs)
        fn.__name__ = method
        fn.__doc__ = f"In-place form of paddle.{n} (mutates x)."
        return fn

    for n in names:
        if hasattr(_T, n + "_") and not hasattr(mod, n + "_"):
            setattr(mod, n + "_", make(n))


_export_inplace_functions()
