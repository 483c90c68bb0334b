"""paddle.inference — Config / Predictor deployment API.

Reference: paddle/fluid/inference/api/analysis_predictor.cc (:1719 Run,
:2752 ZeroCopyRun) + python/paddle/inference/wrapper.py.  The reference
runs an analysis pass pipeline over a serialized program then executes
zero-copy through the StandaloneExecutor; here the saved static Program
(static.save_inference_model) is loaded and each Run is one cached
jax.jit executable — XLA's fusion pipeline plays the role of the 309
analysis/IR passes.
"""
from __future__ import annotations

import numpy as np

__all__ = ["Config", "Predictor", "create_predictor", "PrecisionType",
           "PlaceType", "Tensor"]


class PrecisionType:
    Float32 = "float32"
    Half = "float16"
    Bfloat16 = "bfloat16"
    Int8 = "int8"


class PlaceType:
    CPU = "cpu"
    GPU = "gpu"
    TPU = "tpu"


class Config:
    """Reference: paddle_infer.Config (inference/api/paddle_analysis_config.h)."""

    def __init__(self, model_path=None, params_path=None):
        # static.save_inference_model writes <prefix>.pdmodel.pkl +
        # <prefix>.pdiparams.npz; accept the prefix (or the .pdmodel path)
        if model_path and model_path.endswith(".pdmodel"):
            model_path = model_path[: -len(".pdmodel")]
        self.model_prefix = model_path
        self.params_path = params_path
        self._precision = PrecisionType.Float32
        self._device = None
        self._enable_memory_optim = True
        self._cpu_math_threads = 1
        self._switch_ir_optim = True

    # common toggles kept for API parity; XLA makes most of them no-ops
    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0):
        self._device = ("gpu", device_id)

    def enable_xpu(self, *a, **k):
        self._device = ("xpu", 0)

    def disable_gpu(self):
        self._device = ("cpu", 0)

    def enable_memory_optim(self):
        self._enable_memory_optim = True

    def set_cpu_math_library_num_threads(self, n):
        self._cpu_math_threads = n

    def switch_ir_optim(self, flag=True):
        self._switch_ir_optim = flag

    def enable_tensorrt_engine(self, *a, precision_mode=None, **k):
        # TensorRT has no TPU analog; precision hint maps to dtype cast
        if precision_mode is not None:
            self._precision = precision_mode

    def set_model(self, model_path, params_path=None):
        if model_path.endswith(".pdmodel"):
            model_path = model_path[: -len(".pdmodel")]
        self.model_prefix = model_path
        self.params_path = params_path

    def model_dir(self):
        return self.model_prefix

    def summary(self):
        return (f"Config(model={self.model_prefix}, "
                f"precision={self._precision})")


class _IOTensor:
    """Zero-copy handle (reference: paddle_infer.Tensor over phi tensors)."""

    def __init__(self, name, store):
        self.name = name
        self._store = store

    def copy_from_cpu(self, arr):
        self._store[self.name] = np.ascontiguousarray(arr)

    def copy_to_cpu(self):
        return np.asarray(self._store[self.name])

    def shape(self):
        return list(np.shape(self._store.get(self.name, ())))

    def reshape(self, shape):
        pass  # shapes derive from copy_from_cpu input


Tensor = _IOTensor


class Predictor:
    def __init__(self, config: Config):
        from .. import static

        self.config = config
        prog, feeds, fetches = static.load_inference_model(
            config.model_prefix)
        self._program = prog
        self._feed_names = feeds
        self._fetch_vars = fetches
        self._exe = static.Executor()
        self._inputs = {}
        self._outputs = {}

    def get_input_names(self):
        return list(self._feed_names)

    def get_output_names(self):
        return [v.name for v in self._fetch_vars]

    def get_input_handle(self, name):
        return _IOTensor(name, self._inputs)

    def get_output_handle(self, name):
        return _IOTensor(name, self._outputs)

    def run(self, inputs=None):
        """Positional-list run (new API) or zero-copy handle run."""
        if inputs is not None:
            for name, arr in zip(self._feed_names, inputs):
                self._inputs[name] = np.asarray(arr)
        feed = {n: self._inputs[n] for n in self._feed_names}
        outs = self._exe.run(self._program, feed=feed,
                             fetch_list=self._fetch_vars)
        for v, o in zip(self._fetch_vars, outs):
            self._outputs[v.name] = o
        return outs if inputs is not None else None

    def clone(self):
        """reference Predictor::Clone (goapi predictor.go Clone): a new
        predictor sharing the loaded weights and compiled executables —
        only the I/O buffers are private, so clones are safe to use
        from different request contexts."""
        p = object.__new__(Predictor)
        p.config = self.config
        p._program = self._program
        p._feed_names = list(self._feed_names)
        p._fetch_vars = self._fetch_vars
        p._exe = self._exe
        p._inputs = {}
        p._outputs = {}
        return p


def create_predictor(config: Config) -> Predictor:
    return Predictor(config)


def create_engine(model, **kwargs):
    """Predictor-style entry to the continuous-batching LLM serving
    engine (paddle_tpu/serving/): one engine serves many concurrent
    generation requests over a shared paged KV pool.  Key knobs:
    ``enable_prefix_cache=True`` reuses resident KV pages across
    requests with shared prompt prefixes (prefill runs only the uncached
    suffix); ``sync_interval=N`` makes the greedy decode loop fetch N
    steps' tokens at a time (the host runs one step behind the device
    at every N, so the device never waits for it).  See
    :func:`paddle_tpu.serving.create_engine` for the full list."""
    from ..serving import create_engine as _create
    return _create(model, **kwargs)


class DataType:
    """Tensor dtypes of the inference API (reference
    paddle_infer.DataType)."""
    FLOAT32 = 0
    INT64 = 1
    INT32 = 2
    UINT8 = 3
    INT8 = 4
    FLOAT16 = 5
    BFLOAT16 = 6
    FLOAT64 = 7
    BOOL = 8


class XpuConfig:
    """XPU device config placeholder (reference paddle_infer.XpuConfig);
    recorded, not acted on — there is no XPU here."""

    def __init__(self):
        self.device_id = 0
        self.l3_size = 0
        self.conv_autotune_level = 0


class PredictorPool:
    """Pool of predictors over one config (reference
    paddle_infer.PredictorPool).

    Pool members are clones of one base predictor: they share the loaded
    weights, program, and executor compile cache (one jit executable per
    feed signature for the WHOLE pool), with private I/O buffers — the
    reference Clone() contract.  Building N independent predictors would
    reload and recompile N times."""

    def __init__(self, config, size=1):
        if size < 1:
            raise ValueError(f"PredictorPool size must be >= 1, got {size}")
        base = create_predictor(config)
        self._predictors = [base] + [base.clone() for _ in range(size - 1)]

    def size(self):
        return len(self._predictors)

    def retrieve(self, idx):
        if not 0 <= idx < len(self._predictors):
            raise IndexError(
                f"PredictorPool.retrieve({idx}): pool holds "
                f"{len(self._predictors)} predictors (valid indices "
                f"0..{len(self._predictors) - 1})")
        return self._predictors[idx]


def get_version():
    from .. import __version__
    return __version__


def get_trt_compile_version():
    return (0, 0, 0)  # no TensorRT on TPU


def get_trt_runtime_version():
    return (0, 0, 0)


def get_num_bytes_of_data_type(dtype):
    sizes = {DataType.FLOAT32: 4, DataType.INT64: 8, DataType.INT32: 4,
             DataType.UINT8: 1, DataType.INT8: 1, DataType.FLOAT16: 2,
             DataType.BFLOAT16: 2, DataType.FLOAT64: 8, DataType.BOOL: 1}
    return sizes.get(dtype, 4)


def _walk_refs(obj, params, vars_):
    """Collect ("__param__", i) indices and ("__var__", name) references
    from a pickled node's stripped args/kwargs tree."""
    if isinstance(obj, tuple) and len(obj) == 2:
        if obj[0] == "__param__":
            params.add(obj[1])
            return
        if obj[0] == "__var__":
            vars_.add(obj[1])
            return
    if isinstance(obj, (list, tuple)):
        for x in obj:
            _walk_refs(x, params, vars_)
    elif isinstance(obj, dict):
        for x in obj.values():
            _walk_refs(x, params, vars_)


def _io_and_named_params(model_file):
    """From a saved .pdmodel.pkl: (io_param_indices, param_index ->
    names of the graph vars whose op consumes it).  io params are the
    ones the feed-consuming and fetch-producing ops read — keeping them
    fp32 keeps the model's I/O tensors fp32 (dtype promotion: an fp32
    operand makes the op compute/emit fp32)."""
    import pickle
    with open(model_file, "rb") as f:
        meta = pickle.load(f)
    feeds = set(meta.get("feeds", ()))
    node_params: dict[str, set] = {}
    node_vars: dict[str, set] = {}
    for name, nd in meta["nodes"].items():
        p, v = set(), set()
        if not nd.get("feed"):
            _walk_refs(nd.get("args"), p, v)
            _walk_refs(nd.get("kwargs"), p, v)
        node_params[name] = p
        node_vars[name] = v
    io = set()
    for name in meta.get("fetches", ()):
        io |= node_params.get(name, set())
    for name, v in node_vars.items():
        if v & feeds:
            io |= node_params[name]
    names: dict[int, set] = {}
    for name, p in node_params.items():
        for i in p:
            names.setdefault(i, set()).add(name)
    return io, names


def convert_to_mixed_precision(model_file, params_file, mixed_model_file,
                               mixed_params_file, mixed_precision=None,
                               backend=None, keep_io_types=True,
                               black_list=None, **kwargs):
    """Offline precision conversion (reference
    paddle.inference.convert_to_mixed_precision): rewrites saved
    parameters to bf16/fp16.

    Handles both artifact formats: ``save_inference_model`` output
    (``.pdiparams.npz`` + ``.pdmodel.pkl``) and plain ``paddle.save``
    state-dict pickles.

    ``black_list``: parameter/tensor names kept at their original dtype.
    Entries match state-dict keys, npz keys (``p<i>``), or — for the
    inference-model format — the graph-var names of ops consuming the
    parameter (the reference's op-level blacklist).

    ``keep_io_types``: ``True`` keeps the parameters of feed-consuming
    and fetch-producing ops fp32, so model inputs/outputs stay fp32
    (requires the graph in ``model_file``; a plain state dict has no
    I/O notion and True is a no-op there).  A collection is treated as
    explicit tensor names to keep, same matching as ``black_list``."""
    import shutil

    import ml_dtypes
    import numpy as np

    target = ml_dtypes.bfloat16 if mixed_precision in (None, "bfloat16", 6) \
        else np.float16
    black = set(black_list or ())
    keep_names = set() if isinstance(keep_io_types, bool) \
        else set(keep_io_types)

    def convert(arr):
        arr = np.asarray(arr)
        if np.issubdtype(arr.dtype, np.floating) \
                and arr.dtype == np.float32:
            return arr.astype(target)
        return arr

    try:                                    # inference-model npz format?
        pz = np.load(params_file)
        is_npz = True
    except Exception:
        is_npz = False

    if is_npz:
        from .. import static as _static
        io_params, consumer_names = _io_and_named_params(model_file) \
            if keep_io_types is True or black or keep_names \
            else (set(), {})
        n = _static._npz_param_count(pz)
        out = {}
        for i in range(n):
            key = f"p{i}"
            arr = _static._npz_unpack(pz, key)
            matched = ({key} | consumer_names.get(i, set()))
            keep = bool(matched & black) or bool(matched & keep_names) \
                or (keep_io_types is True and i in io_params)
            out[key] = np.asarray(arr) if keep else convert(arr)
        # write through a handle: np.savez(path) appends '.npz' when the
        # name lacks that suffix, which would move the artifact
        with open(mixed_params_file, "wb") as f:
            np.savez(f, **_static._npz_pack(out))
    else:                                   # paddle.save state dict
        from ..framework.io import load, save
        state = load(params_file)
        out = {}
        for k, v in state.items():
            arr = v.numpy() if hasattr(v, "numpy") else np.asarray(v)
            if k in black or k in keep_names:
                out[k] = np.asarray(arr)
            else:
                out[k] = convert(arr)
        save(out, mixed_params_file)

    if model_file != mixed_model_file:
        shutil.copy(model_file, mixed_model_file)


def _get_phi_kernel_name(op_name):
    """Reference maps fluid op names to phi kernel names; here ops are
    already registry names."""
    return op_name


__all__ += ["DataType", "XpuConfig", "PredictorPool", "create_engine",
            "get_version",
            "get_trt_compile_version", "get_trt_runtime_version",
            "get_num_bytes_of_data_type", "convert_to_mixed_precision",
            "_get_phi_kernel_name"]
