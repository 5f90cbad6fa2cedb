"""paddle_tpu.utils.cpp_extension — build + load native custom ops.

ref: python/paddle/utils/cpp_extension/__init__.py (CppExtension /
CUDAExtension / load / setup / get_build_directory in cpp_extension.py,
extension_utils.py). The reference JIT-compiles user C++/CUDA into its
kernel registry via setuptools + nvcc; a TPU has no user-facing device
toolchain, so the TPU-native design is:

- ``load(name, sources)`` compiles the C++ with g++ into a cached
  shared library (content-hashed — rebuilds only when sources/flags
  change) and returns an :class:`ExtensionModule`.
- ``ExtensionModule.def_op`` wraps an exported C-ABI symbol (see
  ``paddle_tpu_ext.h``) into a framework op: host execution via
  ``jax.pure_callback`` (works eagerly AND inside ``jit``/``to_static``
  — XLA inserts the device↔host transfers), optional custom backward,
  recorded on the autograd tape like any built-in op.
- Raw symbols stay reachable via ``ExtensionModule.lib`` (ctypes) for
  non-op native code.

Device-compute custom kernels should be written as Pallas kernels in
Python (``ops/flash_attention.py`` is the in-tree model); this module
is the escape hatch for host-side native code — the role the
reference's CPU custom kernels play inside GPU models.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
from typing import Callable, Optional, Sequence

import numpy as np

__all__ = [
    "CppExtension",
    "CUDAExtension",
    "load",
    "setup",
    "get_build_directory",
    "BuildExtension",
    "ExtensionModule",
]

_HERE = os.path.dirname(os.path.abspath(__file__))

# keep in sync with PTDtype in paddle_tpu_ext.h
_DTYPE_CODES = {
    np.dtype(np.float32): 0,
    np.dtype(np.float64): 1,
    np.dtype(np.int32): 2,
    np.dtype(np.int64): 3,
    np.dtype(np.uint8): 4,
    np.dtype(np.bool_): 5,
}


class _PTTensor(ctypes.Structure):
    _fields_ = [
        ("data", ctypes.c_void_p),
        ("shape", ctypes.POINTER(ctypes.c_int64)),
        ("ndim", ctypes.c_int32),
        ("dtype", ctypes.c_int32),
    ]


def get_build_directory(verbose: bool = False) -> str:
    """ref: extension_utils.py get_build_directory — honors
    PADDLE_EXTENSION_DIR, defaults to a per-user cache dir."""
    root = os.environ.get("PADDLE_EXTENSION_DIR")
    if not root:
        root = os.path.join(
            os.path.expanduser("~"), ".cache", "paddle_tpu_extensions"
        )
    os.makedirs(root, exist_ok=True)
    return root


class CppExtension:
    """Source + flags bundle (ref: cpp_extension.py CppExtension — the
    setuptools.Extension factory collapses to a descriptor here)."""

    def __init__(self, sources: Sequence[str], *, name: Optional[str] = None,
                 extra_compile_args: Sequence[str] = (),
                 include_dirs: Sequence[str] = (), **kwargs):
        self.name = name
        self.sources = list(sources)
        self.extra_compile_args = list(extra_compile_args)
        self.include_dirs = list(include_dirs)


class CUDAExtension(CppExtension):
    """ref: cpp_extension.py CUDAExtension. There is no nvcc on a TPU
    host: .cu sources are rejected with guidance (device kernels belong
    in Pallas), plain .cc/.cpp sources build exactly like CppExtension."""

    def __init__(self, sources: Sequence[str], **kwargs):
        cu = [s for s in sources if s.endswith((".cu", ".cuh"))]
        if cu:
            raise RuntimeError(
                f"CUDAExtension: no CUDA toolchain on a TPU host (sources "
                f"{cu}). Write device kernels as Pallas kernels "
                "(paddle_tpu/ops/ has in-tree examples); host-side C++ "
                "builds via CppExtension."
            )
        super().__init__(sources, **kwargs)


class ExtensionModule:
    """A loaded extension: raw ctypes access plus op wrapping."""

    def __init__(self, name: str, so_path: str):
        self.name = name
        self.so_path = so_path
        self.lib = ctypes.CDLL(so_path)
        self._ops = {}

    def __getattr__(self, item):
        ops = self.__dict__.get("_ops", {})
        if item in ops:
            return ops[item]
        if "lib" not in self.__dict__:  # pre-__init__ probes (pickle/copy)
            raise AttributeError(item)
        try:
            return getattr(self.__dict__["lib"], item)
        except AttributeError:
            raise AttributeError(
                f"extension '{self.name}' has no op or symbol {item!r}"
            ) from None

    # -- op wrapping -----------------------------------------------------
    def def_op(
        self,
        op_name: str,
        forward: str,
        backward: Optional[str] = None,
        infer_shape: Optional[Callable] = None,
        infer_dtype: Optional[Callable] = None,
        num_outputs: int = 1,
    ):
        """Wrap exported symbols into a differentiable framework op.

        - ``forward``/``backward``: exported symbol names following the
          ``paddle_tpu_ext.h`` contract. The backward receives
          ``inputs + grad_outputs`` and fills one gradient per input.
        - ``infer_shape(*in_shapes) -> [out_shapes]`` and
          ``infer_dtype(*in_dtypes) -> [out_dtypes]`` play the
          reference's InferShapeFn/InferDtypeFn roles (ref:
          op_meta_info.h SetInferShapeFn); both default to
          first-input passthrough.
        """
        import jax
        import jax.numpy as jnp

        from ...base import tape as _tape

        fwd_sym = getattr(self.lib, forward)
        fwd_sym.restype = ctypes.c_int
        bwd_sym = None
        if backward is not None:
            bwd_sym = getattr(self.lib, backward)
            bwd_sym.restype = ctypes.c_int

        def _call_native(sym, in_arrays, out_shapes, out_dtypes):
            ins = [np.ascontiguousarray(a) for a in in_arrays]
            outs = [np.empty(s, d) for s, d in zip(out_shapes, out_dtypes)]
            all_t = ins + outs
            shape_bufs = [
                (ctypes.c_int64 * max(a.ndim, 1))(*(a.shape or (0,)))
                for a in all_t
            ]
            descs = (_PTTensor * len(all_t))()
            for i, a in enumerate(all_t):
                code = _DTYPE_CODES.get(a.dtype)
                if code is None:
                    raise TypeError(
                        f"custom op '{op_name}': unsupported dtype {a.dtype} "
                        f"(supported: {sorted(str(k) for k in _DTYPE_CODES)})"
                    )
                descs[i] = _PTTensor(
                    a.ctypes.data_as(ctypes.c_void_p), shape_bufs[i],
                    a.ndim, code,
                )
            rc = sym(
                ctypes.byref(descs), ctypes.c_int(len(ins)),
                ctypes.byref(descs, ctypes.sizeof(_PTTensor) * len(ins)),
                ctypes.c_int(len(outs)),
            )
            if rc != 0:
                raise RuntimeError(
                    f"custom op '{op_name}' ({sym}) returned error code {rc}"
                )
            return tuple(outs)

        def _shapes_dtypes(arrs):
            in_shapes = [tuple(a.shape) for a in arrs]
            in_dtypes = [np.dtype(a.dtype) for a in arrs]
            out_shapes = (
                list(infer_shape(*in_shapes)) if infer_shape
                else [in_shapes[0]] * num_outputs
            )
            out_dtypes = (
                [np.dtype(d) for d in infer_dtype(*in_dtypes)] if infer_dtype
                else [in_dtypes[0]] * num_outputs
            )
            return out_shapes, out_dtypes

        def _dispatch(sym, arrs, out_shapes, out_dtypes):
            # Concrete inputs (eager, incl. the primal pass inside the
            # tape's jax.vjp): fetch to host and call directly — no
            # callback machinery, and it works on PJRT backends without
            # host-callback support. Tracers
            # (inside jit/to_static): jax.pure_callback, which XLA wires
            # as a host call on backends that support it.
            if any(isinstance(a, jax.core.Tracer) for a in arrs):
                return jax.pure_callback(
                    lambda *a: _call_native(sym, a, out_shapes, out_dtypes),
                    tuple(jax.ShapeDtypeStruct(s, d)
                          for s, d in zip(out_shapes, out_dtypes)),
                    *arrs,
                )
            host = _call_native(sym, [np.asarray(a) for a in arrs],
                                out_shapes, out_dtypes)
            return tuple(jnp.asarray(h) for h in host)

        def fwd_arrays(*arrs):
            out_shapes, out_dtypes = _shapes_dtypes(arrs)
            return _dispatch(fwd_sym, arrs, out_shapes, out_dtypes)

        # ALWAYS custom_vjp (even forward-only): the tape's jax.vjp runs
        # the primal under JVP tracing, where a bare pure_callback is
        # rejected — custom_vjp keeps the forward runnable and defers
        # the no-backward complaint to the moment a gradient is pulled
        @jax.custom_vjp
        def op_core(*arrs):
            return fwd_arrays(*arrs)

        def op_fwd(*arrs):
            return op_core(*arrs), arrs

        def op_bwd(saved, gouts):
            if bwd_sym is None:
                raise RuntimeError(
                    f"custom op '{op_name}' has no backward registered; "
                    "pass backward= to def_op (or mark its inputs "
                    "stop_gradient=True)"
                )
            in_shapes = [tuple(a.shape) for a in saved]
            in_dtypes = [np.dtype(a.dtype) for a in saved]
            return _dispatch(bwd_sym, (*saved, *gouts), in_shapes,
                             in_dtypes)

        op_core.defvjp(op_fwd, op_bwd)

        def op(*tensors):
            from ...base.tensor import Tensor

            def run(*xs):
                outs = op_core(*[x for x in xs])
                return outs[0] if num_outputs == 1 else outs

            wrapped = [
                t if isinstance(t, Tensor) else Tensor(jnp.asarray(t), _internal=True)
                for t in tensors
            ]
            return _tape.apply(run, *wrapped, op_name=f"custom.{op_name}")

        op.__name__ = op_name
        self._ops[op_name] = op
        return op


def _build(name: str, sources: Sequence[str], extra_compile_args=(),
           include_dirs=(), build_directory: Optional[str] = None,
           verbose: bool = False, extra_ldflags=()) -> str:
    build_dir = build_directory or get_build_directory()
    os.makedirs(build_dir, exist_ok=True)
    srcs = [os.path.abspath(s) for s in sources]
    for s in srcs:
        if not os.path.exists(s):
            raise FileNotFoundError(f"cpp_extension source not found: {s}")
    h = hashlib.sha256()
    for s in srcs:
        h.update(open(s, "rb").read())
        h.update(b"\x00")
    # flags and include roots are inputs too: hash per-element (a joined
    # string would collide ["-DA B"] with ["-DA", "-B"]), plus the
    # bundled ABI header's contents so its changes force a rebuild
    for part in (*extra_compile_args, b"--ld--", *extra_ldflags):
        h.update(part if isinstance(part, bytes) else part.encode())
        h.update(b"\x00")
    # header CONTENTS are build inputs too: the bundled ABI header, any
    # header next to a source file, and everything under include_dirs
    # (headers reached through other -I roots or system paths are not
    # tracked — delete the cached .so to force a rebuild)
    header_files = {os.path.join(_HERE, "paddle_tpu_ext.h")}
    for s in srcs:
        src_dir = os.path.dirname(s)
        header_files.update(
            os.path.join(src_dir, f) for f in os.listdir(src_dir)
            if f.endswith((".h", ".hpp", ".hh", ".cuh"))
        )
    for d in include_dirs:
        h.update(os.path.abspath(d).encode() + b"\x00")
        for root, _, files in os.walk(d):
            header_files.update(
                os.path.join(root, f) for f in files
                if f.endswith((".h", ".hpp", ".hh", ".cuh"))
            )
    for hf in sorted(header_files):
        h.update(hf.encode() + b"\x00")
        h.update(open(hf, "rb").read())
        h.update(b"\x00")
    so_path = os.path.join(build_dir, f"{name}_{h.hexdigest()[:12]}.so")
    if os.path.exists(so_path):
        return so_path
    # per-process temp output: concurrent builds of the same extension
    # must not share an intermediate path (a parallel g++ writing into
    # the inode after os.replace would corrupt the cached artifact)
    fd, tmp = tempfile.mkstemp(suffix=".so", prefix=f"{name}_",
                               dir=build_dir)
    os.close(fd)
    cmd = [
        "g++", "-O2", "-shared", "-fPIC", "-std=c++17",
        f"-I{_HERE}", *[f"-I{d}" for d in include_dirs],
        *extra_compile_args, "-o", tmp, *srcs, *extra_ldflags,
    ]
    if verbose:
        print("cpp_extension:", " ".join(cmd))
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
    except subprocess.CalledProcessError as e:
        os.unlink(tmp)
        raise RuntimeError(
            f"cpp_extension build failed for '{name}':\n{e.stderr}"
        ) from e
    except OSError as e:  # compiler missing from PATH etc.
        os.unlink(tmp)
        raise RuntimeError(
            f"cpp_extension build failed for '{name}': cannot run g++ "
            f"({e})"
        ) from e
    os.replace(tmp, so_path)  # atomic publish
    return so_path


def load(name: str, sources: Sequence[str] = (), *,
         extension: Optional[CppExtension] = None,
         extra_cxx_cflags: Sequence[str] = (),
         extra_ldflags: Sequence[str] = (),
         extra_include_paths: Sequence[str] = (),
         build_directory: Optional[str] = None,
         verbose: bool = False, **kwargs) -> ExtensionModule:
    """JIT-compile + load a custom-op extension (ref: cpp_extension.py
    load). Returns an :class:`ExtensionModule`; see ``def_op``."""
    if kwargs:
        import warnings

        warnings.warn(
            f"cpp_extension.load: ignoring unsupported options "
            f"{sorted(kwargs)} (no CUDA toolchain on a TPU host)",
            stacklevel=2,
        )
    if extension is not None:
        sources = extension.sources
        extra_cxx_cflags = list(extra_cxx_cflags) + extension.extra_compile_args
        extra_include_paths = list(extra_include_paths) + extension.include_dirs
    so = _build(name, sources, extra_cxx_cflags, extra_include_paths,
                build_directory, verbose, extra_ldflags)
    return ExtensionModule(name, so)


def setup(name: str = None, ext_modules=None, *, build_directory=None,
          verbose: bool = False, **kwargs):
    """AOT-build extensions (ref: cpp_extension.py setup — the
    setuptools egg install collapses to: build each extension into the
    shared cache and drop a ``<name>.py`` loader next to it, so
    ``import <name>`` works from the build directory)."""
    exts = ext_modules if isinstance(ext_modules, (list, tuple)) else [ext_modules]
    build_dir = build_directory or get_build_directory()
    loaders = []
    for ext in exts:
        if ext is None:
            continue
        ext_name = ext.name or name
        if not ext_name:
            raise ValueError("setup: an extension (or setup) needs a name")
        so = _build(ext_name, ext.sources, ext.extra_compile_args,
                    ext.include_dirs, build_dir, verbose)
        loader = os.path.join(build_dir, f"{ext_name}.py")
        with open(loader, "w") as f:
            f.write(
                "# generated by paddle_tpu.utils.cpp_extension.setup\n"
                "from paddle_tpu.utils.cpp_extension import ExtensionModule\n"
                f"_mod = ExtensionModule({ext_name!r}, {so!r})\n"
                "lib = _mod.lib\n"
                "def_op = _mod.def_op\n"
            )
        loaders.append(loader)
    return loaders


class BuildExtension:
    """API-compat cmdclass stand-in (ref: cpp_extension.py
    BuildExtension.with_options). The setuptools build is replaced by
    :func:`setup` above; this class only preserves the
    ``cmdclass={'build_ext': BuildExtension.with_options(...)}`` idiom."""

    @classmethod
    def with_options(cls, **options):
        return cls

    def __init__(self, *a, **k):
        pass
