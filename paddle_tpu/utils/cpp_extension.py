"""Custom C++ op extension: JIT-compile + register out-of-tree ops.

Reference: paddle/fluid/extension/ (ext_op_meta_info.h custom-op C++ API,
framework/custom_operator.cc registration) and
python/paddle/utils/cpp_extension/ (load(), CppExtension/CUDAExtension).

TPU design: user C++ cannot run on the TPU core — the reference's custom
CUDA kernels map to two TPU-native paths: (a) host-callback kernels (this
module: g++-compiled shared library driven through jax.pure_callback, with
forward/backward symbols wired into the op registry + autograd), which is
the analogue of the reference's custom *CPU* kernels; (b) on-chip custom
kernels, whose TPU path is Pallas (see paddle_tpu.ops.pallas_kernels) —
write those in Python, not C++.

Exported-symbol protocol (the ext_op_meta_info analogue, C ABI):
    extern "C" void pd_<op>_forward(const float* x, float* y, int64_t n);
    extern "C" void pd_<op>_backward(const float* x, const float* gy,
                                     float* gx, int64_t n);   // optional
Elementwise float32 contract keeps the ABI trivial; richer signatures
belong in Pallas.
"""
from __future__ import annotations

import ctypes
import functools
import hashlib
import os
import subprocess
import tempfile
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["load", "CppExtension", "CUDAExtension", "setup",
           "get_build_directory"]


def get_build_directory():
    d = os.environ.get("PADDLE_EXTENSION_DIR") or os.path.join(
        tempfile.gettempdir(), "paddle_tpu_extensions")
    os.makedirs(d, exist_ok=True)
    return d


def _compile(name: str, sources: Sequence[str], extra_cflags, build_dir,
             verbose: bool) -> str:
    src_key = hashlib.sha1()
    for s in sources:
        with open(s, "rb") as f:
            src_key.update(f.read())
    so_path = os.path.join(build_dir, f"{name}_{src_key.hexdigest()[:12]}.so")
    if os.path.exists(so_path):
        return so_path
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17",
           *(extra_cflags or []), *sources, "-o", so_path]
    if verbose:
        print("[cpp_extension]", " ".join(cmd))
    res = subprocess.run(cmd, capture_output=True, text=True)
    if res.returncode != 0:
        raise RuntimeError(f"g++ failed for extension '{name}':\n"
                           f"{res.stderr}")
    return so_path


class _LoadedExtension:
    """Module-like holder: each discovered op becomes an attribute."""

    def __init__(self, name):
        self._name = name
        self._ops = {}

    def __getattr__(self, item):
        try:
            return self.__dict__["_ops"][item]
        except KeyError:
            raise AttributeError(
                f"extension '{self._name}' has no op '{item}'; "
                f"available: {list(self.__dict__['_ops'])}")


def _make_op(lib, op_name: str, has_backward: bool):
    fwd_sym = getattr(lib, f"pd_{op_name}_forward")
    fwd_sym.restype = None
    fwd_sym.argtypes = [ctypes.POINTER(ctypes.c_float),
                        ctypes.POINTER(ctypes.c_float), ctypes.c_int64]
    bwd_sym = None
    if has_backward:
        bwd_sym = getattr(lib, f"pd_{op_name}_backward")
        bwd_sym.restype = None
        bwd_sym.argtypes = [ctypes.POINTER(ctypes.c_float)] * 3 + [
            ctypes.c_int64]

    def host_fwd(x: np.ndarray) -> np.ndarray:
        x = np.ascontiguousarray(x, np.float32)
        y = np.empty_like(x)
        fwd_sym(x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                y.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), x.size)
        return y

    def host_bwd(x: np.ndarray, gy: np.ndarray) -> np.ndarray:
        x = np.ascontiguousarray(x, np.float32)
        gy = np.ascontiguousarray(gy, np.float32)
        gx = np.empty_like(x)
        bwd_sym(x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                gy.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                gx.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), x.size)
        return gx

    def _dispatch(host_fn, out_like, *arrays):
        # concrete arrays (eager): call the C++ kernel directly — works on
        # every backend. Tracers (inside jit/grad): emit a pure_callback
        # (runs where the backend supports host send/recv).
        if any(isinstance(a, jax.core.Tracer) for a in arrays):
            return jax.pure_callback(
                host_fn, jax.ShapeDtypeStruct(out_like.shape, jnp.float32),
                *arrays, vmap_method="sequential")
        return jnp.asarray(host_fn(*[np.asarray(a) for a in arrays]))

    @jax.custom_vjp
    def pure(x):
        return _dispatch(host_fwd, x, x)

    def fwd_rule(x):
        return pure(x), x

    def bwd_rule(x, gy):
        if bwd_sym is None:
            raise NotImplementedError(
                f"custom op '{op_name}' has no pd_{op_name}_backward")
        return (_dispatch(host_bwd, x, x, gy),)

    pure.defvjp(fwd_rule, bwd_rule)

    from ..ops.registry import OPS, OpInfo, run_op
    reg_name = f"custom_{op_name}"
    if reg_name not in OPS:
        OPS[reg_name] = OpInfo(reg_name, pure, tags=("custom",))

    @functools.wraps(pure)
    def eager(x, **kwargs):
        return run_op(reg_name, pure, (x,), kwargs)
    eager.__op_name__ = reg_name
    eager.__pure_fn__ = pure
    return eager


def load(name: str, sources: Sequence[str], extra_cflags=None,
         extra_cuda_cflags=None, extra_ldflags=None,
         extra_include_paths=None, build_directory=None,
         verbose: bool = False):
    """JIT-compile `sources` and register every pd_<op>_forward symbol as a
    framework op (ref utils/cpp_extension/extension_utils.py load)."""
    build_dir = build_directory or get_build_directory()
    flags = list(extra_cflags or [])
    for inc in (extra_include_paths or []):
        flags.append(f"-I{inc}")
    so_path = _compile(name, sources, flags, build_dir, verbose)
    lib = ctypes.CDLL(so_path)

    # discover pd_*_forward symbols by scanning the dynamic symbol table
    syms = subprocess.run(["nm", "-D", so_path], capture_output=True,
                          text=True).stdout
    ops = []
    for line in syms.splitlines():
        parts = line.split()
        if len(parts) >= 3 and parts[1] == "T":
            s = parts[2]
            if s.startswith("pd_") and s.endswith("_forward"):
                ops.append(s[len("pd_"):-len("_forward")])
    if not ops:
        raise RuntimeError(
            f"extension '{name}' exports no pd_<op>_forward symbols")
    mod = _LoadedExtension(name)
    for op_name in ops:
        has_bwd = f"pd_{op_name}_backward" in syms
        mod._ops[op_name] = _make_op(lib, op_name, has_bwd)
    return mod


class CppExtension:
    """setuptools-style extension spec (parity with
    utils/cpp_extension.CppExtension); consumed by setup()."""

    def __init__(self, sources, *args, **kwargs):
        self.sources = list(sources)
        self.kwargs = kwargs


CUDAExtension = CppExtension  # no CUDA here; kept for import parity


def setup(name=None, ext_modules=None, **kwargs):
    """Build-and-register immediately (the setup.py path collapses to
    load() since there is no separate install step in this runtime)."""
    mods = []
    for ext in (ext_modules or []):
        mods.append(load(name or "custom_ext", ext.sources,
                         **{k: v for k, v in ext.kwargs.items()
                            if k in ("extra_cflags", "extra_include_paths",
                                     "build_directory", "verbose")}))
    return mods[0] if len(mods) == 1 else mods
