"""Native (C++) runtime components, built on demand with the system
toolchain and loaded over ctypes — the TPU build's equivalent of the
reference's compiled core (dmlc recordio framing, src/io/).

Build artifacts are cached next to the sources, one per source *content*:
``lib<name>.<sha256 of the .cc, 12 hex>.so``. A library built from another
revision of the source has another name and is never loaded (a checkout
holds only what git commits, so the ``.so`` files are always built where
they run). When no compiler is available the callers fall back to
pure-Python implementations, so the package never hard-fails;
:func:`status` says which of the two each component is using.
"""
import contextlib
import ctypes
import glob
import hashlib
import os
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_LOCK = threading.Lock()
_LIBS = {}  # guarded-by: _LOCK

NAMES = ("recordio", "libsvmparse", "prefetch")

# per-library extra compile flags
_FLAGS = {"prefetch": ["-pthread"]}


def _build(name):
    src = os.path.join(_HERE, name + ".cc")
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    so = os.path.join(_HERE, "lib%s.%s.so" % (name, digest))
    if not os.path.exists(so):
        # build under a private name, then rename: a concurrent process
        # never dlopens a half-written library
        tmp = "%s.%d.tmp" % (so, os.getpid())
        cmd = (["g++", "-O2", "-std=c++14", "-fPIC", "-shared", src]
               + _FLAGS.get(name, []) + ["-o", tmp])
        subprocess.run(cmd, check=True, capture_output=True)
        os.replace(tmp, so)
        for stale in glob.glob(os.path.join(_HERE, "lib%s.*so" % name)):
            if stale != so:
                with contextlib.suppress(FileNotFoundError):
                    os.remove(stale)  # another process may get there first
    return so


def load(name):
    """Load (building if needed) the named native library; None if the
    toolchain is unavailable."""
    with _LOCK:
        if name in _LIBS:
            return _LIBS[name]
        try:
            lib = ctypes.CDLL(_build(name))
        except (OSError, subprocess.CalledProcessError, FileNotFoundError):
            lib = None
        _LIBS[name] = lib
        return lib


def status():
    """``{component: "native" | "python"}`` — whether each native library
    loaded (building it if needed) or its pure-Python stand-in is in
    use."""
    return {name: "native" if load(name) is not None else "python"
            for name in NAMES}


def recordio_lib():
    lib = load("recordio")
    if lib is not None and not getattr(lib, "_rio_typed", False):
        LL = ctypes.c_longlong
        P = ctypes.c_void_p
        lib.rio_open.restype = P
        lib.rio_open.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
        lib.rio_close.argtypes = [P]
        lib.rio_tell.restype = LL
        lib.rio_tell.argtypes = [P]
        lib.rio_seek.restype = ctypes.c_int
        lib.rio_seek.argtypes = [P, LL]
        lib.rio_scan.restype = LL
        lib.rio_scan.argtypes = [P, ctypes.POINTER(LL), LL]
        lib.rio_read.restype = LL
        lib.rio_read.argtypes = [P, ctypes.c_char_p, LL]
        lib.rio_read_at.restype = LL
        lib.rio_read_at.argtypes = [P, LL, ctypes.c_char_p, LL]
        lib.rio_write.restype = LL
        lib.rio_write.argtypes = [P, ctypes.c_char_p, LL, LL]
        lib.rio_flush.restype = ctypes.c_int
        lib.rio_flush.argtypes = [P]
        lib._rio_typed = True
    return lib


def libsvm_lib():
    lib = load("libsvmparse")
    if lib is not None and not getattr(lib, "_lsvm_typed", False):
        LL = ctypes.c_longlong
        P = ctypes.c_void_p
        FP = ctypes.POINTER(ctypes.c_float)
        LP = ctypes.POINTER(LL)
        lib.lsvm_parse.restype = P
        lib.lsvm_parse.argtypes = [ctypes.c_char_p]
        lib.lsvm_rows.restype = LL
        lib.lsvm_rows.argtypes = [P]
        lib.lsvm_nnz.restype = LL
        lib.lsvm_nnz.argtypes = [P]
        lib.lsvm_error_line.restype = LL
        lib.lsvm_error_line.argtypes = [P]
        lib.lsvm_fill.argtypes = [P, FP, LP, LP, FP]
        lib.lsvm_free.argtypes = [P]
        lib._lsvm_typed = True
    return lib


def prefetch_lib():
    lib = load("prefetch")
    if lib is not None and not getattr(lib, "_rpf_typed", False):
        LL = ctypes.c_longlong
        P = ctypes.c_void_p
        lib.rpf_open.restype = P
        lib.rpf_open.argtypes = [ctypes.c_char_p, LL]
        lib.rpf_next.restype = LL
        lib.rpf_next.argtypes = [P, ctypes.c_char_p, LL]
        lib.rpf_peek_size.restype = LL
        lib.rpf_peek_size.argtypes = [P]
        lib.rpf_reset.argtypes = [P]
        lib.rpf_close.argtypes = [P]
        lib._rpf_typed = True
    return lib
