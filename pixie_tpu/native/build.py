"""Build + load the native runtime library (ctypes, no pybind11).

`native/*.cc` compiles lazily with g++ on first use into
`native/libpixie_native.<digest>.so`, where the digest is a content hash of
the sources (never their mtime); loading is cached.  Everything
native-backed has a pure-Python fallback, so a MISSING toolchain degrades
performance, never correctness (set PIXIE_TPU_NO_NATIVE=1 to force the
fallback).  A toolchain that is present and fails is an error.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import pathlib
import subprocess
import threading

from pixie_tpu import flags as _flags

_flags.define_str(
    "PIXIE_TPU_NO_NATIVE", "",
    "force the pure-Python fallbacks even when the g++ toolchain is "
    "available (perf A/B and toolchain-bug escape hatch).  A kill switch: "
    "ANY value except ''/0/false/no/off disables native.  Live: read at "
    "first load_native() use, not import", live=True)


def _no_native() -> bool:
    # historic semantics preserved: any non-empty value disables native
    # unless it is an explicit falsy spelling — a kill switch must not
    # fail silently on a non-canonical truthy value
    val = str(_flags.get("PIXIE_TPU_NO_NATIVE")).strip().lower()
    return bool(val) and val not in ("0", "false", "no", "off")

_flags.define_str(
    "PX_NATIVE_SANITIZE", "",
    "sanitizer build mode for the native STANDALONE test harnesses "
    "(tests/test_native_sanitize.py): 'address' = ASan+UBSan, 'thread' = "
    "TSan over the concurrent pthread driver (the slow lane).  Sanitizers "
    "never apply to the ctypes .so — they need an instrumented host binary",
    live=True)

#: g++ flags per sanitizer mode (the harness tests compile with these)
SANITIZER_ARGS = {
    "address": ["-fsanitize=address,undefined", "-fno-omit-frame-pointer"],
    "thread": ["-fsanitize=thread", "-fno-omit-frame-pointer"],
}

_REPO = pathlib.Path(__file__).resolve().parent.parent.parent
_SRC_DIR = _REPO / "native"
_SO_STEM = "libpixie_native"
_CXX_ARGS = ["-O3", "-shared", "-fPIC", "-std=c++17", "-pthread"]

_lock = threading.Lock()
_lib = None
_tried = False
_built_here = False


class NativeBuildError(RuntimeError):
    """g++ is present and the tracked sources did not compile (or the
    compiled library did not load): a bug, never a silent fallback."""


def _sources() -> list:
    return sorted(_SRC_DIR.glob("*.cc"))


def source_digest() -> str:
    """Content hash of native/*.cc plus the compile flags: the library's
    identity.  It is part of the library's file name, so a stale or foreign
    .so (an older build riding along in a copied tree, a different
    checkout's) is never loaded — only rebuilt past."""
    h = hashlib.sha256(" ".join(_CXX_ARGS).encode())
    for src in _sources():
        h.update(src.name.encode())
        h.update(src.read_bytes())
    return h.hexdigest()[:16]


def so_path() -> pathlib.Path:
    return _SRC_DIR / f"{_SO_STEM}.{source_digest()}.so"


def built_this_process() -> bool:
    """True when THIS process compiled the library it loaded (False: it
    found one whose name already carried the current sources' digest)."""
    return _built_here


def _build(target: pathlib.Path) -> bool:
    """Compile the tracked sources into `target`.  False = no toolchain (the
    documented pure-Python fallback); a compile that FAILS raises."""
    global _built_here
    tmp = target.with_name(f"{target.name}.{os.getpid()}.tmp")
    cmd = ["g++", *_CXX_ARGS, "-o", str(tmp), *[str(s) for s in _sources()]]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=300)
    except FileNotFoundError:
        return False
    except subprocess.CalledProcessError as e:
        raise NativeBuildError(
            f"g++ failed on native/*.cc:\n"
            f"{e.stderr.decode(errors='replace')[-2000:]}") from e
    # atomic publish: a concurrent process (broker + agent starting
    # together) either sees the whole library or none
    os.replace(tmp, target)
    _built_here = True
    for old in _SRC_DIR.glob(f"{_SO_STEM}*.so"):
        if old != target:
            old.unlink(missing_ok=True)
    return True


def load_native():
    """ctypes handle to the native library, or None (fallback mode: the
    kill switch is set, or there is no g++)."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        if _no_native() or not _sources():
            _tried = True
            return None
        target = so_path()
        if not target.exists() and not _build(target):
            _tried = True
            return None
        try:
            lib = ctypes.CDLL(str(target))
        except OSError as e:
            raise NativeBuildError(f"cannot load {target}: {e}") from e
        lib.px_dict_new.restype = ctypes.c_void_p
        lib.px_dict_free.argtypes = [ctypes.c_void_p]
        lib.px_dict_size.argtypes = [ctypes.c_void_p]
        lib.px_dict_size.restype = ctypes.c_int64
        lib.px_dict_encode_ucs4.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p,
        ]
        lib.px_dict_encode_ucs4.restype = ctypes.c_int64
        lib.px_dict_insert_ucs4.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
        ]
        lib.px_dict_insert_ucs4.restype = ctypes.c_int32
        # whole-plan fused loop (native/wholeplan.cc) — args are passed as
        # explicit ctypes objects by codegen.py, so only the return type
        # needs declaring
        lib.px_wholeplan_run.restype = ctypes.c_int64
        # radix hash join (native/join.cc)
        lib.px_join_run.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_void_p,
        ]
        lib.px_join_run.restype = ctypes.c_void_p
        lib.px_join_fetch.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ]
        lib.px_join_free.argtypes = [ctypes.c_void_p]
        _lib = lib
        _tried = True
        return _lib
