"""Native (C++) fast paths, loaded via ctypes with pure-Python fallbacks.

The reference's hot codecs are compiled Pony (SURVEY.md §2: pony-resp's
CommandParser, the framing/serialise codec); their rebuild equivalents are
C++ under native/, built into ``libjylis_native.so`` by `make native` (or
lazily here on first use when a toolchain is available — a few seconds).
Both go through ``build()`` below, which records the sha256 of the sources
beside the binary: staleness is decided by CONTENT, because a copied tree
(an image layer, a chip-run sandbox, a checkout) keeps no meaningful
mtimes and must never serve from a binary that does not match native/.

``lib()`` returns the loaded CDLL or None; callers must keep working
without it (the Python implementations are the semantic oracles).

This file imports only the standard library, so `make native` runs it as
a script (``python jylis_tpu/native/__init__.py``) without importing the
package — and with it jax.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SRC_DIR = os.path.join(_REPO_ROOT, "native")
# deployed images/wheels carry the prebuilt .so without the C++ sources:
# JYLIS_NATIVE_SO points straight at it (see Dockerfile), or `make
# release` bundles it next to this file inside the wheel
_PKG_SO = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "libjylis_native.so")
_SO_PATH = (
    os.environ.get("JYLIS_NATIVE_SO")
    or (_PKG_SO if os.path.exists(_PKG_SO) else None)
    or os.path.join(_SRC_DIR, "libjylis_native.so")
)

# the in-checkout build output: the only binary this module ever (re)builds.
# An explicit JYLIS_NATIVE_SO or a wheel-bundled .so is taken as given.
_BUILT_SO = os.path.join(_SRC_DIR, "libjylis_native.so")
_HASH_SUFFIX = ".srchash"

_lib: ctypes.CDLL | None = None
_tried = False


def _sources() -> list[str]:
    """native/*.cpp and the headers they include, sorted."""
    try:
        names = sorted(os.listdir(_SRC_DIR))
    except OSError:  # no source checkout (installed wheel / image)
        return []
    return [
        os.path.join(_SRC_DIR, f) for f in names if f.endswith((".cpp", ".h"))
    ]


def source_hash() -> str:
    """sha256 over the names and contents of the native sources."""
    h = hashlib.sha256()
    for path in _sources():
        h.update(os.path.basename(path).encode() + b"\0")
        with open(path, "rb") as f:
            h.update(f.read())
        h.update(b"\0")
    return h.hexdigest()


def built_hash() -> str | None:
    """The source hash recorded beside the in-checkout binary, or None."""
    try:
        with open(_BUILT_SO + _HASH_SUFFIX, encoding="ascii") as f:
            return f.read().strip()
    except OSError:
        return None


def build() -> bool:
    """Compile native/*.cpp into the in-checkout .so and record the source
    hash beside it. Both files land by rename, so a concurrent loader
    (a second node, parallel tests) sees the old pair or the new one."""
    units = [p for p in _sources() if p.endswith(".cpp")]
    if not units:
        return False
    want = source_hash()
    tmp = f"{_BUILT_SO}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread",
             "-o", tmp] + units,
            check=True,
            capture_output=True,
            timeout=300,
        )
        os.replace(tmp, _BUILT_SO)
        with open(tmp, "w", encoding="ascii") as f:
            f.write(want + "\n")
        os.replace(tmp, _BUILT_SO + _HASH_SUFFIX)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        detail = getattr(e, "stderr", b"") or b""
        print(
            f"jylis_tpu.native: build failed ({e!r}) "
            f"{detail.decode(errors='replace')[-2000:]}",
            file=sys.stderr,
        )
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def loads_checkout_build() -> bool:
    """Will lib() load (and keep fresh) the in-checkout build, rather
    than a binary handed to it (JYLIS_NATIVE_SO, a wheel's)?"""
    return _SO_PATH == _BUILT_SO


def _stale() -> bool:
    """Does the binary we are about to load mismatch native/'s sources?
    Only the in-checkout build is ever judged (and rebuilt)."""
    if not loads_checkout_build() or not _sources():
        return False  # prebuilt .so (env / wheel / no sources): as given
    return built_hash() != source_hash()


def _declare_codec(cdll: ctypes.CDLL) -> None:
    """Signatures for the cluster wire codec (native/cluster_codec.cpp)."""
    c = ctypes
    p64 = c.POINTER(c.c_int64)
    sigs = {
        # encode: (..., out, cap) -> bytes written or -1
        "jy_push_counters_encode": (
            c.c_int64,
            [c.c_char_p, c.c_int64, c.c_int64, c.c_char_p, c.c_void_p,
             c.c_void_p, c.c_int32, c.c_void_p, c.c_void_p, c.c_void_p,
             c.c_void_p, c.c_int64],
        ),
        "jy_push_treg_encode": (
            c.c_int64,
            [c.c_char_p, c.c_int64, c.c_int64, c.c_char_p, c.c_void_p,
             c.c_void_p, c.c_char_p, c.c_void_p, c.c_void_p, c.c_void_p,
             c.c_void_p, c.c_int64],
        ),
        "jy_push_tlog_encode": (
            c.c_int64,
            [c.c_char_p, c.c_int64, c.c_int64, c.c_char_p, c.c_void_p,
             c.c_void_p, c.c_void_p, c.c_char_p, c.c_void_p, c.c_void_p,
             c.c_void_p, c.c_void_p, c.c_void_p, c.c_int64],
        ),
        # measure/decode: -> 0 ok, -1 malformed, -2 fall back to oracle
        "jy_push_counters_measure": (
            c.c_int32, [c.c_char_p, c.c_int64, c.c_int32, p64, p64],
        ),
        "jy_push_counters_decode": (
            c.c_int32,
            [c.c_char_p, c.c_int64, c.c_int32, c.c_void_p, c.c_void_p,
             c.c_void_p, c.c_void_p, c.c_void_p],
        ),
        "jy_push_treg_measure": (c.c_int32, [c.c_char_p, c.c_int64, p64]),
        "jy_push_treg_decode": (
            c.c_int32,
            [c.c_char_p, c.c_int64, c.c_void_p, c.c_void_p, c.c_void_p,
             c.c_void_p, c.c_void_p],
        ),
        "jy_push_tlog_measure": (c.c_int32, [c.c_char_p, c.c_int64, p64, p64]),
        "jy_push_tlog_decode": (
            c.c_int32,
            [c.c_char_p, c.c_int64, c.c_void_p, c.c_void_p, c.c_void_p,
             c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p],
        ),
        "jy_push_ujson_encode": (
            c.c_int64,
            [c.c_char_p, c.c_int64, c.c_int64, c.c_char_p, c.c_void_p,
             c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p,
             c.c_char_p, c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p,
             c.c_void_p, c.c_void_p, c.c_void_p, c.c_int64],
        ),
        # UJSON wire fast paths (native/ujson_planes.cpp)
        "jy_ujson_split_measure": (c.c_int32, [c.c_char_p, c.c_int64, p64]),
        "jy_ujson_split": (
            c.c_int32,
            [c.c_char_p, c.c_int64, c.c_void_p, c.c_void_p, c.c_void_p,
             c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p],
        ),
        "jy_ujson_grid_fill": (
            c.c_int32,
            [c.c_char_p, c.c_int64, c.c_void_p, c.c_void_p, c.c_void_p,
             c.c_int32, c.c_int64, c.c_int64, c.c_int64, c.c_void_p,
             c.c_int64, c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p,
             c.c_void_p, p64, c.c_void_p, c.c_void_p, p64, p64],
        ),
    }
    for fn_name, (restype, argtypes) in sigs.items():
        fn = getattr(cdll, fn_name)
        fn.restype = restype
        fn.argtypes = argtypes


def lib() -> ctypes.CDLL | None:
    """The native library, building it on first use if needed/possible."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    try:
        if not os.path.exists(_SO_PATH) or _stale():
            if not loads_checkout_build() or not build():
                return None
        cdll = ctypes.CDLL(_SO_PATH)
        cdll.resp_scan.restype = ctypes.c_int32
        cdll.resp_scan.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32),
        ]
        cdll.resp_scan_many.restype = ctypes.c_int32
        cdll.resp_scan_many.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32),
        ]
        _declare_codec(cdll)
        _lib = cdll
    except OSError:
        _lib = None
    return _lib


if __name__ == "__main__":  # `make native`
    sys.exit(0 if build() else 1)
