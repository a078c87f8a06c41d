"""On-demand build of the native libraries (g++; the shim reads the
wire itself and links no protobuf).

Build artifacts live in _build/ which is NOT under version control
(reviewable source only — a committed binary can't be audited);
staleness is a content hash of the sources, not mtimes (mtimes are
arbitrary after a fresh clone)."""
from __future__ import annotations

import hashlib
import os
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_BUILD = os.path.join(_DIR, "_build")
_SO = os.path.join(_BUILD, "libmixer_shim.so")
_HASH = os.path.join(_BUILD, ".srchash")
_HTTPD_SO = os.path.join(_BUILD, "libmixer_httpd.so")
_HTTPD_HASH = os.path.join(_BUILD, ".httpd_srchash")
_H2LOAD = os.path.join(_BUILD, "h2load")
_H2LOAD_HASH = os.path.join(_BUILD, ".h2load_srchash")
_lock = threading.Lock()


class NativeBuildError(RuntimeError):
    pass


def _source_hash(*paths: str) -> str:
    h = hashlib.sha256()
    for p in paths:
        with open(p, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _build_one(srcs: list[str], out: str, hash_path: str,
               extra_args: list[str],
               hash_extra: list[str] | None = None) -> str:
    """Hash-gated g++ build of one native artifact."""
    want = _source_hash(*srcs, *(hash_extra or []))
    with _lock:
        if os.path.exists(out) and os.path.exists(hash_path):
            with open(hash_path, encoding="ascii") as f:
                if f.read().strip() == want:
                    return out
        os.makedirs(_BUILD, exist_ok=True)
        try:
            subprocess.run(
                ["g++", "-O2", "-std=c++17", *extra_args, *srcs,
                 "-o", out],
                check=True, capture_output=True, text=True)
        except subprocess.CalledProcessError as exc:
            raise NativeBuildError(
                f"native build failed for {out}:\n{exc.stderr}") from exc
        except FileNotFoundError as exc:
            raise NativeBuildError(f"toolchain missing: {exc}") from exc
        with open(hash_path, "w", encoding="ascii") as f:
            f.write(want + "\n")
        return out


def ensure_built() -> str:
    """Compile the wire→tensor shim (shim.cpp) → .so path."""
    return _build_one([os.path.join(_DIR, "shim.cpp")], _SO, _HASH,
                      ["-fPIC", "-shared"])


def ensure_httpd_built() -> str:
    """Compile the native HTTP/2 front-end (httpd.cpp) → .so path."""
    return _build_one(
        [os.path.join(_DIR, "httpd.cpp")], _HTTPD_SO, _HTTPD_HASH,
        ["-fPIC", "-shared", "-pthread", f"-I{_DIR}"],
        hash_extra=[os.path.join(_DIR, "hpack_tables.h"),
                    os.path.join(_DIR, "h2_frame.h")])


def ensure_h2load_built() -> str:
    """Compile the C++ load client (h2load.cpp) → binary path."""
    return _build_one(
        [os.path.join(_DIR, "h2load.cpp")], _H2LOAD, _H2LOAD_HASH,
        [f"-I{_DIR}"],
        hash_extra=[os.path.join(_DIR, "h2_frame.h")])
