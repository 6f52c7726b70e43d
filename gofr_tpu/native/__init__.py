"""Native (C++) runtime components, compiled on demand.

The reference framework's runtime is compiled Go end to end; this package
holds the TPU framework's native equivalents for the CPU-bound plane —
currently `_gofr_http`, the HTTP/1.1 wire codec behind the protocol-mode
HTTP server (httpcore.cc; used by gofr_tpu/http/nativeserver.py).

Build strategy: pybind11 and pip are unavailable in the image, so the
extension is compiled straight from source with the system g++ against the
running interpreter's headers (`sysconfig`), cached under
``native/_build/`` keyed by source content+interpreter (an mtime says
when a checkout was unpacked, not what it holds). A build failure (no
compiler, exotic platform) degrades gracefully: `load_http_codec()` returns
None and the HTTP plane falls back to the pure-Python parser — behavior is
identical, only slower (see tests/test_native_http.py which asserts
codec/python parity).

Set GOFR_NATIVE=0 to disable native components entirely.
"""

from __future__ import annotations

import hashlib
import importlib.util
import os
import subprocess
import sys
import sysconfig

_HERE = os.path.dirname(os.path.abspath(__file__))
_BUILD_DIR = os.path.join(_HERE, "_build")

_http_codec = None
_http_codec_tried = False
_data_core = None
_data_core_tried = False


def _ext_suffix() -> str:
    return sysconfig.get_config_var("EXT_SUFFIX") or ".so"


def _build(src: str, modname: str) -> str | None:
    """Compile ``src`` into ``_build/<modname><ext_suffix>``; return the
    path, or None if compilation is impossible/fails."""
    src_path = os.path.join(_HERE, src)
    out_path = os.path.join(_BUILD_DIR, modname + _ext_suffix())
    stamp_path = out_path + ".stamp"
    with open(src_path, "rb") as f:
        stamp = f"{hashlib.sha256(f.read()).hexdigest()}:{sys.version_info[:2]}"
    if os.path.exists(out_path) and os.path.exists(stamp_path):
        try:
            with open(stamp_path) as f:
                if f.read() == stamp:
                    return out_path
        except OSError:
            pass
    include = sysconfig.get_paths()["include"]
    os.makedirs(_BUILD_DIR, exist_ok=True)
    # per-process tmp name: two processes building concurrently must not
    # interleave writes and os.replace a half-written .so into the cache
    tmp_out = out_path + f".tmp.{os.getpid()}"
    cmd = [
        "g++", "-O2", "-std=c++17", "-shared", "-fPIC",
        "-fvisibility=hidden", f"-I{include}", src_path, "-o", tmp_out,
    ]
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=120, check=False
        )
    except (OSError, subprocess.TimeoutExpired):
        return None
    if proc.returncode != 0:
        # leave a breadcrumb for debugging without crashing the app
        try:
            with open(os.path.join(_BUILD_DIR, modname + ".err"), "w") as f:
                f.write(proc.stderr)
        except OSError:
            pass
        return None
    os.replace(tmp_out, out_path)
    with open(stamp_path, "w") as f:
        f.write(stamp)
    return out_path


def _import_from(path: str, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    if spec is None or spec.loader is None:
        return None
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_data_core():
    """Return the `_gofr_data` extension (native batch gather for the
    training data-loader), or None when disabled/unbuildable."""
    global _data_core, _data_core_tried
    if _data_core_tried:
        return _data_core
    _data_core_tried = True
    if os.environ.get("GOFR_NATIVE", "1") == "0":
        return None
    try:
        path = _build("datacore.cc", "_gofr_data")
        if path:
            _data_core = _import_from(path, "_gofr_data")
    except Exception:  # noqa: BLE001 - native load must never break the app
        _data_core = None
    return _data_core


def load_http_codec():
    """Return the `_gofr_http` extension module, building it if needed;
    None when native components are disabled or the build fails."""
    global _http_codec, _http_codec_tried
    if _http_codec_tried:
        return _http_codec
    _http_codec_tried = True
    if os.environ.get("GOFR_NATIVE", "1") == "0":
        return None
    try:
        path = _build("httpcore.cc", "_gofr_http")
        if path:
            _http_codec = _import_from(path, "_gofr_http")
    except Exception:  # noqa: BLE001 - native load must never break the app
        _http_codec = None
    return _http_codec
