"""Loader for the native extension with pure-Python fallback.

``cerbos_native`` (native/src/cerbos_native.cpp) provides the host hot-path
primitives: the glob matcher and the batch double-key encoder. If the
extension isn't built yet, it is compiled on first import (g++, ~1s); if
that fails (no toolchain), callers fall back to the pure-Python
implementations transparently.
"""

from __future__ import annotations

import logging
import os
import subprocess
import sysconfig
from typing import Any, Optional

log = logging.getLogger("cerbos_tpu.native")

_native: Optional[Any] = None
_attempted = False


def _build() -> bool:
    here = os.path.dirname(os.path.abspath(__file__))
    native_dir = os.path.join(os.path.dirname(here), "native")
    if not os.path.isdir(native_dir):
        return False
    src = os.path.join(native_dir, "src", "cerbos_native.cpp")
    suffix = sysconfig.get_config_var("EXT_SUFFIX")
    target = os.path.join(here, f"cerbos_native{suffix}")
    if os.path.exists(target) and os.path.getmtime(target) >= os.path.getmtime(src):
        return True
    include = sysconfig.get_path("include")
    # several processes may build at once (test workers, forked servers): each
    # compiles to a name of its own and renames it into place, so that none
    # ever imports a half-written library
    tmp = f"{target}.{os.getpid()}.tmp"
    cmd = ["g++", "-O2", "-fPIC", "-shared", "-std=c++17", f"-I{include}", "-o", tmp, src]
    try:
        result = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
        if result.returncode == 0:
            os.replace(tmp, target)
    except (OSError, subprocess.TimeoutExpired) as e:
        log.debug("native build unavailable: %s", e)
        return False
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    if result.returncode != 0:
        log.warning("native build failed: %s", result.stderr.strip()[:500])
        return False
    return True


def get() -> Optional[Any]:
    """The cerbos_native module, or None when unavailable."""
    global _native, _attempted
    if _native is not None or _attempted:
        return _native
    _attempted = True
    if os.environ.get("CERBOS_TPU_NO_NATIVE"):
        return None
    try:
        if _build():
            from cerbos_tpu import cerbos_native  # type: ignore[attr-defined]

            _native = cerbos_native
    except Exception as e:  # noqa: BLE001
        log.debug("native extension unavailable: %s", e)
        _native = None
    return _native
