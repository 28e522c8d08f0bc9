"""ctypes loader for the native ffsearch library.

Analog of the reference's in-process C++ search invoked through a Legion
task boundary (GRAPH_OPTIMIZE_TASK_ID, src/runtime/model.cc:2825): here the
boundary is a JSON string through a C ABI. The library is git-ignored and
built from native/ by `make`, which the loader runs before every first
load — a no-op when the library is newer than its sources — so a library
older than ffs_*.hpp/.cpp is never used: a stale one prices a different
lattice than the Python side replays.
"""

from __future__ import annotations

import ctypes
import json
import os
import subprocess
from typing import Any, Dict, Optional

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libffsearch.so")

_lib = None
_load_error: Optional[str] = None


def _make() -> Optional[str]:
    """Bring libffsearch.so up to date with its sources; the error text
    when that fails, else None."""
    try:
        r = subprocess.run(["make", "-C", _NATIVE_DIR], capture_output=True,
                           text=True, timeout=300)
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"`make -C native` did not run: {e!r}"
    if r.returncode != 0:
        return f"`make -C native` failed:\n{r.stderr[-2000:]}"
    return None


def get_lib():
    """Build (when stale) and load the native library; None if unavailable."""
    global _lib, _load_error
    if _lib is not None:
        return _lib
    if _load_error is not None:
        return None
    _load_error = _make()
    if _load_error is not None:
        return None
    try:
        lib = ctypes.CDLL(_LIB_PATH)
    except OSError as e:  # pragma: no cover
        _load_error = str(e)
        return None
    for fn in ("ffs_optimize", "ffs_simulate", "ffs_list_rules",
               "ffs_match_rules"):
        getattr(lib, fn).argtypes = [ctypes.c_char_p]
        getattr(lib, fn).restype = ctypes.c_void_p
    lib.ffs_free.argtypes = [ctypes.c_void_p]
    lib.ffs_version.restype = ctypes.c_char_p
    _lib = lib
    return _lib


def _call(fn_name: str, request: Dict[str, Any]) -> Dict[str, Any]:
    lib = get_lib()
    if lib is None:
        raise RuntimeError(f"ffsearch native library unavailable: {_load_error}")
    fn = getattr(lib, fn_name)
    ptr = fn(json.dumps(request).encode())
    try:
        out = json.loads(ctypes.string_at(ptr).decode())
    finally:
        lib.ffs_free(ptr)
    if "error" in out:
        raise RuntimeError(f"ffsearch: {out['error']}")
    return out


def native_optimize(request: Dict[str, Any]) -> Dict[str, Any]:
    return _call("ffs_optimize", request)


def native_simulate(request: Dict[str, Any]) -> Dict[str, Any]:
    return _call("ffs_simulate", request)


def native_list_rules(rules: Any) -> Dict[str, Any]:
    """Parse a substitution rule corpus (reference RuleCollection JSON or
    the native list form); returns {"count": N, "names": [...]}."""
    return _call("ffs_list_rules", rules)


def native_match_rules(request: Dict[str, Any]) -> Dict[str, Any]:
    """Offline rule audit (corpus-sweep harness): for each rule in
    request["subst_rules"], count matches on request["nodes"], how many
    structurally apply, and whether every rewritten graph still prices
    under the DP. Returns {rule_name: {matches, applied, priced}}."""
    return _call("ffs_match_rules", request)


def available() -> bool:
    return get_lib() is not None
