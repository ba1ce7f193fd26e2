"""Shared build/load machinery for the native (C++) runtime components.

Each component lives in src/ray_tpu_native/<name>.cc and is compiled on
demand into build/lib<name>-<srchash>-<machine>.so. Artifacts are keyed by
source hash + machine so a stale or cross-platform binary is never preferred
over a rebuild (checkout mtimes are meaningless), mirroring how the
reference pins its bazel outputs to the source tree state.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import threading
from typing import Dict, List, Optional

from ray_tpu._private import builtin_metrics

_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "src", "ray_tpu_native")
# <repo>/build — NOT <repo>/src/build (dirname(_SRC) is <repo>/src).
_BUILD_DIR = os.path.abspath(
    os.path.join(os.path.dirname(_SRC), os.pardir, "build"))

_locks: Dict[str, threading.Lock] = {}
_locks_guard = threading.Lock()


def _lock_for(name: str) -> threading.Lock:
    with _locks_guard:
        return _locks.setdefault(name, threading.Lock())


def cleanup_artifacts(build_dir: str, prefix: str, keep: Optional[str],
                      tmp: Optional[str]) -> None:
    """Remove a failed compile's temp file and superseded hash-named .so
    files so build/ doesn't grow without bound across source edits."""
    try:
        if tmp and os.path.exists(tmp):
            os.unlink(tmp)
        if keep is not None:
            for fname in os.listdir(build_dir):
                if (fname.startswith(prefix) and fname.endswith(".so")
                        and fname != keep):
                    os.unlink(os.path.join(build_dir, fname))
    except OSError:
        pass


def _library_path(name: str) -> Optional[str]:
    """build/lib<name>-<srchash>-<machine>.so for the current source, or
    None when there is no such component."""
    src = os.path.join(_SRC, f"{name}.cc")
    if not os.path.exists(src):
        return None
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    return os.path.join(
        _BUILD_DIR, f"lib{name}-{digest}-{platform.machine()}.so")


def built_components() -> Dict[str, bool]:
    """For each native component, whether its library for the current
    source is in build/. False once the runtime has used the component
    means g++ failed and it runs on its Python twin (components build on
    first use, so False before that only means "not needed yet")."""
    return {name: os.path.exists(_library_path(name) or "")
            for name in STRESS_COMPONENTS}


def build_library(name: str, extra_flags: Optional[List[str]] = None
                  ) -> Optional[str]:
    """Compile src/ray_tpu_native/<name>.cc into a shared library and return
    its path (cached by source hash + machine). None if unbuildable."""
    out = _library_path(name)
    if out is None:
        return None
    src = os.path.join(_SRC, f"{name}.cc")
    os.makedirs(_BUILD_DIR, exist_ok=True)
    prefix = f"lib{name}-"
    with _lock_for(name):
        if os.path.exists(out):
            return out
        tmp = f"{out}.tmp{os.getpid()}"
        try:
            # Only a checkout's first use of the component gets here.
            with builtin_metrics.setup_stage(
                    "native_build", "setup::native_build") as span:
                if span is not None:
                    span.attributes["lib"] = name
                subprocess.run(
                    ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", "-o",
                     tmp, src] + (extra_flags or []),
                    check=True, capture_output=True, timeout=120)
            os.replace(tmp, out)
        except (subprocess.SubprocessError, FileNotFoundError, OSError):
            cleanup_artifacts(_BUILD_DIR, prefix, keep=None, tmp=tmp)
            return None
        cleanup_artifacts(_BUILD_DIR, prefix, keep=os.path.basename(out),
                          tmp=None)
    return out


#: Every native component linked into the sanitizer stress binary.
STRESS_COMPONENTS = ("sched", "refcount", "pubsub", "shm_store",
                     "config", "memmon")


def build_stress_binary(sanitize: str) -> Optional[str]:
    """Compile the multithreaded stress driver (stress.cc) plus every
    native component into one executable under ``-fsanitize=<sanitize>``
    (thread | address) — the analog of the reference's TSAN/ASAN bazel
    configs (.bazelrc:92-116). Cached by the combined source hash; None
    when g++ or the sanitizer runtime is unavailable."""
    assert sanitize in ("thread", "address"), sanitize
    srcs = [os.path.join(_SRC, "stress.cc")] + [
        os.path.join(_SRC, f"{c}.cc") for c in STRESS_COMPONENTS]
    if not all(os.path.exists(s) for s in srcs):
        return None
    os.makedirs(_BUILD_DIR, exist_ok=True)
    h = hashlib.sha256()
    for s in srcs:
        with open(s, "rb") as f:
            h.update(f.read())
    digest = h.hexdigest()[:12]
    prefix = f"stress-{sanitize}-"
    out = os.path.join(
        _BUILD_DIR, f"{prefix}{digest}-{platform.machine()}")
    with _lock_for(f"stress:{sanitize}"):
        if os.path.exists(out):
            return out
        tmp = f"{out}.tmp{os.getpid()}"
        try:
            subprocess.run(
                ["g++", "-O1", "-g", "-std=c++17",
                 f"-fsanitize={sanitize}", "-o", tmp] + srcs +
                ["-lpthread", "-lrt"],
                check=True, capture_output=True, timeout=300)
            os.replace(tmp, out)
        except (subprocess.SubprocessError, FileNotFoundError, OSError):
            cleanup_artifacts(_BUILD_DIR, prefix, keep=None, tmp=tmp)
            return None
        cleanup_artifacts(_BUILD_DIR, prefix,
                          keep=os.path.basename(out), tmp=None)
    return out


def load_library(name: str, extra_flags: Optional[List[str]] = None,
                 keep_gil: bool = False) -> Optional[ctypes.CDLL]:
    path = build_library(name, extra_flags)
    if path is None:
        return None
    try:
        # keep_gil (ctypes.PyDLL): microsecond-scale native calls (map
        # insert under an uncontended mutex) must NOT release the GIL —
        # a release/reacquire pair per call becomes a GIL handoff convoy
        # under thread churn (profiled: 1.7us/call quiet, ~80us under an
        # 8-worker task storm). ONLY safe for functions that never block:
        # anything that waits (pubsub long-poll) or moves big payloads
        # (shm memcpy) stays on CDLL.
        return ctypes.PyDLL(path) if keep_gil else ctypes.CDLL(path)
    except OSError:
        return None


_loaded: Dict[str, Optional[ctypes.CDLL]] = {}


def load_library_cached(name: str,
                        extra_flags: Optional[List[str]] = None,
                        configure=None,
                        keep_gil: bool = False) -> Optional[ctypes.CDLL]:
    """Memoized load (failure included). ``configure(lib)`` runs once per
    process to set the ctypes argtypes/restypes — every native component
    wrapper shares this caching pattern instead of re-implementing it."""
    with _lock_for(f"load:{name}"):
        if name not in _loaded:
            lib = load_library(name, extra_flags, keep_gil=keep_gil)
            if lib is not None and configure is not None:
                configure(lib)
            _loaded[name] = lib
        return _loaded[name]
