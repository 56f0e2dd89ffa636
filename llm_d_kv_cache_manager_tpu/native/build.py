"""Builds the native library with g++ (no CUDA, no external deps).

Usage: ``python -m llm_d_kv_cache_manager_tpu.native.build [--force]``.
The library lands next to this file and is picked up by the ctypes loader;
callers that find no compiler fall back to pure Python transparently.

The library is a generated file (``*.so`` is in .gitignore), so which
one gets loaded is decided by the committed sources alone: its name
carries a digest of ``src/`` and the compile flags, and a library built
from anything else has another name and is never loaded.
"""

from __future__ import annotations

import glob
import hashlib
import os
import shutil
import subprocess
import sys
import tempfile

_SRC_FILES = ["hashing.cpp", "numa.cpp", "thread_pool.cpp", "file_io.cpp", "engine.cpp"]
_HEADERS = ["kvtpu_native.hpp", "debug_utils.hpp"]
_FLAGS = [
    "-O3", "-std=c++17", "-fPIC", "-shared", "-pthread", "-Wall", "-Wextra",
]

_LIB_STEM = "libkvtpu_native"


def _paths():
    here = os.path.dirname(os.path.abspath(__file__))
    return here, os.path.join(here, "src")


def _source_digest(src_dir: str) -> str:
    digest = hashlib.sha256(" ".join(_FLAGS).encode())
    for name in (*_SRC_FILES, *_HEADERS):
        with open(os.path.join(src_dir, name), "rb") as handle:
            digest.update(name.encode() + b"\0" + handle.read())
    return digest.hexdigest()[:16]


def lib_path() -> str:
    here, src_dir = _paths()
    return os.path.join(
        here, f"{_LIB_STEM}.{_source_digest(src_dir)}.so"
    )


def build(force: bool = False) -> str | None:
    """Compile the library; returns its path, or None if no compiler."""
    here, src_dir = _paths()
    lib = lib_path()
    if not force and os.path.exists(lib):
        return lib
    compiler = shutil.which("g++") or shutil.which("c++")
    if compiler is None:
        return None
    sources = [os.path.join(src_dir, f) for f in _SRC_FILES]
    # Build into a temp file then rename: concurrent builders (e.g.
    # parallel test workers) must never load a torn .so.
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=here)
    os.close(fd)
    cmd = [compiler, *_FLAGS, "-o", tmp, *sources]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
        os.replace(tmp, lib)
    except subprocess.CalledProcessError as exc:
        os.unlink(tmp)
        raise RuntimeError(
            f"native build failed:\n{exc.stderr}"
        ) from exc
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise
    # Libraries of other source states are dead weight (a process that
    # already loaded one keeps its mapping).
    for stale in glob.glob(os.path.join(here, f"{_LIB_STEM}*.so")):
        if stale != lib:
            os.unlink(stale)
    return lib


def build_stress(tsan: bool = False) -> str | None:
    """Compile the TSan stress harness (src/stress_main.cpp); returns
    the binary path, or None if no compiler.  With ``tsan=True`` the
    whole engine is instrumented with ThreadSanitizer — the race
    detection SURVEY.md §5 notes the reference never wired up."""
    here, src_dir = _paths()
    compiler = shutil.which("g++") or shutil.which("c++")
    if compiler is None:
        return None
    out = os.path.join(here, "stress_tsan" if tsan else "stress")
    sources = [os.path.join(src_dir, f) for f in _SRC_FILES]
    sources.append(os.path.join(src_dir, "stress_main.cpp"))
    cmd = [compiler, "-std=c++17", "-pthread", "-Wall", "-Wextra"]
    if tsan:
        cmd += ["-fsanitize=thread", "-O1", "-g"]
    else:
        cmd += ["-O2"]
    # Temp-then-rename like build(): concurrent builders (parallel test
    # workers) must never exec a torn or ETXTBSY-blocked binary.
    fd, tmp = tempfile.mkstemp(prefix="stress.", dir=here)
    os.close(fd)
    cmd += ["-o", tmp, *sources]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
        os.chmod(tmp, 0o755)
        os.replace(tmp, out)
    except subprocess.CalledProcessError as exc:
        os.unlink(tmp)
        raise RuntimeError(f"stress build failed:\n{exc.stderr}") from exc
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise
    return out


if __name__ == "__main__":
    if "--stress" in sys.argv or "--stress-tsan" in sys.argv:
        binary = build_stress(tsan="--stress-tsan" in sys.argv)
        if binary is None:
            print("no C++ compiler found")
            sys.exit(1)
        print(f"built {binary}; running")
        # Scratch dir cleaned up after the run (repeated `make
        # native-race` must not accumulate ~26 MB per run in /tmp).
        with tempfile.TemporaryDirectory(
            prefix="kvtpu-stress-"
        ) as scratch:
            env = dict(
                os.environ,
                TSAN_OPTIONS="halt_on_error=1",
                KVTPU_STRESS_DIR=scratch,
            )
            sys.exit(subprocess.run([binary], env=env).returncode)
    result = build(force="--force" in sys.argv)
    if result is None:
        print("no C++ compiler found; pure-Python fallback will be used")
        sys.exit(1)
    print(f"built {result}")
