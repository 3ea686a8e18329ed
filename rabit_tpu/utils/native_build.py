"""Build the native core (``native/build/librabit_tpu_core.so``) from the
committed sources when it is missing or older than them.

``native/build/`` is ignored by git, so a fresh checkout (the driver's,
or the copy the chip machine gets) has no library. The test harness
(``tests/conftest.py``) calls :func:`ensure_built` before any test runs.
"""

from __future__ import annotations

import glob
import os
import shutil
import subprocess
import sys


def _sources(root: str) -> list:
    return [p for pat in ("src/**/*", "include/**/*", "CMakeLists.txt")
            for p in glob.glob(os.path.join(root, "native", pat),
                               recursive=True) if os.path.isfile(p)] + \
        glob.glob(os.path.join(root, "examples", "cc", "*.cc")) + \
        glob.glob(os.path.join(root, "native", "test", "*.cc"))


def _gxx_build(root: str, lib: str) -> bool:
    """Bare-compiler fallback for containers without cmake: the shared
    library compiles with one g++ invocation; the cmake-only C++
    selftest binaries are skipped."""
    gxx = shutil.which("g++") or shutil.which("c++")
    srcs = sorted(glob.glob(os.path.join(root, "native", "src", "*.cc")))
    if gxx is None or not srcs:
        return False
    os.makedirs(os.path.dirname(lib), exist_ok=True)
    try:
        subprocess.run(
            [gxx, "-shared", "-fPIC", "-O2", "-std=c++17", "-Wall",
             "-I", os.path.join(root, "native", "include"),
             *srcs, "-o", lib, "-pthread"],
            check=True, capture_output=True, timeout=300)
    except Exception as e:
        detail = (getattr(e, "stderr", b"") or b"").decode(errors="replace")
        print(f"[native_build] g++ fallback build failed: {e}\n{detail}",
              file=sys.stderr)
        return False
    print(f"[native_build] built {lib} via g++ fallback (no cmake)",
          file=sys.stderr)
    return True


def ensure_built(root: str) -> str:
    """Return the library path, (re)building it first when it is missing
    or stale. Raises RuntimeError when a stale library cannot be rebuilt
    (testing stale binaries against edited sources would report green
    for broken code); a missing one that cannot be built is reported on
    stderr and left missing."""
    lib = os.path.join(root, "native", "build", "librabit_tpu_core.so")
    srcs = _sources(root)
    stale = os.path.isfile(lib) and \
        os.path.getmtime(lib) < max(map(os.path.getmtime, srcs))
    if os.path.isfile(lib) and not stale:
        return lib
    gen = ["-G", "Ninja"] if shutil.which("ninja") else []
    try:
        subprocess.run(
            ["cmake", "-S", os.path.join(root, "native"),
             "-B", os.path.join(root, "native", "build"),
             *gen, "-DCMAKE_BUILD_TYPE=Release"],
            check=True, capture_output=True, timeout=120)
        subprocess.run(
            ["cmake", "--build", os.path.join(root, "native", "build"),
             "--parallel"],
            check=True, capture_output=True, timeout=300)
    except Exception as e:
        detail = (getattr(e, "stderr", b"") or b"").decode(errors="replace")
        if _gxx_build(root, lib):
            return lib
        if stale:
            raise RuntimeError(f"native rebuild failed with stale {lib}:\n"
                               f"{e}\n{detail}") from e
        print(f"[native_build] native build failed: {e}\n{detail}",
              file=sys.stderr)
    return lib
