"""The one way this repository compiles native code: g++ on first use,
one translation unit a shared object, into the git-ignored
`native/.build/`, again whenever the source is newer than the object."""

from __future__ import annotations

import os
import subprocess
from typing import Sequence

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
BUILD_DIR = os.path.join(NATIVE_DIR, ".build")


def build_if_stale(src: str, so: str, extra: Sequence[str] = ()) -> None:
    """Compile `src` into `so` unless `so` is there and no older.  Raises
    RuntimeError with the compiler's words when the build fails, OSError
    where there is no compiler or no source."""
    if os.path.exists(so) and os.path.getmtime(so) >= os.path.getmtime(src):
        return
    os.makedirs(os.path.dirname(so), exist_ok=True)
    tmp = so + f".tmp.{os.getpid()}"
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
           "-Wall", "-Wextra"]
    if os.environ.get("VENEUR_TPU_TEST"):
        # the test build path promotes warnings to errors so a warning
        # introduced by a change fails the suite, not just stderr
        cmd.append("-Werror")
    cmd += [*extra, "-o", tmp, src]
    build = subprocess.run(cmd, capture_output=True, text=True)
    if build.returncode != 0:
        raise RuntimeError(
            f"native build failed ({' '.join(cmd)}):\n"
            f"{build.stderr[-4000:]}")
    os.replace(tmp, so)
