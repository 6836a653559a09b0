"""Builds the benchmark's native load generator at set-up.

``openloop.cpp`` is compiled with the system's g++ into
``perfbench/.build/openloop-<hash of source and flags>`` inside the
checkout (ignored by git), once per source change; a later run finds it.
"""

from __future__ import annotations

import hashlib
import os
import subprocess

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
FLAGS = ["-O2", "-std=c++17"]


def openloop() -> str:
    src = os.path.join(BENCH_DIR, "openloop.cpp")
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read() + " ".join(FLAGS).encode()).hexdigest()[:16]
    out_dir = os.path.join(BENCH_DIR, ".build")
    out = os.path.join(out_dir, f"openloop-{digest}")
    if os.path.exists(out):
        return out
    os.makedirs(out_dir, exist_ok=True)
    tmp = f"{out}.{os.getpid()}.tmp"
    r = subprocess.run(["g++", *FLAGS, "-o", tmp, src], capture_output=True, text=True)
    if r.returncode != 0:
        raise RuntimeError(f"g++ failed building the load generator:\n{r.stderr[-4000:]}")
    os.replace(tmp, out)
    return out
