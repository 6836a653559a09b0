"""Shared helpers of the benchmark's tests: a CPU rehearsal of a cell."""

import json
import os
import subprocess
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)

SMALL = ["--buckets", "1024", "--keys", "600", "--rate-per-s", "600"]


def rehearse(workload, seed, *extra, checkout=CHECKOUT, timeout=400):
    """Run one cell on the CPU at a small size; → (rc, result dict or
    None, run.json dict or None, stderr)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out_dir = os.path.join(checkout, "chiprun_out", "perfbench-tests", f"{workload}-{seed}")
    cmd = [sys.executable, os.path.join(checkout, "perfbench", "run.py"), "--workload", workload,
           "--seed", str(seed), "--seconds", "4", "--trace", "0", "--rehearse", "--out", out_dir,
           *SMALL, *extra]
    r = subprocess.run(cmd, cwd=checkout, capture_output=True, text=True, env=env, timeout=timeout)
    result = None
    for line in r.stdout.splitlines():
        if line.startswith("REHEARSAL "):
            result = json.loads(line[len("REHEARSAL "):])
    info = None
    path = os.path.join(out_dir, "run.json")
    if os.path.exists(path):
        with open(path) as f:
            info = json.load(f)
    return r.returncode, result, info, r.stderr


def checkout_with_cell(tmp_path, cell, traffic=None):
    """A copy of the benchmark with one more cell in its BENCHMARK.json
    (and, where given, a new traffic file): a cell added by files and an
    entry alone. The program is linked in, not copied."""
    import shutil

    co = tmp_path / "checkout"
    co.mkdir()
    shutil.copytree(BENCH_DIR, co / "perfbench", ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(CHECKOUT, "patrol_tpu"), co / "patrol_tpu")
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if traffic is not None:
        (co / "perfbench" / "traffic" / f"{cell['traffic']}.json").write_text(json.dumps(traffic))
    bench["workloads"].append(cell)
    (co / "BENCHMARK.json").write_text(json.dumps(bench))
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(CHECKOUT, ".jax_cache"))
    return str(co), bench
