"""The harness end to end on the CPU: cells found by name, a cell added
by files alone, the control, and planted faults that must read false."""

import json
import os

import pytest

from conftest import BENCH_DIR, CHECKOUT, checkout_with_cell, rehearse

FAULT_NODE = os.path.join(BENCH_DIR, "tests", "fault_node.py")


def test_every_cell_finds_its_files():
    bench = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(BENCH_DIR, "configs", w["config"] + ".json"))
        assert os.path.exists(os.path.join(BENCH_DIR, "traffic", w["traffic"] + ".json"))
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(BENCH_DIR, "metrics", m["name"] + ".py"))
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(CHECKOUT, c["file"]))


@pytest.mark.parametrize("workload", ["take-zipf", "take-uniform"])
def test_sound_run_is_correct(workload):
    rc, result, info, err = rehearse(workload, 11)
    assert rc == 3, err[-3000:]
    assert result["correct"], result
    assert info["compiles_in_window"] >= 0
    steps = info["refill_steps"]
    assert steps["exact"] > 0 and steps["capped"] > 0  # both kinds of step were judged
    if workload == "take-zipf":
        assert info["takes_429"] > 0  # the hot keys ran dry: denials were judged


def test_node_that_stalls_past_its_peers_timeout_stays_correct():
    """A node stopped for 4 s heals its peers on waking and drops its
    interval logs; what it held must still read correct."""
    rc, result, info, err = rehearse("take-zipf", 32, "--launcher", f"python3 {FAULT_NODE} stall")
    assert rc == 3, err[-3000:]
    assert result["correct"], result
    out = os.path.join(CHECKOUT, "chiprun_out", "perfbench-tests", "take-zipf-32", "vars.json")
    with open(out) as f:
        assert json.load(f)["drained"]["wire_fullstate_fallbacks"] > 0


@pytest.mark.parametrize("workload,fault,number", [
    ("take-zipf", "refill32", "refill_faults"),  # the control
    ("take-zipf", "answer", "answer_faults"),
    ("take-zipf", "stale_state", "own_lane_gap_keys"),
    ("take-zipf", "half_batch", "readback_gap_keys"),
    ("take-uniform", "half_batch", "readback_gap_keys"),
])
def test_planted_fault_reads_false(workload, fault, number):
    rc, result, info, err = rehearse(workload, 13, "--launcher", f"python3 {FAULT_NODE} {fault}")
    assert rc == 3, err[-3000:]
    assert result["correct"] is False, result
    assert result["compared"][number]["value"] > 0, result["compared"]


def test_cell_added_by_files_alone(tmp_path):
    """A new traffic file and a new BENCHMARK.json entry make a new cell;
    no file of the harness changes."""
    traffic = json.load(open(os.path.join(BENCH_DIR, "traffic", "take-zipf.json")))
    traffic["key_law"] = {"name": "zipf", "theta": 1.2}
    cell = {"name": "take-hot", "config": "sidecar3-10k", "traffic": "take-hot", "chips": 1, "why": "hotter keys"}
    co, bench = checkout_with_cell(tmp_path, cell, traffic)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "take-zipf" in m.get("workloads", []):
            m["workloads"].append("take-hot")
    with open(os.path.join(co, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    rc, result, info, err = rehearse("take-hot", 14, checkout=co)
    assert rc == 3, err[-3000:]
    assert result["correct"], result
    assert set(result["metrics"]) == {m["name"] for m in bench["end_to_end"]
                                      if "take-hot" in m.get("workloads", ["take-hot"])}


def test_no_accelerator_no_result():
    """Without --rehearse the run demands a TPU: on the CPU it exits 1
    and prints no result line."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload", "take-zipf",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=CHECKOUT, capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 1 and r.stdout.strip() == ""
