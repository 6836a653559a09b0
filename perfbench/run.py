#!/usr/bin/env python3
"""Run one benchmark cell against the node on the chip.

    python3 perfbench/run.py --workload NAME --seed N --seconds S --trace 0|1

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``perfbench/configs/<config>.json``: the node's flags, the key
population, the bucket rate, the peers) and a traffic mix
(``perfbench/traffic/<traffic>.json``); each per-layer metric is read by
``perfbench/metrics/<name>.py``. Nothing here is specific to a cell.

One run: boot the node as the one process that holds the chip (this
process never starts a JAX backend), let its peers bind the key
population, open the window for ``--seconds``, drain, read back, compare
with the reference, stop the node, print the result. With ``--trace 1``
the node captures a device trace over the middle of the window and the
result carries the per-layer metrics instead of the end-to-end ones.

The last line of stdout is the result as one JSON object. The compared
numbers and their limits are the last lines of stderr. Without a TPU,
or with fewer chips than the cell asks for, the run exits 1 and prints
no result. ``--rehearse`` runs on whatever backend ``JAX_PLATFORMS``
names, at sizes given on the command line, and never prints a result.
"""

from __future__ import annotations

import time

PROCESS_START_NS = time.monotonic_ns()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)

import numpy as np  # noqa: E402

from perfbench import build, checks, node as node_mod, peers as peers_mod, roofline  # noqa: E402
from perfbench import traffic as traffic_mod, xplane  # noqa: E402

NANO = 1_000_000_000
DRAIN_S = 60.0  # how long past the window an answer may still come
TRACE_S = 2.0  # the device trace's length, over the middle of the window
HOT_KEYS = 20  # the most-taken keys, read back besides the seeded sample


class RunFailed(Exception):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise RunFailed(f"no workload {workload!r} in BENCHMARK.json")


def load_metric_reader(name: str):
    path = os.path.join(BENCH_DIR, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"perfbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Run:
    """What one run saw; the per-layer readers take their numbers from it."""

    def __init__(self):
        self.vars0: dict = {}
        self.vars1: dict = {}
        self.trace_vars0: dict = {}
        self.trace_vars1: dict = {}
        self.trace: dict = {}
        self.device: dict = {}
        self.config: dict = {}
        self.rehearse = False  # a rehearsal's device has no peaks: no roofline

    @staticmethod
    def _delta(v0: dict, v1: dict, key: str) -> int:
        return int(v1.get(key, 0)) - int(v0.get(key, 0))

    @staticmethod
    def _hist_delta(v0: dict, v1: dict, name: str):
        h0 = v0.get("histograms", {}).get(name, {})
        h1 = v1.get("histograms", {}).get(name, {})
        return h1.get("count", 0) - h0.get("count", 0), h1.get("sum", 0) - h0.get("sum", 0)

    def _pair(self, traced: bool):
        if traced:
            return self.trace_vars0, self.trace_vars1
        # The profiler stalls the node while it captures, so in a traced
        # run the host's spans are read over the window before the trace.
        return self.vars0, (self.trace_vars0 or self.vars1)

    def counter(self, key: str, traced: bool = False) -> int:
        return self._delta(*self._pair(traced), key)

    def hist(self, name: str, traced: bool = False):
        """(count, sum) of a node histogram over the window (before the
        trace, in a traced run) or over the trace."""
        return self._hist_delta(*self._pair(traced), name)

    def hist_mean_ms(self, name: str) -> Optional[float]:
        n, s = self.hist(name)
        return s / n / 1e6 if n > 0 else None

    def kernel_s(self, names: List[str]) -> Optional[float]:
        k = self.trace.get("kernels", {})
        t = sum(k.get(n, 0.0) for n in names)
        return t if t > 0 else None

    def roofline_share(self, bytes_moved: int, names: List[str]) -> Optional[float]:
        t = self.kernel_s(names)
        if t is None or bytes_moved <= 0 or self.rehearse:
            return None
        return roofline.share(bytes_moved, t, self.device["kind"])

    def idle_share(self) -> Optional[float]:
        if not self.trace.get("window_s"):
            return None
        return 100.0 * (1.0 - self.trace["busy_s"] / self.trace["window_s"])


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="run on the JAX_PLATFORMS backend; never prints a result")
    p.add_argument("--buckets", type=int, help="rehearsal: node bucket slots")
    p.add_argument("--node-lanes", type=int, help="rehearsal: node lanes")
    p.add_argument("--keys", type=int, help="rehearsal: key population")
    p.add_argument("--rate-per-s", type=float, help="offered rate (knee sweeps, rehearsals)")
    p.add_argument("--sweep", help="comma-separated offered rates, one step each (knee.py)")
    p.add_argument("--launcher", help="the node's command instead of python -m patrol_tpu "
                   "(a node with a planted fault: perfbench/tests/fault_node.py)")
    p.add_argument("--out", help="directory for this run's files")
    args = p.parse_args(argv)

    env_platforms = os.environ.get("JAX_PLATFORMS", "")
    if args.rehearse:
        platform = env_platforms.split(",")[0] or "cpu"
    else:
        if env_platforms and "tpu" not in env_platforms.split(","):
            log(f"FAILED: JAX_PLATFORMS={env_platforms} leaves no tpu")
            return 1
        platform = "tpu"
    try:
        result, numbers = run(args, platform)
    except SweepDone:
        return 0
    except (RunFailed, node_mod.NodeError, OSError, RuntimeError, ValueError, KeyError) as exc:
        log(f"FAILED: {type(exc).__name__}: {exc}")
        return 1
    for k, v in numbers.items():
        log(f"compared {k} = {v} (limit {checks.LIMITS[k]})")
    if args.rehearse:
        print("REHEARSAL " + json.dumps(result), flush=True)
        return 3
    print(json.dumps(result), flush=True)
    return 0


def run(args, platform: str):
    bench = load_json(CHECKOUT, "BENCHMARK.json")
    cell = find_cell(bench, args.workload)
    config = load_json(BENCH_DIR, "configs", f"{cell['config']}.json")
    traffic = load_json(BENCH_DIR, "traffic", f"{cell['traffic']}.json")
    node_cfg = dict(config["node"])
    if args.rehearse:
        for key, attr in (("buckets", "buckets"), ("node_lanes", "node_lanes")):
            if getattr(args, attr):
                node_cfg[key] = getattr(args, attr)
        if args.keys:
            config["keys"] = args.keys
    if args.rate_per_s:
        traffic["rate_per_s"] = args.rate_per_s
    seconds = float(args.seconds)
    out_dir = args.out or os.path.join(
        CHECKOUT, "chiprun_out", "perfbench", f"{args.workload}-s{args.seed}-t{args.trace}"
        + ("-rehearsal" if args.rehearse else "")
    )
    os.makedirs(out_dir, exist_ok=True)

    names = traffic_mod.key_names(config)
    n_keys = len(names)
    freq, per_ns = traffic_mod.parse_rate(config["rate"])
    cap_nt = freq * NANO
    n_peers = int(config["peers"])
    client_bin = build.openloop()

    ps = peers_mod.PeerSet(
        n_peers, [n.encode() for n in names], cap_nt,
        window=int(traffic["peer_window"]), retx_ms=float(traffic["peer_retx_ms"]),
        flush_ms=float(traffic["peer_flush_ms"]),
    )
    node = node_mod.Node(
        node_cfg, ps.addrs, platform, os.path.join(out_dir, "node.log"), bool(args.trace),
        launcher=args.launcher.split() if args.launcher else None,
    )
    r = Run()
    r.config = config
    r.rehearse = args.rehearse
    numbers: Dict[str, int] = {}
    info: dict = {"workload": args.workload, "seed": args.seed, "seconds": seconds}
    trace_dir = None
    client = None
    try:
        v = node.wait_ready(float(config.get("boot_timeout_s", 1200)))
        r.device = node_mod.device_of(v)
        log(f"device: {r.device}")
        if not args.rehearse:
            if r.device["platform"] != "tpu":
                raise RunFailed(f"the node runs on {r.device['platform']}, not a tpu")
            if r.device["count"] < int(cell["chips"]):
                raise RunFailed(f"{r.device['count']} chips, the cell asks for {cell['chips']}")
            roofline.peaks(r.device["kind"])  # an unknown chip is an error
        ps.attach(node.node_port, peers_mod.member_slots(node.node_addr, ps.addrs), int(v["node_slot"]))
        info["node_slot"] = int(v["node_slot"])

        # -- set-up: the peers bind the key population -----------------
        # Each peer has spent one token of every bucket.
        hook = traffic_mod.TakePeers(args.seed, traffic, config, seconds, n_peers)
        for p in ps.peers:
            ps.set_lane(p, np.arange(n_keys), np.zeros(n_keys, np.int64), np.full(n_keys, NANO, np.int64))
        ps.on_tick = hook
        ps.start()
        t_pre = time.monotonic()
        wait_until(lambda: ps.all_answered(), 30, "the node never answered the peers' adverts", ps)
        wait_until(ps.idle, 600, "the preload was never acknowledged", ps)
        # One read of a bound key compiles the stats path's gather
        # (`read_rows`), which the node's warm-up leaves to first use:
        # else the node's own anti-entropy or scrape mirror may compile
        # it inside the window.
        node.tokens([names[0]])
        v = node.wait_quiet(0.5, 300)
        info["preload_s"] = time.monotonic() - t_pre
        info["bound_buckets"] = v.get("buckets")
        info["hosted_buckets_after_preload"] = v.get("engine_hosted_buckets")
        log(f"preload: {n_keys} keys bound by {n_peers} peers in {info['preload_s']:.2f}s; "
            f"buckets={v.get('buckets')} hosted={v.get('engine_hosted_buckets')}")

        # -- the window ------------------------------------------------
        if args.sweep:
            info["sweep"] = sweep(args, node, ps, traffic, config, names, client_bin, out_dir, seconds)
            with open(os.path.join(out_dir, "sweep.json"), "w") as f:
                json.dump(info, f, indent=1)
            print(json.dumps(info["sweep"]), flush=True)
            raise SweepDone()
        sched = traffic_mod.take_schedule(args.seed, "client", traffic, config, seconds)
        results_path = os.path.join(out_dir, "results.bin")
        client, t0 = start_client(client_bin, node, config, traffic, names, sched, out_dir, results_path)
        r.vars0 = node.vars()
        hook.t0_ns = t0
        ps.measure_from_ns, ps.measure_to_ns = t0, t0 + int(seconds * 1e9)
        setup_s = (t0 - PROCESS_START_NS) / 1e9
        sleep_until(t0)
        log_lo = node.log_size()
        if args.trace:
            trace_s = min(TRACE_S, seconds / 2)
            sleep_until(t0 + int((seconds - trace_s) / 2 * 1e9))
            r.trace_vars0 = node.vars()
            trace_dir = node.trace(trace_s)
            r.trace_vars1 = node.vars()
            info["trace_s"] = trace_s
        sleep_until(t0 + int(seconds * 1e9))
        r.vars1 = node.vars()
        log_hi = node.log_size()
        t_close = time.monotonic_ns()

        # -- drain, then read back -------------------------------------
        out, err = client.communicate(timeout=DRAIN_S + 60)
        if client.returncode != 0 or "DONE" not in out:
            raise RunFailed(f"load generator rc={client.returncode}: {err[-2000:]}")
        res = traffic_mod.read_results(results_path)
        wait_until(lambda: ps.idle() and hook.done(), DRAIN_S, "peers never drained", ps)
        v_end = node.wait_quiet(1.0, DRAIN_S, extra=ps.idle)
        r.device = node_mod.device_of(v_end)
        ps.check()
        # A node that healed its peers (silent past their liveness
        # timeout) dropped its unacknowledged intervals and leaves the
        # repair to anti-entropy, which these peers do not speak: where
        # its last published `taken` is not the 200s received, ask it for
        # the key's lanes, as a healing peer does, and take the answer as
        # its last publish.
        admitted = checks.admitted_per_key(sched["key"], res["status"], n_keys)
        stale = np.flatnonzero(ps.node_taken != admitted * NANO)
        info["lanes_requested"] = len(stale)
        if len(stale):
            ps.request_states(stale)
            try:
                wait_until(lambda: ps.state_replies.issuperset(stale.tolist()), 10,
                           "the node did not answer for its lanes", ps)
            except RunFailed:
                pass  # a key it did not answer for stays as published
        # A sample drawn from the seed, with the most-taken keys in it.
        hot = np.argsort(-np.bincount(sched["key"], minlength=n_keys), kind="stable")[:HOT_KEYS]
        sample = np.union1d(hot, traffic_mod.rng(args.seed, "readback").choice(
            n_keys, size=min(int(config["readback_sample"]), n_keys), replace=False))
        info["drain_s"] = (time.monotonic_ns() - t_close) / 1e9
        t_rb = time.monotonic()
        got = node.tokens([names[k] for k in sample.tolist()])
        got = {k: got[names[k]] for k in sample.tolist()}
        info["readback_s"] = time.monotonic() - t_rb
        info["compiles_in_window"] = node_mod.count_compiles(node.log_slice(log_lo, log_hi))
    finally:
        if client is not None and client.poll() is None:
            client.kill()
            client.wait()
        try:
            ps.stop()
        finally:
            node.stop()

    # -- the comparisons ---------------------------------------------------
    peer_added = [p.added for p in ps.peers]
    peer_taken = [p.taken for p in ps.peers]
    rx_errors = sum(p.rx_errors for p in ps.peers) + int(v_end.get("wire_delta_rx_errors", 0))
    window_ns = int(seconds * 1e9)
    metrics: Dict[str, float] = {}
    keys = sched["key"]
    answered = res["status"] > 0
    pub = np.array(ps.node_published, np.int64).reshape(-1, 5)
    own_added_at = checks.StepFn(pub[:, 0], pub[:, 1], pub[:, 2], np.zeros(n_keys, np.int64))
    peer_spend = checks.peer_spend_at([p.shipped for p in ps.peers], n_keys)
    numbers["answer_faults"] = checks.answer_faults(
        keys, t0 + res["sent"], t0 + res["done"], res["status"], res["value"], freq, cap_nt,
        own_added_at.at, peer_spend)
    all_keys = np.arange(n_keys)
    info["refill_steps"] = {}
    numbers["refill_faults"] = checks.refill_faults(
        pub[:, [0, 2, 3, 4]], freq, per_ns, peer_spend(all_keys, np.full(n_keys, t0)),
        sum(peer_taken), info["refill_steps"])
    numbers["own_lane_gap_keys"] = checks.own_lane_gap_keys(admitted, ps.node_taken)
    want = checks.reference_tokens(cap_nt, peer_added, peer_taken, ps.node_added, admitted, sample)
    numbers["readback_gap_keys"] = checks.readback_gap_keys(got, want, sample)
    numbers["rx_errors"] = rx_errors
    attempted, failed = len(res), int((~np.isin(res["status"], (200, 429))).sum())
    lat_ns = np.where(answered, res["done"] - sched["due_ns"],
                      sched["due_ns"][-1] + int(DRAIN_S * 1e9) - sched["due_ns"])
    in_window = answered & (res["done"] <= window_ns)
    metrics["take_p50_ms"] = float(np.quantile(lat_ns, 0.5)) / 1e6
    metrics["take_p99_ms"] = float(np.quantile(lat_ns, 0.99)) / 1e6
    metrics["takes_per_s"] = float(in_window.sum()) / seconds
    sent = res["sent"] >= 0
    info["generator_late_p99_ms"] = float(np.quantile(res["sent"][sent] - sched["due_ns"][sent], 0.99)) / 1e6
    info["takes_offered"] = attempted
    info["takes_200"] = int((res["status"] == 200).sum())
    info["takes_429"] = int((res["status"] == 429).sum())
    info["node_lane_publishes"] = len(pub)
    info["lanes_answered"] = len(ps.state_replies)
    info["peer_takes_attempted"] = sum(hook.attempted)
    info["peer_takes_admitted"] = sum(hook.admitted)

    # Replication: every peer interval first sent inside the window,
    # from its first send to its ack (one never acked: the drain's end).
    lags = [(p.acked_at.get(seq, t_close + int(DRAIN_S * 1e9)) - first)
            for p in ps.peers for seq, first, _ in p.intervals if t0 <= first < t0 + window_ns]
    info["repl_lag_p50_ms"] = float(np.quantile(lags, 0.5)) / 1e6 if lags else None
    if lags:
        metrics["repl_lag_p99_ms"] = float(np.quantile(lags, 0.99)) / 1e6
    metrics["setup_s"] = setup_s
    info["intervals_sent"] = len(lags)
    info["retransmits"] = sum(p.retransmits for p in ps.peers)
    info["unacked_at_end"] = sum(len(p.unacked) for p in ps.peers)
    info["peer_tick_late_p99_ms"] = (
        float(np.quantile(ps.tick_late_ns, 0.99)) / 1e6 if ps.tick_late_ns else None
    )
    info["peer_window_occupancy"] = float(np.mean(ps.occupancy)) if ps.occupancy else None
    info["node_intervals_received"] = ps.node_intervals

    log("generator: " + json.dumps({k: info.get(k) for k in (
        "generator_late_p99_ms", "peer_tick_late_p99_ms", "peer_window_occupancy", "compiles_in_window")}))
    log("end to end: " + json.dumps(metrics))
    log("judged: " + json.dumps({k: info[k] for k in ("takes_200", "takes_429", "refill_steps")}))
    log(f"compiles in window: {info['compiles_in_window']}")

    # -- per-layer metrics (traced run) --------------------------------------
    breakdown = None
    if args.trace:
        if trace_dir is None or xplane.find_xplane(trace_dir) is None:
            raise RunFailed("the node wrote no device trace")
        events = xplane.load_events(xplane.find_xplane(trace_dir))
        r.trace = xplane.reduce(events, int(info["trace_s"] * 1e9))
        shutil.rmtree(trace_dir, ignore_errors=True)
        info["trace_kernels"] = r.trace.get("kernels")
        layer = {}
        for m in bench["per_layer"]:
            if args.workload not in m.get("workloads", [args.workload]):
                continue
            value = load_metric_reader(m["name"])(r)
            if value is not None:
                layer[m["name"]] = {"value": value, "unit": m["unit"]}
        metrics = layer
        if r.trace:
            breakdown = {"device_ops": r.trace["device_ops"], "idle_gaps": r.trace["idle_gaps"]}
    else:
        info["end_to_end"] = metrics
        wanted = [m for m in bench["end_to_end"] if args.workload in m.get("workloads", [args.workload])]
        units = {m["name"]: m["unit"] for m in wanted}
        metrics = {k: {"value": v, "unit": units[k]} for k, v in metrics.items() if k in units}

    device = {
        "platform": r.device["platform"], "kind": r.device["kind"], "count": r.device["count"],
        "memory_peak_bytes": r.device["memory_peak_bytes"],
    }
    if args.trace and r.trace:
        device["busy_s"] = r.trace["busy_s"]
        device["window_s"] = r.trace["window_s"]
    result = {
        "correct": checks.correct(numbers),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = {k: {"value": v, "limit": checks.LIMITS[k]} for k, v in numbers.items()}
    info["result"] = result
    info["run_s"] = (time.monotonic_ns() - PROCESS_START_NS) / 1e9
    with open(os.path.join(out_dir, "run.json"), "w") as f:
        json.dump(info, f, indent=1, default=float)
    with open(os.path.join(out_dir, "vars.json"), "w") as f:
        json.dump({"window_open": r.vars0, "window_close": r.vars1, "drained": v_end}, f)
    return result, numbers


class SweepDone(Exception):
    pass


def start_client(client_bin, node, config, traffic, names, sched, out_dir, results_path):
    """Start the open-loop client on ``sched``; → (process, T0 in
    monotonic ns, when the first request is due)."""
    sched_path = os.path.join(out_dir, "schedule.bin")
    targets_path = os.path.join(out_dir, "targets.txt")
    traffic_mod.write_schedule(sched_path, sched["due_ns"], sched["key"])
    with open(targets_path, "w") as f:
        for n in names:
            f.write(traffic["request"].format(key=n, rate=config["rate"]) + "\n")
    client = subprocess.Popen(
        [client_bin, "127.0.0.1", str(node.api_port), str(int(config["connections"])),
         sched_path, targets_path, results_path, str(int(DRAIN_S * 1000))],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    line = client.stdout.readline()
    if not line.startswith("T0 "):
        client.kill()
        client.wait()
        raise RunFailed(f"load generator did not start: {line!r} {client.stderr.read()[-2000:]}")
    return client, int(line.split()[1])


def sweep(args, node, ps, traffic, config, names, client_bin, out_dir, seconds) -> list:
    """Offer each rate of ``--sweep`` in turn for ``seconds`` on the one
    booted node; → per rate: takes offered and answered, latency
    quantiles from the due time over the whole step and over its first
    and last quarter, which show a backlog that grows."""
    steps = []
    for rate in [float(x) for x in args.sweep.split(",")]:
        tr = dict(traffic, rate_per_s=rate)
        hook = traffic_mod.TakePeers(args.seed, tr, config, seconds, len(ps.peers))
        ps.on_tick = hook
        sched = traffic_mod.take_schedule(args.seed, f"sweep{rate:g}", tr, config, seconds)
        results_path = os.path.join(out_dir, "results.bin")
        client, t0 = start_client(client_bin, node, config, tr, names, sched, out_dir, results_path)
        hook.t0_ns = t0
        try:
            client.communicate(timeout=seconds + DRAIN_S + 60)
        finally:
            if client.poll() is None:
                client.kill()
                client.wait()
        try:
            wait_until(lambda: ps.idle() and hook.done(), DRAIN_S, "peers never drained", ps)
            drained = True
        except RunFailed:
            drained = False
        res = traffic_mod.read_results(results_path)
        due = sched["due_ns"]
        ans = res["status"] > 0
        lat = np.where(ans, res["done"] - due, due[-1] + int(DRAIN_S * 1e9) - due) / 1e6
        sent = res["sent"] >= 0
        q = max(len(lat) // 4, 1)
        step = {
            "rate_per_s": rate,
            "offered": int(len(due)),
            "answered_in_window": int((ans & (res["done"] <= seconds * 1e9)).sum()),
            "answered": int(ans.sum()),
            "p50_ms": float(np.quantile(lat, 0.5)),
            "p99_ms": float(np.quantile(lat, 0.99)),
            "p99_first_quarter_ms": float(np.quantile(lat[:q], 0.99)),
            "p99_last_quarter_ms": float(np.quantile(lat[-q:], 0.99)),
            "generator_late_p99_ms": float(np.quantile(res["sent"][sent] - due[sent], 0.99)) / 1e6,
            "drained": drained,
        }
        log("sweep: " + json.dumps(step))
        steps.append(step)
        if not drained or step["answered_in_window"] < 0.7 * step["offered"]:
            break  # well past the knee
        node.wait_quiet(0.5, DRAIN_S)
    return steps


def wait_until(cond, timeout_s: float, what: str, ps=None) -> None:
    deadline = time.monotonic() + timeout_s
    while not cond():
        if ps is not None:
            ps.check()
        if time.monotonic() > deadline:
            raise RunFailed(what)
        time.sleep(0.05)


def sleep_until(t_ns: int) -> None:
    d = (t_ns - time.monotonic_ns()) / 1e9
    if d > 0:
        time.sleep(d)


if __name__ == "__main__":
    sys.exit(main())
