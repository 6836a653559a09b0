"""CLI entry point (reference: cmd/patrol/main.go).

Flags mirror the reference: ``--api-addr``, ``--node-addr``, repeatable
``--peer-addr`` (host:port-validated, main.go:59-75), ``--clock-offset``
(skew fault injection, main.go:30), ``--log-env`` (main.go:31,40-47) —
plus the TPU-native knobs: ``--buckets`` / ``--node-lanes`` (state shape)
and ``--platform`` to pin the JAX backend.

Run as ``python -m patrol_tpu [flags]``.
"""

from __future__ import annotations

import argparse
import asyncio
import os
import sys


def _addr(value: str) -> str:
    host, sep, port = value.rpartition(":")
    if not sep or not host or not port.isdigit():
        raise argparse.ArgumentTypeError(f"address {value!r} is not host:port")
    return value


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="patrol-tpu",
        description="TPU-native distributed rate-limiting sidecar "
        "(POST /take/:bucket?rate=F:D&count=N)",
    )
    p.add_argument("--api-addr", type=_addr, default="127.0.0.1:8080", help="HTTP API address")
    p.add_argument("--node-addr", type=_addr, default="127.0.0.1:16000", help="replication UDP address")
    p.add_argument(
        "--node-name",
        default="",
        help="human-meaningful node identity for fleet views "
        "(/debug/vars histogram summaries, /cluster/* lane labels); "
        "defaults to --node-addr",
    )
    p.add_argument(
        "--peer-addr",
        type=_addr,
        action="append",
        default=[],
        dest="peer_addrs",
        help="peer node address (repeatable; include all cluster members)",
    )
    p.add_argument(
        "--clock-offset",
        default="0",
        help="offset added to clock timestamps, Go duration syntax (testing)",
    )
    p.add_argument(
        "--log-env",
        choices=["development", "production"],
        default="production",
        help="logging environment",
    )
    p.add_argument("--buckets", type=int, default=65536, help="bucket-slot pool size")
    p.add_argument("--node-lanes", type=int, default=64, help="PN lanes (max cluster size)")
    p.add_argument("--platform", default=None, help="JAX platform override (tpu|cpu)")
    p.add_argument(
        "--udp-backend",
        choices=["auto", "native", "asyncio"],
        default="auto",
        help="replication transport: C++ sendmmsg/recvmmsg or asyncio",
    )
    p.add_argument(
        "--wire-mode",
        choices=["delta", "full", "aggregate", "compat"],
        default="delta",
        help="outgoing replication wire form. Default 'delta': batched "
        "delta-interval datagrams (wire v2) to peers that answer the "
        "capability handshake, full-state aggregate datagrams to "
        "everyone else — so mixed v1/v2 clusters stay safe with no "
        "flags. 'full' (alias 'aggregate') opts out back to the "
        "per-take full-state plane; 'compat' additionally rewrites to "
        "raw own-lane headers for rolling upgrades from pre-lane-"
        "trailer builds (see ops/wire.py and net/delta.py)",
    )
    p.add_argument(
        "--http-front",
        choices=["auto", "python", "native"],
        default="auto",
        help="API server: the C++ epoll front serves /take in-process "
        "(native code, h2c via loopback splice) and is the default when "
        "the toolchain builds it; python asyncio is the protocol-"
        "reference implementation and the fallback",
    )
    p.add_argument(
        "--shutdown-timeout",
        default="30s",
        help="graceful shutdown timeout, Go duration syntax",
    )
    p.add_argument("--checkpoint-dir", default=None, help="snapshot/restore directory")
    p.add_argument(
        "--checkpoint-interval",
        default="0",
        help="periodic snapshot interval, Go duration syntax (0 = shutdown only)",
    )
    p.add_argument(
        "--no-warmup",
        action="store_true",
        help="skip kernel pre-compilation at boot (faster start, JIT spikes later)",
    )
    p.add_argument(
        "--mesh-replicas",
        type=int,
        default=0,
        help="run over all local devices: N full replicas × remaining "
        "devices as bucket shards (0 = single device)",
    )
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    import jax

    if args.platform:
        # An explicit platform is a demand, not a preference: JAX then
        # fails at start-up instead of falling back to the CPU.
        os.environ["JAX_PLATFORMS"] = args.platform
        jax.config.update("jax_platforms", args.platform)
    from patrol_tpu.utils import compile_cache

    compile_cache.enable()

    # Heavy imports after platform selection.
    from patrol_tpu.command import Command
    from patrol_tpu.models.limiter import LimiterConfig
    from patrol_tpu.ops.rate import parse_duration
    from patrol_tpu.runtime.bucket import offset_clock, system_clock
    from patrol_tpu.utils.logging import configure

    try:
        offset_ns = parse_duration(args.clock_offset)
    except ValueError as exc:
        print(f"bad --clock-offset: {exc}", file=sys.stderr)
        return 2
    try:
        shutdown_ns = parse_duration(args.shutdown_timeout)
    except ValueError as exc:
        print(f"bad --shutdown-timeout: {exc}", file=sys.stderr)
        return 2

    log = configure(args.log_env)
    cmd = Command(
        api_addr=args.api_addr,
        node_addr=args.node_addr,
        node_name=args.node_name,
        peer_addrs=args.peer_addrs,
        clock=offset_clock(offset_ns) if offset_ns else system_clock,
        shutdown_timeout_s=shutdown_ns / 1e9,
        config=LimiterConfig(buckets=args.buckets, nodes=args.node_lanes),
        log=log,
        udp_backend=args.udp_backend,
        wire_mode=args.wire_mode,
        http_front=args.http_front,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_interval_s=parse_duration(args.checkpoint_interval) / 1e9,
        warmup=not args.no_warmup,
        mesh_replicas=args.mesh_replicas,
    )
    try:
        asyncio.run(cmd.run())
    except KeyboardInterrupt:
        pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
