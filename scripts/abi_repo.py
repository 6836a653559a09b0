#!/usr/bin/env python
"""Run patrol-abi — the native-ABI conformance prover + cross-boundary
concurrency lint — over every registered obligation
(patrol_tpu/ops/obligations.py::ABI_OBLIGATIONS).

Stage 5 of the `scripts/check.sh` gate, runnable standalone. Exit codes:
0 = every obligation holds; 1 = findings printed one per line as

    path:line: CODE message

77 = the native toolchain/library is unavailable (check.sh maps this to
a LOUD stage skip — never a silent pass).

See patrol_tpu/analysis/abi.py for the passes, the PTA code table in
README.md ("patrol-check"), and `# patrol-lint: disable=PTAxxx` for the
(greppable, reviewed-like-code) suppression format.
"""

import argparse
import os
import sys

# Conformance runs on CPU: the jax twins are tiny-domain evaluations.
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--root",
        default=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        help="repo root (default: this script's parent)",
    )
    ap.add_argument(
        "--only",
        default=None,
        help="comma-separated obligation-name substrings (default: all)",
    )
    ap.add_argument(
        "--list", action="store_true", help="list registered obligations"
    )
    args = ap.parse_args()

    from patrol_tpu.analysis import abi
    from patrol_tpu.ops.obligations import ABI_OBLIGATIONS

    if args.list:
        for ob in ABI_OBLIGATIONS:
            print(
                f"{ob.name}  [{','.join(ob.codes)}]  check={ob.check} "
                f"symbol={ob.symbol or '-'} twins={','.join(ob.twins) or '-'}"
            )
        return 0

    only = (
        [k.strip() for k in args.only.split(",") if k.strip()]
        if args.only
        else None
    )
    try:
        if only:
            findings = abi.abi_all(only=only)
        else:
            findings = abi.abi_repo(args.root)
    except abi.NativeUnavailable as exc:
        print(f"patrol-abi: SKIPPED — {exc}", file=sys.stderr)
        return 77

    for f in findings:
        print(f)
    if findings:
        print(
            f"patrol-abi: {len(findings)} finding(s) across "
            f"{len({f.path for f in findings})} file(s)",
            file=sys.stderr,
        )
        return 1
    print(
        f"patrol-abi: clean ({len(ABI_OBLIGATIONS)} obligations, all hold)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
