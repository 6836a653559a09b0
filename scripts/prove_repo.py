#!/usr/bin/env python
"""Run patrol-prove — the jaxpr-level CRDT invariant prover — over every
registered kernel root (patrol_tpu/ops/obligations.py::PROVE_ROOTS).

Stage 4 of the `scripts/check.sh` gate, runnable standalone. Exit code
0 = every declared obligation holds; 1 = findings printed one per line as

    path:line: CODE message

See patrol_tpu/analysis/prove.py for the passes, the PTP code table in
README.md ("patrol-check"), and `# patrol-lint: disable=PTPxxx` for the
(greppable, reviewed-like-code) suppression format.
"""

import argparse
import os
import sys

# Static proving always runs on CPU: tracing and the tiny-domain model
# enumerations need no accelerator.
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    from patrol_tpu.analysis import driver

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--root",
        default=driver.repo_root_for(__file__),
        help="repo root (default: this script's parent)",
    )
    ap.add_argument(
        "--only",
        default=None,
        help="comma-separated root-name substrings to check (default: all)",
    )
    ap.add_argument(
        "--list", action="store_true", help="list registered roots and exit"
    )
    args = ap.parse_args()

    from patrol_tpu.analysis import prove
    from patrol_tpu.ops.obligations import PROVE_ROOTS

    roots = PROVE_ROOTS
    if args.only:
        keys = [k.strip() for k in args.only.split(",") if k.strip()]
        roots = tuple(r for r in roots if any(k in r.name for k in keys))

    if args.list:
        for r in roots:
            marks = ",".join(r.obligations)
            print(f"{r.name}  [{marks}]  structural={r.structural or '-'} "
                  f"model={r.model or '-'}")
        return 0

    if args.only:
        findings = []
        for r in roots:
            findings.extend(prove.prove_root(r))
        findings.sort(key=lambda f: (f.path, f.line, f.check))
    else:
        findings = prove.prove_repo(args.root)

    return driver.finish(
        "patrol-prove",
        findings,
        f"patrol-prove: clean ({len(roots)} roots, all obligations hold; "
        "engine dispatch graph fully registered)",
        findings_line=lambda fs: (
            f"patrol-prove: {len(fs)} finding(s) across "
            f"{len({f.path for f in fs})} file(s)"
        ),
    )


if __name__ == "__main__":
    sys.exit(main())
