"""Starts the node with one fault planted in its timed path.

    python perfbench/tests/fault_node.py FAULT <node flags...>

The benchmark's fault test hands this to ``run.py --launcher``, so a
whole rehearsal runs against a node that is wrong in one known way and
must come out ``correct: false``:

``answer``       every 7th admitted take answers 429: the take committed,
                 the answer says it did not (an answer altered where it is
                 produced);
``stale_state``  the take kernel returns its state unchanged;
``half_batch``   every other raw ingest batch is dropped, yet acknowledged
                 (half of the work left out; an acknowledged interval that
                 is not held);
``refill32``     the take kernel's refill is computed in float32, the
                 precision below the float64 the reference states: the
                 control of the take cells.

and one mode that is no fault, where the run must stay ``correct``:

``stall``        after its 1000th answered take the whole node stops for
                 4 s (SIGSTOP), past its peers' 3 s liveness timeout, as
                 a stalled host does: it heals its peers on waking, drops
                 its interval logs and ships its lanes the classic way
                 until the delta capability is renegotiated.
"""

import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def plant(fault: str) -> None:
    from patrol_tpu.runtime import engine

    if fault == "answer":
        orig = engine.TakeTicket.complete
        seen = [0]

        def complete(self, remaining, ok):
            if ok:
                seen[0] += 1
                if seen[0] % 7 == 0:
                    ok = False
            return orig(self, remaining, ok)

        engine.TakeTicket.complete = complete
    elif fault == "stale_state":
        orig_take = engine.take_n_batch

        def take(state, packed, node_slot):
            _, out = orig_take(state, packed, node_slot)
            return state, out

        engine.take_n_batch = take
    elif fault == "refill32":
        import jax.numpy as jnp
        from patrol_tpu.ops import take

        def refill_grant_nt(delta, interval):
            f32 = jnp.float32
            grant = (delta.astype(f32) / interval.astype(f32)) * f32(1e9)
            grant = jnp.floor(jnp.clip(grant, 0, f32(2**62))).astype(jnp.int64)
            return jnp.where(delta > 0, grant, 0)

        take.refill_grant_nt = refill_grant_nt
    elif fault == "stall":
        orig = engine.TakeTicket.complete
        seen = [0]

        def complete(self, remaining, ok):
            seen[0] += 1
            if seen[0] == 1000:
                subprocess.Popen(["sh", "-c", f"kill -STOP {os.getpid()}; sleep 4; kill -CONT {os.getpid()}"])
            return orig(self, remaining, ok)

        engine.TakeTicket.complete = complete
    elif fault == "half_batch":
        orig_ingest = engine.DeviceEngine.ingest_raw_planes
        calls = [0]

        def ingest(self, planes, lengths, walk=None, release=None):
            calls[0] += 1
            if calls[0] % 2 == 0:
                if release is not None:
                    release()
                return 0
            return orig_ingest(self, planes, lengths, walk=walk, release=release)

        engine.DeviceEngine.ingest_raw_planes = ingest
    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    plant(sys.argv[1])
    from patrol_tpu.cli import main

    sys.exit(main(sys.argv[2:]))
