"""Self-check of `bench/trace_reduce.py`, on the CPU.

    python -m bench.selfcheck            # check the reduction (CPU is fine)
    python -m bench.selfcheck --record DIR   # on the chip: record it into DIR

The recorded trace (`bench/data/selfcheck.xplane.pb`) holds a known
pattern, made on one chip: inside a `bench:window` span, four rounds of
three runs of one jitted matmul under a `bench:busy` span, each round
followed by a 50 ms host sleep under a `bench:sleep` span. The check
reduces it and asserts what that pattern implies: one device, the four
longest idle gaps labelled `sleep` and each at least the sleep long, the
matmul among the top operations, and busy time within the window. It also
checks the interval arithmetic on hand-made intervals.
"""
from __future__ import annotations

import argparse
import glob
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

DATA = Path(__file__).resolve().parent / "data"
TRACE = DATA / "selfcheck.xplane.pb"
FACTS = DATA / "selfcheck.json"
SLEEP_S, ROUNDS = 0.05, 4


def record(out: Path) -> None:
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("selfcheck --record needs the chip")
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((2048, 2048), jnp.float32)
    f(x).block_until_ready()
    from bench.trace_reduce import profile_options
    d = tempfile.mkdtemp(prefix="bench-selfcheck-")
    jax.profiler.start_trace(d, profiler_options=profile_options())
    with jax.profiler.TraceAnnotation("bench:window"):
        for _ in range(ROUNDS):
            with jax.profiler.TraceAnnotation("bench:busy"):
                for _ in range(3):
                    f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench:sleep"):
                time.sleep(SLEEP_S)
    jax.profiler.stop_trace()
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(glob.glob(d + "/**/*.xplane.pb", recursive=True)[0],
                out / TRACE.name)
    shutil.rmtree(d, ignore_errors=True)
    (out / FACTS.name).write_text(json.dumps({
        "device_kind": jax.devices()[0].device_kind, "rounds": ROUNDS,
        "sleep_s": SLEEP_S}) + "\n")
    print(f"recorded {out / TRACE.name}; copy it and {FACTS.name} to {DATA}")


def check_arithmetic() -> None:
    from bench.trace_reduce import complement, label_gaps, reduce_events, union
    assert union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert complement([(0, 2), (3, 4)], -1, 5) == [(-1, 0), (2, 3), (4, 5)]
    spans = [("step", 0.0, 10.0), ("evaluate", 2.0, 3.5)]
    assert label_gaps([(2.5, 3.0), (5, 6), (11, 12)], spans) == [
        ("evaluate", 0.5), ("step", 1), ("none", 1)]
    r = reduce_events({"/device:TPU:0": [("a", 1.0, 2.0), ("b", 1.5, 3.0)],
                       "/device:TPU:1": [("a", 1.0, 2.0)]},
                      [("window", 0.0, 4.0), ("step", 0.0, 4.0)])
    assert abs(r.busy_s - 1.5) < 1e-12 and r.window_s == 4.0, r
    assert r.top_ops[0] == ("a", 2.0), r.top_ops
    assert abs(r.idle_share - 0.625) < 1e-12


def check_trace() -> None:
    from bench.trace_reduce import reduce_trace
    facts = json.loads(FACTS.read_text())
    r = reduce_trace(str(TRACE))
    print(f"recorded on {facts['device_kind']}: window {r.window_s:.4f}s, "
          f"busy {r.busy_s:.4f}s, idle {100 * r.idle_share:.1f}%, "
          f"top ops {r.top_ops[:3]}, longest gaps {r.gaps[:5]}")
    assert r.n_devices == 1, r.n_devices
    assert 0 < r.busy_s < r.window_s
    sleeps = r.gaps[:facts["rounds"]]
    assert all(n == "sleep" and s >= facts["sleep_s"] for n, s in sleeps), \
        sleeps
    assert r.gap_totals.get("sleep", 0) >= facts["rounds"] * facts["sleep_s"]
    assert r.window_s >= facts["rounds"] * facts["sleep_s"] + r.busy_s - 1e-9
    assert any("dot" in n or "fusion" in n or "convolution" in n
               for n, _ in r.top_ops[:3]), r.top_ops


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--record", type=Path, metavar="DIR")
    args = ap.parse_args(argv)
    if args.record:
        record(args.record)
        return 0
    check_arithmetic()
    if not TRACE.exists():
        print(f"no recorded trace at {TRACE}: record one on the chip with "
              "--record and copy it there")
        return 1
    check_trace()
    print("selfcheck passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
