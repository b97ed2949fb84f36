#!/usr/bin/env python3
"""Record the small profiler trace that tests/bench checks the trace
reduction against, on the chip.

    python3 bench/record_trace.py --out tests/bench/data/small_trace.xplane.pb

It serves a few requests through the program's ServeEngine with one layer
of qwen3-0.6b at its published widths and a vocabulary cut to 4096, and
traces three engine ticks inside a ``bench.window`` span, with the
benchmark's host spans around them.  It prints the trace's planes and
lines, the reduction, and the device's own op and program events in a
form the test reads back (``--out`` + ``.json``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    from bench import run, trace_reduce
    from bench import weights as W

    devices = run.require_chips(1)
    import jax

    from repro.serve import Request, ServeEngine

    conf = json.loads((ROOT / "bench/configs/qwen3-0.6b.json").read_text())
    m = dataclasses.replace(W.model_from_config(conf), layers=1, vocab=4096)
    conf = {**conf, "num_hidden_layers": 1, "vocab_size": 4096}
    cfg = run.arch_config(conf, m)
    engine = ServeEngine(W.make_params(m, 7), cfg, slots=4, max_seq=256)
    for i in range(4):
        engine.submit(Request(uid=i, prompt=list(range(1, 40 + 20 * i)),
                              max_new_tokens=24))
    engine.tick()                       # compiles
    for i in range(4, 6):
        engine.submit(Request(uid=i, prompt=list(range(1, 70)),
                              max_new_tokens=24))
    tmp = tempfile.mkdtemp(prefix="bench-record-")
    jax.profiler.start_trace(tmp, profiler_options=run.profile_options())
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.tick"):
                engine.tick()
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(0.01)
    jax.profiler.stop_trace()
    src = trace_reduce.find_xplane(tmp)
    shutil.copy(src, args.out)
    shutil.rmtree(tmp, ignore_errors=True)
    profile = jax.profiler.ProfileData.from_file(args.out)
    for plane in profile.planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        print(f"plane {plane.name}: {lines}")
        for ln in plane.lines:
            for ev in list(ln.events)[:3]:
                print(f"   {ln.name}: {ev.name} {ev.start_ns} "
                      f"{ev.duration_ns} {dict(ev.stats)}")
    red = trace_reduce.reduce_trace(args.out)
    print(json.dumps(red, indent=1))
    print(json.dumps({"device": {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind,
                                 "count": 1}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
