#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest arrival rate the system
sustains without a growing backlog.  One process, one set-up, one window
per rate and order of the requests.

    python3 bench/sweep.py --workload <chat cell> --seed <n> \
        --orders 0 1 2 --seconds 51 --rates 0.6 0.8 1.0 1.2

For each rate and order it prints one JSON line: requests due, requests
finished by the close, the backlog at the close (queued plus running),
the median queue wait of the first and of the second half of the
arrivals, the time-to-first-token median and 95th percentile, the 95th
percentile of time per output token, and the output tokens per second.
Order 0 is the schedule every run of the cell sends; the others move the
same requests among its due times (``bench/traffic.py``), to show where
that schedule lies among its kind.  A backlog that grows through the
window (second-half waits well above the first half's) is past the knee.  The rate a cell runs at is written into its
``bench/cells/<cell>.json`` by hand.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import numpy as np  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--orders", type=int, nargs="+", default=[0])
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    from bench import run
    from bench import traffic as T

    sess = run.open_session(ROOT, args.workload, args.seed)
    if sess.mix["loop"] != "open":
        sys.exit("sweep: the knee is found for open-loop cells only")
    run.warm_up(sess.engine, run.make_items(sess, args.seconds,
                                            max(args.rates)))
    dev = sess.devices[0]
    for order, rate in ((o, r) for r in args.rates for o in args.orders):
        items = T.reorder(run.make_items(sess, args.seconds, rate), order)
        win = run.window(sess, items, args.seconds, trace=False)
        recs = sorted(win.drv.recs, key=lambda r: r.due)
        half = len(recs) // 2

        def wait(rs):
            return float(np.median([(r.admitted or win.t_end) - r.due
                                    for r in rs])) if rs else None
        ttft = [(r.first or win.t_end) - r.due for r in recs]
        tpot = [(r.last - r.first) / (r.seen - 1) * 1e3
                for r in recs if r.first is not None and r.seen >= 2]
        done = sum(1 for r in recs if r.done is not None
                   and r.done <= win.t_closed)
        print(json.dumps({
            "order": order, "rate_per_s": rate, "due": len(recs),
            "finished_by_close": done,
            "backlog_at_close": len(recs) - done,
            "queue_wait_p50_first_half_s": wait(recs[:half]),
            "queue_wait_p50_second_half_s": wait(recs[half:]),
            "ttft_p50_s": float(np.median(ttft)),
            "ttft_p95_s": float(np.percentile(ttft, 95)),
            "tpot_p95_ms": float(np.percentile(tpot, 95)),
            "output_tok_s": win.tokens_window / (win.t_closed - win.t_open),
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(sess.devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
