"""Find the knee of an open-loop cell: the highest rate served without a
growing queue. Run once on the chip when the cell's rate is set.

    python3 bench/sweep.py --workload <cell> --rates 2 4 6 8 --seconds 20

For each rate this runs the cell (``--trace 0``) with only the traffic's
``rate_per_s`` replaced, and prints one JSON line: requests due in the
window per second, first tokens per second, requests still waiting for a
slot when the window closed, and the time-to-first-token percentiles of
the first and the second half of the window. Below the knee the two
halves agree and the queue stays short; above it the second half's times
grow with the queue. The cell's rate is then set to about four fifths of
the knee.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
        sys.path.pop(0)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import catalog, run
    from bench.stats import nearest_rank

    cell = catalog.load_cell(args.workload, ROOT)
    run.enable_compile_cache()
    try:
        device = run.device_info(cell.chips)
    except RuntimeError as e:
        run.say(f"sweep: {e}")
        return 3
    peaks = catalog.peaks(device["kind"])
    for rate in args.rates:
        c = dataclasses.replace(cell, traffic=dict(cell.traffic,
                                                   rate_per_s=rate))
        seen = {}
        res = run.run_cell(c, args.seed, args.seconds, False, device=device,
                           peaks=peaks, t_start=time.perf_counter(),
                           observe=seen)
        recs, t0, t1 = seen["records"], seen["t_open"], seen["t_close"]
        mid = (t0 + t1) / 2
        due = [r for r in recs if t0 <= r.due < t1]

        def p90(rs):
            v = [(r.first_t - r.due) if r.first_t is not None else math.inf
                 for r in rs]
            return 1e3 * nearest_rank(v, 0.9) if v else None
        print(json.dumps({
            "rate_per_s": rate,
            "due_per_s": len(due) / (t1 - t0),
            "first_tokens_per_s": sum(
                1 for r in recs if r.first_t is not None
                and t0 < r.first_t <= t1) / (t1 - t0),
            "waiting_at_close": seen["waiting_at_close"],
            "ttft_p90_ms_first_half": p90([r for r in due if r.due < mid]),
            "ttft_p90_ms_second_half": p90([r for r in due if r.due >= mid]),
            "metrics": res["metrics"], "correct": res["correct"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
