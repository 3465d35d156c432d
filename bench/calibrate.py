"""Read the comparison's two ends for one cell, on the chip, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 11 12 13 --seconds 20

For every seed this runs the cell as ``bench/run.py --trace 0`` does (at
the cell's own sizes and load, over a window of ``--seconds``), and then
reads, on the same sample of served requests, both the program's widest
gap and the control's: the reference computed in the next lower precision
than the configuration's (``bench.reference.lower``) put in the program's
place, and judges the control by the same checks as the program, its gaps
in place of the served ones: ``control_correct`` has to come out false.
The limit in ``bench/cells/<cell>.json`` lies between the two. The
benchmark's own runs never run the control. Prints one JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
        sys.path.pop(0)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import catalog, reference, run

    cell = catalog.load_cell(args.workload, ROOT)
    run.enable_compile_cache()
    try:
        device = run.device_info(cell.chips)
    except RuntimeError as e:
        run.say(f"calibrate: {e}")
        return 3
    peaks = catalog.peaks(device["kind"])
    low = reference.lower(cell.config)
    for seed in args.seeds:
        res = run.run_cell(cell, seed, args.seconds, False, device=device,
                           peaks=peaks, t_start=time.perf_counter(),
                           control=low)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "correct": res["correct"],
                          "gap_max": res["checks"]["gap_max"]["value"],
                          "detections": res["checks"]["detections"]["value"],
                          "control": low,
                          "control_gap_max": res["info"]["control_gap_max"],
                          "control_correct": res["info"]["control_correct"],
                          "sampled_tokens":
                              res["checks"]["sampled_tokens"]["value"],
                          "metrics": res["metrics"],
                          "memory_peak_bytes":
                              res["device"]["memory_peak_bytes"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
