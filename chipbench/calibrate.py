"""Readings for a cell's limits: the program's logit gaps and the fp8
control's, over several seeds, in one process on the chip.

    python3 chipbench/calibrate.py --workload <name> --seconds <s> --seeds 1 2 3

Each seed is a whole run of the cell (weights, engine, window, sample);
the reference then scores the sample twice, once as it is and once in
fp8 (the control, ``reference.py``). With ``--rates`` and ``--no-check``
it sweeps the offered load instead. Not part of a benchmark run.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rates", type=float, nargs="*", default=[None],
                    help="open-loop rates to run instead of the mix's own "
                         "(knee sweep)")
    ap.add_argument("--slots", type=int, default=None,
                    help="engine slots instead of the mix's own")
    ap.add_argument("--no-check", action="store_true")
    args = ap.parse_args(argv)
    cell = harness.find_cell(harness.load_benchmark(), args.workload)
    program = harness.import_program()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    for rate, seed in [(r, s) for r in args.rates for s in args.seeds]:
        override = {"rate_per_s": rate} if rate else {}
        if args.slots:
            override["slots"] = args.slots
        t = time.perf_counter()
        out = harness.run_cell(cell, seed, args.seconds, False, t,
                               program=program, mix_override=override,
                               check=not args.no_check,
                               control=not args.no_check)
        d = out.diagnostics
        print(json.dumps({
            "workload": args.workload, "seed": seed, "rate": rate,
            "slots": args.slots,
            "gaps": d.get("gaps"), "control_gaps": d.get("control_gaps"),
            "checked_tokens": d.get("checked_tokens"),
            "checked_requests": d.get("checked_requests"),
            "reference_s": d.get("reference_s"),
            "attempted": out.attempted, "failed": out.failed,
            "finished_in_window": d["finished_in_window"],
            "in_flight_at_close": d["in_flight_at_close"],
            "window_compiles": d["window_compiles"],
            "metrics": {k: v["value"] for k, v in out.metrics.items()},
            "peak_bytes": out.device["memory_peak_bytes"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
