"""Runs one cell of the chip benchmark once and prints its result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. Set-up (weights from the seed, the engine,
compilation or cache loads, warm-up) runs first, then the window of
``--seconds``, then the comparison with the plain reference that decides
``correct``. With ``--trace 1`` the window is profiled and the line holds
the cell's per-layer metrics; with ``--trace 0``, its end-to-end metrics.
Exits non-zero, printing no result, unless JAX's devices are TPUs, as
many as the cell asks for. The last line of standard output is the JSON
result; the numbers compared with their limits close standard error.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.find_cell(harness.load_benchmark(), args.workload)
    program = harness.import_program()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX has {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_PROCESS, program=program)
    for key, value in out.diagnostics.items():
        print(f"{key}: {json.dumps(value, default=str)}", file=sys.stderr)
    for name, c in out.compared.items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    result = {"correct": out.correct, "attempted": out.attempted,
              "failed": out.failed, "metrics": out.metrics,
              "device": out.device}
    if out.breakdown is not None:
        result["breakdown"] = out.breakdown
    result["compared"] = out.compared
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
