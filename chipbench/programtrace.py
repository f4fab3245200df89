"""One traced run of a cell, with everything its trace and counters hold.

    python3 chipbench/programtrace.py --workload <name> --seed <n> --seconds <s>

From the root of a checkout, on a TPU. Runs the cell as
``chipbench/run.py --trace 1`` does, without the reference check, and
prints one JSON line: every metric of the cell that its reader reads, the
end-to-end ones too (with the profiler on: their cost of tracing against
a ``--trace 0`` run of the same seed), the engine's counters over the
window, and the reduced trace the readers read (``tracereduce.Trace``:
device seconds per program and per op, the ``serve.*`` spans with their
counts, seconds, device idle and summed args, ``covered_s`` and
``cut_s``).
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import harness  # noqa: E402


def readings(cell: harness.Cell, run) -> dict:
    """Every metric of ``cell`` that its reader finds in ``run``."""
    out = {}
    for m in cell.end_to_end + cell.per_layer:
        v = harness.load_reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = v
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = harness.find_cell(harness.load_benchmark(), args.workload)
    program = harness.import_program()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"programtrace: {args.workload} needs {cell.chips} TPU "
              f"chip(s); JAX has {len(devices)} {devices[0].platform} "
              f"device(s)", file=sys.stderr)
        return 2
    out = harness.run_cell(cell, args.seed, args.seconds, True, T_PROCESS,
                           program=program, check=False)
    print(json.dumps({"readings": readings(cell, out.run),
                      "device": out.device,
                      "counters": out.run.counters,
                      "trace": dataclasses.asdict(out.run.trace),
                      "window_compiles":
                          out.diagnostics["window_compiles"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
