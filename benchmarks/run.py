#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, in this process.

  python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> \
      --trace <0|1>

Set-up (weights on the device from the seed, this cell's shapes warmed,
the compile cache placed by dmlc_tpu.compile_cache), a measured window
of --seconds, then ONE JSON object as the last line of stdout:
{"correct", "attempted", "failed", "metrics", "device"[, "breakdown"]}.
--trace 0 reports the cell's end-to-end metrics, --trace 1 its
per-layer metrics (the window is followed by a short traced one).
Everything else goes to stderr and to benchmarks/out/<workload>/.

A run that finds no TPU, fewer chips than the cell asks for, a device
kind without a row in peaks.json, a compile inside the window, a lax or
interpreted kernel, an AOT fallback or a crash requeue exits non-zero
and prints no result.  --rehearse runs the same code at a toy size on
whatever JAX finds (the CPU here) and prints no result line either:
it is a rehearsal, never a measurement.  See benchmarks/README.md.
"""

import time

T_START = time.monotonic()  # set-up runs from here to the window

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy size, any backend, no result line")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "dmlc_tpu")):
        print(f"benchmarks/run.py: {ROOT} holds the benchmark but not the "
              "dmlc_tpu package it measures", file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    from benchmarks import harness

    try:
        cell = harness.load_cell(args.workload)
        if args.rehearse:
            harness.apply_rehearsal(cell)
        out = harness.runner_for(cell.kind).run(cell, args, T_START)
        if args.trace:
            metrics = harness.read_layer_metrics(cell, out["obs"])
            for name, note in out["obs"].get("notes", {}).items():
                harness.log(f"{name}: {note}")
        else:
            metrics = {m["name"]: {"value": float(out["end_to_end"][
                m["name"]]), "unit": m["unit"]} for m in cell.end_to_end}
        line = harness.result_line(
            out["device"], correct=out["correct"],
            attempted=out["attempted"], failed=out["failed"],
            metrics=metrics, reduction=out["reduction"])
    except harness.BenchFailure as e:
        print(f"benchmarks/run.py: NOT A RESULT: {e}", file=sys.stderr,
              flush=True)
        return 1
    if args.rehearse:
        harness.log("REHEARSAL, NOT A RESULT (toy size): " + line)
        return 0
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
