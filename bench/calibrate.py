"""Readings the limits of a cell's correctness check are set from.

    python -m bench.calibrate --workload NAME --seeds S1 S2 ... \
        [--control-seeds C1 C2 ...] [--seconds 0]

In one process, for each seed: the cell is set up as a run sets it up,
a window of ``--seconds`` (0: one iteration or one batch) runs on the
timed path, and the numbers the run compares are read.  For each control
seed the control is read too: the reference computed in the precision
the cell's limits file names (the precision below the configuration's),
standing in for the program.  The largest program reading over the
seeds is the lower reading of a limit; the smallest control reading is
its upper one.  Prints one JSON object; the benchmark's own runs do not
run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

from bench import run
from bench.common import ROOT, load_json, load_module


def readings(workload: str, seeds, control_seeds, seconds: float,
             files=None) -> dict:
    import jax

    files = files or run.cell_spec(load_json(ROOT / "BENCHMARK.json"),
                                   workload)
    sys.path.insert(0, str(ROOT / "src"))
    driver = load_module(run.BENCH / "drivers" /
                         f"{files['config']['driver']}.py")
    reference = load_module(files["reference"])
    control = files["limits"]["control"]
    program, ctl = {}, {}
    for seed in sorted(set(seeds) | set(control_seeds)):
        cell = driver.Cell(files["config"], files["traffic"], seed, reference)
        cell.setup()
        cell.window(seconds, jax.profiler.TraceAnnotation)
        cell.release()
        if seed in seeds:
            program[seed] = cell.check()
        if seed in control_seeds:
            ctl[seed] = cell.check(control)
        print(json.dumps({"seed": seed, "program": program.get(seed),
                          "control": ctl.get(seed)}), file=sys.stderr,
              flush=True)
        del cell
        gc.collect()
    names = sorted({n for r in list(program.values()) + list(ctl.values())
                    for n in r})
    summary = {n: {"lower": max((r[n] for r in program.values()),
                                default=None),
                   "upper": min((r[n] for r in ctl.values()), default=None)}
               for n in names}
    return {"workload": workload, "control": control, "program": program,
            "control_readings": ctl, "summary": summary}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)
    run.configure_jax()
    run.check_device(1, load_json(run.BENCH / "peaks.json"))
    out = readings(args.workload, args.seeds, args.control_seeds,
                   args.seconds)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
