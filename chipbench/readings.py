#!/usr/bin/env python3
"""Readings that set a cell's limits, many seeds in one process.

    python3 chipbench/readings.py --workload <cell> --seeds 1 2 3 ... [--program]

For each seed: the values of its first request, the float64 reference
and the numbers the harness compares, for the control (the reference in
bfloat16 storage, ``reference.control``) and, with ``--program``, for
the program's own answer through the cell's driver (warmed up once; the
structure is the configuration's, whatever the seed).  One JSON line per
seed and side.  The benchmark's runs never run this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import harness, reference  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program", action="store_true",
                    help="also read the program's answers (needs the chip)")
    args = ap.parse_args(argv)
    root = harness.ROOT
    cell = harness.load_cell(root, args.workload)
    config = cell.config
    gen = harness.plugin("generators", config["generator"])
    driver = harness.plugin("drivers", cell.traffic["driver"])
    shape = (int(config["rows"]), int(config["cols"]))
    limits = config["limits"]
    rpt, col = gen.structure(config)
    P = reference.pattern(rpt, col, shape)
    session = repro = None
    if args.program:
        harness.use_compile_cache(root)
        info = harness.device_info()
        harness.require_accelerator(info, cell.chips)
        repro = harness.import_program(root)
        session = driver.start(repro, rpt, col, shape, cell.traffic,
                               args.seeds[0])
    for seed in args.seeds:
        t0 = time.perf_counter()
        if args.program:    # the seed's first request, through the driver
            driver.reseed(session, seed)
            win = driver.window(repro, session, 1e-9,
                                np.random.default_rng(seed))
            (val, ans), = driver.answers(win)
        else:
            val = driver.values(seed, 0, col.size)
        ref = reference.reference(rpt, col, val, shape, P)
        sides = {"control": reference.control(rpt, col, val, shape, ref)}
        if args.program:
            sides["program"] = ans
        for side, answer in sides.items():
            failed = win.failed if side == "program" else 0
            reported = win.reported if side == "program" \
                else [(ref.nprod, ref.nnz)]
            checks = reference.compare([(ref, answer)], reported, failed,
                                       ref.nprod, ref.nnz, shape[1], limits)
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "side": side,
                              "correct": reference.passed(checks),
                              "checks": checks,
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
