#!/usr/bin/env python3
"""The control of a cell's ``correct``: the reference put in the
program's place with one stated guarantee broken ("answers exact": the
decimal arithmetic done in float32, the chip's native type), at the
cell's own size. It has to come out as not correct on every seed.

    python bench/control.py --workload <cell> --seeds 11 12 13

Prints, per seed and statement, the numbers ``run.py`` compares, as the
control reads them, beside the limits. The benchmark's own runs do not
run it; ``tests/test_correct.py`` keeps it at a small size.
"""

from __future__ import annotations

import argparse
import json
import sys

import compare
import run


def control_readings(cell: dict, seed: int) -> dict:
    """The compared numbers, summed or widest over the cell's
    statements, with the control in the program's place."""
    base, config = cell["dir"], cell["config"]
    statements = run.load_statements(base, cell["traffic"]["statements"])
    data = run.load_module(base, "", config["generator"]).make(
        config["scale_factor"], seed, **config.get("generator_options", {}))
    out = {}
    for sid, st in statements.items():
        ref = st["ref"]
        v = compare.compare(ref.reference(data, "float32"),
                            ref.reference(data), ref.COLUMNS)
        out[sid] = v
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    limits = run.limits_for(run.load_statements(
        cell["dir"], cell["traffic"]["statements"]))
    failed_all = True
    for seed in args.seeds:
        per_stmt = control_readings(cell, seed)
        total = {"wrong_cells": sum(v["wrong_cells"]
                                    for v in per_stmt.values()),
                 "ratio_rel_gap": max(v["ratio_rel_gap"]
                                      for v in per_stmt.values())}
        fails = [k for k, v in total.items()
                 if k in limits and v > limits[k]]
        failed_all &= bool(fails)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "per_statement": per_stmt, "control": total,
                          "limits": {k: limits[k] for k in total
                                     if k in limits},
                          "fails": fails}), flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
