#!/usr/bin/env python3
"""The control of ``correct``: the plain reference put in the program's
place, computed in the precision below the one the configuration states
(``compute_precision`` float32 -> bfloat16, lowprec.py), judged by the
same comparison as a run's replies.  It has to come out as not correct.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 [--requests 64] [--rehearse]

Needs no device: numpy only.  For each seed it makes the cell's data and
the first ``--requests`` requests the window would send, and prints for
each class the control's worst and least error beside the limit.  The
benchmark's own runs never run it; tests/test_control.py keeps it at
rehearsal size.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import run  # noqa: E402


def control_cell(workload: str, seed: int, requests: int,
                 rehearse: bool = False) -> dict:
    """{class: {"worst", "least", "limit", "requests"}, "fails": bool}"""
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    _entry, config, mix = run.load_cell(bench, workload)
    family = run.load_module("queries", mix["family"])
    lower = run.load_module(".", "lowprec").LOWER[config["compute_precision"]]
    cell = run.new_cell(config, rehearse, seed)
    traffic = run.Traffic(family, cell, mix, seed, stream=1)
    out: dict = {}
    for _ in range(requests):
        req = traffic.next()
        verdict, err = run.judge(family, cell, req, None, None,
                                 answer=family.reference(cell, req, lower))
        if verdict != "ok":
            err = run.NO_ANSWER
        c = out.setdefault(req["class"], {
            "worst": 0.0, "least": run.NO_ANSWER,
            "limit": family.LIMITS[req["class"]], "requests": 0})
        c["worst"] = max(c["worst"], err)
        c["least"] = min(c["least"], err)
        c["requests"] += 1
    fails = any(c["worst"] > c["limit"] for c in out.values())
    return {"seed": seed, "workload": workload, "classes": out,
            "fails": fails}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        res = control_cell(args.workload, seed, args.requests, args.rehearse)
        print(json.dumps(res), flush=True)
        ok = ok and res["fails"]
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
