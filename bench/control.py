#!/usr/bin/env python3
"""The control of the benchmark's ``correct``: the number it compares,
read with the reference's control in the program's place.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --sent <n>

For each seed it builds the cell's requests as a run does, draws the same
sample of the first ``--sent`` requests that a run sending that many
checks, and counts the sampled answers on which the control
(``reference.chordal(..., reverse=False)``: the PEO test over the LexBFS
order itself, not its reverse) disagrees with the reference. A sound
control reads above the limit 0 on every seed: the comparison separates
an exact answer from one that breaks the configuration's guarantee.

Host only: it needs no chip. The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import reference, spec, traffic  # noqa: E402


def control_reading(config: dict, seed: int, sent: int) -> dict:
    src = traffic.source(config, seed)
    idx = traffic.sample(sent, int(config["check_sample"]), seed)
    payloads = [src.payload(int(i)) for i in idx]
    want = reference.verdicts(payloads)
    ctrl = reference.verdicts(payloads, reverse=False)
    return {"seed": seed, "sampled": len(idx),
            "chordal_share": float(want.mean()) if len(idx) else 0.0,
            "wrong_verdicts": int((ctrl != want).sum()), "limit": 0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--sent", type=int, required=True,
                    help="requests a run of the cell sends")
    args = ap.parse_args(argv)
    cell = spec.load_cell(spec.load_bench(ROOT), args.workload)
    for s in args.seeds.split(","):
        row = control_reading(cell.config, int(s), args.sent)
        print(json.dumps({"workload": cell.name, **row}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
