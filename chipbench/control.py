"""Readings of the numbers ``correct`` compares, for the program and for
its control, at a cell's own size, on the chip::

    python3 chipbench/control.py --workload sift1m.closed \\
        --seeds 201 202 203

For each seed one run of the cell (set-up, a ``SECONDS`` window at the
cell's own load, the check); then the control, the float64 reference with its
rerank as one bf16 pass, is put in the program's place for the same
sampled requests and judged by the same comparison.  The limits in the
configuration are set between the two readings (see PERF.md).  The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

#: long enough to finish the mix's longest requests and to answer more
#: requests than the check samples
SECONDS = 8.0


def control_numbers(run) -> dict:
    import numpy as np

    from chipbench import data as data_lib
    from chipbench.harness import SAMPLE_SALT
    ref = run.system.reference
    answers = [(r.qi, r.ids, r.dists) for r in run.answered()]
    n = min(run.config["check"]["sample"], len(answers))
    sample = np.sort(data_lib.host_rng(run.seed, SAMPLE_SALT).choice(
        len(answers), size=n, replace=False))
    st = run.system.stated(run.config)
    ctrl = ref.control_answers([answers[j] for j in sample],
                               run.data.queries, run.data.base,
                               run.partition, **st)
    return ref.compare(ctrl, 0, run.data.queries, run.data.base,
                       run.partition, sample=np.arange(len(ctrl)), **st)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from chipbench import harness
    rows = []
    for seed in args.seeds:
        got = {}
        res = harness.execute(
            args.workload, seed, SECONDS, False,
            inspect=lambda run: got.update(control_numbers(run)),
            say=lambda s: print(s, flush=True))
        row = {"seed": seed, "correct": res["correct"],
               "program": {k: v["value"] for k, v in res["checks"].items()},
               "control": got}
        print("control", json.dumps(row), flush=True)
        rows.append(row)
    from chipbench.spec import ROOT
    out_dir = ROOT / ".chipbench"
    out_dir.mkdir(exist_ok=True)
    (out_dir / f"control_{args.workload}.json").write_text(
        json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    root = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root), str(root / "src")]
    sys.exit(main())
