#!/usr/bin/env python3
"""The control: the reference at a lower precision, put in the program's
place, must come out as not correct.

    python bench/control.py --workload <cell> --seeds 11,12,13 [--seconds 1]

For each seed this makes one whole run of the cell through the harness
(`harness.main(..., control=True)`): the cell's graph and traffic at its
own size, a short window, and the harness's own comparison, with the
entry returning the reference at the cell's `control.dtype` (normalized
as the program's answer is) wherever the program's answer would be. It
prints one JSON line a seed: whether the run came out correct (it must
not), and each number compared beside its limit. All seeds run in one
process, which holds the chip. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def readings(cell: str, seeds, seconds: float = 1.0, *,
             bench_root: Path = BENCH, benchmark_path=None,
             require_tpu: bool = True):
    sys.path.insert(0, str(bench_root))
    import harness

    for seed in seeds:
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = harness.main(
                ["--workload", cell, "--seed", str(seed), "--seconds",
                 str(seconds), "--trace", "0"], bench_root=bench_root,
                benchmark_path=benchmark_path, require_tpu=require_tpu,
                control=True)
        lines = out.getvalue().strip().splitlines()
        res = json.loads(lines[-1]) if rc == 0 and lines else {}
        yield dict(cell=cell, seed=seed, rc=rc,
                   correct=res.get("correct"), failed=res.get("failed"),
                   attempted=res.get("attempted"),
                   checks=res.get("checks"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    rows = []
    for row in readings(args.workload,
                        [int(s) for s in args.seeds.split(",")],
                        args.seconds):
        rows.append(row)
        print(json.dumps(row), flush=True)
    # the control has failed only where every run came out not correct
    return 0 if all(r["rc"] == 0 and r["correct"] is False
                    for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
