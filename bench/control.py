"""The control of a cell's comparison, at the cell's own size.

    python bench/control.py --workload <cell> --seeds <n> [<n> ...]

For each seed it builds the cell's graph as a run does, takes as many
of the window's roots as a run checks, and compares the reference with
one guarantee broken (``bench/reference/<kernel>.py``'s ``control``: the
level or relaxation loop stopped one round early) against the plain
reference, by the same comparison that decides a run's ``correct``. The
control has to come out not correct on every seed: a comparison that it
passed would pass a program that stops early. The benchmark's own runs do not
run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--roots", type=int, default=None,
                    help="roots per seed (default: as many as a run checks)")
    args = ap.parse_args(argv)
    spec = harness.resolve(ROOT, args.workload)
    reference = harness.load_module(spec.reference)
    failed_all = True
    for seed in args.seeds:
        t = time.perf_counter()
        n, src, dst = harness.load_module(spec.generator).generate(
            spec.config, seed)
        _, roots = harness.split_roots(
            spec, n, src, dst, harness.draw_roots(n, src, dst, seed))
        roots = roots[:args.roots or int(spec.traffic["check_sample"])]
        control = reference.control(n, src, dst, roots)
        checked = harness.compare(spec, n, src, dst,
                                  list(zip(roots.tolist(), control)))
        failed_all &= checked["mismatched_entries"] > 0
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "roots": len(roots), **checked,
                          "limit": 0, "seconds": time.perf_counter() - t}),
              flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
