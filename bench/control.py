"""The control of a cell's comparison, at the cell's own size.

    python bench/control.py --workload <cell> --seeds <n> [<n> ...]

For each seed it builds the cell's graph as a run does, takes the
window's requests that a run checks, and compares the reference with one
guarantee broken (``bench/reference/<kernel>.py``'s ``control``: the
level or relaxation loop stopped one round early) against the plain
reference, by the same comparison that decides a run's ``correct``. In a
closed loop those are as many of the window's first roots as a run
checks; in a mix of classes, each class's requests that a run of
``run_seconds`` checks, with a line printed per class. The control has
to come out not correct on every seed and class: a comparison that it
passed would pass a program that stops early. The benchmark's own runs
do not run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _checked_requests(harness, spec, seed: int, roots_cap):
    """(graph, [(class, the roots of each request it checks)])."""
    n, src, dst = harness.load_module(spec.generator).generate(
        spec.config, seed)
    if spec.arrivals is not None:
        seconds = harness.read_json(ROOT / "BENCHMARK.json")["run_seconds"]
        plan = harness.plan_open_loop(spec, seed, seconds, n, src, dst)
        picked = []
        for c in spec.classes:
            mine = [a.roots.tolist() for a in plan.arrivals
                    if a.cls is c and a.checked]
            picked.append((c, mine[:roots_cap] if roots_cap else mine))
        return (n, src, dst), picked
    (only,) = spec.classes
    _, roots = harness.split_roots(
        spec, n, src, dst, harness.draw_roots(n, src, dst, seed))
    roots = roots[:roots_cap or only.check_sample]
    return (n, src, dst), [(only, [[int(r)] for r in roots])]


def main(argv=None) -> int:
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--roots", type=int, default=None,
                    help="requests per seed and class (default: those a "
                    "run checks)")
    args = ap.parse_args(argv)
    spec = harness.resolve(ROOT, args.workload)
    failed_all = True
    for seed in args.seeds:
        t = time.perf_counter()
        (n, src, dst), picked = _checked_requests(harness, spec, seed,
                                                  args.roots)
        for cls, requests in picked:
            reference = harness.load_module(cls.reference)
            flat = [r for roots in requests for r in roots]
            control = reference.control(n, src, dst, flat)
            sample, lo = [], 0
            for roots in requests:
                sample.append((roots, control[lo:lo + len(roots)]))
                lo += len(roots)
            checked = harness.compare(cls, n, src, dst, sample)
            failed_all &= checked["mismatched_entries"] > 0
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "kernel": cls.kernel, "roots": len(flat),
                              **checked, "limit": 0,
                              "seconds": time.perf_counter() - t}),
                  flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
