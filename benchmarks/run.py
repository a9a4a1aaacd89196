# Benchmark driver — one harness per paper table/figure + the beyond-paper
# locality tables + the roofline report. Results land in
# benchmarks/results/*.json and are summarized in EXPERIMENTS.md.
#
#   PYTHONPATH=src python -m benchmarks.run                      # everything
#   PYTHONPATH=src python -m benchmarks.run --only skew          # one harness
#   PYTHONPATH=src python -m benchmarks.run --only engine,skew   # a subset
#   PYTHONPATH=src python -m benchmarks.run --scale 0.25         # smaller
from __future__ import annotations

import argparse
import time


HARNESSES = ("skew", "reorder_time", "cache_stats", "kappa_sweep",
             "speedups", "engine", "vocab_locality", "moe_locality",
             "roofline")


def parse_only(value: str | None) -> list[str]:
    """Comma-separated harness subset -> validated list (None = all)."""
    if not value:
        return list(HARNESSES)
    names = [n.strip() for n in value.split(",") if n.strip()]
    unknown = sorted(set(names) - set(HARNESSES))
    if unknown:
        raise SystemExit(f"unknown harness(es) {', '.join(unknown)}; "
                         f"choose from {', '.join(HARNESSES)}")
    return names


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ", ".join(HARNESSES))
    ap.add_argument("--scale", type=float, default=0.5,
                    help="graph-size multiplier for the paper suite")
    args = ap.parse_args()

    todo = parse_only(args.only)
    from repro.compile_cache import enable_compile_cache
    print(f"[compile cache] {enable_compile_cache()}", flush=True)
    for name in todo:
        t0 = time.time()
        print(f"\n{'=' * 70}\n== {name}\n{'=' * 70}", flush=True)
        if name == "skew":
            from .skew import main as m
            m(args.scale)
        elif name == "reorder_time":
            from .reorder_time import main as m
            m(args.scale)
        elif name == "cache_stats":
            from .cache_stats import main as m
            m(args.scale)
        elif name == "kappa_sweep":
            from .kappa_sweep import main as m
            m(min(args.scale, 0.25))
        elif name == "speedups":
            from .speedups import main as m
            m(args.scale)
        elif name == "engine":
            from .engine import main as m
            m(args.scale)
        elif name == "vocab_locality":
            from .vocab_locality import main as m
            m()
        elif name == "moe_locality":
            from .moe_locality import main as m
            m()
        elif name == "roofline":
            from .roofline import main as m
            m()
        print(f"[{name}] {time.time() - t0:.0f}s", flush=True)


if __name__ == "__main__":
    main()
