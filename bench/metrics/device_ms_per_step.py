"""Device time of one loop step of the cell's kernel program:
``kernel_device_ms`` (the program's device time per execution, from the
trace) over ``steps_per_launch`` (the trip counts the program reports)."""
from bench.harness import load_module


def _metric(ctx, name: str):
    return load_module(ctx.bench_dir / "metrics" / f"{name}.py").read(ctx)


def read(ctx):
    per_launch = _metric(ctx, "kernel_device_ms")
    steps = _metric(ctx, "steps_per_launch")
    if per_launch is None or not steps:
        return None
    return per_launch / steps
