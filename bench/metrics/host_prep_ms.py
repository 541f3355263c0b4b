"""Mean host milliseconds per step spent scheduling, materializing and
placing the batch, from the benchmark's spans in the profiler trace."""

SPANS = ("bench.schedule", "bench.materialize", "bench.device_put")


def read(run: dict):
    tr = run.get("trace")
    if not tr:
        return None
    host = tr["host_spans"]
    steps = host.get("bench.step", {}).get("count", 0)
    if not steps:
        return None
    return 1e3 * sum(host.get(n, {}).get("s", 0.0) for n in SPANS) / steps
