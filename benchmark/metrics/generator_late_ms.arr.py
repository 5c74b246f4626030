"""Load generator: p95 of actual - due send time. A starved generator is not
a fast server. layer: load generator; moves submit_commit_p50_ms."""
from harness.loadgen import percentile


def read(ctx):
    late = sorted((r["t_sent"] - r["t_due"]) * 1000.0
                  for r in ctx["window"]["records"] if "t_sent" in r)
    return percentile(late, 0.95) if late else None
