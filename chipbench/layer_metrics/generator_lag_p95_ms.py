"""How late the load generator sent: 95th percentile of (sent - due).
A starved generator must not read as a fast server."""
from chipbench.loadgen import percentile


def read(ctx):
    return percentile(ctx["facts"]["lag_ms"], 95)
