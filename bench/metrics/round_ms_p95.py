"""95th percentile of the host-clock time between successive round
returns in the measured window, with no extra synchronisation."""
import statistics


def read(ctx: dict):
    t = ctx.get("round_returns") or []
    gaps = [(b - a) * 1e3 for a, b in zip(t, t[1:])]
    if len(gaps) < 20:
        return None
    return statistics.quantiles(gaps, n=20)[18]
