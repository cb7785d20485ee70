"""Host time in `fl.fetch` (device-to-host readbacks, the host waiting
on the device's answer) a traced round, in ms (bench/spans.py)."""
from bench import spans


def read(ctx: dict):
    return spans.per_round(ctx, "total_ms", spans.FETCH)
