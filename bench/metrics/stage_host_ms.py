"""Host self time of `fl.stage` (the cohort off the data plane, the
strategy's inputs) a traced round, in ms (bench/spans.py)."""
from bench import spans


def read(ctx: dict):
    return spans.per_round(ctx, "self_ms", spans.STAGE)
