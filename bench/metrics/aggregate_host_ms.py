"""Host self time of `fl.aggregate` (the aggregator's dispatch) a traced
round, in ms (bench/spans.py)."""
from bench import spans


def read(ctx: dict):
    return spans.per_round(ctx, "self_ms", spans.AGGREGATE)
