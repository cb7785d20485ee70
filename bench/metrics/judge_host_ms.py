"""Host self time of `fl.judge` (the judge's verdict) a traced round, in
ms (bench/spans.py)."""
from bench import spans


def read(ctx: dict):
    return spans.per_round(ctx, "self_ms", spans.JUDGE)
