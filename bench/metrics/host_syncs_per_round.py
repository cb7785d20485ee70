"""Device-to-host readbacks (`fl.fetch` spans) a traced round
(bench/spans.py)."""
from bench import spans


def read(ctx: dict):
    s = spans.of_run(ctx)
    if not s or not s["rounds"]:
        return None
    return s["count"].get(spans.FETCH, 0) / s["rounds"]
