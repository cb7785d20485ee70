"""Device time of the client program (`jit_client_update` on the chip's
`XLA Modules` line) a traced round, in ms (bench/spans.py)."""
from bench import spans


def read(ctx: dict):
    return spans.per_round(ctx, "module_ms", "jit_client_update")
