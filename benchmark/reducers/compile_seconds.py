"""Seconds of XLA backend compilation during set-up (`jax.monitoring`
`backend_compile_duration` events; a persistent-cache hit emits one too,
a short one)."""


def read(ctx: dict, event: str):
    secs = [s for _, ev, s in ctx["compile_events"] if ev == event]
    return float(sum(secs)) if secs else None
