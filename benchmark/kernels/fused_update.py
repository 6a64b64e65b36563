"""`ops/fused_update.scatter_update`: the per-key FTRL update in place on
the touched buckets. Needed per unique bucket: its id and gradient read,
z, n, w read and written; about 20 floating-point operations (g^2, two
square roots, sigma, the z and n updates, the learning rate, the
soft-threshold and the division)."""

FLOPS_PER_KEY = 20.0


def cost(batch: dict) -> dict:
    u = batch["uniq"]
    return {"bytes": u * (4 + 4 + 3 * 4 + 3 * 4),
            "flops": FLOPS_PER_KEY * u}
