"""`ops/fused_update.scatter_update` with a count table riding as the
added table (models/difacto.py): the per-key FTRL update in place on the
touched buckets and cnt += the batch's occurrences. Needed per unique
bucket: its id, gradient and occurrence count read, z, n, w, cnt read and
written; about 20 floating-point operations for FTRL (as
kernels/fused_update.py counts them) and one add for the count."""

FLOPS_PER_KEY = 21.0


def cost(batch: dict) -> dict:
    u = batch["uniq"]
    return {"bytes": u * (4 + 4 + 4 + 4 * 4 + 4 * 4),
            "flops": FLOPS_PER_KEY * u}
