"""`ops/coo_kernels.tile_gather` once more in a step that admits by count
(models/difacto.py): the count table's fetch for the batch's unique
buckets, in float32. Needed per unique bucket: its id read, its count
read, its compact copy written. No floating-point operation."""


def cost(batch: dict) -> dict:
    u = batch["uniq"]
    return {"bytes": u * (4 + 4 + 4), "flops": 0.0}
