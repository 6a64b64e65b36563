"""`ops/coo_kernels.tile_gather`: the compacted pull's fetch of w for the
batch's unique buckets. What the algorithm needs, from the batch's shapes:
per unique bucket its id read, its weight read, its compact copy written.
No arithmetic the machine would count as a floating-point operation."""


def cost(batch: dict) -> dict:
    u = batch["uniq"]
    return {"bytes": u * (4 + 4 + 4), "flops": 0.0}
