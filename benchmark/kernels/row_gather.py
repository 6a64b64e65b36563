"""`ops/fused_update.row_gather`: the fetch of V and of nV for the
batch's distinct embedding rows (the step gathers each table's lines
once). What the algorithm needs, at the published width and not the
stored stride: per distinct row its id read, and of each of the two
tables its `dim` floats read and their compact copy written. No
floating-point operation."""


def cost(batch: dict) -> dict:
    rows, dim = batch["distinct"]["vrow"], batch["hyper"]["dim"]
    return {"bytes": rows * (4 + 2 * (4 * dim + 4 * dim)), "flops": 0.0}
