"""`ops/fused_update.v_update` after its gathers (kernels/row_gather.py
counts those): AdaGrad with L2 on the batch's distinct embedding rows and
their write-back. Needed per distinct row: its id read, its gradient and
the compact V and nV read, V and nV written to the tables, `dim` floats
each; per element about 8 floating-point operations (g^2, the add, a
square root, the rate, lambda_V V, the sum, the division, the
subtraction)."""

FLOPS_PER_ELEMENT = 8.0


def cost(batch: dict) -> dict:
    rows, dim = batch["distinct"]["vrow"], batch["hyper"]["dim"]
    return {"bytes": rows * (4 + 4 * dim * (3 + 2)),
            "flops": FLOPS_PER_ELEMENT * rows * dim}
