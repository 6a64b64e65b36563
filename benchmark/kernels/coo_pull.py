"""`ops/coo_kernels.coo_spmv`: xw = X w over the batch's nonzeros (the
dense path's pull). Needed: per nonzero its bucket id, row id and value
read, per unique bucket its weight read, per row the margin written; one
multiply and one add per nonzero."""


def cost(batch: dict) -> dict:
    return {"bytes": batch["nnz"] * (4 + 4 + 4) + batch["uniq"] * 4
            + batch["rows"] * 4,
            "flops": 2.0 * batch["nnz"]}
