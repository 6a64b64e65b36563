"""`ops/coo_kernels.coo_spmv_t`: g = X^T d over the batch's nonzeros.
Needed: the dual read once per row, per nonzero its bucket id, row id and
value read, per unique bucket the summed gradient written; one multiply
and one add per nonzero."""


def cost(batch: dict) -> dict:
    return {"bytes": batch["rows"] * 4 + batch["nnz"] * (4 + 4 + 4)
            + batch["uniq"] * 4,
            "flops": 2.0 * batch["nnz"]}
