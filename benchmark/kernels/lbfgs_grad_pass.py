"""A gradient pass of the batch solver over the resident rows
(`models/batch_objectives._BatchObjBase.grad`): g = X^T (sigmoid(X w) - y).
The algorithm's count, not the implementation's: the rows' COO stream
read once (seg, idx, val: 12 B a nonzero), labels and mask once, w read
once and g written once, each a dense float32 vector of `dim`; per
nonzero a multiply-add for the margin and one for the scatter."""


def cost(batch: dict) -> dict:
    return {"bytes": batch["nnz"] * 12.0 + batch["rows"] * 8.0
            + 2 * batch["dim"] * 4.0,
            "flops": 4.0 * batch["nnz"]}
