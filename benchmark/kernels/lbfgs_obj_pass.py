"""An objective pass of the batch solver over the resident rows
(`_BatchObjBase.eval`, a line-search trial): sum softplus(X w) - y X w.
The algorithm's count: the rows' COO stream read once (12 B a nonzero),
labels and mask once, w read once (a dense float32 vector of `dim`);
a multiply-add per nonzero. The regulariser's second read of w is the
implementation's and is not counted."""


def cost(batch: dict) -> dict:
    return {"bytes": batch["nnz"] * 12.0 + batch["rows"] * 8.0
            + batch["dim"] * 4.0,
            "flops": 2.0 * batch["nnz"]}
