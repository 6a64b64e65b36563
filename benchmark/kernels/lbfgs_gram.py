"""The Gram matrix B B^T of the L-BFGS basis [S..., Y..., pg]
(`solver/lbfgs.py`): `basis` = 2 x history + 1 dense float32 vectors of
`dim`, each read once; a multiply-add per element and pair of vectors,
the matrix being symmetric."""


def cost(batch: dict) -> dict:
    k = batch["basis"]
    return {"bytes": k * batch["dim"] * 4.0,
            "flops": k * (k + 1) * batch["dim"] * 1.0}
