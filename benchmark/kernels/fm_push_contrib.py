"""`ops/coo_kernels.fm_push_contrib`: the embedding gradient by key,
gK[k] = sum over the nonzeros of key k of d (xv - V[row of k]). Needed:
per nonzero its key's rank, its batch row and its value read (4 bytes
each), per batch row its xv (`dim` floats) and dual read once, per
distinct key its V row read and gK written (`dim` floats each).
Operations: per nonzero and element one multiply (d xv) and one add into
the sum, per distinct key and element one multiply and one subtract
((sum d) V). Admission is applied to a key's sum afterwards: every
nonzero is summed, admitted or not."""


def cost(batch: dict) -> dict:
    keys, dim = batch["uniq"], batch["hyper"]["dim"]
    return {"bytes": batch["nnz"] * (4 + 4 + 4)
            + batch["rows"] * 4 * (dim + 1) + keys * 4 * dim * 2,
            "flops": 2.0 * dim * (batch["nnz"] + keys)}
