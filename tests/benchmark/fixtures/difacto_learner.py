"""What the harness asks of `wormhole_tpu.apps.difacto`'s learner where
the learner does not answer itself (`check._ask`). Test fixture
(PR 30): copied to `benchmark/learners/difacto.py` of a temporary copy.
The learner keeps w, z, n, cnt in one store and V, nV in a second over
`v_buckets`; its checkpoint view merges the two. Batches: prepared
`("fm", args, size, train, ids)` with `args` ending in `label, mask`, or
`("xla", db, size)`; staged `("xla_staged", (seg, idx, vidx, val, label,
mask), size, train, ids)`.
"""

import numpy as np


def tables(learner) -> dict:
    return learner.ckpt_store.state


def batch_kind(learner, b) -> str:
    return b[0]


def batch_label(learner, b) -> np.ndarray:
    if b[0] == "xla":
        return np.asarray(b[1].label)
    return np.asarray(b[1][-2])
