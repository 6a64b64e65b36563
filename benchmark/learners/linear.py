"""What the harness asks of `wormhole_tpu.apps.linear`'s learner, where
the learner does not answer itself (`check._ask`): its tables as one
mapping, a batch's kind and a batch's labels. The one place under
`benchmark/` that knows `LinearLearner`'s store and the layout of its
batch tuples (pinned by tests/test_linear.py since PR 28): prepared
`(kind, packed, label, mask, size)` / `("xla", db, size)`, staged
`("staged", kind, args, size, ids, train)` with `args` ending in
`label, mask`. Once the learner has `tables()`, `batch_kind(b)` and
`batch_label(b)` of its own, nothing here is called (PERF.md section 7).
"""

import numpy as np


def tables(learner) -> dict:
    return learner.store.state


def batch_kind(learner, b) -> str:
    return b[1] if b[0] == "staged" else b[0]


def batch_label(learner, b) -> np.ndarray:
    """Labels of a prepared or staged batch, on the host."""
    if b[0] == "staged":
        return np.asarray(b[2][-2])
    if b[0] == "xla":
        return np.asarray(b[1].label)
    return np.asarray(b[-3])
