"""linear.dmlc: async-SGD sparse logistic regression (reference
learn/linear/linear.cc + config.proto surface).

  python -m wormhole_tpu.apps.linear guide/demo.conf lambda_l1=4
"""

from __future__ import annotations

import sys

from wormhole_tpu.apps._runner import app_main
from wormhole_tpu.models.linear import LinearConfig, LinearLearner
from wormhole_tpu.parallel.mesh import local_mesh


def make_learner(cfg: LinearConfig, env):
    return LinearLearner(cfg, local_mesh("linear", cfg.model_shards))


def serve_scorer(cfg: LinearConfig):
    """Scorer for the serving tier (router-side predict math)."""
    from wormhole_tpu.serving.scoring import LinearScorer

    return LinearScorer(cfg)


def main(argv=None) -> int:
    return app_main(LinearConfig, make_learner, argv)


if __name__ == "__main__":
    sys.exit(main())
