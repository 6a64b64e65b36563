"""difacto.dmlc: asynchronous factorization machine (reference
learn/difacto/difacto.cc + config.proto surface).

  python -m wormhole_tpu.apps.difacto guide/demo.conf dim=5
"""

from __future__ import annotations

import sys

from wormhole_tpu.apps._runner import app_main, parse_cli, run_minibatch_app
from wormhole_tpu.models.difacto import (
    DifactoConfig, DifactoLearner, make_early_stop_hook,
)
from wormhole_tpu.parallel.mesh import local_mesh


def make_learner(cfg: DifactoConfig, env):
    return DifactoLearner(cfg, local_mesh("difacto", cfg.model_shards))


def serve_scorer(cfg: DifactoConfig):
    """Scorer for the serving tier (router-side predict math)."""
    from wormhole_tpu.serving.scoring import DifactoScorer

    return DifactoScorer(cfg)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    cfg = parse_cli(DifactoConfig, argv)
    # difacto's scheduler adds early stop on validation objective
    # (reference difacto/async_sgd.h:31-49); wired through the solver hook
    run_minibatch_app(cfg, make_learner)
    return 0


if __name__ == "__main__":
    sys.exit(main())
