#!/usr/bin/env python3
"""The control of the reference check: does a lower precision fail it?

  python3 benchmark/control.py --config <name> --seeds 11,12,13

For each seed: the first steps' batches as run.py would generate them, the
float32 reference of the configuration, and the same reference with the
configuration's `control_precision` put in the program's place (the tables
the reference declares stored in bfloat16 between steps: the nearest
precision below the float32 the configuration states, and the step that
would tempt a later PR, since it halves the update's memory traffic) —
over the first steps from the start (zeroed tables; a leaf that does not
start at zero as the reference's `draw_start` draws it from the seed),
and over one more step from the state they leave (the served step).
Prints the compared numbers of the control beside the limits; every seed has to come out NOT correct. Run on the chip at the
configuration's real sizes for the readings in PERF.md; tests/benchmark
runs it at the rehearsal size. The benchmark's own runs never run it.
"""

import argparse
import importlib
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import check, gen, run  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def control_numbers(config: dict, seed: int, rehearsal: bool = False) -> dict:
    conf, sized = run.sized(config, rehearsal)
    precision = sized["precision"]
    # the control lowers the tables and nothing else
    lower = dict(precision, tables=config["control_precision"]["tables"])
    rows, steps = int(conf["minibatch"]), config["correct"]["steps"]
    reference = importlib.import_module(
        f"benchmark.reference.{config['reference']}")
    model = gen.KeyModel(config["keys"])
    batches = []
    for p in range(steps + 1):
        r = gen.Rows(model, seed, gen.TRAIN_STREAM, p, rows)
        batches.append((r.keys(), r.label))
    sizes, hyper = check.space_sizes(reference, conf), config["hyper"]
    decl = reference.TABLES
    seeded = [k for k, d in decl.items() if not d["zero_start"]]

    def drawn(ids):
        """Leaves that do not start at zero, as the reference draws them
        (the program's own start is nothing the control may take)."""
        return reference.draw_start(ids, sizes, hyper, seed) if seeded else {}

    ids = check.union_ids(reference, sizes, [k for k, _ in batches[:steps]])
    start = {"ids": ids, "tables": drawn(ids)} if seeded else None
    ref = reference.run_steps(batches[:steps], sizes, hyper, precision,
                              start=start)
    low = reference.run_steps(batches[:steps], sizes, hyper, lower,
                              start=start)
    nums = check.numbers(check.reference_as_run(low, rows, start),
                         check.reference_as_run(ref, rows, start))
    # the served step: one more batch from the float32 state the first
    # steps left, by the reference and by the control in its place
    ids = check.union_ids(reference, sizes, [batches[steps][0]])
    fresh, pre = drawn(ids), {}
    for k, v in ref["states"][-1].items():
        known, mine = ref["ids"][decl[k]["space"]], ids[decl[k]["space"]]
        pos = np.minimum(np.searchsorted(known, mine), len(known) - 1)
        hit = (known[pos] == mine).reshape((-1,) + (1,) * (v.ndim - 1))
        pre[k] = np.where(hit, v[pos], fresh.get(k, 0.0)).astype(np.float32)
    start = {"ids": ids, "tables": pre}
    one = [batches[steps]]
    r1 = reference.run_steps(one, sizes, hyper, precision, start=start)
    l1 = reference.run_steps(one, sizes, hyper, lower, start=start)

    def as_run(r):
        return {"pre": pre, "post": r["states"][0], "objv": r["objv"][0],
                "nex": float(rows)}

    nums.update(check.served_numbers(as_run(l1), as_run(r1)))
    return nums


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", default="11,12,13")
    ap.add_argument("--rehearsal", type=int, default=0)
    args = ap.parse_args(argv)
    with open(os.path.join(HERE, "configs", args.config + ".json")) as fh:
        config = json.load(fh)
    import jax

    print(f"[control] platform={jax.devices()[0].platform} "
          f"kind={jax.devices()[0].device_kind!r}", flush=True)
    all_failed = True
    for seed in (int(s) for s in args.seeds.split(",")):
        nums = control_numbers(config, seed, bool(args.rehearsal))
        ok, lines = check.verdict(nums, {
            **config["correct"]["limits"],
            **config["correct"]["served_limits"]})
        all_failed &= not ok
        print(f"[control] seed {seed}: correct={ok} " + json.dumps(nums),
              flush=True)
        for line in lines:
            print(f"[control]   {line}", flush=True)
    print(f"[control] every seed came out not correct: {all_failed}",
          flush=True)
    return 0 if all_failed else 1


if __name__ == "__main__":
    sys.exit(main())
