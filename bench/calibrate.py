"""Readings that a cell's limits (``bench/limits/<cell>.json``) are set
from, on the chip at the cell's own size; the benchmark's runs do not
run this.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        [--faults 3] [--out FILE]

For each seed: the program's first warm-up chunk (the rounds ``run.py``
compares) against the plain float32 reference.  For the first
``--faults`` seeds also, in the program's place: the control (the
reference in bfloat16, the precision below the configuration's float32)
and the faults ``half_cohort`` (each round's mean taken over half of
the solve cohort) and ``unchanged`` (a step that returns its state
unchanged: 1 on ``change_gap`` by construction, and read here for
``loss_gap``).  One JSON line per
reading on standard output, and all of them in ``--out``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import harness, run  # noqa: E402


def calibrate(cell, seeds, faults: int):
    """Yield one reading dict per (seed, kind)."""
    import jax.numpy as jnp
    import numpy as np
    for i, seed in enumerate(seeds):
        s = run.Setup(cell, seed)
        warm = run.warm_up(s, second=False)
        del s.trainer, s.dataset
        gc.collect()
        ref = run.reference(s)
        yield {"seed": seed, "kind": "program",
               "losses": warm["losses"].tolist(),
               "ref_losses": ref[0].tolist(),
               **run.readings(s, warm["losses"], warm["loss_rounds"],
                              warm["params"], ref)}
        if i >= faults:
            continue
        every = np.arange(1, len(ref[0]) + 1)
        for kind, kw in (("control_bfloat16", {"dtype": jnp.bfloat16}),
                         ("half_cohort", {"fault": "half_cohort"}),
                         ("unchanged", {"fault": "unchanged"})):
            out = run.reference(s, **kw)
            yield {"seed": seed, "kind": kind,
                   "losses": np.asarray(out[0]).tolist(),
                   **run.readings(s, out[0], every, out[1], ref)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    try:
        run.device_info(cell.chips)
    except SystemExit as exc:
        return int(exc.code)
    run.enable_compile_cache()
    seeds = [int(x) for x in args.seeds.split(",")]
    rows = []
    for row in calibrate(cell, seeds, args.faults):
        rows.append(row)
        print(json.dumps(row, default=float), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, default=float)
    return 0


if __name__ == "__main__":
    sys.exit(main())
