"""The readings a cell's correctness limit is set from.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed, in one process: set the cell up, serve its traffic for
``--seconds`` (an open-loop cell drains as in a run), draw the sample a
run draws, free the program's state and read two numbers over it:

* ``program``: the widest gap by which a served token's logit lies below
  the float32 reference's best (what a run compares with the limit);
* ``control``: the same gap for the token that the reference computed
  through float8 ranks first, the precision below the configuration's
  bfloat16.

The limit lies between the largest ``program`` reading and the smallest
``control`` one.  Prints one JSON line per seed.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parent.parent)]

from chipbench import run as R  # noqa: E402
from chipbench.spec import Cell  # noqa: E402
from chipbench.window import Window  # noqa: E402


def readings(cell: Cell, seed: int, seconds: float, ref=None) -> dict:
    """Both readings for one seed; ``ref`` is the cell's reference
    module, loaded once where several seeds share it."""
    ref = ref or cell.reference()
    setup = R.Setup(cell, seed, seconds)
    window = Window(setup.batcher, setup.request_cls)
    cell.driver().drive(window, setup.offered, seconds, cell.traffic)
    seqs = R.sample(window, seed, cell.config["cache_len"])
    params = setup.params
    del setup, window
    gc.collect()
    out = {"seed": seed, "sequences": len(seqs),
           "tokens": int(sum(len(s[2]) for s in seqs))}
    for name, precision in (("program", "float32"), ("control", "float8")):
        gaps = ref.logit_gaps(cell.config, params, seqs, precision)
        out[name] = max(float(g.max()) for g in gaps)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = Cell(args.workload)
    R.accelerator_devices(cell.chips)
    R.setup_compile_cache()
    ref = cell.reference()
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(cell, seed, args.seconds, ref)), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
