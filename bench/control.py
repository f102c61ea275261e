"""Readings that set a cell's limits, on the chip at the cell's own size.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 [--seconds S]

For each seed, one JSON line with the numbers the check compares, as the
cell's driver reads them (its ``control_readings``, which reuses its own
check):

* ``program``: the program against the reference (a sound run);
* ``control``: the reference computed with fp8 matrix products (the
  control) in the program's place;
* the faults a cell of this kind can have (training: ``half_batch``, and on
  several chips ``no_exchange``; serving: ``altered_token``).

The benchmark's measured runs never run this.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    from bench import harness

    harness.enable_cache()
    cell, cfg, mix, limits, _, _ = harness.cell_parts(args.workload)
    devs = harness.devices_for(cell["chips"], True)[:cell["chips"]]
    log = lambda *a: print(*a, file=sys.stderr, flush=True)  # noqa: E731
    for seed in args.seeds:
        t0 = time.time()
        drv = harness.driver_class(mix)(cell, cfg, mix, devs, seed, log=log)
        out = drv.control_readings(args.seconds)
        del drv
        out.update(seed=seed, seconds=time.time() - t0, limits=limits)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
