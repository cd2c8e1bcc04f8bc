"""The control: the timed path with one guarantee of the configuration
broken, which the comparison must call not correct.

    python3 -m perfbench.control --workload <name> --seeds 1,2,3 --seconds <s>

The cell's driver supplies it (``control`` in ``drivers/<name>.py``):
for ``mine`` every block sealed at its second hit, for ``replay`` a scan
that skips the chain's last segment.  The runs are those of
``perfbench.run`` in one process, one seed after another.  The
benchmark's own runs never install it.
"""

from __future__ import annotations

import argparse
import sys

from perfbench import cell as cells
from perfbench import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    control = cells.driver(cells.load(args.workload)).control
    rc = 0
    for seed in args.seeds.split(","):
        argv1 = ["--workload", args.workload, "--seed", seed, "--seconds", args.seconds]
        rc |= run.main(argv1 + (["--rehearse"] if args.rehearse else []), patch=control)
    return rc


if __name__ == "__main__":
    sys.exit(main())
