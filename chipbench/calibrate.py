"""Readings for the limits of ``correct``: the program over many seeds, and
the control and the planted faults, in one process (set-up is most of a
run's cost).

    python3 -m chipbench.calibrate --workload <cell> --seeds 1 2 3 --control-seeds 1 2 3

For each seed: the cell's set-up, one job, the cell's comparison; and for the
control seeds the driver's ``control``: the comparison's numbers with the
lower-precision control, and with each fault it plants, in the program's
place. One JSON line a reading, each with the device it was read on and the
``correct`` that ``run.measure`` would have given it; every control and
fault has to read false. Like a run, it reads nothing off the chip.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys


def main(argv=None) -> int:
    from chipbench import run

    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="*", default=[])
    parser.add_argument("--control-seeds", type=int, nargs="*", default=[])
    parser.add_argument("--dry-run-cpu", action="store_true")
    args = parser.parse_args(argv)
    cell = run.load_cell(args.workload)
    _, found = run.look_for_chip(cell, args.dry_run_cpu)
    config, traffic = run.sizes(cell, args.dry_run_cpu)

    from mmlspark_tpu.core.device import configure_compile_cache

    configure_compile_cache()
    run.build_native(lambda **kw: None)
    driver = importlib.import_module(f"chipbench.drivers.{cell['driver']}")

    def reading(seed, side, checks, **more):
        print(json.dumps({"seed": seed, "side": side, "device": found,
                          "correct": run.passes(checks), **more, "checks": checks}), flush=True)

    for seed in sorted(set(args.seeds) | set(args.control_seeds)):
        state = driver.setup(config, traffic, seed)
        if seed in args.control_seeds:
            for side, checks in driver.control(dict(state)).items():
                reading(seed, side, checks)
        if seed in args.seeds:
            out = driver.job(state)
            reading(seed, "program", driver.compare(state, [out], seed),
                    fault=driver.fault(state, out))
        del state
    return 0


if __name__ == "__main__":
    sys.exit(main())
