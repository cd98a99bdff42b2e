"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/control.py --workload archive-drain --seeds 1,2,3 --seconds 3

For each seed, in one process, two runs of the harness at the cell's own
size and load: one of the system (``program``), and one with the control,
the reference computed in bfloat16, standing in for the system's decoder
(``control``).  One JSON line per seed holds each run's ``correct`` and the
numbers its check compared.  The benchmark's own runs do not run this; the
limits in ``bench/traffic/<mix>.json`` lie between the largest program
reading and the smallest control reading (see PERF.md).
"""
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), os.path.join(ROOT, "bench")]


def readings(cell_name, seed, seconds, *, require_chip=True, overrides=None):
    """(program run, control run): each ``{"correct": ..., <number>: value}``."""
    from fptcbench import archive, control, spec
    from fptcbench.harness import run_cell

    def one():
        r = run_cell(cell_name, seed, seconds, False, t_start=time.perf_counter(),
                     require_chip=require_chip, overrides=overrides)
        return dict({n: c["value"] for n, c in r["checks"].items()},
                    correct=r["correct"])

    config = dict(spec.load_cell(cell_name).config, **(overrides or {}))
    tables, _ = archive.domain_tables(config, config["sizes"]["data_seed"])
    program = one()
    with control.in_place_of_decoder(tables):
        return program, one()


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        program, ctl = readings(args.workload, seed, args.seconds)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "program": program, "control": ctl}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
