"""The control of the correctness check: a path that has to come out not correct.

    python3 bench/control.py --workload nws100k.read --seeds 11,12,13 --seconds 10

Runs the cell as ``bench/run.py`` does, one seed after another in this one
process, with the engine's answers cut to their first 8 embeddings: a
limit-k shortcut, the step that would tempt a change (stop the join early),
and a break of the guarantee the configurations state, that every match
set holds all embeddings.  The check has to read more than its limit on
every seed.  For each seed it prints the checks; the benchmark's own runs
never run this.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


LIMIT = 8


def limit_answers():
    """Patch the engine to return at most ``LIMIT`` embeddings per query."""
    from repro.core import GnnPeEngine

    match_many = GnnPeEngine.match_many

    def patched(self, queries, *args, **kwargs):
        return [m[:LIMIT] for m in match_many(self, queries, *args, **kwargs)]

    GnnPeEngine.match_many = patched


def main(argv=None) -> int:
    from gnnbench import cell as cellmod
    from gnnbench import cli

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = cellmod.resolve(cellmod.load_spec(), args.workload)
    why_not = cli.check_devices(cell.chips, cellmod.load_peaks())
    if why_not is not None:
        cli.log(f"control: {why_not}")
        return 2
    cli.enable_compile_cache()
    limit_answers()
    for seed in (int(s) for s in args.seeds.split(",")):
        run, checks = cellmod.run_cell(
            cell, seed, args.seconds, False, time.perf_counter(), log=cli.log
        )
        print(json.dumps({"seed": seed, "correct": cellmod.correct(checks), "checks": checks,
                          "matches": len(run.requests)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
