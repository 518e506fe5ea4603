"""Readings that the limit of ``correct`` is set from, on the chip.

    python3 chipbench/calibrate.py --workload <name> --seeds 11,12,13 --seconds 10
    python3 chipbench/calibrate.py --workload <name> --seeds 11 --seconds 10 \
        --fault state_unchanged

For each seed, in this one process, one run of the cell as ``run.py`` makes
it (``--trace 0``), and then, on the same sample of served positions, the
control: the plain reference computed at 4 bits in the program's place. The
control's widest gap goes through the same decision as the program's
(``run.decide``) and has to come out as not correct. Each seed prints one
JSON line with the program's widest gap (the lower reading) and the
control's (the upper reading). With ``--fault`` the program runs with that
fault planted in its decode burst (``chipbench/faults.py``), and the run has
to come out as not correct. The exit code is 1 where a control or a faulted
run came out as correct. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import run  # noqa: E402  (sets up the import paths)

from chipbench import faults  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU; nothing run", file=sys.stderr)
        return 2
    run.use_compile_cache(jax, run.compile_cache_dir())
    if args.fault:
        faults.plant(args.fault)
    bench = run.manifest.load()
    passed = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run_cell(bench, args.workload, seed, args.seconds, False,
                           control=not args.fault)
        checks = out["checks"]
        line = {"seed": seed, "fault": args.fault, "correct": out["correct"],
                "program_gap": checks["max_logit_gap"]["value"],
                "tokens_checked": checks["served_tokens_checked"]["value"],
                "metrics": {k: v["value"] for k, v in out["metrics"].items()},
                "compiles": out["window"]["compiles"]}
        if args.fault:
            passed += out["correct"]
        else:
            control = checks["control_max_logit_gap"]
            line.update(control_gap=control["value"],
                        control_correct=control["correct"])
            passed += control["correct"]
        print(json.dumps(line), flush=True)
        gc.collect()
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
