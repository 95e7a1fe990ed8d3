#!/usr/bin/env python3
"""Readings for the limit of ``correct``: the program's widest logit gap
and the control's, on many seeds, in one process.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed it makes one run of the cell as ``bench/run.py`` does, with
a window of ``--seconds``, and reads on the same served sequences both the
program's widest gap and the control's (the reference with fp8 operands,
``lib/check.py``), with the harness's verdict on each.  The benchmark's
own runs never run the control.  One JSON line per seed, then a summary
line: the program's largest reading (the lower end of the limit) and the
control's smallest (the upper end).  It exits 1 where the control came
out correct on any seed.  A process's peak memory never falls, so from
the second seed on ``hbm_peak_gb`` holds the earlier seeds' checks too:
only ``bench/run.py``, one run a process, reads it.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import jax
    from lib import harness, spec
    from lib.peaks import peaks
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"readings: needs a TPU, found {dev.platform!r}",
              file=sys.stderr)
        return 2
    bm = spec.load_benchmark()
    prog, ctrl = [], []
    t_start = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        r = harness.run(bm, args.workload, seed=seed, seconds=args.seconds,
                        trace=False, t_start=t_start, device=dev,
                        peaks=peaks(dev.device_kind), control=True)
        gap = r["check"]["logit_gap"]["value"]
        prog.append(gap)
        ctrl.append(r["control"])
        print(json.dumps({"seed": seed, "program_gap": gap,
                          "correct": r["correct"], "control": r["control"],
                          "check": r["check"], "metrics": r["metrics"]}),
              flush=True)
        t_start = time.perf_counter()
    failed = all(c["correct"] is False for c in ctrl)
    print(json.dumps({"workload": args.workload, "seeds": len(prog),
                      "program_max": max(prog),
                      "control_min": min(c["logit_gap"] for c in ctrl),
                      "control_never_correct": failed}))
    return 0 if failed else 1


if __name__ == "__main__":
    sys.exit(main())
