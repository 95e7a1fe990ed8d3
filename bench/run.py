#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
        --trace <0|1>

from the root of a checkout.  The cell, its configuration and its traffic
are found by name in ``BENCHMARK.json`` and ``bench/``.  A run builds the
engine on the configuration's encoded checkpoint (``lib/checkpoint.py``),
compiles every step program, fills the engine with the traffic's clients,
measures for ``--seconds``, checks the served tokens against the plain
reference and prints, as the last line of standard output, one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (end-to-end
with ``--trace 0``, per-layer with ``--trace 1``), ``device``, with
``--trace 1`` a ``breakdown``, and last ``check``, each compared number
beside its limit (also the last lines of standard error).

Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from lib import spec
    bm = spec.load_benchmark()
    cell = spec.cell(bm, args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"bench/run.py: {args.workload} needs {cell['chips']} TPU "
              f"chip(s); JAX found {len(devs)} device(s) of platform "
              f"{devs[0].platform!r}", file=sys.stderr)
        return 2
    from lib import harness
    from lib.peaks import peaks
    table = peaks(devs[0].device_kind)
    result = harness.run(bm, args.workload, seed=args.seed,
                         seconds=args.seconds, trace=bool(args.trace),
                         t_start=T_START, device=devs[0], peaks=table)
    for name, c in result["check"].items():
        print(f"[bench] check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
