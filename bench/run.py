"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the repository root. The cell (`BENCHMARK.json` "workloads"),
its configuration, traffic mix and metrics are found by name under
`bench/`. With `--trace 0` the result carries the cell's end-to-end
metrics, with `--trace 1` its per-layer metrics from a profiled run. The
last line of standard output is the result as one JSON object; the
numbers that decided `correct` are also the last lines of standard error.
Without a TPU (or with fewer chips than the cell asks for) it exits 2 and
prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench/run.py: the program ({ROOT / 'src' / 'repro'}) is not "
              "in this checkout", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]

    from benchlib import harness, spec

    bench = spec.load_benchmark(ROOT)
    try:
        result = harness.run_cell(bench, args.workload, args.seed,
                                  args.seconds, bool(args.trace),
                                  t_process=T_PROCESS, root=ROOT)
    except (harness.NoChip, spec.UnknownDevice) as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"[check] {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
