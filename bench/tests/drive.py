"""Drive one run of a cell on the CPU, past the look for a chip, with the
timed path optionally broken, and print the result line.

    JAX_PLATFORMS=cpu python3 -m bench.tests.drive <cell> <seconds> <fault> [--control]

``fault`` is ``none`` or ``altered``: every output the plan produces has
its first row's answer changed by 0.5, where it is produced.
"""

from __future__ import annotations

import json
import sys


def main(argv) -> int:
    cell, seconds, fault = argv[:3]
    from bench import run

    args = run.parse(["--workload", cell, "--seed", "4000000007",
                      "--seconds", seconds, "--trace", "0"]
                     + (["--control"] if "--control" in argv else []))
    if run.SRC.as_posix() not in sys.path:
        sys.path.insert(0, str(run.SRC))
    if fault == "altered":
        from repro.engine import plan as P

        call = P.ExecutionPlan.__call__

        def altered(self, *a, **kw):
            y = call(self, *a, **kw)
            return y.at[0, 0].add(0.5)

        P.ExecutionPlan.__call__ = altered
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault!r}")
    print(json.dumps(run.run_cell(args)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
