"""Look at one traced run by hand (on-chip-measurement guide, section 6).

    chiprun -- python3 chipbench/tests/look_burst.py --workload <cell> --seed <n> [--seconds 20] [--out <dir>]

Runs `run.py --trace 1 --dump <out>/run.json` in this process and, as the
process leaves, writes what the per-layer readers of the program's own record
worked from, so that a metric can be checked against its sources:

- `<out>/run.json`: run.py's own dump (result line, reduced trace with
  `ops_self_s`, `custom_calls`, `inventory`, flushes);
- `<out>/spans.json`: the recorder's snapshot (`sheeprl_tpu.utils.profiler`);
- `<out>/<program>.hlo.txt.gz`: the optimized text of each registered burst
  program, and `<out>/look.json`: per program the seconds `as_text()` and
  `op_scopes` take, instructions per scope, every custom call with its
  `op_name`, and the tier `ops.kernels.registry` gave each kernel.

Not a benchmark run: nothing here is read by `run.py`.
"""

import argparse
import gzip
import json
import os
import re
import runpy
import sys
import time
from collections import Counter

HERE = os.path.dirname(os.path.abspath(__file__))
RUN_PY = os.path.join(os.path.dirname(HERE), "run.py")


def write_record(out: str) -> None:
    from sheeprl_tpu.analysis.hlo import op_scopes
    from sheeprl_tpu.ops.kernels import registry
    from sheeprl_tpu.utils import profiler

    with open(os.path.join(out, "spans.json"), "w") as f:
        json.dump(profiler.snapshot(), f, default=str)
    look = {"tiers": {name: registry.tier(name) for name in registry.names()}, "programs": {}}
    for name in profiler.programs():
        compiled = profiler.program(name)
        t0 = time.perf_counter()
        text = compiled.as_text()
        t1 = time.perf_counter()
        table = op_scopes(text, regions=profiler.REGIONS, kernel_prefix=profiler.KERNEL_PREFIX)
        t2 = time.perf_counter()
        custom = []
        for line in text.splitlines():
            if "custom-call(" in line or "custom_call_target" in line:
                head = line.strip().split(" = ", 1)[0]
                target = re.search(r'custom_call_target="([^"]*)"', line)
                op_name = re.search(r'op_name="([^"]*)"', line)
                custom.append([head, target.group(1) if target else None, op_name.group(1) if op_name else None])
        look["programs"][name] = {
            "text_bytes": len(text),
            "as_text_s": t1 - t0,
            "op_scopes_s": t2 - t1,
            "instructions": len(table),
            "by_scope": Counter(f"{v['outer']}|{v['scope']}|{'bwd' if v['backward'] else 'fwd'}" for v in table.values()),
            "custom_calls": custom,
        }
        with gzip.open(os.path.join(out, name.replace("/", "_") + ".hlo.txt.gz"), "wt") as f:
            f.write(text)
    with open(os.path.join(out, "look.json"), "w") as f:
        json.dump(look, f)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out", default="")
    args, rest = ap.parse_known_args()
    out = os.path.abspath(args.out or os.path.join("chiprun_out", "look", args.workload))
    os.makedirs(out, exist_ok=True)

    leave = os._exit

    def leave_with_record(code: int) -> None:
        try:
            write_record(out)
        except BaseException as e:  # the run's own exit code stands
            print(f"look_burst: record not written: {type(e).__name__}: {e}", file=sys.stderr)
        leave(code)

    os._exit = leave_with_record
    sys.path.insert(0, os.path.dirname(HERE))  # as `python3 chipbench/run.py` has it: the readers import `layers.*`
    sys.argv = [RUN_PY, "--workload", args.workload, "--seed", str(args.seed), "--seconds", str(args.seconds),
                "--trace", "1", "--dump", os.path.join(out, "run.json"), *rest]
    runpy.run_path(RUN_PY, run_name="__main__")


if __name__ == "__main__":
    main()
