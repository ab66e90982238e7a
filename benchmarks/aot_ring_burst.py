"""Chipless check of a cell's burst program for whole-ring relayouts, run by hand:

    JAX_PLATFORMS=cpu python benchmarks/aot_ring_burst.py <workload> [hlo-out.txt]

Runs `chipbench/tests/aot_burst.py` (the compile for a described v5e at the cell's real sizes, its memory
analysis printed) with ONE line changed in memory: the ring's avals take the stored view
(`data/ring.py:ring_cell`), as `utils/burst.py:init_device_ring` allocates it. The script under `chipbench/`
still builds env-shaped avals, which the burst program no longer accepts, and is a `benchmark` PR's to edit
(PERF.md section 7); when it is, this file goes. Then lists every `copy`, `reshape` or `transpose` whose result
is a whole ring key (`analysis/hlo.py:whole_array_relayouts`). A compile that passes is not a chip run.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
path = os.path.join(ROOT, "chipbench", "tests", "aot_burst.py")
src = open(path).read()
old = "rb = {k: jax.ShapeDtypeStruct((capacity, n_envs) + shape, dtype, sharding=rep) for k, (shape, dtype) in ring_keys.items()}"
assert src.count(old) == 1, "chipbench/tests/aot_burst.py changed: see this file's docstring"
src = src.replace(old, "from sheeprl_tpu.data.ring import ring_cell\n" + old.replace("+ shape,", "+ ring_cell(shape),"))
sys.argv = [path] + sys.argv[1:]
scope = {"__file__": path, "__name__": "__main__"}
exec(compile(src, path, "exec"), scope)

from sheeprl_tpu.analysis.hlo import whole_array_relayouts  # noqa: E402  (aot_burst.py put ROOT on sys.path)

text = scope["compiled"].as_text()
if len(sys.argv) > 2:
    with open(sys.argv[2], "w") as f:
        f.write(text)
found = whole_array_relayouts(text, scope["capacity"])
print(json.dumps({"whole_ring_relayouts": len(found), "logical_bytes": sum(r["bytes"] for r in found)}))
for r in found:
    print(f'  {r["name"]} {r["op"]} {r["dtype"]}{list(r["dims"])}{r["layout"]} {r["bytes"]} B in {r["computation"]}')
