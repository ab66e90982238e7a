"""What the routed-layer and roofline readers of the token-PPO cells share:
the block's own counters, as the adapter recorded them per dispatched block
(`run["flushes"][i]["counters"]`), and the family's kernel-work functions
(flops/<family>.py). A run whose blocks carry no such counters (another
trainer, or a program without them) gives `None` everywhere."""

import importlib.util
import os
import sys


def blocks(run, traced=False):
    """The blocks of the window, or of the traced stretch."""
    if traced:
        info = run.get("trace_info") or {}
        if "flush_start" not in info:
            return []
        found = run["flushes"][info["flush_start"] : info.get("flush_stop")]
    else:
        found = run["flushes"]
    return [b for b in found if "counters" in b]


def flat(value):
    """A counter's numbers, whatever its nesting (iterations x layers)."""
    return [x for sub in value for x in flat(sub)] if isinstance(value, list) else [value]


def total(block, name):
    """A counter of one block, summed over its iterations and layers."""
    return float(sum(flat(block["counters"][name])))


def flops_module(run):
    """flops/<family>.py: the module `run.py` has loaded, or loaded here by path."""
    family = run["config"]["family"]
    name = f"chipbench_flops_{family}"
    if name not in sys.modules:
        path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "flops", family + ".py")
        spec = importlib.util.spec_from_file_location(name, path)
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


def roofline_pct(run, kernel, work_of):
    """100 x the least time the kernel's counted work can take (per phase the
    larger of operations / bf16 peak and bytes / memory bandwidth) over the
    time the trace shows for it, in the traced blocks."""
    from layers._program_record import kernel_ms

    measured_ms = kernel_ms(run, kernel)
    found = blocks(run, traced=True)
    flops = flops_module(run)
    if not measured_ms or not found or not run.get("peaks") or not hasattr(flops, "roofline_seconds"):
        return None
    ideal = sum(flops.roofline_seconds(work_of(flops, b), run["peaks"]) for b in found)
    return 100.0 * ideal / (1e-3 * measured_ms * run["trace"]["grants"])
