"""Share of its roofline that `kernel.window_attention` reaches in the traced blocks: the
score work over the mask's true area (flops/<family>.py `attention_kernel_work`) over the
kernel's measured device time."""

from layers._lm_counters import roofline_pct


def read(run):
    def work(flops, block):
        iters = max(1, block.get("iters", 1))
        return {phase: (f * iters, b * iters) for phase, (f, b) in flops.attention_kernel_work(run["config"]).items()}

    return roofline_pct(run, "window_attention", work)
