"""Share of its roofline that `kernel.moe_grouped_ffn` reaches in the traced blocks: the work
of the assignments the block counted on held experts (flops/<family>.py `moe_kernel_work`)
over the kernel's measured device time."""

from layers._lm_counters import roofline_pct, total


def read(run):
    def work(flops, block):
        iters = max(1, block.get("iters", 1))
        return {
            phase: (f * iters, b * iters)
            for phase, (f, b) in flops.moe_kernel_work(
                run["config"], total(block, "moe_local_assignments") / iters, total(block, "moe_rollout_assignments") / iters
            ).items()
        }

    return roofline_pct(run, "moe_grouped_ffn", work)
