"""Model FLOPs per gradient step (flops/<family>.py, from the widths) times
gradient steps per second of the window, over chips x the bf16 peak."""


def read(run):
    if not run["flops_per_grad_step"] or not run["peaks"]:
        return None
    w = run["window"]
    rate = w["grants"] / w["seconds"]
    return 100.0 * run["flops_per_grad_step"] * rate / (run["chips"] * run["peaks"]["bf16_flops_per_s"])
