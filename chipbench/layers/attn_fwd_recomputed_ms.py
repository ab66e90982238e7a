"""Device self time per gradient step of the splash forward kernels that the update runs inside its backward: the
instructions whose innermost scope is `kernel.window_attention`, whose path went through `transpose(...)` and whose
name starts `splash_mqa_fwd`. Under a per-layer `jax.checkpoint` that saves nothing, the backward recomputes each
layer's attention forward only for the output and log-sum-exp that the backward kernels read; 0.0 where the trace
holds splash kernels and none of that kind, `None` where it holds no splash kernel or there is no trace or table."""

from layers._program_record import attributed

KERNEL_SCOPE = "kernel.window_attention"


def read(run):
    rows = attributed(run)
    if rows is None or not run["trace"]["grants"]:
        return None
    splash = [r for r in rows if r[3] == KERNEL_SCOPE and r[0].startswith("splash_mqa")]
    if not splash:
        return None
    recomputed = sum(r[1] for r in splash if r[4] and r[0].startswith("splash_mqa_fwd"))
    return 1e3 * recomputed / run["trace"]["grants"]
