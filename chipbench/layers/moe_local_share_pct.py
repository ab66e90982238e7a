"""Of the assignments the router made in the update's forwards in the window, the share that
landed on experts held here (the block's `moe_local_assignments` counter over tokens x top_k
x layers); near 100 x experts_held / experts under a fresh router."""

from layers._lm_counters import blocks, total


def read(run):
    found = blocks(run)
    a = run["config"]["as_run"]
    if not found or "algo.lm.moe_num_active_primary_experts" not in a:
        return None
    per_iter = (a["env.num_envs"] * a["algo.update_epochs"] * (a["env.prompt_len"] + a["algo.rollout_steps"])
                * a["algo.lm.moe_num_active_primary_experts"] * a["algo.lm.num_hidden_layers"])
    made = sum(b.get("iters", 1) for b in found) * per_iter
    return 100.0 * sum(total(b, "moe_local_assignments") for b in found) / made
