"""The largest load one held expert saw in one forward of the update (the block's
`moe_max_expert_load` counter, the worst layer and block of the window) over the mean load
a held expert would see under even routing: tokens x top_k / experts."""

from layers._lm_counters import blocks, flat


def read(run):
    found = blocks(run)
    a = run["config"]["as_run"]
    if not found or "algo.lm.moe_num_primary_experts" not in a:
        return None
    tokens = a["algo.per_rank_batch_size"] * (a["env.prompt_len"] + a["algo.rollout_steps"])
    mean = tokens * a["algo.lm.moe_num_active_primary_experts"] / a["algo.lm.moe_num_primary_experts"]
    return max(max(flat(b["counters"]["moe_max_expert_load"])) for b in found) / mean
