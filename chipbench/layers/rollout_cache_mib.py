"""What the rollout's cache holds on all devices, in MiB, from its own arrays' sizes (the block's
`rollout_cache_bytes` on its `iter` span): the latent cache of a latent-attention policy, not the expanded
keys and values. A program without the counter gives `None`."""

from layers._program_record import window_spans


def read(run):
    found = [s["counters"]["rollout_cache_bytes"] for s in window_spans(run, "iter") or [] if "rollout_cache_bytes" in s["counters"]]
    return max(found) / 2**20 if found else None
