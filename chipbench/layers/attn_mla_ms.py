"""Device self time per gradient step under `lm.attn_mla` in the update: latent attention's four projections,
the latent norm, the rotary encoding, the attention over the expanded keys and values and the output product,
forward and backward. (In the rollout the region lies inside `rollout.prefill` / `rollout.decode` and counts to them.)"""

from layers._program_record import region_ms


def read(run):
    return region_ms(run, ("lm.attn_mla",))
