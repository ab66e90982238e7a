"""Model FLOPs of one DreamerV3 gradient step, from the configuration's
widths. Matrix products and convolutions only (2 x multiply-adds); forward
counted once, a backward pass twice the forward where gradients flow to both
weights and inputs. What the program computes twice (the actor over the
imagined trajectory, the initial state's prior in every step of the dynamics
scan) is counted once: recomputed operations are not model FLOPs.
"""

from __future__ import annotations

from typing import Any, Dict


def dense(rows: float, fan_in: int, fan_out: int) -> float:
    return 2.0 * rows * fan_in * fan_out


def mlp(rows: float, fan_in: int, units: int, layers: int, out: int = 0) -> float:
    total, width = 0.0, fan_in
    for _ in range(layers):
        total += dense(rows, width, units)
        width = units
    if out:
        total += dense(rows, width, out)
    return total


def widths(config: Dict[str, Any]) -> Dict[str, int]:
    a = config["as_run"]
    return {
        "dense": a["algo.dense_units"],
        "layers": a["algo.mlp_layers"],
        "mult": a["algo.world_model.encoder.cnn_channels_multiplier"],
        "rec": a["algo.world_model.recurrent_model.recurrent_state_size"],
        "rec_dense": a["algo.world_model.recurrent_model.dense_units"],
        "trans_hidden": a["algo.world_model.transition_model.hidden_size"],
        "repr_hidden": a["algo.world_model.representation_model.hidden_size"],
        "stoch": a["algo.world_model.stochastic_size"] * a["algo.world_model.discrete_size"],
        "bins": a["algo.world_model.reward_model.bins"],
        "critic_bins": a["algo.critic.bins"],
        "horizon": a["algo.horizon"],
        "batch": a["algo.per_rank_batch_size"],
        "seq": a["algo.per_rank_sequence_length"],
        "screen": a["env.screen_size"],
        "actions": config["assumed"]["actions"],
        "channels": config["assumed"]["image"][2],
    }


def parts(config: Dict[str, Any], scan_bodies_once: bool = False) -> Dict[str, float]:
    """FLOPs of one gradient step by part. `scan_bodies_once` counts the body
    of each `lax.scan` a single time, which is how XLA's `cost_analysis()`
    counts a while loop: only the cross-check against XLA's number uses it."""
    w = widths(config)
    rows = w["batch"] * w["seq"]  # one row per (t, b) of the replayed batch
    stages = 4  # 64 -> 32 -> 16 -> 8 -> 4
    # encoder: stage i is a 4x4 stride-2 conv to (2**i) * mult channels; the
    # pixels need no gradient, so the first conv's backward is weights only
    enc_fwd, enc_all, c_in, side = 0.0, 0.0, w["channels"], w["screen"]
    for i in range(stages):
        side //= 2
        c_out = (2**i) * w["mult"]
        f = 2.0 * rows * side * side * 16 * c_in * c_out
        enc_fwd += f
        enc_all += (2.0 if i == 0 else 3.0) * f
        c_in = c_out
    embed = c_in * side * side
    latent = w["stoch"] + w["rec"]
    # decoder: dense to 4x4x(8 mult), then 4x4 stride-2 transposed convs
    dec = dense(rows, latent, embed)
    c_in, side = 8 * w["mult"], 4
    for c_out in (4 * w["mult"], 2 * w["mult"], w["mult"], w["channels"]):
        dec += 2.0 * rows * side * side * 16 * c_in * c_out  # each input pixel meets the whole 4x4 kernel
        c_in, side = c_out, side * 2
    recurrent = dense(1, w["stoch"] + w["actions"], w["rec_dense"]) + dense(1, w["rec"] + w["rec_dense"], 3 * w["rec"])
    transition = dense(1, w["rec"], w["trans_hidden"]) + dense(1, w["trans_hidden"], w["stoch"])
    representation = dense(1, w["rec"] + embed, w["repr_hidden"]) + dense(1, w["repr_hidden"], w["stoch"])
    seq_trips = 1 if scan_bodies_once else w["seq"]
    rssm = w["batch"] * seq_trips * (recurrent + transition + representation)
    reward = mlp(1, latent, w["dense"], w["layers"], w["bins"])
    cont = mlp(1, latent, w["dense"], w["layers"], 1)
    actor = mlp(1, latent, w["dense"], w["layers"], w["actions"])
    critic = mlp(1, latent, w["dense"], w["layers"], w["critic_bins"])
    first_layer = dense(1, latent, w["dense"])  # its input gradient is not needed where the input is stop-gradient
    world = enc_all + 3.0 * (rssm + dec + rows * (reward + cont))
    # behaviour: every (t, b) posterior starts a horizon-long imagined rollout
    h = w["horizon"]
    imagine_fwd = rows * (1 if scan_bodies_once else h) * (recurrent + transition)
    traj = rows * (h + 1)
    heads_fwd = traj * (critic + reward + cont)  # values, rewards, continues: no gradient through them here
    actor_all = traj * (3.0 * actor - first_layer)
    critic_all = rows * h * (3.0 * critic - first_layer) + rows * h * critic  # + the target critic's forward
    return {"world_model": world, "imagination": imagine_fwd + heads_fwd, "actor": actor_all, "critic": critic_all}


def flops_per_grad_step(config: Dict[str, Any]) -> float:
    return float(sum(parts(config).values()))


def flops_as_xla_counts(config: Dict[str, Any]) -> float:
    """With scan bodies counted once: what `compiled.cost_analysis()["flops"]`
    of one gradient step should come near (673 GFLOP for size S, ISSUE 25)."""
    return float(sum(parts(config, scan_bodies_once=True).values()))
