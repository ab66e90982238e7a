"""Policy steps in the window over the window's seconds (host clock): all the
iterations between the opening and the closing tick, times the envs."""


def read(run):
    w = run["window"]
    return w["policy_iters"] * run["num_envs"] / w["seconds"]
