#!/usr/bin/env python
"""Print the observation space an agent will see for a given config
(reference: ``examples/observation_space.py``).

The observation space depends on the env backend AND the agent's
cnn/mlp key selection (the factory wraps, resizes and dict-ifies
accordingly), so this composes the REAL config and builds the REAL env:

    python examples/observation_space.py exp=ppo env=dummy env.id=discrete_dummy
    python examples/observation_space.py exp=dreamer_v3 env=atari_dummy
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(args) -> None:
    import jax

    # examples always run on the host CPU and leave a chip to whoever trains on it
    jax.config.update("jax_platforms", "cpu")

    from sheeprl_tpu.config import compose
    from sheeprl_tpu.envs.factory import make_env

    cfg = compose(list(args))
    cfg.env.capture_video = False
    env = make_env(cfg, cfg.seed, 0)()
    print()
    print(f"Observation space of `{cfg.env.id}` environment for `{cfg.algo.name}` agent:")
    print(env.observation_space)
    print()
    print(f"Action space: {env.action_space}")
    env.close()


if __name__ == "__main__":
    main(sys.argv[1:])
