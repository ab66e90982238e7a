"""Trim run records of the latent-attention token-PPO cell (`run.py --dump <file>`) to what
`correct/ppo_lm_mla.py:update_numbers` needs to be replayed without a chip, one line a run, appended to
`ppo_lm_mla_readings.jsonl` (tests/test_ppo_lm_mla.py replays them):

    python chipbench/tests/trim_ppo_lm_mla_records.py <origin> <tree> <dump.json> [...]

`trim_ppo_lm_records.py`'s own trimming (its docstring has the record's layout), loaded by path with this
family's file and the numbers this family's runs compared as recorded (`router_bias_change` among them).
"""

import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))

spec = importlib.util.spec_from_file_location("trim_ppo_lm_records_for_mla", os.path.join(HERE, "trim_ppo_lm_records.py"))
trimmer = importlib.util.module_from_spec(spec)
spec.loader.exec_module(trimmer)
trimmer.OUT = os.path.join(HERE, "ppo_lm_mla_readings.jsonl")
trimmer.AS_RECORDED = (*trimmer.AS_RECORDED, "router_bias_change")

if __name__ == "__main__":
    trimmer.main(*sys.argv[1:])
