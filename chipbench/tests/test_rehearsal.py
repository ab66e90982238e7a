"""`run.py` end to end on the CPU at a tiny size, with the device demand
skipped: opens and closes a window, prints a well-formed last line that says
it is a rehearsal and carries no device metric, and the reference agrees with
the program on seeded weights. Then the same run with the timed path broken
underneath, once for each fault the cells can have: `correct` comes out false.
"""

import pytest

from conftest import rehearse

CELL = "dreamer_S_atari100k"


def test_rehearsal_line_and_reference_agreement(bench):
    proc, line = rehearse(CELL)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device", "compared"}
    assert list(line)[-1] == "compared"
    wanted = {m["name"] for m in bench["end_to_end"]}
    assert set(line["metrics"]) == wanted
    assert all(v["value"] is None for v in line["metrics"].values())  # no device number from a CPU run
    assert line["attempted"] > 0 and line["run"]["bursts"] >= 1
    assert line["run"]["policy_steps"] == line["attempted"]  # replay ratio 1, whole bursts
    # float32 on both sides here: the program's numbers sit on the reference's
    c = line["compared"]
    assert c["ring_heads_mismatch"]["value"] == 0
    assert c["loss_world_model"]["value"] < 1e-5 and c["loss_critic"]["value"] < 1e-5
    assert c["loss_actor"]["value"] < 1e-3
    assert c["grad_norm_world_model"]["value"] < 1e-4 and c["param_change_world_model"]["value"] < 1e-2
    assert "compared loss_world_model" in proc.stderr and proc.stderr.rstrip().endswith(("True", "False"))


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_broken_timed_path_is_not_correct(fault):
    proc, line = rehearse(CELL, "--fault", fault)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is False
    c = line["compared"]
    if fault == "state_unchanged":  # nothing moved: the change reads 1 by the measure
        assert c["param_change_world_model"]["value"] > 0.9
    else:
        assert c["grad_norm_world_model"]["value"] > 0.05


def test_refuses_without_a_tpu():
    import os
    import subprocess
    import sys

    from conftest import ROOT

    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"), "--workload", CELL, "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT, timeout=300)
    assert proc.returncode != 0 and not proc.stdout.strip().startswith("{")
