"""The public names of the program's own record, pinned on one tiny burst run
on the CPU (``exp=dreamer_v3`` with the hybrid host player forced on), and the
seam the on-chip benchmark's adapter stands on: ``TraceProfiler`` looked up at
call time and ticked with one positional ``int``, ``BurstRunner.flush`` that
returns the grant, ``BurstRunner._step`` whose job ends in the trained flag,
``runner._burst_fn(carry, rb, blob)`` callable from the trainer thread.

One run, made once for the module; each test reads what it left behind.
"""

import collections

import numpy as np
import pytest

import sheeprl_tpu.utils.profiler as profiler_mod
from sheeprl_tpu.utils import burst as burst_mod
from sheeprl_tpu.utils.profiler import BURST_REGIONS, SPANS

N_ENVS, TOTAL_STEPS, TRAIN_EVERY = 2, 96, 4
ARGS = [
    "exp=dreamer_v3", "env=dummy", f"env.num_envs={N_ENVS}", "env.sync_env=True", "env.capture_video=False",
    "buffer.memmap=False", "fabric.devices=1", "metric.log_level=0", "checkpoint.save_last=False",
    "algo.run_test=False", "algo=dreamer_v3_XS", "algo.per_rank_batch_size=2", "algo.horizon=4",
    "algo.dense_units=8", "algo.mlp_layers=1", "algo.world_model.encoder.cnn_channels_multiplier=2",
    "algo.world_model.recurrent_model.recurrent_state_size=16", "algo.world_model.representation_model.hidden_size=8",
    "algo.world_model.transition_model.hidden_size=8", "algo.world_model.discrete_size=4",
    "algo.world_model.stochastic_size=4", "algo.world_model.reward_model.bins=17", "algo.critic.bins=17",
    "algo.cnn_keys.encoder=[rgb]", "algo.mlp_keys.encoder=[state]", "env.screen_size=64", "dry_run=False",
    "algo.hybrid_player.enabled=true", f"algo.hybrid_player.train_every={TRAIN_EVERY}",
    "algo.hybrid_player.snapshot_every=2", f"algo.total_steps={TOTAL_STEPS}", "algo.learning_starts=32",
    "algo.per_rank_sequence_length=4", "buffer.size=2000",
]


@pytest.fixture(scope="module")
def burst_run(tmp_path_factory):
    from sheeprl_tpu.cli import run

    seen = {"ticks": [], "flushes": [], "steps": [], "probe": None}

    class StubProfiler:  # what the benchmark's adapter puts in TraceProfiler's place
        def __init__(self, cfg, log_dir):
            seen["profiler_args"] = (cfg, log_dir)

        def tick(self, *args, **kwargs):
            seen["ticks"].append((args, kwargs))

        def close(self):
            seen["closed"] = True

    orig_flush, orig_step = burst_mod.BurstRunner.flush, burst_mod.BurstRunner._step

    def flush(runner, key, grant_backlog):
        rows = len(runner._staged)
        chunk = orig_flush(runner, key, grant_backlog)
        seen["flushes"].append({"rows": rows, "chunk": chunk, "backlog": grant_backlog})
        return chunk

    def _step(runner, carry_rb, job):
        trained = bool(job[-1])
        if trained and seen["probe"] is None:
            # the adapter's first-burst reading: the same compiled program, dispatched
            # from the trainer thread with no step granted, the donated ring taken back
            carry, rb = carry_rb
            blob = job[0]
            layout = next(l for l in runner._layouts.values() if l.nbytes == blob.shape[0])
            off, shape, dtype = next((o, s, d) for n, o, s, d in layout.segments if n == "__validmask__")
            probe = blob.copy()
            probe[off : off + int(np.prod(shape)) * np.dtype(dtype).itemsize] = 0
            before = profiler_mod.programs()
            cn, rb, metrics = runner._burst_fn(carry, rb, probe)
            seen["probe"] = {"programs_before": before, "programs_after": profiler_mod.programs(),
                             "cum_before": int(carry[3]), "cum_after": int(cn[3]), "n_metrics": len(metrics)}
            carry_rb = (carry, rb)
        out = orig_step(runner, carry_rb, job)
        seen["steps"].append({"trained": trained, "job_len": len(job), "blob": type(job[0]).__name__,
                              "metrics_none": out[1] is None})
        seen["runner_attrs"] = [hasattr(runner, a) for a in ("_staged", "dev_pos", "dev_valid", "_layouts", "grad_chunk")]
        return out

    mp = pytest.MonkeyPatch()
    mp.setattr(profiler_mod, "TraceProfiler", StubProfiler)
    mp.setattr(burst_mod.BurstRunner, "flush", flush)
    mp.setattr(burst_mod.BurstRunner, "_step", _step)
    profiler_mod.reset()
    seen["programs_at_start"] = profiler_mod.programs()  # what earlier tests of this process registered
    try:
        run(ARGS + [f"log_root={tmp_path_factory.mktemp('burst_run')}/logs"])
    finally:
        mp.undo()
    seen["spans"] = profiler_mod.snapshot()["spans"]
    return seen


def _named(seen, name):
    return [s for s in seen["spans"] if s["name"] == name]


def test_every_public_span_is_recorded_and_nests_as_documented(burst_run):
    assert {s["name"] for s in burst_run["spans"]} == set(SPANS)
    by_id = {s["id"]: s for s in burst_run["spans"]}
    parents = collections.defaultdict(set)
    for s in burst_run["spans"]:
        parents[s["name"]].add(by_id[s["parent"]]["name"] if s["parent"] else None)
    assert parents["iter"] == {None} and parents["snapshot.refresh"] == {None}
    for child in ("player.adopt", "player.act", "stage", "env.step"):
        assert parents[child] == {"iter"}, child
    assert parents["burst.flush"] == {"iter", None}  # None: the tail flush after the loop
    assert parents["burst.pack"] == parents["burst.submit"] == parents["burst.dispatch"] == {"burst.flush"}
    for s in burst_run["spans"]:
        if s["parent"]:  # a child on its parent's thread lies inside it
            p = by_id[s["parent"]]
            if p["thread"] == s["thread"]:
                assert p["t_start"] <= s["t_start"] and s["t_end"] <= p["t_end"], (s["name"], p["name"])


def test_iter_spans_carry_the_loop_counters(burst_run):
    iters = _named(burst_run, "iter")
    n_iters = TOTAL_STEPS // N_ENVS
    assert [s["counters"]["iter_num"] for s in iters] == list(range(1, n_iters + 1))
    assert all(set(s["counters"]) == {"iter_num", "policy_step", "grad_steps", "grant_backlog", "staged_rows"} for s in iters)
    assert all(s["counters"]["policy_step"] == N_ENVS * s["counters"]["iter_num"] for s in iters)
    grad_steps = [s["counters"]["grad_steps"] for s in iters]
    assert grad_steps == sorted(grad_steps) and grad_steps[0] == 0 and grad_steps[-1] > 0
    assert len(_named(burst_run, "env.step")) == n_iters
    assert 0 < len(_named(burst_run, "player.act")) < n_iters  # none while the prefill acts at random
    assert len({s["thread"] for s in iters}) == 1


def test_flush_spans_carry_the_burst_counters(burst_run):
    flushes = _named(burst_run, "burst.flush")
    assert all(
        set(s["counters"]) == {"burst", "rows", "bucket", "blob_bytes", "chunk", "queue_depth"} for s in flushes
    )
    assert [s["counters"]["burst"] for s in flushes] == list(range(1, len(flushes) + 1))
    # the counters are what the wrapped flush saw and returned
    assert [(s["counters"]["rows"], s["counters"]["chunk"]) for s in flushes] == [
        (f["rows"], f["chunk"]) for f in burst_run["flushes"]
    ]
    assert all(s["counters"]["rows"] <= s["counters"]["bucket"] for s in flushes)
    assert all(s["counters"]["blob_bytes"] > s["counters"]["bucket"] * N_ENVS * 64 * 64 * 3 for s in flushes)
    assert all(0 <= s["counters"]["queue_depth"] <= 2 for s in flushes)


def test_one_dispatch_per_flush_with_the_same_burst_number(burst_run):
    flushes, dispatches = _named(burst_run, "burst.flush"), _named(burst_run, "burst.dispatch")
    assert len(dispatches) == len(flushes) >= 3
    by_burst = {s["counters"]["burst"]: s for s in flushes}
    for d in dispatches:
        f = by_burst[d["counters"]["burst"]]
        assert d["parent"] == f["id"] and d["counters"]["bucket"] == f["counters"]["bucket"]
        assert d["thread"] != f["thread"] and d["t_start"] >= f["t_start"]
        assert set(d["counters"]) == {"burst", "bucket", "program"}
    assert len({d["counters"]["burst"] for d in dispatches}) == len(dispatches)


def test_dispatched_programs_are_registered_with_their_scope_tables(burst_run):
    programs = {d["counters"]["program"] for d in _named(burst_run, "burst.dispatch")}
    assert programs and programs <= set(profiler_mod.programs())
    assert all(p.startswith("packed_burst/") for p in programs)
    table = profiler_mod.scope_table(sorted(programs)[0])
    assert {v["outer"] for v in table.values()} == set(BURST_REGIONS) | {None}
    assert any(v["scope"] == "kernel.ragged_ring_scatter" and v["outer"] == "ring.append" for v in table.values())
    assert profiler_mod.scope_table(sorted(programs)[0]) is table  # parsed once


def test_profiler_is_looked_up_at_call_time_and_ticked_with_one_positional_int(burst_run):
    n_iters = TOTAL_STEPS // N_ENVS
    assert len(burst_run["ticks"]) == n_iters and burst_run.get("closed")
    assert all(len(args) == 1 and type(args[0]) is int and not kwargs for args, kwargs in burst_run["ticks"])
    assert [args[0] for args, _ in burst_run["ticks"]] == list(range(1, n_iters + 1))
    assert isinstance(burst_run["profiler_args"][1], str)


def test_flush_returns_the_grant_and_step_sees_the_trained_flag_last(burst_run):
    flushes, steps = burst_run["flushes"], burst_run["steps"]
    assert all(type(f["chunk"]) is int and 0 <= f["chunk"] <= f["backlog"] for f in flushes)
    assert sum(f["chunk"] for f in flushes) >= _named(burst_run, "iter")[-1]["counters"]["grad_steps"] > 0
    assert len(steps) == len(flushes)
    assert [s["trained"] for s in steps] == [f["chunk"] > 0 for f in flushes]
    assert all(s["blob"] == "ndarray" for s in steps)  # job[0] is the packed blob
    assert all(s["metrics_none"] == (not s["trained"]) for s in steps)
    assert all(burst_run["runner_attrs"])


def test_burst_fn_is_callable_from_the_trainer_thread_without_a_second_compile(burst_run):
    probe = burst_run["probe"]
    assert probe is not None
    # the probe ran the bucket's one compiled program (compiling it if it came first):
    # nothing is registered that a burst.dispatch span does not name
    dispatched = {d["counters"]["program"] for d in _named(burst_run, "burst.dispatch")}
    earlier = set(burst_run["programs_at_start"])
    assert set(probe["programs_before"]) - earlier <= set(probe["programs_after"]) - earlier <= dispatched
    assert probe["cum_after"] == probe["cum_before"]  # no step granted, no step taken
    assert probe["n_metrics"] == 10
