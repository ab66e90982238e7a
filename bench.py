#!/usr/bin/env python
"""Benchmark harness: one lane per measured topology, one JSON line out.

Mirrors the reference benchmark conditions for the default lane
(``sheeprl/configs/exp/ppo_benchmarks.yaml``: 65536 total steps, 1 env, sync,
logging/checkpoints off; reference wall-clock 81.27 s on 4 CPUs → ~806
env-steps/s, see BASELINE.md).

``BENCH_METRIC`` selects the lane from the registry below (default ``host``
so the recorded trajectory stays comparable). Adding a lane = one
``@lane(...)``-decorated runner — the selection error message and the CI
matrix read the registry, nothing is hand-enumerated:

- ``host`` — ``ppo_cartpole_env_steps_per_sec``: the host-loop PPO
  (``exp=ppo_benchmarks``), one jitted policy dispatch per env step;
- ``ondevice`` — the Anakin path (``exp=ppo_anakin_benchmarks``) with the
  rollout fused in-graph (howto/on_device_rollout.md);
- ``sebulba`` — the decoupled actor/learner pipeline
  (``exp=ppo_sebulba_benchmarks``, howto/decoupled_training.md);
- ``replay`` — SAC grad-steps/s through the replay data path
  (``exp=sac_replay_benchmarks``; ``BENCH_REPLAY_MODE=device|host`` pairs
  the device-resident ring against host sampling, howto/device_replay.md);
- ``sac_sebulba`` — the async off-policy pipeline vs its coupled twin at an
  identical recipe (``BENCH_SAC_MODE=async|coupled``,
  howto/async_offpolicy.md);
- ``dreamer_sebulba`` — async DreamerV3 over the ragged per-env-head device
  sequence ring vs the coupled host loop at an identical recipe
  (``BENCH_DREAMER_MODE=sebulba|coupled``, howto/async_offpolicy.md);
- ``serve`` — the continuous-batching inference tier: p50/p99 latency +
  throughput at fixed offered loads, AOT bucketed engine
  (``BENCH_SERVE_MODE=aot``) vs naive per-request jit dispatch (``naive``),
  one hot weight swap per load (howto/serving.md; benchmarks/serve_bench.py);
- ``serve_fleet`` — replicated serving: N replica processes behind the
  FleetRouter vs a single replica on identical offered load, one replica
  SIGKILL per fleet rep, ``dropped == 0`` asserted in-lane
  (howto/serving.md; benchmarks/serve_fleet_bench.py);
- ``population`` — P-member population training on the Anakin path:
  ``BENCH_POP_MODE=vmapped`` trains all P members in ONE jitted dispatch
  (``exp=ppo_anakin_population_benchmarks``) vs ``sequential`` = P
  back-to-back ``ppo_anakin_benchmarks`` runs at the matched recipe;
  reports aggregate env-steps/s and the fused-block compile count
  (howto/population_training.md);
- ``scenario_matrix`` — the scenario axis of the population block:
  ``BENCH_SCENARIO_MODE=vmapped`` trains P CartPole pole-length variants in
  ONE dispatch (``algo.population.env_params``) vs ``sequential`` = P
  single-scenario size-1 runs at identical seeds/steps; reports aggregate
  env-steps/s, the block compile count from the tracecheck ledger (1 vs
  >= P) and the per-scenario fitness spread read back from the final
  checkpoints (howto/population_training.md);
- ``env_zoo`` — raw vmapped ``BatchedJaxEnv.step`` throughput per
  registered pure-JAX env at a fixed batch ladder (no agent, no learning:
  the env-side budget an Anakin rollout spends per step);
- ``kernels`` — the Pallas kernel tier microbench: every kernel in the
  ``ops.kernels`` registry timed pallas-vs-lax on identical inputs at 2-3
  call-site shapes (``BENCH_KERNEL=<name>|all``,
  ``BENCH_KERNEL_BACKEND=pallas|lax|both``; interpret-mode caveat in the
  payload, howto/kernels.md; benchmarks/kernel_bench.py);
- ``pod_restart`` — gang-restart MTTR of the fault-tolerant pod: real
  2-process pods with one seeded ``kill-host`` per rep, MTTR = SIGKILL ->
  first post-restart completed train iteration, every rep must converge to
  its configured ``total_steps`` (howto/fault_tolerance.md#pod-training;
  benchmarks/pod_bench.py).
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Callable, Dict, List

BASELINE_STEPS_PER_SEC = 65536 / 81.27  # reference PPO benchmark (README.md:100-117)

#: lane name -> {"runner": fn, "aliases": (...)}; populated by @lane
LANES: Dict[str, Dict[str, object]] = {}


def lane(name: str, *aliases: str) -> Callable:
    """Register a bench lane under ``name`` (+ aliases, e.g. the metric id)."""

    def decorator(fn: Callable[[], None]) -> Callable[[], None]:
        LANES[name] = {"runner": fn, "aliases": (name, *aliases)}
        return fn

    return decorator


def resolve_lane(which: str) -> Callable[[], None]:
    for entry in LANES.values():
        if which in entry["aliases"]:
            return entry["runner"]  # type: ignore[return-value]
    raise SystemExit(f"Unknown BENCH_METRIC '{which}' (expected one of {sorted(LANES)})")


def _env_steps(default_steps: int) -> int:
    return int(os.environ.get("BENCH_TOTAL_STEPS", default_steps))


def _run_cli(exp: str, total_steps: int, extra: List[str] = (), keep_timer: bool = False) -> float:
    """Run one training CLI invocation under the shared bench conditions;
    returns the elapsed wall-clock seconds."""
    overrides = [
        f"exp={exp}",
        f"algo.total_steps={total_steps}",
        "env.capture_video=False",
        "buffer.memmap=False",
        "checkpoint.save_last=False",
        "metric.log_level=0",
        # keep_timer: the Time/* instrumentation stays alive so per-segment
        # seconds are readable after a log_level=0 run
        f"metric.disable_timer={'False' if keep_timer else 'True'}",
        *extra,
    ]
    from sheeprl_tpu.cli import run

    start = time.perf_counter()
    run(overrides)
    return time.perf_counter() - start


@lane("host", "", "default", "ppo_cartpole_env_steps_per_sec")
def _lane_host() -> None:
    total_steps = _env_steps(65536)
    elapsed = _run_cli("ppo_benchmarks", total_steps)
    steps_per_sec = total_steps / elapsed
    print(
        json.dumps(
            {
                "metric": "ppo_cartpole_env_steps_per_sec",
                "value": round(steps_per_sec, 2),
                "unit": "env-steps/s",
                "vs_baseline": round(steps_per_sec / BASELINE_STEPS_PER_SEC, 3),
            }
        )
    )


@lane("ondevice", "anakin", "ppo_cartpole_ondevice_env_steps_per_sec")
def _lane_ondevice() -> None:
    # The fused path retires 65536 steps in ~3s of loop time: at the host
    # metric's step count the measurement is interpreter/compile-bound, not
    # framework-bound. 16x the steps keeps the whole-wall convention while
    # the training loop dominates (still well under a minute).
    total_steps = _env_steps(1048576)
    elapsed = _run_cli("ppo_anakin_benchmarks", total_steps)
    steps_per_sec = total_steps / elapsed
    print(
        json.dumps(
            {
                "metric": "ppo_cartpole_ondevice_env_steps_per_sec",
                "value": round(steps_per_sec, 2),
                "unit": "env-steps/s",
                "vs_baseline": round(steps_per_sec / BASELINE_STEPS_PER_SEC, 3),
            }
        )
    )


@lane("sebulba", "ppo_cartpole_sebulba_env_steps_per_sec")
def _lane_sebulba() -> None:
    total_steps = _env_steps(65536)
    elapsed = _run_cli("ppo_sebulba_benchmarks", total_steps)
    steps_per_sec = total_steps / elapsed
    print(
        json.dumps(
            {
                "metric": "ppo_cartpole_sebulba_env_steps_per_sec",
                "value": round(steps_per_sec, 2),
                "unit": "env-steps/s",
                "vs_baseline": round(steps_per_sec / BASELINE_STEPS_PER_SEC, 3),
            }
        )
    )


@lane("replay", "sac_pendulum_replay_grad_steps_per_sec")
def _lane_replay() -> None:
    replay_mode = os.environ.get("BENCH_REPLAY_MODE", "device").strip().lower()
    if replay_mode not in ("device", "host"):
        raise SystemExit(f"Unknown BENCH_REPLAY_MODE '{replay_mode}' (expected 'device' or 'host')")
    total_steps = _env_steps(8192)
    exp = "sac_replay_benchmarks"
    elapsed = _run_cli(
        exp,
        total_steps,
        extra=[f"buffer.device_resident={'true' if replay_mode == 'device' else 'false'}"],
        keep_timer=True,
    )
    # Both modes execute the identical grant schedule (same Ratio, same
    # seeds), so per-mode throughput is directly comparable. Two views:
    # - end-to-end grad-steps/s (whole wall): on a CPU-only host the two
    #   modes tie — the gradient math dominates and there is no device
    #   boundary to cross;
    # - grad-steps per second of REPLAY-PATH time: the serialized host-side
    #   sample+stage segment each gradient step waits on — numpy sampling +
    #   device staging for the host tier vs one packed blob for the resident
    #   tier. This is exactly the host-in-the-loop cost the subsystem
    #   removes, so it is the headline `value`.
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.utils.timer import timer as _timer

    cfg = compose([f"exp={exp}", f"algo.total_steps={total_steps}"])
    grad_steps = max(1, int(cfg.algo.replay_ratio * (total_steps - cfg.algo.learning_starts)))
    replay_path_s = _timer.compute().get("Time/replay_path_time", 0.0)
    value = grad_steps / replay_path_s if replay_path_s > 0 else 0.0
    print(
        json.dumps(
            {
                "metric": "sac_pendulum_replay_grad_steps_per_sec",
                "value": round(value, 2),
                "unit": "grad-steps per replay-path second",
                "mode": replay_mode,
                "grad_steps": grad_steps,
                "replay_path_s": round(replay_path_s, 3),
                "end_to_end_grad_steps_per_sec": round(grad_steps / elapsed, 2),
                "elapsed_s": round(elapsed, 2),
                # no vs_baseline: the PPO reference bar is env-steps/s —
                # dividing grad-steps/s by it would be a unit mismatch
            }
        )
    )


@lane("sac_sebulba", "sac_async", "sac_pendulum_sebulba_env_steps_per_sec")
def _lane_sac_sebulba() -> None:
    sac_mode = os.environ.get("BENCH_SAC_MODE", "async").strip().lower()
    if sac_mode not in ("async", "coupled"):
        raise SystemExit(f"Unknown BENCH_SAC_MODE '{sac_mode}' (expected 'async' or 'coupled')")
    # the coupled twin is a dedicated exp with the IDENTICAL recipe (model,
    # batch, replay ratio, env) so the ONLY difference between the two runs
    # is the topology
    exp = "sac_sebulba_benchmarks" if sac_mode == "async" else "sac_async_coupled_benchmarks"
    total_steps = _env_steps(8192)
    elapsed = _run_cli(exp, total_steps, keep_timer=True)
    # Both modes consume the identical grant schedule, so env-steps/s is
    # directly comparable. The replay-path seconds show WHERE the time went:
    # coupled = the serialized host sample+stage segment on the env-step
    # critical path; async = just the learner's append dispatch (packing +
    # transfer ride the actor threads).
    from sheeprl_tpu.utils.timer import timer as _timer

    timers = _timer.compute()
    print(
        json.dumps(
            {
                "metric": "sac_pendulum_sebulba_env_steps_per_sec",
                "value": round(total_steps / elapsed, 2),
                "unit": "env-steps/s",
                "mode": sac_mode,
                "elapsed_s": round(elapsed, 2),
                "replay_path_s": round(timers.get("Time/replay_path_time", 0.0), 3),
                "train_s": round(timers.get("Time/train_time", 0.0), 3),
                "env_interaction_s": round(timers.get("Time/env_interaction_time", 0.0), 3),
                # no vs_baseline: the PPO reference bar is a different
                # algorithm's env rate
            }
        )
    )


@lane("dreamer_sebulba", "dreamer_async", "dreamer_dummy_sebulba_env_steps_per_sec")
def _lane_dreamer_sebulba() -> None:
    dreamer_mode = os.environ.get("BENCH_DREAMER_MODE", "sebulba").strip().lower()
    if dreamer_mode not in ("sebulba", "coupled"):
        raise SystemExit(f"Unknown BENCH_DREAMER_MODE '{dreamer_mode}' (expected 'sebulba' or 'coupled')")
    # the coupled twin is a dedicated exp with the IDENTICAL recipe (model,
    # batch, sequence length, replay ratio, env) so the ONLY difference
    # between the two runs is the topology
    exp = "dreamer_sebulba_benchmarks" if dreamer_mode == "sebulba" else "dreamer_coupled_benchmarks"
    total_steps = _env_steps(4096)
    elapsed = _run_cli(exp, total_steps, keep_timer=True)
    # Both modes consume the identical grant schedule, so env-steps/s is
    # directly comparable. The per-segment seconds show WHERE the time went:
    # coupled = env + player inference + host window sampling + train, all
    # serialized per env step; sebulba = the learner's append + train only
    # (env/player/packing/transfer ride the actor threads).
    from sheeprl_tpu.utils.timer import timer as _timer

    timers = _timer.compute()
    print(
        json.dumps(
            {
                "metric": "dreamer_dummy_sebulba_env_steps_per_sec",
                "value": round(total_steps / elapsed, 2),
                "unit": "env-steps/s",
                "mode": dreamer_mode,
                "elapsed_s": round(elapsed, 2),
                "replay_path_s": round(timers.get("Time/replay_path_time", 0.0), 3),
                "train_s": round(timers.get("Time/train_time", 0.0), 3),
                "env_interaction_s": round(timers.get("Time/env_interaction_time", 0.0), 3),
                # no vs_baseline: the PPO reference bar is a different
                # algorithm's env rate
            }
        )
    )


@lane("population", "ppo_cartpole_population_env_steps_per_sec")
def _lane_population() -> None:
    pop_mode = os.environ.get("BENCH_POP_MODE", "vmapped").strip().lower()
    if pop_mode not in ("vmapped", "sequential"):
        raise SystemExit(f"Unknown BENCH_POP_MODE '{pop_mode}' (expected 'vmapped' or 'sequential')")
    pop_size = int(os.environ.get("BENCH_POP_SIZE", 8))
    # per-member steps, identical to the single-run ondevice recipe so the
    # pairing measures the topology (one dispatch vs P) and nothing else
    total_steps = _env_steps(65536)

    from sheeprl_tpu.analysis.tracecheck import tracecheck

    tracecheck.reset()
    if pop_mode == "vmapped":
        # seed-only population (hparams={} in the exp): every member runs the
        # EXACT recipe the sequential baseline runs
        elapsed = _run_cli(
            "ppo_anakin_population_benchmarks",
            total_steps,
            # hparams override: the exp's seed-only intent must survive the
            # algo default's lr grid through deep-merge at any BENCH_POP_SIZE
            extra=[f"algo.population.size={pop_size}", "algo.population.hparams={}"],
        )
        block_name = "ppo_anakin_pop.block"
    else:
        elapsed = 0.0
        for member in range(pop_size):
            elapsed += _run_cli("ppo_anakin_benchmarks", total_steps, extra=[f"seed={42 + member}"])
        block_name = "ppo_anakin.block"
    # compile counts come from the tracecheck dump payload — the SAME
    # artifact CI/`analysis tracecheck` read — not from scraping run logs
    ledger = tracecheck.dump(os.environ.get("BENCH_TRACECHECK_DUMP") or None)
    block = ledger["entries"].get(block_name, {})
    aggregate_steps = pop_size * total_steps
    # per-member rate = each member's own training rate: the vmapped members
    # share the whole wall-clock, a sequential member only its elapsed/P slice
    member_elapsed = elapsed if pop_mode == "vmapped" else elapsed / pop_size
    print(
        json.dumps(
            {
                "metric": "ppo_cartpole_population_env_steps_per_sec",
                "value": round(aggregate_steps / elapsed, 2),
                "unit": "aggregate env-steps/s",
                "mode": pop_mode,
                "population_size": pop_size,
                "per_member_env_steps_per_sec": round(total_steps / member_elapsed, 2),
                "block_compiles": int(block.get("compiles", 0)),
                "block_calls": int(block.get("calls", 0)),
                "elapsed_s": round(elapsed, 2),
                "vs_baseline": round((aggregate_steps / elapsed) / BASELINE_STEPS_PER_SEC, 3),
            }
        )
    )


@lane("scenario_matrix", "ppo_cartpole_scenario_matrix_env_steps_per_sec")
def _lane_scenario_matrix() -> None:
    scenario_mode = os.environ.get("BENCH_SCENARIO_MODE", "vmapped").strip().lower()
    if scenario_mode not in ("vmapped", "sequential"):
        raise SystemExit(
            f"Unknown BENCH_SCENARIO_MODE '{scenario_mode}' (expected 'vmapped' or 'sequential')"
        )
    pop_size = int(os.environ.get("BENCH_SCENARIO_SIZE", 8))
    # per-scenario steps, identical to the single-run ondevice recipe so the
    # pairing measures the topology (one dispatch vs P) and nothing else
    total_steps = _env_steps(65536)
    # the scenario ladder: P CartPole pole half-lengths spanning 0.25..1.0
    # (default 0.5) — genuinely different dynamics, same spaces/shapes
    lengths = [round(0.25 + i * 0.75 / max(1, pop_size - 1), 4) for i in range(pop_size)]

    import tempfile

    from sheeprl_tpu.analysis.tracecheck import tracecheck
    from sheeprl_tpu.fault.manager import find_latest_run_checkpoint
    from sheeprl_tpu.utils.checkpoint import load_state

    log_root = os.environ.get("BENCH_SCENARIO_LOG_ROOT") or tempfile.mkdtemp(prefix="scenario_bench_")

    def _fitness_of(run_root: str) -> List[float]:
        state = load_state(
            find_latest_run_checkpoint(os.path.join(run_root, "ppo_anakin_population", "CartPole-v1"))
        )
        return [round(float(v), 3) for v in state["fitness"]]

    tracecheck.reset()
    block_name = "ppo_anakin_pop.block"
    fitness: List[float] = []
    if scenario_mode == "vmapped":
        # seed-only hparams: every scenario trains the EXACT recipe the
        # sequential baseline runs; the env_params grid is the ONE axis
        ladder = "[" + ", ".join(str(v) for v in lengths) + "]"
        elapsed = _run_cli(
            "ppo_anakin_population_benchmarks",
            total_steps,
            extra=[
                f"algo.population.size={pop_size}",
                "algo.population.hparams={}",
                f"algo.population.env_params={{length: {ladder}}}",
                "seed=42",
                # save_last back on (the shared bench conditions disable it):
                # the per-scenario fitness is read from the final checkpoint
                "checkpoint.save_last=True",
                f"log_root={log_root}/vmapped",
            ],
        )
        fitness = _fitness_of(f"{log_root}/vmapped")
    else:
        elapsed = 0.0
        for i, length in enumerate(lengths):
            elapsed += _run_cli(
                "ppo_anakin_population_benchmarks",
                total_steps,
                extra=[
                    "algo.population.size=1",
                    "algo.population.hparams={}",
                    f"algo.population.env_params={{length: [{length}]}}",
                    "seed=42",
                    "checkpoint.save_last=True",
                    f"log_root={log_root}/seq_{i}",
                ],
            )
            fitness += _fitness_of(f"{log_root}/seq_{i}")
    # compile counts come from the tracecheck dump payload — the SAME
    # artifact CI/`analysis tracecheck` read — not from scraping run logs
    ledger = tracecheck.dump(os.environ.get("BENCH_TRACECHECK_DUMP") or None)
    block = ledger["entries"].get(block_name, {})
    aggregate_steps = pop_size * total_steps
    member_elapsed = elapsed if scenario_mode == "vmapped" else elapsed / pop_size
    print(
        json.dumps(
            {
                "metric": "ppo_cartpole_scenario_matrix_env_steps_per_sec",
                "value": round(aggregate_steps / elapsed, 2),
                "unit": "aggregate env-steps/s",
                "mode": scenario_mode,
                "population_size": pop_size,
                "scenario_lengths": lengths,
                "per_scenario_fitness": fitness,
                "fitness_spread": round(max(fitness) - min(fitness), 3) if fitness else None,
                # CartPole pays +1 per env-step under every pole length, so the
                # block fitness (rollout raw-reward mean) is structurally
                # rollout_steps for EVERY scenario: spread 0.0 is the
                # hand-computable expectation here and doubles as a ferry
                # check; cost-shaped envs (Pendulum g sweeps) show real spread
                "fitness_note": "CartPole raw-reward fitness == rollout_steps by construction",
                "per_member_env_steps_per_sec": round(total_steps / member_elapsed, 2),
                "block_compiles": int(block.get("compiles", 0)),
                "block_calls": int(block.get("calls", 0)),
                "elapsed_s": round(elapsed, 2),
                "vs_baseline": round((aggregate_steps / elapsed) / BASELINE_STEPS_PER_SEC, 3),
            }
        )
    )


@lane("env_zoo", "jax_env_zoo_env_steps_per_sec")
def _lane_env_zoo() -> None:
    # Raw env-side throughput: a jitted lax.scan of vmapped BatchedJaxEnv.step
    # (auto-reset included, traced default params, no agent in the loop) per
    # registered env across a batch ladder. This bounds what any Anakin
    # rollout can spend on env physics; compare against Sample Factory's
    # ~100k FPS full-training bar (arXiv 2006.11751) to see how far pure-JAX
    # env stepping is from being the bottleneck.
    import gymnasium as gym
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.envs.jax_envs import JAX_ENV_REGISTRY, BatchedJaxEnv, make_jax_env

    batches = [int(b) for b in os.environ.get("BENCH_ZOO_BATCHES", "128,1024,4096").split(",")]
    scan_len = int(os.environ.get("BENCH_ZOO_STEPS", 256))
    reps = int(os.environ.get("BENCH_ZOO_REPS", 3))

    per_env: Dict[str, Dict[str, float]] = {}
    for env_id in sorted(JAX_ENV_REGISTRY):
        env = make_jax_env(env_id)
        params = env.default_params()
        rates: Dict[str, float] = {}
        for batch in batches:
            benv = BatchedJaxEnv(env, batch)
            if isinstance(env.action_space, gym.spaces.Box):
                acts = jnp.zeros((batch, *env.action_space.shape), jnp.float32)
            else:
                acts = jnp.zeros((batch,), jnp.int32)

            def _rollout(state, _benv=benv, _acts=acts, _params=params):
                def _body(s, _):
                    s2, _, rew, _, _ = _benv.step(s, _acts, _params)
                    return s2, rew

                s, rews = jax.lax.scan(_body, state, None, length=scan_len)
                return s, rews.sum()

            roll = jax.jit(_rollout)
            state, _ = jax.jit(benv.reset)(jax.random.PRNGKey(0), params)
            state, warm = roll(state)  # compile outside the timed window
            warm.block_until_ready()
            start = time.perf_counter()
            for _ in range(reps):
                state, out = roll(state)
            out.block_until_ready()
            dt = time.perf_counter() - start
            rates[str(batch)] = round(batch * scan_len * reps / dt, 1)
        per_env[env_id] = rates
    top_batch = str(max(batches))
    print(
        json.dumps(
            {
                "metric": "jax_env_zoo_env_steps_per_sec",
                # headline: the SLOWEST registered env at the top of the
                # ladder — the conservative env-side budget
                "value": min(r[top_batch] for r in per_env.values()),
                "unit": "raw env-steps/s",
                "batch_ladder": batches,
                "scan_len": scan_len,
                "per_env": per_env,
                "note": (
                    "raw vmapped BatchedJaxEnv.step (auto-reset on, traced default params, no "
                    "agent); Sample Factory's ~100k-FPS bar (arXiv 2006.11751) is full training "
                    "throughput — these rates bound the env-physics share of an Anakin rollout"
                ),
            }
        )
    )


@lane("serve", "serve_policy_inference", "ppo_cartpole_serve_requests_per_sec")
def _lane_serve() -> None:
    # Offered-load latency/throughput SLO lane for the inference tier; all
    # knobs (BENCH_SERVE_MODE / _LOADS / _DURATION / _CLIENTS) documented in
    # benchmarks/serve_bench.py, results interpretation in howto/serving.md.
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmarks"))
    from serve_bench import main as serve_main

    serve_main()


@lane("serve_fleet", "fleet", "serve_fleet_requests_per_sec")
def _lane_serve_fleet() -> None:
    # Replicated-serving SLO lane: fleet (N=BENCH_FLEET_REPLICAS replica
    # PROCESSES behind the FleetRouter) vs single replica behind the same
    # router on identical offered load, with one replica SIGKILL per fleet
    # rep and dropped == 0 / errors == 0 asserted in-lane. Knobs in
    # benchmarks/serve_fleet_bench.py, interpretation in howto/serving.md.
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmarks"))
    from serve_fleet_bench import main as fleet_main

    fleet_main()


@lane("serve_flywheel", "flywheel", "serve_flywheel_rows_ingested_per_sec")
def _lane_serve_flywheel() -> None:
    # Production-loop SLO lane: closed-loop feedback clients against the
    # full flywheel topology (SAC server + spool transport + the REAL
    # `run --from-serve` learner subprocess under its supervisor), paired
    # learner-off vs learner-on phases on identical traffic, with
    # dropped == 0 / errors == 0 / rows_shed == 0 and nonzero learner ingest
    # asserted in-lane. Knobs (BENCH_FLYWHEEL_DURATION / _CLIENTS / _CKPT)
    # in benchmarks/serve_flywheel_bench.py, interpretation in
    # howto/serving.md#the-flywheel.
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmarks"))
    from serve_flywheel_bench import main as flywheel_main

    flywheel_main()


@lane("pod_restart", "pod", "pod_restart_mttr_s")
def _lane_pod_restart() -> None:
    # Gang-restart MTTR lane: real 2-process pods through the CLI with one
    # seeded kill-host injection per rep; MTTR = SIGKILL -> first
    # post-restart completed train iteration, and every rep must FINISH at
    # its configured total_steps (recovery that converges, not just
    # respawns). Knobs (BENCH_POD_WORKERS / _REPS / _KILL_AT / _TOTAL_STEPS
    # / _TIMEOUT) in benchmarks/pod_bench.py, interpretation in
    # howto/fault_tolerance.md#pod-training.
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmarks"))
    from pod_bench import main as pod_main

    pod_main()


@lane("kernels", "kernel", "kernel_tier_lax_over_pallas_median")
def _lane_kernels() -> None:
    # Pallas kernel tier microbench: every registered kernel timed through
    # its dispatch wrapper at 2-3 call-site shapes, pallas vs lax paired on
    # identical inputs (BENCH_KERNEL / BENCH_KERNEL_BACKEND / _REPS / _OUT in
    # benchmarks/kernel_bench.py). On a TPU-less host the pallas column is
    # interpret mode — a correctness vehicle, not a performance claim; see
    # the lane's in-payload note and howto/kernels.md.
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmarks"))
    from kernel_bench import main as kernel_main

    kernel_main()


@lane("serve_sessions", "sessions", "ppo_recurrent_serve_session_steps_per_sec")
def _lane_serve_sessions() -> None:
    # Stateful-session SLO lane: K closed-loop session clients against the
    # graft-sessions tier; BENCH_SESSIONS_MODE=batched|naive pairs the bucket
    # ladder against per-session dispatch on identical traffic. Knobs in
    # benchmarks/serve_sessions_bench.py, interpretation in howto/serving.md.
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmarks"))
    from serve_sessions_bench import main as sessions_main

    sessions_main()


def main() -> None:
    from sheeprl_tpu.utils.utils import enable_compile_cache, pin_cpu_platform

    # The reference PPO benchmark conditions are CPU (`fabric.accelerator:
    # cpu`), and every lane here is a CPU lane: pin the platform so this
    # process leaves the chip, where there is one, to whoever measures on it.
    pin_cpu_platform("cpu")
    # The PPO train/rollout programs cost ~15s to compile; the persistent
    # cache keeps that out of repeated invocations.
    enable_compile_cache()

    which = os.environ.get("BENCH_METRIC", "host").strip().lower()
    resolve_lane(which)()


if __name__ == "__main__":
    main()
