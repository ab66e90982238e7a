"""Command-line dispatch (reference: ``sheeprl/cli.py:23-449``).

Verbs mirror the reference console scripts:

- ``sheeprl_tpu run exp=ppo ...`` (or just ``sheeprl_tpu exp=ppo``) — train;
- ``sheeprl_tpu eval checkpoint_path=...`` — evaluate a checkpoint;
- ``sheeprl_tpu serve checkpoint_path=...`` — serve a checkpoint behind the
  continuous-batching inference tier (howto/serving.md);
- ``sheeprl_tpu serve --fleet N ...`` / ``sheeprl_tpu serve_fleet ...`` —
  serve from N supervised replica processes behind the FleetRouter front
  end (howto/serving.md#the-serve-fleet);
- ``sheeprl_tpu agents`` — list registered algorithms;
- ``sheeprl_tpu registration ...`` — MLflow model registration (optional dep).

Arguments are hydra-style ``key=value`` tokens handled by
:func:`sheeprl_tpu.config.compose`.
"""

from __future__ import annotations

import importlib
import os
import pathlib
import sys
import warnings
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

from sheeprl_tpu.config import ConfigError, DotDict, compose, dotdict, load_yaml
from sheeprl_tpu.utils.registry import (
    algorithm_registry,
    evaluation_registry,
    get_entrypoint,
    resolve_algorithm,
    resolve_evaluation,
)

__all__ = [
    "run",
    "evaluation",
    "serve",
    "serve_fleet",
    "registration",
    "available_agents",
    "main",
    "run_algorithm",
    "eval_algorithm",
    "serve_algorithm",
    "find_run_config",
]


def find_run_config(checkpoint_path: "str | Path") -> Path:
    """Locate the ``config.yaml`` of the run that wrote ``checkpoint_path``.

    The canonical layout puts the checkpoint at
    ``<run_dir>/checkpoint/ckpt_*.ckpt`` with the config at
    ``<run_dir>/config.yaml`` — but checkpoints get copied around, and the
    old ``checkpoint_path.parent.parent / "config.yaml"`` guess died with a
    raw open failure. Discovery order:

    1. the canonical ``parent.parent / config.yaml``;
    2. the checkpoint-manifest anchor: if an ancestor directory holds the
       fault-runtime ``manifest.json``, that directory is the run's
       ``checkpoint/`` dir, so its parent's ``config.yaml`` is the run
       config;
    3. walking upward from the checkpoint: the nearest ancestor (up to the
       filesystem root) with a ``config.yaml``.

    Raises a typed :class:`~sheeprl_tpu.utils.checkpoint.CheckpointError`
    naming the checkpoint and every path searched when nothing is found.
    """
    from sheeprl_tpu.fault.manager import MANIFEST_NAME
    from sheeprl_tpu.utils.checkpoint import CheckpointError

    ckpt = Path(checkpoint_path)
    candidates: List[Path] = [ckpt.parent.parent / "config.yaml"]
    for anc in ckpt.parents:
        if (anc / MANIFEST_NAME).is_file():
            candidates.append(anc.parent / "config.yaml")
    candidates.extend(anc / "config.yaml" for anc in ckpt.parents)
    searched: List[Path] = []
    for cand in candidates:
        if cand in searched:
            continue
        searched.append(cand)
        if cand.is_file():
            return cand
    raise CheckpointError(
        f"No run config.yaml found for checkpoint {ckpt}. Searched: "
        + ", ".join(str(p) for p in searched)
        + ". Pass a checkpoint inside its run directory (<run>/checkpoint/ckpt_*.ckpt) "
        "or place the run's config.yaml next to it.",
        searched[0],
    )


def resolve_resume_latest(cfg: DotDict) -> DotDict:
    """``checkpoint.resume_from=latest`` → the newest *complete* checkpoint
    under this experiment's root (``<log_root>/<root_dir>``), discovered via
    the run manifests; half-written/corrupt saves are skipped."""
    if str(cfg.checkpoint.resume_from).strip().lower() != "latest":
        return cfg
    from sheeprl_tpu.fault.manager import find_latest_run_checkpoint
    from sheeprl_tpu.utils.checkpoint import CheckpointError

    root = pathlib.Path(cfg.get("log_root", "logs/runs")) / str(cfg.root_dir)
    resolved = find_latest_run_checkpoint(root)
    if resolved is None:
        raise CheckpointError(
            f"checkpoint.resume_from=latest: no complete checkpoint found under {root}", root
        )
    print(f"checkpoint.resume_from=latest -> {resolved}")
    cfg.checkpoint.resume_from = str(resolved)
    return cfg


def resume_from_checkpoint(cfg: DotDict) -> DotDict:
    """Merge the checkpoint run's saved config over the current one
    (reference: ``cli.py:23-56``)."""
    import copy

    from sheeprl_tpu.config import deep_merge

    ckpt_path = pathlib.Path(cfg.checkpoint.resume_from)
    old_cfg = dotdict(load_yaml(find_run_config(ckpt_path)))
    if old_cfg.env.id != cfg.env.id:
        raise ValueError(
            "This experiment is run with a different environment from the one of the experiment you want to restart. "
            f"Got '{cfg.env.id}', but the environment of the experiment of the checkpoint was {old_cfg.env.id}."
        )
    if old_cfg.algo.name != cfg.algo.name:
        raise ValueError(
            "This experiment is run with a different algorithm from the one of the experiment you want to restart. "
            f"Got '{cfg.algo.name}', but the algorithm of the experiment of the checkpoint was {old_cfg.algo.name}."
        )
    if old_cfg.algo.get("learning_starts", 0) and old_cfg.algo.learning_starts > 0:
        warnings.warn(
            "The `algo.learning_starts` parameter is greater than zero: the resuming experiment will pre-fill "
            "the buffer for `algo.learning_starts` steps. Set `algo.learning_starts=0` if not intended."
        )
    old_cfg = copy.deepcopy(old_cfg)
    old_cfg.pop("root_dir", None)
    old_cfg.pop("run_name", None)
    old_cfg.pop("log_root", None)  # repo-specific: keep the resumed run's own log tree
    old_cfg.get("checkpoint", {}).pop("resume_from", None)
    old_cfg.get("algo", {}).pop("learning_starts", None)
    merged = dict(cfg)
    deep_merge(merged, old_cfg)
    return dotdict(merged)


def check_configs(cfg: DotDict) -> None:
    """Config validation (reference: ``cli.py:270-344``). Torch-specific
    precision flags don't apply; strategy strings are validated loosely since
    the mesh is always the mechanism."""
    entry = resolve_algorithm(cfg.algo.name)
    if entry is None:
        raise RuntimeError(f"Given the algorithm named '{cfg.algo.name}', no module has been found to be imported.")
    strategy = str(cfg.fabric.get("strategy", "auto")).lower()
    if strategy not in ("auto", "ddp", "dp", "single_device"):
        warnings.warn(
            f"Strategy '{strategy}' has no TPU meaning; the device mesh is always used. Proceeding with 'auto'.",
            UserWarning,
        )
    from sheeprl_tpu.utils.imports import _IS_MLFLOW_AVAILABLE

    if not (_IS_MLFLOW_AVAILABLE or cfg.model_manager.disabled):
        warnings.warn("MLFlow is not installed. Setting `cfg.model_manager.disabled=True`", UserWarning)
        cfg.model_manager.disabled = True
    if cfg.algo.get("learning_starts") is not None and cfg.algo.learning_starts < 0:
        raise ValueError("The `algo.learning_starts` parameter must be greater or equal to zero.")
    if cfg.env.action_repeat < 1:
        cfg.env.action_repeat = 1


def _load_utils_module(entry: Dict[str, Any]):
    pkg = entry["module"].rsplit(".", 1)[0]
    return importlib.import_module(f"{pkg}.utils")


def _prepare_process(cfg: DotDict) -> None:
    """What every verb does before JAX opens a backend: pin the CPU platform
    for a ``fabric.accelerator=cpu`` run, point the persistent compile cache
    at the repository's one directory, and wire ``ops.backend=auto|pallas|lax``
    + per-kernel overrides into the kernel registry (howto/kernels.md)."""
    from sheeprl_tpu.ops.kernels import configure_from_config
    from sheeprl_tpu.utils.utils import enable_compile_cache, pin_cpu_platform

    pin_cpu_platform(cfg.get("fabric", {}).get("accelerator", "auto"))
    enable_compile_cache()
    configure_from_config(cfg.get("ops"))


def run_algorithm(cfg: DotDict) -> None:
    """(reference: ``cli.py:59-198``)"""
    os.environ.setdefault("OMP_NUM_THREADS", str(cfg.num_threads))
    _prepare_process(cfg)

    entry = resolve_algorithm(cfg.algo.name)
    if entry is None:
        raise RuntimeError(f"Given the algorithm named '{cfg.algo.name}', no module has been found to be imported.")
    utils = _load_utils_module(entry)
    command = get_entrypoint(entry)

    kwargs: Dict[str, Any] = {}
    if "finetuning" in cfg.algo.name and "p2e" in entry["module"]:
        ckpt_path = pathlib.Path(cfg.checkpoint.exploration_ckpt_path)
        exploration_cfg = dotdict(load_yaml(find_run_config(ckpt_path)))
        if exploration_cfg.env.id != cfg.env.id:
            raise ValueError(
                "This experiment is run with a different environment from the one of the exploration you want to "
                f"finetune. Got '{cfg.env.id}', but the environment used during exploration was "
                f"{exploration_cfg.env.id}."
            )
        kwargs["exploration_cfg"] = exploration_cfg
        for k in (
            "frame_stack",
            "screen_size",
            "action_repeat",
            "grayscale",
            "clip_rewards",
            "frame_stack_dilation",
            "max_episode_steps",
            "reward_as_observation",
        ):
            cfg.env[k] = exploration_cfg.env[k]

    # Metric key filtering (reference: cli.py:150-164)
    from sheeprl_tpu.utils.metric import MetricAggregator
    from sheeprl_tpu.utils.timer import timer

    from sheeprl_tpu.distributions import set_validate_args

    set_validate_args(bool(cfg.get("distribution", {}).get("validate_args", False)))

    if cfg.get("metric") is not None:
        predefined = getattr(utils, "AGGREGATOR_KEYS", None)
        if predefined is None:
            warnings.warn(
                f"No 'AGGREGATOR_KEYS' set found for the {cfg.algo.name} algorithm. No metric will be logged.",
                UserWarning,
            )
            predefined = set()
        # disable_timer is tri-state: null → auto (timers off iff nothing
        # logs them), an explicit true/false always wins — the replay bench
        # sets false to read Time/replay_path_time at log_level 0
        _dt = cfg.metric.disable_timer
        timer.disabled = (cfg.metric.log_level == 0) if _dt is None else bool(_dt)
        metrics_cfg = cfg.metric.aggregator.get("metrics") or {}
        for k in set(metrics_cfg.keys()) - set(predefined):
            metrics_cfg.pop(k, None)
        MetricAggregator.disabled = cfg.metric.log_level == 0 or len(metrics_cfg) == 0

    # Model-manager key filtering (reference: cli.py:166-180)
    if cfg.get("model_manager") is not None and not cfg.model_manager.disabled and cfg.model_manager.models is not None:
        predefined_models = getattr(utils, "MODELS_TO_REGISTER", set())
        for k in set(cfg.model_manager.models.keys()) - set(predefined_models):
            cfg.model_manager.models.pop(k, None)

    from sheeprl_tpu.parallel import Fabric
    from sheeprl_tpu.parallel.distributed import maybe_init
    from sheeprl_tpu.parallel.pod import maybe_start_worker_runtime
    from sheeprl_tpu.utils.callback import CheckpointCallback

    # pod worker runtime (heartbeat thread + SIGTERM drain flag) BEFORE the
    # slow bring-up below: the launcher's liveness lease must survive
    # jax.distributed connect + mesh compile stalls
    maybe_start_worker_runtime()
    # multi-host bring-up BEFORE the fabric builds its mesh: config-driven
    # (fabric.distributed.*) with the SHEEPRL_* env vars as the pod
    # runtime's per-host override
    maybe_init(cfg.fabric.get("distributed"))
    callbacks = []
    for cb_spec in cfg.fabric.get("callbacks") or []:
        target = cb_spec.get("_target_", "") if isinstance(cb_spec, dict) else ""
        if target.endswith("CheckpointCallback"):
            from sheeprl_tpu.fault.manager import CheckpointManager

            manager = CheckpointManager(
                keep_last=cb_spec.get("keep_last"),
                async_save=bool(cfg.checkpoint.get("async_save", False)),
            )
            callbacks.append(CheckpointCallback(keep_last=cb_spec.get("keep_last"), manager=manager))
    fabric = Fabric.from_config(cfg.fabric, callbacks=callbacks)

    def reproducible(func):
        def wrapper(fabric, cfg, *args, **kw):
            fabric.seed_everything(cfg.seed)
            return func(fabric, cfg, *args, **kw)

        return wrapper

    fabric.launch(reproducible(command), cfg, **kwargs)


def eval_algorithm(cfg: DotDict) -> None:
    """(reference: ``cli.py:201-267``)"""
    from sheeprl_tpu.parallel import Fabric
    from sheeprl_tpu.utils.checkpoint import load_state

    _prepare_process(cfg)

    fabric = Fabric(devices=1, accelerator=cfg.fabric.get("accelerator", "auto"), precision=str(cfg.fabric.get("precision", "32-true")))
    fabric.seed_everything(cfg.seed if cfg.get("seed") is not None else 42)
    state = load_state(cfg.checkpoint_path)

    entry = resolve_evaluation(cfg.algo.name)
    if entry is None:
        raise RuntimeError(f"Given the algorithm named '{cfg.algo.name}', no evaluation has been registered.")
    command = get_entrypoint(entry)
    fabric.launch(command, cfg, state)


def serve_algorithm(cfg: DotDict) -> None:
    """Build the serving tier for one checkpoint and run it
    (howto/serving.md). Mirrors :func:`eval_algorithm` — single-device
    fabric, checkpoint state, per-algo registry resolution — but resolves
    the algorithm's *policy builder* and hands off to the continuous-batching
    server instead of the offline test loop."""
    from sheeprl_tpu.parallel import Fabric
    from sheeprl_tpu.parallel.distributed import maybe_init
    from sheeprl_tpu.serve.server import serve_policy
    from sheeprl_tpu.utils.checkpoint import load_state
    from sheeprl_tpu.utils.registry import registered_policy_builder_names, resolve_policy_builder

    _prepare_process(cfg)
    # serve joins the same multi-host bring-up contract as train: a serve
    # replica launched by a pod runtime initializes jax.distributed from the
    # identical fabric.distributed.* / SHEEPRL_* knobs
    maybe_init(cfg.get("fabric", {}).get("distributed"))

    fabric = Fabric(
        devices=1,
        accelerator=cfg.fabric.get("accelerator", "auto"),
        precision=str(cfg.fabric.get("precision", "32-true")),
    )
    fabric.seed_everything(cfg.seed if cfg.get("seed") is not None else 42)
    state = load_state(cfg.checkpoint_path)

    entry = resolve_policy_builder(cfg.algo.name)
    if entry is None:
        raise RuntimeError(
            f"Given the algorithm named '{cfg.algo.name}', no serving policy builder has been "
            f"registered. Registered builders: {', '.join(registered_policy_builder_names())}."
        )
    builder = get_entrypoint(entry)
    fabric.launch(serve_policy, cfg, state, builder)


def flywheel_algorithm(cfg: DotDict) -> None:
    """Run the flywheel LEARNER for one serve spool directory
    (howto/serving.md#the-flywheel). Mirrors :func:`serve_algorithm` —
    single-device fabric, checkpoint state — but hands off to the spool
    tailer/trainer instead of the request scheduler; the algorithm's
    learner-ingest builder is resolved (and the typed
    :class:`~sheeprl_tpu.serve.flywheel.FlywheelConfigError` raised) inside
    :func:`~sheeprl_tpu.serve.flywheel.run_flywheel_learner`."""
    from sheeprl_tpu.parallel import Fabric
    from sheeprl_tpu.serve.flywheel import run_flywheel_learner
    from sheeprl_tpu.utils.checkpoint import load_state

    _prepare_process(cfg)

    fabric = Fabric(
        devices=1,
        accelerator=cfg.fabric.get("accelerator", "auto"),
        precision=str(cfg.fabric.get("precision", "32-true")),
    )
    fabric.seed_everything(cfg.seed if cfg.get("seed") is not None else 42)
    state = load_state(cfg.checkpoint_path)
    fabric.launch(run_flywheel_learner, cfg, state)


def learn_from_serve(args: List[str], directory: str) -> None:
    """``sheeprl_tpu run --from-serve <dir>``: the flywheel learner as its
    own process — tail the serve fleet's spool directory, train through the
    algorithm's registered learner-ingest builder starting from the served
    checkpoint, and publish checkpoints back next to it. Composes like
    ``serve`` (checkpoint-run config discovered and merged) so the learner
    rebuilds the exact agent the fleet is serving."""
    serve_cfg = compose(args, config_name="serve_config")
    if not serve_cfg.get("checkpoint_path"):
        raise ValueError("You must specify the checkpoint path the flywheel learner starts from")
    serve_block = dict(serve_cfg.get("serve", {}))
    fly = dict(serve_block.get("flywheel") or {})
    fly["enabled"] = True
    fly["dir"] = str(directory)
    serve_block["flywheel"] = fly
    merged = _merged_ckpt_cfg(
        serve_cfg,
        "flywheel",
        capture_video=False,
        extra={"serve": serve_block},
    )
    flywheel_algorithm(merged)


def _extract_fleet_flag(args: List[str]) -> Tuple[List[str], Optional[int]]:
    """Pull ``--fleet [N]`` / ``--fleet=N`` out of hydra-style args; returns
    (remaining args, replica count or None). Bare ``--fleet`` means 3."""
    out: List[str] = []
    fleet: Optional[int] = None
    i = 0
    while i < len(args):
        tok = args[i]
        if tok == "--fleet":
            if i + 1 < len(args) and args[i + 1].isdigit():
                fleet = int(args[i + 1])
                i += 2
            else:
                fleet = 3
                i += 1
            continue
        if tok.startswith("--fleet="):
            fleet = int(tok.split("=", 1)[1])
            i += 1
            continue
        out.append(tok)
        i += 1
    return out, fleet


def _extract_flywheel_flag(args: List[str]) -> Tuple[List[str], bool, Optional[str]]:
    """Pull ``--flywheel [DIR]`` / ``--flywheel=DIR`` out of hydra-style
    args; returns (remaining args, enabled, spool dir or None). Bare
    ``--flywheel`` enables the loop with the default spool dir (a
    ``flywheel/`` sibling of the served checkpoint)."""
    out: List[str] = []
    enabled = False
    directory: Optional[str] = None
    i = 0
    while i < len(args):
        tok = args[i]
        if tok == "--flywheel":
            enabled = True
            nxt = args[i + 1] if i + 1 < len(args) else None
            if nxt is not None and "=" not in nxt and not nxt.startswith("-"):
                directory = nxt
                i += 2
            else:
                i += 1
            continue
        if tok.startswith("--flywheel="):
            enabled = True
            directory = tok.split("=", 1)[1] or None
            i += 1
            continue
        out.append(tok)
        i += 1
    return out, enabled, directory


def _extract_from_serve_flag(args: List[str]) -> Tuple[List[str], Optional[str]]:
    """Pull ``--from-serve DIR`` / ``--from-serve=DIR`` out of hydra-style
    args; returns (remaining args, spool dir or None). DIR is required —
    the learner is meaningless without the spool directory to tail."""
    out: List[str] = []
    directory: Optional[str] = None
    i = 0
    while i < len(args):
        tok = args[i]
        if tok == "--from-serve":
            if i + 1 >= len(args) or "=" in args[i + 1]:
                raise ValueError("--from-serve needs the flywheel spool directory (`--from-serve <dir>`)")
            directory = args[i + 1]
            i += 2
            continue
        if tok.startswith("--from-serve="):
            directory = tok.split("=", 1)[1]
            if not directory:
                raise ValueError("--from-serve needs the flywheel spool directory (`--from-serve=<dir>`)")
            i += 1
            continue
        out.append(tok)
        i += 1
    return out, directory


def _extract_pod_flag(args: List[str]) -> Tuple[List[str], Optional[int]]:
    """Pull ``--pod [N]`` / ``--pod=N`` out of hydra-style args; returns
    (remaining args, worker count or None). Bare ``--pod`` means 2."""
    out: List[str] = []
    pod: Optional[int] = None
    i = 0
    while i < len(args):
        tok = args[i]
        if tok == "--pod":
            if i + 1 < len(args) and args[i + 1].isdigit():
                pod = int(args[i + 1])
                i += 2
            else:
                pod = 2
                i += 1
            continue
        if tok.startswith("--pod="):
            pod = int(tok.split("=", 1)[1])
            i += 1
            continue
        out.append(tok)
        i += 1
    return out, pod


def serve(args: Optional[List[str]] = None, fleet: Optional[int] = None, require_fleet: bool = False) -> None:
    """Serve a checkpoint behind the continuous-batching inference tier
    (``sheeprl_tpu serve checkpoint_path=... [serve.buckets=[1,8,32] ...]``).
    Shares :func:`find_run_config` discovery and the config-merge shape with
    :func:`evaluation`.

    ``--fleet N`` (or ``serve.fleet.replicas=N``, or the ``serve_fleet``
    verb) serves the checkpoint from N supervised replica PROCESSES behind
    the :class:`~sheeprl_tpu.serve.fleet.FleetRouter` front end instead of
    one in-process server (howto/serving.md#the-serve-fleet)."""
    args = list(sys.argv[1:] if args is None else args)
    args, flag_fleet = _extract_fleet_flag(args)
    args, flag_flywheel, flywheel_dir = _extract_flywheel_flag(args)
    fleet = flag_fleet if flag_fleet is not None else fleet
    serve_cfg = compose(args, config_name="serve_config")
    if not serve_cfg.get("checkpoint_path"):
        raise ValueError("You must specify the checkpoint path to serve")
    if fleet is not None:
        serve_cfg.serve.fleet.replicas = int(fleet)
    if flag_flywheel:
        serve_cfg.serve.flywheel.enabled = True
        if flywheel_dir is not None:
            serve_cfg.serve.flywheel.dir = str(flywheel_dir)
    merged = _merged_ckpt_cfg(
        serve_cfg,
        "serve",
        capture_video=False,
        extra={"serve": dict(serve_cfg.get("serve", {}))},
    )
    replicas = int(((merged.get("serve") or {}).get("fleet") or {}).get("replicas", 0) or 0)
    if (require_fleet or flag_fleet is not None) and replicas < 2:
        # an operator who asked for a FLEET must get one or a loud error —
        # silently falling back to a single unsupervised server would deploy
        # without any of the fleet's fault tolerance
        raise ValueError(
            f"fleet serving needs serve.fleet.replicas >= 2, got {replicas} — "
            "drop the fleet flag/verb for a single-process server"
        )
    if replicas >= 2:
        from sheeprl_tpu.parallel.distributed import maybe_init
        from sheeprl_tpu.serve.fleet import serve_fleet as serve_fleet_body
        from sheeprl_tpu.utils.utils import pin_cpu_platform

        pin_cpu_platform(merged.get("fabric", {}).get("accelerator", "auto"))
        maybe_init(merged.get("fabric", {}).get("distributed"))
        serve_fleet_body(merged)
        return
    serve_algorithm(merged)


def serve_fleet(args: Optional[List[str]] = None) -> None:
    """Fleet serving verb: ``sheeprl_tpu serve_fleet checkpoint_path=...``
    is ``serve --fleet N`` with N from ``serve.fleet.replicas`` (>= 2
    enforced; unset defaults to 3)."""
    args = list(sys.argv[1:] if args is None else args)
    has_replicas = any(a.startswith("serve.fleet.replicas=") for a in args)
    serve(args, fleet=None if has_replicas else 3, require_fleet=True)


def available_agents() -> None:
    """Rich table of registered algorithms
    (reference: ``sheeprl/available_agents.py:7-35``)."""
    from sheeprl_tpu.utils.registry import _ensure_populated

    _ensure_populated()
    try:
        from rich.console import Console
        from rich.table import Table

        table = Table(title="SheepRL-TPU Agents")
        table.add_column("Module")
        table.add_column("Algorithm")
        table.add_column("Entrypoint")
        table.add_column("Decoupled")
        for module, algos in algorithm_registry.items():
            for algo in algos:
                table.add_row(algo["module"], algo["name"], algo["entrypoint"], str(algo["decoupled"]))
        Console().print(table)
    except ImportError:  # pragma: no cover
        for module, algos in algorithm_registry.items():
            for algo in algos:
                print(f"{algo['module']}: {algo['name']} ({algo['entrypoint']}, decoupled={algo['decoupled']})")


def run(args: Optional[List[str]] = None) -> None:
    """Train (reference: ``cli.py:357-365``).

    ``--pod N`` (or ``fabric.pod.workers=N``) trains over a gang-supervised
    pod of N worker processes spanning ONE ``jax.distributed`` mesh instead
    of a single process (howto/fault_tolerance.md#pod-training).

    ``--from-serve <dir>`` runs the flywheel LEARNER instead of an offline
    training run: tail the serve fleet's trajectory spool under <dir>,
    fine-tune the served checkpoint on production rows, and publish
    checkpoints back for the fleet's watchers to adopt
    (howto/serving.md#the-flywheel)."""
    args = list(sys.argv[1:] if args is None else args)
    args, from_serve = _extract_from_serve_flag(args)
    if from_serve is not None:
        learn_from_serve(args, from_serve)
        return
    args, pod_flag = _extract_pod_flag(args)
    cfg = compose(args)
    from sheeprl_tpu.utils.utils import print_config

    print_config(cfg)
    if pod_flag is not None:
        cfg.fabric.pod.workers = int(pod_flag)
    pod_workers = int(((cfg.get("fabric") or {}).get("pod") or {}).get("workers", 0) or 0)
    from sheeprl_tpu.parallel.pod import pod_worker_active, run_pod

    if (pod_flag is not None or pod_workers) and not pod_worker_active():
        # an operator who asked for a POD must get one or a loud error —
        # PodLauncher enforces workers >= 2 (same contract as the serve fleet)
        check_configs(cfg)
        run_pod(cfg, args)
        return
    if cfg.checkpoint.resume_from:
        cfg = resolve_resume_latest(cfg)
        cfg = resume_from_checkpoint(cfg)
    check_configs(cfg)
    run_algorithm(cfg)


def _merged_ckpt_cfg(
    verb_cfg: DotDict,
    verb: str,
    capture_video: bool,
    extra: Optional[Dict[str, Any]] = None,
) -> DotDict:
    """The eval/serve config-merge shape: the checkpoint run's own config
    (via :func:`find_run_config`) overlaid with single-device fabric, the
    verb's seed/accelerator overrides and the run-relative log anchors.
    ``root_dir``/``run_name`` follow the canonical
    ``<root>/<algo>/<env>/<run>/checkpoint/ckpt_*.ckpt`` layout (for a
    checkpoint discovered elsewhere they only steer where the verb's own
    logs land)."""
    from sheeprl_tpu.config import deep_merge

    checkpoint_path = Path(os.path.abspath(verb_cfg.checkpoint_path))
    ckpt_cfg = dotdict(load_yaml(find_run_config(checkpoint_path)))
    merged = dict(ckpt_cfg)
    deep_merge(
        merged,
        {
            "env": {"capture_video": capture_video, "num_envs": 1},
            "fabric": {
                "devices": 1,
                "strategy": "auto",
                "accelerator": verb_cfg.get("fabric", {}).get("accelerator", "auto"),
            },
            "checkpoint_path": str(checkpoint_path),
            "seed": verb_cfg.get("seed") if verb_cfg.get("seed") is not None else ckpt_cfg.get("seed", 42),
            "root_dir": str(checkpoint_path.parent.parent.parent.parent),
            "run_name": str(
                Path(
                    os.path.join(
                        os.path.basename(str(checkpoint_path.parent.parent.parent)),
                        os.path.basename(str(checkpoint_path.parent.parent)),
                        verb,
                    )
                )
            ),
            **(extra or {}),
        },
    )
    return dotdict(merged)


def evaluation(args: Optional[List[str]] = None) -> None:
    """Evaluate a checkpoint (reference: ``cli.py:368-404``)."""
    args = list(sys.argv[1:] if args is None else args)
    eval_cfg = compose(args, config_name="eval_config")
    if not eval_cfg.get("checkpoint_path"):
        raise ValueError("You must specify the evaluation checkpoint path")
    capture_video = eval_cfg.get("env", {}).get("capture_video", True)
    eval_algorithm(_merged_ckpt_cfg(eval_cfg, "evaluation", capture_video=capture_video))


def registration(args: Optional[List[str]] = None) -> None:
    """MLflow model registration (reference: ``cli.py:407-449``)."""
    from sheeprl_tpu.utils.imports import _IS_MLFLOW_AVAILABLE

    if not _IS_MLFLOW_AVAILABLE:
        raise ModuleNotFoundError("MLflow is not installed; model registration is unavailable.")
    args = list(sys.argv[1:] if args is None else args)
    cfg = compose(args, config_name="model_manager_config")
    checkpoint_path = Path(cfg.checkpoint_path)
    ckpt_cfg = dotdict(load_yaml(find_run_config(checkpoint_path)))
    for k in ("env", "exp_name", "algo", "distribution", "seed"):
        cfg[k] = ckpt_cfg[k]
    cfg.to_log = ckpt_cfg

    from sheeprl_tpu.utils.checkpoint import load_state
    from sheeprl_tpu.utils.mlflow import register_model_from_checkpoint

    state = load_state(cfg.checkpoint_path)
    algo_name = cfg.algo.name.replace("_decoupled", "")
    if algo_name.startswith("p2e_dv"):
        algo_name = "_".join(algo_name.split("_")[:2])
    utils = importlib.import_module(f"sheeprl_tpu.algos.{algo_name}.utils")
    from sheeprl_tpu.parallel import Fabric

    fabric = Fabric(devices=1)
    fabric.launch(register_model_from_checkpoint, cfg, state, utils.log_models_from_checkpoint)


def main() -> None:
    """Entry: dispatch on first positional verb."""
    argv = sys.argv[1:]
    if argv and argv[0] in ("run", "eval", "evaluation", "serve", "serve_fleet", "agents", "registration"):
        verb, rest = argv[0], argv[1:]
    else:
        verb, rest = "run", argv
    if verb == "run":
        run(rest)
    elif verb in ("eval", "evaluation"):
        evaluation(rest)
    elif verb == "serve":
        serve(rest)
    elif verb == "serve_fleet":
        serve_fleet(rest)
    elif verb == "agents":
        available_agents()
    elif verb == "registration":
        registration(rest)


if __name__ == "__main__":
    main()
