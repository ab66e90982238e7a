"""One run of one cell of the benchmark: a new process that opens the chip,
composes the cell's config, calls `sheeprl_tpu.cli.run_algorithm` in-process,
warms up, measures one window and prints the contract's one JSON line.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one metric is
a file of its own, found by the name `BENCHMARK.json` gives it (README.md).
This file knows no cell, configuration or metric by name.
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import signal
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
EXIT_NO_DEVICE = 3
EXIT_BAD_CHECKOUT = 4


def load_module(kind: str, name: str):
    """`chipbench/<kind>/<name>.py`, loaded by path (no package needed)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        return None
    spec = importlib.util.spec_from_file_location(f"chipbench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts: str):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def fold_seed(seed: int) -> int:
    """The driver's seeds pass 2**31; the program adds small constants to its
    seed and hands it to 32-bit generators."""
    return int(seed) % 2147480000


def find_cell(bench, workload: str):
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"unknown workload {workload!r}")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(ROOT, conf["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    return cell, config, traffic


def compose_overrides(config, traffic, seed: int, extra):
    """exp of the traffic, then the configuration's own overrides, then the
    traffic's, then what every cell assumes, then the seed."""
    return [
        *traffic["base"],
        *config["overrides"],
        *traffic["overrides"],
        *traffic.get("assumed_overrides", []),
        *extra,
        f"seed={fold_seed(seed)}",
    ]


def as_run_of(cfg, config):
    """The keys the configuration file states, read from a composed config."""
    out = {}
    for dotted in config["as_run"]:
        node = cfg
        for part in dotted.split("."):
            node = node[part]
        out[dotted] = node
    return out


def check_config_as_run(cfg, config) -> None:
    """The configuration file holds the sizes as they are run: every width it
    states is read back from the composed config."""
    got = as_run_of(cfg, config)
    for dotted, want in config["as_run"].items():
        if got[dotted] != want:
            raise SystemExit(f"configuration file says {dotted}={want!r}, the composed config has {got[dotted]!r}")


def applies(metric, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def reap_children() -> None:
    """Stop every process this run started (env workers, their fork server)
    and wait for each."""
    import multiprocessing

    for child in multiprocessing.active_children():
        child.terminate()
    for child in multiprocessing.active_children():
        child.join(5)
    me = str(os.getpid())
    pids = []
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat") as f:
                fields = f.read().rsplit(")", 1)[1].split()
            if fields[1] == me:
                pids.append(int(entry))
        except OSError:
            continue
    for pid in pids:
        try:
            os.kill(pid, signal.SIGTERM)
        except OSError:
            pass
    deadline = time.time() + 5
    for pid in pids:
        while time.time() < deadline:
            try:
                done, _ = os.waitpid(pid, os.WNOHANG)
            except ChildProcessError:
                break
            if done:
                break
            time.sleep(0.02)
        else:
            try:
                os.kill(pid, signal.SIGKILL)
                os.waitpid(pid, 0)
            except OSError:
                pass


def free_device_state() -> int:
    """Drop what the program left on the devices; returns the bytes that were
    still live."""
    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()
    left = 0
    for arr in jax.live_arrays():
        left += arr.nbytes
        arr.delete()
    return left


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearsal", type=int, default=0, help="skip the device demand; the line says so")
    ap.add_argument("--override", action="append", default=[], help="rehearsal only: extra config overrides")
    ap.add_argument("--fault", default="", help="rehearsal only: break the timed path (tests/test_rehearsal.py)")
    ap.add_argument("--control", type=int, default=0, help="also read the lower-precision control (not a benchmark run)")
    ap.add_argument("--dump", default="", help="write the run record (ticks, readings, trace summary) to this file")
    args = ap.parse_args()
    if (args.override or args.fault) and not args.rehearsal:
        raise SystemExit("--override and --fault are for --rehearsal runs only")

    bench = load_json(ROOT, "BENCHMARK.json")
    cell, config, traffic = find_cell(bench, args.workload)
    peaks = load_json(HERE, "peaks.json")
    if not os.path.isdir(os.path.join(ROOT, "sheeprl_tpu")):
        print("chipbench: the program (sheeprl_tpu/) is not in this checkout", file=sys.stderr)
        sys.exit(EXIT_BAD_CHECKOUT)
    sys.path.insert(0, ROOT)
    os.chdir(ROOT)

    import jax

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if not args.rehearsal:
        if platform != "tpu" or kind not in peaks["devices"] or len(devices) < cell["chips"]:
            print(
                f"chipbench: needs {cell['chips']} TPU chip(s) of a kind in peaks.json; JAX has "
                f"{len(devices)} x {platform} {kind!r}",
                file=sys.stderr,
            )
            sys.exit(EXIT_NO_DEVICE)

    from sheeprl_tpu.cli import check_configs, run_algorithm
    from sheeprl_tpu.config import compose

    overrides = compose_overrides(config, traffic, args.seed, args.override)
    cfg = compose(overrides)
    if args.rehearsal:  # the widths of the rehearsal's overrides, not of the file
        config = {**config, "as_run": as_run_of(cfg, config)}
    else:
        check_config_as_run(cfg, config)
    check_configs(cfg)

    t_composed = time.perf_counter() - T_START
    entry = load_module("entries", traffic["entry"])
    work = os.path.join(ROOT, ".chipbench")
    trace_dir = os.path.join(work, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    reference = load_module("reference", config["family"] + "_ref")
    hyper = reference.hyper(config["as_run"], config["assumed"], cfg)
    adapter = entry.Adapter(
        seconds=args.seconds, trace=bool(args.trace), trace_dir=trace_dir, t_start=T_START, traffic=traffic,
        program_module=config["program_module"], make_weights=lambda: reference.init_params(hyper, int(cfg.seed)),
        faults={args.fault: True} if args.fault else None,
    )
    adapter.install()
    adapter.marks["config_composed"] = t_composed
    adapter.marks["run_algorithm_called"] = time.perf_counter() - T_START
    try:
        run_algorithm(cfg)
        raise SystemExit("the program ended before the window closed")
    except entry.WindowClosed:
        pass
    adapter.after_run()

    stats = [d.memory_stats() or {} for d in jax.local_devices()[: cell["chips"]]]
    memory_peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    compile_total = adapter.compile_stats.snapshot()
    live_left = free_device_state()

    run = {
        "cell": cell,
        "config": config,
        "traffic": traffic,
        "peaks": None if args.rehearsal else peaks["devices"][kind],
        "chips": cell["chips"],
        "num_envs": int(cfg.env.num_envs),
        "window": adapter.window_counts(),
        "host_step_gaps": adapter.host_step_gaps(),
        "compile_total": {"programs": compile_total[0], "seconds": compile_total[1], "cache_hits": compile_total[2],
                          "cache_writes": compile_total[3]},
        "memory_peak_bytes": memory_peak,
        "trace": None,
        "trace_info": adapter.trace_info,
        "flushes": adapter.flushes,
        "rehearsal": bool(args.rehearsal),
    }
    flops_mod = load_module("flops", config["family"])
    run["flops_per_grad_step"] = flops_mod.flops_per_grad_step(config) if flops_mod else None

    if args.trace:
        reducer = load_module("", "trace_reduce")
        run["trace"] = reducer.reduce_dir(trace_dir, adapter.trace_info, adapter.flushes, cell["chips"])
        shutil.rmtree(trace_dir, ignore_errors=True)

    # -- correct: the first training burst against the plain reference --------
    correct_mod = load_module("correct", config["family"])
    t_check = time.perf_counter()
    verdict = correct_mod.check(adapter, cfg, config, reference, control=bool(args.control))
    check_seconds = time.perf_counter() - t_check
    if adapter.error:
        verdict["correct"] = False
        verdict["numbers"]["harness_error"] = {"value": 1.0, "limit": 0.0, "note": adapter.error}

    # -- metrics --------------------------------------------------------------
    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in bench[group]:
        if not applies(m, cell["name"]):
            continue
        reader = load_module("layers" if args.trace else "end_to_end", m["name"])
        value = reader.read(run)
        if value is None:
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if args.rehearsal:
        # a CPU rehearsal carries no device metric: names and units only
        metrics = {k: {"value": None, "unit": v["unit"]} for k, v in metrics.items()}

    device = {"platform": platform, "kind": kind, "count": cell["chips"] if not args.rehearsal else len(devices),
              "memory_peak_bytes": memory_peak}
    if args.trace and run["trace"]:
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
    w = run["window"]
    attempted = int(w["grants"])
    line = {
        "correct": bool(verdict["correct"]),
        "attempted": attempted,
        "failed": int(verdict.get("failed", 0)),
        "metrics": metrics,
        "device": device,
    }
    if args.trace and run["trace"]:
        line["breakdown"] = run["trace"]["breakdown"]
    if args.rehearsal:
        line["rehearsal"] = True
    line["run"] = {
        "window_s": w["seconds"], "bursts": w["bursts"], "policy_steps": w["policy_iters"] * int(cfg.env.num_envs),
        "drain_open_s": w["drain_open_s"], "drain_close_s": w["drain_close_s"],
        "compile": run["compile_total"], "check_seconds": check_seconds, "live_bytes_freed": live_left,
        "first_burst_seconds": (adapter.first or {}).get("seconds"), "setup_marks": w["setup_marks"],
        "reference_seconds": verdict.get("reference_seconds"),
    }
    line["compared"] = {k: {"value": v["value"], "limit": v["limit"]} for k, v in verdict["numbers"].items()}

    if args.dump:
        os.makedirs(os.path.dirname(os.path.abspath(args.dump)), exist_ok=True)
        with open(args.dump, "w") as f:
            json.dump({"line": line, "verdict": verdict, "window": w, "trace": run["trace"],
                       "host_step_gaps": run["host_step_gaps"][:2000], "flushes": run["flushes"],
                       "rows_staged": (adapter.first_flush or {}).get("rows_total")}, f, default=float)

    for name, v in verdict["numbers"].items():
        note = f"  ({v['note']})" if v.get("note") else ""
        print(f"compared {name}: value {v['value']:.6g} limit {v['limit']:.6g}{note}", file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line, default=float))
    sys.stdout.flush()
    reap_children()
    os._exit(0)


if __name__ == "__main__":
    try:
        main()
    except SystemExit as e:
        # threads of the program may still be alive: leave without waiting for them
        if e.code not in (0, None):
            print(f"chipbench: {e.code}", file=sys.stderr)
        sys.stderr.flush()
        reap_children()
        os._exit(e.code if isinstance(e.code, int) else 1)
    except BaseException:
        import traceback

        traceback.print_exc()
        sys.stderr.flush()
        reap_children()
        os._exit(1)
