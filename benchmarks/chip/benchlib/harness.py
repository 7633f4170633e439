"""Run one cell of ``BENCHMARK.json`` once and print its result line.

A cell names a configuration (``configs/<name>.json``) and a traffic mix
(``traffic/<name>.json``); the mix names the engine module that runs it
(``benchlib/engines/<engine>.py``).  Each per-layer metric is read by
``layers/<name>.py``.  Everything is found by the names in the file, so a
new cell, mix or metric is a new file and a new entry."""
from __future__ import annotations

import hashlib
import importlib
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace

from .compare import limits_for, verdict

HERE = Path(__file__).resolve().parents[1]          # benchmarks/chip
ROOT = HERE.parents[1]                              # the checkout
TRACE_DIR = HERE / ".traces"
CACHE_DIR = ROOT / ".jax_cache"
# a traced run measures at most this long: the trace of a round cell holds
# about 10^5 device ops a second, and the run must read it back in time
TRACE_SECONDS = 10.0


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def load_config(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def load_traffic(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def engine(name: str):
    return importlib.import_module(f"benchlib.engines.{name}")


def layer_reader(metric: str):
    path = HERE / "layers" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"benchlayer_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_of(bench: dict, kind: str, workload: str):
    """The cell's entries of ``end_to_end`` or ``per_layer``."""
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def program_seed(seed: int) -> int:
    """The 31-bit seed the program's RNGs take, drawn from ``--seed`` (any
    whole number); the same ``--seed`` always gives the same one."""
    digest = hashlib.sha256(str(int(seed)).encode()).digest()
    return int.from_bytes(digest[:4], "little") % (2 ** 31 - 1024)


def enable_cache() -> str:
    """JAX's persistent compilation cache, at a fixed path inside the
    checkout (also where ``JAX_COMPILATION_CACHE_DIR`` names another):
    the path is part of the cache's key, and two checkouts share
    nothing."""
    import jax

    path = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no size cap (a machine may set one in its environment): an evicted
    # program would compile again in every run
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def devices(chips: int, require_tpu: bool = True):
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def device_info(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(devs):
    stats = [d.memory_stats() or {} for d in devs]
    vals = [s.get("peak_bytes_in_use") for s in stats]
    return max(vals) if all(v is not None for v in vals) else None


def _quiet_trace():
    """Device ops and the benchmark's spans, without the Python tracer
    (which records every Python call and slows the host many times)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def run(workload: str, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, log=None, plant=None) -> dict:
    """One run of one cell.  Returns the result line's object; the lines
    before it go to ``log`` (standard error).  ``plant`` is a context
    manager held around set-up and window only, so that a test can break
    the timed path underneath and leave the reference alone; it is called
    with the cell's configuration."""
    import contextlib

    import jax

    from .compiles import CompileCounter
    from .peaks import peaks_for
    from .spans import Spans

    log = log or (lambda s: print(s, file=sys.stderr, flush=True))
    bench = load_benchmark()
    c = cell(bench, workload)
    cache = enable_cache()
    devs = devices(c["chips"], require_tpu)
    dev = device_info(devs)
    tag = f"[{dev['kind']} x{dev['count']}]"
    peaks = peaks_for(dev["kind"]) if require_tpu else None
    traffic = load_traffic(c["traffic"])
    drv = engine(traffic["engine"])
    spans = Spans()
    # what an engine module gets: the cell's files, the seed, the spans
    ctx = SimpleNamespace(config=load_config(c["config"]), traffic=traffic,
                          seed=program_seed(seed), spans=spans,
                          devices=devs, chips=c["chips"], workload=workload)
    log(f"{tag} {workload} seed={seed} program_seed={ctx.seed} "
        f"compile_cache={cache}")

    planted = plant(ctx.config) if plant else contextlib.nullcontext()
    with CompileCounter() as counter, planted:
        t0 = time.perf_counter()
        state = drv.setup(ctx)
        setup_s = time.perf_counter() - t0
        setup_compiles = counter.count()
        log(f"{tag} setup_s={setup_s:.3f} setup_compiles={setup_compiles}")
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(TRACE_DIR),
                                     profiler_options=_quiet_trace())
            seconds = min(seconds, TRACE_SECONDS)
        res = drv.window(state, seconds)
        if trace:
            jax.profiler.stop_trace()
        compiles_in_window = counter.count() - setup_compiles
    peak = memory_peak(devs)
    log(f"{tag} window_s={res['window_s']:.3f} rounds={res['rounds']} "
        f"compiles_in_window={compiles_in_window} peak_bytes_in_use={peak}")
    dev["memory_peak_bytes"] = peak

    metrics, breakdown = {}, None
    if not trace:
        e2e = drv.end_to_end(res)
        e2e["setup_s"] = setup_s
        for m in metrics_of(bench, "end_to_end", workload):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        from .tracing import load

        tr = load(str(TRACE_DIR), c["chips"], drv.SPANS)
        dev["busy_s"], dev["window_s"] = tr.busy_s(), tr.window_s()
        log(f"{tag} trace planes={tr.planes}")
        # what a per-layer reader gets
        rd = SimpleNamespace(trace=tr, result=res, peaks=peaks,
                             chips=c["chips"],
                             compiles_in_window=compiles_in_window)
        for m in metrics_of(bench, "per_layer", workload):
            v = layer_reader(m["name"])(rd)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = {"device_ops": [[n, s] for n, s in tr.top_ops(10)],
                     "idle_gaps": [[n, s] for n, s in
                                   tr.idle_by_span()[:10]]}

    drv.free(state)
    t_ref = time.perf_counter()
    numbers = drv.check(state, workload)
    ref_s = time.perf_counter() - t_ref
    correct, rows, info = verdict(numbers, limits_for(workload))
    log(f"{tag} reference_s={ref_s:.3f}")
    for name, value in info.items():
        log(f"{tag} not compared {name}={value!r}")
    for name, value, lim in rows:
        log(f"{tag} check {name}={value!r} limit={lim!r}")
    out = {"correct": bool(correct), "attempted": int(res["attempted"]),
           "failed": int(numbers.get("count_rounds_off",
                                     0 if correct else res["attempted"])),
           "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {name: {"value": value, "limit": lim}
                     for name, value, lim in rows}
    return out


def main(args) -> int:
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"no result: {e}", file=sys.stderr, flush=True)
        return 3
    print(json.dumps(out), flush=True)
    return 0
