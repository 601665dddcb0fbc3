"""Run one cell of the benchmark once on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name:
``BENCHMARK.json`` at the root of the checkout names the cell's
``config`` (``bench/configs/<config>.json``, whose ``model`` names its
builder, ``bench/models/<model>.py``, and whose ``reference`` names its
plain reference, ``bench/references/<reference>.py``) and its ``traffic``
(``bench/traffic/<traffic>.json``).  Each metric the cell reports is read by
``bench/metrics/<name>.py`` (for a name with a dot, ``<name before the
dot>.py``: the part after it only splits one quantity by the end-to-end
metric it moves).

A run: weights from the seed on the device, every program the cell's
traffic needs warmed, then ``--seconds`` of traffic through the scheduler
and engine (with ``--trace 1`` under the profiler, spans around the calls
into each layer), then the check of what the window served against the
plain reference.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer ones), ``device``, with
``--trace 1`` ``breakdown``, and last ``checks``: each number compared, with
its limit.  Without a TPU, or with fewer chips than the cell asks for, it
prints no result and exits non-zero.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


class NoChip(RuntimeError):
    """The machine does not have the chips the cell asks for."""


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, root: pathlib.Path = ROOT):
    """The reader of metric ``name``: ``bench/metrics/<name>.py``, or for a
    dotted name the file of the part before the first dot."""
    for stem in (name, name.split(".")[0]):
        path = root / "bench" / "metrics" / f"{stem}.py"
        if path.is_file():
            return load_module(path).read
    raise FileNotFoundError(f"no reader for metric {name!r} in "
                            f"{root / 'bench' / 'metrics'}")


def load_cell(root: pathlib.Path, name: str):
    """(BENCHMARK.json, the cell, its configuration, its traffic mix) of
    the cell ``name``, each found by name under ``root``."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} (known: {sorted(cells)})")
    cell = cells[name]
    conf = load_json(root / "bench" / "configs" / f"{cell['config']}.json")
    traffic = load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    return bench, cell, conf, traffic


def cell_metrics(bench: dict, cell: str, trace: bool) -> list:
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def compile_cache(platform: str) -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, unless ``JAX_COMPILATION_CACHE_DIR`` names one.  Every
    program is cached, however quick to compile."""
    import jax
    if not jax.config.jax_compilation_cache_dir and platform != "cpu":
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(trace) -> dict:
    import jax
    devs = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": int(peak)}
    if trace is not None:
        from bench import trace as T
        out["busy_s"] = T.busy_s(trace)
        out["window_s"] = trace.window_s
    return out


def run_cell(bench: dict, cell: dict, conf: dict, traffic: dict, *,
             seed: int, seconds: float, trace: bool, chips: int = 1,
             require_tpu: bool = True, control: bool = False, log=print,
             also=None, root: pathlib.Path = ROOT) -> dict:
    """One run of ``cell``; returns the result line as a dict.  With
    ``control`` the tokens judged are the control's (the int8
    reference's first choice at each served position), not the served
    ones.  ``also``, when given, is called with the reference, the weights
    and the sample once the window's sample is judged (``bench/control.py``,
    ``bench/sweep.py``)."""
    phases = {"start": time.perf_counter() - T_START}
    import jax
    phases["import_jax"] = time.perf_counter() - T_START

    from bench import check, driver, generator, model
    from repro.serve.engine import Engine

    devs = jax.devices()
    phases["devices"] = time.perf_counter() - T_START
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    compile_cache(devs[0].platform)
    peaks = check.peaks(devs[0].device_kind, required=require_tpu)

    build = model.builder(conf, root)
    cfg = build.model_config(conf)
    key = jax.random.key(generator.seed_words(seed)[0])
    for word in generator.seed_words(seed)[1:]:
        key = jax.random.fold_in(key, word)
    weights = model.init_weights(build, cfg, key)
    jax.block_until_ready(weights)
    phases["weights"] = time.perf_counter() - T_START
    e = conf["engine"]
    eng = Engine(cfg, weights, slots=e["slots"], max_len=e["max_len"],
                 n_sessions=e["n_sessions"])
    jax.block_until_ready((eng.cache, eng.sessions))
    phases["engine"] = time.perf_counter() - T_START
    plan = generator.sessions(traffic, seed=seed, seconds=seconds,
                              max_len=e["max_len"])
    buckets = driver.warm(eng, [s.prompt_len for s in plan],
                          driver.resumes(traffic))
    jax.block_until_ready((eng.cache, eng.sessions))
    phases["warm"] = time.perf_counter() - T_START
    sched = driver.scheduler(eng, traffic)
    rec = driver.new_record()
    driver.instrument(eng, sched, rec, annotate=trace)
    win = driver.Window(sched, traffic, plan, seed=seed,
                        vocab=cfg.vocab_size, rec=rec)
    resumes_before = eng.stats["resumes"]

    # a program that comes into the window is traced first, and then
    # compiled or read from the persistent cache: count both
    compiles = []
    listen = lambda event, secs, **_: (
        event.startswith(("/jax/core/compile/jaxpr_trace",
                          "/jax/core/compile/backend_compile",
                          "/jax/compilation_cache/cache_retrieval"))
        and compiles.append(event))
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    from jax.profiler import TraceAnnotation
    try:
        if trace:
            # the harness's own annotations and the device: no Python
            # function tracing, which would slow the host's tick
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        setup_s = time.perf_counter() - T_START
        jax.monitoring.register_event_duration_secs_listener(listen)
        # what set-up made lives on; the collector does not walk it again
        # in the window
        gc.collect()
        gc.freeze()
        try:
            with TraceAnnotation("bench.window"):
                win.run(seconds)
                jax.block_until_ready(eng.cache)
        finally:
            gc.unfreeze()
            jax.monitoring.unregister_event_duration_listener(listen)
        tr = None
        if trace:
            jax.profiler.stop_trace()
            from bench import trace as T
            tr = T.load(trace_dir)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    device = device_info(tr)
    verify_failed = eng.verify_failure_count()
    resumed = eng.stats["resumes"] - resumes_before
    one_gap = driver.token_gaps_ms(win)
    log(f"[setup] {setup_s:.3f} s; phases end at "
        f"{ {k: round(v, 3) for k, v in phases.items()} } s", file=sys.stderr)
    log(f"[window] {win.t1 - win.t0:.3f} s, {win.ticks} ticks, "
        f"{len(win.turns)} requests due, {driver.window_tokens(win)} "
        f"tokens, {len(compiles)} programs traced, compiled or loaded in "
        f"the window, buckets {buckets}, store-row waits {win.row_waits} "
        f"({len(win.waiting)} sessions without a row at the close), "
        f"generator late p99 {driver.percentile(win.late, 99)} s, max "
        f"{max(win.late, default=None)} s, longest tick "
        f"{win.longest_tick} s; single token gaps "
        f"p50/p90/p95/p99 "
        f"{[driver.percentile(one_gap, q) for q in (50, 90, 95, 99)]} ms; "
        f"fresh/resume requests due "
        f"{len(driver.first_token_ms(win, 'fresh'))}/"
        f"{len(driver.first_token_ms(win, 'resume'))}", file=sys.stderr)
    log(f"[movement] {len(win.turns)} turns due, "
        f"{sum(t.index == 0 for t in win.turns)} of them first turns, "
        f"{sum(t.done for t in win.turns)} turns finished; "
        f"{resumed} sessions resumed (resume_many) in "
        f"{len(rec['resume'])} waves, {sum(map(len, rec['suspend']))} "
        f"suspended in {len(rec['suspend'])} calls", file=sys.stderr)
    if tr is not None:
        from bench import trace as T
        log(f"[trace] longest device operations (name, start, seconds): "
            f"{T.longest_ops(tr)}", file=sys.stderr)
    for at, secs, parts in win.long_ticks:
        log(f"[long tick] at +{at:.3f} s lasted {secs:.3f} s; host seconds "
            f"in {parts}, the scheduler's own "
            f"{secs - sum(v for k, v in parts.items() if k != 'tick'):.3f}",
            file=sys.stderr)

    # free the program's state before the reference runs on the chip
    sessions = check.finished(win)
    del sched, win.s, win.eng, eng
    gc.collect()

    run = SimpleNamespace(window=win, rec=rec, trace=tr, cfg=cfg, conf=conf,
              model=build, peaks=peaks, setup_s=setup_s, traffic=traffic)
    metrics = {}
    for m in cell_metrics(bench, cell["name"], trace):
        v = reader(m["name"], root)(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    ref = load_module(root / "bench" / "references" /
                      f"{conf['reference']}.py")
    sample = check.sample(sessions, seed=seed,
                          n=traffic["check_sessions"])
    judge = check.control_gaps if control else check.served_gaps
    gaps = judge(ref, conf, weights, sample, e["max_len"])
    if also is not None:        # bench/control.py, bench/sweep.py
        also(SimpleNamespace(ref=ref, conf=conf, weights=weights, sample=sample,
                 max_len=e["max_len"], window=win, gaps=gaps))
    checks = {
        "widest_logit_gap": {"value": gaps["widest"],
                             "limit": conf["widest_logit_gap_limit"]},
        "mean_logit_gap": {"value": gaps["mean"],
                           "limit": conf["mean_logit_gap_limit"]},
        "verify_failed": {"value": verify_failed, "limit": 0},
    }
    correct = gaps["positions"] > 0 and all(
        c["limit"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    log(f"[check] {len(sample)} sessions, {gaps['positions']} served "
        f"tokens, {sum(s.resumes for s in sample)} follow-up turns "
        f"compared; share off the reference's best "
        f"{gaps['off_best']!r} (not compared)",
        file=sys.stderr)
    for k, c in checks.items():
        log(f"[check] {k} {c['value']!r} limit {c['limit']!r}",
            file=sys.stderr)
    due = [t for t in run.window.turns
           if run.window.t0 <= t.due <= run.window.t1]
    out = {"correct": bool(correct), "attempted": len(due),
           "failed": 0 if correct else len(sample),
           "metrics": metrics, "device": device}
    if trace:
        from bench import trace as T
        out["breakdown"] = {"device_ops": T.top_ops(tr),
                            "idle_gaps": T.top_idle(tr)}
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench/run.py: the program (src/repro) is not in the "
              f"checkout at {ROOT}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        bench, cell, conf, traffic = load_cell(ROOT, args.workload)
    except KeyError as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    try:
        out = run_cell(bench, cell, conf, traffic, seed=args.seed,
                       seconds=args.seconds, trace=bool(args.trace),
                       chips=cell["chips"])
    except NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
