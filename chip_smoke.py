"""Smoke test on the chip: serve full-width TinyLlama-1.1B through the normal
serving path and check what comes out.

    python chip_smoke.py               # one TPU chip
    python chip_smoke.py --four-chips  # four replicas, one per chip

The path is ``repro.launch.serve.main`` -> ``Scheduler`` / ``Engine`` -> the
paged VILLA session store -> the Pallas page kernels, at the model's
published widths and depth and its published context (``--max-len 2048``),
with random weights from a fixed seed and the launcher's default synthetic
workload (fresh sessions, suspends and resume waves).

One chip: a logit check (``lm.prefill`` + ``lm.decode_logits_batched``, the
bodies the engine jits, against one ``lm.forward`` over the whole sequence),
then the served run, which must complete every job's budget with
``verify_failed == 0``, one decode compilation, no dropped buffer donation,
and suspend-many / resume-many programs that contain the page kernels
(``tpu_custom_call``).

``--four-chips``: the same model on ``--replicas 4 --slots 2`` (replica ``i``
on chip ``i``), compared with ``--replicas 1 --slots 2`` on chip 0.  Its
workload is bursty, with 16 fresh sessions and 32 follow-ups: under the
default one a home replica is rarely full when a follow-up arrives, so
nothing migrates.  Every session must complete its budget, at least one
migration must cross chips, ``verify_failed == 0``, the four-replica run
must leave no buffer on chip 0 once it returns, and every session's token
stream must be identical between the two runs (and, in each run, add up to
the tokens the launcher reports).

The script exits non-zero and prints no result when JAX finds no TPU, when
the repository's ``src/`` is not next to it, or when any check fails.
Otherwise its last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import json
import os
import sys
import time
import warnings
from functools import partial

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

SEED = 0
BASE_ARGV = ["--arch", "tinyllama-1.1b", "--max-len", "2048",
             "--seed", str(SEED)]
# The logit check compares the serving bodies with lm.forward at "highest"
# matmul precision, where both are float32 computations that differ only in
# summation order (chunked prefill attention and cached decode against one
# full forward).  Across 22 layers that leaves errors many orders below
# 1e-3 of the largest logit, while a wrong position, mask or cache row moves
# logits by a sizeable fraction of their own scale.
LOGIT_RTOL = 1e-3
PROMPT_LEN, DECODE_STEPS = 8, 4
FOUR_CHIP_SESSIONS = 16
FOUR_CHIP_WORKLOAD = ["--requests", str(FOUR_CHIP_SESSIONS),
                      "--followups", "32", "--bursty"]


class SmokeFailure(Exception):
    """A check of the smoke test failed."""


@contextlib.contextmanager
def observe_serving():
    """Record what one ``serve.main`` call builds and emits, without
    changing it: the scheduler it runs, and every token each session emits,
    per uid, in emission order (the prefill token, then each decoded
    token; a resume's seed token is not new)."""
    from repro import sched
    from repro.serve.engine import Engine

    seen = {"scheduler": None, "streams": collections.defaultdict(list)}
    streams = seen["streams"]
    run, submit, step_end = sched.Scheduler.run, Engine.submit, Engine.step_end

    def run_(self, *a, **k):
        seen["scheduler"] = self
        return run(self, *a, **k)

    def submit_(self, req):
        slot = submit(self, req)
        streams[req.uid] += req.generated[:1]
        return slot

    def step_end_(self, handle):
        live = [(r, len(r.generated)) for r in self.active.values()]
        done = step_end(self, handle)
        for r, n in live:
            streams[r.uid] += r.generated[n:]
        return done

    sched.Scheduler.run, Engine.submit, Engine.step_end = (
        run_, submit_, step_end_)
    try:
        yield seen
    finally:
        sched.Scheduler.run, Engine.submit, Engine.step_end = (
            run, submit, step_end)


def serve(argv):
    """One ``repro.launch.serve.main`` call under observation.  A dropped
    buffer donation (a silent copy of the buffer on the device) raises."""
    from repro.launch import serve as launch_serve

    with observe_serving() as seen, warnings.catch_warnings():
        warnings.filterwarnings(
            "error", message="Some donated buffers were not usable")
        t0 = time.perf_counter()
        out = launch_serve.main(argv)
        wall = time.perf_counter() - t0
    s = seen["scheduler"]
    short = [(j.uid, j.kind, j.done, j.target_new) for j in s.jobs()
             if j.state != "done" or j.done < j.target_new]
    print(f"[serve] {' '.join(argv)}: wall {wall:.3f} s, set-up "
          f"{wall - out['seconds']:.3f} s, run {out['seconds']} s = "
          f"compile {out['compile_s']} s + serve {out['serve_s']} s",
          flush=True)
    problems = []
    if short:
        problems.append(f"jobs short of their budget (uid, kind, done, "
                        f"budget): {short}")
    if out["verify_failed"] != 0:
        problems.append(f"verify_failed = {out['verify_failed']}")
    return out, s, dict(seen["streams"]), problems


def check_logits(cfg, params) -> float:
    """Prefill a prompt, decode DECODE_STEPS more tokens of the same
    sequence through the cache, and compare every position's logits with
    one ``lm.forward`` over the whole sequence.  Returns the error relative
    to the largest reference logit."""
    import jax
    import numpy as np
    from repro.models import lm

    n = PROMPT_LEN + DECODE_STEPS
    toks = np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, n).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, t: lm.forward(cfg, p, t)[0])(
            params, toks[None])[0]
        cache = lm.init_cache(cfg, 1, max_len=2048)
        logits, cache = jax.jit(partial(lm.prefill, cfg))(
            params, toks[None, :PROMPT_LEN], cache)
        got = [logits[0]]
        decode = jax.jit(partial(lm.decode_logits_batched, cfg),
                         donate_argnums=(1,))
        active = np.ones(1, bool)
        for t in range(PROMPT_LEN, n):       # token t at position t
            logits, cache = decode(params, cache, toks[t:t + 1],
                                   np.full(1, t, np.int32), active)
            got.append(logits)
    got = np.concatenate([np.asarray(g) for g in got])
    ref = np.asarray(ref)
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


def one_chip() -> None:
    import jax
    from repro.analysis.entrypoints import engine_targets
    from repro.configs import get_config
    from repro.models import lm

    cfg = get_config("tinyllama-1.1b")
    t0 = time.perf_counter()
    params = lm.init_lm(cfg, jax.random.key(SEED))
    rel = check_logits(cfg, params)
    del params
    print(f"[logits] prefill + {DECODE_STEPS} decode steps vs lm.forward: "
          f"max |err| / max |logit| = {rel!r} (limit {LOGIT_RTOL}), "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    problems = []
    if not rel <= LOGIT_RTOL:
        problems.append(f"logit error {rel!r} exceeds {LOGIT_RTOL}")

    out, s, _, serve_problems = serve(BASE_ARGV)
    problems += serve_problems
    if out["decode_compile_count"] != 1:
        problems.append(f"decode compiled {out['decode_compile_count']}x")
    peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
    print(f"[memory] peak_bytes_in_use {peak}", flush=True)
    targets = {t.name: t for t in engine_targets(s.eng)}
    for name in ("suspend_many", "resume_many"):
        t = targets[name]
        has = "tpu_custom_call" in t.fn.lower(*t.args).compile().as_text()
        print(f"[kernels] compiled {name} contains tpu_custom_call: {has}",
              flush=True)
        if not has:
            problems.append(f"{name} has no page kernel (tpu_custom_call)")
    if problems:
        raise SmokeFailure("; ".join(problems))


def first_difference(a, b):
    """(position, a[pos], b[pos]) of the first mismatch, None if equal."""
    for i in range(max(len(a), len(b))):
        x = a[i] if i < len(a) else None
        y = b[i] if i < len(b) else None
        if x != y:
            return i, x, y
    return None


def replica_devices(cluster) -> list:
    """The set of devices that hold each replica's params, cache and
    session store, printed per replica."""
    import jax

    out = []
    for eng in cluster.replicas:
        devs = frozenset(d for leaf in jax.tree_util.tree_leaves(
            (eng.params, eng.cache, eng.sessions)) for d in leaf.devices())
        print(f"[replica {eng.replica_id}] arrays on "
              f"{sorted(str(d) for d in devs)}", flush=True)
        out.append(devs)
    return out


def live_bytes(device) -> int:
    """Bytes of the live arrays that have a shard on ``device``."""
    import jax

    return sum(a.nbytes for a in jax.live_arrays() if device in a.devices())


def four_chips(base_argv=BASE_ARGV + FOUR_CHIP_WORKLOAD,
               sessions=FOUR_CHIP_SESSIONS) -> None:
    """The four-replica run against the one-replica run on chip 0."""
    import jax

    out4, s4, streams4, problems = serve(
        base_argv + ["--replicas", "4", "--slots", "2"])
    devices = replica_devices(s4.eng)
    # the last reference to the fleet: its params, caches and stores leave
    # the chips before the comparison run allocates its own on chip 0
    del s4
    left = live_bytes(jax.devices()[0])
    print(f"[four-chips] live bytes on chip 0 after the four-replica run: "
          f"{left}", flush=True)
    if left:
        problems.append(f"the four-replica run left {left} bytes on chip 0")
    if len(set(devices)) != 4 or any(len(d) != 1 for d in devices):
        problems.append(f"replicas are not on four distinct devices: "
                        f"{[sorted(map(str, d)) for d in devices]}")
    if out4["migrations"] <= 0:
        problems.append("no migration crossed chips")
    print(f"[four-chips] migrations {out4['migrations']}, migrated bytes "
          f"{out4['migrated_bytes']}, peak_bytes_in_use per chip "
          f"{[(d.memory_stats() or {}).get('peak_bytes_in_use')
              for d in jax.devices()]}", flush=True)

    out1, s1, streams1, problems1 = serve(
        base_argv + ["--replicas", "1", "--slots", "2"])
    del s1
    problems += problems1
    # the captured streams must be what each run reports it served, or the
    # comparison below could pass over nothing
    for name, out, streams in (("4 replicas", out4, streams4),
                               ("1 replica", out1, streams1)):
        captured = sum(map(len, streams.values()))
        if not 0 < captured == out["tokens"]:
            problems.append(f"{name}: captured {captured} tokens, the "
                            f"launcher reports {out['tokens']}")
        if len(streams) != sessions:
            problems.append(f"{name}: captured {len(streams)} sessions, "
                            f"the workload has {sessions}")
    if set(streams4) != set(streams1):
        problems.append(f"session sets differ: {sorted(streams4)} vs "
                        f"{sorted(streams1)}")
    for uid in sorted(set(streams4) & set(streams1)):
        diff = first_difference(streams4[uid], streams1[uid])
        if diff is not None:
            problems.append(f"uid {uid}: token streams differ first at "
                            f"position {diff[0]} ({diff[1]} with 4 "
                            f"replicas vs {diff[2]} with 1)")
    print(f"[four-chips] {len(streams4)} sessions, "
          f"{sum(map(len, streams4.values()))} tokens compared", flush=True)
    if problems:
        raise SmokeFailure("; ".join(problems))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the four-replica path and its one-replica "
                        "comparison (needs four chips)")
    args = p.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke.py: no repository at {SRC}; run it from the "
              f"root of a checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    import jax
    from repro.launch.compile_cache import use_compile_cache

    devices = jax.devices()
    dev = devices[0]
    print(f"[device] platform {dev.platform}, kind {dev.device_kind}, "
          f"count {len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke.py: JAX found no TPU", file=sys.stderr)
        return 1
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke.py: --four-chips needs 4 chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    cache = use_compile_cache()
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"[compile cache] {cache} ({entries} entries before this run)",
          flush=True)
    try:
        four_chips() if args.four_chips else one_chip()
    except SmokeFailure as e:
        print(f"chip_smoke.py: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
