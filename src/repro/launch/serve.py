"""Serving launcher: the cost-aware scheduler serving synthetic traffic on
one engine or a multi-replica cluster with live session migration.

Quickstart::

  # one replica, cost-aware continuous batching
  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b --reduced \
      --requests 12 --followups 24 --policy cost_aware

  # four replicas on a mesh ring: placement + cost-priced live migration
  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b --reduced \
      --replicas 4 --slots 2 --requests 16 --followups 32

  # the migration-off A/B arm (resumes pinned to their home replica)
  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b --reduced \
      --replicas 4 --no-migrate

This module drives no engine loop of its own: every submit, suspend,
resume — and with ``--replicas > 1`` every placement and migration — is a
:class:`repro.sched.Scheduler` / :class:`repro.sched.ClusterScheduler`
decision.  Admission overflow QUEUES (never EngineFull), suspend/resume
traffic drains as fused waves (one dispatch per replica per wave), and
migrations cross the mesh as priced ``hop_chain`` movement plans.
``--policy fifo`` reproduces the pre-scheduler behavior for A/B runs
(``benchmarks/run.py sched`` and ``cluster`` automate the comparisons).
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro import sched
from repro.configs import get_config, get_reduced
from repro.launch.compile_cache import use_compile_cache
from repro.models import lm
from repro.serve.cluster import Cluster
from repro.serve.engine import Engine, Request

QUICKSTART = """examples:
  %(prog)s --arch tinyllama-1.1b --reduced --requests 12 --followups 24
  %(prog)s --arch tinyllama-1.1b --reduced --replicas 4 --slots 2
  %(prog)s --arch tinyllama-1.1b --reduced --replicas 4 --no-migrate \
--policy fifo

With --replicas N the engines sit on a mesh ring (DESIGN.md Sec. 10):
placement scores each replica by free slots, VILLA fast-tier occupancy and
the modeled ICI hop cost from the session's residence; a resume placed off
its home replica live-migrates the suspended pages as one fused hop-chain
plan per route.  --no-migrate pins every resume to its home replica (the
SLO A/B arm).

Chaos (DESIGN.md Sec. 12): --fault-rate R injects seeded at-rest bit rot
and migration-leg corruption at per-event probability R.  Every corruption
is caught by the per-page checksum sidecar; with recovery on (default) the
scheduler retries corrupted movement legs (priced, backoff on the virtual
clock) and restores corrupt sessions from periodic snapshots; --no-recovery
turns the run into a detection-only audit.  --fault-seed picks the chaos
RNG stream; the same (rate, seed) replays the same faults bit-for-bit:

  %(prog)s --arch tinyllama-1.1b --reduced --replicas 2 --slots 2 \
--fault-rate 0.25 --fault-seed 7

Shared-prefix forking (DESIGN.md Sec. 13): --fork-prefix N prefills one
N-token system prompt ONCE, then admits every fresh session as a zero-copy
FORK of that template (refcounted page alias, RowClone FPM pricing); each
session diverges at its first decode and copies-on-write at its first
suspend.  --no-fork is the A/B arm: the same shared prefix is prepended to
every prompt and prefilled per session.  The prefix must leave room for
the decode budget: --fork-prefix + --max-new <= --max-len.

  %(prog)s --arch tinyllama-1.1b --reduced --requests 12 --fork-prefix 24"""


def main(argv=None) -> dict:
    p = argparse.ArgumentParser(
        epilog=QUICKSTART,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--arch", required=True)
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--replicas", type=int, default=1,
                   help="engine replicas on the mesh ring (default 1; >1 "
                        "enables placement + live session migration)")
    p.add_argument("--no-migrate", action="store_true",
                   help="pin resumes to their home replica (A/B arm; only "
                        "meaningful with --replicas > 1)")
    p.add_argument("--slots", type=int, default=4,
                   help="decode slots PER replica")
    p.add_argument("--max-len", type=int, default=96)
    p.add_argument("--requests", type=int, default=8,
                   help="fresh sessions (may exceed the slot count: "
                        "overflow queues)")
    p.add_argument("--followups", "--resumes", type=int, default=16,
                   dest="followups", help="follow-up (resume) arrivals")
    p.add_argument("--policy", default=None, choices=sched.policies(),
                   help="scheduling policy (default: cost_aware, or "
                        "cost_aware_cluster with --replicas > 1)")
    p.add_argument("--mean-gap-ns", type=float, default=2000.0)
    p.add_argument("--bursty", action="store_true")
    p.add_argument("--zipf-s", type=float, default=1.3)
    p.add_argument("--max-new", type=int, default=8)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--fault-rate", type=float, default=0.0,
                   help="seeded chaos: per-event probability of injecting "
                        "a fault (at-rest bit rot, migration-leg "
                        "corruption); 0 disables injection (default)")
    p.add_argument("--fault-seed", type=int, default=None,
                   help="chaos RNG stream (default: --seed); the same "
                        "(rate, seed) pair replays identical faults")
    p.add_argument("--no-recovery", action="store_true",
                   help="detection-only chaos: count checksum detections "
                        "but never retry or restore (audit arm)")
    p.add_argument("--snapshot-every", type=int, default=4,
                   help="ticks between session-snapshot refreshes backing "
                        "chaos recovery (0 disables snapshots)")
    p.add_argument("--fork-prefix", type=int, default=None, metavar="N",
                   help="serve every fresh session as a zero-copy fork of "
                        "ONE N-token shared system prompt (prefilled once; "
                        "children alias its snapshot and copy-on-write at "
                        "divergence)")
    p.add_argument("--no-fork", action="store_true",
                   help="A/B arm for --fork-prefix: prepend the same shared "
                        "prefix to every prompt and prefill it per session "
                        "(no aliasing)")
    p.add_argument("--trace-out", default=None, metavar="PATH",
                   help="record the run's virtual-clock span trace and "
                        "write it as Chrome trace_events JSON (open in "
                        "Perfetto); composes with every chaos/fork flag — "
                        "fault incidents, retries, snapshots, forks and "
                        "CoW breaks all appear as spans")
    args = p.parse_args(argv)

    wl_prompt_lens = (6, 8, 10, 12)
    if args.replicas < 1:
        p.error(f"--replicas must be >= 1 (got {args.replicas})")
    if args.max_new < 1:
        p.error(f"--max-new must be >= 1 (got {args.max_new})")
    if args.max_len < max(wl_prompt_lens) + args.max_new:
        p.error(f"--max-len {args.max_len} cannot hold the synthetic "
                f"workload: prompts run up to {max(wl_prompt_lens)} tokens "
                f"plus --max-new {args.max_new} decode positions")
    if not 0.0 <= args.fault_rate <= 1.0:
        p.error(f"--fault-rate must be a probability in [0, 1] "
                f"(got {args.fault_rate})")
    if args.snapshot_every < 0:
        p.error(f"--snapshot-every must be >= 0 (got {args.snapshot_every})")
    if args.fault_rate > 0 and args.replicas < 2:
        p.error("--fault-rate needs --replicas >= 2: chaos injection "
                "targets the cluster scheduler (migration legs, replica "
                "storage)")
    if (args.no_recovery or args.fault_seed is not None) \
            and args.fault_rate == 0:
        p.error("--no-recovery / --fault-seed are chaos flags: set "
                "--fault-rate > 0 to enable injection first")
    if args.no_fork and args.fork_prefix is None:
        p.error("--no-fork is the A/B arm of --fork-prefix: set "
                "--fork-prefix N to define the shared prefix first")
    if args.fork_prefix is not None:
        if args.fork_prefix < 1:
            p.error(f"--fork-prefix must be >= 1 (got {args.fork_prefix})")
        # the envelope: a forked child resumes at position N and must fit
        # its whole decode budget before max_len (the engine refuses
        # out-of-envelope resumes — fail fast at the CLI instead)
        if args.fork_prefix + args.max_new > args.max_len:
            p.error(f"--fork-prefix {args.fork_prefix} + --max-new "
                    f"{args.max_new} exceeds --max-len {args.max_len}: the "
                    f"shared prefix must leave room for the decode budget")
        if args.no_fork and (args.fork_prefix + max(wl_prompt_lens)
                             + args.max_new > args.max_len):
            p.error(f"--no-fork prefills the prefix plus each prompt (up "
                    f"to {max(wl_prompt_lens)} tokens): --max-len "
                    f"{args.max_len} is too small for --fork-prefix "
                    f"{args.fork_prefix} + --max-new {args.max_new}")
    policy = args.policy or ("cost_aware_cluster" if args.replicas > 1
                             else "cost_aware")
    use_compile_cache()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    params = lm.init_lm(cfg, jax.random.key(args.seed))

    wl = sched.WorkloadConfig(
        n_fresh=args.requests, n_followups=args.followups,
        mean_gap_ns=args.mean_gap_ns,
        arrival="bursty" if args.bursty else "poisson",
        zipf_s=args.zipf_s, prompt_lens=wl_prompt_lens,
        new_tokens=tuple(sorted({max(args.max_new // 2, 1), args.max_new})))
    arrivals = sched.generate_workload(wl, seed=args.seed,
                                       vocab_size=cfg.vocab_size)
    # the store holds one snapshot per session — admission pressure is the
    # QUEUE's problem (a burst beyond the slot count waits, it never raises
    # EngineFull), store pressure would be silent eviction, so size it out
    n_sessions = sched.n_sessions_for(wl)
    fork_template_uid, fork_seeds, prefix = None, {}, None
    if args.fork_prefix is not None:
        frng = np.random.default_rng(args.seed + 1)
        prefix = frng.integers(0, cfg.vocab_size,
                               args.fork_prefix).astype(np.int32)
        if args.no_fork:
            # A/B arm: the same shared prefix, prefilled per session
            arrivals = [a._replace(prompt=np.concatenate([prefix, a.prompt]))
                        if a.kind == "fresh" else a for a in arrivals]
        else:
            # template homes at row n_sessions (no workload uid maps there);
            # each fresh arrival becomes a RESUME of its forked child, which
            # diverges at the first token of its original prompt
            fork_template_uid = n_sessions
            n_sessions += 1
            fork_seeds = {a.uid: int(a.prompt[0]) for a in arrivals
                          if a.kind == "fresh"}
            arrivals = [a._replace(kind="resume", prompt=None)
                        if a.kind == "fresh" else a for a in arrivals]
    injector = None
    if args.fault_rate > 0:
        from repro.faults import FaultInjector, FaultSpec
        injector = FaultInjector(FaultSpec(
            rate=args.fault_rate,
            seed=args.seed if args.fault_seed is None else args.fault_seed,
            recover=not args.no_recovery))
    tracer = None
    if args.trace_out:
        from repro import movement as MV
        from repro.obs import Tracer
        tracer = Tracer()
        MV.set_tracer(tracer)       # host-side plan executes -> exec marks
    if args.replicas > 1:
        cluster = Cluster(cfg, params, n_replicas=args.replicas,
                          slots=args.slots, max_len=args.max_len,
                          n_sessions=n_sessions, faults=injector)
        s = sched.ClusterScheduler(cluster, policy=policy,
                                   arrivals=arrivals,
                                   migrate=not args.no_migrate,
                                   snapshot_every=(args.snapshot_every
                                                   if injector else 0),
                                   tracer=tracer)
        eng = cluster
    else:
        engine = Engine(cfg, params, slots=args.slots, max_len=args.max_len,
                        n_sessions=n_sessions)
        s = sched.Scheduler(engine, policy=policy, arrivals=arrivals,
                            tracer=tracer)
        eng = engine
    # the engines hold their own copies (bfloat16 matmul weights on a
    # TPU): drop the float32 tree so the chip does not hold both
    del params

    if fork_template_uid is not None:
        # prefill the shared prefix ONCE (max_new=1 auto-suspends at the
        # prefill token) and alias every workload session off its snapshot
        # — zero device dispatches for the whole fan-out
        uids = sorted(fork_seeds)
        if args.replicas > 1:
            cluster.submit(Request(uid=fork_template_uid, prompt=prefix,
                                   max_new=1), replica=0)
            for uid in uids:
                cluster.fork(fork_template_uid, uid,
                             seed_token=fork_seeds[uid])
        else:
            engine.submit(Request(uid=fork_template_uid, prompt=prefix,
                                  max_new=1))
            engine.fork_many(fork_template_uid, uids,
                             seed_tokens=[fork_seeds[u] for u in uids])

    # programs compile at their first call inside the run: JAX's compile
    # events split the run's wall time into compile and serve seconds
    compile_s = 0.0

    def on_event(event: str, secs: float, **_) -> None:
        nonlocal compile_s
        if event.startswith("/jax/core/compile/"):
            compile_s += secs

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        t0 = time.time()
        summary = s.run()
        dt = time.time() - t0
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    eng_stats = eng.stats

    out = {
        "policy": policy,
        "replicas": args.replicas,
        **summary,
        **{k: eng_stats[k] for k in ("decoded_tokens", "suspends", "resumes",
                                     "decode_dispatches", "host_transfers",
                                     "tag_reads", "prefill_reads",
                                     "weight_bytes_bf16",
                                     "weight_bytes_f32")},
        "preempt_suspend": s.metrics.decision_counts().get(
            "preempt_suspend", 0),
        "villa_hit_rate": round(eng.hit_rate(), 3),
        "decode_compile_count": eng.compile_counts()["decode"],
        "ticks": s.tick_count,
        "tokens_per_s": round(eng_stats["decoded_tokens"] / max(dt, 1e-9),
                              1),
        "seconds": round(dt, 1),
        "compile_s": round(compile_s, 3),
        "serve_s": round(dt - compile_s, 3),
        "verify_failed": eng.verify_failure_count(),
    }
    if args.fork_prefix is not None:
        out["fork"] = {
            "enabled": not args.no_fork,
            "prefix_len": args.fork_prefix,
            "prefills": eng_stats["prefills"],
            "forks": eng_stats["forks"],
            "bytes_not_copied": eng_stats["bytes_not_copied"],
            "demotions": eng_stats["demotions"],
            "evictions": eng_stats["evictions"],
        }
    if args.replicas > 1:
        out["migrations"] = eng_stats["migrations"]
        out["migrated_bytes"] = eng_stats["migrated_bytes"]
    if injector is not None:
        out["fault_ledger"] = injector.summary()
        out["at_rest_corrupt"] = int(eng.scrub())
    if tracer is not None:
        from repro import movement as MV
        from repro.obs import write_chrome_trace
        MV.set_tracer(None)         # don't leak into later runs in-process
        write_chrome_trace(tracer, args.trace_out)
        roll = tracer.rollup()
        # replaces the summary's rollup-only "trace" key with the launcher
        # digest: per-phase span counts, per-leg ns split, top-5 spans
        out["trace"] = {
            "spans": roll["spans"],
            "per_phase": {k: v["count"]
                          for k, v in roll["per_phase"].items()},
            "legs": roll["legs"],
            "top_spans_ns": tracer.top_spans(5),
            "chrome_trace": args.trace_out,
        }
    print(json.dumps(out, allow_nan=False))
    return out


if __name__ == "__main__":
    main()
