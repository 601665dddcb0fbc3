"""Benchmark harness — one entry per paper table/figure + system benches.

Prints ``name,us_per_call,derived`` CSV rows:
  * ``table1_*`` / ``fig2_*``  — Table 1 / Fig. 2: 8 KB copy latency+energy
    per mechanism, from the functional substrate (data-correct copies) with
    the calibrated command-level timing model; derived = modeled ns / uJ and
    the paper's headline ratios.
  * ``fig3_*``  — VILLA hit rate + weighted-speedup improvement on the
    synthetic 4-core workloads (Ramulator-style controller sim).
  * ``fig4_*``  — combined RISC/+VILLA/+LIP speedups and energy reduction.
  * ``rbm_bandwidth`` — Sec. 2's 26x-channel claim.
  * ``kernel_*`` — Pallas kernels (interpret mode) vs jnp oracles.
  * ``ring_*``  — LISA hop-chain collectives on 8 host devices (subprocess).
  * ``train/serve_throughput`` — end-to-end reduced-model system benches.
  * ``bank_*`` — bank-contention A/B: load-dependent p99, wave overlap
    vs serialization, refresh stalls (writes ``BENCH_bank.json``).
  * ``roofline_*`` — live lowering + HLO byte/flop attribution of every
    audited jitted entry point (writes ``ROOFLINE_REPORT.json``).

Every invocation appends its headline gates to ``BENCH_TRAJECTORY.jsonl``
(strict JSON per line, monotone ``seq`` — validated by ``--check``).
"""
from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys
import time

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
sys.path.insert(0, SRC)

import jax                                                     # noqa: E402
import jax.numpy as jnp                                        # noqa: E402
import numpy as np                                             # noqa: E402

ROWS = []


def row(name, us, derived):
    ROWS.append((name, us, derived))
    print(f"{name},{us:.2f},{derived}", flush=True)


def _time(fn, n=5, warmup=1):
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        ts.append((time.perf_counter() - t0) * 1e6)
    return statistics.median(ts)


# ---------------------------------------------------------------------------
def bench_table1():
    from repro.core.dram import substrate as S
    from repro.core.dram.spec import DDR3_1600

    # Costs are reported from the full-geometry spec (Table-1 exact); the
    # functional bank uses short 1 KB rows so the data-correct copy we *time*
    # stays small.
    spec = DDR3_1600
    bank_spec = DDR3_1600.with_geometry(16, 16, 1024)
    bank = S.make_bank(bank_spec, key=jax.random.key(0))
    paper = {"RC-InterSA": (1363.75, 4.33), "RC-Bank": (701.25, 2.08),
             "RC-IntraSA": (83.75, 0.06), "LISA-RISC-1": (148.5, 0.09),
             "LISA-RISC-7": (196.5, 0.12), "LISA-RISC-15": (260.5, 0.17),
             "memcpy": (None, 6.2)}
    # Table-1 row -> (registry mechanism, src_sa, src_row, dst_sa, dst_row):
    # each row times the mechanism actually named, via execute_copy.
    copies = {"memcpy": ("memcpy", 0, 1, 7, 2),
              "RC-InterSA": ("rc_intersa", 0, 1, 7, 2),
              "RC-Bank": ("rc_bank", 0, 1, 7, 2),
              "RC-IntraSA": ("rc_intrasa", 0, 1, 0, 2),
              "LISA-RISC-1": ("lisa", 0, 1, 1, 2),
              "LISA-RISC-7": ("lisa", 0, 1, 7, 2),
              "LISA-RISC-15": ("lisa", 0, 1, 15, 2)}
    for mech, (lat, ene) in spec.table1().items():
        plat, pene = paper[mech]
        name, *args = copies[mech]
        us = _time(lambda: jax.block_until_ready(
            S.execute_copy(bank, name, *args,
                           spec=bank_spec).state.row_buffer))
        row(f"table1_{mech}", us,
            f"lat_ns={lat:.2f};paper={plat};energy_uJ={ene:.3f};paper={pene}")
    lat1, e1 = spec.table1()["LISA-RISC-1"]
    row("fig2_latency_ratio_vs_rowclone", 0.0,
        f"{spec.copy_latency('rc_intersa')/lat1:.1f}x;paper=9x")
    row("fig2_energy_ratio_vs_rowclone", 0.0,
        f"{spec.copy_energy('rc_intersa')/e1:.1f}x;paper=48x")
    row("fig2_energy_ratio_vs_memcpy", 0.0,
        f"{spec.copy_energy('memcpy')/e1:.1f}x;paper=69x")
    row("rbm_bandwidth", 0.0,
        f"{spec.rbm_bw_gbps:.0f}GB/s="
        f"{spec.rbm_bw_gbps/spec.channel_bw_gbps:.1f}x_channel;paper=26x")


def bench_fig3_fig4():
    from repro.core.dram.controller import (MechanismConfig, simulate_grid,
                                            weighted_speedup)
    from repro.core.dram.traces import TraceConfig, generate_batch

    # "50 workloads": sweep copy-intensity x locality (5 x 5 x 2 seeds).
    # All 50 traces are generated in one vmapped call (workload knobs are
    # traced data) and the whole (mechanism x workload) grid runs as ONE
    # vmapped execution of the single jitted simulator (mechanism config is
    # traced data too), instead of re-jitting per cell.
    t0 = time.perf_counter()
    tcfg = TraceConfig(n_requests=4096)
    cells = [(copy_prob, zipf, seed)
             for copy_prob in (0.002, 0.005, 0.01, 0.02, 0.04)
             for zipf in (1.0, 1.2, 1.4, 1.6, 1.8)
             for seed in (1, 2)]
    traces = generate_batch(
        jnp.stack([jax.random.key(s) for _, _, s in cells]),
        jnp.asarray([cp for cp, _, _ in cells]),
        jnp.asarray([z for _, z, _ in cells]), tcfg)
    names = ["base", "lisa", "villa", "comb", "rc_villa", "lip"]
    grid = simulate_grid(traces, tcfg, [
        MechanismConfig("memcpy"),
        MechanismConfig("lisa"),
        MechanismConfig("lisa", use_villa=True),
        MechanismConfig("lisa", use_villa=True, use_lip=True),
        MechanismConfig("memcpy", use_villa=True,
                        villa_copy_mech="rc_intersa"),
        MechanismConfig("memcpy", use_lip=True),
    ])
    jax.block_until_ready(grid)
    base = {k: v[0] for k, v in grid.items()}
    res = {n: {k: v[i] for k, v in grid.items()}
           for i, n in enumerate(names) if n != "base"}
    ws_all = {k: np.asarray(weighted_speedup(base["core_stall"],
                                             r["core_stall"]))
              for k, r in res.items()}
    hits = np.asarray(res["villa"]["villa_hit_rate"])
    en_red = 1 - np.asarray(res["comb"]["energy_uJ"]) / np.asarray(
        base["energy_uJ"])
    total_us = (time.perf_counter() - t0) * 1e6 / 50
    gm = lambda xs: float(np.exp(np.mean(np.log(np.maximum(xs, 1e-9)))))
    row("fig3_villa_hit_rate", total_us,
        f"mean={np.mean(hits):.3f};paper_range=0.15-0.8")
    row("fig3_villa_vs_risc_gain", total_us,
        f"+{(gm(ws_all['villa'])/gm(ws_all['lisa'])-1)*100:.1f}%;paper=+16.5%")
    row("fig3_rc_villa_ws", total_us,
        f"{(gm(ws_all['rc_villa'])-1)*100:.1f}%;paper=-52.3%")
    row("fig4_risc_ws", total_us,
        f"+{(gm(ws_all['lisa'])-1)*100:.1f}%;paper=+59.6%")
    row("fig4_lip_over_risc_villa", total_us,
        f"+{(gm(ws_all['comb'])/gm(ws_all['villa'])-1)*100:.1f}%;paper=+8.8%")
    row("fig4_lip_alone_ws", total_us,
        f"+{(gm(ws_all['lip'])-1)*100:.1f}%;paper=+10.3%")
    row("fig4_combined_ws", total_us,
        f"+{(gm(ws_all['comb'])-1)*100:.1f}%;paper=+94.8%")
    row("fig4_combined_energy_reduction", total_us,
        f"-{np.mean(en_red)*100:.1f}%;paper=-49%")


def bench_kernels():
    from repro.kernels import ops
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (1, 8, 256, 64), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 4, 256, 64), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 4, 256, 64), jnp.bfloat16)
    us_k = _time(lambda: jax.block_until_ready(
        ops.flash_attention(q, k, v, block_q=64, block_k=64)))
    us_r = _time(lambda: jax.block_until_ready(
        ops.flash_attention_ref(q, k, v)))
    err = float(jnp.abs(
        ops.flash_attention(q, k, v, block_q=64, block_k=64).astype(jnp.float32)
        - ops.flash_attention_ref(q, k, v).astype(jnp.float32)).max())
    row("kernel_flash_attention_interpret", us_k,
        f"ref_us={us_r:.0f};max_err={err:.1e}")

    x = jax.random.normal(jax.random.key(1), (512, 512))
    us_c = _time(lambda: jax.block_until_ready(ops.rbm_copy(x)))
    row("kernel_rbm_copy_interpret", us_c,
        f"bytes={x.size*4};ok={bool((ops.rbm_copy(x)==x).all())}")

    pages = jax.random.normal(jax.random.key(2), (32, 8, 128))
    table = jnp.arange(16, dtype=jnp.int32) % 32
    us_g = _time(lambda: jax.block_until_ready(ops.villa_gather(pages, table)))
    ok = bool((ops.villa_gather(pages, table) == pages[table]).all())
    row("kernel_villa_gather_interpret", us_g, f"ok={ok}")

    upd = jax.random.normal(jax.random.key(3), (16, 8, 128))
    # non-donating non-jit entry so the timed region is the scatter alone
    # (ops.villa_scatter donates its pages arg)
    from repro.kernels.rbm_copy import villa_scatter as scatter_nodonate
    scat = jax.jit(scatter_nodonate, static_argnames=("interpret",))
    us_s = _time(lambda: jax.block_until_ready(scat(pages, table, upd)))
    ok = bool((scat(pages, table, upd)
               == ops.villa_scatter_ref(pages, table, upd)).all())
    row("kernel_villa_scatter_interpret", us_s, f"ok={ok}")


RING_BENCH = r"""
import time, statistics, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core.lisa import rbm

mesh = jax.make_mesh((8,), ("x",))
x = jax.random.normal(jax.random.key(0), (8, 1 << 16))

ring = jax.jit(jax.shard_map(lambda s: rbm.ring_allreduce(s, "x"),
                             mesh=mesh, in_specs=P("x"), out_specs=P("x")))
psum = jax.jit(jax.shard_map(lambda s: jax.lax.psum(s, "x"),
                             mesh=mesh, in_specs=P("x"), out_specs=P("x")))
def t(f):
    f(x).block_until_ready()
    ts = []
    for _ in range(5):
        t0 = time.perf_counter(); f(x).block_until_ready()
        ts.append((time.perf_counter()-t0)*1e6)
    return statistics.median(ts)
ru, pu = t(ring), t(psum)
ok = bool(jnp.allclose(ring(x), psum(x), atol=1e-4))
print(f"RESULT,{ru:.1f},{pu:.1f},{ok}")
"""


def bench_ring_collectives():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    # a host-device rehearsal: pinned to the CPU, so the child never
    # contends with this process (which imported JAX) for an accelerator
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", RING_BENCH],
                       capture_output=True, text=True, timeout=480, env=env)
    for line in r.stdout.splitlines():
        if line.startswith("RESULT"):
            _, ru, pu, ok = line.split(",")
            row("ring_allreduce_8dev", float(ru),
                f"xla_psum_us={pu};allclose={ok}")
            return
    raise RuntimeError(f"ring_allreduce_8dev child failed "
                       f"(rc {r.returncode}):\n{r.stderr[-2000:]}")


def bench_train_throughput():
    from repro.configs import get_reduced
    from repro.data.pipeline import DataConfig, batch_at
    from repro.launch.mesh import make_local_mesh
    from repro.optim.adamw import OptConfig
    from repro.train.step import (ParallelConfig, init_train_state,
                                  make_train_step)
    cfg = get_reduced("tinyllama-1.1b")
    pcfg = ParallelConfig(fsdp=False)
    state = init_train_state(cfg, jax.random.key(0), pcfg)
    _, compile_step, _ = make_train_step(
        cfg, make_local_mesh(1, 1), pcfg,
        OptConfig(warmup_steps=1, total_steps=100))
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=128, global_batch=8)
    batch = batch_at(dcfg, 0)
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          (state, batch))
    step = compile_step(*shapes)
    state, _ = step(state, batch)                     # warmup/compile
    t0 = time.perf_counter()
    n = 5
    for i in range(n):
        state, m = step(state, batch_at(dcfg, i + 1))
    jax.block_until_ready(m["loss"])
    dt = time.perf_counter() - t0
    toks = n * 8 * 128
    row("train_throughput_reduced_cpu", dt / n * 1e6,
        f"tokens_per_s={toks/dt:.0f};loss={float(m['loss']):.3f}")


def bench_serve_throughput(out_path="BENCH_serve.json"):
    """Serving hot path A/B: one-sync batched decode vs the pre-PR grouped
    path, plus paged suspend/resume bandwidth.  Writes ``BENCH_serve.json``.

    Prompt lengths are staggered so slot positions stay ragged — the
    continuous-batching steady state, where the grouped path degrades to one
    dispatch per distinct position plus one sync per slot."""
    from repro.configs import get_reduced
    from repro.models import lm as LM
    from repro.serve.engine import Engine, Request

    cfg = get_reduced("tinyllama-1.1b")
    params = LM.init_lm(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, ln).astype(np.int32)
               for ln in (6, 9, 12, 15)]
    n_steps = 48

    def run(step_name):
        eng = Engine(cfg, params, slots=4, max_len=96, n_sessions=16)
        for i, p in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=p, max_new=10**9))
        step = getattr(eng, step_name)
        step()                                   # warm the jit caches
        t0 = time.perf_counter()
        for _ in range(n_steps):
            step()
        jax.block_until_ready(eng.cache)
        dt = time.perf_counter() - t0
        return eng, n_steps * len(prompts) / dt, dt

    eng_new, tps_new, dt_new = run("step")
    eng_old, tps_old, dt_old = run("step_unbatched")
    speedup = tps_new / tps_old

    # suspend/resume bandwidth through the paged VILLA store (Pallas
    # gather/scatter path); bytes are true dtype bytes, both directions.
    eng = Engine(cfg, params, slots=4, max_len=96, n_sessions=16)
    eng.submit(Request(uid=0, prompt=prompts[0], max_new=2))
    while eng.active:
        eng.step()
    slot = eng.resume(0, extra_new=2)            # warm suspend/resume jits
    eng.suspend(slot)
    n_moves = 24
    t0 = time.perf_counter()
    for _ in range(n_moves):
        eng.suspend(eng.resume(0, extra_new=2))
    jax.block_until_ready(eng.sessions.slow)
    dt_mv = time.perf_counter() - t0
    gbps = 2 * n_moves * eng.snapshot_bytes / dt_mv / 1e9

    # fused waves: a burst of equal-length requests completes on one step
    # (ONE suspend_many dispatch), then the whole burst resumes in ONE
    # resume_many dispatch — the compile counts pin both waves to a single
    # compilation (pre-fix this bench never drove a wave, so the recorded
    # resume_many count was a vacuous 0).
    eng_w = Engine(cfg, params, slots=4, max_len=96, n_sessions=16)
    for i in range(4):
        eng_w.submit(Request(uid=i, prompt=prompts[0], max_new=3))
    while eng_w.active:
        eng_w.step()                     # burst completion: one fused wave
    assert eng_w.stats["suspends"] == 4, eng_w.stats
    eng_w.resume_many([0, 1, 2, 3], extra_new=2)     # one fused resume wave
    while eng_w.active:
        eng_w.step()
    wave_cc = eng_w.compile_counts()
    assert wave_cc["suspend_many"] == 1, wave_cc
    assert wave_cc["resume_many"] == 1, wave_cc

    bench = {
        "decode_tokens_per_s": round(tps_new, 1),
        "legacy_tokens_per_s": round(tps_old, 1),
        "decode_speedup": round(speedup, 2),
        "decode_dispatches_per_step": eng_new.stats["decode_dispatches"]
        / (n_steps + 1),
        "legacy_dispatches_per_step": eng_old.stats["decode_dispatches"]
        / (n_steps + 1),
        "suspend_resume_gbps": round(gbps, 4),
        "snapshot_bytes": eng.snapshot_bytes,
        # decode/prefill from the throughput engine, suspend/resume from the
        # bandwidth engine, the fused waves from the wave engine (each from
        # the engine that exercised that path)
        "compile_counts": {**eng_new.compile_counts(),
                           "suspend": eng.compile_counts()["suspend"],
                           "resume": eng.compile_counts()["resume"],
                           "suspend_many": wave_cc["suspend_many"],
                           "resume_many": wave_cc["resume_many"]},
        "wave": {"suspend_wave_sessions": 4, "resume_wave_sessions": 4,
                 "suspend_many_compiles": wave_cc["suspend_many"],
                 "resume_many_compiles": wave_cc["resume_many"]},
        "config": {"arch": "tinyllama-1.1b-reduced", "slots": 4,
                   "max_len": 96, "steps": n_steps,
                   "prompt_lens": [len(p) for p in prompts]},
    }
    with open(out_path, "w") as f:
        json.dump(bench, f, indent=2, allow_nan=False)
    row("serve_decode_one_sync", 1e6 / max(tps_new, 1e-9),
        f"tokens_per_s={tps_new:.1f};speedup_vs_grouped={speedup:.2f}x")
    row("serve_decode_grouped_legacy", 1e6 / max(tps_old, 1e-9),
        f"tokens_per_s={tps_old:.1f}")
    row("serve_suspend_resume_paged", dt_mv / (2 * n_moves) * 1e6,
        f"GB/s={gbps:.3f};snapshot_bytes={eng.snapshot_bytes}")
    row("serve_decode_compile_count", 0.0,
        f"{bench['compile_counts']['decode']}")
    row("serve_fused_wave_compiles", 0.0,
        f"suspend_many={wave_cc['suspend_many']};"
        f"resume_many={wave_cc['resume_many']}")


def bench_movement(out_path="BENCH_movement.json"):
    """Movement-substrate A/B: the planned path (movement.plan/execute
    inside the engine's jitted suspend/resume) vs the pre-redesign legacy
    path (the same pack + VILLA policy + Pallas gather/scatter, called
    directly without plans).  Both lower to the same XLA; the bench pins
    the plan/execute indirection at <= 5% overhead (it is trace-time-only)
    and records the plans' modeled MovementCost.  Writes
    ``BENCH_movement.json``."""
    import statistics as stats
    import warnings as W
    from functools import partial

    from repro.configs import get_reduced
    from repro.core.dram.villa import villa_access
    from repro.kernels.rbm_copy import villa_gather, villa_scatter
    from repro.models import lm as LM
    from repro.serve import paged_store as PSm
    from repro.serve.engine import Engine, Request

    cfg = get_reduced("tinyllama-1.1b")
    params = LM.init_lm(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)

    def make_engine():
        eng = Engine(cfg, params, slots=4, max_len=96, n_sessions=16)
        eng.submit(Request(uid=0, prompt=prompt, max_new=2))
        while eng.active:
            eng.step()
        return eng

    eng = make_engine()
    pspec, vcfg = eng.page_spec, eng.villa_cfg

    # ---- legacy direct-call path: pre-redesign movement, no plans --------
    def _read(arr, i):
        n_, spp, P, d = arr.shape
        tbl = i * spp + jnp.arange(spp, dtype=jnp.int32)
        return villa_gather(arr.reshape(n_ * spp, P, d), tbl)

    def _write(arr, i, data):
        n_, spp, P, d = arr.shape
        tbl = i * spp + jnp.arange(spp, dtype=jnp.int32)
        return villa_scatter(arr.reshape(n_ * spp, P, d), tbl,
                             data).reshape(arr.shape)

    @partial(jax.jit, donate_argnums=(1,))
    def legacy_suspend(cache, store, slot, idx):
        pages = PSm.pack_slot(pspec, cache, slot)
        slow = _write(store.slow, idx, pages)
        resident = store.policy.tags == idx
        s = jnp.argmax(resident)
        fast = jnp.where(resident.any(), _write(store.fast, s, pages),
                         store.fast)
        return store._replace(slow=slow, fast=fast)

    @partial(jax.jit, donate_argnums=(0, 1))
    def legacy_resume(cache, store, slot, idx):
        policy, hit, insert, victim = villa_access(store.policy, idx, vcfg)
        slow_data = _read(store.slow, idx)
        fast = jnp.where(insert, _write(store.fast, victim, slow_data),
                         store.fast)
        s = jnp.argmax(policy.tags == idx)
        pages = jnp.where(hit, _read(fast, s), slow_data)
        store = store._replace(policy=policy, fast=fast,
                               hits=store.hits + hit.astype(jnp.int32),
                               accesses=store.accesses + 1)
        return PSm.unpack_into_slot(pspec, cache, slot, pages), store

    # Both paths driven at identical granularity: the jitted move bodies.
    zero = jnp.int32(0)

    def drive_planned(state, n):
        cache, store = state
        for _ in range(n):
            cache, store = eng._resume(cache, store, zero, zero)
            store = eng._suspend(cache, store, zero, zero)
        jax.block_until_ready(store.slow)
        return cache, store

    def drive_legacy(state, n):
        cache, store = state
        for _ in range(n):
            cache, store = legacy_resume(cache, store, zero, zero)
            store = legacy_suspend(cache, store, zero, zero)
        jax.block_until_ready(store.slow)
        return cache, store

    n_moves, rounds = 16, 5
    with W.catch_warnings():
        W.filterwarnings("ignore",
                         message="Some donated buffers were not usable")
        st_p = (eng.cache, eng.sessions)
        eng2 = make_engine()
        st_l = (eng2.cache, eng2.sessions)
        st_p = drive_planned(st_p, 2)            # warm both jit caches
        st_l = drive_legacy(st_l, 2)
        t_planned, t_legacy = [], []
        for _ in range(rounds):                  # interleave to share noise
            t0 = time.perf_counter()
            st_p = drive_planned(st_p, n_moves)
            t_planned.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            st_l = drive_legacy(st_l, n_moves)
            t_legacy.append(time.perf_counter() - t0)
    us_planned = stats.median(t_planned) / (2 * n_moves) * 1e6
    us_legacy = stats.median(t_legacy) / (2 * n_moves) * 1e6
    ratio = us_planned / us_legacy

    cc = eng.compile_counts()
    bench = {
        "planned_us_per_move": round(us_planned, 2),
        "legacy_us_per_move": round(us_legacy, 2),
        "planned_over_legacy": round(ratio, 4),
        "within_5pct": bool(ratio <= 1.05),
        # deterministic trace-time-only guard: the planned bodies compile
        # once each, however many moves ran
        "planned_compile_counts": {"suspend": cc["suspend"],
                                   "resume": cc["resume"]},
        "snapshot_bytes": eng.snapshot_bytes,
        "plan_suspend": eng.plan_suspend.describe(),
        "plan_resume": eng.plan_resume.describe(),
        "modeled_ns_lisa_per_move": eng.plan_resume.cost.ns_lisa,
        "modeled_ns_memcpy_per_move": eng.plan_resume.cost.ns_memcpy,
        "modeled_advantage": round(eng.plan_resume.cost.advantage, 2),
        "config": {"arch": "tinyllama-1.1b-reduced", "n_moves": n_moves,
                   "rounds": rounds, "workload": "serve suspend/resume"},
    }
    with open(out_path, "w") as f:
        json.dump(bench, f, indent=2, allow_nan=False)
    row("movement_planned_suspend_resume", us_planned,
        f"ratio_vs_legacy={ratio:.3f};within_5pct={bench['within_5pct']}")
    row("movement_legacy_suspend_resume", us_legacy,
        f"modeled_advantage={bench['modeled_advantage']}x")


def bench_sched(out_path="BENCH_sched.json"):
    """Scheduler A/B: ``fifo`` vs ``cost_aware`` serving the SAME offered
    load (identical arrival stream, engine geometry and virtual-clock
    constants).  Latency runs on the scheduler's modeled clock — decode
    ticks plus occupancy-aware Table-1 movement pricing — so the comparison
    is deterministic (job completion depends on token *counts*, never token
    values) and CI can gate on it: ``cost_aware`` must beat ``fifo`` on p99
    latency or SLO attainment, and every scheduler-issued suspend/resume
    must stay ONE fused dispatch per wave (compile-count asserted).
    Writes ``BENCH_sched.json``."""
    from repro import sched
    from repro.configs import get_reduced
    from repro.models import lm as LM
    from repro.serve.engine import Engine

    cfg = get_reduced("tinyllama-1.1b")
    params = LM.init_lm(cfg, jax.random.key(0))
    wl = sched.WorkloadConfig(
        n_fresh=8, n_followups=28, mean_gap_ns=1600.0,
        arrival="bursty", burst=4, zipf_s=1.8, think_ns=2000.0,
        class_slo_ns=(40_000.0, 150_000.0, float("inf")))
    arrivals = sched.generate_workload(wl, seed=4, vocab_size=cfg.vocab_size)

    results = {}
    for pol in ("fifo", "cost_aware"):
        eng = Engine(cfg, params, slots=4, max_len=96,
                     n_sessions=sched.n_sessions_for(wl))
        tracer = None
        if pol == "cost_aware":
            # the headline arm runs traced: zero device dispatches, zero
            # schedule impact — the summary gains a "trace" rollup block
            from repro.obs import Tracer
            tracer = Tracer()
        s = sched.Scheduler(eng, policy=pol, arrivals=arrivals,
                            tracer=tracer)
        t0 = time.perf_counter()
        summary = s.run()
        dt = time.perf_counter() - t0
        resume_widths = s.metrics.wave_widths("resume_wave")
        suspend_widths = (s.metrics.wave_widths("preempt_suspend")
                          + s.metrics.wave_widths("complete_suspend"))
        cc = eng.compile_counts()
        # fused-dispatch invariants: every resume the engine performed came
        # from a scheduler wave, and each distinct wave width compiles once
        assert eng.stats["resumes"] == sum(resume_widths), (pol, resume_widths)
        assert eng.stats["suspends"] == sum(suspend_widths), (pol,
                                                              suspend_widths)
        # resume waves always route through resume_many (any width); a
        # single-slot suspend routes through the unbatched suspend body —
        # so each entry point compiles at most once per distinct wave width
        n_resume_shapes = len(set(resume_widths))
        n_suspend_shapes = len({w for w in suspend_widths if w > 1})
        assert cc["resume_many"] in range(n_resume_shapes + 1), (
            pol, resume_widths, cc)
        assert cc["suspend_many"] in range(n_suspend_shapes + 1), (
            pol, suspend_widths, cc)
        results[pol] = {
            **summary,
            "ticks": s.tick_count,
            "resume_wave_widths": resume_widths,
            "compile_counts": {k: cc[k] for k in
                               ("decode", "resume_many", "suspend_many")},
            "wall_seconds": round(dt, 2),
        }

    fifo, ca = results["fifo"], results["cost_aware"]
    p99_gain = fifo["p99_latency_ns"] / max(ca["p99_latency_ns"], 1e-9)
    slo_gain = ca["slo_attainment"] - fifo["slo_attainment"]
    import dataclasses
    import math
    # strict-JSON artifact: the batch class's infinite SLO must not leak as
    # a bare `Infinity` literal (json.dump emits it for float('inf'))
    load = {k: ([("inf" if isinstance(x, float) and math.isinf(x) else x)
                 for x in v] if isinstance(v, tuple) else v)
            for k, v in dataclasses.asdict(wl).items()}
    bench = {
        **results,
        "p99_speedup_cost_aware": round(p99_gain, 3),
        "slo_attainment_gain": round(slo_gain, 4),
        "cost_aware_beats_fifo": bool(p99_gain > 1.0 or slo_gain > 0.0),
        "config": {"arch": "tinyllama-1.1b-reduced", "slots": 4,
                   "seed": 4, "offered_load": load},
    }
    with open(out_path, "w") as f:
        json.dump(bench, f, indent=2, allow_nan=False)
    row("sched_fifo", 0.0,
        f"p99_us={fifo['p99_latency_ns']/1e3:.1f};"
        f"slo={fifo['slo_attainment']}")
    row("sched_cost_aware", 0.0,
        f"p99_us={ca['p99_latency_ns']/1e3:.1f};slo={ca['slo_attainment']};"
        f"p99_speedup={p99_gain:.2f}x;beats_fifo="
        f"{bench['cost_aware_beats_fifo']}")
    row("sched_movement_advantage", 0.0,
        f"{ca['movement']['advantage']}x_lisa_vs_memcpy")


def bench_cluster(out_path="BENCH_cluster.json"):
    """Cluster serving A/Bs on the deterministic virtual clock (the
    BENCH_sched idiom: completion depends on token COUNTS, never values,
    so CI gates on exact numbers).  Two comparisons:

      * **1 vs 4 replicas at equal offered load** — the same arrival
        stream driven through a 1-replica and a 4-replica cluster; the
        4-replica run must complete >= 2x the jobs before deadline misses
        begin (and strictly more jobs within SLO).
      * **migration on vs off** — a skewed-residence burst (sessions
        concentrated on one replica while long jobs pin the others, then
        all return at once with a tight SLO); migration-enabled placement
        fans the burst across idle replicas via priced hop-chain plans,
        migration-off serializes on the home replica and misses.

    Writes ``BENCH_cluster.json``."""
    import math

    from repro import sched
    from repro.configs import get_reduced
    from repro.models import lm as LM
    from repro.serve.cluster import Cluster

    cfg = get_reduced("tinyllama-1.1b")
    params = LM.init_lm(cfg, jax.random.key(0))

    def jobs_before_first_miss(records):
        n = 0
        for j in sorted(records, key=lambda r: r.done_ns):
            if math.isfinite(j.slo_ns) and not j.slo_met:
                break
            n += 1
        return n

    def in_slo_jobs(records):
        return sum(1 for j in records
                   if math.isfinite(j.slo_ns) and j.slo_met)

    # ---- 1 vs 4 replicas, equal offered load -----------------------------
    wl = sched.WorkloadConfig(
        n_fresh=12, n_followups=24, mean_gap_ns=900.0, arrival="bursty",
        burst=4, zipf_s=1.4, think_ns=2500.0,
        class_slo_ns=(35_000.0, 120_000.0, math.inf))
    arrivals = sched.generate_workload(wl, seed=4, vocab_size=cfg.vocab_size)
    scale = {}
    for n_rep in (1, 4):
        cl = Cluster(cfg, params, n_replicas=n_rep, slots=4, max_len=96,
                     n_sessions=sched.n_sessions_for(wl))
        s = sched.ClusterScheduler(cl, arrivals=arrivals)
        t0 = time.perf_counter()
        summary = s.run()
        scale[f"replicas{n_rep}"] = {
            "jobs_completed": summary["jobs_completed"],
            "jobs_before_first_miss": jobs_before_first_miss(s.metrics.jobs),
            "jobs_in_slo": in_slo_jobs(s.metrics.jobs),
            "p99_latency_ns": summary["p99_latency_ns"],
            "slo_attainment": summary["slo_attainment"],
            "ticks": s.tick_count,
            "decode_compiles": cl.compile_counts()["decode"],
            "wall_seconds": round(time.perf_counter() - t0, 2),
        }
    r1, r4 = scale["replicas1"], scale["replicas4"]
    scaling = r4["jobs_before_first_miss"] / max(
        r1["jobs_before_first_miss"], 1)

    # ---- migration on vs off (skewed-residence burst) --------------------
    # one scenario definition, two drivers: tests/test_cluster.py asserts
    # the same stream at test scale (sched.skewed_residence_burst)
    mig = {}
    for enabled in (True, False):
        cl = Cluster(cfg, params, n_replicas=4, slots=1, max_len=96,
                     n_sessions=128)
        s = sched.ClusterScheduler(
            cl, arrivals=sched.skewed_residence_burst(cfg.vocab_size),
            cfg=sched.SchedConfig(age_every=64), migrate=enabled)
        summary = s.run()
        burst = [j for j in s.metrics.jobs if j.priority == 0]
        mig["migration_on" if enabled else "migration_off"] = {
            "jobs_completed": summary["jobs_completed"],
            "slo_attainment": summary["slo_attainment"],
            "burst_slo_met": sum(j.slo_met for j in burst),
            "burst_jobs": len(burst),
            "sessions_migrated": summary["migration"]["sessions_migrated"],
            "p99_latency_ns": summary["p99_latency_ns"],
            "per_replica_utilization": summary["per_replica_utilization"],
        }
    on, off = mig["migration_on"], mig["migration_off"]

    bench = {
        **scale,
        "scaling_before_miss": round(scaling, 2),
        "scales_2x": bool(scaling >= 2.0
                          and r4["jobs_in_slo"] > r1["jobs_in_slo"]),
        **mig,
        "migration_wins": bool(
            on["slo_attainment"] > off["slo_attainment"]
            and on["sessions_migrated"] >= 2
            and off["sessions_migrated"] == 0),
        "config": {"arch": "tinyllama-1.1b-reduced", "seed": 4,
                   "scale_slots_per_replica": 4,
                   "migration_slots_per_replica": 1,
                   "offered_load": "bursty gap=900 zipf=1.4 12f+24r",
                   "burst": "4-session skewed-residence, slo=18us"},
    }
    with open(out_path, "w") as f:
        json.dump(bench, f, indent=2, allow_nan=False)
    row("cluster_scale_1v4", 0.0,
        f"before_miss={r1['jobs_before_first_miss']}->"
        f"{r4['jobs_before_first_miss']};x{bench['scaling_before_miss']};"
        f"slo={r1['slo_attainment']}->{r4['slo_attainment']}")
    row("cluster_migration_ab", 0.0,
        f"slo_on={on['slo_attainment']};slo_off={off['slo_attainment']};"
        f"migrations={on['sessions_migrated']};"
        f"wins={bench['migration_wins']}")


def bench_faults(out_path="BENCH_faults.json"):
    """Chaos bench: seeded fault injection against the cluster scheduler,
    gated on the zero-silent-corruption identity and graceful degradation.
    Four deterministic scenarios (fixed seeds, virtual-clock latencies):

      * **detection** — recovery OFF: every injected at-rest corruption is
        accounted for — caught by the device-side checksum verify at resume
        or still sitting at rest for the end-of-run scrub.  No incident is
        ever silent.
      * **recovery** — recovery ON with periodic snapshots: corrupt
        sessions are restored from their last clean snapshot before they
        resume; the same ledger identity holds.
      * **recovery_parity** — replica death mid-service: snapshot, fail
        the replica, restore the session on a survivor, resume — the
        decode must be token-identical to the uninterrupted run (the PR 5
        migration-parity chain, extended across a failure).
      * **degradation** — the same offered load clean vs faulted: the
        faulted run must retain >= 70% of the clean run's SLO attainment
        and still complete every job (graceful, not collapsing).

    Writes ``BENCH_faults.json``."""
    from repro import sched
    from repro.configs import get_reduced
    from repro.faults import (FaultInjector, FaultSpec, restore_session,
                              snapshot_sessions)
    from repro.models import lm as LM
    from repro.serve.cluster import Cluster
    from repro.serve.engine import Request

    cfg = get_reduced("tinyllama-1.1b")
    params = LM.init_lm(cfg, jax.random.key(0))
    wl = sched.WorkloadConfig(n_fresh=4, n_followups=6)
    arrivals = sched.generate_workload(wl, seed=5, vocab_size=cfg.vocab_size)
    n_sessions = sched.n_sessions_for(wl)

    def chaos_run(spec, snapshot_every=0):
        inj = FaultInjector(spec) if spec is not None else None
        cl = Cluster(cfg, params, n_replicas=2, slots=2, max_len=48,
                     n_sessions=n_sessions, faults=inj)
        s = sched.ClusterScheduler(cl, arrivals=arrivals,
                                   snapshot_every=snapshot_every)
        summary = s.run()
        out = {"jobs_completed": summary["jobs_completed"],
               "slo_attainment": summary["slo_attainment"],
               "p99_latency_ns": summary["p99_latency_ns"],
               "faults": summary["faults"]}
        if inj is not None:
            out["ledger"] = inj.summary()
            out["verify_failed"] = cl.verify_failure_count()
            out["at_rest_corrupt"] = int(cl.scrub())
        return out

    def accounted(r):
        led = r["ledger"]
        closed = (led["detected"] + led["recovered"] + led["destroyed"]
                  + led["at_rest_corrupt"])
        return (led["new_corrupt"] == closed
                and r["verify_failed"] == led["detected"]
                and r["at_rest_corrupt"] == led["at_rest_corrupt"])

    # ---- detection (recovery off) + recovery (snapshots on) --------------
    detection = chaos_run(FaultSpec(rate=0.4, seed=7, recover=False))
    detection["all_accounted"] = accounted(detection)
    recovery = chaos_run(FaultSpec(rate=0.4, seed=3), snapshot_every=2)
    recovery["all_accounted"] = accounted(recovery)

    # ---- recovery parity: fail a replica, restore, decode bit-exact ------
    def greedy_ref(prompt, n_new):
        from repro.models import lm as L
        cache = L.init_cache(cfg, 1, max_len=48)
        logits, cache = L.prefill(cfg, params, jnp.asarray(prompt)[None],
                                  cache)
        toks = [int(jnp.argmax(logits[0, -1]))]
        pos = len(prompt)
        while len(toks) < n_new:
            lg, cache = L.decode_step(cfg, params, cache,
                                      jnp.asarray([[toks[-1]]]),
                                      jnp.int32(pos))
            toks.append(int(jnp.argmax(lg[0, 0])))
            pos += 1
        return toks

    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, 9).astype(np.int32)
    straight = greedy_ref(prompt, 8)
    inj = FaultInjector(FaultSpec(rate=0.0, seed=1))
    cl = Cluster(cfg, params, n_replicas=2, slots=2, max_len=48,
                 n_sessions=8, faults=inj)
    req = Request(uid=7, prompt=prompt, max_new=4)
    cl.submit(req, replica=0)
    while cl.active:
        cl.step()
    snaps, snap_cost = snapshot_sessions(cl)
    cl.fail_replica(0)
    assert 7 not in cl.session_pos          # the snapshot is the only copy
    recover_cost = restore_session(cl, snaps[7], 1)
    slot = cl.resume(7, extra_new=5)        # seed + 4 new tokens
    r2 = cl.active[slot]
    while cl.active:
        cl.step()
    parity = {
        "tokens_match": req.generated + r2.generated[1:] == straight,
        "verify_failed": cl.verify_failure_count(),
        "snapshot_ns_lisa": round(snap_cost.ns_lisa, 2),
        "recover_ns_lisa": round(recover_cost.ns_lisa, 2),
    }

    # ---- graceful degradation: clean vs faulted SLO at equal load --------
    clean = chaos_run(None)
    faulted = chaos_run(FaultSpec(rate=0.4, seed=3,
                                  replica_failures=((25, 1),)),
                        snapshot_every=2)
    retention = ((faulted["slo_attainment"] / clean["slo_attainment"])
                 if clean["slo_attainment"] else 1.0)
    degradation = {
        "clean_slo": clean["slo_attainment"],
        "faulted_slo": faulted["slo_attainment"],
        "slo_retention": round(retention, 4),
        "clean_jobs": clean["jobs_completed"],
        "faulted_jobs": faulted["jobs_completed"],
        "ledger": faulted["ledger"],
    }

    bench = {
        "detection": detection,
        "recovery": recovery,
        "recovery_parity": parity,
        "degradation": degradation,
        "zero_silent_corruption": bool(detection["all_accounted"]
                                       and recovery["all_accounted"]),
        "graceful_degradation": bool(
            retention >= 0.7
            and faulted["jobs_completed"] == clean["jobs_completed"]),
        "config": {"arch": "tinyllama-1.1b-reduced", "replicas": 2,
                   "slots": 2, "max_len": 48, "workload_seed": 5,
                   "fault_seeds": {"detection": 7, "recovery": 3,
                                   "degradation": 3}},
    }
    with open(out_path, "w") as f:
        json.dump(bench, f, indent=2, allow_nan=False)
    row("faults_detection", 0.0,
        f"injected={detection['ledger']['new_corrupt']};"
        f"detected={detection['verify_failed']};"
        f"at_rest={detection['at_rest_corrupt']};"
        f"accounted={detection['all_accounted']}")
    row("faults_recovery", 0.0,
        f"recovered={recovery['ledger']['recovered']};"
        f"accounted={recovery['all_accounted']}")
    row("faults_recovery_parity", 0.0,
        f"tokens_match={parity['tokens_match']};"
        f"verify_failed={parity['verify_failed']}")
    row("faults_degradation", 0.0,
        f"slo_retention={degradation['slo_retention']};"
        f"graceful={bench['graceful_degradation']}")


def bench_bank(out_path="BENCH_bank.json"):
    """Bank-contention A/B under the virtual clock (DESIGN.md Sec. 15).
    Three deterministic arms, gated exactly:

      * **offered load** — the same open-loop request stream (fixed
        service, round-robin banks) at 1x vs 2x rate, contention on vs
        off.  On: per-bank queues grow with load, so p99 sojourn is
        strictly worse at 2x.  Off: the multiplexer is a pass-through and
        p99 is EXACTLY the service time at both loads (flat).
      * **wave overlap** — one migration-wave's routes priced from the
        real resume plan: disjoint-bank routes complete in less than the
        sum of their isolated costs (bank-level parallelism), same-bank
        routes serialize exactly (completion == sum).
      * **scheduler A/B** — the same arrival stream through the tick loop
        with ``contention`` off vs on: identical jobs and identical
        movement bills (contention never reprices), p99 no better with
        contention on, and the run observes refresh stalls (the virtual
        time crosses several tREFI windows).

    Writes ``BENCH_bank.json``."""
    from repro import sched
    from repro.configs import get_reduced
    from repro.core.dram.bank import RequestMultiplexer
    from repro.core.dram.spec import DDR3_1600
    from repro.models import lm as LM
    from repro.sched.metrics import percentile_ns
    from repro.serve.engine import Engine

    # ---- arm 1: open-loop sojourn vs offered load ------------------------
    service_ns, n_banks, n_req = 600.0, 4, 400

    def sojourn_p99(enabled, gap_ns):
        m = RequestMultiplexer(DDR3_1600, n_banks=n_banks, enabled=enabled)
        sj = []
        for i in range(n_req):
            ready = i * gap_ns
            _, end = m.submit(m.bank_of(i), ready, service_ns)
            sj.append(end - ready)
        return round(percentile_ns(sj, 99), 3), m

    # 1x: per-bank utilization 600/800 — queues drain between refreshes;
    # 2x: 600/400 — overloaded, per-bank queues grow without bound
    p99_on_1x, _ = sojourn_p99(True, 200.0)
    p99_on_2x, m_2x = sojourn_p99(True, 100.0)
    p99_off_1x, _ = sojourn_p99(False, 200.0)
    p99_off_2x, _ = sojourn_p99(False, 100.0)
    load = {"service_ns": service_ns, "n_banks": n_banks,
            "n_requests": n_req,
            "on": {"p99_1x": p99_on_1x, "p99_2x": p99_on_2x},
            "off": {"p99_1x": p99_off_1x, "p99_2x": p99_off_2x},
            "mux_2x": m_2x.snapshot()}

    # ---- arms 2+3 share the reduced model ---------------------------------
    cfg = get_reduced("tinyllama-1.1b")
    params = LM.init_lm(cfg, jax.random.key(0))
    eng0 = Engine(cfg, params, slots=2, max_len=96, n_sessions=8)
    route_ns = eng0.plan_resume.cost.ns_lisa    # one route's isolated bill
    n_routes = 3
    mux = RequestMultiplexer(DDR3_1600, n_banks=8)
    disjoint = mux.wave([(r, route_ns) for r in range(n_routes)], 0.0)
    mux2 = RequestMultiplexer(DDR3_1600, n_banks=8)
    same_bank = mux2.wave([(0, route_ns)] * n_routes, 0.0)
    waves = {"route_ns": round(route_ns, 3), "n_routes": n_routes,
             "sum_isolated_ns": round(n_routes * route_ns, 3),
             "disjoint_completion_ns": round(disjoint, 3),
             "same_bank_completion_ns": round(same_bank, 3)}

    wl = sched.WorkloadConfig(n_fresh=8, n_followups=16, mean_gap_ns=1200.0,
                              arrival="bursty", burst=4, zipf_s=1.5,
                              think_ns=2000.0)
    arrivals = sched.generate_workload(wl, seed=4, vocab_size=cfg.vocab_size)
    ab = {}
    for contention in (False, True):
        eng = Engine(cfg, params, slots=2, max_len=96,
                     n_sessions=sched.n_sessions_for(wl))
        s = sched.Scheduler(eng, arrivals=arrivals,
                            cfg=sched.SchedConfig(contention=contention))
        t0 = time.perf_counter()
        summary = s.run()
        arm = {"jobs_completed": summary["jobs_completed"],
               "p99_latency_ns": summary["p99_latency_ns"],
               "movement_ns_lisa": summary["movement"]["ns_lisa"],
               "movement_advantage": summary["movement"]["advantage"],
               "virtual_ns": round(s.now_ns, 2),
               "ticks": s.tick_count,
               "wall_seconds": round(time.perf_counter() - t0, 2)}
        if contention:
            arm["stalls"] = summary.get("stalls", {})
            arm["mux"] = s.mux.snapshot()
        ab["contention_on" if contention else "contention_off"] = arm
    off, on = ab["contention_off"], ab["contention_on"]

    # pricing invariance needs an IDENTICAL schedule in both arms (the
    # bursty A/B above diverges: the shifted clock feeds back into
    # admission), so it gates on a sequential stream whose decisions
    # cannot depend on completion times
    rng = np.random.default_rng(11)
    seq_arrivals = [
        sched.Arrival(t_ns=i * 400.0, uid=i, kind="fresh", priority=1,
                      slo_ns=float("inf"), new_tokens=2,
                      prompt=rng.integers(0, cfg.vocab_size,
                                          4).astype(np.int32))
        for i in range(6)]
    bills = {}
    for contention in (False, True):
        eng = Engine(cfg, params, slots=2, max_len=96, n_sessions=8)
        s = sched.Scheduler(eng, arrivals=list(seq_arrivals),
                            cfg=sched.SchedConfig(contention=contention))
        summary = s.run()
        bills["on" if contention else "off"] = {
            "ns_lisa": summary["movement"]["ns_lisa"],
            "ns_memcpy": summary["movement"]["ns_memcpy"],
            "advantage": summary["movement"]["advantage"],
            "jobs_completed": summary["jobs_completed"]}

    gates = {
        "on_p99_load_dependent": bool(p99_on_2x > p99_on_1x),
        "off_p99_flat": bool(p99_off_1x == p99_off_2x == service_ns),
        "disjoint_routes_overlap": bool(
            disjoint < n_routes * route_ns and disjoint >= route_ns),
        "same_bank_serializes_exactly": bool(
            same_bank == n_routes * route_ns),
        "contention_never_reprices": bool(
            bills["on"] == bills["off"]),
        "same_jobs_served": bool(
            on["jobs_completed"] == off["jobs_completed"]),
        # the bank model moves completion times both ways: same-bank queues
        # and refresh windows delay, disjoint-bank wave overlap accelerates
        # vs the serial contention-off clock — the gate is that it SHIFTS
        # the clock without touching the bill, not a one-sided inequality
        "contention_shifts_the_clock": bool(
            on["p99_latency_ns"] != off["p99_latency_ns"]),
        "refresh_stalls_observed": bool(
            on["mux"]["n_decode_stalls"] >= 1),
    }
    bench = {
        "load": load, "waves": waves, **ab,
        "pricing_invariance": bills, "gates": gates,
        "config": {"arch": "tinyllama-1.1b-reduced", "seed": 4,
                   "timing": {"tREFI": DDR3_1600.timing.tREFI,
                              "tRFC": DDR3_1600.timing.tRFC},
                   "offered_load": "bursty gap=1200 zipf=1.5 8f+16r"},
    }
    with open(out_path, "w") as f:
        json.dump(bench, f, indent=2, allow_nan=False)
    row("bank_load_p99", 0.0,
        f"on_1x={p99_on_1x};on_2x={p99_on_2x};"
        f"off_flat={gates['off_p99_flat']}")
    row("bank_wave_overlap", 0.0,
        f"disjoint={waves['disjoint_completion_ns']};"
        f"same_bank={waves['same_bank_completion_ns']};"
        f"sum={waves['sum_isolated_ns']}")
    row("bank_sched_ab", 0.0,
        f"p99_off={off['p99_latency_ns']};p99_on={on['p99_latency_ns']};"
        f"decode_stalls={on['mux']['n_decode_stalls']};"
        f"gates_ok={all(gates.values())}")


def bench_fork(out_path="BENCH_fork.json"):
    """Shared-prefix serving A/B: 64 sessions sharing one long system
    prompt, forked (zero-copy CoW aliasing — the RowClone analogue) vs
    admitted independently (one prefill each).  Writes ``BENCH_fork.json``.

    The fork-ON arm prefills the shared prefix ONCE, forks 64 children off
    the suspended template (pure host bookkeeping — the in-bench dispatch
    delta pins ZERO device work), then forces a store-index collision on
    the shared row to exercise the demotion path (a shared snapshot is
    migrated, never destroyed).  The fork-OFF arm prefills the same prefix
    64 times.  Both arms then decode the same per-child divergence seeds;
    the gate demands bit-exact tokens — aliasing must be invisible to the
    data path."""
    from repro.analysis import testlib as TL
    from repro.configs import get_reduced
    from repro.models import lm as LM
    from repro.serve.engine import Engine, Request

    cfg = get_reduced("tinyllama-1.1b")
    params = LM.init_lm(cfg, jax.random.key(0))
    rng = np.random.default_rng(7)
    n_children, decode_n = 64, 4
    prefix = rng.integers(0, cfg.vocab_size, 24).astype(np.int32)
    seeds = [int(s) for s in rng.integers(1, cfg.vocab_size, n_children)]
    # geometry: template uid 0 homes at row 0; children uids 6..69 home at
    # rows 6..69, leaving rows 1..5 as demotion headroom so the forced
    # collision never cascades into the children's own write-breaks
    template_uid, collider_uid = 0, 70
    children = list(range(6, 6 + n_children))

    def mk():
        return Engine(cfg, params, slots=8, max_len=96, n_sessions=70)

    # warm the shared jits (prefill/suspend/decode and the wave widths both
    # arms use) so admission wall-clock measures the steady state
    eng_w = mk()
    eng_w.submit(Request(uid=0, prompt=prefix, max_new=1))
    eng_w.resume_many([0], extra_new=1 + decode_n)
    while eng_w.active:
        eng_w.step()

    def drain(eng, toks):
        while eng.active:
            for _, req in eng.step():
                toks[req.uid] = [int(t) for t in req.generated]

    def decode_children(eng):
        toks = {}
        for i in range(0, n_children, eng.slots):
            wave = children[i:i + eng.slots]
            eng.resume_many(wave, extra_new=1 + decode_n)
            drain(eng, toks)
        return toks

    # ---- fork ON: prefill once, alias everywhere --------------------------
    eng_on = mk()
    eng_on.adopt_jits(eng_w)
    jax.block_until_ready(eng_on.sessions.slow)
    t0 = time.perf_counter()
    eng_on.submit(Request(uid=template_uid, prompt=prefix, max_new=1))
    before = TL.snapshot_stats(eng_on)
    eng_on.fork_many(template_uid, children, seed_tokens=seeds)
    jax.block_until_ready(eng_on.sessions.slow)
    admit_on_s = time.perf_counter() - t0
    # the fork fast path is PURE host bookkeeping: zero fused dispatches,
    # zero device->host transfers over the fork_many window
    TL.assert_dispatch_delta(before, eng_on.stats, decode=0, host=0)
    fork_zero_dispatch = (
        eng_on.stats["decode_dispatches"] == before["decode_dispatches"]
        and eng_on.stats["host_transfers"] == before["host_transfers"])
    # collide with the SHARED row while all 64 aliases still read it: the
    # fork-aware store demotes (clones + repoints) instead of destroying
    eng_on.submit(Request(uid=collider_uid, prompt=prefix, max_new=1))
    assert eng_on.stats["demotions"] == 1, eng_on.stats
    toks_on = decode_children(eng_on)
    stats_on = dict(eng_on.stats)
    verify_failed_on = eng_on.verify_failure_count()

    # ---- fork OFF: 64 independent admissions ------------------------------
    eng_off = mk()
    eng_off.adopt_jits(eng_w)
    jax.block_until_ready(eng_off.sessions.slow)
    t0 = time.perf_counter()
    for uid, seed in zip(children, seeds):
        eng_off.submit(Request(uid=uid, prompt=prefix, max_new=1))
        eng_off.reseed(uid, seed)
    jax.block_until_ready(eng_off.sessions.slow)
    admit_off_s = time.perf_counter() - t0
    eng_off.submit(Request(uid=collider_uid, prompt=prefix, max_new=1))
    toks_off = decode_children(eng_off)
    stats_off = dict(eng_off.stats)

    tokens_match = toks_on == toks_off and len(toks_on) == n_children
    fp = eng_on.plan_fork.cost
    modeled_ratio = fp.ns_memcpy / fp.ns_lisa
    bench = {
        "n_children": n_children,
        "prefix_len": len(prefix),
        "decode_per_child": decode_n,
        "fork_on": {
            "shared_prefix_prefills": 1,     # the template's, ever
            "prefills": stats_on["prefills"],
            "forks": stats_on["forks"],
            "bytes_not_copied": stats_on["bytes_not_copied"],
            "demotions": stats_on["demotions"],
            "evictions": stats_on["evictions"],
            "verify_failed": verify_failed_on,
            "admission_s": round(admit_on_s, 6),
        },
        "fork_off": {
            "shared_prefix_prefills": n_children,
            "prefills": stats_off["prefills"],
            "forks": stats_off["forks"],
            "bytes_not_copied": stats_off["bytes_not_copied"],
            "admission_s": round(admit_off_s, 6),
        },
        # modeled per-session admission: the fork-kind plan prices the alias
        # as RowClone FPM (ns_lisa) vs the full-snapshot copy it avoids
        # (ns_memcpy) — the Table-1 gap at serving granularity
        "modeled_admission_ratio": round(modeled_ratio, 2),
        "modeled_fork_ns_lisa": fp.ns_lisa,
        "modeled_fork_ns_memcpy": fp.ns_memcpy,
        "bytes_not_copied": stats_on["bytes_not_copied"],
        "snapshot_bytes": eng_on.snapshot_bytes,
        "fork_zero_dispatch": bool(fork_zero_dispatch),
        "tokens_match": bool(tokens_match),
        "admission_speedup_wallclock": round(
            admit_off_s / max(admit_on_s, 1e-9), 2),   # recorded, not gated
        "config": {"arch": "tinyllama-1.1b-reduced", "slots": 8,
                   "max_len": 96, "n_sessions": 70,
                   "template_uid": template_uid,
                   "collider_uid": collider_uid,
                   "child_uids": [children[0], children[-1]],
                   "seed": 7},
    }
    with open(out_path, "w") as f:
        json.dump(bench, f, indent=2, allow_nan=False)
    row("fork_admission", admit_on_s * 1e6 / n_children,
        f"prefills_on={stats_on['prefills']};"
        f"prefills_off={stats_off['prefills']};"
        f"wallclock_speedup={bench['admission_speedup_wallclock']}x")
    row("fork_modeled_ratio", 0.0,
        f"rowclone_vs_memcpy={bench['modeled_admission_ratio']}x;"
        f"bytes_not_copied={bench['bytes_not_copied']}")
    row("fork_cow_divergence", 0.0,
        f"tokens_match={tokens_match};demotions={stats_on['demotions']};"
        f"evictions={stats_on['evictions']};"
        f"zero_dispatch={fork_zero_dispatch}")


# ---------------------------------------------------------------------------
# --check: validate committed BENCH_*.json against their deterministic gates
# ---------------------------------------------------------------------------

def _check_serve(b, errs):
    if not b["decode_tokens_per_s"] > 0:
        errs.append("serve: decode_tokens_per_s not positive")
    if not b["suspend_resume_gbps"] > 0:
        errs.append("serve: suspend_resume_gbps not positive")
    if b["compile_counts"]["decode"] != 1:
        errs.append(f"serve: decode compiled "
                    f"{b['compile_counts']['decode']}x")
    for k in ("suspend_many_compiles", "resume_many_compiles"):
        if b["wave"][k] != 1:
            errs.append(f"serve: {k}={b['wave'][k]}")


def _check_movement(b, errs):
    for k, v in b["planned_compile_counts"].items():
        if v != 1:
            errs.append(f"movement: {k} compiled {v}x")
    if not b["modeled_advantage"] > 1:
        errs.append("movement: Table-1 advantage lost")
    if b["planned_over_legacy"] > 1.5:
        errs.append(f"movement: planned path {b['planned_over_legacy']}x "
                    f"of legacy (structural overhead)")


def _check_sched(b, errs):
    if not b["cost_aware_beats_fifo"]:
        errs.append("sched: cost_aware no longer beats fifo")
    for pol in ("fifo", "cost_aware"):
        r = b[pol]
        if r["jobs_completed"] != 36:
            errs.append(f"sched: {pol} completed {r['jobs_completed']} "
                        f"jobs, expected 36")
        widths = r["resume_wave_widths"]
        if r["decisions"]["resume_wave"] != len(widths):
            errs.append(f"sched: {pol} resume decisions != wave count")
        cc = r["compile_counts"]
        if cc["resume_many"] not in range(len(set(widths)) + 1):
            errs.append(f"sched: {pol} resume_many compiles {cc}")
        if cc["decode"] != 1:
            errs.append(f"sched: {pol} decode compiles {cc['decode']}")
    tr = b["cost_aware"].get("trace")
    if not tr or not tr.get("spans"):
        errs.append("sched: cost_aware arm lost its trace rollup")
    else:
        for phase in ("tick", "decode", "move", "leg"):
            if phase not in tr["per_phase"]:
                errs.append(f"sched: trace rollup missing phase {phase!r}")


def _check_cluster(b, errs):
    if not b["scales_2x"]:
        errs.append(f"cluster: 4-replica scaling "
                    f"{b['scaling_before_miss']}x < 2x before misses")
    if not b["migration_wins"]:
        errs.append("cluster: migration-on no longer beats migration-off")
    for k in ("replicas1", "replicas4", "migration_on", "migration_off"):
        if b[k]["jobs_completed"] < 1:
            errs.append(f"cluster: {k} completed no jobs")
    if b["migration_on"]["jobs_completed"] != \
            b["migration_off"]["jobs_completed"]:
        errs.append("cluster: A/B arms completed different job counts")


def _check_faults(b, errs):
    if not b["zero_silent_corruption"]:
        errs.append("faults: an injected corruption went unaccounted "
                    "(zero-silent-corruption gate)")
    if not b["graceful_degradation"]:
        errs.append(f"faults: SLO retention "
                    f"{b['degradation']['slo_retention']} < 0.7 or jobs "
                    f"lost under chaos (graceful-degradation gate)")
    if not b["recovery_parity"]["tokens_match"]:
        errs.append("faults: post-failure restored decode diverged from "
                    "the uninterrupted run")
    if b["recovery_parity"]["verify_failed"] != 0:
        errs.append("faults: snapshot-restored session failed the device "
                    "checksum verify")
    for scen in ("detection", "recovery"):
        led = b[scen]["ledger"]
        if led["new_corrupt"] < 3:
            errs.append(f"faults: {scen} scenario injected only "
                        f"{led['new_corrupt']} corruptions (needs >= 3 to "
                        f"be a meaningful gate)")
        if b[scen]["verify_failed"] != led["detected"]:
            errs.append(f"faults: {scen} device detections "
                        f"{b[scen]['verify_failed']} != ledger "
                        f"{led['detected']}")
    if b["recovery"]["ledger"]["recovered"] < 1:
        errs.append("faults: recovery scenario never exercised a "
                    "snapshot restore")


def _check_fork(b, errs):
    n = b["n_children"]
    if b["fork_on"]["shared_prefix_prefills"] != 1:
        errs.append("fork: fork-on arm prefilled the shared prefix more "
                    "than once (amortization gate)")
    if b["fork_off"]["shared_prefix_prefills"] < 64 or n < 64:
        errs.append(f"fork: A/B must span >= 64 shared-prefix sessions "
                    f"(got {n})")
    if b["fork_on"]["forks"] != n:
        errs.append(f"fork: {b['fork_on']['forks']} forks for {n} children")
    if not b["modeled_admission_ratio"] >= 10:
        errs.append(f"fork: modeled admission ratio "
                    f"{b['modeled_admission_ratio']}x < 10x (RowClone FPM "
                    f"pricing gate)")
    if not b["bytes_not_copied"] > 0:
        errs.append("fork: no bytes credited to the zero-copy path")
    if not b["fork_zero_dispatch"]:
        errs.append("fork: fork_many issued device work (zero-dispatch "
                    "fast-path gate)")
    if not b["tokens_match"]:
        errs.append("fork: forked children diverged from independent "
                    "sessions (bit-exactness gate)")
    if b["fork_on"]["demotions"] != 1:
        errs.append(f"fork: shared-row collision recorded "
                    f"{b['fork_on']['demotions']} demotions, expected 1")
    if b["fork_on"]["evictions"] != 0:
        errs.append(f"fork: {b['fork_on']['evictions']} evictions — a "
                    f"shared snapshot was destroyed, not migrated")
    if b["fork_on"]["verify_failed"] != 0:
        errs.append(f"fork: {b['fork_on']['verify_failed']} checksum "
                    f"failures after demotion (sidecar must travel with "
                    f"the clone)")


def _check_bank(b, errs):
    """``BENCH_bank.json``: recompute every contention gate from the
    recorded values — the artifact must not merely CLAIM the gates passed
    (regenerate with ``python benchmarks/run.py bank``)."""
    load, waves = b["load"], b["waves"]
    on, off = load["on"], load["off"]
    if not on["p99_2x"] > on["p99_1x"]:
        errs.append(f"bank: contention-on p99 not load-dependent "
                    f"({on['p99_1x']} -> {on['p99_2x']} at 2x)")
    if not (off["p99_1x"] == off["p99_2x"] == load["service_ns"]):
        errs.append(f"bank: contention-off p99 not flat at the service "
                    f"time ({off['p99_1x']}, {off['p99_2x']})")
    total = waves["sum_isolated_ns"]
    if not waves["disjoint_completion_ns"] < total:
        errs.append(f"bank: disjoint-route wave "
                    f"{waves['disjoint_completion_ns']} !< sum of isolated "
                    f"costs {total}")
    if not waves["disjoint_completion_ns"] >= waves["route_ns"]:
        errs.append("bank: disjoint-route wave faster than one route")
    if waves["same_bank_completion_ns"] != total:
        errs.append(f"bank: same-bank wave "
                    f"{waves['same_bank_completion_ns']} != sum of "
                    f"isolated costs {total} (must serialize exactly)")
    sa, sb = b["contention_on"], b["contention_off"]
    if sa["jobs_completed"] != sb["jobs_completed"]:
        errs.append("bank: the A/B arms served different job counts")
    bills = b["pricing_invariance"]
    if bills["on"] != bills["off"]:
        errs.append("bank: contention repriced the identical-schedule "
                    "sequential stream (must only shift the clock)")
    if sa["p99_latency_ns"] == sb["p99_latency_ns"]:
        errs.append("bank: contention never moved a completion time "
                    "(the A/B arms are identical)")
    if sa["mux"]["n_decode_stalls"] < 1:
        errs.append("bank: scheduler A/B observed no decode refresh stall")
    if sa["virtual_ns"] < b["config"]["timing"]["tREFI"]:
        errs.append("bank: A/B run too short to cross one tREFI window")
    for gate, ok in b["gates"].items():
        if ok is not True:
            errs.append(f"bank: gate {gate} recorded as {ok!r}")


def _check_lint(b, errs):
    """The committed repro-lint report: clean, waiver-free, and covering
    every registered jitted entry point (regenerate with
    ``python -m repro.analysis --strict --audit --report
    LINT_REPORT.json``)."""
    if b["schema"] != "repro-lint-report/v1":
        errs.append(f"lint: unknown report schema {b['schema']!r}")
        return
    if b["findings"]:
        errs.append(f"lint: {len(b['findings'])} active finding(s) in the "
                    f"committed report")
    if b["waived"]:
        errs.append(f"lint: {len(b['waived'])} waiver(s) active — the "
                    f"waiver file must stay empty")
    audit = b["audit"]
    if audit.get("findings"):
        errs.append(f"lint: {len(audit['findings'])} dispatch-audit "
                    f"finding(s)")
    names = {t["name"] for t in audit.get("targets", ())}
    need = {"decode", "suspend", "suspend_many", "resume", "resume_many",
            "migrate", "simulate_params"}
    if need - names:
        errs.append(f"lint: audit missing entry points {sorted(need - names)}")
    if not any(n.startswith("prefill[") for n in names):
        errs.append("lint: audit covers no prefill bucket")
    for t in audit.get("targets", ()):
        if t["donated_leaves"] != t["expected_donated_leaves"]:
            errs.append(f"lint: {t['name']} donation not verified "
                        f"({t['donated_leaves']}/"
                        f"{t['expected_donated_leaves']} buffers)")
        if t.get("jaxpr_host_transfer_eqns", 0) or \
                t.get("hlo_host_transfer_ops", 0):
            errs.append(f"lint: {t['name']} has in-graph host transfers")


def _check_roofline(b, errs):
    """The committed live-roofline report: every audited entry point
    present with positive traffic and a kernel attribution (regenerate
    with ``python benchmarks/run.py roofline``)."""
    if b["schema"] != "roofline-report/v1":
        errs.append(f"roofline: unknown report schema {b['schema']!r}")
        return
    names = set(b["entries"])
    need = {"decode", "suspend", "suspend_many", "resume", "resume_many",
            "migrate", "simulate_params"}
    if need - names:
        errs.append(f"roofline: missing entry points {sorted(need - names)}")
    if not any(n.startswith("prefill[") for n in names):
        errs.append("roofline: no prefill bucket attributed")
    if b["n_entry_points"] != len(names):
        errs.append(f"roofline: n_entry_points {b['n_entry_points']} != "
                    f"{len(names)} entries")
    if len(names) < 9:
        errs.append(f"roofline: {len(names)} entry points, expected >= 9")
    for n in sorted(names):
        e = b["entries"][n]
        if not e["bytes"] > 0:
            errs.append(f"roofline: {n} has no memory traffic")
        if not e["flops"] >= 0:
            errs.append(f"roofline: {n} flops negative")
        if not e["top_kernels"]:
            errs.append(f"roofline: {n} has no kernel attribution")
        elif e["dominant"] != e["top_kernels"][0]["name"]:
            errs.append(f"roofline: {n} dominant kernel disagrees with "
                        f"its top_kernels ranking")


BENCH_SCHEMAS = {
    "BENCH_serve.json": _check_serve,
    "BENCH_movement.json": _check_movement,
    "BENCH_sched.json": _check_sched,
    "BENCH_cluster.json": _check_cluster,
    "BENCH_faults.json": _check_faults,
    "BENCH_fork.json": _check_fork,
    "BENCH_bank.json": _check_bank,
    "LINT_REPORT.json": _check_lint,
    "ROOFLINE_REPORT.json": _check_roofline,
}


def check_artifacts(root=".") -> int:
    """Validate every committed BENCH_*.json against its deterministic-gate
    schema (``benchmarks/run.py --check``).  Wall-clock numbers are recorded
    data and never gated; the gates are the platform-independent invariants
    CI relies on.  Returns the number of failures."""
    def reject(const):
        raise ValueError(f"non-strict JSON constant {const}")

    errs, clean = [], 0
    for name, check in BENCH_SCHEMAS.items():
        before = len(errs)
        path = os.path.join(root, name)
        if not os.path.exists(path):
            errs.append(f"{name}: missing (regenerate and commit it)")
            continue
        try:
            with open(path) as f:
                payload = json.load(f, parse_constant=reject)
            check(payload, errs)
        except ValueError as e:
            errs.append(f"{name}: invalid strict JSON ({e})")
        except (KeyError, TypeError) as e:
            errs.append(f"{name}: schema drifted ({type(e).__name__}: {e})")
        clean += len(errs) == before
    before = len(errs)
    _check_trajectory(os.path.join(root, "BENCH_TRAJECTORY.jsonl"), errs,
                      reject)
    clean += len(errs) == before
    for e in errs:
        print(f"CHECK FAIL {e}")
    print(f"bench check: {clean}/{len(BENCH_SCHEMAS) + 1} artifacts clean, "
          f"{len(errs)} failure(s)")
    return len(errs)


# the gate keys every trajectory line must carry: the core artifacts that
# have existed since the log began (newer artifacts appear in later lines
# only, so they are validated as a subset, not required)
TRAJECTORY_CORE_GATES = frozenset({
    "BENCH_serve.json", "BENCH_movement.json",
    "BENCH_sched.json", "BENCH_cluster.json"})


def _check_trajectory(path, errs, reject):
    """``BENCH_TRAJECTORY.jsonl``: strict JSON per line, ``seq`` a strictly
    increasing int, and every line's ``gates`` dict keyed by known
    artifact names (with the core four always present, values strictly
    ``true``/``false``/``null``) — an append-only record of every bench
    invocation's headline gates (plot it to see the repo's trajectory)."""
    name = os.path.basename(path)
    if not os.path.exists(path):
        errs.append(f"{name}: missing (run any bench to append a line)")
        return
    last = None
    with open(path) as f:
        for i, line in enumerate(f, 1):
            if not line.strip():
                errs.append(f"{name}:{i}: blank line in append-only log")
                continue
            try:
                rec = json.loads(line, parse_constant=reject)
            except ValueError as e:
                errs.append(f"{name}:{i}: invalid strict JSON ({e})")
                continue
            seq = rec.get("seq")
            if not isinstance(seq, int):
                errs.append(f"{name}:{i}: seq missing or not an int")
                continue
            if last is not None and seq <= last:
                errs.append(f"{name}:{i}: seq {seq} not monotone "
                            f"(previous {last})")
            last = seq
            if not isinstance(rec.get("benches"), list):
                errs.append(f"{name}:{i}: benches missing or not a list")
            if not isinstance(rec.get("rows"), dict):
                errs.append(f"{name}:{i}: rows missing or not a dict")
            gates = rec.get("gates")
            if not isinstance(gates, dict):
                errs.append(f"{name}:{i}: gates missing or not a dict")
                continue
            unknown = set(gates) - set(BENCH_SCHEMAS)
            if unknown:
                errs.append(f"{name}:{i}: unknown gate keys "
                            f"{sorted(unknown)}")
            missing = TRAJECTORY_CORE_GATES - set(gates)
            if missing:
                errs.append(f"{name}:{i}: core gate keys missing "
                            f"{sorted(missing)}")
            for k, v in gates.items():
                if v is not None and not isinstance(v, bool):
                    errs.append(f"{name}:{i}: gate {k} is {v!r}, expected "
                                f"true/false/null")
    if last is None:
        errs.append(f"{name}: no records")


def _append_trajectory(benches, path="BENCH_TRAJECTORY.jsonl"):
    """Append one strict-JSON line per bench invocation: which benches ran,
    every headline ``derived`` value this run printed, and each committed
    artifact's gate status at append time.  ``seq`` continues monotonically
    from the last committed line (``--check`` validates)."""
    last = -1
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                if line.strip():
                    try:
                        seq = json.loads(line).get("seq", -1)
                        if isinstance(seq, int):
                            last = max(last, seq)
                    except ValueError:
                        pass
    gates = {}
    for name, check in BENCH_SCHEMAS.items():
        if not os.path.exists(name):
            gates[name] = None          # never generated: not a failure
            continue
        art_errs = []
        try:
            with open(name) as f:
                check(json.load(f), art_errs)
        except (ValueError, KeyError, TypeError) as e:
            art_errs.append(str(e))
        gates[name] = not art_errs
    rec = {"seq": last + 1, "ts": round(time.time(), 2),
           "benches": sorted(benches),
           "rows": {name: derived for name, _us, derived in ROWS},
           "gates": gates}
    with open(path, "a") as f:
        f.write(json.dumps(rec, sort_keys=True, separators=(",", ":"),
                           allow_nan=False) + "\n")


def bench_roofline(out_path="ROOFLINE_REPORT.json"):
    """Live roofline attribution over the audited hot path: lower every
    registered jitted entry point (``analysis.entrypoints.default_targets``
    — the SAME live jit objects serving runs and repro-lint audits) at
    audit geometry, run the optimized HLO through ``roofline.hlo.analyze``
    + ``roofline.attribution.attribute``, and write ``ROOFLINE_REPORT.json``
    (strict JSON, schema ``roofline-report/v1``, validated by ``--check``).
    This replaces the old dry-run-artifact scan: the report now always
    describes the code as committed, not a stale experiment directory."""
    from repro.analysis.entrypoints import default_targets
    from repro.roofline import attribution as ATTR
    from repro.roofline import hlo as H

    targets, engine = default_targets()
    entries = {}
    for t in targets:
        t0 = time.perf_counter()
        txt = t.fn.lower(*t.args, **t.kwargs).compile().as_text()
        dt = time.perf_counter() - t0
        cost = H.analyze(txt)
        top = ATTR.attribute(txt, top=5)
        names = list(top)
        flops, nbytes = cost["flops"], cost["bytes"]
        entries[t.name] = {
            "flops": flops,
            "bytes": nbytes,
            "bytes_kernel_adjusted": cost["bytes_kernel_adjusted"],
            "link_bytes_total": cost["link_bytes_total"],
            "arithmetic_intensity": round(flops / max(nbytes, 1.0), 4),
            "dominant": names[0] if names else None,
            "top_kernels": [{"name": k, "weighted_bytes": v}
                            for k, v in top.items()],
            "compile_s": round(dt, 3),
        }
        row(f"roofline_{t.name}", dt * 1e6,
            f"GF={flops / 1e9:.3f};GB={nbytes / 1e9:.4f};"
            f"AI={entries[t.name]['arithmetic_intensity']}")
    report = {
        "schema": "roofline-report/v1",
        "arch": "tinyllama-1.1b-reduced",
        "geometry": {"slots": engine.slots, "max_len": engine.max_len},
        "n_entry_points": len(entries),
        "entries": entries,
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True, allow_nan=False)
        f.write("\n")
    row("roofline_entry_points", 0.0, f"{len(entries)}")


BENCHES = {
    "table1": bench_table1,
    "fig34": bench_fig3_fig4,
    "kernels": bench_kernels,
    "ring": bench_ring_collectives,
    "train": bench_train_throughput,
    "serve": bench_serve_throughput,
    "movement": bench_movement,
    "sched": bench_sched,
    "cluster": bench_cluster,
    "faults": bench_faults,
    "fork": bench_fork,
    "bank": bench_bank,
    "roofline": bench_roofline,
}


def main(argv=None) -> None:
    """Run all benches, or a subset: ``python benchmarks/run.py serve train``.
    ``--check`` instead validates the committed BENCH_*.json artifacts
    against their deterministic-gate schemas (no benches run)."""
    argv = list(argv if argv is not None else sys.argv[1:])
    if "--check" in argv:
        argv.remove("--check")
        if argv:
            raise SystemExit("--check takes no bench names")
        raise SystemExit(1 if check_artifacts() else 0)
    sel = set(argv)
    unknown = sel - set(BENCHES)
    if unknown:
        raise SystemExit(f"unknown benches {sorted(unknown)}; "
                         f"choose from {sorted(BENCHES)}")
    print("name,us_per_call,derived")
    ran = []
    for name, fn in BENCHES.items():
        if not sel or name in sel:
            fn()
            ran.append(name)
    if ran:
        _append_trajectory(ran)


if __name__ == "__main__":
    main()
