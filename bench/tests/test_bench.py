"""CPU tests of the benchmark's yardstick: traffic, peaks, the layout that
finds cells and readers by name, the trace reduction, and the comparison
that decides ``correct``, driven end to end at reduced widths with the
timed path sound and broken.  The chip's own readings are in ``PERF.md``.
"""
from __future__ import annotations

import collections
import json
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, generator, run, trace as T

BENCH = pathlib.Path(__file__).resolve().parents[1]
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))
CONFIGS = sorted(p.stem for p in (BENCH / "configs").glob("*.json"))
# 2 s of a multi-turn chat mix on Mistral-7B-v0.3 (4 layers), traced on one
# TPU v5e (``bench/run.py --trace 1``)
FIXTURE = BENCH / "tests" / "trace_fixture" / "chat_2s.xplane.pb.gz"


def load(kind: str, name: str) -> dict:
    return json.loads((BENCH / kind / f"{name}.json").read_text())


# ---- traffic ---------------------------------------------------------------
@pytest.mark.parametrize("mix", MIXES)
def test_traffic_is_deterministic_per_seed(mix):
    t = load("traffic", mix)
    a = generator.sessions(t, seed=2**31 + 5, seconds=30, max_len=2048)
    b = generator.sessions(t, seed=2**31 + 5, seconds=30, max_len=2048)
    assert a == b
    assert np.array_equal(generator.prompt_tokens(2**33 + 1, 3, 50, 1000),
                          generator.prompt_tokens(2**33 + 1, 3, 50, 1000))
    assert not np.array_equal(generator.prompt_tokens(1, 3, 50, 1000),
                              generator.prompt_tokens(2, 3, 50, 1000))


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_offers_the_same_work_in_the_window(mix):
    t = load("traffic", mix)
    n = generator.pool_size(t, 30)
    sizes = lambda s: sorted((x.prompt_len, x.outputs)
                             for x in generator.sessions(
                                 t, seed=s, seconds=30, max_len=2048)[:n])
    assert sizes(1) == sizes(2**31 + 99)
    if t["loop"] == "open":
        for seed in (1, 7):
            due = [x.due_s for x in generator.sessions(
                t, seed=seed, seconds=30, max_len=2048)]
            assert due[0] == 0 and abs(due[n] - 30) < 1e-9


@pytest.mark.parametrize("max_len", [2048, 600])
@pytest.mark.parametrize("mix", MIXES)
def test_contexts_fit_and_lengths_follow_the_clips(mix, max_len):
    t = load("traffic", mix)
    p, o = t["prompt_tokens"], t["output_tokens"]
    for s in generator.sessions(t, seed=3, seconds=51, max_len=max_len):
        assert s.context <= max_len
        assert p["min"] <= s.prompt_len <= p["max"]
        assert 1 <= len(s.outputs) <= 1 + t["followups"]["max"]
        assert len(s.think_s) == len(s.outputs) - 1
        assert all(x <= o["max"] for x in s.outputs)
        assert all(x >= o["min"] for x in s.outputs[1:])
        if s.context < max_len:         # nothing clipped by the envelope
            assert s.outputs[0] >= o["min"]


def test_bursts_arrive_together_at_the_same_rate():
    t = dict(load("traffic", "chat_short"), burst=4)
    s = generator.sessions(t, seed=5, seconds=30, max_len=2048)
    due = [x.due_s for x in s]
    assert all(due[i] == due[i - i % 4] for i in range(len(due)))
    n = generator.pool_size(t, 30)
    assert due[-(-n // 4) * 4] == pytest.approx(30)
    assert sorted(x.prompt_len for x in s[:n]) == sorted(
        x.prompt_len for x in generator.sessions(
            load("traffic", "chat_short"), seed=5, seconds=30,
            max_len=2048)[:n])


def test_lengths_follow_the_stated_median():
    spec = {"median": 256, "sigma": 0.7, "min": 32, "max": 1024}
    x = generator.lengths(np.random.default_rng(0), spec, 20000)
    assert x.min() == 32 and x.max() == 1024
    assert abs(np.median(x) - 256) < 8


def test_envelope_drops_short_follow_ups():
    assert generator.fit_envelope(1000, [40, 30, 20], 1050, 16) == (40,)
    assert generator.fit_envelope(1000, [40, 30, 20], 1060, 16) == (40, 20)
    assert generator.fit_envelope(2000, [100], 2048, 16) == (48,)


def test_seed_words_take_large_seeds():
    assert generator.seed_words(5) == [5]
    assert generator.seed_words(2**40 + 3) == [3, 256]
    with pytest.raises(ValueError):
        generator.seed_words(-1)


# ---- peaks and layout --------------------------------------------------
def test_unknown_device_kind_raises():
    assert check.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        check.peaks("TPU v99 imaginary")
    assert check.peaks("cpu", required=False) is None


def test_cells_and_metrics_are_found_by_name():
    for w in CELLS.values():
        assert w["config"] in CONFIGS and w["traffic"] in MIXES
        conf = load("configs", w["config"])
        assert (BENCH / "references" / f"{conf['reference']}.py").is_file()
        assert (BENCH / "models" / f"{conf['model']}.py").is_file()
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert callable(run.reader(m["name"]))
    for c in SPEC["configs"]:
        conf = json.loads((BENCH.parent / c["file"]).read_text())
        assert conf["name"] == c["name"] and conf["reduced"] == c["reduced"]


def copy_bench(root: pathlib.Path) -> dict:
    """A copy of the benchmark's files under ``root``, without its tests;
    returns each file's bytes, to show that none changed."""
    import shutil
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    return {p: p.read_bytes() for p in (root / "bench").rglob("*")
            if p.is_file()}


def test_a_new_cell_mix_and_metric_are_found_by_name(tmp_path):
    """A later PR adds a cell by adding files and entries: a copy of the
    benchmark with a new mix (``bench/traffic``), a new metric's reader
    (``bench/metrics``) and their entries in ``BENCHMARK.json`` runs the new
    cell, with no file of the copy changed."""
    before = copy_bench(tmp_path)
    mix = dict(load("traffic", "chat_short"), burst=8)
    (tmp_path / "bench" / "traffic" / "chat_bursty.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench" / "metrics" / "ticks_per_s.py").write_text(
        "def read(run):\n"
        "    w = run.window\n"
        "    return w.ticks / (w.t1 - w.t0)\n")
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append(dict(
        CELLS["mistral7b-chat-short"], name="mistral7b-chat-bursty",
        traffic="chat_bursty"))
    spec["end_to_end"].append({
        "name": "ticks_per_s", "unit": "1/s", "better": "higher",
        "bound": 0.05, "source": "host_clock",
        "workloads": ["mistral7b-chat-bursty"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    bench, cell, conf, t = run.load_cell(tmp_path, "mistral7b-chat-bursty")
    assert t["burst"] == 8
    conf.update(TINY, engine={"slots": 4, "max_len": 256, "n_sessions": 24},
                mean_logit_gap_limit=TINY_LIMIT,
                widest_logit_gap_limit=TINY_LIMIT)
    t["prompt_tokens"].update(median=40, min=8, max=100)
    t["output_tokens"].update(median=12, min=4, max=30)
    out = run.run_cell(bench, cell, conf, t, seed=2**32 + 5, seconds=2.0,
                       trace=False, require_tpu=False, root=tmp_path,
                       log=lambda *a, **k: None)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"setup_s", "ticks_per_s"}
    assert out["metrics"]["ticks_per_s"]["value"] > 0
    assert all(p.read_bytes() == b for p, b in before.items())


def test_a_new_configuration_and_builder_are_found_by_name(tmp_path):
    """A later PR adds a configuration by adding files and entries: a copy
    of the benchmark with a new configuration that names a new builder
    (``bench/models``, here the dense one under another name) and the
    existing ``llama`` reference runs its cell, correct, with no file of
    the copy changed."""
    before = copy_bench(tmp_path)
    models = tmp_path / "bench" / "models"
    (models / "dense_twin.py").write_bytes((models / "dense.py").read_bytes())
    conf = dict(load("configs", "mistral-7b-v0.3-d4"), name="mistral-twin",
                model="dense_twin")
    (tmp_path / "bench" / "configs" / "mistral-twin.json").write_text(
        json.dumps(conf))
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append(dict(SPEC["configs"][0], name="mistral-twin",
                                file="bench/configs/mistral-twin.json"))
    spec["workloads"].append(dict(
        CELLS["mistral7b-chat-short"], name="twin-chat-short",
        config="mistral-twin"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    bench, cell, conf, t = run.load_cell(tmp_path, "twin-chat-short")
    from bench import model
    assert model.builder(conf, tmp_path).__file__ == str(
        models / "dense_twin.py")
    conf.update(TINY, engine={"slots": 4, "max_len": 256, "n_sessions": 24},
                mean_logit_gap_limit=TINY_LIMIT,
                widest_logit_gap_limit=TINY_LIMIT)
    t["prompt_tokens"].update(median=40, min=8, max=100)
    t["output_tokens"].update(median=12, min=4, max=30)
    t.update(sessions_per_s=4.0)
    out = run.run_cell(bench, cell, conf, t, seed=2**32 + 9, seconds=2.0,
                       trace=False, require_tpu=False, root=tmp_path,
                       log=lambda *a, **k: None)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and set(out["metrics"]) == {"setup_s"}
    assert all(p.read_bytes() == b for p, b in before.items())


# DeepSeek-V2's Hugging Face keys (latent attention; routed and shared
# experts) at the program's reduced widths (``deepseek_v2_236b.REDUCED``)
MLA_MOE = dict(
    name="mla-moe-tiny", model="mla_moe", torch_dtype="bfloat16",
    hidden_act="silu", tie_word_embeddings=False, num_hidden_layers=4,
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
    intermediate_size=128, vocab_size=512, rope_theta=10000.0,
    rms_norm_eps=1e-6, kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8,
    n_shared_experts=1, num_experts_per_tok=2, moe_intermediate_size=64,
    moe_layer_freq=1)


def test_a_latent_attention_expert_builder_brings_its_own_counts(tmp_path):
    """A builder for a latent-attention, mixture-of-experts model is one
    new file: its weights pass the engine's layout check, and the readers
    that count bytes and operations read its counts (the latent K/V of a
    position, the experts a step reaches), with no file of the copy
    changed."""
    import dataclasses
    from types import SimpleNamespace

    import jax

    from bench import model
    from repro.configs.deepseek_v2_236b import REDUCED
    before = copy_bench(tmp_path)
    (tmp_path / "bench" / "models" / "mla_moe.py").write_bytes(
        (BENCH / "tests" / "mla_moe_builder.py").read_bytes())
    build = model.builder(MLA_MOE, tmp_path)
    cfg = build.model_config(MLA_MOE)
    # REDUCED's head_dim (48) is not qk_nope + qk_rope (24), as its full
    # CONFIG's is (192); latent layers do not read it.  The rest are the
    # program's settings, not the model's shape.
    shape = lambda c: {k: v for k, v in dataclasses.asdict(c).items()
                       if k not in ("name", "head_dim", "capacity_factor",
                                    "remat", "attn_block", "scan_chunk")}
    assert shape(cfg) == shape(REDUCED)
    weights = model.init_weights(build, cfg, jax.random.key(3))
    assert weights["stage0"]["b0"]["mlp"]["wi_gate"].shape == (4, 8, 64, 64)

    r = SimpleNamespace(
        trace=synthetic(), peaks=check.peaks("TPU v5 lite"), cfg=cfg,
        conf=MLA_MOE, model=build, window=SimpleNamespace(t0=0.0, t1=1.0),
        rec={"decode": [(2, 30), (3, 41)], "prefill": [10, 20],
             "suspend": [[16]], "resume": [[20]]})
    read = {m: run.reader(m, tmp_path)(r) for m in (
        "decode_roofline", "serve_mfu", "page_kernel_roofline")}
    kv = build.kv_bytes_per_position(cfg, 2)
    assert kv == 4 * (32 + 8) * 2                   # latent + rotary key
    peak, bw = r.peaks["flops_per_s"], r.peaks["hbm_bytes_per_s"]
    assert read["page_kernel_roofline"] == pytest.approx(
        100 * 2 * (16 + 20) * kv / bw / T.op_time(r.trace, "villa_gather")[0])
    steps = [build.decode_step(cfg, a, h, 2) for a, h in r.rec["decode"]]
    least = [max(f / peak, b / bw) for f, b in steps]
    secs, n = T.program_time(r.trace, "decode_step_batched")
    assert read["decode_roofline"] == pytest.approx(
        100 * sum(least) / len(least) * n / secs)
    flops = (sum(build.prefill_flops(cfg, k) for k in (10, 20))
             + sum(f for f, _ in steps))
    assert read["serve_mfu"] == pytest.approx(100 * flops / peak)
    # the dense family's counts of the same shapes read otherwise
    dense = model.builder({"model": "dense"}, tmp_path)
    assert dense.kv_bytes_per_position(cfg, 2) != kv
    assert dense.decode_step(cfg, 2, 30, 2) != steps[0]
    assert all(p.read_bytes() == b for p, b in before.items())


# the matmul parameters a decode step reads (layers and head) and the K/V
# bytes of one position at bfloat16, counted by hand from the published
# widths: Mistral 4 x (2 x 4096 x 4096 + 2 x 4096 x 1024 + 3 x 4096 x
# 14336) + 4096 x 32768; DeepSeek 2 x (4 x 4096 x 4096 + 3 x 4096 x 11008)
# + 4096 x 102400; K/V 4 x 2 x 8 x 128 x 2 and 2 x 2 x 32 x 128 x 2
HAND_COUNTS = {"mistral-7b-v0.3-d4": (1_006_632_960, 16_384),
               "deepseek-llm-7b-d2": (824_180_736, 32_768)}


@pytest.mark.parametrize("name", sorted(HAND_COUNTS))
def test_dense_counts_equal_the_hand_counts(name):
    from types import SimpleNamespace

    from bench import model, work
    conf = load("configs", name)
    build = model.builder(conf)
    r = SimpleNamespace(cfg=build.model_config(conf), conf=conf,
                        model=build)
    params, kv = HAND_COUNTS[name]
    p = build.param_count(r.cfg)
    assert p["layers"] + p["head"] == params
    assert work.value_bytes(conf) == 2
    assert work.kv_bytes_per_position(r) == kv
    # a step with no slot reads the weights alone, at 2 bytes each
    assert work.decode_step(r, 0, 0) == (0.0, 2 * params)
    assert work.decode_step(r, 1, 9)[1] == 2 * params + 2 * 4096 + 10 * kv


@pytest.mark.parametrize("name", CONFIGS)
def test_weights_are_those_of_the_seed(name):
    """The weights of a fixed seed at reduced widths, bit for bit: the
    checksum the harness's weights gave before the builders moved to
    ``bench/models``."""
    import hashlib

    import jax

    from bench import model
    conf = dict(load("configs", name), **TINY)
    build = model.builder(conf)
    w = model.init_weights(build, build.model_config(conf),
                           jax.random.key(2**31 - 1))
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(w)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == (
        "6d5da36b190160c9cafe4bd518db6072cfe14aaf3c36fdcfb6afece8958fe8ce")


def test_benchmark_json_keeps_its_shape():
    import re
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert name.match(c["name"]) and len(c["why"]) <= 200
        assert c["file"].startswith("bench/")
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"]: w for w in SPEC["workloads"]}
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert name.match(w["name"]) and len(w["why"]) <= 200
        assert w["chips"] == 1
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert "bound" not in m and len(m["layer"]) <= 200
        moved = e2e[m["moves"]]
        # every cell a per-layer metric reads reports the metric it moves
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    for w in cells:                 # setup_s and one more, one per-layer
        assert sum(w in m.get("workloads", cells)
                   for m in SPEC["end_to_end"]) >= 2
        assert any(w in m["workloads"] for m in SPEC["per_layer"])


# ---- trace reduction -----------------------------------------------------
def synthetic() -> T.Trace:
    ops = [("%fusion.1 = f32[4] fusion()", 10, 20),
           ("%villa_gather.2 = u8[8] custom-call()", 15, 30),
           ("%fusion.3 = f32[4] fusion()", 50, 60)]
    mods = [("jit_decode_step_batched(1)", 10, 30),
            ("jit_decode_step_batched(1)", 50, 60)]
    host = [("bench.window", 0, 100), ("bench.tick", 0, 45),
            ("bench.step_end", 32, 44), ("bench.tick", 45, 100)]
    return T.Trace(window=(0, 100), modules=[mods], ops=[ops], host=host)


def test_union_busy_and_idle_attribution():
    tr = synthetic()
    assert T.union_ns(tr.ops[0]) == 30
    assert T.busy_s(tr) == pytest.approx(30e-9)
    assert T.idle_gaps(tr) == [(0, 10), (30, 50), (60, 100)]
    assert T.program_time(tr, "decode_step_batched") == (
        pytest.approx(30e-9), 2)
    assert T.op_time(tr, "villa_gather") == (pytest.approx(15e-9), 1)
    idle = dict(T.top_idle(tr))
    assert idle == {"bench.tick": pytest.approx(50e-9),
                    "bench.step_end": pytest.approx(20e-9)}
    assert T.top_ops(tr)[0] == ["fusion", pytest.approx(20e-9)]
    assert T.longest_ops(tr, 1) == [["villa_gather", pytest.approx(15e-9),
                                     pytest.approx(15e-9)]]


def test_recorded_chip_trace_reduces():
    tr = T.load(str(FIXTURE))
    assert len(tr.modules) == len(tr.ops) == 1          # one chip
    assert 0 < T.busy_s(tr) <= tr.window_s
    assert tr.window_s == pytest.approx(2.0, abs=0.05)
    # every execution is named after the function the engine jitted
    progs = collections.Counter(T._short(m[0]) for m in tr.modules[0])
    assert "jit__unknown" not in progs
    assert progs["jit_decode_step_batched"] == 49
    assert progs["jit_prefill_insert"] == 2 and progs["jit__suspend_body"] == 1
    secs, n = T.program_time(tr, "decode_step_batched")
    assert n == 49 and 0.5 < secs < tr.window_s
    # one suspend writes through to both pools: two page scatters
    assert T.op_time(tr, "villa_scatter")[1] == 2
    assert T.op_time(tr, "villa_gather")[1] == 0
    ops = dict(T.top_ops(tr))
    assert len(ops) == 10 and "convert" in ops
    idle = dict(T.top_idle(tr))
    assert sum(idle.values()) == pytest.approx(
        tr.window_s - T.busy_s(tr), rel=1e-6)
    assert "bench.idle" in idle and "bench.step_end" in idle


def test_chat_short_offers_no_follow_ups():
    t = load("traffic", "chat_short")
    plan = generator.sessions(t, seed=2**31 + 17, seconds=51, max_len=2048)
    assert all(len(x.outputs) == 1 and not x.think_s for x in plan)
    # the window's first turns leave ten or more beyond the percentile that
    # the cell's first-token metric reports
    ttft = [m["name"] for m in SPEC["end_to_end"]
            if m["name"].startswith("ttft_p")]
    for name in ttft:
        q = int(name[len("ttft_p"):-len("_ms")])
        assert generator.pool_size(t, SPEC["run_seconds"]) * (
            1 - q / 100) >= 10


def test_multiturn_conversations_take_two_turns():
    t = load("traffic", "multiturn_backlog")
    plan = generator.sessions(t, seed=2**31 + 17, seconds=51, max_len=2048)
    assert len(plan) == t["pool"] > t["concurrent"]
    assert all(len(x.outputs) == 2 for x in plan)
    assert all(x.think_s == (0.0,) * (len(x.outputs) - 1) for x in plan)


# ---- the comparison that decides `correct`, at reduced widths -----------
TINY = dict(hidden_size=64, intermediate_size=176, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, vocab_size=512,
            num_hidden_layers=2)
# the limit at these widths on the CPU: float32 matmuls are exact to
# rounding there, so a sound run reads a mean and a widest gap of 0 (0.0
# on 4 runs), the int8 control a mean of 3.4e-4 to 1.0e-3 (6 runs), and a
# wrong token, a missing cache write or a lost resume moves the
# reference's logits by O(1)
TINY_LIMIT = 1e-5
# the multi-turn cell's mix is the one that suspends and resumes every
# conversation
MULTITURN = "mistral7b-multiturn-backlog"
CELLS = {x["name"]: x for x in SPEC["workloads"]}


def tiny_cell(cell: str):
    w = CELLS[cell]
    conf = load("configs", w["config"])
    conf.update(TINY, engine={"slots": 4, "max_len": 256, "n_sessions": 24},
                mean_logit_gap_limit=TINY_LIMIT,
                widest_logit_gap_limit=TINY_LIMIT)
    t = load("traffic", w["traffic"])
    t["prompt_tokens"].update(median=40, min=8, max=100)
    t["output_tokens"].update(median=12, min=4, max=30)
    if t["loop"] == "open":
        t.update(sessions_per_s=4.0)
    return w, conf, t


def run_tiny(cell: str, seconds: float = 3.0, seed: int = 2**31 + 3,
             **kw):
    w, conf, t = tiny_cell(cell)
    kw.setdefault("log", lambda *a, **k: None)
    return run.run_cell(SPEC, w, conf, t, seed=seed, seconds=seconds,
                        trace=False, require_tpu=False, **kw)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_served_path_agrees_with_the_reference(cell):
    out = run_tiny(cell)
    assert out["correct"], out["checks"]
    assert out["checks"]["mean_logit_gap"]["value"] < 1e-4
    assert out["attempted"] > 0
    assert list(out)[-1] == "checks"


def test_second_turns_resume_through_resume_many():
    """Every turn after a session's first comes back through one
    ``resume_many`` wave, and the sample judged holds resumed turns."""
    seen = {}
    lines = []
    run_tiny(MULTITURN, seconds=4.0, seed=11,
             log=lambda *a, **k: lines.append(" ".join(map(str, a))),
             also=lambda ctx: seen.update(sample=ctx.sample,
                                          win=ctx.window))
    win = seen["win"]
    later = [t for t in win.turns if t.index > 0]
    placed = [t for t in later if t.placed is not None]
    resumed = sum(map(len, win.rec["resume"]))
    assert len(later) > 20
    # a later turn with a slot came back through resume_many, and nothing
    # else did (no preemption in a backlog of one class)
    assert resumed == len(placed)
    assert any(line.startswith("[movement]") for line in lines)
    assert sum(s.resumes for s in seen["sample"]) > 0
    assert sum(len(s.served) for s in seen["sample"]) > 50


def _tokens_altered(real):
    def decode(cfg, params, cache, tokens, pos, active, **kw):
        nxt, new = real(cfg, params, cache, tokens, pos, active, **kw)
        return jnp.where(active, (nxt + 1) % cfg.vocab_size, nxt), new
    return decode


def _state_unchanged(real):
    def decode(cfg, params, cache, tokens, pos, active, **kw):
        nxt, _ = real(cfg, params, cache, tokens, pos, active, **kw)
        return nxt, cache
    return decode


def _half_batch_left_out(real):
    """The second half of the slots is left out of the step: no cache row
    written, and its last token repeated."""
    def decode(cfg, params, cache, tokens, pos, active, **kw):
        half = jnp.arange(active.shape[0]) < active.shape[0] // 2
        nxt, new = real(cfg, params, cache, tokens, pos, active & half, **kw)
        return jnp.where(half, nxt, jnp.where(active, tokens, -1)), new
    return decode


def _resume_lost(real):
    def resume(plan, waves, cache, store, sums, failed, slots, idxs):
        _, store, failed = real(plan, waves, cache, store, sums, failed,
                                slots, idxs)
        return cache, store, failed
    return resume


FAULTS = {
    "token_altered": ("repro.models.lm", "decode_step_batched",
                      _tokens_altered),
    "decode_state_unchanged": ("repro.models.lm", "decode_step_batched",
                               _state_unchanged),
    "half_batch_left_out": ("repro.models.lm", "decode_step_batched",
                            _half_batch_left_out),
    "resume_restores_nothing": ("repro.serve.engine", "_resume_many_body",
                                _resume_lost),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_reads_not_correct(fault, monkeypatch):
    import importlib
    mod, attr, make = FAULTS[fault]
    m = importlib.import_module(mod)
    monkeypatch.setattr(m, attr, make(getattr(m, attr)))
    out = run_tiny(MULTITURN, seconds=4.0)
    assert not out["correct"]
    limit = load("configs", "mistral-7b-v0.3-d4")["mean_logit_gap_limit"]
    assert out["checks"]["mean_logit_gap"]["value"] > 10 * limit


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_control_reads_not_correct(cell):
    """The int8 reference put in the program's place, judged by the
    run's own check, fails it and reads above the served tokens."""
    got = {}

    def program(ctx):
        got["program"] = check.served_gaps(ctx.ref, ctx.conf, ctx.weights,
                                           ctx.sample, ctx.max_len)

    out = run_tiny(cell, seed=2**31 + 8, control=True, also=program)
    assert not out["correct"], out["checks"]
    gap = out["checks"]["mean_logit_gap"]
    assert gap["value"] > gap["limit"] > got["program"]["mean"]
