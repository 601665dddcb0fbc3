"""Serving engine: decode fidelity, suspension/resume, VILLA tiering."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import testlib as TL
from repro.configs import ARCH_NAMES, get_reduced
from repro.models import lm
from repro.serve.engine import (Engine, EngineFull, Request, UnknownSession,
                                rounds_f32_dots_to_bf16)


@pytest.fixture(scope="module")
def setup():
    cfg = get_reduced("tinyllama-1.1b")
    params = lm.init_lm(cfg, jax.random.key(0))
    return cfg, params


@pytest.fixture(scope="module", params=["as_given", "bf16_matmul_weights"])
def held(request, setup):
    """The engine's params as given, and as the engine holds them on a TPU
    (``lm.bf16_matmul_weights``): on the CPU an engine serves either as
    given, so both must decode, suspend and resume alike."""
    cfg, params = setup
    if request.param == "bf16_matmul_weights":
        params = lm.bf16_matmul_weights(cfg, params)
    return cfg, params


def _greedy_reference(cfg, params, prompt, n_new):
    cache = lm.init_cache(cfg, 1, max_len=96)
    logits, cache = lm.prefill(cfg, params, jnp.asarray(prompt)[None], cache)
    toks = [int(jnp.argmax(logits[0, -1]))]
    pos = len(prompt)
    while len(toks) < n_new:
        lg, cache = lm.decode_step(cfg, params, cache,
                                   jnp.asarray([[toks[-1]]]), jnp.int32(pos))
        toks.append(int(jnp.argmax(lg[0, 0])))
        pos += 1
    return toks


def test_engine_matches_reference_decode(held):
    cfg, params = held
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, 12).astype(np.int32)
    eng = Engine(cfg, params, slots=2, max_len=96)
    req = Request(uid=0, prompt=prompt, max_new=6)
    eng.submit(req)
    while eng.active:
        eng.step()
    assert eng.stats["suspends"] == 1
    assert req.generated == _greedy_reference(cfg, params, prompt, 6)


def test_engine_continuous_batching_isolation(setup):
    """Two concurrent requests must produce the same tokens as served
    alone — slots don't leak state across the batch."""
    cfg, params = setup
    rng = np.random.default_rng(1)
    p1 = rng.integers(0, cfg.vocab_size, 10).astype(np.int32)
    p2 = rng.integers(0, cfg.vocab_size, 10).astype(np.int32)

    eng = Engine(cfg, params, slots=2, max_len=96)
    r1, r2 = Request(0, p1, 5), Request(1, p2, 5)
    eng.submit(r1)
    eng.submit(r2)
    while eng.active:
        eng.step()
    alone1 = _greedy_reference(cfg, params, p1, 5)
    alone2 = _greedy_reference(cfg, params, p2, 5)
    assert r1.generated == alone1
    assert r2.generated == alone2


def test_suspend_resume_roundtrip(held):
    cfg, params = held
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
    eng = Engine(cfg, params, slots=2, max_len=96, n_sessions=8)
    req = Request(uid=3, prompt=prompt, max_new=4)
    eng.submit(req)
    while eng.active:
        eng.step()
    pos_after = eng.session_pos[3]
    assert pos_after == len(prompt) + 3      # prompt + (max_new-1) decodes
    slot = eng.resume(3, extra_new=2)
    assert eng.pos[slot] == pos_after
    while eng.active:
        eng.step()
    assert eng.stats["resumes"] == 1


def test_one_dispatch_one_transfer_per_step(setup):
    """The tentpole invariant: however ragged the slot positions are, a step
    is exactly ONE jitted dispatch and ONE device→host transfer, and the
    decode function compiles exactly once."""
    cfg, params = setup
    rng = np.random.default_rng(4)
    eng = Engine(cfg, params, slots=3, max_len=96)
    # three different prompt lengths -> three different positions per step
    for uid, ln in enumerate((5, 9, 13)):
        eng.submit(Request(uid=uid, max_new=50,
                           prompt=rng.integers(0, cfg.vocab_size, ln)
                           .astype(np.int32)))
    assert len(set(eng.pos[list(eng.active)])) == 3
    before = TL.snapshot_stats(eng)
    for _ in range(6):
        eng.step()
    TL.assert_dispatch_delta(before, eng.stats, decode=6, host=6)
    TL.assert_compile_count(eng, "decode", 1)


def test_engine_full_raises_clearly(setup):
    cfg, params = setup
    rng = np.random.default_rng(5)
    eng = Engine(cfg, params, slots=1, max_len=96, n_sessions=8)
    prompt = rng.integers(0, cfg.vocab_size, 4).astype(np.int32)
    eng.submit(Request(uid=0, prompt=prompt, max_new=30))
    with pytest.raises(EngineFull):
        eng.submit(Request(uid=1, prompt=prompt, max_new=2))
    while eng.active:
        eng.step()
    eng.submit(Request(uid=1, prompt=prompt, max_new=30))  # slot freed
    with pytest.raises(EngineFull):
        eng.resume(0, extra_new=2)
    while eng.active:
        eng.step()
    assert eng.resume(0, extra_new=2) == 0


def test_resume_unknown_uid_is_rejected_without_mutation(setup):
    cfg, params = setup
    eng = Engine(cfg, params, slots=2, max_len=96, n_sessions=8)
    before = jax.tree.map(np.asarray, eng.sessions)
    with pytest.raises(UnknownSession):
        eng.resume(99, extra_new=2)
    after = jax.tree.map(np.asarray, eng.sessions)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        assert np.array_equal(a, b)            # store untouched by the error
    assert not eng.active and eng.stats["resumes"] == 0


def test_resume_of_active_uid_and_duplicate_wave_rejected(setup):
    """A uid can only be resumed while suspended: resuming it twice (or
    duplicating it in a wave) would fork a stale snapshot."""
    cfg, params = setup
    rng = np.random.default_rng(12)
    eng = Engine(cfg, params, slots=3, max_len=96, n_sessions=8)
    prompt = rng.integers(0, cfg.vocab_size, 5).astype(np.int32)
    eng.submit(Request(uid=0, prompt=prompt, max_new=3))
    eng.submit(Request(uid=1, prompt=prompt, max_new=3))
    while eng.active:
        eng.step()
    eng.resume(0, extra_new=30)
    with pytest.raises(ValueError, match="already active"):
        eng.resume(0, extra_new=2)
    with pytest.raises(ValueError, match="already active"):
        eng.resume_many([1, 0], extra_new=2)
    with pytest.raises(ValueError, match="duplicate"):
        eng.resume_many([1, 1], extra_new=2)
    assert [r.uid for r in eng.active.values()] == [0]  # failed waves: no-op


def test_store_index_collision_evicts_explicitly(setup):
    """uid and uid+n_sessions alias the same store index: the older session
    must be evicted (stats + UnknownSession), never silently corrupted."""
    cfg, params = setup
    rng = np.random.default_rng(6)
    eng = Engine(cfg, params, slots=2, max_len=96, n_sessions=4)
    prompt = rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
    for uid in (1, 5):                          # 5 % 4 == 1 % 4
        eng.submit(Request(uid=uid, prompt=prompt, max_new=3))
        while eng.active:
            eng.step()
    assert eng.stats["evictions"] == 1
    with pytest.raises(UnknownSession):
        eng.resume(1, extra_new=2)              # evicted by uid 5
    eng.resume(5, extra_new=2)                  # survivor resumes fine
    while eng.active:
        eng.step()


def test_suspend_resume_decode_matches_uninterrupted(held):
    """End-to-end equivalence: suspend→resume→decode produces exactly the
    tokens an uninterrupted decode would have produced."""
    cfg, params = held
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, cfg.vocab_size, 9).astype(np.int32)
    straight = _greedy_reference(cfg, params, prompt, 10)

    eng = Engine(cfg, params, slots=2, max_len=96, n_sessions=8)
    req = Request(uid=11, prompt=prompt, max_new=4)
    eng.submit(req)
    while eng.active:
        eng.step()                              # emit 4, then auto-suspend
    slot = eng.resume(11, extra_new=4)          # continue: 3 more tokens
    r1 = eng.active[slot]
    while eng.active:
        eng.step()
    slot = eng.resume(11, extra_new=4)          # and 3 more again
    r2 = eng.active[slot]
    while eng.active:
        eng.step()
    # generated[0] of a resumed request is the pre-suspension token (the
    # decode seed), so the genuinely new tokens are generated[1:]
    got = req.generated + r1.generated[1:] + r2.generated[1:]
    assert got == straight
    assert eng.stats["suspends"] == 3 and eng.stats["resumes"] == 2


def test_suspend_resume_preserves_dtypes(setup):
    """The session store holds raw bytes (uint8 pages) sized by the true leaf
    dtypes — no float32 upcast — and restore is bit-exact."""
    cfg, params = setup
    rng = np.random.default_rng(8)
    eng = Engine(cfg, params, slots=2, max_len=96, n_sessions=8)
    assert eng.sessions.slow.dtype == jnp.uint8
    exact = sum(np.prod(l.shape[:1] + l.shape[2:]) * l.dtype.itemsize
                for l in jax.tree.leaves(eng.cache))
    assert eng.snapshot_bytes == exact          # not 4x'd by an upcast
    prompt = rng.integers(0, cfg.vocab_size, 7).astype(np.int32)
    eng.submit(Request(uid=0, prompt=prompt, max_new=3))
    while eng.active:
        eng.step()
    snap = jax.tree.map(lambda x: np.asarray(x[:, 0]), eng.cache)
    slot = eng.resume(0, extra_new=2)
    restored = jax.tree.map(lambda x: np.asarray(x[:, slot]), eng.cache)
    for a, b in zip(jax.tree.leaves(snap), jax.tree.leaves(restored)):
        assert a.dtype == b.dtype
        assert np.array_equal(a, b)             # bit-exact round trip


def test_resume_many_single_wave_matches_sequential(setup):
    cfg, params = setup
    rng = np.random.default_rng(9)
    prompts = {uid: rng.integers(0, cfg.vocab_size, 6 + uid).astype(np.int32)
               for uid in range(3)}

    def serve(resume_batched):
        eng = Engine(cfg, params, slots=3, max_len=96, n_sessions=8)
        for uid, p in prompts.items():
            eng.submit(Request(uid=uid, prompt=p, max_new=3))
        while eng.active:
            eng.step()
        if resume_batched:
            slots = eng.resume_many([0, 1, 2], extra_new=3)
        else:
            slots = [eng.resume(uid, extra_new=3) for uid in range(3)]
        resumed = {eng.active[s].uid: eng.active[s] for s in slots}
        while eng.active:
            eng.step()
        # post-resume tokens per uid — the state the wave restored
        return {uid: r.generated for uid, r in resumed.items()}

    seq = serve(False)
    bat = serve(True)
    assert set(bat) == {0, 1, 2}
    assert all(len(t) == 3 for t in bat.values())
    assert seq == bat


def test_step_unbatched_reference_path_and_ragged_fix(setup):
    """Uniform positions: the kept pre-PR path (position groups + per-slot
    sync) emits the same tokens as the one-dispatch path.  Ragged positions:
    the grouped path pays one dispatch per group AND corrupts neighbouring
    slots (every group's cache write lands in all rows — the latent bug the
    active-mask fixes), so there only the one-dispatch path tracks the
    per-request greedy reference."""
    cfg, params = setup
    rng = np.random.default_rng(10)

    def serve(step_name, lens):
        prompts = [rng.integers(0, cfg.vocab_size, ln).astype(np.int32)
                   for ln in lens]
        eng = Engine(cfg, params, slots=3, max_len=96)
        reqs = [Request(uid=i, prompt=p, max_new=5)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        while eng.active:
            getattr(eng, step_name)()
        refs = [_greedy_reference(cfg, params, p, 5) for p in prompts]
        return [r.generated for r in reqs], refs, eng.stats["decode_dispatches"]

    rng = np.random.default_rng(10)
    toks_new, refs_new, d_new = serve("step", (7, 7, 7))
    rng = np.random.default_rng(10)
    toks_old, refs_old, d_old = serve("step_unbatched", (7, 7, 7))
    assert toks_new == toks_old == refs_new     # uniform: paths agree
    assert d_new == d_old == 4                  # one group per step

    toks_new, refs, d_new = serve("step", (5, 8, 11))
    assert toks_new == refs                     # ragged: one-sync path exact
    toks_old, refs, d_old = serve("step_unbatched", (5, 8, 11))
    assert d_old > d_new                        # one dispatch per group
    assert toks_old != refs                     # the corruption being fixed


def test_suspend_many_wave_matches_sequential(setup):
    """A burst of completions suspends in ONE fused wave (step() routes
    through suspend_many): session state, later resumed tokens, and the
    modeled movement charge all match per-slot sequential suspends."""
    cfg, params = setup
    rng = np.random.default_rng(13)
    prompts = {uid: rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
               for uid in range(3)}

    def finish(eng):
        toks = {}
        for uid in prompts:                  # resume + decode to completion
            slot = eng.resume(uid, extra_new=3)
            toks[uid] = eng.active[slot]
        while eng.active:
            eng.step()
        return {uid: r.generated for uid, r in toks.items()}

    # wave path: same-length prompts all complete on the same step, so
    # step() suspends the whole burst through suspend_many
    eng_w = Engine(cfg, params, slots=3, max_len=96, n_sessions=8)
    for uid, p in prompts.items():
        eng_w.submit(Request(uid=uid, prompt=p, max_new=3))
    while eng_w.active:
        eng_w.step()
    assert eng_w.stats["suspends"] == 3
    TL.assert_compile_count(eng_w, "suspend_many", 1)
    TL.assert_compile_count(eng_w, "suspend", 0)          # wave, not 3 calls

    # sequential reference: stop at the same position, suspend one by one
    eng_s = Engine(cfg, params, slots=3, max_len=96, n_sessions=8)
    for uid, p in prompts.items():
        eng_s.submit(Request(uid=uid, prompt=p, max_new=10**9))
    eng_s.step()
    eng_s.step()                             # 3 generated tokens, like above
    for s in sorted(eng_s.active):
        eng_s.suspend(s)
    assert eng_w.session_pos == eng_s.session_pos
    assert eng_w.session_tok == eng_s.session_tok
    # fusion is cost-transparent: wave charge == sum of single charges
    assert eng_w.stats["modeled_move_ns_lisa"] == pytest.approx(
        eng_s.stats["modeled_move_ns_lisa"])
    assert finish(eng_w) == finish(eng_s)    # resumed decode identical


def test_resume_many_single_element_wave(setup):
    """A wave of exactly one resume is valid and equals a plain resume
    (regression: the k=1 fused plan must still take the batched env path)."""
    cfg, params = setup
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, cfg.vocab_size, 6).astype(np.int32)

    def serve(batched):
        eng = Engine(cfg, params, slots=2, max_len=96, n_sessions=8)
        eng.submit(Request(uid=0, prompt=prompt, max_new=3))
        while eng.active:
            eng.step()
        slots = (eng.resume_many([0], extra_new=3) if batched
                 else [eng.resume(0, extra_new=3)])
        req = eng.active[slots[0]]
        while eng.active:
            eng.step()
        return req.generated, eng.stats["modeled_move_ns_lisa"]

    toks_wave, ns_wave = serve(True)
    toks_one, ns_one = serve(False)
    assert toks_wave == toks_one
    assert ns_wave == ns_one                   # wave of 1 charges like 1


def test_villa_hit_rate_with_hot_sessions(setup):
    cfg, params = setup
    rng = np.random.default_rng(3)
    eng = Engine(cfg, params, slots=2, max_len=96, n_sessions=8)
    for uid in range(6):
        eng.submit(Request(uid=uid,
                           prompt=rng.integers(0, cfg.vocab_size, 6).astype(
                               np.int32), max_new=3))
        while eng.active:
            eng.step()
    for _ in range(24):                       # hot sessions 0 and 1
        uid = int(rng.integers(0, 2)) if rng.random() < 0.85 else \
            int(rng.integers(0, 6))
        eng.resume(uid, extra_new=2)
        while eng.active:
            eng.step()
    assert eng.hit_rate() > 0.15


# ---- held weights: bfloat16 matmul weights where the chip rounds to them ---

@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_bf16_matmul_weights_casts_only_dense_path_matmuls(arch):
    """Only the dense path's matmul-only leaves turn bfloat16: attention
    q/k/v/o, a dense MLP's three matrices and an untied head.  Gathered
    tables, norms, biases and the other mixers' weights stay as given."""
    cfg = get_reduced(arch)
    params = jax.eval_shape(lambda: lm.init_lm(cfg, jax.random.key(0)))
    held = jax.eval_shape(partial(lm.bf16_matmul_weights, cfg), params)
    want = set() if cfg.tie_embeddings else {("head",)}
    for i, (_, group) in enumerate(lm.stages_of(cfg)):
        for j, (mixer_kind, mlp_kind) in enumerate(group):
            block = (f"stage{i}", f"b{j}")
            if mixer_kind in ("attn_full", "attn_local"):
                want |= {block + ("mixer", k) for k in ("wq", "wk", "wv", "wo")}
            if mlp_kind == "dense":
                want |= {block + ("mlp", k) for k in ("wi_gate", "wi_up", "wo")}
    cast = {tuple(k.key for k in path)
            for path, leaf in jax.tree_util.tree_leaves_with_path(held)
            if leaf.dtype == jnp.bfloat16}
    assert cast == want


def test_engine_off_tpu_holds_params_as_given(setup):
    cfg, params = setup
    eng = Engine(cfg, params, slots=2, max_len=32, n_sessions=4)
    assert not rounds_f32_dots_to_bf16(eng.device, eng.slots)
    leaves = jax.tree.leaves(params)
    for a, b in zip(leaves, jax.tree.leaves(eng.params)):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    assert eng.stats["weight_bytes_bf16"] == 0
    assert eng.stats["weight_bytes_f32"] == sum(l.nbytes for l in leaves)


class _Device:
    def __init__(self, platform):
        self.platform = platform


@pytest.mark.parametrize("platform,precision,rows,rounds", [
    ("tpu", None, 4, True), ("tpu", "default", 4, True),
    ("tpu", "bfloat16", 4, True), ("tpu", None, 2, True),
    ("tpu", None, 1, False), ("tpu", "default", 1, False),
    ("tpu", "highest", 4, False), ("tpu", "float32", 4, False),
    ("tpu", "tensorfloat32", 4, False), ("cpu", None, 4, False),
    ("gpu", None, 4, False)])
def test_bf16_weights_only_where_f32_dots_round_to_bf16(platform, precision,
                                                         rows, rounds):
    """The engine holds bfloat16 matmul weights exactly where a float32
    dot already runs as one bfloat16 pass: a TPU at the default precision,
    with more than one row (a one-row dot is a float32 multiply-reduce).
    It reads the precision in effect, a context manager's included."""
    with jax.default_matmul_precision(precision):
        assert rounds_f32_dots_to_bf16(_Device(platform), rows) is rounds
