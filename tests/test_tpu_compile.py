"""v5e compile rehearsals of the serving path, at full width.

Each test compiles one program for one chip of a described ``v5e:2x2``
topology (no chip attached): the page kernels at the shapes of full-width
TinyLlama-1.1B sessions at the model's published 2048-token context, and the
engine's decode, one prefill bucket and its suspend / resume waves at full
width.  Shapes come from
``jax.eval_shape``, so nothing is allocated.  Each test asserts what the
chip's compiler proves: the Pallas page kernels are present as
``tpu_custom_call`` (not interpret-mode stand-ins) where a kernel is
expected, and the program fits the chip's 16 GB.  Decode and prefill
compile twice, with the weights as given (float32) and as the engine holds
them on the chip (``lm.bf16_matmul_weights``), which must leave no weight
to cast on every step.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library, and every test worker
imports this file.
"""
from __future__ import annotations

import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.rbm_copy import villa_gather, villa_scatter
from repro.models import lm
from repro.roofline.hlo import split_computations
from repro.serve import paged_store as PS
from repro.core.dram.spec import DDR3_1600
from repro.serve import engine as E

V5E_HBM_BYTES = 16 * 10**9
# the smoke's serving geometry: the launcher's default workload of 8 fresh
# sessions gets one store row each
SLOTS, MAX_LEN, N_SESSIONS = 4, 2048, 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler library on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def model(one_chip):
    """Full-width TinyLlama-1.1B params and a SLOTS x MAX_LEN cache, as
    shapes placed on the described chip."""
    cfg = get_config("tinyllama-1.1b")
    on_chip = lambda t: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        t)
    params = jax.eval_shape(lambda: lm.init_lm(cfg, jax.random.key(0)))
    cache = jax.eval_shape(lambda: lm.init_cache(cfg, SLOTS, MAX_LEN))
    return cfg, on_chip(params), on_chip(cache)


@pytest.fixture(scope="module")
def held(model, one_chip):
    """The params as the engine holds them on the chip."""
    cfg, params, _ = model
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(partial(lm.bf16_matmul_weights, cfg), params))


def compile_for_chip(fn, *args):
    """Compile ``fn`` for the described chip with the persistent compile
    cache off: a TPU executable written here could not be read back."""
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return fn.lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def nbytes(tree) -> int:
    return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(tree))


def weight_converts(compiled, held) -> list:
    """The converts in ``compiled``'s optimized HLO that read or write a
    float32 array shaped like a weight ``held`` in bfloat16 (whole, or one
    layer of a stacked one): a weight cast on every call, down from float32
    or up to it."""
    shapes = set()
    for leaf in jax.tree.leaves(held):
        if leaf.dtype == jnp.bfloat16:
            shapes |= {leaf.shape, leaf.shape[1:]}

    def f32_weight(type_text: str) -> bool:
        m = re.match(r"f32\[([0-9,]*)\]", type_text)
        dims = tuple(int(d) for d in m.group(1).split(",")) if m else ()
        while dims[:1] == (1,):
            dims = dims[1:]
        return m is not None and dims in shapes

    return [op.name
            for comp in split_computations(compiled.as_text()).values()
            for op in comp.ops if op.opcode == "convert" and (
                f32_weight(op.type_text) or
                f32_weight(comp.types.get(op.operand_names[0], "")))]


def pool_shapes(model, one_chip):
    """A session store's slow pool as flat pages, and one session's page
    table: what a resume gathers and a suspend scatters."""
    spec = PS.PageSpec.for_cache(model[2])
    pool = jax.ShapeDtypeStruct(
        (N_SESSIONS * spec.n_pages, spec.page_rows, spec.page_lanes),
        jnp.uint8, sharding=one_chip)
    table = jax.ShapeDtypeStruct((spec.n_pages,), jnp.int32,
                                 sharding=one_chip)
    return spec, pool, table


def test_villa_gather_compiles_for_v5e(model, one_chip):
    spec, pool, table = pool_shapes(model, one_chip)
    c = compile_for_chip(jax.jit(villa_gather), pool, table)
    assert "tpu_custom_call" in c.as_text()
    assert device_bytes(c) < V5E_HBM_BYTES


def test_villa_scatter_compiles_for_v5e(model, one_chip):
    spec, pool, table = pool_shapes(model, one_chip)
    updates = jax.ShapeDtypeStruct(
        (spec.n_pages, spec.page_rows, spec.page_lanes), jnp.uint8,
        sharding=one_chip)
    c = compile_for_chip(jax.jit(villa_scatter, donate_argnums=(0,)),
                         pool, table, updates)
    assert "tpu_custom_call" in c.as_text()
    # the donated pool is the output: updated in place, not copied
    assert c.memory_analysis().alias_size_in_bytes >= pool.size
    assert device_bytes(c) < V5E_HBM_BYTES


@pytest.fixture(scope="module")
def decode(model, held, one_chip):
    """Full-width decode compiled with the params as given and as held:
    {"f32": ..., "bf16": ...}."""
    cfg, params, cache = model
    vec = lambda dt: jax.ShapeDtypeStruct((SLOTS,), dt, sharding=one_chip)
    fn = jax.jit(partial(lm.decode_step_batched, cfg), donate_argnums=(1,))
    return {name: compile_for_chip(fn, p, cache, vec(jnp.int32),
                                   vec(jnp.int32), vec(jnp.bool_))
            for name, p in (("f32", params), ("bf16", held))}


@pytest.fixture(scope="module")
def prefill(model, held, one_chip):
    """One prefill bucket compiled with the params as given and as held."""
    cfg, params, cache = model
    bucket = 16                  # the synthetic workload's prompts (<= 12)
    row = jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=one_chip)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    fn = jax.jit(partial(E.prefill_insert, cfg, MAX_LEN), donate_argnums=(1,))
    return {name: compile_for_chip(fn, p, cache, row, row, scalar, scalar)
            for name, p in (("f32", params), ("bf16", held))}


def test_full_width_decode_compiles_for_v5e(decode):
    assert device_bytes(decode["f32"]) < V5E_HBM_BYTES


def test_full_width_prefill_bucket_compiles_for_v5e(prefill):
    assert device_bytes(prefill["f32"]) < V5E_HBM_BYTES


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_bf16_held_weights_are_not_cast_per_call_on_v5e(program, model,
                                                         held, request):
    """Held in bfloat16, no weight is read as float32 and cast (the f32
    program casts them: the check sees what it looks for), and the
    program needs at least a third of the float32 weight bytes less."""
    compiled = request.getfixturevalue(program)
    assert weight_converts(compiled["bf16"], held) == []
    assert weight_converts(compiled["f32"], held) != []
    cast = [p for p, h in zip(jax.tree.leaves(model[1]),
                              jax.tree.leaves(held))
            if h.dtype == jnp.bfloat16]
    saved = device_bytes(compiled["f32"]) - device_bytes(compiled["bf16"])
    assert saved >= nbytes(cast) / 3


def test_one_slot_decode_reads_float32_weights_uncast_on_v5e(model, held,
                                                             one_chip):
    """A one-row dot is a float32 multiply-reduce: the one-slot decode
    reads its float32 weights without rounding them to bfloat16, which is
    why an engine with one slot holds its weights as given
    (``engine.rounds_f32_dots_to_bf16``)."""
    cfg, params, _ = model
    cache = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(lambda: lm.init_cache(cfg, 1, MAX_LEN)))
    vec = lambda dt: jax.ShapeDtypeStruct((1,), dt, sharding=one_chip)
    fn = jax.jit(partial(lm.decode_step_batched, cfg), donate_argnums=(1,))
    c = compile_for_chip(fn, params, cache, vec(jnp.int32), vec(jnp.int32),
                         vec(jnp.bool_))
    assert weight_converts(c, held) == []
    assert not E.rounds_f32_dots_to_bf16(one_chip.device_set.pop(), 1)


def session_store(model, one_chip):
    """The engine's session store and checksum sidecar at the smoke's
    geometry, as shapes on the described chip, with its suspend and resume
    plans."""
    spec = PS.PageSpec.for_cache(model[2])
    villa = E.default_villa(N_SESSIONS)
    store = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(lambda: PS.make_session_store(spec, N_SESSIONS,
                                                     villa)))
    sums = jax.ShapeDtypeStruct((N_SESSIONS, spec.n_pages), jnp.uint32,
                                sharding=one_chip)
    return store, sums, E.session_plans(spec, villa, DDR3_1600)


# A full wave (every slot at once) is the widest program the engine builds:
# packing the whole wave's pages at once needs more than the chip's 16 GB
# from a width of 3 up, so the pack leg goes slot by slot.
def test_full_width_suspend_wave_compiles_for_v5e(model, one_chip):
    cfg, params, cache = model
    store, sums, (plan, _) = session_store(model, one_chip)
    idx = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one_chip)
    suspend_many = jax.jit(partial(E._suspend_many_body, plan, {}),
                           donate_argnums=(1, 2))
    c = compile_for_chip(suspend_many, cache, store, sums, idx, idx)
    assert "tpu_custom_call" in c.as_text()
    assert device_bytes(c) < V5E_HBM_BYTES


@pytest.fixture(scope="module")
def resume_wave(model, one_chip):
    cfg, params, cache = model
    store, sums, (_, plan) = session_store(model, one_chip)
    idx = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one_chip)
    failed = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    resume_many = jax.jit(partial(E._resume_many_body, plan, {}),
                          donate_argnums=(0, 1, 3))
    return compile_for_chip(resume_many, cache, store, sums, failed, idx, idx)


def test_full_width_resume_wave_compiles_for_v5e(resume_wave):
    assert "tpu_custom_call" in resume_wave.as_text()
    assert device_bytes(resume_wave) < V5E_HBM_BYTES


def test_full_width_resume_wave_fits_beside_bf16_weights_on_v5e(
        resume_wave, model, held):
    """The widest movement program still fits while the chip holds the
    caller's float32 params and the engine's bfloat16-held copy."""
    resident = nbytes(model[1]) + nbytes(held)
    assert resident + device_bytes(resume_wave) < V5E_HBM_BYTES
