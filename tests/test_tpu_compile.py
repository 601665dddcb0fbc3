"""v5e compile rehearsals of the serving path, at full width.

Each test compiles one program for one chip of a described ``v5e:2x2``
topology (no chip attached): the page kernels at the shapes of full-width
TinyLlama-1.1B sessions at the model's published 2048-token context, and the
engine's decode, one prefill bucket and its suspend / resume waves at full
width.  Shapes come from
``jax.eval_shape``, so nothing is allocated.  Each test asserts what the
chip's compiler proves: the Pallas page kernels are present as
``tpu_custom_call`` (not interpret-mode stand-ins) where a kernel is
expected, and the program fits the chip's 16 GB.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library, and every test worker
imports this file.
"""
from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.rbm_copy import villa_gather, villa_scatter
from repro.models import lm
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


def test_full_width_decode_compiles_for_v5e(model, one_chip):
    cfg, params, cache = model
    vec = lambda dt: jax.ShapeDtypeStruct((SLOTS,), dt, sharding=one_chip)
    decode = jax.jit(partial(lm.decode_step_batched, cfg),
                     donate_argnums=(1,))
    c = compile_for_chip(decode, params, cache, vec(jnp.int32),
                         vec(jnp.int32), vec(jnp.bool_))
    assert device_bytes(c) < V5E_HBM_BYTES


def test_full_width_prefill_bucket_compiles_for_v5e(model, one_chip):
    cfg, params, cache = model
    bucket = 16                  # the synthetic workload's prompts (<= 12)
    row = jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=one_chip)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    prefill = jax.jit(partial(E.prefill_insert, cfg, MAX_LEN),
                      donate_argnums=(1,))
    c = compile_for_chip(prefill, params, cache, row, row, scalar, scalar)
    assert device_bytes(c) < V5E_HBM_BYTES


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


def test_full_width_resume_wave_compiles_for_v5e(model, one_chip):
    cfg, params, cache = model
    store, sums, (_, plan) = session_store(model, one_chip)
    idx = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one_chip)
    failed = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    resume_many = jax.jit(partial(E._resume_many_body, plan, {}),
                          donate_argnums=(0, 1, 3))
    c = compile_for_chip(resume_many, cache, store, sums, failed, idx, idx)
    assert "tpu_custom_call" in c.as_text()
    assert device_bytes(c) < V5E_HBM_BYTES
