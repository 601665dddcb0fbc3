"""v5e compile rehearsals of each benchmark configuration's programs, at
the cells' sizes, for one chip of a described ``v5e:2x2`` (no chip
attached): the decode step, the largest prefill bucket the traffic hits,
and the suspend and resume waves at every width from 1 to slots.  Each must compile and,
together with what the engine keeps resident (weights, cache, session store
and its checksum sidecar), fit the chip's 16 GB.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library, and every test
worker imports this file.
"""
from __future__ import annotations

import json
import os
import pathlib
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from bench import driver
from bench import model as M
from repro.core.dram.spec import DDR3_1600
from repro.models import lm
from repro.serve import engine as E
from repro.serve import paged_store as PS

BENCH = pathlib.Path(__file__).resolve().parents[1]
HBM = json.loads((BENCH / "peaks.json").read_text())["devices"][
    "TPU v5 lite"]["hbm_bytes"]
# every configuration of the benchmark with its cells' mixes
CELLS: dict = {}
for _w in json.loads((BENCH.parent / "BENCHMARK.json").read_text())[
        "workloads"]:
    CELLS.setdefault(_w["config"], []).append(_w["traffic"])
SLOTS = 4       # every configuration's engine has 4 slots
PROGRAMS = ("decode", "prefill",
            *(f"{p}@{k}" for p in ("suspend_many", "resume_many")
              for k in range(1, SLOTS + 1)))


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


def on_chip(tree, sharding):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sharding), tree)


def compile_for_chip(fn, *args):
    """Compile with the persistent compile cache off: a TPU executable
    written here could not be read back."""
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return fn.lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def nbytes(tree) -> int:
    return sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(tree))


def largest_bucket(conf: dict) -> int:
    n = min(max(t["prompt_tokens"]["max"] for t in mixes_of(conf["name"])),
            conf["engine"]["max_len"])
    return min(max(16, 1 << (n - 1).bit_length()), conf["engine"]["max_len"])


def mixes_of(name: str) -> list:
    return [json.loads((BENCH / "traffic" / f"{t}.json").read_text())
            for t in CELLS[name]]


# a configuration whose mixes never resume a session never builds
# resume_many
CASES = [(name, program) for name in sorted(CELLS) for program in PROGRAMS
         if not program.startswith("resume_many")
         or any(driver.resumes(t) for t in mixes_of(name))]


@pytest.mark.parametrize("name,program", CASES)
def test_program_compiles_and_fits_v5e(name, program, one_chip):
    conf = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    build = M.builder(conf)
    cfg = build.model_config(conf)
    e = conf["engine"]
    slots, max_len, n_sess = e["slots"], e["max_len"], e["n_sessions"]
    assert slots == SLOTS
    program, _, width = program.partition("@")
    params = on_chip(jax.eval_shape(
        lambda: build.weights(cfg, jax.random.key(0))), one_chip)
    cache = on_chip(jax.eval_shape(
        lambda: lm.init_cache(cfg, slots, max_len)), one_chip)
    spec = PS.PageSpec.for_cache(cache)
    villa = E.default_villa(n_sess)
    store = on_chip(jax.eval_shape(
        lambda: PS.make_session_store(spec, n_sess, villa)), one_chip)
    sums = jax.ShapeDtypeStruct((n_sess, spec.n_pages), jnp.uint32,
                                sharding=one_chip)
    suspend_plan, resume_plan = E.session_plans(spec, villa, DDR3_1600)
    vec = lambda dt, n=int(width or slots): jax.ShapeDtypeStruct(
        (n,), dt, sharding=one_chip)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    if program == "decode":
        fn = jax.jit(partial(lm.decode_step_batched, cfg),
                     donate_argnums=(1,))
        args = (params, cache, vec(jnp.int32), vec(jnp.int32),
                vec(jnp.bool_))
    elif program == "prefill":
        row = jax.ShapeDtypeStruct((1, largest_bucket(conf)), jnp.int32,
                                   sharding=one_chip)
        fn = jax.jit(partial(E.prefill_insert, cfg, max_len),
                     donate_argnums=(1,))
        args = (params, cache, row, row, scalar, scalar)
    elif program == "suspend_many":
        fn = jax.jit(partial(E._suspend_many_body, suspend_plan, {}),
                     donate_argnums=(1, 2))
        args = (cache, store, sums, vec(jnp.int32), vec(jnp.int32))
    else:
        fn = jax.jit(partial(E._resume_many_body, resume_plan, {}),
                     donate_argnums=(0, 1, 3))
        args = (cache, store, sums, scalar, vec(jnp.int32), vec(jnp.int32))
    c = compile_for_chip(fn, *args)
    m = c.memory_analysis()
    resident = nbytes(params) + nbytes(cache) + nbytes(store) + nbytes(sums)
    total = resident + m.temp_size_in_bytes + (
        m.output_size_in_bytes - m.alias_size_in_bytes)
    print(f"{name} {program}@{width or slots}: resident {resident}, temp "
          f"{m.temp_size_in_bytes}, out {m.output_size_in_bytes}, alias "
          f"{m.alias_size_in_bytes}, total {total}")
    if program in ("suspend_many", "resume_many"):
        assert "tpu_custom_call" in c.as_text()
    assert total < HBM
