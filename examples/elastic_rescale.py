"""Elastic rescaling (fault tolerance): checkpoint on one mesh, resume on a
different one.  The relayout is the bulk cross-device movement that the LISA
substrate accelerates (checkpoint restore -> NamedSharding placement; on a
live cluster the same plan runs as lisa_copy hop chains).

Run:  PYTHONPATH=src python examples/elastic_rescale.py
(Spawns subprocesses with 8 forced host devices, pinned to the CPU.)
"""
import os
import subprocess
import sys
import tempfile

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

PHASE1 = """
import jax
from repro.checkpoint import manager as ckpt
from repro.configs import get_reduced
from repro.data.pipeline import DataConfig, batch_at
from repro.launch.mesh import make_local_mesh
from repro.train.step import ParallelConfig, init_train_state, make_train_step

cfg = get_reduced("tinyllama-1.1b")
mesh = make_local_mesh(4, 2)                       # 8 chips: 4-way DP x 2 TP
pcfg = ParallelConfig(fsdp=True)
state = init_train_state(cfg, jax.random.key(0), pcfg)
_, compile_step, _ = make_train_step(cfg, mesh, pcfg)
dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=64, global_batch=8)
b = batch_at(dcfg, 0)
step = compile_step(*jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), (state, b)))
for i in range(3):
    state, m = step(state, batch_at(dcfg, i))
ckpt.save(state, DIR, 3)
print("phase1 loss:", float(m["loss"]))
"""

PHASE2 = """
import jax
from repro.checkpoint import manager as ckpt
from repro.configs import get_reduced
from repro.data.pipeline import DataConfig, batch_at
from repro.launch.mesh import make_local_mesh
from repro.train.step import ParallelConfig, init_train_state, make_train_step

cfg = get_reduced("tinyllama-1.1b")
mesh = make_local_mesh(2, 2)                       # "lost" 4 chips: 2x2 mesh
pcfg = ParallelConfig(fsdp=True)
template = init_train_state(cfg, jax.random.key(0), pcfg)
_, compile_step, state_shardings = make_train_step(cfg, mesh, pcfg)
sh = state_shardings(jax.tree.map(
    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), template))
state = ckpt.restore(template, DIR, shardings=sh)   # elastic relayout
print("resumed at step", int(state.step), "on", mesh.devices.shape)
dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=64, global_batch=8)
b = batch_at(dcfg, 3)
step = compile_step(*jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), (state, b)))
state, m = step(state, b)
print("phase2 (rescaled) loss:", float(m["loss"]))
"""

if __name__ == "__main__":
    d = tempfile.mkdtemp()
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH", ""))
    for phase in (PHASE1, PHASE2):
        r = subprocess.run([sys.executable, "-c",
                            f"DIR={d!r}\n" + phase], env=env)
        assert r.returncode == 0
    print("elastic rescale OK: 4x2 -> 2x2 resume succeeded")
