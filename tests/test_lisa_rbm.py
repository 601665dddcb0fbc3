"""Ring/hop primitives vs oracles on an 8-device mesh (subprocess)."""
from _multidev import run_with_devices

CODE = r"""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core.lisa import rbm, compression as C

# Auto axes: the oracles below index the sharded results eagerly, which
# Explicit-axis meshes (make_mesh's default) refuse
mesh = jax.make_mesh((8,), ("x",), axis_types=(jax.sharding.AxisType.Auto,))
x = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)

def smap(f, in_specs=P("x"), out_specs=P("x")):
    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs))

# point-to-point, both directions incl. wraparound
for src, dst in [(2, 6), (6, 2), (0, 7), (7, 1)]:
    cp = smap(lambda s, src=src, dst=dst: rbm.lisa_copy(s, src, dst, "x"))(x)
    assert (cp == x.at[dst].set(x[src])).all(), (src, dst)

# 1-to-N multicast with intermediate latching
bc = smap(lambda s: rbm.lisa_broadcast(s, 3, "x", dsts=[0, 5, 7]))(x)
exp = x.at[0].set(x[3]).at[5].set(x[3]).at[7].set(x[3])
assert (bc == exp).all()
bca = smap(lambda s: rbm.lisa_broadcast(s, 3, "x"))(x)
assert (bca == jnp.broadcast_to(x[3], x.shape)).all()

# ring collectives vs dense oracles
ag = smap(lambda s: rbm.ring_allgather(s, "x"), out_specs=P("x", None))(x)
assert (ag.reshape(8, 8, 4)[0] == x).all()
ar = smap(lambda s: rbm.ring_allreduce(s, "x"))(x)
assert jnp.allclose(ar, jnp.broadcast_to(x.sum(0), (8, 4)))
rs_in = jax.random.normal(jax.random.key(1), (8, 8, 4))
rs = smap(lambda s: rbm.ring_reduce_scatter(s[0], "x")[None])(rs_in)
assert jnp.allclose(rs, rs_in.sum(0), atol=1e-5)

# overlapped allgather-matmul == dense matmul
w = jax.random.normal(jax.random.key(2), (8, 2, 3))
xx = jax.random.normal(jax.random.key(3), (8, 5, 16))
mm = jax.jit(jax.shard_map(
    lambda xs, ws: rbm.ring_allgather_matmul(xs[0], ws[0], "x")[None],
    mesh=mesh, in_specs=(P("x"), P("x")), out_specs=P("x")))(xx, w)
assert jnp.allclose(mm[0], xx[0] @ w.reshape(16, 3), atol=1e-4)

# int8 error-feedback allreduce ~= exact mean
gr = jax.random.normal(jax.random.key(4), (8, 100))
got = jax.jit(jax.shard_map(
    lambda gg: C.allreduce_mean_compressed(gg[0], jnp.zeros(100), "x")[0][None],
    mesh=mesh, in_specs=P("x"), out_specs=P("x")))(gr)
assert jnp.allclose(got[0], gr.mean(0), atol=2e-2)
print("RBM_OK")
"""


def test_rbm_primitives_8dev():
    out = run_with_devices(CODE, 8)
    assert "RBM_OK" in out
