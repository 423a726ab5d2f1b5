"""Pins the installed JAX's behaviour behind the names in `compat.py`.

Every distributed module routes through these three names, and the step
builders rest on one property of ``jax.shard_map`` that nothing else
states: grads of a REPLICATED param, taken inside a mapped body over
device-sharded data, come out as the global sum EXACTLY ONCE, with no
psum written by the caller (summing again would double-count; zero times
would train on 1/world of the gradient).
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from distributed_embeddings_tpu import compat
from distributed_embeddings_tpu.parallel import create_mesh

WORLD = 4


def test_names_are_the_installed_jax():
  assert compat.shard_map is jax.shard_map
  assert compat.axis_size is jax.lax.axis_size
  assert compat.enable_x64 is jax.enable_x64
  assert not hasattr(compat, "psum_replicated_grads")
  assert not hasattr(compat, "SHARD_MAP_PSUMS_REPLICATED_GRADS")


def test_shard_map_maps_body_over_mesh():
  mesh = create_mesh(WORLD)
  x = jnp.arange(2 * WORLD, dtype=jnp.float32).reshape(WORLD, 2)
  f = compat.shard_map(lambda xl: xl * 2.0, mesh=mesh,
                       in_specs=(P("mp", None),), out_specs=P("mp", None))
  np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(x) * 2.0)


def test_enable_x64_context_roundtrip():
  import warnings
  with warnings.catch_warnings():
    # outside the context an explicit int64 request truncates (and warns)
    warnings.simplefilter("ignore", UserWarning)
    assert jnp.asarray(1, jnp.int64).dtype == jnp.int32  # x64 off (default)
    with compat.enable_x64():
      assert jnp.asarray(1, jnp.int64).dtype == jnp.int64
      assert jnp.asarray(1.0, jnp.float64).dtype == jnp.float64
    assert jnp.asarray(1, jnp.int64).dtype == jnp.int32  # restored


def test_axis_size_is_static_inside_shard_map():
  mesh = create_mesh(WORLD)
  seen = []

  def body(xl):
    world = compat.axis_size("mp")
    seen.append(world)
    return xl + jnp.float32(world)

  f = jax.jit(compat.shard_map(body, mesh=mesh, in_specs=(P("mp"),),
                               out_specs=P("mp")))
  out = np.asarray(f(jnp.zeros(WORLD, jnp.float32)))
  np.testing.assert_array_equal(out, np.full(WORLD, WORLD, np.float32))
  # a Python int at trace time — usable as a shape/scale constant, and no
  # collective is left in the program to compute it
  assert seen and all(type(w) is int and w == WORLD for w in seen)
  assert "psum" not in str(jax.make_jaxpr(f)(jnp.zeros(WORLD, jnp.float32)))


def test_replicated_param_grads_summed_exactly_once():
  """The hybrid-backward convention `training.py` is built on: the
  replicated param's grad equals the sum of every device's local grad —
  not 1x the local grad and not world x the global sum — with no
  explicit psum in the body."""
  mesh = create_mesh(WORLD)
  x = jnp.arange(1.0, WORLD + 1.0)          # one element per device
  p0 = jnp.asarray(2.0)

  def local_step(p, xl):
    loss, g = jax.value_and_grad(lambda q: jnp.sum(q * xl))(p)
    return g, jax.lax.psum(loss, "mp")

  f = jax.jit(compat.shard_map(
      local_step, mesh=mesh, in_specs=(P(), P("mp")),
      out_specs=(P(), P())))
  g, loss = f(p0, x)
  assert float(g) == float(np.sum(np.asarray(x)))          # 10.0
  assert float(loss) == float(p0) * float(np.sum(np.asarray(x)))


def test_replicated_param_grads_tree():
  """Holds leaf-wise over grad pytrees (the step builders differentiate
  the whole dense-param tree at once)."""
  mesh = create_mesh(WORLD)
  x = jnp.ones(WORLD)

  def body(tree, xl):
    def loss(t):
      return jnp.sum(t["a"] * xl) + jnp.sum(t["b"] * xl) * 2.0
    return jax.grad(loss)(tree)

  f = jax.jit(compat.shard_map(
      body, mesh=mesh, in_specs=({"a": P(), "b": P()}, P("mp")),
      out_specs={"a": P(), "b": P()}))
  g = f({"a": jnp.zeros(()), "b": jnp.zeros(())}, x)
  assert float(g["a"]) == WORLD
  assert float(g["b"]) == 2.0 * WORLD
