"""The expert layer's token-major combine (`ops/pallas_moe_combine.py`), in
Pallas's interpreter on the CPU, against the scatter-add it stands for.

`combine(rows, pos, scale)[t] = sum over j with pos[t, j] < R of scale[t, j]
* rows[pos[t, j]]` is `zeros.at[tok].add(sorted_scale * rows)` read from the
token's side, `pos` the inverse of the sorted order. Held here: the values
over shapes, blocks and depths, with a token all of whose positions lie past
`R`, one with all below it, `n_live` 0 and `R`, and zeros in the scale; the
`custom_vjp` pair of `layers/moe.py` (`_dispatch`, whose backward is the
kernel, and `_combine`, whose forward is) against JAX's own transpose of the
gather and the scatter-add, for `h`, the rows and `p`; the whole share with
the kernel in the interpreter against the share without; what `fits`
refuses; what the kernel costs a process before its first step (the
conditionals in its body's jaxpr, how often a step's trace runs its body's
Python, whose scope each call carries in the compiled step: PR 44); and the
kernel compiled for a described v5e at the two shapes the four MoE cells run. What the interpreter cannot show (Mosaic's refusal of a
one-row slice of an `(8, 128)`-tiled array, which is why the kernel reads
`rows` tile by tile) the last test does; times only the chip gives
(`tools/bench_moe_combine.py`)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_embeddings_tpu.layers import moe, remat
from distributed_embeddings_tpu.ops import pallas_moe_combine as pmc


def _stream(rng, tokens, top_k, rows):
  """A sorted stream's bookkeeping: (order ``[T * k]`` a permutation, pos
  ``[T, k]`` its inverse, tok ``[rows]`` the token of each row of the head)."""
  n = tokens * top_k
  order = rng.permutation(n).astype(np.int32)
  pos = np.empty(n, np.int32)
  pos[order] = np.arange(n, dtype=np.int32)
  return order, pos.reshape(tokens, top_k), order[:rows] // top_k


def _oracle(rows, pos, scale, tokens):
  """The scatter-add: every row of the head into its token, weighted by the
  scale of its assignment."""
  n_rows = rows.shape[0]
  flat, s = pos.reshape(-1), scale.reshape(-1)
  below = flat < n_rows
  tok = (np.arange(flat.size) // pos.shape[1])[below]
  return jnp.zeros((tokens, rows.shape[1]), jnp.float32).at[tok].add(
      jnp.asarray(s[below])[:, None] * jnp.asarray(rows)[flat[below]])


# (T, k, R, d, block, depth): one block and several, a ring as deep as the
# block has groups, T no multiple of the block or of 8, R no multiple of 8,
# R larger than T * k / 2 and as large as T * k
SHAPES = [
    (64, 4, 128, 256, None, None),
    (64, 4, 96, 256, 32, 2),
    (40, 8, 100, 128, 16, 2),
    (128, 2, 256, 128, 64, 4),
    (24, 8, 64, 384, 8, 2),
    (20, 3, 60, 128, None, None),
    (256, 8, 1024, 128, 64, 8),
]


@pytest.mark.parametrize("tokens,top_k,n_rows,d,block,depth", SHAPES)
def test_the_kernel_is_the_scatter_add(tokens, top_k, n_rows, d, block,
                                       depth):
  rng = np.random.default_rng(tokens + n_rows)
  _, pos, _ = _stream(rng, tokens, top_k, n_rows)
  rows = rng.standard_normal((n_rows, d)).astype(np.float32)
  scale = rng.standard_normal((tokens, top_k)).astype(np.float32)
  scale[rng.random((tokens, top_k)) < 0.2] = 0.0
  got = pmc.combine(jnp.asarray(rows), jnp.asarray(pos), jnp.asarray(scale),
                    block=block, depth=depth, interpret=True)
  want = _oracle(rows, pos, scale, tokens)
  np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)


@pytest.mark.parametrize("top_k", [4, 8, 3])
@pytest.mark.parametrize("case", ["all_past", "all_below", "none_live",
                                  "all_live", "zero_scale"])
def test_the_edges_of_the_head(case, top_k):
  """A token all of whose positions lie past the head adds nothing and reads
  nothing (its slots hold an earlier group's rows, NaN here); a token with
  every position below it sums ``k`` rows; ``n_live`` 0 and ``R`` as the
  layer hands them over: a scale of zero on every dead position. At the
  cells' two ``top_k`` and at one that is no power of two (a slot and a token
  are a quotient and a remainder of the copies' loop index)."""
  tokens, n_rows, d = 32, 16 * top_k, 128
  rng = np.random.default_rng(7)
  rows = rng.standard_normal((n_rows, d)).astype(np.float32)
  # token t owns positions k t .. k t + k - 1: tokens 0..15 lie below the head
  pos = np.arange(tokens * top_k, dtype=np.int32).reshape(tokens, top_k)
  scale = rng.standard_normal((tokens, top_k)).astype(np.float32)
  if case == "all_past":
    # only the last tokens lie below the head: every earlier group fetches
    # nothing and must write zeros whatever the ring holds
    pos = pos[::-1].copy()
  elif case == "none_live":
    scale = np.where(pos < 0, scale, 0.0).astype(np.float32)
  elif case == "all_live":
    scale = np.where(pos < n_rows, scale, 0.0).astype(np.float32)
  elif case == "zero_scale":
    scale[:, 1] = 0.0
    rows[pos[:16, 1]] = np.nan      # a row scaled by zero is selected away
  got = np.asarray(pmc.combine(jnp.asarray(rows), jnp.asarray(pos),
                               jnp.asarray(scale), block=8, depth=2,
                               interpret=True))
  clean = np.nan_to_num(rows)
  want = np.asarray(_oracle(clean, pos, scale, tokens))
  np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
  past = (pos >= n_rows).all(axis=1)
  assert past.sum() == 16 and not got[past].any()
  if case == "none_live":
    assert not got.any()


@pytest.mark.parametrize("n_live", [0, 37, 96, 200])
def test_the_pair_has_the_gradients_of_the_gather_and_the_scatter_add(n_live):
  """`moe._dispatch` and `moe._combine` with the kernel in the interpreter
  against the same two lines in XLA under JAX's own transpose: the
  cotangents of ``h``, of the experts' rows and of ``p``. ``n_live`` 0, in
  the head, the head's size, and past it (the tail's rows are no part of the
  head)."""
  tokens, top_k, n_rows, d = 48, 4, 96, 128
  rng = np.random.default_rng(n_live)
  order, pos, tok = _stream(rng, tokens, top_k, n_rows)
  f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
  h, w, c = f32(tokens, d), f32(d, d), f32(tokens, d)
  top_p = jnp.abs(f32(tokens, top_k))
  tok, pos, live_n = jnp.asarray(tok), jnp.asarray(pos), jnp.int32(n_live)
  order = jnp.asarray(order)

  def sorted_p(top_p):
    return jnp.take(top_p.reshape(-1), order)[:n_rows]

  def plain(h, w, top_p):
    live = jnp.arange(n_rows) < live_n
    x = jnp.where(live[:, None], jnp.take(h, tok, axis=0), 0)
    y = jnp.tanh(x @ w)
    y = jnp.where(live[:, None], y * sorted_p(top_p)[:, None], 0)
    return jnp.sum(jnp.zeros_like(h).at[tok].add(y) * c)

  def paired(h, w, top_p):
    y = jnp.tanh(moe._dispatch(True, h, tok, pos, live_n) @ w)
    out = moe._combine(True, y, sorted_p(top_p), tok, pos,
                       jax.lax.stop_gradient(top_p), live_n)
    return jnp.sum(out * c)

  want = jax.value_and_grad(plain, argnums=(0, 1, 2))(h, w, top_p)
  got = jax.value_and_grad(paired, argnums=(0, 1, 2))(h, w, top_p)
  np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
  for a, b in zip(got[1], want[1]):
    np.testing.assert_allclose(a, b, rtol=0,
                               atol=2e-5 * (1 + float(jnp.max(jnp.abs(b)))))


@pytest.mark.parametrize("router,held", [
    (moe.Router(), (4, 4)), (moe.Router("sigmoid", True, 2.5), (0, 8))])
def test_the_share_with_the_kernel_is_the_share_without(monkeypatch, router,
                                                        held):
  """`moe_share` under `remat.checkpoint_layer`, values and the five
  gradients, with `combine_kernel` answering the interpreter; and what the
  backward's jaxpr holds: one kernel call forward, one backward, none in the
  rebuilt forward, the twelve grouped matmuls as before."""
  tokens, d, f, experts, top_k = 64, 128, 64, 16, 4
  share = moe.MoEShare(experts, top_k, held, router)
  rng = np.random.default_rng(3)
  f32 = lambda *s, sc=1.0: jnp.asarray(rng.standard_normal(s) * sc,
                                       jnp.float32)
  args = (f32(tokens, d), f32(d, experts, sc=0.3), f32(held[1], d, f, sc=0.1),
          f32(held[1], d, f, sc=0.1), f32(held[1], f, d, sc=0.1))
  c = f32(tokens, d)

  def grad():
    # a function of its own a path: a trace is cached by the function traced
    def loss(*a):
      out, counters = remat.checkpoint_layer(
          lambda *a: moe.moe_share(*a, share))(*a)
      return jnp.sum(out * c), counters
    return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)

  (want, counters), want_g = jax.jit(grad())(*args)
  assert "pallas_call" not in str(jax.make_jaxpr(grad())(*args))
  monkeypatch.setattr(moe, "combine_kernel", lambda *_: True)
  (got, counters_k), got_g = jax.jit(grad())(*args)
  np.testing.assert_allclose(got, want, rtol=1e-5)
  assert int(counters_k["computed"]) == int(counters["computed"]) > 0
  for a, b in zip(got_g, want_g):
    np.testing.assert_allclose(a, b, rtol=0,
                               atol=1e-5 * (1 + float(jnp.max(jnp.abs(b)))))
  text = str(jax.make_jaxpr(grad())(*args))
  assert text.count("pallas_call[") == 2
  assert text.count("ragged_dot_general") == 12


def _stack(layers, tokens=64, d=128, f=64, experts=16, top_k=4):
  """(loss of ``layers`` expert layers one after another, each under
  `remat.checkpoint_layer` as a model runs it; its arguments' shapes)."""
  share = moe.MoEShare(experts, top_k, (4, 4))
  f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
  args = (f32(tokens, d), [(f32(d, experts), f32(4, d, f), f32(4, d, f),
                            f32(4, f, d))] * layers)

  def loss(h, weights):
    for w in weights:
      out, _ = remat.checkpoint_layer(
          lambda *a: moe.moe_share(*a, share))(h, *w)
      h = h + out
    return jnp.sum(h * h)

  return loss, args


def test_both_kernel_calls_lie_under_their_part_of_the_route(monkeypatch):
  """Lowered for the TPU (no chip): each of the two jitted entry points is a
  function of the module with its part's scopes INSIDE it (``de_moe_route /
  de_moe_return`` the forward's, ``de_moe_route / de_moe_dispatch`` the
  backward's), so whichever call the function is lowered for first, the
  kernel's time lies under the right part."""
  monkeypatch.setattr(moe, "combine_kernel", lambda *_: False)
  loss, args = _stack(1)
  text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).trace(
      *args).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
  stacks = sorted(m for m in re.findall(r'loc\("([^"]+)"', text)
                  if pmc.KERNEL_NAME in m)
  assert len(stacks) == 2, stacks
  assert re.search(r"de_moe_route/de_moe_dispatch/de_moe_combine", stacks[0])
  assert re.search(r"de_moe_route/de_moe_return/de_moe_combine", stacks[1])
  assert "de_moe_return" not in stacks[0] and "de_moe_dispatch" not in stacks[1]
  assert "rematted_computation" not in "".join(stacks)
  assert len(re.findall(r"call @_return_sum", text)) == 1
  assert len(re.findall(r"call @_dispatch_sum", text)) == 1


def _conds(jaxpr) -> int:
  """``cond`` equations of a jaxpr, those of every jaxpr inside it too."""
  n = 0
  for eqn in jaxpr.eqns:
    n += eqn.primitive.name == "cond"
    for sub in jax.core.jaxprs_in_params(eqn.params):
      n += _conds(sub)
  return n


# (tokens, top_k) of sdar / laguna / keye, and of lfm2
@pytest.mark.parametrize("tokens,top_k", [(8192, 8), (16384, 4)])
def test_the_kernels_body_holds_few_conditionals(tokens, top_k):
  """What a trace of the body costs follows the conditionals written out in
  it (each a closure traced to a jaxpr of its own): one for the copies a
  group starts (a loop, unrolled where it is lowered and not in Python), two
  for the first and the last turns, one a binary digit of a group's count of
  copies. With a conditional a position the body held over 130 at ``top_k``
  8 and was 90% of a step's trace (PR 43)."""
  shape = jax.ShapeDtypeStruct
  jaxpr = jax.make_jaxpr(pmc.combine)(
      shape((32768, 2048), jnp.float32), shape((tokens, top_k), jnp.int32),
      shape((tokens, top_k), jnp.float32))
  calls = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
  assert len(calls) == 1
  conds = _conds(calls[0].params["jaxpr"])
  assert 3 <= conds <= 24, conds


def test_a_steps_trace_runs_the_kernels_body_once_a_part(monkeypatch):
  """`jax.grad` of four expert layers under `checkpoint_layer`, the backend
  answered "tpu": the forward, its rebuilt copy and the backward rule call
  the two jitted entry points twelve times, and the body's Python runs once
  or twice an entry point (PR 43: once a call; sixteen times in a cell's
  step)."""
  traced = []
  body = pmc._combine_kernel

  def counted(*a, **kw):
    traced.append(1)
    return body(*a, **kw)

  monkeypatch.setattr(pmc, "_combine_kernel", counted)
  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
  # a width no other test of this file traces: the entry points' traces are
  # kept for the process
  loss, args = _stack(4, tokens=72, d=256)
  text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(*args))
  assert text.count("name=_return_sum") == 4
  assert text.count("name=_dispatch_sum") == 4
  assert 1 <= len(traced) <= 4, len(traced)


def test_every_compiled_call_carries_its_own_parts_scope(one_chip,
                                                         monkeypatch):
  """Four layers compiled for the described chip (a second; nothing runs):
  the eight kernel calls of the step, each by the ``op_name`` the profiler
  will read: the forward's four under ``de_moe_return`` and outside any
  ``transpose(``, the backward's four under ``de_moe_dispatch`` inside one,
  none under the other part's scope or in the rebuilt forward. (JAX lowers
  a jitted function once a module; the scopes inside it are the function's
  own, the ones outside come from each call.)"""
  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
  loss, args = _stack(4)
  on_chip = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip)
  text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
      *jax.tree_util.tree_map(on_chip, args)).compile().as_text()
  names = [re.search(r'op_name="([^"]*)"', line).group(1)
           for line in text.splitlines()
           if "custom-call(" in line and re.search(r"%?de_moe_combine\S* =",
                                                   line)]
  assert len(names) == 8, names
  forward = [n for n in names if "transpose(" not in n]
  backward = [n for n in names if "transpose(" in n]
  assert len(forward) == len(backward) == 4
  for n in forward:
    assert "/de_moe_route/de_moe_return/" in n and "de_moe_dispatch" not in n
  for n in backward:
    assert "/de_moe_route/de_moe_dispatch/" in n and "de_moe_return" not in n
  assert "rematted_computation" not in "".join(names)


def test_sorted_positions_is_the_inverse_of_the_stable_argsort():
  rng = np.random.default_rng(0)
  for classes, n in ((17, 4096), (3, 64), (1, 8), (33, 1000)):
    key = jnp.asarray(rng.integers(0, classes, n), jnp.int32)
    order = np.asarray(jnp.argsort(key, stable=True))
    pos = np.asarray(moe.sorted_positions(key, classes))
    assert pos.dtype == np.int32
    np.testing.assert_array_equal(pos[order], np.arange(n))


def test_fits_refuses_what_the_kernel_cannot_take():
  assert pmc.fits(32768, 8192, 8, 2048) and pmc.fits(32768, 16384, 4, 2048)
  assert pmc.fits(8, 1, 1, 128)
  assert not pmc.fits(32768, 8192, 8, 2000)        # no whole lane tile
  assert not pmc.fits(32768, 8192, 8, 64)
  assert not pmc.fits(32768, 8192, 32, 2048)       # more slots than the ring
  assert not pmc.fits(32768, 8192, 8, 1 << 16)     # two groups pass its bytes
  assert not pmc.fits(0, 8192, 8, 2048)
  # a block of 128 tokens (what 4 MiB hold at d = 8192) is 512 positions at
  # top 4: no whole SMEM tile of a long `pos`
  assert pmc.fits(32768, 16384, 8, 8192)
  assert not pmc.fits(32768, 16384, 4, 8192)
  with pytest.raises(ValueError):
    pmc.combine(jnp.zeros((8, 100)), jnp.zeros((8, 2), jnp.int32),
                jnp.zeros((8, 2)), interpret=True)
  with pytest.raises(ValueError):                  # bfloat16 rows: not this sum
    pmc.combine(jnp.zeros((8, 128), jnp.bfloat16), jnp.zeros((8, 2), jnp.int32),
                jnp.zeros((8, 2)), interpret=True)


@pytest.fixture(scope="module")
def one_chip():
  """A described, not attached, v5e chip to compile for; the persistent
  compile cache is off meanwhile (an entry written for a described chip
  cannot be read back and warns)."""
  from jax.experimental import topologies
  from jax.experimental.compilation_cache import compilation_cache
  from jax.sharding import SingleDeviceSharding
  try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
  except Exception as e:  # no TPU compiler in this installation
    pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
  was = jax.config.jax_enable_compilation_cache
  jax.config.update("jax_enable_compilation_cache", False)
  compilation_cache.reset_cache()
  yield SingleDeviceSharding(topo.devices[0])
  jax.config.update("jax_enable_compilation_cache", was)
  compilation_cache.reset_cache()


# (tokens, top_k) of sdar / laguna / keye, and of lfm2; a small stream that is
# one SMEM block (chip_smoke's first shape)
@pytest.mark.parametrize("tokens,top_k,n_rows", [
    (8192, 8, 32768), (16384, 4, 32768), (256, 8, 1024)])
def test_the_chips_compiler_takes_the_kernel_at_the_cells_shapes(
    one_chip, tokens, top_k, n_rows):
  """Compiled for the TPU (nothing runs), between two XLA ops: the kernel is
  there, and the two views of ``rows`` and of the output tile by tile are
  bitcasts, no copy of 268 MB."""
  shape = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

  def around(rows, pos, scale, add):
    return pmc.combine(rows * 2.0, pos, scale) + add

  text = jax.jit(around).lower(
      shape((n_rows, 2048), jnp.float32), shape((tokens, top_k), jnp.int32),
      shape((tokens, top_k), jnp.float32),
      shape((tokens, 2048), jnp.float32)).compile().as_text()
  assert pmc.KERNEL_NAME in text
  big = f"f32[{n_rows // 8},16,8,128]"
  assert big in text
  for line in text.splitlines():
    assert not (" copy(" in line and ("2048]" in line or ",16,8,128]" in line)
                ), line
