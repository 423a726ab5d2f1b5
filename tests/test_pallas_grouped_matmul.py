"""The expert layer's grouped products in the kernels of
`ops/pallas_grouped_matmul.py`, against `lax.ragged_dot`.

On the CPU, in Pallas's interpreter. The kernels are forced as
`tests/test_moe.py` forces the combine kernel (`dense.grouped_kernel`
replaced); the other side of every comparison is the same function on this
backend's own path, the three `lax.ragged_dot` calls.

Written before the kernels, from the refusals the ledger holds: PR 49 was
`outputs_incorrect` because megablox's `gmm` leaves a row that no group owns
unwritten (a NaN there times a dead row's zero cotangent is a NaN in the
router's gradient), so here such rows are ZEROS in `y` and `dx` and enter no
`dw`; a group of no rows gets a zero `dw`; a group boundary inside a row tile,
inside a cut piece, and exactly on a tile's edge; a row count no tile divides
(Solar's 6,560 = 2^5 * 5 * 41). The last tests compile the kernels under the
rule's tiles for a described v5e at every benchmark cell's expert shape (no
chip), and hold what a step pays before its first run.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import SingleDeviceSharding

from distributed_embeddings_tpu.layers import dense
from distributed_embeddings_tpu.ops import (
    pallas_grouped_matmul as grouped_matmul,
)
from distributed_embeddings_tpu.ops.pallas_grouped_matmul import Tiles
from distributed_embeddings_tpu.telemetry import registry

BF16, F32 = jnp.bfloat16, jnp.float32
K, N = 256, 128
# tiles of 64 rows, cut pieces of 16: every case below has boundaries inside
# a tile, inside a piece and on an edge
TOY = Tiles(64, 16, 128, 128)

# name -> (rows, group sizes)
CASES = {
    # every row in a group, the last one padded as `whole` pads the head
    "every_row_owned": (256, (70, 50, 30, 106)),
    # the tail's piece: rows past the groups belong to none
    "rows_past_the_groups": (256, (50, 27, 30, 20)),
    # no group reaches the second tile: whole tiles of rows nobody owns
    "tiles_nobody_owns": (256, (20, 10, 5, 8)),
    "empty_first": (256, (0, 100, 106, 50)),
    "empty_middle": (256, (64, 0, 150, 42)),
    "empty_last": (256, (100, 106, 50, 0)),
    "two_empty_at_a_tile_edge": (256, (128, 0, 0, 100)),
    "a_boundary_on_every_edge": (256, (64, 64, 64, 64)),
    "one_row_groups": (256, (1, 1, 253, 1)),
    # rows no tile divides
    "rows_no_tile_divides": (164, (50, 0, 28, 86)),
    "rows_no_tile_divides_and_some_unowned": (164, (50, 0, 28, 30)),
    # a piece of the tail wholly past the live rows
    "no_group_has_a_row": (192, (0, 0, 0, 0)),
}
PRODUCTS = ("y", "dx", "dw")


def _operands(rows, groups, k=K, n=N, seed=0):
  rng = np.random.default_rng(seed)
  draw = lambda *s: jnp.asarray(rng.normal(size=s), F32).astype(BF16)
  return draw(rows, k), draw(groups, k, n), draw(rows, n)


def _xla(x, w, dy, sizes):
  return {
      "y": lax.ragged_dot(x, w, sizes, preferred_element_type=F32),
      "dx": lax.ragged_dot(dy, jnp.swapaxes(w, 1, 2), sizes,
                           preferred_element_type=F32),
      "dw": lax.ragged_dot_general(x, dy, sizes, dense._GROUPED_DW,
                                   preferred_element_type=F32)}


def _kernels(x, w, dy, sizes, t=TOY):
  return {
      "y": grouped_matmul.grouped_dot(x, w, sizes, interpret=True, t=t),
      "dx": grouped_matmul.grouped_dot(dy, w, sizes, transposed=True,
                                       interpret=True, t=t),
      "dw": grouped_matmul.grouped_dw(x, dy, sizes, interpret=True, t=t)}


@functools.lru_cache(maxsize=None)
def _both(case):
  rows, sizes = CASES[case]
  x, w, dy = _operands(rows, len(sizes))
  sizes = jnp.asarray(sizes, jnp.int32)
  return _kernels(x, w, dy, sizes), _xla(x, w, dy, sizes), int(sizes.sum())


@pytest.mark.parametrize("product", PRODUCTS)
@pytest.mark.parametrize("case", CASES)
def test_a_product_is_ragged_dots(case, product):
  """`y`, `dx` and `dw` on bfloat16 operands: the same float32 sums in
  another order, on EVERY row: a row no group owns is zeros here as it is
  there, and adds nothing to a `dw`."""
  got, want, _ = _both(case)
  assert got[product].shape == want[product].shape
  assert got[product].dtype == want[product].dtype == F32
  assert np.all(np.isfinite(got[product]))
  np.testing.assert_allclose(
      got[product], want[product], rtol=1e-5,
      atol=1e-5 * float(jnp.max(jnp.abs(want[product])) + 1))


@pytest.mark.parametrize("case", [c for c, (rows, sizes) in CASES.items()
                                  if sum(sizes) < rows])
def test_rows_no_group_owns_are_exactly_zero(case):
  """PR 49's fault, held: `y` and `dx` at or past `sum(sizes)` are written,
  and are zeros to the bit (NaN != 0)."""
  got, _, live = _both(case)
  for product in ("y", "dx"):
    past = np.asarray(got[product][live:])
    assert past.size and np.array_equal(past, np.zeros_like(past))


@pytest.mark.parametrize("case", [c for c, (_, sizes) in CASES.items()
                                  if 0 in sizes])
def test_a_group_of_no_rows_gets_a_zero_dw(case):
  got, _, _ = _both(case)
  empty = np.asarray(CASES[case][1]) == 0
  held = np.asarray(got["dw"])
  assert np.array_equal(held[empty], np.zeros_like(held[empty]))
  assert np.all(np.abs(held[~empty]).max(axis=(1, 2), initial=1) > 0)


def test_what_lies_past_the_rows_is_never_multiplied():
  """164 rows in tiles of 64: the last tile's rows past `m` hold whatever the
  buffer does. With NaN in the operands' rows that no group owns, up to the
  last row, every product of a group is what it was."""
  rows, sizes = CASES["rows_no_tile_divides_and_some_unowned"]
  x, w, dy = _operands(rows, len(sizes))
  sizes = jnp.asarray(sizes, jnp.int32)
  live = int(sizes.sum())
  want = _kernels(x, w, dy, sizes)
  got = _kernels(x.at[live:].set(jnp.nan), w, dy.at[live:].set(jnp.nan),
                 sizes)
  np.testing.assert_array_equal(got["dw"], want["dw"])
  for product in ("y", "dx"):
    np.testing.assert_array_equal(got[product][:live], want[product][:live])


@pytest.mark.parametrize("t", [Tiles(64, 64, 128, 128),
                               Tiles(128, 32, 128, 256),
                               Tiles(256, 128, 128, 128),
                               Tiles(32, 16, 128, 256)])
def test_the_products_under_other_tiles(t):
  """The column blocks cut (`y`: 2 blocks of `dx`'s 256 columns; `dw`: `x`'s
  columns too), one tile for all the rows, pieces as long as a tile."""
  rows, sizes = CASES["rows_past_the_groups"]
  x, w, dy = _operands(rows, len(sizes), seed=1)
  sizes = jnp.asarray(sizes, jnp.int32)
  got, want = _kernels(x, w, dy, sizes, t), _xla(x, w, dy, sizes)
  for product in PRODUCTS:
    np.testing.assert_allclose(
        got[product], want[product], rtol=1e-5,
        atol=1e-5 * float(jnp.max(jnp.abs(want[product])) + 1))


@pytest.mark.parametrize("case", ["every_row_owned", "rows_past_the_groups",
                                  "one_row_groups", "no_group_has_a_row",
                                  "rows_no_tile_divides"])
def test_the_walk_visits_every_row_of_a_tile_once(case):
  """`visits`: tiles and groups never decrease, every (group, tile) pair
  that shares rows is there once, a group of no rows once, and what is left
  over names the empty range."""
  rows, sizes = CASES[case]
  tm, groups = 64, len(sizes)
  starts, ends, group, tile = map(np.asarray, grouped_matmul.visits(
      jnp.asarray(sizes, jnp.int32), rows, tm))
  n_tiles = -(-rows // tm)
  assert group.shape == tile.shape == (n_tiles + groups,)
  assert np.all(np.diff(group) >= 0) and np.all(np.diff(tile) >= 0)
  assert tile.max() == n_tiles - 1 and group.max() <= groups + 1
  owner = np.full(n_tiles * tm, -1)
  for g, t in zip(group, tile):
    lo, hi = max(starts[g], t * tm), min(ends[g], (t + 1) * tm)
    assert np.all(owner[lo:hi] == -1)
    owner[lo:max(hi, lo)] = g
  want = np.repeat(np.arange(groups + 1),
                   list(sizes) + [n_tiles * tm - sum(sizes)])
  np.testing.assert_array_equal(owner, want)
  for g in range(groups):
    assert np.sum(group == g) >= 1
  assert ends[groups + 1] == starts[groups + 1]


# --- through `grouped_mxu_dots`, against the XLA path ----------------------

def _forced(monkeypatch, interpret=True):
  monkeypatch.setattr(dense, "grouped_kernel", lambda *_: interpret)
  monkeypatch.setattr(dense, "mxu_operand_dtype",
                      lambda dt: BF16 if dt == F32 else dt)


def _layer_grads(rows, sizes, seed=0, d=K, f=N):
  """`value_and_grad` of one checkpointed expert layer whose loss selects the
  rows past the groups away, as `layers/moe.py` does."""
  rng = np.random.default_rng(seed)
  draw = lambda *s: jnp.asarray(rng.normal(size=s), F32)
  groups = len(sizes)
  args = (draw(rows, d), draw(groups, d, f) * 0.1, draw(groups, d, f) * 0.1,
          draw(groups, f, d) * 0.1, jnp.linspace(0.5, 1.5, rows))
  sizes = jnp.asarray(sizes, jnp.int32)
  live = (jnp.arange(rows) < sizes.sum())[:, None]

  # a new function a call: `jax.jit` caches a trace by the function traced
  @jax.checkpoint
  def loss(x, w_gate, w_up, w_down, p):
    gate, up = dense.grouped_mxu_dots(x, (w_gate, w_up), sizes)
    y, = dense.grouped_mxu_dots(jax.nn.silu(gate) * up, (w_down,), sizes)
    return jnp.sum(jnp.where(live, y * p[:, None], 0) ** 2)
  return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(*args)


@pytest.mark.parametrize("case", ["every_row_owned", "rows_past_the_groups",
                                  "tiles_nobody_owns", "empty_middle",
                                  "rows_no_tile_divides_and_some_unowned"])
def test_grad_through_the_kernels_is_the_xla_paths(monkeypatch, case):
  """`jax.grad` through `grouped_mxu_dots` with the kernels forced against
  the same function on XLA's kernels, both on bfloat16 operands: the loss,
  `dx`, the three `dw` and the row weights' gradient (PR 49's NaN was there),
  to what the rounding of `silu(gate) * up` lets two orders of summation
  differ by."""
  rows, sizes = CASES[case]
  monkeypatch.setattr(dense, "mxu_operand_dtype",
                      lambda dt: BF16 if dt == F32 else dt)
  want = _layer_grads(rows, sizes)
  _forced(monkeypatch)
  got = _layer_grads(rows, sizes)
  for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
    assert g.dtype == w.dtype == F32 and np.all(np.isfinite(g))
    np.testing.assert_allclose(g, w, rtol=2e-2,
                               atol=1e-2 * float(jnp.max(jnp.abs(w))) + 1e-6)


def test_the_products_carry_no_lower_precision(monkeypatch):
  """bfloat16 into every kernel, float32 out of both passes."""
  _forced(monkeypatch)
  import test_mxu_dot
  jaxpr = jax.make_jaxpr(lambda: _layer_grads(256, (70, 50, 30, 106)))().jaxpr
  calls = test_mxu_dot._eqns(jaxpr, "pallas_call")
  assert len(calls) == 12      # 3 forward, 3 rebuilt, 6 backward
  for eqn in calls:
    *walk, a, b = eqn.invars
    assert all(v.aval.dtype == jnp.int32 for v in walk) and len(walk) == 4
    assert a.aval.dtype == b.aval.dtype == BF16
    assert [v.aval.dtype for v in eqn.outvars] == [F32]
  assert not test_mxu_dot._eqns(jaxpr, "ragged_dot_general")


def _share_case(skewed, seed=2, t=64, d=128, f=128, experts=32):
  """A layer's input and weights; `skewed`: a router that gives expert 3
  every token with a positive first feature, past the head of its share."""
  rng = np.random.default_rng(seed)
  draw = lambda *shape, s=1.0: rng.normal(size=shape) * s
  h, wr = draw(t, d), draw(d, experts)
  if skewed:
    wr *= 0.1
    wr[:, 3] = 0.0
    wr[0, 3] = 40.0
  return tuple(jnp.asarray(a, F32) for a in (
      h, wr, draw(experts, d, f, s=0.1), draw(experts, d, f, s=0.1),
      draw(experts, f, d, s=0.1)))


@pytest.mark.parametrize("skewed,held", [(False, (2, 4)), (True, (3, 1))],
                         ids=["head_only", "tail_walked"])
def test_a_share_through_the_kernels_head_and_tail(monkeypatch, skewed, held):
  """`layers/moe.py::moe_share` whole, its head's products and its tail's
  pieces' (rows no group owns in every piece) through the kernels, against
  the same share on XLA's kernels: the output, the counters and every
  gradient, the ROUTER's among them (PR 49: a NaN left in an unowned row
  times a dead row's zero cotangent was a NaN there)."""
  from distributed_embeddings_tpu.layers import moe
  h, wr, wg, wu, wd = _share_case(skewed)
  sl = slice(held[0], held[0] + held[1])
  args = (h, wr, wg[sl], wu[sl], wd[sl])
  share = moe.MoEShare(32, 2, held)
  monkeypatch.setattr(dense, "mxu_operand_dtype",
                      lambda dt: BF16 if dt == F32 else dt)

  def both():
    # new functions a call: `jax.jit` caches a trace by the function traced
    out, counters = jax.jit(lambda a: moe.moe_share(*a, share))(args)
    grads = jax.jit(jax.grad(
        lambda a: jnp.sum(jnp.sin(moe.moe_share(*a, share)[0]))))(args)
    return out, counters, grads

  want_out, want_counters, want = both()
  _forced(monkeypatch)
  before = _counted()
  out, counters, got = both()
  assert _counted()[0] > before[0] and _counted()[1] == before[1]
  walked = int(counters["loads"].sum()) > share.head_rows(64 * 2)
  assert walked == skewed
  for name in ("assignments", "computed", "loads"):
    np.testing.assert_array_equal(counters[name], want_counters[name])
  assert int(counters["computed"]) == int(counters["assignments"])
  np.testing.assert_allclose(out, want_out, rtol=2e-2, atol=2e-2 * float(
      jnp.max(jnp.abs(want_out))))
  for g, w in zip(got, want):
    assert np.all(np.isfinite(g))
    np.testing.assert_allclose(g, w, rtol=2e-2,
                               atol=2e-2 * float(jnp.max(jnp.abs(w))) + 1e-7)
  assert float(jnp.max(jnp.abs(got[1]))) > 0   # the router learns too


# --- where the kernels do not apply, `lax.ragged_dot` stands ---------------

@pytest.mark.parametrize("backend,k,n,cd,dtype,kernel", [
    ("tpu", 256, 128, BF16, F32, True),
    ("tpu", 4096, 1280, BF16, F32, True),
    ("cpu", 256, 128, BF16, F32, False),   # this backend, operands forced
    ("gpu", 256, 128, BF16, F32, False),
    ("tpu", 192, 128, BF16, F32, False),   # k in no whole lane tile
    ("tpu", 256, 24, BF16, F32, False),    # n in none
    ("tpu", 256, 128, F32, F32, False),    # a precision keeping float32
    ("tpu", 256, 128, BF16, BF16, False),  # bfloat16 rows: not rounded here
    ("tpu", 65536, 128, BF16, F32, False),  # no block of it fits VMEM
])
def test_the_kernels_are_chosen_from_what_the_code_observes(
    monkeypatch, backend, k, n, cd, dtype, kernel):
  monkeypatch.setattr(jax, "default_backend", lambda: backend)
  x = jax.ShapeDtypeStruct((512, k), dtype)
  w = jax.ShapeDtypeStruct((4, k, n), dtype)
  assert (dense.grouped_kernel(cd, x, (w, w)) is not None) == kernel
  if k > 1 << 14:
    return
  # through the public function, with the policy's answer forced: the jaxpr
  # has the kernels or it has the three `ragged_dot`s, never both
  monkeypatch.setattr(dense, "mxu_operand_dtype",
                      lambda dt: cd if dt == F32 else dt)
  text = str(jax.make_jaxpr(jax.grad(
      lambda x, w, s: jnp.sum(dense.grouped_mxu_dots(x, (w,), s)[0]),
      argnums=(0, 1)))(x, w, jax.ShapeDtypeStruct((4,), jnp.int32)))
  assert ("pallas_call" in text) == kernel
  assert ("ragged_dot" in text) != kernel


def test_on_this_backend_nothing_changed():
  """The CPU keeps the three `lax.ragged_dot` calls."""
  text = str(jax.make_jaxpr(lambda: _layer_grads(164, (50, 0, 28, 86)))())
  assert "pallas_call" not in text
  assert text.count("ragged_dot_general") == 12


# --- the counters -----------------------------------------------------------

def _counted():
  return (registry.counter(dense.KERNEL_PRODUCTS).value,
          registry.counter(dense.XLA_PRODUCTS).value)


def test_the_counters_say_what_formed_a_layers_products(monkeypatch):
  """The grouped products MET while a checkpointed expert layer's gradient
  is traced, all on one side: the primal's three, its forward rule's three
  (the checkpoint's forward and rebuilt forward are one trace) and the
  backward's six."""
  before = _counted()
  jax.make_jaxpr(lambda: _layer_grads(256, (70, 50, 30, 106)))()
  plain = _counted()
  # the policy keeps float32 here: the forward's three `lax.ragged_dot` are
  # met, their backward is JAX's own transpose
  assert plain[0] == before[0] and plain[1] - before[1] == 3
  monkeypatch.setattr(dense, "mxu_operand_dtype",
                      lambda dt: BF16 if dt == F32 else dt)
  jax.make_jaxpr(lambda: _layer_grads(256, (70, 50, 30, 106)))()
  rounded = _counted()
  assert rounded[0] == plain[0] and rounded[1] - plain[1] == 12
  _forced(monkeypatch)
  jax.make_jaxpr(lambda: _layer_grads(256, (70, 50, 30, 106)))()
  forced = _counted()
  assert forced[0] - rounded[0] == 12 and forced[1] == rounded[1]


def _olmo_step(monkeypatch):
  """The jaxpr of `value_and_grad` of Olmo's toy step."""
  import test_mxu_dot
  return str(test_mxu_dot._grad_jaxpr(monkeypatch, test_mxu_dot._olmo)[0])


def _dlrm_step(monkeypatch):
  """The DLRM toy's sparse training step, lowered."""
  import test_scopes
  from distributed_embeddings_tpu.models import bce_loss
  from distributed_embeddings_tpu.training import (
      init_sparse_state_direct,
      make_sparse_train_step,
  )
  model, plan, rule, opt, cats, acts, n_num = test_scopes._dlrm(1)
  numerical = jnp.zeros((test_scopes.BATCH, n_num), F32)
  params = model.init(jax.random.PRNGKey(0), numerical[:2],
                      [c[:2] for c in cats], emb_acts=acts)["params"]
  state = init_sparse_state_direct(plan, rule, params, opt,
                                   jax.random.PRNGKey(1), mesh=None)
  labels = jnp.zeros((test_scopes.BATCH,), F32)
  step = make_sparse_train_step(model, plan, bce_loss, opt, rule, None, state,
                                (numerical, cats, labels), donate=False)
  return step.lower(state, numerical, cats, labels).as_text()


@pytest.mark.parametrize("toy", [_olmo_step, _dlrm_step],
                         ids=["olmo_hybrid", "dlrm"])
def test_a_model_without_experts_is_not_touched(monkeypatch, toy):
  """Tracing Olmo's toy step and the DLRM toy step, with the kernels forced
  wherever a grouped product is met, leaves both counters where they were,
  and the program holds no call of the kernels and no `ragged_dot`."""
  _forced(monkeypatch)
  before = _counted()
  text = toy(monkeypatch)
  assert _counted() == before
  assert "pallas_call" not in text and "ragged_dot" not in text
  assert grouped_matmul.DOT_KERNEL not in text
  assert grouped_matmul.DW_KERNEL not in text


# --- the tiling rule -------------------------------------------------------

# rows of the head x hidden x expert width x experts held: the six MoE cells
# of BENCHMARK.json
CELL_SHAPES = {
    "solar": (6560, 4096, 1280, 8),
    "sdar": (32768, 2048, 768, 16),
    "keye": (32768, 2048, 768, 16),
    "laguna": (32768, 2048, 512, 32),
    "lfm2": (32768, 2048, 1536, 8),
    "glm": (16384, 2048, 1536, 8),
}


def _cell_products(rows, d, f):
  """(m, k, n, dw) of the six products of an expert layer."""
  return [(rows, d, f, False), (rows, f, d, False), (rows, d, f, True),
          (rows, f, d, True)]


@pytest.mark.parametrize("tail", [False, True])
@pytest.mark.parametrize("cell", CELL_SHAPES)
def test_the_rule_is_total_over_the_cells_and_within_vmem(monkeypatch, cell,
                                                           tail):
  """Every product of every cell's head, and of its tail's pieces (as long
  as the head, or what is left of the stream), has tiles: whole lane tiles,
  whole sublane tiles of bfloat16, pieces that divide a tile, and blocks that
  by the rule's own arithmetic fit the VMEM the kernel asks for, inside a
  v5e's 128 MiB."""
  rows, d, f, held = CELL_SHAPES[cell]
  if tail:
    rows = max(rows // 3 // 8 * 8, 8)
  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
  w = jax.ShapeDtypeStruct((held, d, f), F32)
  w_down = jax.ShapeDtypeStruct((held, f, d), F32)
  assert dense.grouped_kernel(BF16, jax.ShapeDtypeStruct((rows, d), F32),
                              (w, w)) is False
  assert dense.grouped_kernel(BF16, jax.ShapeDtypeStruct((rows, f), F32),
                              (w_down,)) is False
  for m, k, n, dw in _cell_products(rows, d, f):
    t = grouped_matmul.tiles(m, k, n, held, dw)
    assert t == grouped_matmul.tiles(m, k, n, held, dw)   # a pure function
    assert t.tm % 16 == 0 and t.tm % t.sub == 0 and t.sub % 16 == 0
    assert n % t.tn == 0 and t.tn % 128 == 0
    assert t.tn % t.tc == 0 and t.tc % 128 == 0 and t.tc <= 256
    assert k % t.tk == 0 and t.tk % 128 == 0 and (dw or t.tk == k)
    blocks = grouped_matmul.block_bytes(t, dw)
    assert blocks <= grouped_matmul.VMEM_BLOCKS
    assert blocks + grouped_matmul.VMEM_BESIDE <= 100 << 20


def test_the_rule_refuses_what_no_block_fits():
  assert grouped_matmul.tiles(512, 65536, 128, 4) is None
  assert grouped_matmul.tiles(512, 192, 128, 4) is None
  assert grouped_matmul.tiles(512, 128, 100, 4) is None
  # a short stream is one tile of its own rows, in whole sublane tiles
  assert grouped_matmul.tiles(40, 128, 128, 4).tm == 48


# --- the kernels compiled for a described v5e, at the cells' shapes --------

@pytest.fixture(scope="module")
def one_chip():
  """A described, not attached, v5e chip to compile for; the persistent
  compile cache is off meanwhile (an entry written for a described chip
  cannot be read back and warns)."""
  from jax.experimental import topologies
  from jax.experimental.compilation_cache import compilation_cache
  try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
  except Exception as e:  # pylint: disable=broad-except
    pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
  was = jax.config.jax_enable_compilation_cache
  jax.config.update("jax_enable_compilation_cache", False)
  compilation_cache.reset_cache()
  yield SingleDeviceSharding(topo.devices[0])
  jax.config.update("jax_enable_compilation_cache", was)
  compilation_cache.reset_cache()


@pytest.mark.parametrize("cell", [c for c in CELL_SHAPES if c != "keye"])
def test_the_kernels_compile_for_a_v5e_at_a_cells_shape(one_chip, cell):
  """Both projections' three products under the rule's tiles: Mosaic takes
  them, in the VMEM the kernel asks for."""
  rows, d, f, held = CELL_SHAPES[cell]
  aval = lambda *s, dt=BF16: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
  sizes = aval(held, dt=jnp.int32)
  for k, n in ((d, f), (f, d)):
    for fn, args in (
        (grouped_matmul.grouped_dot, (aval(rows, k), aval(held, k, n), sizes)),
        (functools.partial(grouped_matmul.grouped_dot, transposed=True),
         (aval(rows, n), aval(held, k, n), sizes)),
        (grouped_matmul.grouped_dw, (aval(rows, k), aval(rows, n), sizes))):
      text = jax.jit(fn).lower(*args).compile().as_text()
      assert "tpu_custom_call" in text and "ragged-dot" not in text


def test_a_kernels_code_does_not_grow_with_its_blocks_width(one_chip):
  """A step holds a hundred calls of these kernels and keeps the code of
  each in HBM, where `hbm_peak_gib` sees it: written out whole, a block's
  product made GLM's step 0.2 GB larger (PERF.md, PR 53). The columns are
  walked a chunk at a time by a loop the compiler keeps rolled, so four
  times the width is the same code but for the stores of a tile of zeros."""
  aval = lambda *s, dt=BF16: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

  def code(fn, *args):
    return jax.jit(fn).lower(*args).compile().memory_analysis(
        ).generated_code_size_in_bytes

  sizes = aval(4, dt=jnp.int32)
  for fn, args in (
      (grouped_matmul.grouped_dot,
       lambda n: (aval(1024, 512), aval(4, 512, n), sizes)),
      (functools.partial(grouped_matmul.grouped_dot, transposed=True),
       lambda n: (aval(1024, 512), aval(4, n, 512), sizes)),
      (grouped_matmul.grouped_dw,
       lambda n: (aval(1024, 512), aval(1024, n), sizes))):
    narrow, wide = code(fn, *args(512)), code(fn, *args(2048))
    assert wide < 1.5 * narrow, (narrow, wide)


# --- what a step pays before its first run ---------------------------------

def _layers_lowered_for_the_tpu(n_layers, monkeypatch):
  """The lowered module (no chip) of `grad` over `n_layers` checkpointed
  expert layers, each a function of its own as a packed model's are."""
  from distributed_embeddings_tpu.layers import remat
  from distributed_embeddings_tpu.telemetry import scopes
  _forced(monkeypatch, interpret=False)
  d, f, held, rows = 256, 128, 4, 512

  def layer(i):
    def run(x, w_gate, w_up, w_down, sizes):
      with jax.named_scope(scopes.MOE_EXPERTS):
        gate, up = dense.grouped_mxu_dots(x, (w_gate, w_up), sizes)
        y, = dense.grouped_mxu_dots(jax.nn.silu(gate) * up, (w_down,), sizes)
      return x + (i + 1) * y
    return remat.checkpoint_layer(run)

  def loss(x, weights, sizes):
    for i, w in enumerate(weights):
      x = layer(i)(x, *w, sizes)
    return jnp.sum(x)

  f32 = lambda *s: jax.ShapeDtypeStruct(s, F32)
  w = (f32(held, d, f), f32(held, d, f), f32(held, f, d))
  return jax.jit(jax.grad(loss, argnums=(0, 1))).trace(
      f32(rows, d), (w,) * n_layers,
      jax.ShapeDtypeStruct((held,), jnp.int32)).lower(
          lowering_platforms=("tpu",))


def test_the_kernels_are_lowered_once_a_module_however_many_layers(
    monkeypatch):
  """`setup_s` sees every kernel body lowered (PERF.md, PR 44, PR 52): a
  checkpoint takes a bare `jit` apart at every call. Entered as
  `layers/dense.py` enters them, four layers lower as many kernels as two."""
  two = _layers_lowered_for_the_tpu(2, monkeypatch).as_text()
  four = _layers_lowered_for_the_tpu(4, monkeypatch).as_text()
  kernels = two.count("tpu_custom_call")
  # the forward's two widths, their transposes for `dx`, two `dw`; what the
  # forward traces under two contexts is counted twice at most
  assert 6 <= kernels <= 8
  assert four.count("tpu_custom_call") == kernels
  assert "ragged_dot" not in two


def test_every_kernel_call_lies_under_the_experts_scope(monkeypatch):
  """All three passes: each jitted pass is a function of the module, and
  every CALL of one carries the caller's `de_moe_experts` (XLA joins it on as
  it inlines the call), so `moe_experts_ms` and the `moe_experts*_mxu_pct`
  shares go on reading the work; the kernels keep their own names inside."""
  import re
  from distributed_embeddings_tpu.telemetry import scopes
  text = _layers_lowered_for_the_tpu(2, monkeypatch).as_text(debug_info=True)
  named = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
  calls = re.findall(r"call @(_products|_cotangents)\w*\(.* loc\((#loc\d+)\)",
                     text)
  # a layer: the forward's two calls, the rebuilt forward's two, the
  # backward's two (the first layer's forward is not kept apart)
  assert len(calls) >= 10, calls
  for fn, loc in calls:
    assert scopes.MOE_EXPERTS in named[loc], (fn, named[loc])
  assert {fn for fn, _ in calls} == {"_products", "_cotangents"}
  assert any("transpose(" in named[loc] for _, loc in calls)
  assert any("rematted_computation" in named[loc] for _, loc in calls)
  kernels = [name for name in named.values() if "pallas_call" in name]
  assert kernels and all(
      name.startswith((grouped_matmul.DOT_KERNEL, grouped_matmul.DW_KERNEL))
      for name in kernels)
