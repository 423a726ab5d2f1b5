"""The program's side of a cell, shared by the families: the state filled
with the benchmark's own weights in the program's packed layout, the jitted
step as the window drives it, and the small read-backs the one-step check
needs. A family supplies the program objects (model, plan, rule, optimizer)
built through the program's normal path; nothing here names a family.
"""

from __future__ import annotations

import dataclasses
import functools
import re
from typing import Any, Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import reference, traffic, weights
from distributed_embeddings_tpu.compat import shard_map
from distributed_embeddings_tpu.parallel.lookup_engine import (
    DistributedLookup,
    class_param_name,
    padded_rows,
)
from distributed_embeddings_tpu.training import (
    init_sparse_state_direct,
    make_sparse_train_step,
    shard_params,
)

AXIS = "mp"
APPLY_KERNEL = "de_apply_rows_cached"
READ_CHUNK = 1 << 16  # physical rows a read-back gathers at a time

_KERNEL_NAME = re.compile(r'op_name="[^"]*?(\w+)/pallas_call"')
_SHAPE = re.compile(r"\w+\[([\d,]*)\]")


def _braced(text: str, key: str) -> str:
  """The ``{...}`` that follows ``key`` in ``text`` (braces nest); '' where
  the key is absent."""
  at = text.find(key + "{")
  if at < 0:
    return ""
  depth, begin = 0, at + len(key)
  for i in range(begin, len(text)):
    depth += (text[i] == "{") - (text[i] == "}")
    if depth == 0:
      return text[begin + 1:i]
  return ""


def kernel_calls(hlo_text: str) -> List[Tuple[str, List[Tuple[int, ...]]]]:
  """The Pallas (Mosaic) kernel calls in a compiled program's HLO: per
  ``tpu_custom_call`` line the ``pallas_call``'s name (the end of its
  ``op_name`` metadata) and the shapes of its operands."""
  calls = []
  for line in hlo_text.splitlines():
    name = _KERNEL_NAME.search(line) if "tpu_custom_call" in line else None
    if name is None:
      continue
    shapes = [tuple(int(d) for d in dims.split(",") if d) for dims in
              _SHAPE.findall(_braced(line, "operand_layout_constraints="))]
    calls.append((name.group(1), shapes))
  return calls


def mosaic_kernels(hlo_text: str) -> List[str]:
  """Names of the Pallas (Mosaic) kernels in a compiled program's HLO."""
  return sorted({name for name, _ in kernel_calls(hlo_text)})


@dataclasses.dataclass
class Parts:
  """What a family builds through the program's normal path."""
  model: Any
  plan: Any
  rule: Any
  optimizer: Any
  loss_fn: Callable
  dense_template: Any  # pytree of ShapeDtypeStruct, the model's dense params
  split_cats: Callable  # [B, sum(hotness)] matrix -> the model's cats list


@dataclasses.dataclass(frozen=True)
class Home:
  """Where the plan put one table."""
  cls: str      # state key under 'fused' or 'emb_dense'
  kind: str     # 'sparse' (packed) | 'dense' (simple layout)
  rank: int
  offset: int   # first logical row inside the rank's class block


def _path_name(path) -> str:
  return "/".join(str(getattr(p, "key", getattr(p, "name", p))) for p in path)


class Program:

  def __init__(self, parts: Parts, spec: reference.ModelSpec, seed: int,
               mesh, table_dtype=jnp.float32):
    self.parts, self.spec, self.seed, self.mesh = parts, spec, seed, mesh
    self.table_dtype = table_dtype
    self._take, self._change = None, {}
    plan = parts.plan
    self.world = plan.world_size
    self.layouts = DistributedLookup(plan, axis_name=AXIS).fused_layouts(
        parts.rule)
    self.class_rows = {class_param_name(*k): padded_rows(plan, k)
                       for k in plan.class_keys}
    self.class_width = {class_param_name(*k): plan.classes[k].width
                        for k in plan.class_keys}
    self.homes: Dict[int, Home] = {}
    self.class_spans: Dict[str, List[List[Tuple[int, int, int]]]] = {}
    for key in plan.class_keys:
      cp, name = plan.classes[key], class_param_name(*key)
      self.class_spans[name] = []
      for rank in range(self.world):
        spans = []
        for sh, off in zip(cp.shards_per_rank[rank],
                           cp.row_offsets_per_rank[rank]):
          tb = spec.tables[sh.table_id]
          if (sh.row_sliced or sh.col_start != 0 or sh.col_end != tb.width
              or sh.input_dim != tb.rows or sh.table_id in self.homes):
            raise NotImplementedError(
                f"table {sh.table_id} is sliced or placed twice by the "
                "plan; the benchmark's fill places whole tables only")
          self.homes[sh.table_id] = Home(name, cp.kind, rank, int(off))
          spans.append((int(off), int(sh.input_dim), sh.table_id))
        self.class_spans[name].append(spans)
    missing = set(range(len(spec.tables))) - set(self.homes)
    if missing:
      raise ValueError(f"the plan places no table for {sorted(missing)}")
    self.keys = [weights.leaf_key(seed, reference.table_name(t))
                 for t in range(len(spec.tables))]

  # ---- the state, filled with the benchmark's weights ----------------------
  def _span_tables(self, name):
    """Per rank, padded to one length: offsets, lengths, keys, scales."""
    spans = self.class_spans[name]
    n = max(1, max(len(s) for s in spans))
    offs = np.zeros((self.world, n), np.int32)
    lens = np.zeros((self.world, n), np.int32)
    keys = np.zeros((self.world, n), np.uint32)
    scales = np.zeros((self.world, n), np.float32)
    for r, sp in enumerate(spans):
      for j, (off, rows, t) in enumerate(sp):
        offs[r, j], lens[r, j] = off, rows
        keys[r, j], scales[r, j] = self.keys[t], self.spec.tables[t].scale
    return offs, lens, keys, scales

  def _block_values(self, name, logical, cols, rank, keys):
    """Weights at (logical class row, column) of one rank's block; 0 on the
    block's padding rows. ``logical``/``cols`` broadcast. ``keys`` (the
    seed's, ``[world, spans]``) is an argument of the compiled program, so
    one program serves every seed."""
    offs, lens, _, scales = (jnp.asarray(t)[rank]
                             for t in self._span_tables(name))
    keys = keys[rank]
    key = jnp.zeros(logical.shape, jnp.uint32)
    scale = jnp.zeros(logical.shape, jnp.float32)
    row = jnp.zeros(logical.shape, jnp.int32)
    for j in range(offs.shape[0]):
      inside = (logical >= offs[j]) & (logical < offs[j] + lens[j])
      key = jnp.where(inside, keys[j], key)
      scale = jnp.where(inside, scales[j], scale)
      row = jnp.where(inside, logical - offs[j], row)
    u = weights.unit_uniform(jnp, key, row.astype(jnp.uint32),
                             cols.astype(jnp.uint32))
    return u * scale, scale > 0

  def _packed_block(self, name, rank, keys):
    """One rank's packed buffer ``[phys_rows, phys_width]``: table lanes from
    the benchmark's weights, optimizer lanes at the rule's initial values."""
    lay = self.layouts[name]
    lane = np.arange(lay.phys_width)
    sub, col = lane // lay.stride, lane % lay.stride
    packed = lane < lay.rows_per_phys * lay.stride
    is_table = packed & (col < lay.width)
    aux = np.zeros((lay.phys_width,), np.float32)
    for j, v in enumerate(self.parts.rule.aux_init):
      aux[packed & (col // lay.width == 1 + j)] = v
    p = jax.lax.broadcasted_iota(jnp.int32, lay.shape, 0)
    logical = p * lay.rows_per_phys + jnp.asarray(sub, jnp.int32)[None, :]
    vals, live = self._block_values(
        name, logical, jnp.asarray(col % lay.width, jnp.int32)[None, :], rank,
        keys)
    out = jnp.where(jnp.asarray(is_table)[None, :], vals,
                    jnp.where(live, jnp.asarray(aux)[None, :], 0.0))
    return out.astype(self.table_dtype)

  def _simple_block(self, name, rank, keys):
    rows, width = self.class_rows[name], self.class_width[name]
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    vals, _ = self._block_values(name, r, c, rank, keys)
    return vals.astype(self.table_dtype)

  def _class_array(self, name, kind):
    block = self._packed_block if kind == "sparse" else self._simple_block
    keys = jnp.asarray(self._span_tables(name)[2])
    if self.mesh is None:
      if self.world != 1:
        raise ValueError("a plan for several ranks needs its mesh")
      return jax.jit(lambda k: block(name, 0, k))(keys)
    fn = shard_map(lambda k: block(name, jax.lax.axis_index(AXIS), k),
                   mesh=self.mesh, in_specs=P(), out_specs=P(AXIS),
                   check_vma=False)
    return jax.jit(fn)(keys)

  def fill(self):
    """The train state with the benchmark's weights in every parameter, in
    the structure and placement ``init_sparse_state_direct`` gives."""
    plan, parts = self.parts.plan, self.parts
    w0 = reference.dense_weights(self.spec, self.seed)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        parts.dense_template)
    names = [_path_name(p) for p, _ in leaves]
    if sorted(names) != sorted(w0):
      raise ValueError(f"the model's dense leaves {sorted(names)} are not "
                       f"the configuration's {sorted(w0)}")
    for n, (_, leaf) in zip(names, leaves):
      if tuple(leaf.shape) != w0[n].shape:
        raise ValueError(f"{n}: model {leaf.shape}, config {w0[n].shape}")
    dense = jax.tree_util.tree_unflatten(
        treedef, [jnp.asarray(w0[n]) for n in names])
    fused, emb_dense = {}, {}
    for key in plan.class_keys:
      name, kind = class_param_name(*key), plan.classes[key].kind
      (fused if kind == "sparse" else emb_dense)[name] = \
          self._class_array(name, kind)
    state = shard_params({
        "dense": dense,
        "dense_opt": parts.optimizer.init(dense),
        "emb_dense": emb_dense,
        "emb_dense_opt": parts.optimizer.init(emb_dense),
        "fused": fused,
        "step": jnp.zeros((), jnp.int32),
    }, self.mesh, AXIS)
    want = self.state_avals()
    got = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    if jax.tree_util.tree_structure(want) != jax.tree_util.tree_structure(
        got) or jax.tree_util.tree_leaves(want) != \
        jax.tree_util.tree_leaves(got):
      raise ValueError("the filled state is not the shape of the program's "
                       "own init_sparse_state_direct")
    return state

  def state_avals(self):
    """Shapes of the program's own state constructor, never run."""
    parts = self.parts
    avals = jax.eval_shape(lambda: init_sparse_state_direct(
        parts.plan, parts.rule,
        jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype),
                               parts.dense_template),
        parts.optimizer, jax.random.PRNGKey(0), dtype=self.table_dtype))
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), avals)

  # ---- the step, as the window calls it ------------------------------------
  def put(self, batch: traffic.Batch):
    """One host batch onto the device(s), split over the mesh by rows: the
    window's feed. ``labels`` is an array or the family's tree of arrays,
    every leaf with the batch as its leading dimension."""
    arrays = (batch.numerical, batch.cats, batch.labels)
    if self.mesh is None:
      return tuple(jax.device_put(a) for a in arrays)
    sharding = NamedSharding(self.mesh, P(AXIS))
    return tuple(jax.device_put(a, sharding) for a in arrays)

  def compile_step(self, state, batch: traffic.Batch):
    """The donated, jitted step over (state, numerical, cats matrix, labels),
    lowered and compiled for this state and batch. The categorical ids travel
    as one matrix and are split on the device, as `examples/dlrm/main.py`
    feeds them; the labels go to the family's ``loss_fn`` as they are."""
    parts = self.parts
    example = (jnp.zeros(batch.numerical.shape, jnp.float32),
               parts.split_cats(jnp.zeros(batch.cats.shape, jnp.int32)),
               jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, x.dtype),
                                      batch.labels))
    inner = make_sparse_train_step(
        parts.model, parts.plan, parts.loss_fn, parts.optimizer, parts.rule,
        self.mesh, state, example, donate=False)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step_fn(carry, numerical, cats, labels):
      return inner(carry, numerical, parts.split_cats(cats), labels)

    return step_fn.lower(state, *self.put(batch)).compile()

  def apply_shapes(self, pool: Sequence[traffic.Batch], hlo_text: str
                   ) -> Dict[str, Any]:
    """What `roofline.apply_rows_hbm_bytes` counts, for the packed classes
    the compiled step hands to the apply kernel. Which those are is read
    from the compiled step itself (``hlo_text``): every call of the kernel
    names its buffer ``[physical rows, lanes]`` and its delta stream
    ``[delta rows, lanes]``, and a buffer's shape names its class. Per such
    class and rank: the delta rows one step scatters into it (every
    occurrence of the global batch that reads one of its tables; the call's
    static stream is that or longer, padded), the distinct physical rows
    among them (mean over the pool), and the bytes of a physical row. A call
    whose buffer is no class's, classes of one shape of which only some are
    served, or a stream shorter than the occurrences cannot be counted: an
    error, not a guess."""
    calls: Dict[Tuple[int, ...], List[int]] = {}
    for name, shapes in kernel_calls(hlo_text):
      if name == APPLY_KERNEL:
        ids, buf = shapes[0], shapes[1]
        calls.setdefault(buf, []).append(ids[0])
    by_shape: Dict[Tuple[int, ...], List[str]] = {}
    for name, lay in self.layouts.items():
      by_shape.setdefault((lay.phys_rows, lay.phys_width), []).append(name)
    spans = traffic.column_spans(self.spec.inputs)
    classes = []
    for buf, streams in calls.items():
      names = by_shape.get(buf, [])
      if len(names) != len(streams) or len(set(streams)) > 1:
        raise ValueError(
            f"{len(streams)} {APPLY_KERNEL} call(s) on a buffer {buf} with "
            f"delta streams {streams}: the plan's classes of that shape are "
            f"{names}; the roofline's bytes cannot be counted")
      for name in names:
        lay = self.layouts[name]
        for rank in range(self.world):
          tables = {t: off for off, _, t in self.class_spans[name][rank]}
          cols = [(tables[i.table], a, b)
                  for i, (a, b) in zip(self.spec.inputs, spans)
                  if i.table in tables]
          occurrences = sum(b - a for _, a, b in cols) * pool[0].cats.shape[0]
          if occurrences > streams[0]:
            raise ValueError(
                f"{name} rank {rank}: {occurrences} occurrences a step, but "
                f"the kernel's delta stream holds {streams[0]}")
          unique = [len(np.unique(np.concatenate(
              [(off + batch.cats[:, a:b].reshape(-1).astype(np.int64))
               // lay.rows_per_phys for off, a, b in cols])))
                    if cols else 0 for batch in pool]
          classes.append({"class": name, "rank": rank,
                          "occurrences": occurrences,
                          "unique_rows": float(np.mean(unique)),
                          "row_bytes": lay.phys_width * 4})
    return {"apply_classes": classes, "ranks": self.world}

  # ---- read-backs for the one-step check -----------------------------------
  def _take_rows(self, buf, idx):
    """``idx [world, n]`` rank-local row numbers -> ``[world, n, width]``."""
    if self._take is None:
      take = lambda b, i: jnp.take(b, i[0], axis=0)[None]
      if self.mesh is not None:
        take = shard_map(take, mesh=self.mesh, in_specs=(P(AXIS), P(AXIS)),
                         out_specs=P(AXIS), check_vma=False)
      self._take = jax.jit(take)
    return self._take(buf, self._rank_major(idx))

  def _rank_major(self, x):
    if self.mesh is None:
      return x
    return jax.device_put(x, NamedSharding(self.mesh, P(AXIS)))

  def _change_fn(self, cls: str):
    """Per packed class, one compiled program: gather a chunk of physical
    rows, cut each logical row out (its table lanes, then the rule's
    accumulator lanes), and subtract what the fill put there: the
    benchmark's weight of that row and the rule's initial values. The
    table's key, scale and offset are arguments."""
    if cls in self._change:
      return self._change[cls]
    lay = self.layouts[cls]
    width = lay.width

    def change(buf, idx, sub, key, scale, offset):
      phys = jnp.take(buf, idx[0], axis=0).astype(jnp.float32)
      lanes = jnp.arange(lay.stride, dtype=jnp.int32)[None, :]
      vals = jnp.take_along_axis(
          phys, sub[0][:, None] * lay.stride + lanes, axis=1)
      row = idx[0] * lay.rows_per_phys + sub[0] - offset
      cols = jnp.arange(width, dtype=jnp.uint32)[None, :]
      w0 = weights.unit_uniform(jnp, key, row.astype(jnp.uint32)[:, None],
                                cols) * scale
      filled = [w0.astype(self.table_dtype).astype(jnp.float32)] + [
          jnp.full(w0.shape, v, self.table_dtype).astype(jnp.float32)
          for v in self.parts.rule.aux_init]
      return (vals - jnp.concatenate(filled, axis=1))[None]

    if self.mesh is not None:
      change = shard_map(
          change, mesh=self.mesh,
          in_specs=(P(AXIS), P(AXIS), P(AXIS), P(), P(), P()),
          out_specs=P(AXIS), check_vma=False)
    self._change[cls] = jax.jit(change)
    return self._change[cls]

  def table_changes(self, state, touched: Dict[int, np.ndarray]
                    ) -> Tuple[Dict[int, np.ndarray], Dict[int, np.ndarray]]:
    """Per table, the state's rows at ``touched`` minus the benchmark's
    initial weights of those rows, float32 ``[n, width]``; and, for the
    tables of packed classes under a rule that keeps accumulators in the
    row, those rows' accumulators minus their initial values, the rule's
    lane groups side by side ``[n, n_aux * width]``. Packed
    tables are gathered on the device in chunks of ``READ_CHUNK`` physical
    rows (so the read-back's own temporary stays far under the step's); the
    small simple-layout classes come to the host whole."""
    out, acc, simple = {}, {}, {}
    for t, ids in touched.items():
      home, tb = self.homes[t], self.spec.tables[t]
      logical = home.offset + np.asarray(ids, np.int64)
      if home.kind == "dense":
        if home.cls not in simple:
          simple[home.cls] = np.asarray(state["emb_dense"][home.cls])
        rows = home.rank * self.class_rows[home.cls] + logical
        w0 = weights.rows_np(self.keys[t], tb.scale, ids, tb.width)
        out[t] = simple[home.cls][rows].astype(np.float32) \
            - np.asarray(w0, self.table_dtype).astype(np.float32)
        continue
      lay, fn = self.layouts[home.cls], self._change_fn(home.cls)
      parts = []
      for a in range(0, len(ids), READ_CHUNK):
        n = min(READ_CHUNK, len(ids) - a)
        idx = np.zeros((self.world, READ_CHUNK), np.int32)
        sub = np.zeros((self.world, READ_CHUNK), np.int32)
        idx[home.rank, :n] = logical[a:a + n] // lay.rows_per_phys
        sub[home.rank, :n] = logical[a:a + n] % lay.rows_per_phys
        got = fn(state["fused"][home.cls], self._rank_major(idx),
                 self._rank_major(sub), np.uint32(self.keys[t]),
                 np.float32(tb.scale), np.int32(home.offset))
        parts.append(np.asarray(got)[home.rank, :n])
      row = np.concatenate(parts) if parts else \
          np.zeros((0, lay.stride), np.float32)
      out[t] = row[:, :tb.width]
      if self.parts.rule.aux_init:
        acc[t] = row[:, tb.width:]
    return out, acc

  def read_dense(self, state) -> Dict[str, np.ndarray]:
    leaves = jax.tree_util.tree_flatten_with_path(state["dense"])[0]
    return {_path_name(p): np.asarray(x) for p, x in leaves}

  def read_blocks(self, state, n_blocks: int = 8, block_rows: int = 512):
    """A sample of row blocks of every packed class, drawn from the seed:
    ``(class, rank, first physical row, values [block_rows, phys_width])``.
    Nothing of a table's size is allocated."""
    rng = np.random.default_rng(weights.seed_words(self.seed, 0xB10C))
    out = []
    for name, lay in self.layouts.items():
      rows = min(block_rows, lay.phys_rows)
      starts = rng.integers(0, lay.phys_rows - rows + 1,
                            size=(self.world, n_blocks)).astype(np.int32)
      idx = (starts[:, :, None] + np.arange(rows, dtype=np.int32)
             ).reshape(self.world, n_blocks * rows)
      got = np.asarray(self._take_rows(state["fused"][name], idx)).astype(
          np.float32).reshape(self.world, n_blocks, rows, lay.phys_width)
      for r in range(self.world):
        for b in range(n_blocks):
          out.append((name, r, int(starts[r, b]), got[r, b]))
    return out

  def expected_block(self, name: str, rank: int, start: int, rows: int):
    """What the fill put into physical rows ``start .. start+rows`` of one
    rank's packed block (the benchmark's weights on table lanes, the rule's
    initial values on accumulator lanes, 0 on padding), and per (row, lane)
    the table and table row it belongs to (-1 on padding), computed on the
    host."""
    lay = self.layouts[name]
    lane = np.arange(lay.phys_width)
    sub, col = lane // lay.stride, lane % lay.stride
    packed = lane < lay.rows_per_phys * lay.stride
    group = col // lay.width  # 0: the table; 1 + j: the rule's accumulator j
    aux = np.array([0.0, *self.parts.rule.aux_init], np.float32)
    logical = (start + np.arange(rows))[:, None] * lay.rows_per_phys \
        + sub[None, :]
    vals = np.zeros((rows, lay.phys_width), np.float32)
    table = np.full((rows, lay.phys_width), -1, np.int64)
    trow = np.zeros((rows, lay.phys_width), np.int64)
    for off, n, t in self.class_spans[name][rank]:
      inside = (logical >= off) & (logical < off + n) & packed[None, :]
      if not inside.any():
        continue
      r_idx, l_idx = np.nonzero(inside)
      rr = logical[r_idx, l_idx] - off
      with np.errstate(over="ignore"):
        u = weights.unit_uniform(
            np, np.uint32(self.keys[t]) * np.ones(1, np.uint32),
            rr.astype(np.uint32), (col[l_idx] % lay.width).astype(np.uint32))
      vals[r_idx, l_idx] = np.where(
          group[l_idx] == 0, u * np.float32(self.spec.tables[t].scale),
          aux[group[l_idx]])
      table[r_idx, l_idx], trow[r_idx, l_idx] = t, rr
    return vals, table, trow
