"""What a language-model cell's compiled step runs twice, and what it costs
to keep what it does not.

`hbm_peak_gib` is the allocator's peak after a window and leaves a step's
temporaries out, so it cannot see what a rematerialisation plan
(`layers/remat.py`) spends; and a trace names the kernels a step ran, not how
many of them were repeats. This compiles the cell's training step, as the
benchmark builds it, for a DESCRIBED v5e (no chip: the TPU's compiler is
installed beside JAX) and prints, for one step, from the compiled HLO:

  splash_fwd   calls of the splash-attention forward kernel: one a layer
               where its output is kept, two where the layer's forward is
               run again whole
  sparse_attn_fwd, sparse_attn_mean, sparse_attn_dq, sparse_attn_dkv
               calls of the four kernels of `ops/pallas_sparse_attn.py`
               (attention under an indexer's selection): a layer runs each
               once (the heads' mean in the forward, `dq` sums the backward's
               as it goes); a rebuilt layer runs none
  moe_combine  calls of `ops/pallas_moe_combine.py` (`de_moe_combine`, the
               expert layer's combine read token-major): two an expert layer,
               the layer's output in the forward and the cotangent of its
               input in the backward; none in the rebuilt forward; 0 in a
               cell without experts
  ragged_dot   grouped matmuls of the expert layers' heads (a layer: 3
               forward, 3 rematerialised, 6 backward = 12); ragged_dot_tail:
               those inside a conditional (the tail's, walked only where a
               router overflows the head); ragged_dot_bf16,
               ragged_dot_tail_bf16: those of each that are handed bfloat16
               rows and weights (all of them on a TPU at default precision
               since PR 45, `layers/dense.py::grouped_mxu_dots`; 0 before).
               Since PR 53 all four are 0 wherever the kernels below apply
  grouped_dot, grouped_dw   calls of the grouped-matmul kernels of
               `ops/pallas_grouped_matmul.py` (`de_grouped_dot`: a layer's 3
               forward, 3 rematerialised and 3 `dx` products; `de_grouped_dw`:
               its 3 `dw`); grouped_dot_tail, grouped_dw_tail: those inside a
               conditional (the tail's)
  route_sort   the expert layers' stable argsort of the assignments: once a
               layer where the sorted order is kept; route_top_k: the sorts
               the router's `top_k` compiles to (forward and rematerialised);
               sort: every sort op of the step (scatters' and the summed
               table rule's among them)
  dense_dot_f32, dense_dot_bf16   the step's plain products (`dot` and
               `convolution` instructions, which is what XLA makes of a
               matmul; a Mosaic kernel's products are inside its body and
               not counted) by what they are handed: every operand bfloat16,
               or some operand float32 (the router's logits and the delta
               rule's products, which stay at `highest`, and any product
               `layers/dense.py` does not serve)
  state_gb, temporaries_gb, total_gb   the compiled step's arguments (the
               train state and a batch) and its temporaries, in GB of the
               chip's 17.18; code_gb: the executable itself, which lies in
               HBM too and which `hbm_peak_gib` sees (a Mosaic kernel's body
               is kept once a CALL: PR 53's first kernels, their products
               written out whole, added 0.2 GB to GLM's step)
  program_sha  sha256 of the compiled step's HLO text with every
               `metadata={...}`, `frontend_attributes={...}` and source
               location taken out: what the chip runs, less what a profiler
               reads. Equal on two commits: the same program, whatever its
               scopes are named (run this file over each commit's checkout)

Counts and the compiler's own byte counts: nothing runs and nothing here is
a time. The model's own check of its backend is answered "tpu" in this
process, as `benchmark/README.md` step 3 says a rehearsal may; a minute a cell.

  JAX_PLATFORMS=cpu python tools/step_recompute.py laguna_moe_train_1chip
"""

import argparse
import base64
import hashlib
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# an instruction of HLO text: `%name = type opcode(operands), attributes`
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*(?P<type>\([^=]*?\)|\S+)\s+"
    r"(?P<opcode>[\w\-]+)\(")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}"
                       r"|(?:true|false)_computation=([^,\s]+)")
_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# under de_moe_route, with or without the part's scope between
_ROUTE_SORT = re.compile(r"de_moe_route/(?:de_moe_sort/)?jit\(argsort\)/sort$")
_ROUTE_TOP_K = re.compile(r"de_moe_route/(?:de_moe_router/)?top_k$")
# what an instruction's line says of where it came from, and nothing of what
# it computes: `metadata={op_name=".." source_file=".." ..}` and
# `frontend_attributes={..}` (one level of braces inside, strings skipped)
_PROVENANCE = re.compile(
    r',?\s*(?:metadata|frontend_attributes)=\{(?:[^{}"]|"(?:[^"\\]|\\.)*"'
    r'|\{(?:[^{}"]|"(?:[^"\\]|\\.)*")*\})*\}')
# the module's own tables of source locations, each up to its blank line
_TABLES = re.compile(
    r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*",
    re.M)
_KERNEL_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')


def _computations(hlo_text: str):
  """name -> the lines of each computation of an HLO module's text."""
  out, name = {}, None
  for line in hlo_text.splitlines():
    m = _COMPUTATION.match(line)
    if m:
      name = m.group(1)
      out[name] = []
    elif line.strip() == "}":
      name = None
    elif name is not None:
      out[name].append(line)
  return out


def _operands(line: str, start: int):
  """The operands of the instruction whose ``(`` ends at ``start``, split at
  the commas outside every bracket."""
  out, depth, piece = [], 0, []
  for ch in line[start:]:
    if ch in "([{":
      depth += 1
    elif ch in ")]}":
      if depth == 0:
        break
      depth -= 1
    if ch == "," and depth == 0:
      out.append("".join(piece).strip())
      piece = []
    else:
      piece.append(ch)
  return out + ["".join(piece).strip()]


def _branches(line: str):
  """Names of the computations an instruction's line gives as branches."""
  return [name.strip().lstrip("%") for m in _BRANCHES.finditer(line)
          for name in (m.group(1) or m.group(2)).split(",")]


def _under_conditionals(comps):
  """Names of the computations reached through a ``conditional``'s branches
  (and whatever those call)."""
  todo = [name for lines in comps.values() for line in lines
          for name in _branches(line)]
  seen = set()
  while todo:
    name = todo.pop()
    if name in seen or name not in comps:
      continue
    seen.add(name)
    for line in comps[name]:
      todo.extend(_CALLS.findall(line) + _branches(line))
  return seen


def count_ops(hlo_text: str):
  """-> calls in a compiled program's HLO text, each instruction counted once,
  where it is defined. The TPU compiler names a Pallas or Mosaic call after
  its kernel: a splash forward kernel is a custom call named
  ``splash_*fwd*``, a grouped matmul one named ``ragged-dot-*`` (its
  ``ragged-dot-metadata`` calls, a few hundred bytes each, are not counted;
  ``ragged_dot_tail``: those inside a conditional; ``ragged_dot_bf16``,
  ``ragged_dot_tail_bf16``: those of each whose two matrices, its last
  operands, are bfloat16), a kernel of
  ``ops/pallas_sparse_attn.py`` one named ``de_sparse_attn_<which>``, the
  expert layer's combine ``de_moe_combine``, a grouped-matmul kernel of
  ``ops/pallas_grouped_matmul.py`` ``de_grouped_dot`` or ``de_grouped_dw``
  (``*_tail``: inside a conditional). A sort is told by the
  ``op_name`` the program gave it: ``route_sort`` is the expert layer's stable
  argsort, ``route_top_k`` the sort the router's ``top_k`` compiles to. A
  plain product is a ``dot`` or a ``convolution`` instruction, counted by its
  operands' element types: ``dense_dot_bf16`` where all are bfloat16,
  ``dense_dot_f32`` where one is float32."""
  comps = _computations(hlo_text)
  tail = _under_conditionals(comps)
  counts = dict.fromkeys(("splash_fwd", "ragged_dot", "ragged_dot_tail",
                          "ragged_dot_bf16", "ragged_dot_tail_bf16", "sort",
                          "route_sort", "route_top_k", "dense_dot_f32",
                          "dense_dot_bf16", "moe_combine", "grouped_dot",
                          "grouped_dot_tail", "grouped_dw", "grouped_dw_tail",
                          *(f"sparse_attn_{which}" for which in
                            ("fwd", "mean", "dq", "dkv"))), 0)
  for comp, lines in comps.items():
    instructions = [m for m in map(_INSTRUCTION.match, lines) if m]
    element = {m.group("name"): m.group("type").split("[")[0]
               for m in instructions}
    for m in instructions:
      line = m.string
      opcode, name = m.group("opcode"), m.group("name")
      if opcode in ("dot", "convolution"):
        # an operand is a name of this computation, or carries its type
        handed = {o.split("[")[0] if " " in o else element.get(o.lstrip("%"))
                  for o in _operands(line, m.end())}
        if "f32" in handed:
          counts["dense_dot_f32"] += 1
        elif handed == {"bf16"}:
          counts["dense_dot_bf16"] += 1
      elif opcode == "sort":
        counts["sort"] += 1
        op_name = _OP_NAME.search(line)
        where = op_name.group(1) if op_name else ""
        if _ROUTE_SORT.search(where):
          counts["route_sort"] += 1
        elif _ROUTE_TOP_K.search(where):
          counts["route_top_k"] += 1
      elif opcode == "ragged-dot" or (
          opcode == "custom-call"
          and re.match(r"ragged-dot-(?!metadata)", name)):
        which = "ragged_dot_tail" if comp in tail else "ragged_dot"
        counts[which] += 1
        # the kernel takes the group sizes and its tables first and the two
        # matrices last; the plain instruction the matrices first
        operands = _operands(line, m.end())
        matrices = operands[:2] if opcode == "ragged-dot" else operands[-2:]
        if {element.get(re.sub(r"^/\*.*?\*/", "", o).lstrip("%"))
            for o in matrices} == {"bf16"}:
          counts[which + "_bf16"] += 1
      elif opcode == "custom-call" and re.match(r"splash_\w*fwd", name):
        counts["splash_fwd"] += 1
      elif opcode == "custom-call" and (
          kernel := re.match(r"de_(sparse_attn_(?:fwd|mean|dq|dkv))\b", name)):
        counts[kernel.group(1)] += 1
      elif opcode == "custom-call" and re.match(r"de_moe_combine\b", name):
        counts["moe_combine"] += 1
      elif opcode == "custom-call" and (
          kernel := re.match(r"de_(grouped_(?:dot|dw))\b", name)):
        counts[kernel.group(1) + ("_tail" if comp in tail else "")] += 1
  return counts


def without_provenance(hlo_text: str) -> str:
  """A compiled program's HLO text less what says where an instruction came
  from and nothing of what it computes: every ``metadata={...}`` and
  ``frontend_attributes={...}``, the module's tables of files, functions,
  locations and stack frames, and the locations inside a Mosaic kernel's
  body (MLIR bytecode or text in the custom call's ``backend_config``,
  base64: a Pallas kernel's carries the file and line of every call on the
  way to it; it is printed again without them)."""
  from jax._src.interpreters import mlir
  from jax._src.lib import tpu
  from jax._src.lib.mlir import ir
  ctx = mlir.make_ir_context()
  tpu.register_dialect(ctx)                # XLA's own kernels come as text
  ctx.allow_unregistered_dialects = True   # `stable_mosaic`: read, never run

  def kernel_without_locations(match) -> str:
    with ctx:
      body = ir.Module.parse(base64.b64decode(match.group(1)))
      return '"body":' + json.dumps(
          body.operation.get_asm(enable_debug_info=False))

  text = _PROVENANCE.sub("", _TABLES.sub("", hlo_text))
  return _KERNEL_BODY.sub(kernel_without_locations, text)


def program_sha(hlo_text: str) -> str:
  """sha256 of :func:`without_provenance`: equal for two programs that
  differ in names of scopes, files and lines alone."""
  return hashlib.sha256(without_provenance(hlo_text).encode()).hexdigest()


def build_program(cell_name: str):
  """What `benchmark/run.py` times as "plan and model", for one chip of a
  described v5e -> (the cell, its model spec, the `program.Program`)."""
  import jax

  from benchmark import program, specs

  cell = specs.load_cell(cell_name)
  if cell.chips != 1:
    raise SystemExit(f"{cell_name}: a cell of {cell.chips} chips; this tool "
                     "describes one")
  # the models ask the backend before they name a TPU kernel, and this
  # process compiles for a chip it does not have
  jax.default_backend = lambda: "tpu"
  family = cell.family()
  spec = family.model_spec(cell.config)
  parts = family.build_parts(cell.config, cell.chips,
                             int(cell.traffic["global_batch"]))
  return cell, spec, program.Program(parts, spec, 0, None)


def compile_built(cell, spec, prog):
  """What :func:`build_program` gave -> the cell's training step compiled
  for one chip of a described v5e."""
  import jax
  from jax.experimental import topologies
  from jax.sharding import SingleDeviceSharding

  from benchmark import traffic

  batch = traffic.make_batch(cell.traffic, spec.inputs, spec.n_numerical, 0,
                             0, traffic.family_labels(cell.family(),
                                                      cell.config))
  topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
  chip = SingleDeviceSharding(topo.devices[0])
  on_chip = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)
  # the benchmark's own builder of the step, fed shapes on the described chip
  # where its window feeds arrays on a real one
  prog.put = lambda b: jax.tree_util.tree_map(
      on_chip, (b.numerical, b.cats, b.labels))
  return prog.compile_step(
      jax.tree_util.tree_map(on_chip, prog.state_avals()), batch)


def compile_step(cell_name: str):
  """The cell's training step compiled for one chip of a described v5e ->
  the compiled executable."""
  return compile_built(*build_program(cell_name))


def main(argv=None):
  ap = argparse.ArgumentParser()
  ap.add_argument("cell")
  args = ap.parse_args(argv)
  compiled = compile_step(args.cell)
  mem = compiled.memory_analysis()
  gb = lambda n: round(n / 1e9, 3)
  text = compiled.as_text()
  report = {"cell": args.cell, "compiled_for": "v5e (described, no chip)",
            **count_ops(text),
            "state_gb": gb(mem.argument_size_in_bytes),
            "temporaries_gb": gb(mem.temp_size_in_bytes),
            "total_gb": gb(mem.argument_size_in_bytes
                           + mem.temp_size_in_bytes),
            "code_gb": gb(mem.generated_code_size_in_bytes),
            "program_sha": program_sha(text)}
  print(json.dumps(report))
  return report


if __name__ == "__main__":
  main()
