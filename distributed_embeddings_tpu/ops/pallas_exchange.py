"""Pallas TPU kernel fusing the packed-table row gather into the exchange
send buffer: ``make_async_remote_copy`` ships chunk k while chunk k+1's
rows stream HBM->VMEM.

The fused schedule (``overlap='fused'``, `parallel/lookup_engine.py`
§26) already gives XLA per-(round, chunk) gathers with data dependence
only on the rows each round ships, so the compiler may overlap round k's
ppermute with round k+1's gather. This kernel closes the remaining gap
on real TPUs: XLA still materializes each gathered chunk in HBM before
the collective reads it back. Here the gather lands directly in the VMEM
send staging and the send starts the moment the chunk's last row DMA
completes — the hardware form of fused computation-collective
(arXiv 2305.06942) the ROADMAP bullet called for.

One body, two transports, double-buffered either way:

  for chunk k (static unroll):
    slot = k % 2
    wait the send that last used ``slot``          (k >= 2)
    stream chunk k's rows  buf[ids] -> stage[slot]  (per-row async copies)
    zero OOB rows in the staging slot
    start send of stage[slot] -> out chunk k        (remote or local DMA)
  wait the final (up to two) in-flight sends

so chunk k's send DMA is in flight while chunk k+1's rows stream in.

- ``gather_rows``: transport = LOCAL copy; ``out`` is this device's send
  buffer for the wire round (the ppermute payload). This is the entry the
  lookup engine's ``_fused_gather`` uses under ``DE_TPU_PALLAS_EXCHANGE``.
- ``gather_send_rows``: transport = ``make_async_remote_copy``; ``out``
  is the RECEIVING device's buffer — every rank gathers its routed rows
  and pushes them straight to rank ``send_to`` while receiving from
  ``recv_from`` (one fused ppermute round). Neighbor-barriered before any
  remote traffic, as every remote-DMA kernel must be.

Serves plain-row layouts (``rows_per_phys == 1``) with 128-lane physical
rows in f32 — the same Mosaic 1-row dynamic-HBM-slice limit as
``ops/pallas_apply.py``; OOB/sentinel ids produce all-zero rows exactly
like ``packed_table.gather_fused``. Gate: ``DE_TPU_PALLAS_EXCHANGE=1``
AND a real TPU backend (``_use_pallas_exchange``; kernels never run on
the CPU proxy). The interpret-mode twin `ops/pallas_exchange_sim.py`
runs THIS body (local transport) on CPU so tier-1 exercises the chunk /
double-buffer / OOB protocol bit-for-bit against the XLA gather.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_util import out_struct

LANES = 128

# the kernel's name in HLO (the Mosaic custom call) and in device traces
KERNEL_NAME = "de_exchange_gather_send"


def _use_pallas_exchange() -> bool:
  """True when the fused gather->send kernel may run: ``DE_TPU_PALLAS_``
  ``EXCHANGE=1`` (opt-in — unlike the apply kernel there is no measured
  CPU-proxy win to auto-select on; the fused XLA schedule is the
  default) AND a real TPU backend."""
  if os.environ.get("DE_TPU_PALLAS_EXCHANGE", "0") != "1":
    return False
  return jax.default_backend() == "tpu"


def _exchange_kernel(chunk, nchunks, remote, *refs):
  """Shared double-buffered gather->send body (module docstring).

  ``refs``: ids (SMEM, [nchunks*chunk]), nbr (SMEM, [2] = send_to,
  recv_from; ignored for local transport), buf (ANY), out (ANY), stage
  (VMEM [2, chunk, LANES]), rsem/send_sem/recv_sem (DMA semaphores [2]).
  """
  (ids_ref, nbr_ref, buf_ref, out_ref, stage, rsem, send_sem,
   recv_sem) = refs
  rows = buf_ref.shape[0]

  if remote:
    # ready-to-receive barrier: signal my SENDER (recv_from) that my out
    # buffer may be written; the matching signal reaching me comes from
    # my RECEIVER (send_to). No remote DMA starts before its destination
    # rank has entered the kernel.
    bsem = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(bsem, inc=1, device_id=(nbr_ref[1],),
                           device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_wait(bsem, 1)

  def _send(slot, k):
    dst = out_ref.at[pl.ds(k * chunk, chunk), :]
    if remote:
      return pltpu.make_async_remote_copy(
          src_ref=stage.at[slot], dst_ref=dst,
          send_sem=send_sem.at[slot], recv_sem=recv_sem.at[slot],
          device_id=(nbr_ref[0],),
          device_id_type=pltpu.DeviceIdType.LOGICAL)
    return pltpu.make_async_copy(stage.at[slot], dst, send_sem.at[slot])

  sends = [None] * nchunks
  for k in range(nchunks):        # static: nchunks is a Python int
    slot = k % 2
    if k >= 2:
      # slot reuse: the send that last staged from this slot must have
      # drained before its VMEM is overwritten (for the remote form this
      # also waits the matching chunk's arrival in OUR out buffer — the
      # SPMD-symmetric peer send on the same slot sequence)
      sends[k - 2].wait()

    def start_row(j, _):
      idx = ids_ref[k * chunk + j]
      safe = jnp.where(jnp.logical_and(idx >= 0, idx < rows), idx, 0)
      pltpu.make_async_copy(
          buf_ref.at[pl.ds(safe, 1), :],
          stage.at[slot, pl.ds(j, 1), :],
          rsem.at[slot]).start()
      return 0
    lax.fori_loop(0, chunk, start_row, 0)

    def wait_row(j, _):
      # descriptor refs only carry the byte count to decrement
      pltpu.make_async_copy(
          buf_ref.at[pl.ds(0, 1), :], stage.at[slot, pl.ds(0, 1), :],
          rsem.at[slot]).wait()
      return 0
    lax.fori_loop(0, chunk, wait_row, 0)

    def mask_row(j, _):
      idx = ids_ref[k * chunk + j]

      @pl.when(jnp.logical_or(idx < 0, idx >= rows))
      def _zero():
        stage[slot, pl.ds(j, 1), :] = jnp.zeros_like(
            stage[slot, pl.ds(j, 1), :])
      return 0
    lax.fori_loop(0, chunk, mask_row, 0)

    sends[k] = _send(slot, k)
    sends[k].start()              # chunk k ships while k+1 gathers

  for k in range(max(0, nchunks - 2), nchunks):
    sends[k].wait()


def _call_exchange(buf: jax.Array, flat_ids: jax.Array, nbr: jax.Array,
                   chunk: int, remote: bool, interpret: bool,
                   collective_id: Optional[int]) -> jax.Array:
  n = flat_ids.shape[0]
  pad = (-n) % chunk
  if pad:
    flat_ids = jnp.concatenate(
        [flat_ids, jnp.full((pad,), -1, flat_ids.dtype)])
  nchunks = (n + pad) // chunk
  kernel = functools.partial(_exchange_kernel, chunk, nchunks, remote)
  return pl.pallas_call(
      kernel,
      in_specs=[
          pl.BlockSpec(memory_space=pltpu.SMEM),   # ids
          pl.BlockSpec(memory_space=pltpu.SMEM),   # (send_to, recv_from)
          pl.BlockSpec(memory_space=pl.ANY),       # buf
      ],
      out_specs=pl.BlockSpec(memory_space=pl.ANY),
      out_shape=out_struct((n + pad, LANES), buf.dtype, flat_ids, nbr, buf),
      scratch_shapes=[
          pltpu.VMEM((2, chunk, LANES), jnp.float32),
          pltpu.SemaphoreType.DMA((2,)),
          pltpu.SemaphoreType.DMA((2,)),
          pltpu.SemaphoreType.DMA((2,)),
      ],
      compiler_params=pltpu.CompilerParams(has_side_effects=True,
                                           collective_id=collective_id),
      interpret=interpret,
      name=KERNEL_NAME,
  )(flat_ids, nbr, buf)


def _validate(buf: jax.Array, rows_per_phys: int) -> None:
  if rows_per_phys != 1:
    raise ValueError(
        f"gather kernel serves plain-row layouts (rows_per_phys == 1), "
        f"got rows_per_phys={rows_per_phys}: narrow classes' sub-row "
        "window selects belong on the VPU (packed_table.gather_fused)")
  if buf.dtype != jnp.float32:
    raise ValueError(f"buf must be float32 (got {buf.dtype}): the VMEM "
                     "send staging is f32")
  if buf.ndim != 2 or buf.shape[1] != LANES:
    raise ValueError(
        f"buf must be [rows, {LANES}] (got {buf.shape}): Mosaic rejects "
        "1-row dynamic HBM slices of memrefs wider than one 128-lane "
        "tile — the same limit as ops/pallas_apply.py")


def gather_rows(layout, buf: jax.Array, ids: jax.Array, *,
                chunk: int = 128, interpret: bool = False) -> jax.Array:
  """``gather_fused`` for rpp==1/f32/128-lane layouts, staged through the
  double-buffered send-buffer kernel (local transport).

  Semantics are identical to
  ``packed_table.gather_fused(layout, buf, ids)``: returns
  ``ids.shape + (layout.stride,)`` with all-zero rows for OOB/sentinel
  ids. The output IS the wire round's send payload — under
  ``DE_TPU_PALLAS_EXCHANGE=1`` on TPU, ``lookup_engine._fused_gather``
  routes each per-(round, chunk) gather here so the staging never makes
  an HBM round-trip between gather and collective.
  """
  _validate(buf, layout.rows_per_phys)
  flat = ids.reshape(-1).astype(jnp.int32)
  n = flat.shape[0]
  if n == 0:
    return jnp.zeros(ids.shape + (layout.stride,), buf.dtype)
  nbr = jnp.zeros((2,), jnp.int32)  # unused for local transport
  out = _call_exchange(buf, flat, nbr, chunk, remote=False,
                       interpret=interpret, collective_id=None)
  return out[:n, :layout.stride].reshape(ids.shape + (layout.stride,))


def gather_send_rows(buf: jax.Array, ids: jax.Array, send_to, recv_from,
                     *, chunk: int = 128, interpret: bool = False,
                     collective_id: int = 1) -> jax.Array:
  """One fused exchange round: gather ``buf[ids]`` and push the chunks to
  rank ``send_to`` via ``make_async_remote_copy`` while receiving the
  symmetric payload from rank ``recv_from``.

  Every rank must call this with the same static shapes and a consistent
  (send_to, recv_from) rotation — the rotate-by-k ppermute geometry of
  `parallel/wire.fused_round_perm`. Returns the ``[n, 128]`` f32 rows
  RECEIVED from ``recv_from`` (padded tail rows stripped). Real-TPU only
  (``_use_pallas_exchange``); the interpret twin models the transport as
  a loopback copy (`ops/pallas_exchange_sim.py`).
  """
  _validate(buf, 1)
  flat = ids.reshape(-1).astype(jnp.int32)
  n = flat.shape[0]
  if n == 0:
    return jnp.zeros((0, LANES), buf.dtype)
  nbr = jnp.stack([jnp.asarray(send_to, jnp.int32),
                   jnp.asarray(recv_from, jnp.int32)])
  out = _call_exchange(buf, flat, nbr, chunk, remote=True,
                       interpret=interpret, collective_id=collective_id)
  return out[:n]
