"""Mesh helpers: the TPU-native replacement for Horovod process bootstrap.

The reference initializes Horovod and derives (world_size, rank) per process
(`/root/reference/distributed_embeddings/python/layers/dist_model_parallel.py:369-372`).
On TPU the equivalent is a 1-D ``jax.sharding.Mesh`` over all devices: the
same axis carries the data-parallel batch shard AND the model-parallel table
placement (exactly like the reference, where every Horovod rank is both a dp
and an mp worker). Multi-host pods extend this mesh over ICI/DCN via
``jax.distributed`` with no code change here.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DEFAULT_AXIS = "mp"


def device_summary() -> dict:
  """The devices as JAX reports them; every entry point prints this next
  to its results so a number can never be read without its device."""
  devices = jax.devices()
  return {"platform": devices[0].platform,
          "kind": devices[0].device_kind,
          "count": len(devices)}


def require_tpu(who: str) -> dict:
  """Exit non-zero unless the default backend is a TPU.

  For programs whose result only means something on the chip (the
  benchmark, the kernel smokes): they have no CPU mode, and a run that
  found no accelerator must say so rather than continue."""
  dev = device_summary()
  if dev["platform"] != "tpu":
    raise SystemExit(f"{who}: needs a TPU backend, found {dev}; "
                     "there is no CPU mode")
  return dev


def create_mesh(world_size: Optional[int] = None,
                axis_name: str = DEFAULT_AXIS,
                devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
  """1-D hybrid-parallel mesh over ``world_size`` devices."""
  if devices is None:
    devices = jax.devices()
  if world_size is None:
    world_size = len(devices)
  if world_size > len(devices):
    raise ValueError(
        f"world_size {world_size} exceeds available devices {len(devices)}")
  return Mesh(np.asarray(devices[:world_size]), (axis_name,))


def balanced_devices(world_size: int,
                     devices: Optional[Sequence[jax.Device]] = None):
  """``world_size`` devices drawn EVENLY across processes.

  ``create_mesh(w)`` takes the first ``w`` entries of ``jax.devices()``,
  which in a multi-controller pod are all process 0's — a shrunken mesh
  built that way strands every other controller outside the computation
  and its collectives hang. This helper keeps each surviving process
  holding exactly ``world_size / process_count`` devices so a
  membership-barrier resize can shrink *in place* with every controller
  still participating. Requires ``process_count | world_size``.
  """
  if devices is None:
    devices = jax.devices()
  by_proc = {}
  for d in devices:
    by_proc.setdefault(d.process_index, []).append(d)
  procs = sorted(by_proc)
  n_proc = len(procs)
  if world_size % n_proc != 0:
    raise ValueError(
        f"world_size {world_size} not divisible by process count {n_proc}: "
        "a balanced multi-controller submesh needs the same device count "
        "on every controller")
  per = world_size // n_proc
  short = [p for p in procs if len(by_proc[p]) < per]
  if short:
    raise ValueError(
        f"processes {short} hold fewer than {per} devices; cannot build a "
        f"balanced {world_size}-device submesh")
  out = []
  for p in procs:
    out.extend(by_proc[p][:per])
  return out


def initialize_multihost(coordinator_address: Optional[str] = None,
                         num_processes: Optional[int] = None,
                         process_id: Optional[int] = None) -> Mesh:
  """Bring up the multi-host runtime and return the global 1-D mesh.

  The TPU-native replacement for the reference's ``hvd.init()`` + MPI
  launcher bootstrap: call once per host process before any jax op (on
  Cloud TPU pods the arguments are auto-detected from the environment and
  may be omitted). Afterwards ``jax.devices()`` is the global device list,
  and every train step built by this library runs unchanged — within-slice
  collectives ride ICI, cross-slice DCN, both inserted by XLA from the
  same ``PartitionSpec``s.
  """
  jax.distributed.initialize(coordinator_address=coordinator_address,
                             num_processes=num_processes,
                             process_id=process_id)
  return create_mesh()


def table_sharding(mesh: Mesh, axis_name: str = DEFAULT_AXIS) -> NamedSharding:
  """Sharding for class-stacked table params [world * rows, width]."""
  return NamedSharding(mesh, P(axis_name, None))


def batch_sharding(mesh: Mesh, axis_name: str = DEFAULT_AXIS) -> NamedSharding:
  """Sharding for data-parallel batches [global_batch, ...]."""
  return NamedSharding(mesh, P(axis_name))


def replicated(mesh: Mesh) -> NamedSharding:
  return NamedSharding(mesh, P())


def addressable_row_spans(arr: jax.Array):
  """Yield ``(row_start, row_stop, shard)`` for this process's addressable
  shards of a row-sharded 2-D array (replica 0 only, sorted by start).

  The single source of truth for local shard geometry — used by both the
  checkpoint save path and ``get_weights``'s window fetch so the two can
  never diverge on index arithmetic."""
  spans = []
  for shard in arr.addressable_shards:
    if shard.replica_id != 0:
      continue
    sl = shard.index[0]
    s0 = sl.start or 0
    s1 = sl.stop if sl.stop is not None else arr.shape[0]
    spans.append((s0, s1, shard))
  spans.sort(key=lambda t: t[0])
  return spans
