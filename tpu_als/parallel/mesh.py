"""Device mesh helpers — the substrate for the sharded trainer.

The reference stack's scale-out substrate is Spark's cluster runtime
(executors + netty RPC + sort shuffle, SURVEY.md §2.B8/§2.C2).  Here the
substrate is a 1-D ``jax.sharding.Mesh`` with a single ``"d"`` axis: user
factors, item factors, and rating shards are all partitioned along it, and
each ALS half-step either all-gathers the opposite factor shard, streams it
around a ``ppermute`` ring, or exchanges referenced rows with
``all_to_all`` (tpu_als.parallel.{trainer,comm,a2a}).

Multi-slice (DCN) awareness: on a multi-slice deployment the devices of one
slice share ICI while slices talk over the much slower data-center network.
All three gather strategies move data between *neighboring* positions of
the 1-D axis (a ring permute, or the segment layout of an all_gather), so
the whole DCN story reduces to **device order**: :func:`make_mesh` orders
devices slice-major (all of slice 0, then slice 1, …), which makes ring
neighbors ICI-local with exactly one DCN hop per slice boundary and lets
XLA schedule the intra-slice part of each collective on ICI.  This mirrors the
scaling-book recipe: pick the mesh so collectives ride ICI, not DCN.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: F401

AXIS = "d"

# one definition — trainer, serve and the kernel probes alias this
shard_map = jax.shard_map


def _default_slice_of(device):
    """The platform's slice assignment: ``device.slice_index`` on
    multi-slice TPU deployments, None elsewhere (single slice, CPU)."""
    return getattr(device, "slice_index", None)


def simulated_slice_of(n_slices, all_devices=None):
    """A ``slice_of`` callable that partitions ``all_devices`` (default:
    ``jax.devices()``) into ``n_slices`` equal contiguous-by-id groups.

    CPU devices carry no ``slice_index``, so the multi-slice code path —
    slice-major ordering, boundary accounting, collectives whose device
    order crosses a slice boundary — could otherwise never be exercised
    without pod hardware.  Tests and the driver dryrun pass this to
    :func:`make_mesh` to pin that path on the forced-host-device CPU
    backend (SURVEY.md §5.8 "DCN across slices").
    """
    devices = sorted(all_devices or jax.devices(), key=lambda d: d.id)
    per = max(1, (len(devices) + n_slices - 1) // n_slices)
    assignment = {d.id: k // per for k, d in enumerate(devices)}
    return lambda d: assignment[d.id]


def order_devices_slice_major(devices, slice_of=None):
    """Sort devices so same-slice devices are contiguous.

    ``slice_of`` maps a device to its slice index; the default reads
    ``device.slice_index`` where the platform exposes it (multi-slice
    TPU deployments; single-slice and CPU devices don't have it and keep
    their given order).  The sort is stable on the slice index alone, so
    a caller-chosen intra-slice order (e.g. a custom ring) is preserved.
    """
    slice_of = slice_of or _default_slice_of
    devices = list(devices)
    if any(slice_of(d) is not None for d in devices):
        devices.sort(key=lambda d: slice_of(d) or 0)
    return devices


def make_mesh(n_devices=None, devices=None, axis=AXIS, slice_of=None):
    """1-D mesh over ``n_devices`` (default: all) devices, slice-major
    ordered.  Ordering happens BEFORE truncation, so asking for one slice's
    worth of devices on a multi-slice deployment yields ICI-connected
    devices of the first slice, not an interleaved sample crossing DCN.
    ``slice_of`` overrides the platform slice assignment (see
    :func:`simulated_slice_of`)."""
    if devices is None:
        devices = order_devices_slice_major(jax.devices(), slice_of)
        if n_devices is not None:
            if n_devices > len(devices):
                # fixed at depth (advisor r4): every caller — CLI train,
                # CLI recommend, library users — must get an error, not
                # a silently smaller mesh than requested
                raise ValueError(
                    f"requested a {n_devices}-device mesh but only "
                    f"{len(devices)} devices are visible; refusing to "
                    "build a silently smaller mesh")
            devices = devices[:n_devices]
    else:
        devices = order_devices_slice_major(devices, slice_of)
    return Mesh(np.asarray(devices), (axis,))


def slice_boundaries(devices, slice_of=None):
    """Positions in the 1-D (slice-major) order where a DCN hop occurs —
    observability helper for the ring strategy's cost model: bytes moved
    over DCN per iteration = boundary_count × shard_bytes."""
    slice_of = slice_of or _default_slice_of
    devices = order_devices_slice_major(devices, slice_of)
    slices = [slice_of(d) or 0 for d in devices]
    return [k for k in range(1, len(slices)) if slices[k] != slices[k - 1]]


def shard_leading(mesh, axis=AXIS):
    """NamedSharding that splits the leading array axis over the mesh."""
    return NamedSharding(mesh, P(axis))
