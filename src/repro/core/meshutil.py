"""Mesh-aware sharding helpers, usable from any layer.

Two families live here:

* GSPMD annotation (`maybe_constrain`): soft sharding hints that XLA may
  honor; the same code runs unsharded on a laptop.
* The explicit client mesh (`client_mesh`, `psum_scatter_mod`,
  `all_gather_clients`, `all_to_all_clients`): the shard_map substrate of the
  distributed COPML engine (protocol.Copml.train_sharded), where the client
  axis of every share array is physically split over a 1-D ("clients",) mesh
  and the protocol's EXCHANGE/OPEN steps are real collectives.

The mod-p reductions exploit that field elements are canonical in [0, p):
a raw int32 psum of D partial sums stays below D * p < 2^31 for D <= 31,
so one fold26 after the collective restores the canonical representative --
bit-identical to computing the same contraction on one device.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def make_mesh(shape, axes):
    """jax.make_mesh with every axis Auto (GSPMD-annotated, not explicit
    sharding), the mode `maybe_constrain`'s hints are written for."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def maybe_constrain(x, *spec):
    """with_sharding_constraint iff a usable mesh is active (jax.set_mesh).

    Axes absent from the mesh or not dividing the dim are dropped, so the
    same code runs on a laptop and on the 512-chip production mesh."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not mesh.shape:
        return x
    fixed = []
    for dim, entry in zip(x.shape, spec + (None,) * (x.ndim - len(spec))):
        if entry is None:
            fixed.append(None)
            continue
        entries = entry if isinstance(entry, tuple) else (entry,)
        kept = tuple(a for a in entries if a in mesh.shape)
        size = 1
        for a in kept:
            size *= mesh.shape[a]
        if not kept or dim % size:
            fixed.append(None)
        else:
            fixed.append(kept if len(kept) > 1 else kept[0])
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(x, P(*fixed))


CLIENTS = ("clients", "pod", "data", "model")   # COPML client axis spans the mesh

# name of the 1-D mesh axis the distributed engine shards clients over
CLIENT_AXIS = "clients"

# raw int32 psum of canonical field elements must not wrap: D * (p-1) < 2^31.
# Wider meshes switch to the two-limb reduction (see _reduce_mod), exact for
# any realistic shard count.
NARROW_SHARDS = 31


def client_mesh(n_devices: int | None = None, devices=None):
    """1-D ("clients",) mesh over (a prefix of) the host's devices.

    This is the mesh Copml.train_sharded runs on; on a CPU host expose
    multiple devices with XLA_FLAGS=--xla_force_host_platform_device_count=8
    (set BEFORE the first jax import).  Unlike make_mesh this accepts a
    device subset, so one 8-device process can build 4- and 8-way meshes.
    """
    devs = list(jax.devices()) if devices is None else list(devices)
    if n_devices is not None:
        assert n_devices <= len(devs), (n_devices, len(devs))
        devs = devs[:n_devices]
    return jax.sharding.Mesh(np.array(devs), (CLIENT_AXIS,))


def _reduce_mod(x, nshards, reducer):
    """Exact mod-p cross-shard reduction of canonical field elements.

    nshards <= NARROW_SHARDS: one raw int32 reduction (sum < D*p < 2^31),
    one fold26.  Wider: reduce the 13-bit halves separately (sums < D*2^13,
    safe to D = 2^17) and recombine with field ops -- two collectives, still
    the same canonical value because everything is mod-p linear.
    """
    from . import field
    if nshards <= NARROW_SHARDS:
        return field.fold26(reducer(x))
    lo = jnp.bitwise_and(x, (1 << 13) - 1)
    hi = jax.lax.shift_right_logical(x, 13)
    return field.add(field.mul_scalar(field.fold26(reducer(hi)), 1 << 13),
                     field.fold26(reducer(lo)))


def psum_scatter_mod(x, axis_name: str = CLIENT_AXIS,
                     nshards: int | None = None):
    """Mod-p reduce-scatter over the leading axis (must divide evenly)."""
    return _reduce_mod(x, nshards or NARROW_SHARDS + 1,
                       lambda v: jax.lax.psum_scatter(
                           v, axis_name, scatter_dimension=0, tiled=True))


def all_gather_clients(x, axis_name: str = CLIENT_AXIS):
    """Concatenate every shard's leading axis in device order (OPEN step)."""
    return jax.lax.all_gather(x, axis_name, axis=0, tiled=True)


def all_to_all_clients(x, axis_name: str = CLIENT_AXIS):
    """Owner<->holder transpose (EXCHANGE step): split the leading (holder)
    axis across shards, concatenate the received blocks on axis 1 (owner).
    (n_pad, n_loc, ...) per shard -> (n_loc, n_pad, ...) per shard."""
    return jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=1,
                              tiled=True)


# --------------------------------------------------------------------------
# Ring (ppermute-chained) forms of the two EXCHANGE collectives.
#
# Monolithic psum_scatter / all_to_all force the WHOLE local contraction to
# finish before any byte moves.  The ring forms take a `segment_fn(j)` /
# `block_fn(j)` producing only shard j's slice of the local result, so each
# hop's operand is computed just before its ppermute -- the GEMM for
# segment j+1 has no data dependence on hop j and XLA is free to overlap
# compute with the in-flight transfer.  Both are bit-exact with their
# monolithic twins: segment values are the same canonical field elements
# (a row slice of a matmul is the same contraction), the ring's raw int32
# accumulation is the same no-overflow integer sum in a different order,
# and the single trailing fold26 matches _reduce_mod's narrow path.


def ring_reduce_scatter_mod(segment_fn, axis_name: str, ndev: int):
    """Mod-p reduce-scatter as a D-1 hop ring; shard r ends with
    fold26(sum_s segment_fn_of_shard_s(r)).

    segment_fn(j) -> this shard's canonical-field partial destined for
    shard j (j traced).  Requires ndev <= NARROW_SHARDS (raw int32 sum of D
    canonical elements must not wrap); callers fall back to
    psum_scatter_mod beyond that.
    """
    from . import field
    assert ndev <= NARROW_SHARDS, ndev
    r = jax.lax.axis_index(axis_name)
    if ndev == 1:
        return field.fold26(segment_fn(r))
    perm = [(i, (i + 1) % ndev) for i in range(ndev)]
    # shard r's chunk travels the whole ring: start with the partial for
    # destination r-1 (which r sends first), finish holding destination r
    acc = segment_fn((r + ndev - 1) % ndev)
    for k in range(ndev - 1):
        acc = jax.lax.ppermute(acc, axis_name, perm)
        acc = acc + segment_fn((r + ndev - k - 2) % ndev)
    return field.fold26(acc)


def ring_all_to_all(block_fn, axis_name: str, ndev: int):
    """Owner<->holder transpose as D-1 ppermute hops; bit-exact with
    all_to_all_clients applied to the stacked blocks.

    block_fn(j) -> this shard's (n_loc, ...) block destined for shard j
    (j traced), i.e. rows j*n_loc..(j+1)*n_loc of the monolithic operand.
    Each block is computed just before its hop.  Returns the received
    blocks stacked on a NEW leading axis in SOURCE-shard order (shard s's
    block at index s) -- shape (ndev, n_loc, ...).
    """
    r = jax.lax.axis_index(axis_name)
    received = [block_fn(r)]                      # own block, k = 0
    for k in range(1, ndev):
        perm = [(i, (i + k) % ndev) for i in range(ndev)]
        received.append(jax.lax.ppermute(block_fn((r + k) % ndev),
                                         axis_name, perm))
    stacked = jnp.stack(received)                 # index k <- shard (r-k)%D
    # reorder k-major to source-shard-major: source s sits at k = (r-s)%D
    return jnp.take(stacked, (r - jnp.arange(ndev)) % ndev, axis=0)
