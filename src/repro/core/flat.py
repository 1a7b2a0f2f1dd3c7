"""Flat-state layout: pack a pytree once into contiguous (R, 128) rows.

The master hot loop views every algorithm's state as a handful of dense
f32 streams (theta, per-worker momentum, running sums).  Re-padding every
pytree leaf on every receive — what ``dana_update/ops.py`` does per call —
is pure overhead: the layout never changes between messages.  ``FlatSpec``
computes the layout ONCE at ``init`` and then packing/unpacking is a
single concatenate/split, so the whole coalesced batch can run as one
kernel over one contiguous buffer.

Layout: all leaves raveled in treedef order, concatenated, zero-padded to
a whole number of 128-lane rows (TPU lane dimension), viewed as (R, 128).
R is a multiple of 8 (TPU sublanes) and, once the state is taller than
one kernel row tile (``TILE_ROWS``), a multiple of that tile, so the flat
kernels always run full-height tiles (at most ``TILE_ROWS - 1`` rows of
padding).
Per-worker stacked state (leaves shaped (N, ...)) packs to (N, R, 128)
with the SAME per-row layout, so row r of worker i's slab and row r of
theta describe the same parameters.

Zero padding is load-bearing: every update rule in the family maps
(0, 0, ..., 0) -> 0 in the padding region (momentum of zero gradient stays
zero), so packed buffers never leak padding into real rows and norms over
flat buffers equal pytree norms.

Because the layout is row-major and every family update rule is
elementwise per row, any contiguous row range [r0, r1) of a flat buffer
is itself a self-contained shard of the state: ``row_ranges`` splits the
row space into S contiguous ranges and ``FlatSubSpec`` packs/extracts
exactly one range, which is what the row-sharded multi-master
(``repro.cluster.sharded``) builds on — concatenating the S shard slices
in range order reconstructs the single-master buffer bit-for-bit.

Two kinds of per-worker state live beside theta:

* **slabs** — (N, rows, 128) stacks sharing theta's per-row layout
  (``pack_stacked``): the momentum slab ``v`` and, for the
  delay-compensated / gap-aware family, the ``sent`` snapshot slab
  (worker i's row r describes the same parameters as theta's row r, so
  ``theta - sent[i]`` is a plain elementwise subtract and slabs shard by
  the same row ranges as theta);
* **scalar lanes** — ``ScalarLane``: one 128-lane f32 row per worker
  holding a handful of *named* scalars (staleness signals such as the
  master step a ``sent`` snapshot was taken at, or the rate-telemetry
  pair below).  Lanes have no row dimension to shard; the sharded
  master copies them whole per shard, exactly like the t / lr_prev /
  vscale scalars.

The **rate lane** (``RATE_LANE``) is the per-worker rate telemetry the
rate-weighted DANA extension (dana-hetero) keeps at the master: an EMA
of each worker's inter-push interval plus the last push timestamp.
Rates derived from it weight the per-worker momentum slabs in the
flat send path's weighted-slab reduction (``kernels/flat_update/send``)
— every shard of a row-sharded master sees every message with the same
timestamp, so the lane trajectories are replica-identical and the lane
rides the existing copied-scalar path.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LANES = 128
# the flat kernels' row tile (kernels/flat_update BLOCK_ROWS): states
# taller than one tile pad to whole tiles
TILE_ROWS = 256


class FlatSpec:
    """Layout of one pytree flattened to (rows, 128) f32.

    Built once from a template tree; ``pack``/``unpack`` are then pure
    reshape/concat/split traffic with no host-side tree walking beyond
    the (static) leaf list.  ``row_align`` (rows pad to its multiples;
    shard boundaries snap to it) defaults to 8, or to ``TILE_ROWS`` for
    a state taller than one tile.
    """

    def __init__(self, treedef, shapes, dtypes, *,
                 row_align: int | None = None):
        self.treedef = treedef
        self.shapes = tuple(tuple(s) for s in shapes)
        self.dtypes = tuple(dtypes)
        self.sizes = tuple(int(math.prod(s)) for s in self.shapes)
        self.n_elems = int(sum(self.sizes))
        rows = -(-self.n_elems // LANES)
        if row_align is None:
            row_align = TILE_ROWS if rows > TILE_ROWS else 8
        self.row_align = int(row_align)
        self.rows = -(-rows // row_align) * row_align
        self.padded = self.rows * LANES
        offs, o = [], 0
        for s in self.sizes:
            offs.append(o)
            o += s
        self.offsets = tuple(offs)

    @classmethod
    def from_tree(cls, tree, *,
                  row_align: int | None = None) -> "FlatSpec":
        leaves, treedef = jax.tree.flatten(tree)
        return cls(treedef, [l.shape for l in leaves],
                   [l.dtype for l in leaves], row_align=row_align)

    # -- pack -----------------------------------------------------------
    def pack(self, tree) -> jax.Array:
        """Pytree -> (rows, 128) f32, zero-padded.

        Cold-path reference: concatenate + pad materializes the flat
        vector twice.  The worker hot loop uses ``pack_fused``, which is
        bit-identical (tested) but writes each leaf straight into its
        ``offsets`` span of one padded buffer.
        """
        leaves = self.treedef.flatten_up_to(tree)
        flat = jnp.concatenate(
            [jnp.ravel(l).astype(jnp.float32) for l in leaves])
        return jnp.pad(flat, (0, self.padded - self.n_elems)).reshape(
            self.rows, LANES)

    def pack_fused(self, tree) -> jax.Array:
        """Pytree -> (rows, 128) f32 via leaf-offset writes (hot path).

        Each leaf is raveled and written at its precomputed ``offsets``
        span of a single zero-initialized (padded,) buffer — one output
        allocation, and inside a jit XLA turns the static-slice writes
        into in-place updates, so the backward pass can donate straight
        into the wire buffer.  The zero init doubles as the padding tail,
        preserving the zero-padding invariant ``pack`` gets from
        ``jnp.pad``.  Bit-identical to ``pack`` by construction: same
        values, same placement, same f32 cast.
        """
        buf = jnp.zeros((self.padded,), jnp.float32)
        for leaf, o, s in zip(self.treedef.flatten_up_to(tree),
                              self.offsets, self.sizes):
            buf = buf.at[o:o + s].set(jnp.ravel(leaf).astype(jnp.float32))
        return buf.reshape(self.rows, LANES)

    def pack_stacked(self, tree) -> jax.Array:
        """Pytree of (N, ...) leaves -> (N, rows, 128) f32."""
        leaves = self.treedef.flatten_up_to(tree)
        n = leaves[0].shape[0]
        flat = jnp.concatenate(
            [l.reshape(n, -1).astype(jnp.float32) for l in leaves], axis=1)
        return jnp.pad(flat, ((0, 0), (0, self.padded - self.n_elems))) \
            .reshape(n, self.rows, LANES)

    # -- unpack ---------------------------------------------------------
    def unpack(self, buf: jax.Array):
        """(rows, 128) -> pytree (original shapes/dtypes, padding dropped)."""
        flat = buf.reshape(-1)
        leaves = [
            flat[o:o + s].reshape(shape).astype(dt)
            for o, s, shape, dt in zip(self.offsets, self.sizes,
                                       self.shapes, self.dtypes)
        ]
        return jax.tree.unflatten(self.treedef, leaves)

    def unpack_stacked(self, buf: jax.Array):
        """(N, rows, 128) -> pytree of (N, ...) leaves."""
        n = buf.shape[0]
        flat = buf.reshape(n, -1)
        leaves = [
            flat[:, o:o + s].reshape((n,) + shape).astype(dt)
            for o, s, shape, dt in zip(self.offsets, self.sizes,
                                       self.shapes, self.dtypes)
        ]
        return jax.tree.unflatten(self.treedef, leaves)

    # -- row sharding ----------------------------------------------------
    def row_ranges(self, shards: int) -> tuple[tuple[int, int], ...]:
        """Split [0, rows) into ``shards`` contiguous non-empty ranges.

        Boundaries are snapped down to ``row_align`` multiples when that
        keeps every range non-empty (TPU sublane alignment); tiny states
        fall back to plain even row splits so S <= rows always works.
        Concatenating the ranges in order always covers [0, rows) exactly.
        """
        if not 1 <= shards <= self.rows:
            raise ValueError(
                f"need 1 <= shards <= rows={self.rows}, got {shards}")
        bounds = [round(s * self.rows / shards) for s in range(shards + 1)]
        for s in range(1, shards):
            snapped = (bounds[s] // self.row_align) * self.row_align
            if bounds[s - 1] < snapped:
                bounds[s] = snapped
        return tuple((bounds[s], bounds[s + 1]) for s in range(shards))

    def subspec(self, r0: int, r1: int) -> "FlatSubSpec":
        return FlatSubSpec(self, r0, r1)

    def concat_rows(self, pieces) -> jax.Array:
        """Reassemble range-ordered shard slices into one full buffer
        ((rows, 128) or (N, rows, 128) pieces; inverse of per-shard
        ``FlatSubSpec.take``)."""
        return jnp.concatenate(list(pieces), axis=-2)


class ScalarLane:
    """Named per-worker scalars packed as one (N, 128) f32 row per worker.

    Slot j of worker i's lane row holds the scalar named ``names[j]``;
    lanes beyond ``len(names)`` are zero (the flat zero-padding
    invariant, so lane norms equal the packed columns' norms).  The lane
    is deliberately NOT part of the row space: every shard of a
    row-sharded master carries a full copy (all shards see every message,
    so their lane trajectories are identical — like vscale / t).
    """

    def __init__(self, names):
        names = tuple(names)
        if not 0 < len(names) <= LANES:
            raise ValueError(f"need 1..{LANES} scalar names, "
                             f"got {len(names)}")
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate scalar names in {names}")
        self.names = names
        self.index = {n: j for j, n in enumerate(names)}

    def init(self, num_workers: int, **values) -> jax.Array:
        """Zeroed (N, 128) lane; ``values`` seeds named columns with a
        scalar or an (N,) vector."""
        lane = jnp.zeros((num_workers, LANES), jnp.float32)
        for name, v in values.items():
            lane = lane.at[:, self.index[name]].set(
                jnp.asarray(v, jnp.float32))
        return lane

    def pack(self, cols: dict) -> jax.Array:
        """{name: (N,) array} -> (N, 128) f32 lane (zero-padded)."""
        n = next(iter(cols.values())).shape[0]
        return self.init(n, **cols)

    def unpack(self, lane: jax.Array) -> dict:
        """(N, 128) lane -> {name: (N,) f32 column}."""
        return {n: lane[:, j] for j, n in enumerate(self.names)}

    def get(self, lane: jax.Array, name: str) -> jax.Array:
        return lane[:, self.index[name]]

    def set_at(self, lane: jax.Array, name: str, i, value) -> jax.Array:
        """Lane with worker i's ``name`` slot <- value (dynamic i ok)."""
        return lane.at[i, self.index[name]].set(
            jnp.asarray(value, jnp.float32))


# rate-telemetry slots (dana-hetero): EMA of worker i's inter-push
# interval, and the timestamp of its last push.  Column extraction /
# point updates mirror the pytree algorithm's (N,) ``interval`` /
# ``last_t`` vectors bit-for-bit (both are plain f32).
RATE_INTERVAL = "interval"
RATE_LAST_T = "last_t"
RATE_LANE = ScalarLane((RATE_INTERVAL, RATE_LAST_T))


class FlatSubSpec:
    """One contiguous row range [r0, r1) of a ``FlatSpec`` layout.

    ``take``/``put`` slice the range out of / back into a full flat
    buffer — ``take`` is the sharded runtime's scatter step (workers
    pack the full gradient once, then take each shard's rows inside the
    same jit, where XLA fuses the slices for free).  ``pack`` builds the
    range's rows directly from a pytree without materializing the full
    buffer — bit-identical to ``spec.pack(tree)[r0:r1]`` (tested); it
    exists for callers that hold only this range (per-shard checkpoint
    restore / streaming packing), not the worker hot path.
    """

    def __init__(self, spec: FlatSpec, r0: int, r1: int):
        if not 0 <= r0 < r1 <= spec.rows:
            raise ValueError(f"bad row range [{r0}, {r1}) for "
                             f"rows={spec.rows}")
        self.spec = spec
        self.r0, self.r1 = int(r0), int(r1)
        self.rows = self.r1 - self.r0
        # element span of this range within the concatenated flat vector
        self.e0 = self.r0 * LANES
        self.e1 = min(self.r1 * LANES, spec.n_elems)

    # -- slicing a full buffer ------------------------------------------
    def take(self, buf: jax.Array) -> jax.Array:
        """(.., rows, 128) -> (.., r1-r0, 128): this range's rows."""
        return buf[..., self.r0:self.r1, :]

    def put(self, buf: jax.Array, piece: jax.Array) -> jax.Array:
        """Write this range's rows back into a full buffer."""
        return buf.at[..., self.r0:self.r1, :].set(piece)

    # -- packing just this range ----------------------------------------
    def pack(self, tree) -> jax.Array:
        """Pytree -> only this range's (r1-r0, 128) rows."""
        leaves = self.spec.treedef.flatten_up_to(tree)
        parts = []
        for leaf, o, s in zip(leaves, self.spec.offsets, self.spec.sizes):
            lo, hi = max(self.e0 - o, 0), min(self.e1 - o, s)
            if lo < hi:
                parts.append(jnp.ravel(leaf)[lo:hi].astype(jnp.float32))
        flat = (jnp.concatenate(parts) if parts
                else jnp.zeros((0,), jnp.float32))
        pad = self.rows * LANES - flat.shape[0]
        return jnp.pad(flat, (0, pad)).reshape(self.rows, LANES)
