"""Blocked tensors — the TPU-native replacement for netsDB's matrix-block sets.

netsDB represents a matrix as a *set* of ``FFMatrixBlock`` objects, each
carrying ``FFMatrixMeta`` (blockRowIndex, blockColIndex, totalRows, totalCols)
plus an Eigen-mapped ``Vector<double>`` payload
(reference ``src/FF/headers/FFMatrixBlock.h:18-156``, ``FFMatrixMeta.h``,
``FFMatrixData.h``). Distributed matmul is then an equi-join on the
contraction block index plus an aggregation over block products — SUMMA on a
relational engine (``src/FF/headers/FFTransposeMult.h:38-92``,
``FFAggMatrix.h:11-30``).

On TPU the idiomatic representation is ONE logical ``jax.Array`` padded up to
a whole number of blocks; the block grid is purely *metadata* that
(a) defines the sharding granularity on a device mesh and (b) preserves the
reference's ragged-last-block semantics (``FFMatrixBlock.h:79-87``) via
explicit padding + masking rather than dynamic shapes, which XLA cannot tile
onto the MXU.

``BlockedTensor`` is a pytree, so it traces through ``jax.jit`` with the
meta as static structure.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

Shape = Tuple[int, ...]

#: the TPU's lane width: the last axis of an array is tiled in whole lanes
LANES = 128


def batch_block(rows: int, block_shape: Sequence[int], axis: int = 0) -> Shape:
    """The block of a batch of ``rows`` rows scored against a model
    blocked ``block_shape``, with the batch along ``axis``.

    The batch's block follows its own rows, rounded up to whole lanes
    (the batch is the last axis of the ``(hidden x batch)`` product)
    and never past the model's; every other axis keeps the model's
    block, so the contraction joins the stored weights' blocks
    unchanged. A batch of at least the model's block rows, and a model
    whose block is under a lane, take the model's block as it is."""
    out = [int(b) for b in block_shape]
    out[axis] = min(out[axis], max(1, -(-int(rows) // LANES)) * LANES)
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class BlockMeta:
    """Static metadata: logical (unpadded) shape + block shape.

    Equivalent of ``FFMatrixMeta`` fields totalRows/totalCols + the implicit
    block dims carried by every block's rowNums/colNums; one meta describes
    the whole tensor instead of one object per block.
    """

    shape: Shape  # logical, unpadded
    block_shape: Shape

    def __post_init__(self):
        if len(self.shape) != len(self.block_shape):
            raise ValueError(
                f"rank mismatch: shape {self.shape} vs block {self.block_shape}"
            )
        if any(b <= 0 for b in self.block_shape):
            raise ValueError(f"non-positive block shape {self.block_shape}")

    @property
    def rank(self) -> int:
        return len(self.shape)

    @property
    def grid(self) -> Shape:
        """Number of blocks along each dim (ceil-div, ragged last block padded)."""
        return tuple(-(-s // b) for s, b in zip(self.shape, self.block_shape))

    @property
    def padded_shape(self) -> Shape:
        return tuple(g * b for g, b in zip(self.grid, self.block_shape))

    @property
    def num_blocks(self) -> int:
        return int(math.prod(self.grid))

    @property
    def is_padded(self) -> bool:
        return self.padded_shape != self.shape

    def block_slice(self, index: Sequence[int]) -> Tuple[slice, ...]:
        """Slice of the padded array covered by block ``index``."""
        if len(index) != self.rank:
            raise ValueError(f"block index {index} has wrong rank for {self}")
        for i, (ix, g) in enumerate(zip(index, self.grid)):
            if not 0 <= ix < g:
                raise IndexError(f"block index {ix} out of range [0,{g}) on dim {i}")
        return tuple(
            slice(ix * b, (ix + 1) * b) for ix, b in zip(index, self.block_shape)
        )


class BlockedTensor:
    """A logical tensor stored padded-to-block, with block-grid metadata.

    ``data`` always has ``meta.padded_shape``; entries beyond ``meta.shape``
    are zero (ops that are not padding-invariant must mask — see
    ``netsdb_tpu.ops``).
    """

    def __init__(self, data: jax.Array, meta: BlockMeta):
        if tuple(data.shape) != meta.padded_shape:
            raise ValueError(
                f"data shape {tuple(data.shape)} != padded {meta.padded_shape}"
            )
        self.data = data
        self.meta = meta

    # --- construction -------------------------------------------------
    @staticmethod
    def from_dense(
        dense: Union[np.ndarray, jax.Array],
        block_shape: Shape,
        dtype: Optional[jnp.dtype] = None,
    ) -> "BlockedTensor":
        """Pad a dense array up to whole blocks (zeros in the ragged margin)."""
        dense = jnp.asarray(dense, dtype=dtype)
        meta = BlockMeta(tuple(dense.shape), tuple(block_shape))
        if meta.is_padded:
            pad = [(0, p - s) for s, p in zip(meta.shape, meta.padded_shape)]
            dense = jnp.pad(dense, pad)
        return BlockedTensor(dense, meta)

    @staticmethod
    def zeros(shape: Shape, block_shape: Shape, dtype=jnp.float32) -> "BlockedTensor":
        meta = BlockMeta(tuple(shape), tuple(block_shape))
        return BlockedTensor(jnp.zeros(meta.padded_shape, dtype=dtype), meta)

    @staticmethod
    def from_blocks(
        blocks: dict, shape: Shape, block_shape: Shape, dtype=jnp.float32
    ) -> "BlockedTensor":
        """Assemble from a {block_index: array} dict — the ingest path that
        mirrors sending a ``Vector<Handle<FFMatrixBlock>>`` (reference
        ``src/FF/headers/FFMatrixUtil.h`` load path). Ragged edge blocks may
        be passed unpadded; they are zero-padded into place."""
        meta = BlockMeta(tuple(shape), tuple(block_shape))
        out = np.zeros(meta.padded_shape, dtype=dtype)
        for index, arr in blocks.items():
            index = tuple(index)
            sl = meta.block_slice(index)
            arr = np.asarray(arr)
            dst = tuple(
                slice(s.start, s.start + d) for s, d in zip(sl, arr.shape)
            )
            out[dst] = arr
        return BlockedTensor(jnp.asarray(out), meta)

    # --- access -------------------------------------------------------
    @property
    def shape(self) -> Shape:
        return self.meta.shape

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def grid(self) -> Shape:
        return self.meta.grid

    def block(self, *index: int) -> jax.Array:
        """One padded block — analogue of pulling one ``FFMatrixBlock``."""
        return self.data[self.meta.block_slice(index)]

    def blocks(self):
        """Iterate ``(index, block)`` pairs in row-major block order."""
        for flat in range(self.meta.num_blocks):
            index, rem = [], flat
            for g in reversed(self.meta.grid):
                index.append(rem % g)
                rem //= g
            index = tuple(reversed(index))
            yield index, self.block(*index)

    def to_dense(self) -> jax.Array:
        """Strip padding back to the logical shape."""
        if not self.meta.is_padded:
            return self.data
        return self.data[tuple(slice(0, s) for s in self.meta.shape)]

    def mask(self, dtype=jnp.float32) -> jax.Array:
        """1.0 inside the logical extent, 0.0 in the padded margin."""
        m = jnp.ones((), dtype=dtype)
        for dim, (s, p) in enumerate(zip(self.meta.shape, self.meta.padded_shape)):
            idx = jnp.arange(p)
            dim_mask = (idx < s).astype(dtype)
            bshape = [1] * self.meta.rank
            bshape[dim] = p
            m = m * dim_mask.reshape(bshape)
        return jnp.broadcast_to(m, self.meta.padded_shape)

    def astype(self, dtype) -> "BlockedTensor":
        return BlockedTensor(self.data.astype(dtype), self.meta)

    def with_data(self, data: jax.Array) -> "BlockedTensor":
        return BlockedTensor(data, self.meta)

    def reblock(self, block_shape: Shape) -> "BlockedTensor":
        """Change block granularity (re-pad as needed)."""
        return BlockedTensor.from_dense(self.to_dense(), block_shape)

    def __repr__(self) -> str:
        return (
            f"BlockedTensor(shape={self.meta.shape}, block={self.meta.block_shape}, "
            f"grid={self.meta.grid}, dtype={self.dtype})"
        )


def _bt_flatten(t: BlockedTensor):
    return (t.data,), t.meta


def _bt_unflatten(meta: BlockMeta, children):
    (data,) = children
    # Inside transforms children may be tracers/None; skip shape validation.
    obj = object.__new__(BlockedTensor)
    obj.data = data
    obj.meta = meta
    return obj


jax.tree_util.register_pytree_node(BlockedTensor, _bt_flatten, _bt_unflatten)
