"""Columnar Page data model, TPU-first.

The reference's unit of data is an immutable columnar ``Page`` of ``Block``s
(core/trino-spi .../spi/Page.java:31, spi/block/Block.java:21).  The TPU re-design keeps the
columnar batch but makes every buffer a *fixed-capacity* device array so XLA traces one program
per shape class:

- a column is a dense jnp array of ``capacity`` elements (struct-of-arrays);
- partially-filled / filtered pages carry a boolean ``valid`` row mask instead of being
  compacted (the reference's SelectedPositions, operator/project/SelectedPositions.java,
  becomes a mask — masks fuse into downstream kernels for free, compaction would be a
  data-dependent shape);
- NULLs are per-column boolean masks (reference: Block#isNull / null flags in every Block impl);
- VARCHAR columns hold int32 dictionary ids; the dictionary itself is host-side metadata owned
  by the connector/catalog, NOT part of the device page (reference: DictionaryBlock,
  spi/block/DictionaryBlock.java — here made the primary representation).

Pages are jax pytrees, so whole operator pipelines over pages jit-compile.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .types import Type

__all__ = ["Field", "Schema", "Page", "pad_to_capacity"]


@dataclasses.dataclass(frozen=True)
class Field:
    name: str
    type: Type


@dataclasses.dataclass(frozen=True)
class Schema:
    """Static (hashable) description of a page's columns; jit aux data."""

    fields: tuple[Field, ...]

    def __post_init__(self):
        object.__setattr__(self, "_index", {f.name: i for i, f in enumerate(self.fields)})

    @staticmethod
    def of(*pairs) -> "Schema":
        return Schema(tuple(Field(n, t) for n, t in pairs))

    def index(self, name: str) -> int:
        return self._index[name]

    def field(self, name: str) -> Field:
        return self.fields[self.index(name)]

    @property
    def names(self) -> tuple[str, ...]:
        return tuple(f.name for f in self.fields)

    @property
    def types(self) -> tuple[Type, ...]:
        return tuple(f.type for f in self.fields)

    def __len__(self) -> int:
        return len(self.fields)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Page:
    """A fixed-capacity columnar batch of rows on device.

    ``columns[i]`` is a jnp array of shape ``(capacity,)`` (dtype per ``schema``);
    ``null_masks[i]`` is an optional bool array (True = NULL); ``valid`` is an optional
    bool row mask (None = all ``capacity`` rows are live).  ``live`` is an optional
    HOST int: the page is packed (its live rows are its first ``live`` lanes) and
    whoever made it already knows the count, so a consumer neither pulls nor
    reduces for it.  It is no part of the pytree: a page that crosses a jit
    boundary forgets it.
    """

    schema: Schema
    columns: tuple
    null_masks: tuple
    valid: Optional[jnp.ndarray] = None
    live: Optional[int] = None

    # -- pytree protocol --------------------------------------------------------
    def tree_flatten(self):
        children = (self.columns, self.null_masks, self.valid)
        return children, self.schema

    @classmethod
    def tree_unflatten(cls, schema, children):
        columns, null_masks, valid = children
        return cls(schema, columns, null_masks, valid)

    # -- constructors -----------------------------------------------------------
    @staticmethod
    def from_arrays(schema: Schema, arrays: Sequence, valid=None, null_masks=None) -> "Page":
        cols = tuple(jnp.asarray(a, dtype=f.type.dtype) for a, f in zip(arrays, schema.fields))
        if null_masks is None:
            null_masks = tuple(None for _ in cols)
        return Page(schema, cols, tuple(null_masks), valid)

    # -- accessors --------------------------------------------------------------
    @property
    def capacity(self) -> int:
        return int(self.columns[0].shape[0]) if self.columns else 0

    def column(self, name: str):
        return self.columns[self.schema.index(name)]

    def null_mask(self, name: str):
        return self.null_masks[self.schema.index(name)]

    def num_rows(self):
        """Traced count of live rows."""
        if self.valid is None:
            return jnp.asarray(self.capacity, jnp.int32)
        return jnp.sum(self.valid, dtype=jnp.int32)

    def valid_mask(self):
        if self.valid is None:
            return jnp.ones((self.capacity,), dtype=bool)
        return self.valid

    def with_valid(self, valid) -> "Page":
        return Page(self.schema, self.columns, self.null_masks, valid)

    def select(self, names: Sequence[str]) -> "Page":
        idx = [self.schema.index(n) for n in names]
        return Page(
            Schema(tuple(self.schema.fields[i] for i in idx)),
            tuple(self.columns[i] for i in idx),
            tuple(self.null_masks[i] for i in idx),
            self.valid,
        )

    # -- host materialization (tests / client results) --------------------------
    def to_numpy(self, dictionaries: Optional[dict] = None) -> dict:
        """Materialize live rows to host numpy arrays (decoding dictionary ids and
        decimal scaling when ``dictionaries``/types say so).  Host-side only."""
        from .types import DecimalType, VarcharType, CharType

        valid = np.asarray(self.valid_mask())
        out = {}
        for f, col, nulls in zip(self.schema.fields, self.columns, self.null_masks):
            arr = np.asarray(col)[valid]
            if isinstance(f.type, DecimalType):
                arr = arr.astype(np.float64) / (10**f.type.scale)
            elif isinstance(f.type, (VarcharType, CharType)) and dictionaries and f.name in dictionaries:
                d = dictionaries[f.name]
                arr = d.decode(arr) if hasattr(d, "decode") else np.asarray(d)[arr]
            elif f.type.name == "date":
                # decode epoch days like the engine's result surface, so
                # pandas oracles built from pages compare like-for-like
                arr = arr.astype("datetime64[D]")
            if nulls is not None:
                n = np.asarray(nulls)[valid]
                arr = np.where(n, None, arr) if arr.dtype == object else np.ma.masked_array(arr, n)
            out[f.name] = arr
        return out


def pad_to_capacity(arr: np.ndarray, capacity: int):
    """Host-side helper: pad a length-n array to ``capacity`` and return (padded, valid)."""
    n = len(arr)
    if n > capacity:
        raise ValueError(f"array of {n} rows exceeds capacity {capacity}")
    padded = np.zeros((capacity,), dtype=arr.dtype)
    padded[:n] = arr
    valid = np.zeros((capacity,), dtype=bool)
    valid[:n] = True
    return padded, valid
