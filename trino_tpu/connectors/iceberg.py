"""Iceberg connector: hadoop-table-layout metadata over the Parquet device path.

Reference: plugin/trino-iceberg — table metadata JSON resolution
(IcebergUtil/TableMetadataParser analogs), snapshot -> manifest list ->
manifests -> data-file splits (IcebergSplitSource), per-file min/max bound
pruning (IcebergMetadata.java:466's constraint pushdown narrowed to split
pruning), all over the existing Parquet decode machinery
(connectors/parquet.py — dictionary-id decode, buffer decimals, row-group
statistics).

No catalog service: tables live as ``<warehouse>/<table>/metadata/*.json`` +
avro manifests (the hadoop-table layout), read with the in-tree Avro
container reader (formats/avro.py).  Reads only — writes go through the
engine's Parquet CTAS path.
"""

from __future__ import annotations

import glob
import json
import os
import struct
from dataclasses import dataclass

from ..page import Field, Schema
from ..types import (BIGINT, BOOLEAN, DATE, DOUBLE, INTEGER, REAL, TIMESTAMP,
                     DecimalType, VarcharType)
from .parquet import ParquetConnector

__all__ = ["IcebergConnector", "IcebergSplit"]


@dataclass(frozen=True)
class IcebergSplit:
    table: str
    file_index: int
    row_group: int


@dataclass
class _DataFile:
    path: str
    pseudo: str  # delegate table name inside the ParquetConnector
    record_count: int
    lower: dict  # field name -> raw python bound
    upper: dict


@dataclass
class _IcebergTable:
    schema: Schema
    files: list  # _DataFile
    n_rows: int


def _iceberg_type(t) -> object:
    if isinstance(t, dict):
        # struct/list/map values are not yet scannable columns
        raise NotImplementedError(f"iceberg nested type {t.get('type')!r}")
    if t.startswith("decimal("):
        p, s = t[len("decimal("):-1].split(",")
        return DecimalType.of(int(p), int(s))
    base = {"boolean": BOOLEAN, "int": INTEGER, "long": BIGINT,
            "float": REAL, "double": DOUBLE, "date": DATE,
            "string": VarcharType.of(None), "uuid": VarcharType.of(None)}
    if t in base:
        return base[t]
    if t.startswith("timestamp"):
        return TIMESTAMP
    raise NotImplementedError(f"iceberg type {t!r}")


def _decode_bound(ty, raw: bytes):
    """Iceberg single-value binary serialization -> python scalar
    (spec: Appendix D single-value serialization; ints/floats little-endian,
    decimals unscaled big-endian two's-complement, dates as int days)."""
    if raw is None:
        return None
    raw = bytes(raw)
    try:
        if isinstance(ty, DecimalType):
            return int.from_bytes(raw, "big", signed=True)
        if ty.name in ("integer", "date"):
            return struct.unpack("<i", raw)[0]
        if ty.name in ("bigint", "timestamp(6)"):
            return struct.unpack("<q", raw)[0]
        if ty.name == "real":
            return struct.unpack("<f", raw)[0]
        if ty.name == "double":
            return struct.unpack("<d", raw)[0]
    except struct.error:
        return None
    return None  # strings/bools: not used for range pruning


class IcebergConnector:

    CACHEABLE_SCANS = True  # file pages are immutable between DDL;
    # the buffer pool keeps decoded columns device-resident across queries
    name = "iceberg"
    HOST_DECODE = True  # pages decode on the host: scans benefit from
    # background-thread split prefetch (see exec/boundary._prefetched_pages)

    def __init__(self, warehouse: str):
        self.warehouse = warehouse
        self._tables: dict = {}
        self._pq = ParquetConnector(directory=warehouse)

    # -- metadata resolution -----------------------------------------------------
    def tables(self):
        out = []
        if os.path.isdir(self.warehouse):
            for d in sorted(os.listdir(self.warehouse)):
                if os.path.isdir(os.path.join(self.warehouse, d, "metadata")):
                    out.append(d)
        return out

    def _resolve(self, table_dir: str, path: str) -> str:
        """Manifest/data paths may be absolute URIs from the writing engine;
        re-root them under the table directory (the hadoop layout keeps
        everything inside it)."""
        p = path
        if p.startswith("file://"):
            p = p[len("file://"):]
        if os.path.exists(p):
            return p
        # re-root: find the table dir's basename inside the recorded path
        marker = "/" + os.path.basename(table_dir.rstrip("/")) + "/"
        if marker in p:
            return os.path.join(table_dir, p.split(marker, 1)[1])
        return os.path.join(table_dir, os.path.basename(p))

    def _load(self, table: str) -> _IcebergTable:
        t = self._tables.get(table)
        if t is not None:
            return t
        from ..formats.avro import read_container

        table_dir = os.path.join(self.warehouse, table)
        meta_dir = os.path.join(table_dir, "metadata")
        hint = os.path.join(meta_dir, "version-hint.text")
        if os.path.exists(hint):
            with open(hint) as f:
                v = f.read().strip()
            meta_path = os.path.join(meta_dir, f"v{v}.metadata.json")
        else:
            candidates = sorted(glob.glob(os.path.join(meta_dir,
                                                       "*.metadata.json")))
            if not candidates:
                raise FileNotFoundError(f"no iceberg metadata in {meta_dir}")
            meta_path = candidates[-1]
        with open(meta_path) as f:
            meta = json.load(f)

        # schema: current-schema-id among "schemas", or the legacy "schema"
        schema_json = meta.get("schema")
        if schema_json is None:
            sid = meta.get("current-schema-id", 0)
            schema_json = next(s for s in meta["schemas"]
                               if s.get("schema-id", 0) == sid)
        fields, by_id = [], {}
        for f_json in schema_json["fields"]:
            try:
                ty = _iceberg_type(f_json["type"])
            except NotImplementedError:
                continue  # unsupported column types are simply not exposed
            fields.append(Field(f_json["name"], ty))
            by_id[f_json["id"]] = (f_json["name"], ty)
        schema = Schema(tuple(fields))

        # current snapshot -> manifest list -> manifests -> data files
        files: list = []
        snap_id = meta.get("current-snapshot-id")
        snap = next((s for s in meta.get("snapshots", ())
                     if s["snapshot-id"] == snap_id), None)
        if snap is not None:
            if "manifest-list" in snap:
                mlist_path = self._resolve(table_dir, snap["manifest-list"])
                manifests, _ = read_container(mlist_path)
                manifest_paths = [m["manifest_path"] for m in manifests]
            else:
                # legacy v1 snapshots may inline the manifest paths directly
                manifest_paths = list(snap.get("manifests", ()))
            for mp in manifest_paths:
                mpath = self._resolve(table_dir, mp)
                entries, _ = read_container(mpath)
                for e in entries:
                    if e.get("status") == 2:  # DELETED
                        continue
                    df = e["data_file"]
                    if df.get("content", 0) not in (0, None):
                        continue  # position/equality deletes unsupported
                    fpath = self._resolve(table_dir, df["file_path"])
                    lower = self._bounds(df.get("lower_bounds"), by_id)
                    upper = self._bounds(df.get("upper_bounds"), by_id)
                    idx = len(files)
                    pseudo = f"{table}#ice{idx}"
                    self._pq._paths[pseudo] = fpath
                    files.append(_DataFile(fpath, pseudo,
                                           int(df["record_count"]),
                                           lower, upper))

        t = _IcebergTable(schema, files, sum(f.record_count for f in files))
        self._unify_dictionaries(t)
        self._tables[table] = t
        return t

    def _bounds(self, raw, by_id) -> dict:
        """lower/upper bounds arrive as a field-id map — either an avro map
        with stringified keys or the k/v-record array form — decode per the
        column's type."""
        out = {}
        if raw is None:
            return out
        items = raw.items() if isinstance(raw, dict) else (
            (kv["key"], kv["value"]) for kv in raw)
        for k, v in items:
            info = by_id.get(int(k))
            if info is None:
                continue
            name, ty = info
            b = _decode_bound(ty, v)
            if b is not None:
                out[name] = b
        return out

    def _unify_dictionaries(self, t: _IcebergTable) -> None:
        """String ids must be stable across every data file of the table:
        merge the per-file dictionaries into one table-wide mapping and
        install it on each delegate file (the decode path then remaps each
        row group's local dictionary through it)."""
        import numpy as np

        string_cols = [f.name for f in t.schema.fields if f.type.is_string]
        if not string_cols or not t.files:
            return
        from .tpch import Dictionary

        values: dict = {c: set() for c in string_cols}
        opened = [self._pq._open(f.pseudo) for f in t.files]
        for pt in opened:
            for c in string_cols:
                d = pt.dicts.get(c)
                if d is not None:
                    values[c].update(d.values.tolist())
        for c in string_cols:
            uniq = sorted(values[c])
            gd = Dictionary(values=np.array(uniq or [""], dtype=object))
            id_map = {v: i for i, v in enumerate(uniq)}
            for pt in opened:
                pt.dicts[c] = gd
                pt.id_maps[c] = id_map

    # -- connector protocol ------------------------------------------------------
    def schema(self, table: str) -> Schema:
        return self._load(table).schema

    def dictionaries(self, table: str) -> dict:
        t = self._load(table)
        if not t.files:
            return {}
        return dict(self._pq._open(t.files[0].pseudo).dicts)

    def row_count(self, table: str) -> int:
        return self._load(table).n_rows

    def column_range(self, table: str, column: str):
        """Table-wide min/max from the manifests' per-file bounds (CBO +
        direct-index sizing)."""
        t = self._load(table)
        los = [f.lower[column] for f in t.files if column in f.lower]
        his = [f.upper[column] for f in t.files if column in f.upper]
        if len(los) == len(t.files) and len(his) == len(t.files) and t.files:
            return (min(los), max(his))
        return (None, None)

    def splits(self, table: str, n_hint: int = 0):
        t = self._load(table)
        out = []
        for i, f in enumerate(t.files):
            pt = self._pq._open(f.pseudo)
            for rg in range(pt.n_row_groups):
                out.append(IcebergSplit(table, i, rg))
        return out

    def split_range(self, split: IcebergSplit, column: str):
        """Row-group statistics when present, else the manifest's FILE-level
        bounds — both feed the same tuple-domain split pruning.  (Pruning
        saves row-group DECODE; footers and string dictionaries were already
        read once at table load to build stable ids — see
        _unify_dictionaries.)"""
        from .parquet import ParquetSplit

        t = self._load(split.table)
        f = t.files[split.file_index]
        rg = self._pq.split_range(ParquetSplit(f.pseudo, split.row_group),
                                  column)
        if rg is not None:
            return rg
        if column in f.lower and column in f.upper:
            lo, hi = f.lower[column], f.upper[column]
            if isinstance(lo, (int, float)) and isinstance(hi, (int, float)):
                return (lo, hi)
        return None

    def generate(self, split: IcebergSplit, columns=None):
        from .parquet import ParquetSplit

        t = self._load(split.table)
        f = t.files[split.file_index]
        return self._pq.generate(ParquetSplit(f.pseudo, split.row_group),
                                 columns)
