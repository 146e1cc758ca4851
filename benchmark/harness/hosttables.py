"""Host copies of the columns a cell's references read, pulled from the connector.

The connector is the deployment's data: the reference computes its answers from the
same rows with its own code (numpy and pandas), so only ``generate``/``splits``/
``dictionaries`` of the connector are touched here, one ``generate`` per split for all
of a table's wanted columns.  Dictionary-encoded columns stay as their integer codes
in ``columns()`` (decoding 60M strings would take longer than a window) and are decoded
in ``frame()``, which small tables and SF1 use.
"""

import numpy as np


class HostTables:
    def __init__(self, conn, wanted):
        """``wanted``: {table: [column, ...]}, the union over the cell's statements."""
        self.conn = conn
        self.wanted = {t: list(dict.fromkeys(cols)) for t, cols in wanted.items()}
        self._columns = {}
        self._frames = {}

    def columns(self, table):
        """{column: numpy array} of the table's valid rows; dictionary columns as codes."""
        if table not in self._columns:
            names = self.wanted[table]
            parts = {name: [] for name in names}
            for split in self.conn.splits(table):
                page = self.conn.generate(split, list(names))
                valid = np.asarray(page.valid_mask())
                for name in names:
                    parts[name].append(np.asarray(page.column(name))[valid])
            self._columns[table] = {name: np.concatenate(parts[name]) for name in names}
        return self._columns[table]

    def decode(self, table, column, codes):
        d = self.conn.dictionaries(table).get(column)
        return codes if d is None else d.decode(np.asarray(codes))

    def frame(self, table):
        """pandas frame of the table with dictionary columns decoded to strings."""
        import pandas as pd

        if table not in self._frames:
            cols = self.columns(table)
            self._frames[table] = pd.DataFrame(
                {name: self.decode(table, name, arr) for name, arr in cols.items()})
        return self._frames[table]

    def __getitem__(self, table):
        return self.frame(table)
