"""One generator file per table, found by the table's name: ``generate(rows,
seed)`` takes the configuration's row counts (every table's, for foreign
keys) and ``--seed`` and returns the table's columns: numpy arrays (dates as
int32 days since 1970-01-01), or for a long string column an Arrow array.

The rules are the TPC-H specification's (rev 3, clause 4.2.3): every column
of the table with the spec's type and width, key rules, date arithmetic and
value ranges. What is shared between tables (the orders a line belongs to)
is drawn here from a stream of its own, so that a table made later agrees
with one made now."""

import zlib

import numpy as np

STARTDATE = 8035      # 1992-01-01 in days since 1970-01-01
ENDDATE = 10591       # 1998-12-31
CURRENTDATE = 9298    # 1995-06-17


def stream(seed, *names):
    """The random stream of one column or group of columns: a function of
    the seed and the names alone."""
    return np.random.default_rng(
        [abs(int(seed))] + [zlib.crc32(n.encode()) for n in names])


def order_keys(n_orders):
    """4.2.3 O_ORDERKEY: sparse, the first 8 of every 32 keys are used."""
    i = np.arange(n_orders, dtype=np.int64)
    return (i // 8) * 32 + i % 8 + 1


def order_dates(n_orders, seed):
    """O_ORDERDATE: uniform in STARTDATE .. ENDDATE - 151 days."""
    return stream(seed, "orders", "o_orderdate").integers(
        STARTDATE, ENDDATE - 151 + 1, n_orders).astype(np.int32)


def lines_per_order(n_orders, n_lines, seed):
    """1..7 lines to an order (4.2.3), as a fixed multiset in a seeded
    order: every seed has the same sizes, ``n_lines`` rows exactly."""
    counts = np.arange(n_orders, dtype=np.int64) % 7 + 1
    short = n_lines - int(counts.sum())
    fours = np.flatnonzero(counts == 4)
    if abs(short) > len(fours):
        raise ValueError(f"{n_lines} lines do not fit {n_orders} orders "
                         "of 1..7 lines")
    counts[fours[:abs(short)]] += np.sign(short)
    stream(seed, "orders", "lines").shuffle(counts)
    return counts


def words(rng, lengths, pool_bytes=1 << 20):
    """A text column as an Arrow string array: consecutive slices, of the
    given lengths, of a pseudo-text of random lower-case words."""
    import pyarrow as pa
    letters = rng.integers(97, 123, pool_bytes).astype(np.uint8)
    letters[rng.integers(0, pool_bytes, pool_bytes // 6)] = 32
    offsets = np.zeros(len(lengths) + 1, dtype=np.int32)
    np.cumsum(lengths, out=offsets[1:])
    data = np.resize(letters, int(offsets[-1]))
    return pa.StringArray.from_buffers(
        len(lengths), pa.py_buffer(offsets), pa.py_buffer(data))


def choice(rng, values, n):
    """A column drawn uniformly from a short list of strings, as Arrow."""
    import pyarrow as pa
    codes = rng.integers(0, len(values), n).astype(np.int8)
    return pa.DictionaryArray.from_arrays(
        pa.array(codes), pa.array(values)).cast(pa.string())
