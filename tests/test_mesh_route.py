"""The exchange's pack and unpack (``parallel/mesh._route``'s two helpers)
against a plain numpy reference, and the mechanism pinned in the lowered
text of a stage program: one permutation for every target and k moves for k
arrays on the way in, n contiguous writes an array on the way out."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.parallel import mesh as M

CAP = 64
WIDTH = 8


def _payload(rng, cap):
    """One array of every kind a stage ships: 1-D int64 / float64 / int32,
    string bytes ``uint8[cap, W]`` and a validity. No row is all zeros, so
    a row that strays into the padding shows."""
    return [
        rng.integers(1, 1 << 40, cap).astype(np.int64),
        rng.uniform(1.0, 2.0, cap),
        rng.integers(1, 1 << 20, cap).astype(np.int32),
        rng.integers(1, 255, (cap, WIDTH)).astype(np.uint8),
        np.ones(cap, np.bool_),
    ]


def _case(name, n, cap, rng):
    """(pids, live) of one traffic case."""
    pids = rng.integers(0, n, cap).astype(np.int32)
    live = np.zeros(cap, np.bool_)
    if name == "no_live_rows":
        pass
    elif name == "partly_live":
        live[:cap * 5 // 8] = True
    elif name == "all_live":
        live[:] = True
    elif name == "one_target":
        live[:cap - 3] = True
        pids[:] = n - 1
    elif name == "empty_target":
        live[:cap - 5] = True
        pids = np.where(pids == 1, 0, pids).astype(np.int32)
    else:
        raise AssertionError(name)
    return pids, live


def _ref_bucket(arrays, pids, live, n, cap):
    slots = [np.zeros((n, cap) + a.shape[1:], a.dtype) for a in arrays]
    counts = np.zeros(n, np.int32)
    for t in range(n):
        rows = np.nonzero(live & (pids == t))[0]
        counts[t] = len(rows)
        for slot, a in zip(slots, arrays):
            slot[t, :len(rows)] = a[rows]
    return slots, counts


def _ref_flatten(stacked, counts, out_cap):
    outs = [np.zeros((out_cap,) + a.shape[2:], a.dtype) for a in stacked]
    at = 0
    for s, c in enumerate(counts):
        for out, a in zip(outs, stacked):
            out[at:at + c] = a[s, :c]
        at += int(c)
    return outs, at


def _same(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        g = np.asarray(g)
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)


CASES = ["no_live_rows", "partly_live", "all_live", "one_target",
         "empty_target"]


@pytest.mark.parametrize("n", [2, 4, 8])
@pytest.mark.parametrize("case", CASES)
def test_bucket_packs_each_targets_rows_in_order_and_pads_with_zeros(case, n):
    rng = np.random.default_rng(1000 * n + CASES.index(case))
    arrays = _payload(rng, CAP)
    pids, live = _case(case, n, CAP, rng)
    want, want_counts = _ref_bucket(arrays, pids, live, n, CAP)
    got, got_counts = jax.jit(
        lambda a, p, l: M.bucket_rows_for_exchange(a, p, l, n, CAP))(
            [jnp.asarray(a) for a in arrays], jnp.asarray(pids),
            jnp.asarray(live))
    assert np.asarray(got_counts).dtype == np.int32
    np.testing.assert_array_equal(np.asarray(got_counts), want_counts)
    _same(got, want)
    if case == "empty_target":
        assert want_counts[1] == 0 and want_counts.sum() == CAP - 5
    if case == "one_target":
        assert want_counts[n - 1] == CAP - 3


@pytest.mark.parametrize("n", [2, 4, 8])
@pytest.mark.parametrize("case", CASES + ["every_slot_full"])
def test_flatten_concatenates_the_slots_prefixes_and_zeros_the_rest(case, n):
    """The window is slot 0's live rows, then slot 1's ..., zeros behind
    the total. ``every_slot_full`` is the receive under the worst skew (one
    owner of every key: each of the n senders fills its slot): the last
    write ends exactly at the window's end, and none is clipped."""
    rng = np.random.default_rng(2000 * n + len(case))
    if case == "every_slot_full":
        stacked = [np.stack(col) for col in zip(
            *[_payload(rng, CAP) for _ in range(n)])]
        counts = np.full(n, CAP, np.int32)
    else:
        pids, live = _case(case, n, CAP, rng)
        stacked, counts = _ref_bucket(_payload(rng, CAP), pids, live, n, CAP)
    out_cap = n * CAP
    want, want_total = _ref_flatten(stacked, counts, out_cap)
    got, got_total = jax.jit(
        lambda s, c: M.flatten_received(s, c, out_cap))(
            [jnp.asarray(a) for a in stacked], jnp.asarray(counts))
    assert np.asarray(got_total).dtype == np.int32
    assert int(got_total) == want_total
    _same(got, want)
    if case == "every_slot_full":
        assert want_total == out_cap and np.asarray(got[0]).all()


def test_bucket_sends_no_row_whose_target_names_no_worker():
    """A live row with a target outside [0, n) goes nowhere, as before."""
    rng = np.random.default_rng(7)
    arrays = _payload(rng, CAP)
    pids = rng.integers(-2, 6, CAP).astype(np.int32)
    live = np.ones(CAP, np.bool_)
    want, want_counts = _ref_bucket(arrays, pids, live, 4, CAP)
    got, got_counts = M.bucket_rows_for_exchange(
        [jnp.asarray(a) for a in arrays], jnp.asarray(pids),
        jnp.asarray(live), 4, CAP)
    np.testing.assert_array_equal(np.asarray(got_counts), want_counts)
    _same(got, want)


# ---------------------------------------------------------------------------
# The mechanism, in the lowered text of a stage program
# ---------------------------------------------------------------------------

def _stage_ops(n: int, col_dtypes):
    """How often each op of interest stands in the lowered text of the
    join's exchange stage over ``n`` (virtual) devices."""
    mesh = M.make_mesh(n)
    fn = M.copartition_exchange_fn(mesh, col_dtypes, [0], CAP)
    structs = []
    for t in col_dtypes:
        if t.var_width:
            structs.append(jax.ShapeDtypeStruct((n, CAP, WIDTH), jnp.uint8))
        else:
            structs.append(jax.ShapeDtypeStruct((n, CAP), t.numpy_dtype))
        structs.append(jax.ShapeDtypeStruct((n, CAP), jnp.bool_))
        if t.var_width:
            structs.append(jax.ShapeDtypeStruct((n, CAP), jnp.int32))
    structs.append(jax.ShapeDtypeStruct((n,), jnp.int32))
    text = fn.lower(*structs).as_text()
    # the op itself, not its ``#stablehlo.gather<...>`` attribute
    return {op: len(re.findall(r"(?<!#)stablehlo\.%s\b" % op, text))
            for op in ("sort", "gather", "scatter", "all_to_all")}


def test_stage_program_moves_each_array_once_whatever_the_worker_count():
    """k payload arrays cost k moves and one permutation, not n x k + k:
    no sort, and as many gathers and scatters over 2, 4 and 8 workers."""
    col_dtypes = [dt.INT64, dt.FLOAT64, dt.FLOAT64, dt.DATE, dt.STRING]
    k = sum(3 if t.var_width else 2 for t in col_dtypes)
    ops = {n: _stage_ops(n, col_dtypes) for n in (2, 4, 8)}
    assert ops[2] == ops[4] == ops[8], ops
    at4 = ops[4]
    assert at4["sort"] == 0
    assert at4["all_to_all"] == k + 1          # the arrays and the counts
    moves = at4["gather"] + at4["scatter"]
    assert moves == k + 1, at4                 # k moves + the permutation
